"""The plain references agree with the program's plans at a tiny size, and
the control (the reference in bfloat16) reads above the committed limits
while the program reads below them."""
import numpy as np
import pytest

from bench.configs import npb_mg_ref, pop_hdifft_ref
from bench.tests.conftest import ROOT
from bench.yardstick import rel_gap

A = [-8 / 3, 0.0, 1 / 6, 1 / 12]
C = [-3 / 17, 1 / 33, -1 / 61, 0.0]


@pytest.mark.parametrize("backend", ["xla", "auto"])
def test_npb_mg_step_matches_the_plans(backend):
    from repro.apps.paper_kernels import get_case
    from repro.core.executor import compile_plan
    from repro.core.race import race

    n = 8
    rng = np.random.default_rng(0)
    u_int = rng.uniform(-1, 1, (n, n, n)).astype(np.float32)
    v_int = rng.uniform(-1, 1, (n, n, n)).astype(np.float32)
    wrap = lambda x: np.pad(x, 1, mode="wrap")  # noqa: E731
    a = {f"a{i}": np.float32(x) for i, x in enumerate(A)}
    c = {f"w{i}": np.float32(x) for i, x in enumerate(C)}
    res = {k: race(get_case(k, n + 2).program, reassociate=4)
           for k in ("resid", "psinv")}
    env_r = dict(V=wrap(v_int), U=wrap(u_int), **a)
    r = np.asarray(compile_plan(res["resid"].plan, env_r, backend)
                   .run(env_r)["Rr"])
    env_p = dict(U=wrap(u_int), R=wrap(r), **c)
    u = np.asarray(compile_plan(res["psinv"].plan, env_p, backend)
                   .run(env_p)["U"])
    slab = np.take(u_int, np.arange(-2, n + 2), axis=0, mode="wrap")
    v_slab = np.take(v_int, np.arange(-1, n + 1), axis=0, mode="wrap")
    want_r, want_u = npb_mg_ref.step(slab, v_slab, A, C)
    assert rel_gap(r, want_r[1:-1]) < 1e-6
    assert rel_gap(u, want_u) < 1e-6


@pytest.mark.parametrize("backend", ["xla", "auto"])
def test_pop_hdifft_matches_the_plan(backend):
    from repro.apps.paper_kernels import CASES
    from repro.core.executor import compile_plan
    from repro.core.race import race

    case = CASES["hdifft_gm"][0](14, 12)
    res = race(case.program, reassociate=case.reassociate)
    rng = np.random.default_rng(1)
    t, s = rng.uniform(-1, 1, (2, 3, 14, 12)).astype(np.float32)
    ex = compile_plan(res.plan, {"T": t[0], "S": s[0]}, backend)
    got = ex.run_batch({"T": t, "S": s})
    want = pop_hdifft_ref.hdifft(t, s)
    for k in ("dn", "dso"):
        assert rel_gap(got[k], want[k]) < 1e-6


@pytest.mark.parametrize("workload", [
    "npb_mg_b.smooth", "pop_gx1v6.levels", "pop_gx1v6.serve"])
def test_control_fails_and_program_passes(spec, tiny, workload):
    """At a tiny size on the CPU: the numbers the program reads are under
    the committed limits, the control's are over them."""
    import time

    from bench import harness

    rep = harness.run(ROOT, workload, 3, 0.3, False, time.perf_counter(),
                      require_tpu=False, spec=spec, cell=tiny(workload),
                      control=True)
    assert rep["result"]["correct"]
    for name, c in rep["checks"].items():
        assert c["value"] < c["limit"] < rep["control"][name]
