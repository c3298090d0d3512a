"""Unified backend layer: full-registry differential verification plus the
capability-probe contract.

Every case in ``repro.apps.paper_kernels`` runs baseline vs RACE-XLA vs
RACE-Pallas (where the probe passes) and must agree within per-dtype
tolerances; ineligible plans must carry structured fallback reasons rather
than raise or silently degrade.
"""
import numpy as np
import pytest

from repro.apps.paper_kernels import CASES, Case, get_case
from repro.core.backend import (R_MIXED_STRIDE, R_PLATFORM, R_TPU_GATHER,
                                R_TPU_STRIDED, BackendUnavailable,
                                probe_pallas, select_backend)
from repro.core.executor import compile_plan
from repro.core.ir import arr, loopnest, program
from repro.core.race import race
from repro.lowering import ORDERS
from repro.testing import (build_env, coverage_matrix, reference, run_case,
                           sweep_registry)
from repro.testing.differential import SWEEP_SIZES

pytestmark = pytest.mark.pallas


# ---------------------------------------------------------------------------
# registry-wide differential sweep (tier-1: binary + the case's paper level)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(CASES))
def test_registry_differential(name):
    case = get_case(name, SWEEP_SIZES.get(name))
    levels = sorted({0, case.reassociate})
    report = run_case(case, reassociate_levels=levels)
    assert not report.failures(), coverage_matrix([report])
    # the whole registry now lowers to Pallas — a regression back to the
    # XLA fallback (even a "reasoned" one) would silently void the claim
    assert report.pallas_covered(), coverage_matrix([report])


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("name", sorted(CASES) + ["hdifft_gm-batched"])
def test_registry_differential_orders(name, order):
    """Every registry case in both iteration orders of the Pallas kernel:
    as laid (one chip's executors) and level-major (a sharded executor's
    local kernel).  The corners: flips (mirror_deriv), a 4-D nest
    (blocked4d), the 1-D lift (smooth1d), rank-1 operands (rhs_ph1), a
    gather operand in its whole-array block (diag2d), and hdifft_gm through
    the vmapped batch."""
    base, _, batched = name.partition("-")
    case = get_case(base, SWEEP_SIZES.get(base))
    levels = sorted({0, case.reassociate})
    if not batched:
        report = run_case(case, reassociate_levels=levels, order=order)
        assert not report.failures(), coverage_matrix([report])
        assert report.pallas_covered(), coverage_matrix([report])
        return
    envs = [build_env(case, np.float32, seed=s) for s in range(3)]
    for lvl in levels:
        res = race(case.program, reassociate=lvl)
        ex = compile_plan(res.plan, envs[0], "pallas", order=order)
        assert ex.spec.order == order
        got = ex.run_batch(envs)
        for b, env in enumerate(envs):
            for k, want in reference(res.plan, env).items():
                np.testing.assert_allclose(np.asarray(got[k][b]),
                                           np.asarray(want), rtol=1e-5,
                                           atol=1e-6, err_msg=f"{k}[{b}]")


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_registry_differential_full(dtype):
    """All reassociation levels {0, 3, 4} x both backends x all cases."""
    reports = sweep_registry(dtype=dtype)
    fails = [f for r in reports for f in r.failures()]
    assert not fails, coverage_matrix(reports)
    assert all(r.pallas_covered() for r in reports), coverage_matrix(reports)


def test_strided_rprj3_takes_pallas_path():
    """Acceptance: the stride-2 restriction kernel must not fall back."""
    case = get_case("rprj3", 12)
    res = race(case.program, reassociate=case.reassociate, backend="pallas")
    sel = res.select_backend()
    assert sel.backend == "pallas" and not sel.fell_back
    env = build_env(case, np.float32)
    got = res.run(env)
    want = reference(res.plan, env)  # baseline evaluator, interior
    for k in want:
        g = np.asarray(got[k], np.float64)
        w = np.asarray(want[k], np.float64)
        rel = np.abs(g - w).max() / np.abs(w).max()
        assert rel <= 1e-5, f"{k}: rel err {rel:.3e}"


def test_strided_2d_synthetic():
    """Mixed per-level strides in a 2-D nest (a=2 and a=3), Pallas vs XLA."""
    loops, (i, j) = loopnest(("i", 1, 9), ("j", 1, 7))
    v, out = arr("v"), arr("st2")
    body = (v[2 * i + 1, 3 * j] + v[2 * i - 1, 3 * j]) + v[2 * i + 1, 3 * j - 2]
    prog = program(loops, [(out[i, j], body)])
    case = Case("strided2d", "synthetic", prog, reassociate=3)
    report = run_case(case, reassociate_levels=(0, 3))
    assert not report.failures(), coverage_matrix([report])
    assert report.pallas_covered()


# ---------------------------------------------------------------------------
# capability probe: structured fallback reasons, never an exception
# ---------------------------------------------------------------------------
#
# Negative-coefficient and repeated-level programs used to live here as
# fallback fixtures; the dimension-generic lowering engine retired those
# codes (they run on Pallas now — pinned in test_lowering.py and by the
# mirror_deriv/diag2d registry rows above).  A genuinely out-of-model case —
# one array read with *different* per-level coefficients, which no single
# flip or window normalization can reconcile — keeps the fallback machinery
# itself covered.


def _mixed_stride_case():
    loops, (i, j) = loopnest(("i", 1, 6), ("j", 1, 6))
    u, out = arr("u"), arr("mix_out")
    prog = program(loops, [(out[i, j], u[2 * i, j] + u[i, j])])
    return Case("mixstride", "synthetic", prog, reassociate=0)


def test_probe_reports_structured_fallback():
    case = _mixed_stride_case()
    res = race(case.program)
    cap = probe_pallas(res.plan)  # must not raise
    assert not cap.eligible
    assert R_MIXED_STRIDE in {r.code for r in cap.reasons}
    assert all(r.detail for r in cap.reasons)

    # auto selection falls back to XLA, carrying the reasons
    sel = res.select_backend("auto")
    assert sel.backend == "xla" and sel.fell_back
    assert R_MIXED_STRIDE in {r.code for r in sel.capability.reasons}

    # the XLA gather path still executes the program correctly
    env = build_env(case, np.float32)
    got = res.run(env, "auto")
    want = reference(res.plan, env)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-6)

    # an explicit pallas demand raises the structured error
    with pytest.raises(BackendUnavailable) as exc:
        select_backend(res.plan, "pallas")
    assert R_MIXED_STRIDE in {r.code for r in exc.value.capability.reasons}


def test_differential_harness_flags_ineligible_as_explicit_fallback():
    report = run_case(_mixed_stride_case(), reassociate_levels=(0,))
    assert not report.failures()  # fallback with a reason is not a failure
    pallas = [c for c in report.combos if c.backend == "pallas"]
    assert pallas and all(c.explicit_fallback for c in pallas)
    assert R_MIXED_STRIDE in pallas[0].reason


def test_unknown_backend_rejected():
    case = get_case("hdifft_gm", 10)
    with pytest.raises(ValueError, match="unknown backend"):
        race(case.program, backend="tpu")
    res = race(case.program)
    with pytest.raises(ValueError, match="unknown backend"):
        res.select_backend("cuda")


# ---------------------------------------------------------------------------
# the platform decides the Pallas mode, and what the probe must refuse
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,code", [("rprj3", R_TPU_STRIDED),
                                       ("diag2d", R_TPU_GATHER)])
def test_tpu_refusal_falls_back_loudly(name, code, monkeypatch):
    """On a TPU, ``auto`` takes XLA with a ``backend_fallback`` event naming
    the pinned code, and an explicit ``pallas`` raises it; it never
    interprets.  On the CPU backend the same plan runs interpreted."""
    import repro.core.backend as backend
    from repro import obs
    from repro.core.executor import ExecutorCache, compile_plan

    case = get_case(name, SWEEP_SIZES[name])
    res = race(case.program, reassociate=case.reassociate)
    env = build_env(case)
    ex = compile_plan(res.plan, env, "pallas", cache=ExecutorCache())
    assert ex.backend == "pallas" and ex.spec.interpret is True

    monkeypatch.setattr(backend, "target_platform", lambda: "tpu")
    monkeypatch.setenv(obs.ENV_OBS, "1")
    obs.reset()
    assert select_backend(res.plan, "auto").backend == "xla"
    ev = obs.events(kind="backend_fallback")[-1]
    assert code in ev["codes"] and ev["backend"] == "xla"
    with pytest.raises(BackendUnavailable, match=code):
        select_backend(res.plan, "pallas")
    assert compile_plan(res.plan, env, "auto",
                        cache=ExecutorCache()).backend == "xla"


def test_pallas_mode_follows_the_platform(monkeypatch):
    import repro.core.backend as backend
    import repro.lowering.emit as emit
    from repro.lowering import pallas_interpret

    assert pallas_interpret() is True  # the CPU backend interprets
    monkeypatch.setattr(emit, "target_platform", lambda: "tpu")
    assert pallas_interpret() is False  # a TPU compiles
    monkeypatch.setattr(emit, "target_platform", lambda: "gpu")
    with pytest.raises(ValueError, match=R_PLATFORM):
        pallas_interpret()  # no mode here: never a silent interpreter
    monkeypatch.setattr(backend, "target_platform", lambda: "gpu")
    res = race(get_case("psinv", 10).program)
    cap = probe_pallas(res.plan)
    assert not cap.eligible and cap.reasons[0].code == R_PLATFORM
    assert select_backend(res.plan, "auto").backend == "xla"
