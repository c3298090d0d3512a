"""POP's Gent-McWilliams tracer box sums (``hdifft_gm``) through RACE, one
horizontal level per call.

For the ``sweep`` driver a sweep is one ``run_batch`` over every level of
the grid, on stacked device arrays.  For the ``open_loop`` driver a
request is one level's T and S as host numpy, sent through the serve
runtime.  Data comes from the seed, made on the device: T and S uniform in
[-1, 1] on every level.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from bench.configs import pop_hdifft_ref as ref
from bench.yardstick import compulsory_bytes, rel_gap, seed_key


def footprints(nx: int, ny: int, levels: int) -> dict:
    """What one sweep over ``levels`` levels reads and writes: T and S over
    ``i = 1 .. nx-1`` and every ``j``, dn and dso over the interior."""
    read, write = (levels, nx - 1, ny), (levels, nx - 2, ny - 2)
    return {"hdifft_gm": {"read": {"T": read, "S": read},
                          "write": {"dn": write, "dso": write}}}


def prepare(cfg: dict, mix: dict, seed: int, devices: list, clock):
    import jax

    from repro.apps.paper_kernels import CASES
    from repro.core.executor import compile_plan, env_signature
    from repro.core.race import race

    nx, ny, levels = int(cfg["nx"]), int(cfg["ny"]), int(cfg["levels"])
    dtype = np.dtype(cfg["dtype"])
    with clock.span("race_s"):
        case = CASES[cfg["kernels"][0]][0](nx, ny)
        res = race(case.program, reassociate=case.reassociate,
                   rewrite_div=case.rewrite_div)

    @jax.jit
    def make(key):
        kt, ks = jax.random.split(key)
        shape = (levels, nx, ny)
        return (jax.random.uniform(kt, shape, dtype, -1.0, 1.0),
                jax.random.uniform(ks, shape, dtype, -1.0, 1.0))

    with clock.span("data_s"):
        T, S = jax.block_until_ready(make(seed_key(seed)))
    live = dict(T=T, S=S)

    def host_inputs():
        return np.asarray(live["T"]), np.asarray(live["S"])

    def compare_outputs(outs: list) -> dict:
        """``outs``: ``[(levels, {"dn": ..., "dso": ...}), ...]``."""
        t, s = inputs
        gap = 0.0
        for lv, got in outs:
            want = ref.hdifft(t[lv], s[lv])
            gap = max(gap, *(rel_gap(got[k], want[k]) for k in want))
        return {"out_rel_gap": gap}

    def control_outputs(outs: list) -> dict:
        import ml_dtypes

        t, s = inputs
        gap = 0.0
        for lv, _ in outs:
            want = ref.hdifft(t[lv], s[lv])
            low = ref.hdifft(t[lv], s[lv], ml_dtypes.bfloat16)
            gap = max(gap, *(rel_gap(low[k], want[k]) for k in want))
        return {"out_rel_gap": gap}

    notes = {}
    if mix["driver"] == "sweep":
        with clock.span("warmup_s"):
            one = {"T": T[0], "S": S[0]}
            ex = compile_plan(res.plan, env_signature(one), "auto")
            env = dict(live)

            def sweep(_):
                return ex.run_batch(env)

            jax.block_until_ready(sweep(None))
        notes["backend"] = ex.backend
        everything = np.arange(levels)
        inputs = ()

        def fetch(pairs):
            nonlocal inputs
            inputs = host_inputs()
            return [(everything, {k: np.asarray(v) for k, v in out.items()})
                    for _, out in pairs]

        def release():
            live.clear()
            env.clear()

        return SimpleNamespace(
            state0=None, sweep=sweep, executors=[ex],
            sweep_bytes=compulsory_bytes(footprints(nx, ny, levels),
                                         dtype.itemsize),
            fetch=fetch, compare=compare_outputs, control=control_outputs,
            release=release, notes=notes)

    # open loop: one level per request, as host numpy
    with clock.span("data_s"):
        inputs = host_inputs()
    t_host, s_host = inputs
    envs = [{"T": t_host[lv], "S": s_host[lv]} for lv in range(levels)]
    live.clear()

    def executors():
        ex = compile_plan(res.plan, env_signature(envs[0]), "auto")
        notes["backend"] = ex.backend
        return [ex]

    def compare_requests(samples: list) -> dict:
        return compare_outputs([([lv], {k: v[None] for k, v in out.items()})
                                for lv, out in samples])

    def control_requests(samples: list) -> dict:
        return control_outputs([([lv], out) for lv, out in samples])

    return SimpleNamespace(
        target=res, request_envs=envs, executors=executors,
        compare_requests=compare_requests, control_requests=control_requests,
        release=lambda: None, notes=notes)
