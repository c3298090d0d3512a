"""RACE + the Pallas VMEM-contracted stencil kernel, end to end.

    PYTHONPATH=src python examples/optimize_stencil.py

Takes the 27-point Jacobi stencil (paper Table 1 'j3d27pt'), runs RACE, then
executes the optimized plan three ways — XLA baseline, XLA RACE evaluator,
and the blocked Pallas kernel (interpreted on CPU, compiled on a TPU) —
validating they agree and reporting op counts and wall-clock.

Two entry paths are demonstrated:
  * the internal DSL (``repro.core.ir`` builders, as in ``paper_kernels``);
  * the capture frontend: the same stencil written as a plain-Python loop
    nest, decorated with ``@race_kernel``, captured to the identical IR and
    executed through the same backend layer.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import time

import numpy as np

import jax

from repro.apps import frontend_kernels
from repro.apps.paper_kernels import stencil_j3d27pt
from repro.core.codegen import required_shapes
from repro.core.race import race
from repro.frontend import race_kernel
from repro.kernels import ref as kref
from repro.kernels.ops import race_stencil


def main():
    case = stencil_j3d27pt(48)
    res = race(case.program, reassociate=3)
    tb, tr = res.op_table(base=True), res.op_table()
    print(f"j3d27pt 48^3: aux={res.n_aux()} rounds={res.rounds()}")
    print(f"  ops/iter: base add={tb['add']:.0f} mul={tb['mul']:.0f} -> "
          f"RACE add={tr['add']:.0f} mul={tr['mul']:.0f} "
          f"(reduced {res.reduced_ops():.2f})")

    rng = np.random.default_rng(0)
    env = {}
    for nm, shp in required_shapes(case.program).items():
        env[nm] = (np.float32(rng.uniform(0.2, 1.0)) if nm in case.scalars
                   else rng.uniform(-1, 1, shp).astype(np.float32))

    base_fn = jax.jit(res.baseline_evaluator())
    opt_fn = jax.jit(res.evaluator())

    def bench(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(3):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 3

    t_base, t_opt = bench(base_fn, env), bench(opt_fn, env)
    t0 = time.perf_counter()
    pallas_out = race_stencil(res, env, block_rows=8)
    t_pal = time.perf_counter() - t0

    want = kref.reference(res.plan, env)
    for k in want:
        np.testing.assert_allclose(np.asarray(pallas_out[k]),
                                   np.asarray(want[k]), rtol=2e-4, atol=2e-4)
    print(f"  XLA baseline {t_base*1e3:.1f} ms | XLA RACE {t_opt*1e3:.1f} ms "
          f"({t_base/t_opt:.2f}x)")
    print(f"  Pallas ({jax.default_backend()}, correctness-validated) ran "
          f"in {t_pal*1e3:.0f} ms")
    print("  kernel == oracle: OK")

    # -- the same stencil through the capture frontend ----------------------
    # j3d27pt written as an ordinary Python loop nest (see
    # repro/apps/frontend_kernels.py) — @race_kernel captures the AST into
    # the identical Program, so the plan, op counts, and backends all match.
    kern = race_kernel(reassociate=3)(frontend_kernels.j3d27pt)
    t0 = time.perf_counter()
    fe_out = kern.run(env, backend="xla")  # backend="auto"/"pallas" work too
    t_fe = time.perf_counter() - t0
    fe_res = kern.trace({nm: np.shape(v) for nm, v in env.items()})
    assert fe_res.program == case.program, "frontend/DSL divergence"
    want_fe = kref.reference_plan(fe_res.plan, env)  # interior convention
    # kern.run is the jitted executor path; XLA fusion reorders f32 rounding
    # relative to the eager oracle, so compare at same-plan f32 tolerance
    np.testing.assert_allclose(np.asarray(fe_out["j27"]),
                               np.asarray(want_fe["j27"]),
                               rtol=1e-5, atol=1e-5)
    print(f"  @race_kernel frontend: captured identical program, "
          f"ran in {t_fe*1e3:.1f} ms (capture "
          f"{kern.last_capture_seconds*1e3:.1f} ms) — frontend == DSL: OK")


if __name__ == "__main__":
    main()
