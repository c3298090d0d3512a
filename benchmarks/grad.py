"""Differentiable-RACE benchmark: what the adjoint-stencil VJP costs.

For each case the sweep times, through the compiled-executor serving path,

  * ``fwd_us``       one forward ``res.run`` call (the custom_vjp primal);
  * ``fwd_bwd_us``   one ``jax.grad`` step — forward + every adjoint-spec
    executor — after warmup (steady state, all plans cached);
  * ``adjoint_plans``  how many adjoint stencil programs back the VJP
    (one per differentiable input, or 0 when the detector refuses and the
    VJP falls back to autodiff);
  * ``adjoint_reduced_ops``  the elimination fraction of the array-input
    adjoint plan — the proof that the backward pass itself went through
    RACE, not just transposition;
  * ``reuse_hit_rate``  executor-cache hit rate across ``GRAD_STEPS``
    repeated grad steps measured from a cold cache: after the first step
    compiles forward + adjoint executors, every later step must be pure
    hits (the plan-reuse contract for training loops).

Interpret-mode timings on the CPU backend are correctness-plus-plumbing
signal; absolute µs needs a TPU, where Pallas runs compiled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps.paper_kernels import get_case
from repro.core.adjoint import adjoint_build
from repro.core.executor import executor_cache
from repro.core.race import race

from .common import build_env, csv_line, time_callable

#: (case, grid size): the acceptance trio + one adjoint-autodiff fallback
CASES = [("psinv", 10), ("resid", 10), ("diffusion3", 10), ("rprj3", 12)]

GRAD_STEPS = 4


def _grad_fn(res, env, diff_keys):
    def loss(p):
        outs = res.run({**env, **p}, "xla")
        return sum(jnp.sum(jnp.asarray(v)) for v in outs.values())

    grad = jax.grad(loss)
    return lambda e: grad({k: e[k] for k in diff_keys})


def run(print_fn=print, quick: bool = False, repeats: int = None):
    """Returns one row per case; CSV is printed en route."""
    repeats = repeats or (3 if quick else 7)
    rows = []
    for name, n in CASES[:2] if quick else CASES:
        case = get_case(name, n)
        env = build_env(case)
        diff_keys = sorted(k for k, v in env.items()
                           if np.issubdtype(np.asarray(v).dtype,
                                            np.floating))
        res = race(case.program, reassociate=case.reassociate,
                   rewrite_div=case.rewrite_div)
        build = adjoint_build(case.program)
        adj_reduced = 0.0
        if build.ok:
            arr_specs = [s for s in build.specs
                         if np.asarray(env[s.input]).ndim]
            if arr_specs:
                adj_reduced = max(s.result().reduced_ops()
                                  for s in arr_specs)

        cache = executor_cache()
        cache.clear()
        grad_fn = _grad_fn(res, env, diff_keys)
        for _ in range(GRAD_STEPS):  # cold 1st step compiles fwd + adjoints
            jax.block_until_ready(grad_fn(env))
        info = cache.cache_info()
        hit_rate = info["hits"] / max(1, info["hits"] + info["misses"])

        fwd_s = time_callable(lambda e: res.run(e, "xla"), env,
                              repeats=repeats, warmup=1)
        bwd_s = time_callable(grad_fn, env, repeats=repeats, warmup=1)

        row = dict(
            case=case.name, fwd_us=fwd_s * 1e6, fwd_bwd_us=bwd_s * 1e6,
            bwd_over_fwd=bwd_s / fwd_s,
            adjoint_supported=build.ok,
            adjoint_reason=build.reason,
            adjoint_plans=len(build.specs) if build.ok else 0,
            adjoint_reduced_ops=adj_reduced,
            reuse_hit_rate=hit_rate,
            cached_executors=info["currsize"],
            grad_steps=GRAD_STEPS,
        )
        if build.ok and hit_rate <= 0.0:  # the plan-reuse contract
            raise AssertionError(
                f"{case.name}: no executor-cache reuse across "
                f"{GRAD_STEPS} grad steps ({info})")
        rows.append(row)
        mode = (f"adjoint={row['adjoint_plans']}"
                if build.ok else "adjoint=autodiff")
        print_fn(csv_line(
            f"grad.{case.name}", row["fwd_bwd_us"],
            f"fwd={row['fwd_us']:.0f}us {mode} "
            f"reduced_ops={adj_reduced:.2f} "
            f"reuse_hit_rate={hit_rate:.2f}"))
    return rows


if __name__ == "__main__":
    from .common import section_main

    section_main("grad", run)
