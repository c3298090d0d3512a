"""Paper Figure 10: memory volume saved by array contraction.

Two measurements per kernel:
  * analytic: auxiliary elements materialized with contraction off/on
    (depgraph windows; the paper's RACE-NC-NR vs RACE-NR comparison);
  * compiled: XLA's 'bytes accessed' for the jitted evaluator with
    contraction off/on (captures what fusion actually materializes).
"""
from __future__ import annotations

import numpy as np

import jax

from repro.apps.paper_kernels import get_case
from repro.core.race import race

from .common import build_env, csv_line

KERNELS = {"calc_tpoints": 512, "gaussian": 500, "psinv": 48, "resid": 48,
           "diffusion1": 48, "derivative": 32}


def bytes_accessed(fn, env):
    comp = jax.jit(fn).lower(env).compile()
    return float(comp.cost_analysis().get("bytes accessed", 0.0))


def run(print_fn=print):
    rows = []
    for name, n in KERNELS.items():
        case = get_case(name, n)
        env = build_env(case)
        nc = race(case.program, reassociate=0, contraction=False)
        c = race(case.program, reassociate=0, contraction=True)
        elems_nc = nc.materialized_elements(contracted=False)
        elems_c = c.materialized_elements(contracted=True)
        b_nc = bytes_accessed(nc.evaluator(), env)
        b_c = bytes_accessed(c.evaluator(), env)
        b_base = bytes_accessed(c.baseline_evaluator(), env)
        derived = (
            f"aux_elems_nc={elems_nc};aux_elems_contracted={elems_c}"
            f";xla_bytes_base={b_base:.0f};xla_bytes_nc={b_nc:.0f};xla_bytes_c={b_c:.0f}"
        )
        print_fn(csv_line(f"memory.{name}", 0.0, derived))
        rows.append(dict(name=name, elems_nc=elems_nc, elems_c=elems_c,
                         b_base=b_base, b_nc=b_nc, b_c=b_c))
    return rows


if __name__ == "__main__":
    run()
