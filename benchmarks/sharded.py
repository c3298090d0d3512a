"""Sharded-execution benchmark: scaling rows over host-device submeshes.

For a 3-D registry case the sweep runs the same compiled plan single-device
and then under ``run_sharded`` on 1/2/4/8-shard submeshes carved from the
same forced-host-device process (``make_stencil_mesh`` subsets), for both
halo strategies, reporting

  * ``us_per_call``   median steady-state wall time per call;
  * ``scaling_vs_1``  throughput ratio against this strategy's own 1-shard
    row (>= 1 means sharding pays);
  * ``halo_bytes`` / ``restack_bytes``  the static transport accounting the
    ``auto`` heuristic trades off (ppermute payload vs replicated copies);
  * ``partition`` / ``strategy`` / ``retraces``  what actually ran.

Honesty note: host "devices" here are XLA's forced CPU partitions of ONE
physical machine — on a 1-core CI container every shard timeshares the same
core, so wall-clock speedup from sharding is *physically unattainable*; the
expected ``scaling_vs_1`` is <= 1 (sharding overhead only).  The rows pin
the overhead trajectory and the transport accounting; real >= 2x scaling
needs >= 2 physical cores (or accelerator devices), which is why each row
records ``host_cpu_count`` — compare like with like across artifacts.
"""
from __future__ import annotations

import os

# process-global XLA flag: must be set before jax initializes any backend.
# An explicit caller setting (CI pins 8) always wins.
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import numpy as np

import jax

from repro.apps.paper_kernels import get_case
from repro.core.race import race
from repro.launch.mesh import make_stencil_mesh
from repro.shard import compile_sharded

from .common import build_env, csv_line, section_main, time_callable

#: 3-D registry rows sized so every submesh axis divides the extents
#: (E = n - 2 must be divisible by 4 and 2 for the (4, 2) 8-shard mesh)
CASES = [("j3d27pt", 18), ("poisson", 18)]
CASES_QUICK = [("j3d27pt", 10)]

SHARD_COUNTS = (1, 2, 4, 8)


def run(print_fn=print, quick: bool = False, repeats: int = None):
    """Returns one row per (case, shards, strategy) plus a single-device
    baseline row per case; CSV is printed en route."""
    repeats = repeats or (5 if quick else 20)
    n_dev = jax.device_count()
    host_cores = os.cpu_count()
    rows = []
    for name, n in (CASES_QUICK if quick else CASES):
        case = get_case(name, n)
        env = build_env(case)
        res = race(case.program, reassociate=case.reassociate,
                   rewrite_div=case.rewrite_div, backend="xla")
        t_single = time_callable(lambda e: res.run(e, "xla"), env,
                                 repeats=repeats)
        rows.append(dict(case=name, n=n, shards=0, strategy="single-device",
                         us_per_call=t_single * 1e6, scaling_vs_1=None,
                         host_cpu_count=host_cores, devices=n_dev))
        print_fn(csv_line(f"sharded.{name}.single", t_single * 1e6,
                          f"n={n}"))
        t_one = {}
        for strategy in ("exchange", "recompute"):
            for k in SHARD_COUNTS:
                if k > n_dev:
                    print_fn(csv_line(
                        f"sharded.{name}.{strategy}.k{k}", 0.0,
                        f"SKIPPED:only_{n_dev}_devices"))
                    continue
                mesh = make_stencil_mesh(k, ("sx", "sy"))
                ex = compile_sharded(res, env, mesh, halo=strategy,
                                     backend="xla")
                t = time_callable(ex, env, repeats=repeats)
                t_one.setdefault(strategy, t)
                scaling = t_one[strategy] / t
                hp = ex.halo_prog
                row = dict(
                    case=name, n=n, shards=k, strategy=hp.strategy,
                    partition=str(ex.partition.key()),
                    us_per_call=t * 1e6, scaling_vs_1=scaling,
                    single_over_sharded=t_single / t,
                    halo_bytes=hp.halo_bytes,
                    restack_bytes=hp.restack_bytes,
                    retraces=ex.trace_count,
                    host_cpu_count=host_cores, devices=n_dev)
                rows.append(row)
                print_fn(csv_line(
                    f"sharded.{name}.{strategy}.k{k}", t * 1e6,
                    f"scaling_vs_1={scaling:.2f};halo_B={hp.halo_bytes};"
                    f"restack_B={hp.restack_bytes};cores={host_cores}"))
    return rows


if __name__ == "__main__":
    section_main("sharded", run)
