"""Shared benchmark utilities."""
from __future__ import annotations

import time

import numpy as np

import jax

from repro.apps.paper_kernels import get_case
from repro.core.race import race
# single source for test/benchmark input generation (same conditioning)
from repro.testing.differential import build_env  # noqa: F401


def variants(case, auto_level: bool = True):
    """(tag, RaceResult) for Base-equivalent NR / ESR+ / full RACE.

    ``auto_level`` picks the reassociation level {3,4} (and NR) with the best
    static profit — a beyond-paper knob (the paper selects levels manually
    per case); the paper-faithful level stays available as case.reassociate.
    """
    out = {"RACE-NR": race(case.program)}
    out["ESR+"] = race(case.program, reassociate=3, esr=True)
    full = race(case.program, reassociate=case.reassociate,
                rewrite_div=case.rewrite_div)
    if auto_level:
        cands = [full] + [
            race(case.program, reassociate=lvl, rewrite_div=case.rewrite_div)
            for lvl in (3, 4)
            if lvl != case.reassociate
        ]
        cands.append(out["RACE-NR"])
        full = min(cands, key=lambda r: r.op_table()["weighted_total"])
    out["RACE"] = full
    return out


def time_callable(fn, env, repeats: int = 5, warmup: int = 2):
    """Median wall time of an already-compiled callable (e.g. a
    ``CompiledRace`` executor), seconds."""
    res = None
    for _ in range(warmup):
        res = fn(env)
    jax.block_until_ready(res)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(env))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def time_fn(fn, env, repeats: int = 5, warmup: int = 2):
    """Median wall time of a jitted evaluator, seconds."""
    return time_callable(jax.jit(fn), env, repeats=repeats, warmup=warmup)


def csv_line(name: str, us: float, derived: str) -> str:
    return f"{name},{us:.2f},{derived}"


def bench_stamp() -> dict:
    """Provenance stamp for machine-readable benchmark output.

    One source of truth shared by ``BENCH_*.json`` (run.py / serving.py /
    tuning.py / grad.py), ``launch/serve.py --json`` and the observability
    dumps: schema version, UTC timestamp, device/backend string, jax
    version — so perf-trajectory artifacts from different commits and
    machines are comparable without guessing.
    """
    from repro.obs import run_stamp

    return run_stamp()


def record_history(section: str, rows, stamp: dict) -> None:
    """Append one section's rows to the cross-run benchmark history
    (``repro.obs.history``) — a no-op unless ``$RACE_BENCH_HISTORY`` names
    the trajectory file.  The regression sentinel (``repro.obs.check``)
    gates later runs against what lands here."""
    from repro.obs.history import append_rows, history_file

    n = append_rows(section, rows, stamp)
    if n:
        print(csv_line(f"history.{section}", 0.0,
                       f"appended={n};path={history_file()}"))


def section_main(section: str, run_fn, argv=None) -> None:
    """Shared ``python -m benchmarks.<section>`` entry point.

    ``--quick`` shrinks the sweep, ``--json [PATH]`` writes the stamped structured rows (default
    ``BENCH_<section>.json``).  With ``RACE_OBS=1`` the accumulated metrics
    + event snapshot lands in ``OBS_metrics.json``; with
    ``RACE_BENCH_HISTORY`` set the rows also append to the cross-run
    benchmark history.
    """
    import argparse
    import json

    ap = argparse.ArgumentParser(description=f"{section} benchmark")
    ap.add_argument("--quick", action="store_true", help="smaller sweep")
    ap.add_argument("--json", nargs="?", const=f"BENCH_{section}.json",
                    default=None, metavar="PATH",
                    help="write stamped structured rows")
    args = ap.parse_args(argv)

    print("name,us_per_call,derived")
    stamp = bench_stamp()
    rows = run_fn(quick=args.quick)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(dict(stamp=stamp, section=section,
                           rows=rows), f, indent=1, default=str)
        print(csv_line(f"json.{section}", 0.0, f"wrote={args.json}"))
    record_history(section, rows, stamp)
    from repro import obs

    if obs.enabled():
        obs.dump("OBS_metrics.json")
        print(csv_line("obs", 0.0, "wrote=OBS_metrics.json"))
