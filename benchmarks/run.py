"""Benchmark entrypoint: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only table1,...]

Prints ``name,us_per_call,derived`` CSV per the harness convention.
Sections: table1 (Table 1), speedup (Figs 7-8), scaling (Fig 9),
memory (Fig 10), serving (PR-3 executor cache: cold vs steady-state µs/call,
hit rate, batched throughput), tuning (ISSUE-4 autotuner: static default vs
correctness-gated measured winner, search time, store round-trip),
grad (ISSUE-6 differentiable RACE: fwd vs fwd+bwd µs/step, adjoint-plan
count and elimination fraction, executor-cache reuse across grad steps),
roofline (EXPERIMENTS.md section Roofline;
reads the dry-run JSON and is skipped with a note if the dry-run has not
been run).  Fig 11 (OpenMP thread scaling) has no analogue on this 1-core
container; its distributed counterpart is the sharded dry-run — noted, not
faked.

``--json`` additionally writes each section's structured rows to
``BENCH_<section>.json`` (machine-readable; CI records ``BENCH_serving.json``
as the perf-trajectory artifact) plus a ``BENCH_status.json`` summary with
one ok/error entry per section, and appends the rows to the cross-run
benchmark history when ``$RACE_BENCH_HISTORY`` is set (the
``repro.obs.check`` sentinel gates on that trajectory).

``--strict`` (what CI runs) exits nonzero when any section crashed; the
default keeps the harness lenient for local exploration — a broken section
prints its traceback and the sweep continues with exit 0.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


def _jsonable(o):
    """Recursively coerce numpy scalars/arrays for json.dump."""
    import numpy as np

    if isinstance(o, dict):
        return {str(k): _jsonable(v) for k, v in o.items()}
    if isinstance(o, (list, tuple)):
        return [_jsonable(v) for v in o]
    if isinstance(o, np.generic):
        return o.item()
    if isinstance(o, np.ndarray):
        return o.tolist()
    return o


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller sweeps")
    ap.add_argument("--only", type=str, default="")
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_<section>.json with each "
                         "section's structured rows plus a "
                         "BENCH_status.json per-section ok/error summary")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero if any section failed (the CI "
                         "default); without it a crashed section is "
                         "reported but the run exits 0")
    ap.add_argument("--backend", choices=("xla", "pallas"), default="xla",
                    help="execution backend for the speedup section; "
                         "'pallas' adds a RACE-pallas column (cases the "
                         "capability probe rejects report their reason)")
    ap.add_argument("--from-frontend", action="store_true",
                    help="add the 'frontend' section: capture the "
                         "plain-Python twins (repro.frontend), report "
                         "capture overhead and plan equivalence vs the "
                         "hand-built DSL path")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    sections = []
    from . import grad, memory, scaling, serving, speedup, table1, tuning

    sections = [
        ("table1", lambda: table1.run()),
        ("speedup", lambda: speedup.run(
            cases=["calc_tpoints", "gaussian", "psinv", "derivative"] if args.quick else None,
            backend=args.backend)),
        ("scaling", lambda: scaling.run()),
        ("memory", lambda: memory.run()),
        ("serving", lambda: serving.run(quick=args.quick)),
        ("tuning", lambda: tuning.run(quick=args.quick)),
        ("grad", lambda: grad.run(quick=args.quick)),
    ]
    if args.from_frontend:
        from . import frontend

        sections.append(("frontend", lambda: frontend.run()))
    try:
        from . import roofline

        sections.append(("roofline", lambda: roofline.run()))
    except Exception:  # pragma: no cover
        pass

    print("name,us_per_call,derived")
    from .common import bench_stamp, record_history

    stamp = bench_stamp()
    status = {}
    for name, fn in sections:
        if only and name not in only:
            continue
        if args.quick and name == "scaling":
            continue
        try:
            rows = fn()
            status[name] = dict(status="ok")
            if args.json and rows is not None:
                path = f"BENCH_{name}.json"
                doc = dict(stamp=stamp, section=name, status="ok",
                           rows=_jsonable(rows))
                with open(path, "w") as f:
                    json.dump(doc, f, indent=1, default=str)
                print(f"json.{name},0.00,wrote={path}")
                record_history(name, doc["rows"], stamp)
        except Exception as e:  # keep the harness going; report at the end
            status[name] = dict(status="error",
                                error=f"{type(e).__name__}: {e}")
            print(f"{name},0.00,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    failures = sum(1 for s in status.values() if s["status"] != "ok")
    if args.json:
        with open("BENCH_status.json", "w") as f:
            json.dump(dict(stamp=stamp, strict=args.strict,
                           sections_failed=failures, sections=status),
                      f, indent=1)
        print("json.status,0.00,wrote=BENCH_status.json")
    from repro import obs

    if obs.enabled():
        obs.dump("OBS_metrics.json")
        print("obs,0.00,wrote=OBS_metrics.json")
    print(f"done,0.00,sections_failed={failures}")
    if failures and args.strict:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
