"""Readings that a cell's limits are set from: the program's and the
control's, seed by seed, in one process.

    python3 bench/control.py --workload npb_mg_b.smooth --seconds 3 \\
        --seeds 11 12 13

For each seed the cell is set up and run for a short window exactly as
``bench/run.py`` runs it; then the numbers it compares are read twice: for
what the timed path produced, and for the control, the plain reference
computed in bfloat16 (the precision below the configuration's float32) on
the same inputs.  A limit lies above every program reading and below
every control reading.  The benchmark's own runs never run the control.
"""
import argparse
import json
import sys
import time

from run import ROOT, boot


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    if not boot():
        return 2
    from bench import harness

    for seed in args.seeds:
        rep = harness.run(ROOT, args.workload, seed, args.seconds, False,
                          time.perf_counter(), control=True)
        print(json.dumps(dict(
            seed=seed, correct=rep["result"]["correct"],
            program={k: c["value"] for k, c in rep["checks"].items()},
            control=rep["control"], limit={k: c["limit"] for k, c in
                                           rep["checks"].items()},
            notes=rep["notes"]), default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
