"""Find the serve cell's highest sustained rate, once, by a sweep on the chip.

    python3 bench/sweep_rate.py --workload pop_gx1v6.serve --seconds 5 \\
        --rates 100 200 400 800

Runs the cell's open loop at each rate in turn, in one process (set-up
paid once), and prints one JSON line per rate: the latency quantiles, the
batch sizes, and how long the queue took to drain after the last request
was sent.  A rate is sustained where the drain stays short and the tail
does not grow with the window.  The cell then runs at a fixed rate of
about four fifths of the highest sustained one, written into its mix; the
benchmark's own runs never search.
"""
import argparse
import json
import sys
import time

from run import ROOT, boot


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    if not boot():
        return 2
    from bench import harness, traffic

    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.resolve(spec, args.workload, ROOT)
    devices = harness.devices_for(cell.chips, require_tpu=True)
    compiles = harness.CompileCounter()
    fam = harness.family(cell.config)
    for rate in args.rates:
        cell.traffic = dict(cell.traffic, rate_per_s=rate)
        clock = harness.Clock(time.perf_counter())
        out = traffic.open_loop(fam, cell, args.seed, args.seconds, False,
                                devices, clock, compiles)
        print(json.dumps(dict(rate_per_s=rate, **out.e2e,
                              drain_s=out.notes["window_s"]
                              - out.notes["send_s"], failed=out.failed,
                              batch_mean=out.counters["completed"]
                              / max(1, out.counters["batches"]),
                              compiles=sum(out.compiles.values()),
                              late_p95_ms=out.notes["late_p95_ms"],
                              checks=out.check())), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
