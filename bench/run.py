"""Run one benchmark cell once on the machine it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell named in ``BENCHMARK.json`` (counted in ``setup_s`` from
process start), measures for ``--seconds`` seconds, checks what the timed
path produced against the plain reference, and prints one JSON result as
the last line of stdout.  With ``--trace 1`` the window (at most ten
seconds of it) is profiled and the metrics are the cell's per-layer ones.

Exits non-zero, printing no result, without a TPU, with fewer chips than
the cell asks for, or where the program under test (``src/repro``) is not
beside the benchmark.
"""
import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def boot() -> bool:
    """Prepare a benchmark process before JAX is imported; False where the
    program under test is missing.

    JAX's persistent compilation cache goes to ``<checkout>/.jax-compile-
    cache``, a fixed path, and keeps every program however quick to
    compile, so only a checkout's first run compiles.  The import path
    holds the program and the benchmark package, not this script's
    directory, whose module names would shadow the standard library's."""
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under test at {ROOT / 'src' / 'repro'}",
              file=sys.stderr)
        return False
    cache = ROOT / ".jax-compile-cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    here = Path(__file__).resolve().parent
    sys.path[:] = [str(ROOT / "src"), str(ROOT)] + [
        p for p in sys.path if Path(p or ".").resolve() != here]
    return True


def main(argv=None) -> int:
    if not boot():
        return 2
    from bench import harness

    return harness.main(sys.argv[1:] if argv is None else argv, ROOT, T0)


if __name__ == "__main__":
    sys.exit(main())
