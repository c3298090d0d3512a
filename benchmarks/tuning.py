"""Autotuner benchmark: what measured selection buys over the static default.

For each case the sweep runs :func:`repro.tuning.autotune` against a *fresh*
store (so the reported search time is a real cold search, not a store hit)
and reports

  * ``baseline_us``  the static default config (the case's paper-faithful
    reassociation level on the capability probe's backend, default blocks);
  * ``tuned_us``     the correctness-gated winner;
  * ``choice``       which candidate won (level / backend / blocks);
  * ``search_s``     wall time of the whole search;
  * ``store_hit``    a second ``autotune`` call answers from the store with
    zero re-measurement (the persistence contract, re-checked every run).

The tuner falls back to the default on ties, so ``tuned_us <= baseline_us``
up to measurement noise — the sweep asserts it (``never_slower``).

Pallas candidates are interpreted on the CPU backend: timings there are
correctness-plus-plumbing signal; the real strategy search needs a TPU,
where they run compiled.
"""
from __future__ import annotations

import tempfile
from pathlib import Path

from repro.apps.paper_kernels import get_case
from repro.tuning import TuningStore, autotune

from .common import build_env, csv_line

#: (case, grid size): one transcendental 2-D, one halo-heavy 2-D, one 3-D
CASES = [("calc_tpoints", 48), ("gaussian", 48), ("psinv", 12)]


def run(print_fn=print, quick: bool = False, repeats: int = None):
    """Returns one row per case; CSV is printed en route."""
    repeats = repeats or (3 if quick else 7)
    levels = (0, 3) if quick else (0, 3, 4)
    rows = []
    store = TuningStore(
        Path(tempfile.mkdtemp(prefix="race-tuning-bench-")) / "tuning.jsonl")
    for name, n in CASES[:2] if quick else CASES:
        case = get_case(name, n)
        env = build_env(case)
        dec = autotune(case.program, env, levels=levels, repeats=repeats,
                       warmup=1, quick=quick,
                       default_reassociate=case.reassociate,
                       rewrite_div=case.rewrite_div, store=store)
        # same search-shaping options as the first call: the store key now
        # includes them (a narrowed search never answers a wider one)
        redo = autotune(case.program, env, levels=levels, quick=quick,
                        default_reassociate=case.reassociate,
                        rewrite_div=case.rewrite_div, store=store)
        if dec.default_us is None:  # default gated/errored: name the culprit
            bad = next((m for m in dec.measurements
                        if m.config == dec.default), None)
            raise AssertionError(
                f"{case.name}: static default {dec.default.describe()} did "
                f"not survive measurement "
                f"({bad.status if bad else 'missing'}: "
                f"{bad.detail if bad else ''})")
        row = dict(
            case=case.name,
            baseline_us=dec.default_us, tuned_us=dec.tuned_us,
            speedup=dec.speedup,
            choice=dec.choice.as_dict(), default=dec.default.as_dict(),
            search_s=dec.search_seconds,
            n_candidates=len(dec.measurements),
            n_ok=sum(m.ok for m in dec.measurements),
            n_gated=sum(m.status == "gated" for m in dec.measurements),
            store_hit=redo.from_cache,
            never_slower=dec.tuned_us <= dec.default_us,
        )
        if not row["never_slower"]:  # the acceptance invariant
            raise AssertionError(
                f"{case.name}: tuned {dec.tuned_us:.1f}us slower than "
                f"static default {dec.default_us:.1f}us")
        if not redo.from_cache:
            raise AssertionError(
                f"{case.name}: second autotune re-measured instead of "
                f"answering from the store")
        derived = (f"baseline_us={dec.default_us:.1f}"
                   f";speedup={dec.speedup:.2f}x"
                   f";choice={dec.choice.describe()}"
                   f";search_s={dec.search_seconds:.2f}"
                   f";candidates={row['n_ok']}/{row['n_candidates']}"
                   f";store_hit={redo.from_cache}")
        print_fn(csv_line(f"tuning.{name}", dec.tuned_us, derived))
        rows.append(row)
    return rows


if __name__ == "__main__":
    from .common import section_main

    section_main("tuning", run)
