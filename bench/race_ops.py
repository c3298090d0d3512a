"""RACE's own device work in a trace, found by the names the program gives
it.

An executor's jitted step compiles to an XLA module named
``jit_race_<program>_<plan8>`` (``jit_race_batch_...`` for a vmapped
batch, ``jit_race_sharded_...`` over a mesh), and its Pallas kernel to an
operation whose name holds ``race_kernel_`` (``vmap_race_kernel_..._``
in a batch).  Every other operation inside a RACE module is a layout pass:
the transposes, pads, slices and copies around the kernel.  Operations
outside RACE's modules (the benchmark's own write-back) are neither.  A
program that does not give these names reads as nothing.
"""
from __future__ import annotations

import bisect

KERNEL = "race_kernel_"
MODULE = "jit_race_"


def _window_evs(tr, chip: str, kind: str) -> list:
    lo, hi = tr.window
    return [(max(s, lo), min(e, hi), n) for s, e, n, k in tr.devices[chip]
            if k == kind and e > lo and s < hi]


def split_s(tr) -> dict:
    """``{chip: (kernel seconds, layout seconds)}`` inside the window, for
    the chips on which a RACE module ran.  An operation counts as RACE's
    when a RACE module's interval holds its start."""
    out = {}
    for chip in tr.devices:
        mods = sorted((s, e) for s, e, n in _window_evs(tr, chip, "module")
                      if n.startswith(MODULE))
        if not mods:
            continue
        starts = [s for s, _ in mods]
        kernel = layout = 0
        for s, e, name in _window_evs(tr, chip, "op"):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1]:
                continue
            if KERNEL in name:
                kernel += e - s
            else:
                layout += e - s
        out[chip] = (kernel * 1e-9, layout * 1e-9)
    return out


def per_sweep_ms(ctx, part: int):
    """Milliseconds per sweep of one part of :func:`split_s` (0 kernel, 1
    layout), averaged over the chips; None where nothing was read."""
    split = split_s(ctx.trace)
    if not ctx.sweeps or not split:
        return None
    total = sum(v[part] for v in split.values())
    if part == 0 and total <= 0:
        return None  # RACE's modules ran, but no named kernel: not Pallas
    return 1e3 * total / len(ctx.trace.devices) / ctx.sweeps
