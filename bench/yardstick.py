"""The benchmark's own arithmetic: compulsory bytes, the comparison that
decides ``correct``, and the key data is drawn with.  None takes anything
from the program under test."""
from __future__ import annotations

import math

import numpy as np


def compulsory_bytes(footprints: dict, itemsize: int) -> int:
    """Bytes a sweep must move at least: every array each program reads,
    once, over the region it reads, and every array it writes, once, at its
    written extent.

    ``footprints`` is ``{program: {"read": {array: shape}, "write": {array:
    shape}}}``; the count is the same whatever implements the programs."""
    total = 0
    for prog in footprints.values():
        for kind in ("read", "write"):
            for shape in prog.get(kind, {}).values():
                total += math.prod(shape) * itemsize
    return total


def rel_gap(got, want) -> float:
    """``max |got - want| / max |want|``, in float64."""
    g = np.asarray(got, np.float64)
    w = np.asarray(want, np.float64)
    if g.shape != w.shape:
        return math.inf
    scale = float(np.abs(w).max()) if w.size else 0.0
    gap = float(np.abs(g - w).max()) if w.size else 0.0
    if not math.isfinite(gap):
        return math.inf
    return gap / max(scale, 1e-30)


def seed_key(seed: int):
    """A JAX PRNG key from a seed of any size (seeds pass 32 bits)."""
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
