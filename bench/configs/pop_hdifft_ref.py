"""Plain reference of the POP Gent-McWilliams tracer box sums, in NumPy.

For every level and every interior point (``1 <= i <= nx-2``,
``1 <= j <= ny-2``), with ``box(A, d) = A[i, j+d] + A[i+1, j+d] +
A[i, j+d+1] + A[i+1, j+d+1]``::

    dn[i, j]  = box(T, 0)  + box(S, 0)
    dso[i, j] = box(T, -1) + box(S, -1)

Arrays carry a leading level axis.  ``dtype`` is the precision every
operation is rounded to: float64 for the oracle, bfloat16 for the control.
"""
from __future__ import annotations

import numpy as np


def _box(a, d: int):
    ny = a.shape[-1]
    lo, hi = 1 + d, ny - 1 + d  # j + d over j = 1 .. ny-2
    return ((a[:, 1:-1, lo:hi] + a[:, 2:, lo:hi])
            + (a[:, 1:-1, lo + 1:hi + 1] + a[:, 2:, lo + 1:hi + 1]))


def hdifft(t, s, dtype=np.float64) -> dict:
    """``{"dn": ..., "dso": ...}`` of shape ``(levels, nx-2, ny-2)``."""
    t = np.asarray(t).astype(dtype)
    s = np.asarray(s).astype(dtype)
    return {"dn": _box(t, 0) + _box(s, 0), "dso": _box(t, -1) + _box(s, -1)}
