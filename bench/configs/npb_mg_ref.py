"""Plain reference of NPB MG's finest-level smoothing step, in NumPy.

One step on the periodic ``n^3`` interior (NPB's ``comm3`` keeps the ghost
layer periodic)::

    r = v - A u          (resid: a0 centre, a1 faces, a2 edges, a3 corners)
    u = u + S r          (psinv: c0 centre, c1 faces, c2 edges, c3 corners)

The 27-point sums are written out here from NPB's definition; nothing is
taken from the program under test.  The step works on a slab: a run of
consecutive planes along axis 0 (already gathered with the periodic wrap),
periodic along axes 1 and 2, so a sample of planes can be checked without
the whole grid.  ``dtype`` is the precision every operation is rounded to:
float64 for the oracle, bfloat16 for the control.
"""
from __future__ import annotations

import numpy as np


def _pair(x, axis: int):
    return np.roll(x, 1, axis) + np.roll(x, -1, axis)


def _classes(x):
    """Centre, face, edge and corner sums of the 27-point neighbourhood for
    the inner planes ``x[1:-1]`` of a slab."""
    c = x[1:-1]
    z = x[:-2] + x[2:]
    c1, c2 = _pair(c, 1), _pair(c, 2)
    faces = z + c1 + c2
    edges = _pair(z, 1) + _pair(z, 2) + _pair(c1, 2)
    corners = _pair(_pair(z, 1), 2)
    return c, faces, edges, corners


def _apply(x, w, dt):
    c, f, e, k = _classes(x)
    return dt(w[0]) * c + dt(w[1]) * f + dt(w[2]) * e + dt(w[3]) * k


def step(u, v, a, c, dtype=np.float64):
    """One resid+psinv step on a slab.

    ``u``: ``T`` consecutive planes of the interior of U; ``v``: the ``T-2``
    middle planes of the interior of V.  Returns ``(r, u_new)``: the
    residual on the ``T-2`` middle planes and the smoothed U on the ``T-4``
    middle planes."""
    dt = np.dtype(dtype).type
    u = np.asarray(u).astype(dtype)
    v = np.asarray(v).astype(dtype)
    r = v - _apply(u, a, dt)
    return r, u[2:-2] + _apply(r, c, dt)
