"""JAX code generation for RACE plans (hardware adaptation, DESIGN.md §2).

The paper emits scalar Fortran/C loops; the TPU-native realization evaluates
each statement as a *whole-array* expression over its iteration box:

  * ``A[a*i+b, ...]`` over ``i in [lo, hi]``  ->  strided slice (fast path) or
    broadcasted gather (general path: repeated levels, negative coefs);
  * an auxiliary array + precompute loop  ->  one materialized intermediate
    tensor per range circle, emitted in topological order;
  * inlined (rule-1) auxs never materialize — their expression was spliced
    back by ``depgraph.finalize``.

Evaluators are plain Python callables over ``{name: jnp.ndarray}`` and are
`jax.jit`-compatible (everything static except array values).

Scope note (paper §4.1): programs must not read an array they write except
pointwise at identical subscripts (e.g. ``U[i] = U[i] + ...``); RACE only
reasons about unmodified arrays, and the whole-array semantics relies on it.
"""
from __future__ import annotations

from dataclasses import dataclass
from fractions import Fraction
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from .depgraph import Plan
from .ir import Const, Expr, FuncName, Node, Program, Ref, Stmt

FUNCS = {
    "sin": jnp.sin,
    "cos": jnp.cos,
    "exp": jnp.exp,
    "log": jnp.log,
    "sqrt": jnp.sqrt,
    "tanh": jnp.tanh,
    "abs": jnp.abs,
}


@dataclass
class _Buf:
    """Array plus the absolute index of its [0, 0, ...] corner per dim."""

    data: object
    lo: tuple


def _as_int(f) -> int:
    f = Fraction(f)
    if f.denominator != 1:
        raise ValueError(f"non-integral subscript offset {f}")
    return int(f)


def _eval_ref(ref: Ref, bufs: dict, domain_levels: tuple, ranges: dict):
    """Evaluate a reference over the domain box; result broadcasts against
    arrays shaped (extent(l) for l in domain_levels)."""
    buf = bufs[ref.name]
    if not ref.subs:  # scalar
        return buf.data if isinstance(buf, _Buf) else buf
    data, base_lo = (buf.data, buf.lo) if isinstance(buf, _Buf) else (buf, (0,) * buf.ndim)

    dims_levels = [s.s for s in ref.subs]
    fast = (
        len(set(l for l in dims_levels if l != 0)) == len([l for l in dims_levels if l != 0])
        and all(s.a >= 0 for s in ref.subs)
    )
    if fast:
        # strided slice per dim, then transpose into domain order and insert
        # singleton axes for unreferenced levels.
        starts, stops, strides, keep = [], [], [], []
        for d, s in enumerate(ref.subs):
            if s.s == 0:
                idx = _as_int(s.b) - base_lo[d]
                starts.append(idx)
                stops.append(idx + 1)
                strides.append(1)
                keep.append(False)
            else:
                lo, hi = ranges[s.s]
                start = s.a * lo + _as_int(s.b) - base_lo[d]
                stop = s.a * hi + _as_int(s.b) - base_lo[d] + 1
                starts.append(start)
                stops.append(stop)
                strides.append(max(s.a, 1))
                keep.append(True)
        sl = jax.lax.slice(data, starts, stops, strides)
        # drop constant dims
        sl = sl.reshape([n for n, k in zip(sl.shape, keep) if k])
        ref_levels = [l for l in dims_levels if l != 0]
        # transpose ascending-level order, then place into domain positions
        perm = sorted(range(len(ref_levels)), key=lambda k: ref_levels[k])
        sl = jnp.transpose(sl, perm)
        sorted_levels = sorted(ref_levels)
        shape = [1] * len(domain_levels)
        for ax, lvl in enumerate(sorted_levels):
            shape[domain_levels.index(lvl)] = sl.shape[ax]
        return sl.reshape(shape)

    # general gather path (duplicate levels / negative coefficients)
    idxs = []
    for d, s in enumerate(ref.subs):
        if s.s == 0:
            idxs.append(jnp.asarray(_as_int(s.b) - base_lo[d]))
        else:
            lo, hi = ranges[s.s]
            vec = s.a * jnp.arange(lo, hi + 1) + _as_int(s.b) - base_lo[d]
            shape = [1] * len(domain_levels)
            shape[domain_levels.index(s.s)] = hi - lo + 1
            idxs.append(vec.reshape(shape))
    return data[tuple(idxs)]


def _eval_expr(e: Expr, bufs: dict, domain_levels: tuple, ranges: dict,
               memo: dict = None):
    if isinstance(e, Ref):
        # the same Ref often occurs many times in one statement (that is the
        # reuse RACE detects); slice it once per statement, not per occurrence
        if memo is None:
            return _eval_ref(e, bufs, domain_levels, ranges)
        val = memo.get(e)
        if val is None:
            val = memo[e] = _eval_ref(e, bufs, domain_levels, ranges)
        return val
    if isinstance(e, Const):
        return e.val
    if isinstance(e, FuncName):  # only under 'call'
        raise ValueError("bare function name")
    ev = partial(_eval_expr, bufs=bufs, domain_levels=domain_levels,
                 ranges=ranges, memo=memo)
    if e.op == "call":
        return FUNCS[e.kids[0].name](ev(e.kids[1]))
    if e.op == "neg":
        return -ev(e.kids[0])
    if e.op == "inv":
        return 1.0 / ev(e.kids[0])
    a, b = ev(e.kids[0]), ev(e.kids[1])
    if e.op == "+":
        return a + b
    if e.op == "-":
        return a - b
    if e.op == "*":
        return a * b
    if e.op == "/":
        return a / b
    raise ValueError(f"bad op {e.op}")


def _write_stmt(st: Stmt, value, out: dict, env: dict, ranges: dict, domain_levels):
    """Scatter the computed box into the lhs array region."""
    # value axes follow domain_levels; lhs dims may order levels differently
    lhs_levels = [s.s for s in st.lhs.subs]
    perm = [domain_levels.index(l) for l in lhs_levels]
    value = jnp.transpose(jnp.broadcast_to(value, tuple(
        ranges[l][1] - ranges[l][0] + 1 for l in domain_levels)), perm)
    name = st.lhs.name
    lo_idx, hi_idx = [], []
    for s in st.lhs.subs:
        lo, hi = ranges[s.s]
        lo_idx.append(s.a * lo + _as_int(s.b))
        hi_idx.append(s.a * hi + _as_int(s.b) + 1)
    if name in out:
        base = out[name]
    elif name in env:
        base = jnp.asarray(env[name])
    else:
        shape = tuple(hi_idx)
        base = jnp.zeros(shape, dtype=value.dtype)
    region = tuple(slice(l, h) for l, h in zip(lo_idx, hi_idx))
    out[name] = base.at[region].set(value.astype(base.dtype))


def build_plan_evaluator(plan: Plan):
    """Evaluator for the RACE-transformed program."""

    program = plan.program
    full = program.ranges()
    all_levels = tuple(sorted(full))

    def run(env: dict) -> dict:
        bufs: dict = dict(env)
        for aux in plan.aux_order:
            rng = plan.ranges[aux.name]
            levels = tuple(sorted(aux.levels))
            val = _eval_expr(plan.aux_exprs[aux.name], bufs, levels, rng, {})
            shape = tuple(rng[l][1] - rng[l][0] + 1 for l in levels)
            val = jnp.broadcast_to(val, shape)
            # force a materialization boundary: XLA's fusion otherwise
            # duplicates the aux producer into every consumer, silently
            # recomputing what RACE just de-duplicated (the compiler
            # rematerialization hazard of paper section 8)
            val = jax.lax.optimization_barrier(val)
            bufs[aux.name] = _Buf(val, tuple(rng[l][0] for l in levels))
        out: dict = {}
        for st in plan.body:
            # fresh memo per statement: bufs mutates between statements
            val = _eval_expr(st.rhs, bufs, all_levels, full, {})
            _write_stmt(st, val, out, env, full, all_levels)
            bufs[st.lhs.name] = out[st.lhs.name]
        return out

    return run


def build_baseline_evaluator(program: Program):
    """Evaluator for the unmodified program (same machinery, no auxs)."""
    full = program.ranges()
    all_levels = tuple(sorted(full))

    def run(env: dict) -> dict:
        bufs: dict = dict(env)
        out: dict = {}
        for st in program.body:
            val = _eval_expr(st.rhs, bufs, all_levels, full, {})
            _write_stmt(st, val, out, env, full, all_levels)
            bufs[st.lhs.name] = out[st.lhs.name]
        return out

    return run


def required_shapes(program: Program) -> dict:
    """Minimal array shapes covering every access (for building test data)."""
    full = program.ranges()
    shapes: dict = {}
    from .ir import expr_refs

    def see(ref: Ref):
        if not ref.subs:
            shapes.setdefault(ref.name, ())
            return
        dims = []
        for s in ref.subs:
            if s.s == 0:
                dims.append(_as_int(s.b) + 1)
            else:
                lo, hi = full[s.s]
                dims.append(max(s.a * lo + _as_int(s.b), s.a * hi + _as_int(s.b)) + 1)
        cur = shapes.get(ref.name)
        shapes[ref.name] = tuple(
            max(a, b) for a, b in zip(cur, dims)
        ) if cur else tuple(dims)

    for st in program.body:
        see(st.lhs)
        for r in expr_refs(st.rhs):
            see(r)
    return shapes
