"""Kernel emission: the traceable body and the ``LoweredStencil`` artifact.

This is the hardware-adapted form of the paper's array contraction
(DESIGN.md section 2, rule 3): auxiliary arrays are *never* materialized in
HBM — each output tile recomputes its auxiliary slices into VMEM values of
size O(tile + reuse-halo), the paper's "compute the precompute loop inside
the streaming loop with a small rolling buffer" re-expressed for the
HBM->VMEM hierarchy — now generic over nest depth and window shape:

  * the iteration space is laid out in one of two orders
    (``specialize_stencil(order=...)``): as laid, the kernel's axes follow
    the first output's own dim order, so operands laid the same way and the
    outputs cross without a transpose; level-major, they follow the loop
    levels.  Either way the engine sees a level-major nest: as laid is the
    plan with its levels renumbered (:func:`~repro.lowering.geometry.
    relabel_levels`).  ``repro.lowering.blocks`` grid-tiles every axis but
    the last (any depth), and each window operand
    arrives as one overlapping window per grid step: the output tile
    widened by the operand's offset envelope, aligned to the TPU's (8, 128)
    tiling on the last two axes (``LoweredStencil.read_factor`` records
    what that costs in reads);
  * window references — positive *or* negative integer coefficients — lower
    to static strided slices; mirrored-origin references read their flipped
    operand through normalized offsets (``repro.lowering.geometry``);
  * repeated-level and constant-dim references lower to an in-kernel index
    gather over whole-array operands (``repro.lowering.gather``);
  * auxiliary arrays index the iteration space directly and are evaluated in
    topological order with per-aux tile extensions, so every reuse the
    detection found is realized as a VMEM hit.

``specialize_stencil`` does every shape-dependent but data-independent step
once — analysis, layout, BlockSpecs, grid, kernel closure, the
``pl.pallas_call`` construction itself — and returns a
:class:`LoweredStencil` whose ``apply(env)`` is the pure per-call data path
(transpose/flip/pad/slice/pallas_call/unpad), fully ``jax.jit``-traceable
and ``jax.vmap``-batchable.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs as _obs
from repro.core.depgraph import Plan
from repro.core.ir import Const, Expr, Node, Ref

from .blocks import ArrayPrep, Layout, build_layout
from .facts import LoweringError
from .gather import gather_ref
from .geometry import (ORDER_AS_LAID, ORDER_LEVEL_MAJOR, LoweringAnalysis,
                       analyze_plan, aux_shift, nest_levels, platform_reasons,
                       ref_affine, relabel_levels, target_platform)

#: device-trace names: the Pallas kernel's op is named ``<prefix><program
#: label>`` (under ``vmap``, ``vmap_<prefix><label>_``), and the layout
#: passes of :meth:`LoweredStencil.apply` sit in these named scopes
KERNEL_PREFIX = "race_kernel_"
LAYOUT_IN = "race_layout_in"
LAYOUT_OUT = "race_layout_out"

_FUNCS = {"sin": jnp.sin, "cos": jnp.cos, "exp": jnp.exp, "log": jnp.log,
          "sqrt": jnp.sqrt, "tanh": jnp.tanh, "abs": jnp.abs}


# ---------------------------------------------------------------------------
# kernel body generation
# ---------------------------------------------------------------------------


def build_kernel(plan: Plan, analysis: LoweringAnalysis, layout: Layout):
    """Returns kernel(scalars, operands..., outs...) for ``pl.pallas_call``.

    Window operands covering a level subset broadcast via size-1 axes at the
    levels they lack; gather operands arrive whole and are indexed by global
    iteration coordinates."""
    m = layout.m
    blocks = layout.blocks
    out_tile = layout.out_tile
    arrays = analysis.arrays
    ext = analysis.ext
    aux_names = [a.name for a in plan.aux_order]
    aux_levels = {a.name: a.levels for a in plan.aux_order}

    def _tile_width(lvl, re):  # tile width along a level (1-based)
        return out_tile[lvl - 1] + 2 * re[lvl - 1]

    def load(ref):  # a window block, without the 1-D nest's lift axis
        return ref[0] if layout.lift else ref[...]

    def kernel(*refs):
        it = iter(refs)
        scal = next(it)  # (1, n_scalars)
        # a gather operand arrives whole; a window operand as one window
        windows = {nm: next(it)[...] if nm in layout.gather_names
                   else load(next(it)) for nm in layout.base_names}
        outs = [next(it) for _ in layout.out_names]

        env_scalar = {nm: scal[0, i]
                      for i, nm in enumerate(layout.scalar_names)}
        aux_vals = {}
        ref_memo = {}  # (Ref, ext) -> evaluated value; dedup repeated refs

        def ev(e: Expr, re):
            """Evaluate e over the tile extended by re (per level); result
            has one axis per level (size 1 where e doesn't vary)."""
            if isinstance(e, Const):
                return jnp.float32(e.val)
            if isinstance(e, Ref):
                if not e.subs:
                    return env_scalar[e.name]
                key = (e, tuple(re))
                hit = ref_memo.get(key)
                if hit is not None:
                    return hit
                ref_memo[key] = val = _ev_ref(e, re)
                return val
            if isinstance(e, Node):
                if e.op == "call":
                    return _FUNCS[e.kids[0].name](ev(e.kids[1], re))
                if e.op == "neg":
                    return -ev(e.kids[0], re)
                if e.op == "inv":
                    return 1.0 / ev(e.kids[0], re)
                a, b = ev(e.kids[0], re), ev(e.kids[1], re)
                return {"+": a + b, "-": a - b, "*": a * b, "/": a / b}[e.op]
            raise TypeError(e)

        def _ev_ref(e: Ref, re):
            if e.name in aux_vals:
                sh = aux_shift(e)
                val, store_ext, covered = aux_vals[e.name]
                sl = []
                for lvl in range(1, m + 1):
                    if lvl in covered:
                        s0 = store_ext[lvl - 1] + sh.get(lvl, 0) - re[lvl - 1]
                        sl.append(slice(s0, s0 + _tile_width(lvl, re)))
                    else:
                        sl.append(slice(0, 1))
                return val[tuple(sl)]
            if e.name in layout.gather_names:
                return gather_ref(e, windows[e.name], re, m=m, lo=layout.lo,
                                  blocks=blocks, grid_pos=layout.grid_pos,
                                  out_tile=out_tile)
            info = arrays[e.name]
            raw = ref_affine(e)
            mir = layout.mirror[e.name]
            sb = layout.slice_base[e.name]
            w = windows[e.name]
            sl = []
            for lvl in range(1, m + 1):
                if lvl not in raw:  # size-1 axis at a level e lacks
                    sl.append(slice(None))
                    continue
                _, b = raw[lvl]
                if lvl in mir:
                    b = mir[lvl] - b  # mirrored-origin: b' = (L-1) - b
                a = info.coefs[lvl]  # normalized |a|
                width = _tile_width(lvl, re)
                s0 = sb[lvl] + b - a * re[lvl - 1]
                sl.append(slice(s0, s0 + a * (width - 1) + 1, a))
            return w[tuple(sl)]

        # auxiliary arrays: VMEM values (the contraction payoff)
        for nm in aux_names:
            aux_vals[nm] = (ev(plan.aux_exprs[nm], ext[nm]), ext[nm],
                            set(aux_levels[nm]))

        for ref, st in zip(outs, plan.body):
            val = ev(st.rhs, (0,) * m)
            ref[...] = jnp.broadcast_to(val, ref.shape).astype(ref.dtype)

    return kernel


# ---------------------------------------------------------------------------
# host-side call: specialize-time phase vs per-call data path
# ---------------------------------------------------------------------------


@dataclass
class LoweredStencil:
    """Specialize-time product for one (plan, shapes, dtypes, block config).

    Everything here is static; :meth:`apply` only performs traceable array
    ops, so one artifact serves arbitrarily many calls (and batches) without
    redoing host-side prep.  ``analysis`` carries the lowering facts
    (mirrored windows, gather operands, N-D depth) this specialization
    engaged, in the plan's own level numbers; ``order`` is the order the
    kernel iterates the nest in (``extents`` and the kernel's axes follow
    it)."""

    plan: Plan
    scalar_names: tuple
    base_names: tuple
    out_names: tuple
    dt: object  # result dtype of the kernel operands/outputs
    prep: dict  # base name -> ArrayPrep
    extents: tuple
    out_axes: dict  # out name -> transpose to its own dim order, or ()
    interpret: bool
    order: str = ORDER_AS_LAID  # ORDER_AS_LAID | ORDER_LEVEL_MAJOR
    lift: bool = False  # 1-D nest: outputs carry a leading size-1 axis
    #: base name -> elements the kernel fetches over the grid / elements of
    #: the region it reads (1.0: each element once)
    read_factor: dict = field(default_factory=dict)
    analysis: LoweringAnalysis = None
    _call: object = None  # the constructed pl.pallas_call callable

    def apply(self, env: dict) -> dict:
        """The per-call data path (traceable; shapes must match the spec)."""
        scal = jnp.array([[env[nm] for nm in self.scalar_names]],
                         dtype=self.dt) \
            if self.scalar_names else jnp.zeros((1, 1), self.dt)
        ins = [scal]
        # the layout passes around the kernel carry named scopes: metadata
        # for the device trace's op names, no op of their own
        with jax.named_scope(LAYOUT_IN):
            for nm in self.base_names:
                pr = self.prep[nm]
                arr = jnp.asarray(env[nm])
                if pr.gather:
                    ins.append(arr)
                    continue
                if pr.tperm:
                    arr = jnp.transpose(arr, pr.tperm)
                for ax in pr.flips:
                    arr = jnp.flip(arr, ax)
                if any(l or r for l, r in pr.pads):
                    arr = jnp.pad(arr, pr.pads)
                arr = arr[pr.sls]
                if pr.expand:
                    arr = arr.reshape(pr.expand)
                ins.append(arr)
        outs = self._call(*ins)
        result = {}
        with jax.named_scope(LAYOUT_OUT):
            for nm, arr in zip(self.out_names, outs):
                if self.lift:
                    arr = arr[0]
                arr = arr[tuple(slice(0, e) for e in self.extents)]
                axes = self.out_axes[nm]
                result[nm] = jnp.transpose(arr, axes) if axes else arr
        return result

    __call__ = apply


def pallas_interpret() -> bool:
    """The Pallas mode, chosen from the platform kernels run on: compiled on
    a TPU, interpreted on the CPU backend.  Any other platform has no mode
    (the capability probe refuses it with ``pallas-platform``)."""
    platform = target_platform()
    if platform not in ("tpu", "cpu"):
        raise LoweringError(platform_reasons(None, platform))
    return platform == "cpu"


def specialize_stencil(plan: Plan, shapes: dict, dtypes: dict,
                       block_rows: int = 8, block_cols: int = 8,
                       interpret: Optional[bool] = None,
                       block_inner: int = 0,
                       order: str = ORDER_AS_LAID) -> LoweredStencil:
    """Build the static half of the blocked Pallas execution.

    ``shapes`` maps env entry names to ``np.shape``-style tuples (``()`` for
    scalars) and ``dtypes`` to their dtypes; together they are the
    environment *signature* the artifact is specialized against.

    ``order`` is the order the kernel iterates the nest in
    (:data:`~repro.lowering.geometry.ORDERS`): as laid (the default, one
    chip's executors) its axes follow the first output's dims, level-major
    (a sharded executor's local kernel) the loop levels.  The grid tiles
    every axis but the last — the first by ``block_rows``, middle ones by
    ``block_cols``.  The last axis (the only one of a 1-D nest) stays
    full-width by default (VPU lanes); ``block_inner > 0`` grid-tiles it
    too — for very wide rows whose full-width blocks would not fit VMEM — at
    the cost of a halo copy along it.

    ``interpret=None`` takes the platform's mode (:func:`pallas_interpret`);
    an explicit value lets a test build the compiled kernel on a CPU host.

    Raises :class:`~repro.lowering.facts.LoweringError` (a ``ValueError``)
    carrying the capability probe's exact structured reasons when the plan
    is outside the lowering model, or, for the compiled kernel, outside
    what the TPU compiler accepts.
    """
    if interpret is None:
        interpret = pallas_interpret()
    analysis = analyze_plan(plan)
    reasons = analysis.reasons or platform_reasons(
        analysis, "cpu" if interpret else "tpu")
    if reasons:
        raise LoweringError(reasons)
    levels = nest_levels(plan, order)
    kplan, kanalysis = plan, analysis
    if levels != tuple(sorted(levels)):
        kplan = relabel_levels(plan, levels)
        kanalysis = analyze_plan(kplan)
    layout = build_layout(kanalysis, shapes, dtypes, block_rows, block_cols,
                          block_inner)
    kernel = build_kernel(kplan, kanalysis, layout)
    call = pl.pallas_call(
        kernel,
        grid=layout.grid,
        in_specs=layout.in_specs,
        out_specs=layout.out_specs,
        out_shape=layout.out_shape,
        interpret=interpret,
        name=KERNEL_PREFIX + plan.program.label,
    )
    if _obs.enabled():
        label = plan.program.label
        for nm, factor in layout.read_factor.items():
            _obs.gauge("race_kernel_read_factor", program=label,
                       array=nm).set(factor)
        _obs.gauge("race_kernel_level_major", program=label).set(
            float(order == ORDER_LEVEL_MAJOR))
    return LoweredStencil(plan=plan, scalar_names=layout.scalar_names,
                          base_names=layout.base_names,
                          out_names=layout.out_names, dt=layout.dt,
                          prep=layout.prep, extents=layout.extents,
                          out_axes=layout.out_axes, interpret=interpret,
                          order=order, lift=layout.lift,
                          read_factor=layout.read_factor,
                          analysis=analysis, _call=call)
