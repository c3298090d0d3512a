"""N-D BlockSpec / grid construction for the generic lowering engine.

The iteration space is laid out level-major (outermost loop level = axis 0).
Every level except the innermost is grid-tiled — level 1 by ``block_rows``,
levels ``2..m-1`` by ``block_cols`` — and the innermost level stays
full-width for the VPU lanes unless ``block_inner > 0`` tiles it too.  The
single level of a 1-D nest is its innermost level, so it follows the same
rule.  A 4-D nest gets a 3-axis grid (levels 1-3) with 27 halo block copies
per fully-covered window operand.

Every block satisfies the TPU's tiling rule, also under ``jax.vmap`` (which
adds a squeezed batch axis in front): the last block axis spans its whole
array axis (or ``block_inner``, a multiple of 128 on a TPU) and the one
before it is whole or a multiple of 8.  A window operand that misses a level
gets a size-1 axis there, so it has rank ``m``, and a 1-D nest is lifted to
rank 2 by a leading size-1 axis on its windows and outputs.

Per window-class array and blocked level the input window is the standard
three consecutive input blocks (prev/cur/next) of ``|a|·tile`` elements; a
*center* offset ``c`` positions the reference offsets inside that 3-block
span.  Ordinary small offsets keep ``c = 0`` (the historical layout);
mirrored-origin references — whose normalized offsets ``b' = L-1-b`` sit
near the far end of the axis — recenter instead, so negative coefficients
cost nothing beyond the per-call ``jnp.flip``.  Unblocked levels carry the
asymmetric ``[off_lo, off_hi]`` envelope as a compile-time halo pad.

Gather-class arrays bypass the window machinery entirely: the whole
(untransposed, unpadded) array is one BlockSpec whose index map pins block
(0, ..., 0); ``repro.lowering.gather`` indexes it in-kernel.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass

import numpy as np

import jax
from jax.experimental import pallas as pl

from .facts import LoweringError
from .geometry import K_GATHER, K_WINDOW, LoweringAnalysis


def level_blocks(m: int, block_rows: int, block_cols: int,
                 block_inner: int) -> dict:
    """{level: tile size} for a depth-``m`` nest (innermost full by default).

    The innermost level is the TPU's lane axis, where a block must span the
    whole axis or a multiple of 128; so the single level of a 1-D nest is
    tiled only by ``block_inner``, like the innermost level of any nest."""
    if m == 1:
        return {1: block_inner} if block_inner else {}
    blocks = {1: block_rows}
    for l in range(2, m):
        blocks[l] = block_cols
    if block_inner:
        blocks[m] = block_inner
    return blocks


def _knob(l: int, m: int, block_inner: int) -> str:
    if l == m and block_inner:
        return "block_inner"
    if l == 1:
        return "block_rows"
    return "block_cols"


@dataclass
class ArrayPrep:
    """Per-call data movement for one base array (static amounts)."""

    tperm: tuple  # transpose into ascending-level order, or () if identity
    flips: tuple  # post-transpose axes to jnp.flip (mirrored-origin levels)
    pads: tuple  # per-axis (left, right) zero pad
    sls: tuple  # per-axis window slice after padding
    n_copies: int  # 3**len(blocked levels); 1 for gather operands
    gather: bool = False  # whole-array operand, indexed in-kernel
    expand: tuple = ()  # full-rank shape: size-1 axes at missing levels


@dataclass
class Layout:
    """Shape-specialized geometry: everything the kernel emitter consumes."""

    m: int
    extents: tuple  # per-level statement extent
    lo: tuple  # per-level statement lower bound
    blocks: dict  # grid-tiled level -> tile size
    grid: tuple
    grid_pos: dict  # level -> grid axis
    nb: dict  # level -> number of blocks
    scalar_names: tuple
    base_names: tuple
    out_names: tuple
    dt: object
    prep: dict  # name -> ArrayPrep
    slice_base: dict  # window name -> {level: kernel slice-start base}
    mirror: dict  # window name -> {level: L-1} for mirrored levels
    lift: bool  # 1-D nest: windows and outputs carry a leading size-1 axis
    gather_names: frozenset
    in_specs: list
    out_specs: list
    out_shape: list
    out_tile: tuple
    out_axes: dict  # out name -> inverse level-major transpose, or ()


def build_layout(analysis: LoweringAnalysis, shapes: dict, dtypes: dict,
                 block_rows: int, block_cols: int,
                 block_inner: int) -> Layout:
    plan = analysis.plan
    prog = plan.program
    m = analysis.depth
    ranges = prog.ranges()
    extents = tuple(ranges[l][1] - ranges[l][0] + 1 for l in range(1, m + 1))
    lo = tuple(ranges[l][0] for l in range(1, m + 1))

    blocks = level_blocks(m, block_rows, block_cols, block_inner)
    grid_levels = sorted(blocks)
    nb = {l: -(-extents[l - 1] // blocks[l]) for l in grid_levels}
    grid = tuple(nb[l] for l in grid_levels)
    grid_pos = {l: gi for gi, l in enumerate(grid_levels)}

    scalar_names = tuple(sorted(
        nm for nm, shp in shapes.items() if tuple(shp) == ()))
    base_names = tuple(sorted(analysis.arrays))
    out_names = tuple(st.lhs.name for st in plan.body)
    if not base_names:
        raise LoweringError(
            (), "Pallas stencil path needs at least one array operand on a "
                "right-hand side; this plan reads only scalars "
                f"(env entries: {sorted(shapes)}) — run it on the XLA "
                f"backend")
    missing = [nm for nm in base_names if nm not in shapes]
    if missing:
        raise ValueError(f"environment is missing base arrays {missing}")
    dt = jax.numpy.result_type(
        *[np.dtype(dtypes[nm]) for nm in base_names])

    in_specs = [pl.BlockSpec((1, max(len(scalar_names), 1)),
                             lambda *pids: (0, 0))]

    # a 1-D nest is lifted to rank 2: its window operands and outputs carry
    # a leading size-1 axis, so no block is rank 1
    lift = m == 1
    lead = (1,) if lift else ()

    def _imap(covered, ds_map, lead=()):
        # block-index map: blocked axes follow the grid id plus their halo
        # offset d in {0,1,2}; unblocked axes are one full-width block
        def imap(*pids):
            return (0,) * len(lead) + tuple(
                pids[grid_pos[l]] + ds_map[l] if l in ds_map else 0
                for l in covered)
        return imap

    prep: dict = {}
    slice_base: dict = {}
    mirror: dict = {}
    for nm in base_names:
        info = analysis.arrays[nm]
        shape = tuple(shapes[nm])
        if len(shape) != info.ndim:
            raise ValueError(
                f"{nm}: environment array has rank {len(shape)}, plan "
                f"references rank {info.ndim}")
        if info.kind == K_GATHER:
            prep[nm] = ArrayPrep((), (), (), (), 1, gather=True)
            in_specs.append(pl.BlockSpec(
                shape, _imap(tuple(range(len(shape))), {})))
            continue
        tperm = info.perm
        if tperm == tuple(range(len(shape))):
            tperm = ()
        else:
            shape = tuple(shape[i] for i in tperm)
        covered = info.levels
        flips, pads, sls, block_shape = [], [], [], []
        sb: dict = {}
        mir: dict = {}
        for ax, l in enumerate(covered):
            a = info.coefs[l]
            L = shape[ax]
            if info.signs[l] < 0:
                # mirrored-origin window: the per-call jnp.flip makes the
                # effective coefficient +|a| with offsets b' = L-1-b
                flips.append(ax)
                mir[l] = L - 1
                off_lo = (L - 1) - info.off_hi[l]
                off_hi = (L - 1) - info.off_lo[l]
            else:
                off_lo, off_hi = info.off_lo[l], info.off_hi[l]
            if l in blocks:
                abl = a * blocks[l]
                c_min = off_hi - abl - (a - 1)
                c_max = off_lo + abl
                if c_min > c_max:
                    knob = _knob(l, m, block_inner)
                    raise LoweringError(
                        (), f"{nm}: level-{l} halo spread "
                            f"{off_hi - off_lo} exceeds the input block "
                            f"size {abl}; raise {knob}")
                c = min(max(0, c_min), c_max)
                start = a * lo[l - 1] - abl + c
                length = (nb[l] + 2) * abl
                block_shape.append(abl)
                sb[l] = abl - c
            else:
                start = a * lo[l - 1] + off_lo
                length = a * (extents[l - 1] - 1) + (off_hi - off_lo) + 1
                block_shape.append(length)
                sb[l] = -off_lo
            left = max(0, -start)
            right = max(0, start + length - L)
            pads.append((left, right))
            sls.append(slice(start + left, start + left + length))
        blk = [l for l in covered if l in blocks]
        n_copies = 3 ** len(blk)
        expand = ()
        if len(covered) < m or lift:
            # a lower-rank operand would put a tiled level (or, under vmap,
            # the batch axis) in its last two block axes: blocks the TPU
            # refuses.  Give it a size-1 axis at every level it lacks, and a
            # 1-D nest a leading size-1 axis, so both are whole.  Every
            # window operand then spans levels 1..m in order.
            size = {l: sl.stop - sl.start for l, sl in zip(covered, sls)}
            bsize = dict(zip(covered, block_shape))
            covered = tuple(range(1, m + 1))
            expand = lead + tuple(size.get(l, 1) for l in covered)
            block_shape = list(lead) + [bsize.get(l, 1) for l in covered]
        prep[nm] = ArrayPrep(tperm, tuple(flips), tuple(pads), tuple(sls),
                             n_copies, expand=expand)
        slice_base[nm] = sb
        mirror[nm] = mir
        for ds in itertools.product((0, 1, 2), repeat=len(blk)):
            in_specs.append(pl.BlockSpec(
                tuple(block_shape), _imap(covered, dict(zip(blk, ds)), lead)))

    out_tile = tuple(blocks.get(l, extents[l - 1]) for l in range(1, m + 1))
    out_padded = tuple(nb[l] * blocks[l] if l in blocks else extents[l - 1]
                       for l in range(1, m + 1))
    out_shape = [jax.ShapeDtypeStruct(lead + out_padded, dt)
                 for _ in out_names]
    out_specs = [pl.BlockSpec(lead + out_tile,
                              _imap(tuple(range(1, m + 1)),
                                    {l: 0 for l in grid_levels}, lead))
                 for _ in out_names]

    out_axes = {}
    for st in plan.body:
        # transpose back from level-major to the output's own dim order:
        # output dim d carries level lhs.subs[d].s -> take level-major axis
        # s-1
        axes = tuple(s.s - 1 for s in st.lhs.subs)
        out_axes[st.lhs.name] = () if axes == tuple(range(m)) else axes

    return Layout(
        m=m, extents=extents, lo=lo, blocks=blocks, grid=grid,
        grid_pos=grid_pos, nb=nb, scalar_names=scalar_names,
        base_names=base_names, out_names=out_names, dt=dt, prep=prep,
        slice_base=slice_base, mirror=mirror, lift=lift,
        gather_names=frozenset(nm for nm in base_names
                               if analysis.arrays[nm].kind == K_GATHER),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        out_tile=out_tile, out_axes=out_axes)
