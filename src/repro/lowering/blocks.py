"""N-D BlockSpec / grid construction for the generic lowering engine.

The iteration space is laid out level-major over the plan it is given
(outermost loop level = axis 0).  An as-laid specialization gives it the plan
with its levels renumbered in the first output's dim order
(:func:`~repro.lowering.geometry.relabel_levels`), so the same rules tile
the output's own dims and its last dim takes the lanes.  Every level
except the innermost is grid-tiled — level 1 by ``block_rows``, levels
``2..m-1`` by ``block_cols`` — and the innermost level stays
full-width for the VPU lanes unless ``block_inner > 0`` tiles it too.  The
single level of a 1-D nest is its innermost level, so it follows the same
rule.  A 4-D nest gets a 3-axis grid (levels 1-3).

Every block satisfies the TPU's tiling rule, also under ``jax.vmap`` (which
adds a squeezed batch axis in front): the last block axis spans its whole
array axis (or ``block_inner``, a multiple of 128 on a TPU) and the one
before it is whole or a multiple of 8.  A window operand that misses a level
gets a size-1 axis there, so it has rank ``m``, and a 1-D nest is lifted to
rank 2 by a leading size-1 axis on its windows and outputs.

Each window-class array is one operand with one block per grid step: the
output tile widened by the array's own ``[off_lo, off_hi]`` envelope on
every grid-tiled level, so neighbouring steps' windows overlap by the halo.
The per-call prep cuts the array to start at the envelope's low end
(``a·lo + off_lo``), so the window of grid step ``p`` starts at element
``p·|a|·tile`` and a reference with offset ``b`` reads window element
``b - off_lo``.  Its width is ``|a|·tile + spread`` rounded up to the axis'
alignment (:func:`_align`): 1 on a leading axis, 8 on the second-minor axis,
128 on a tiled lane axis.  Mosaic (jax 0.9, TPU v5e) takes such windows as
``pl.Element`` block dims on all axes or on none; on the last two axes the
element offset must be provably a multiple of the (8, 128) tiling and the
size a multiple too, while a leading axis takes any offset and size.  An
array with no halo on any tiled level takes the bare tile by block index.
Unblocked levels carry the whole envelope as one full-width block.
Mirrored-origin references (negative coefficients) are flipped per call,
so their normalized offsets ``b' = L-1-b`` need no more than any other.

Gather-class arrays bypass the window machinery entirely: the whole
(untransposed, unpadded) array is one BlockSpec whose index map pins block
(0, ..., 0); ``repro.lowering.gather`` indexes it in-kernel.
"""
from __future__ import annotations

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


def _align(l: int, m: int) -> int:
    """The multiple a window's size and start keep along level ``l``: the
    TPU tiles a block's last two axes by (8, 128) and checks that an element
    offset there is provably a multiple of the tiling."""
    if l == m:
        return 128
    if l == m - 1:
        return 8
    return 1


def _knob(l: int, m: int, block_inner: int) -> str:
    if l == m and block_inner:
        return "block_inner"
    if l == 1:
        return "block_rows"
    return "block_cols"


@dataclass
class ArrayPrep:
    """Per-call data movement for one base array (static amounts)."""

    tperm: tuple  # transpose into the kernel's axis order, or () if identity
    flips: tuple  # post-transpose axes to jnp.flip (mirrored-origin levels)
    pads: tuple  # per-axis (left, right) zero pad
    sls: tuple  # per-axis window slice after padding
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
    out_axes: dict  # out name -> transpose to its own dim order, or ()
    read_factor: dict  # base name -> elements fetched / elements it reads


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

    def _imap(covered, step, lead=()):
        # index map: a grid-tiled level moves by ``step[l]`` per grid id (1
        # for a block index, the window's stride for an element offset);
        # every other axis is one whole block at 0
        def imap(*pids):
            return (0,) * len(lead) + tuple(
                pids[grid_pos[l]] * step[l] if l in step else 0
                for l in covered)
        return imap

    prep: dict = {}
    slice_base: dict = {}
    mirror: dict = {}
    read_factor: dict = {}
    for nm in base_names:
        info = analysis.arrays[nm]
        shape = tuple(shapes[nm])
        if len(shape) != info.ndim:
            raise ValueError(
                f"{nm}: environment array has rank {len(shape)}, plan "
                f"references rank {info.ndim}")
        if info.kind == K_GATHER:
            prep[nm] = ArrayPrep((), (), (), (), gather=True)
            in_specs.append(pl.BlockSpec(
                shape, _imap(tuple(range(len(shape))), {})))
            read_factor[nm] = 1.0  # fetched whole, once
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
        stride: dict = {}  # blocked level -> window start per grid step
        halo = False  # some blocked level's window overlaps its neighbour's
        region = 1  # elements of the region the operand reads
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
            # the prepared operand starts at the envelope's low end, so a
            # reference reads window element b - off_lo (+ a·k in the tile)
            start = a * lo[l - 1] + off_lo
            span = a * (extents[l - 1] - 1) + (off_hi - off_lo) + 1
            region *= span
            if l in blocks:
                abl = a * blocks[l]
                need = abl - (a - 1) + (off_hi - off_lo)
                if need > 3 * abl:
                    knob = _knob(l, m, block_inner)
                    raise LoweringError(
                        (), f"{nm}: level-{l} halo spread "
                            f"{off_hi - off_lo} exceeds the input block "
                            f"size {abl}; raise {knob}")
                align = _align(l, m)
                width = -(-max(need, abl) // align) * align
                length = (nb[l] - 1) * abl + width
                stride[l] = abl
                halo = halo or width != abl
            else:
                width = length = span
            block_shape.append(width)
            sb[l] = -off_lo
            left = max(0, -start)
            right = max(0, start + length - L)
            pads.append((left, right))
            sls.append(slice(start + left, start + left + length))
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
                             expand=expand)
        slice_base[nm] = sb
        mirror[nm] = mir
        # the TPU skips a block's copy while its index stays unchanged, so
        # the window is fetched once per step of the grid axes up to the
        # innermost one it moves along
        moves = [grid_pos[l] for l in stride]
        fetches = int(np.prod(grid[:max(moves) + 1])) if moves else 1
        read_factor[nm] = fetches * int(np.prod(block_shape)) / region
        if not halo:
            # no halo on any tiled level: the bare tile, by block index
            in_specs.append(pl.BlockSpec(
                tuple(block_shape),
                _imap(covered, dict.fromkeys(stride, 1), lead)))
        else:
            # one overlapping window: element offsets on every axis
            in_specs.append(pl.BlockSpec(
                tuple(pl.Element(d) for d in block_shape),
                _imap(covered, stride, lead)))

    out_tile = tuple(blocks.get(l, extents[l - 1]) for l in range(1, m + 1))
    out_padded = tuple(nb[l] * blocks[l] if l in blocks else extents[l - 1]
                       for l in range(1, m + 1))
    out_shape = [jax.ShapeDtypeStruct(lead + out_padded, dt)
                 for _ in out_names]
    out_specs = [pl.BlockSpec(lead + out_tile,
                              _imap(tuple(range(1, m + 1)),
                                    dict.fromkeys(grid_levels, 1), lead))
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
        out_tile=out_tile, out_axes=out_axes, read_factor=read_factor)
