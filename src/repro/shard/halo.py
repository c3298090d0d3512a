"""Halo transport for sharded plan execution, sized by offset envelopes.

Given a :class:`~repro.shard.partition.PartitionPlan` and the *local* plan
(the global plan re-ranged to one chunk), this module builds a
:class:`HaloProgram`: the host-side argument layout, ``shard_map``
in/out ``PartitionSpec``s, and the device-side prologue that turns the
sharded arguments into exactly the env the local compiled executor reads.
Every slab is sized by the per-array *program* offset envelopes
(:func:`repro.lowering.geometry.program_envelopes` — the influencing reach
of any plan derived from the program, see :mod:`repro.shard.partition`)
and nothing else: the right-halo along a sharded dim is
``t = max(0, lo + off_hi)`` for *that array*, so a 3-point stencil ships
one plane while a 5-point one ships two, per array, never a worst-case
union.

Two transport strategies produce bit-identical local slabs:

* ``"exchange"`` — each slab operand crosses the ``shard_map`` boundary in
  one of two in-layouts, chosen from its shape alone
  (:attr:`HaloProgram.in_layout`):

  - ``"shard"``, the array as it lies: the whole array goes in sharded
    over its full extent ``N`` along each sharded dim, so shard ``p`` holds
    ``u[p·S : p·S + S]`` with ``S = N / shards``.  Its slab
    ``u[p·e : p·e + e + t]`` misses ``L_p = p·(S − e)`` planes on the left
    and ``R_p = e + t − S − p·(S − e)`` on the right.  Per sharded dim, in
    ascending order, every shard fetches its left neighbour's last
    ``kL = max_p L_p`` planes and its right neighbour's first
    ``kR = max_p R_p`` planes (one ``lax.ppermute`` each, both clipped at
    0) and concatenates ``[left, own, right]``; one ``dynamic_slice``
    then cuts the slab at ``kL − L_p`` along every dim.  It engages when,
    along every sharded dim, ``shards`` divides ``N`` and ``kL, kR <= S``
    (the slab lies inside the shard and its two neighbours;
    ``shard-halo-exceeds-chunk`` ensures it whenever ``N = E + t``).
    Data sharded as it is laid out, such as a ghost-extended grid split
    over the mesh, then needs no reshard: only edge planes cross the
    links.
  - ``"core_tail"``, for every other shape (MG over a 4-way axis:
    ``N = E + 2`` is not a multiple of 4 when ``E`` is): the core region
    ``u[0:E]`` is sharded in chunks of ``e``; per halo dim the device
    fetches its right neighbour's leading ``t``-slab via ``lax.ppermute``
    and concatenates.  The last shard's halo is the global tail
    ``u[E:E+t]``, passed replicated, one block per subset ``S`` of haloed
    dims (dims in ``S`` carry the global tail, the others the sharded
    core).  XLA reshards the array into the core's chunks at the boundary.

  In both, each dim's exchange works on the block already extended along
  the dims before it, so the planes it sends carry the neighbours' edges
  along those dims: corners arrive shape-consistent without dedicated
  corner sends.
* ``"recompute"`` — the array crosses the boundary *replicated* (``P()``,
  in-layout ``"replicated"``) and each device carves its own
  overlap-extended slab with ``lax.dynamic_slice`` at
  ``lax.axis_index * chunk``.  No collectives, but every device pulls the
  full global array through memory each call.  (An earlier formulation
  pre-stacked overlapping slabs on the host; XLA's SPMD partitioner
  miscompiles that stack-of-overlapping-slices when it is fused into the
  same jit as the ``shard_map`` consumer — each slab arrived doubled — so
  the slicing lives device-side on purpose.)

``"auto"`` picks by a bytes-over-bandwidth roofline using the chip's
published peaks (:func:`repro.launch.mesh.chip_peaks`, keyed by device
kind; an unlisted TPU kind raises): exchange moves its halo bytes over one
ICI link, recompute pulls one full replicated copy per device through HBM.
Auxiliary-array halo *flops* do not enter the comparison: both strategies
hand the executor the same envelope-extended slab and recompute aux values
over it locally, so that work is identical and cancels.  On the CPU
backend, which has neither link, ``"auto"`` is always ``"exchange"`` — the
strategy that exercises the collectives.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from repro.core.codegen import required_shapes
from repro.launch.mesh import chip_peaks
from repro.lowering.geometry import K_WINDOW, analyze_program

HALO_STRATEGIES = ("auto", "exchange", "recompute")

#: device-trace names (op metadata, no op of their own): the core and tail
#: slicing of ``HaloProgram.host_args``, with the reshard into the
#: ``shard_map`` in-specs that it causes, sits in ``SHARD_IN`` (empty for
#: the ``"shard"`` in-layout); the exchange or recompute slice of
#: ``HaloProgram.device_env`` in ``HALO``
SHARD_IN = "race_shard_in"
HALO = "race_halo"

#: ArraySpec.mode values
M_SLAB = "slab"  # sliced along >=1 sharded dim, halo-extended
M_REPLICATED = "replicated"  # passed whole to every shard
M_CANVAS = "canvas"  # output-only: synthesized as device-side zeros
M_SCALAR = "scalar"  # rank-0 passthrough

#: HaloProgram.in_layout values: how a slab operand crosses the boundary
L_SHARD = "shard"  # whole array, sharded over its full extent (as laid)
L_CORE_TAIL = "core_tail"  # core in chunks of e plus replicated tails
L_REPLICATED = "replicated"  # recompute: whole array on every device


@dataclass(frozen=True)
class SlabDim:
    """One sharded dim of one array."""

    dim: int  # array dim index
    level: int
    mesh_axis: str
    shards: int
    chunk: int  # e: core elements per shard
    extent: int  # E: global core extent (shards * chunk)
    halo: int  # t: this array's right-halo width along this dim


@dataclass(frozen=True)
class ArraySpec:
    """How one env entry crosses the shard_map boundary."""

    name: str
    mode: str
    shape: tuple  # global shape from the env signature ((), scalar)
    dtype: str
    slabs: tuple = ()  # SlabDim ascending by dim (slab mode only)
    local_shape: tuple = ()  # what the local executor sees


def _edges(sd: SlabDim, n: int) -> tuple:
    """``(S, kL, kR)`` of the ``"shard"`` in-layout along one sharded dim of
    global extent ``n``: the shard size, and the planes every shard fetches
    from its left and right neighbour (the largest ``L_p``, ``R_p``)."""
    size = n // sd.shards
    step = size - sd.chunk  # L_p = p * step
    k_left = max(0, (sd.shards - 1) * step)
    k_right = max(0, sd.chunk + sd.halo - size)  # R_p is largest at p = 0
    return size, k_left, k_right


def _as_laid(spec: ArraySpec) -> bool:
    """The ``"shard"`` in-layout's engagement rule (module docstring)."""
    for sd in spec.slabs:
        n = spec.shape[sd.dim]
        size, k_left, k_right = _edges(sd, n)
        if n % sd.shards or k_left > size or k_right > size:
            return False
    return True


def _subset_key(s: frozenset) -> str:
    """Canonical pytree key of a halo-dim subset (dict keys must sort)."""
    return "t" + "_".join(str(d) for d in sorted(s)) if s else "core"


def _subsets(dims: tuple) -> list:
    out = [frozenset()]
    for d in dims:
        out += [s | {d} for s in out]
    return out


class HaloProgram:
    """Static halo plan: host layout + device prologue for one partition."""

    def __init__(self, partition, local_plan, env_sig, strategy: str = "auto"):
        if strategy not in HALO_STRATEGIES:
            raise ValueError(
                f"halo strategy {strategy!r} not in {HALO_STRATEGIES}")
        self.partition = partition
        self.local_plan = local_plan
        # program-level geometry: the influencing reach (see partition.py);
        # the local program's envelopes equal the global ones — re-ranging
        # loops changes no reference offsets
        analysis = analyze_program(local_plan.program)
        assert analysis.eligible, "partition accepted an ineligible program"
        by_level = partition.by_level
        out_names = [st.lhs.name for st in local_plan.body]
        read = set(analysis.arrays)
        local_req = required_shapes(local_plan.program)

        specs = {}
        for nm, shape, dtype, _weak in env_sig:
            if not shape:
                specs[nm] = ArraySpec(nm, M_SCALAR, shape, dtype)
                continue
            info = analysis.arrays.get(nm)
            slabs = []
            if info is not None and info.kind == K_WINDOW:
                for d, level in enumerate(info.dims):
                    a = by_level.get(level)
                    if a is None:
                        continue
                    t = max(0, a.lo + info.off_hi[level])
                    slabs.append(SlabDim(d, level, a.mesh_axis, a.shards,
                                         a.chunk, a.extent, t))
            if slabs:
                local = list(shape)
                for sd in slabs:
                    local[sd.dim] = sd.chunk + sd.halo
                specs[nm] = ArraySpec(nm, M_SLAB, shape, dtype,
                                      tuple(slabs), tuple(local))
            elif nm in read:
                specs[nm] = ArraySpec(nm, M_REPLICATED, shape, dtype,
                                      local_shape=shape)
            elif nm in out_names:
                specs[nm] = ArraySpec(nm, M_CANVAS, shape, dtype,
                                      local_shape=tuple(local_req[nm]))
            else:  # unreferenced extra env entry: hand it through whole
                specs[nm] = ArraySpec(nm, M_REPLICATED, shape, dtype,
                                      local_shape=shape)
        self.specs = specs

        n_devices = 1
        for _, size in partition.mesh_axes:
            n_devices *= size
        slabs = [s for s in specs.values() if s.mode == M_SLAB]
        as_laid = {s.name: _as_laid(s) for s in slabs}
        self.halo_bytes = sum(
            self._exchange_bytes(s, n_devices, as_laid[s.name])
            for s in slabs)
        self.restack_bytes = sum(
            self._restack_bytes(s, n_devices) for s in slabs)
        if strategy == "auto":
            strategy = self._auto_strategy()
        self.strategy = strategy
        #: slab operand -> in-layout (L_SHARD, L_CORE_TAIL or L_REPLICATED)
        self.in_layout = {
            nm: (L_REPLICATED if strategy == "recompute"
                 else L_SHARD if laid else L_CORE_TAIL)
            for nm, laid in as_laid.items()}

        # shard_map out_specs: local interiors concatenate along each
        # assigned mesh axis back into the global interior
        from jax.sharding import PartitionSpec as P

        self.out_specs = {}
        self.out_local_extent = {}
        ranges = local_plan.program.ranges()
        for st in local_plan.body:
            axes = []
            ext = []
            for s in st.lhs.subs:
                a = by_level.get(s.s)
                axes.append(a.mesh_axis if a is not None else None)
                lo, hi = ranges[s.s]
                ext.append(hi - lo + 1)
            self.out_specs[st.lhs.name] = P(*axes)
            self.out_local_extent[st.lhs.name] = tuple(ext)
        self.in_specs = {nm: self._in_spec(s) for nm, s in specs.items()
                         if s.mode != M_CANVAS}

    def _auto_strategy(self) -> str:
        import jax

        dev = jax.devices()[0]
        if dev.platform == "cpu":
            return "exchange"
        peaks = chip_peaks(dev.device_kind)
        return ("exchange"
                if self.halo_bytes / peaks["ici_bw_per_link"]
                <= self.restack_bytes / peaks["hbm_bw"] else "recompute")

    # -- static accounting ----------------------------------------------------

    @staticmethod
    def _halo_dims(spec: ArraySpec) -> tuple:
        return tuple(sd.dim for sd in spec.slabs if sd.halo > 0)

    def _exchange_bytes(self, spec: ArraySpec, n_devices: int,
                        as_laid: bool) -> int:
        """ppermute payload per call, summed over every device (mirrors the
        device algorithms in :meth:`_device_edges` and
        :meth:`_device_exchange` exactly)."""
        import numpy as np

        count = self._edge_elements if as_laid else self._tail_elements
        return np.dtype(spec.dtype).itemsize * count(spec, n_devices)

    @staticmethod
    def _edge_elements(spec: ArraySpec, n_devices: int) -> int:
        """``"shard"``: along each sharded dim in turn, ``kL + kR`` planes
        of the block extended (``kL + S + kR``) along the dims before
        it."""
        block = list(spec.shape)
        for sd in spec.slabs:
            block[sd.dim] = spec.shape[sd.dim] // sd.shards
        total = 0
        for sd in spec.slabs:
            size, k_left, k_right = _edges(sd, spec.shape[sd.dim])
            plane = 1
            for d, n in enumerate(block):
                if d != sd.dim:
                    plane *= n
            # as in _tail_elements, each way
            total += ((k_left + k_right) * plane
                      * (n_devices // sd.shards) * (sd.shards - 1))
            block[sd.dim] = k_left + size + k_right
        return total

    @classmethod
    def _tail_elements(cls, spec: ArraySpec, n_devices: int) -> int:
        """``"core_tail"``: along each halo dim in turn, the ``t``-slab of
        every block still to be extended along the later halo dims."""
        by_dim = {sd.dim: sd for sd in spec.slabs}
        halo_dims = cls._halo_dims(spec)
        total = 0
        for i_pos, i in enumerate(halo_dims):
            sd_i = by_dim[i]
            if sd_i.shards <= 1:
                continue
            for s in _subsets(halo_dims[i_pos + 1:]):
                size = 1
                for d, n in enumerate(spec.shape):
                    sd = by_dim.get(d)
                    if sd is None:
                        size *= n
                    elif d == i or d in s:
                        size *= sd.halo
                    elif d in halo_dims[:i_pos]:
                        size *= sd.chunk + sd.halo  # already extended
                    else:
                        size *= sd.chunk
                # one ppermute along axis i per combination of the other
                # mesh coordinates; (shards - 1) senders each
                total += size * (n_devices // sd_i.shards) * (sd_i.shards - 1)
        return total

    def _restack_bytes(self, spec: ArraySpec, n_devices: int) -> int:
        """Memory traffic per call under recompute: every device reads the
        full replicated array to carve its slab."""
        import numpy as np

        size = np.dtype(spec.dtype).itemsize
        for n in spec.shape:
            size *= n
        return size * n_devices

    # -- shard_map specs --------------------------------------------------

    def _in_spec(self, spec: ArraySpec):
        from jax.sharding import PartitionSpec as P

        if spec.mode in (M_SCALAR, M_REPLICATED):
            return P()
        by_dim = {sd.dim: sd for sd in spec.slabs}
        layout = self.in_layout[spec.name]
        if layout == L_REPLICATED:
            return P()  # devices slice their own slab
        if layout == L_SHARD:  # the whole array, over its full extent
            return P(*(by_dim[d].mesh_axis if d in by_dim else None
                       for d in range(len(spec.shape))))
        halo_dims = self._halo_dims(spec)
        out = {}
        for s in _subsets(halo_dims):
            axes = []
            for d in range(len(spec.shape)):
                sd = by_dim.get(d)
                sharded = sd is not None and d not in s
                axes.append(sd.mesh_axis if sharded else None)
            out[_subset_key(s)] = P(*axes)
        return out

    # -- host side ---------------------------------------------------------

    def host_args(self, env) -> dict:
        """Pre-shard_map argument pytree (traceable; runs under the outer
        jit).  Canvas entries never cross the boundary."""
        import jax
        import jax.numpy as jnp

        args = {}
        with jax.named_scope(SHARD_IN):
            for nm, spec in self.specs.items():
                if spec.mode == M_CANVAS:
                    continue
                if spec.mode in (M_SCALAR, M_REPLICATED):
                    args[nm] = jnp.asarray(env[nm])
                    continue
                arr = jnp.asarray(env[nm])
                if self.in_layout[nm] == L_CORE_TAIL:
                    args[nm] = self._host_blocks(arr, spec)
                else:  # whole: as laid, or replicated to slice device-side
                    args[nm] = arr
        return args

    def _host_blocks(self, arr, spec: ArraySpec) -> dict:
        by_dim = {sd.dim: sd for sd in spec.slabs}
        out = {}
        for s in _subsets(self._halo_dims(spec)):
            sl = []
            for d in range(len(spec.shape)):
                sd = by_dim.get(d)
                if sd is None:
                    sl.append(slice(None))
                elif d in s:
                    sl.append(slice(sd.extent, sd.extent + sd.halo))
                else:
                    sl.append(slice(0, sd.extent))
            out[_subset_key(s)] = arr[tuple(sl)]
        return out

    # -- device side ---------------------------------------------------------

    def device_env(self, args) -> dict:
        """Runs *inside* shard_map: assemble the local executor env."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        env = {}
        for nm, spec in self.specs.items():
            if spec.mode == M_CANVAS:
                env[nm] = jnp.zeros(spec.local_shape, np.dtype(spec.dtype))
            elif spec.mode in (M_SCALAR, M_REPLICATED):
                env[nm] = args[nm]
            else:
                carve = {L_SHARD: self._device_edges,
                         L_CORE_TAIL: self._device_exchange,
                         L_REPLICATED: self._device_slice}[
                             self.in_layout[nm]]
                with jax.named_scope(HALO):
                    env[nm] = carve(args[nm], spec)
        return env

    @staticmethod
    def _device_slice(x, spec: ArraySpec):
        """Recompute prologue: carve this shard's overlap-extended slab out
        of the replicated global array.  The slab ``[p*e : p*e + e + t]``
        always ends inside the array (the last shard's end, ``E + t``, is
        exactly the global required extent), so dynamic_slice never clamps."""
        from jax import lax

        for sd in spec.slabs:
            start = lax.axis_index(sd.mesh_axis) * sd.chunk
            x = lax.dynamic_slice_in_dim(x, start, sd.chunk + sd.halo,
                                         axis=sd.dim)
        return x

    @staticmethod
    def _device_edges(x, spec: ArraySpec):
        """``"shard"`` prologue: extend this shard by its neighbours' edge
        planes, one sharded dim at a time, then cut the slab out of the
        extended block with one ``dynamic_slice``.  Along each dim the
        shard sits at ``kL`` in ``[left, own, right]`` and the slab starts
        ``L_p = p·(S − e)`` planes left of it.  A later dim's edge planes
        are cut from the block extended along the earlier dims (uncut, so
        ``kL + S + kR`` long there); a single cut at the end lets XLA fuse
        the joins and the cut into one fusion.  That fusion still writes
        the slab whole, beside the level-major swap before it and the
        kernel's pad after it (NPB MG class D on a v5e 2x2: seven
        whole-shard ops per module besides the kernel)."""
        import jax.numpy as jnp
        from jax import lax

        starts = [jnp.int32(0)] * x.ndim  # axis_index is int32
        sizes = list(x.shape)
        for sd in spec.slabs:
            size, k_left, k_right = _edges(sd, spec.shape[sd.dim])
            parts = [x]
            if k_left:  # left neighbour's last kL planes, sent rightwards
                edge = lax.slice_in_dim(x, size - k_left, size, axis=sd.dim)
                perm = [(r, r + 1) for r in range(sd.shards - 1)]
                parts.insert(0, lax.ppermute(edge, sd.mesh_axis, perm))
            if k_right:  # right neighbour's first kR planes, sent leftwards
                edge = lax.slice_in_dim(x, 0, k_right, axis=sd.dim)
                perm = [(r, r - 1) for r in range(1, sd.shards)]
                parts.append(lax.ppermute(edge, sd.mesh_axis, perm))
            if len(parts) > 1:
                x = jnp.concatenate(parts, axis=sd.dim)
            step = size - sd.chunk
            starts[sd.dim] = k_left - lax.axis_index(sd.mesh_axis) * step
            sizes[sd.dim] = sd.chunk + sd.halo
        return lax.dynamic_slice(x, starts, sizes)

    def _device_exchange(self, blocks: dict, spec: ArraySpec):
        import jax.numpy as jnp
        from jax import lax

        by_dim = {sd.dim: sd for sd in spec.slabs}
        halo_dims = self._halo_dims(spec)
        cur = {frozenset(): blocks["core"]}
        for s in _subsets(halo_dims):
            if s:
                cur[s] = blocks[_subset_key(s)]
        for i_pos, i in enumerate(halo_dims):
            sd = by_dim[i]
            perm = [(r, r - 1) for r in range(1, sd.shards)]
            idx = lax.axis_index(sd.mesh_axis)
            # only blocks still to be extended along later dims are live
            for s in _subsets(halo_dims[i_pos + 1:]):
                blk = cur[s]
                lead = lax.slice_in_dim(blk, 0, sd.halo, axis=sd.dim)
                shifted = lax.ppermute(lead, sd.mesh_axis, perm)
                tail = cur[s | {i}]
                halo = jnp.where(idx == sd.shards - 1, tail, shifted)
                cur[s] = jnp.concatenate([blk, halo], axis=sd.dim)
        return cur[frozenset()]


def plan_halo(partition, local_plan, env_sig,
              strategy: str = "auto") -> HaloProgram:
    """Build the halo program for one (partition, local plan, signature)."""
    return HaloProgram(partition, local_plan, env_sig, strategy)
