"""Sharded plan execution: the compiled executor under ``shard_map``.

This is the front door tying the partitioner (:mod:`repro.shard.partition`)
and the halo program (:mod:`repro.shard.halo`) to jax: :func:`compile_sharded`
takes a :class:`~repro.core.race.RaceResult` plus a device mesh, re-ranges the
plan's sharded levels to one chunk, runs RACE on the *local* program with the
global result's own knobs (so the per-shard plan is the same optimization the
single-device path would execute on a chunk-sized grid), compiles it through
the ordinary plan-keyed executor cache, and wraps its raw core in a
``shard_map`` whose in/out specs and device prologue come from the halo
program.  The whole dispatch — host slab layout, collective exchange, local
stencil — is jitted once per :class:`ShardedRace`.

Cache identity: sharded entries live in the *same* process-wide
:class:`~repro.core.executor.ExecutorCache` as single-device ones, but their
:class:`~repro.core.executor.ExecutorKey` carries the mesh axes + concrete
device ids, the partition spec, and the requested halo strategy, so a sharded
compile of a plan hash can never serve (or be served by) its single-device
twin.  The key holds the *requested* backend and halo strategy — resolution
(capability probe, bytes-over-bandwidth heuristic) happens inside the
builder; two requests that resolve identically cost one redundant entry,
which is cheaper than resolving before every cache probe.

Differentiation composes: ``ShardedRace`` installs a ``custom_vjp`` whose
backward mirrors :func:`repro.core.adjoint.backward` over the *global*
program's adjoint build, running each input's transposed plan through its own
:func:`compile_sharded` under the same mesh — the adjoint stencil's negated
offsets re-derive the partition with halos flowing the opposite way, no
special-casing.  An adjoint plan the partitioner refuses falls back to the
single-device executor for that input (recorded as a ``shard_adjoint_fallback``
event), and the usual autodiff gates (``RACE_ADJOINT=autodiff``, build
refusal) behave exactly as in the unsharded path.
"""
from __future__ import annotations

from typing import Mapping, Optional, Union

from repro import obs as _obs
from repro.core.ir import Loop, Program

from .halo import plan_halo
from .partition import plan_partition


class ShardingUnavailable(Exception):
    """The partitioner refused this (plan, mesh) pair.

    Carries the full :class:`~repro.shard.partition.PartitionPlan` so callers
    can inspect the structured :class:`ShardRefusal` reasons."""

    def __init__(self, partition):
        self.partition = partition
        self.refusals = partition.refusals
        super().__init__(partition.explain())


class ShardedRace:
    """One sharded specialization: jitted shard_map over the local executor.

    Mirrors :class:`~repro.core.executor.CompiledRace`'s contract — callable
    on any same-signature env, interior-convention outputs, ``trace_count``
    as the retrace detector — with the iteration box spatially partitioned
    over ``mesh`` per ``partition`` and halos transported per ``halo_prog``.
    """

    def __init__(self, result, mesh, partition, halo_prog, local_ex, *,
                 backend: Optional[str], halo: str, block_rows: int,
                 block_cols: int, block_inner: int, cache):
        import jax

        from repro.core.executor import plan_hash

        self.result = result
        self.mesh = mesh
        self.partition = partition
        self.halo_prog = halo_prog
        self.local = local_ex
        self.backend = local_ex.backend
        self.calls = 0
        self.trace_count = 0
        self._plan_h = plan_hash(result.plan)
        self._requested = dict(backend=backend, halo=halo,
                               block_rows=block_rows, block_cols=block_cols,
                               block_inner=block_inner)
        self._cache = cache
        self._adj_memo: dict = {}

        hp = halo_prog
        core = local_ex.core_fn

        def body(args):
            return core(hp.device_env(args))

        # check_vma=False: pallas_call (and our replicated tails) carry no
        # varying-manual-axes typing; correctness is carried by the
        # differential tests, not the checker
        shmapped = jax.shard_map(body, mesh=mesh, in_specs=(hp.in_specs,),
                                 out_specs=hp.out_specs, check_vma=False)

        def raw(env):
            return shmapped(hp.host_args(env))

        @jax.custom_vjp
        def vjp_core(env):
            return raw(env)

        def fwd(env):
            return raw(env), dict(env)

        def bwd(env, g):
            return (self._backward(env, g),)

        vjp_core.defvjp(fwd, bwd)
        self._vjp_core = vjp_core

        def _call(env):
            self.trace_count += 1  # python side effect: fires at trace only
            return vjp_core(env)

        self._jit = jax.jit(_call)

    # -- forward ------------------------------------------------------------

    def run(self, env: Mapping) -> dict:
        """Execute sharded; returns the same interior-convention outputs as
        the single-device ``run`` (local interiors concatenated along the
        assigned mesh axes)."""
        self.calls += 1
        env = dict(env)
        if not _obs.enabled():
            return self._jit(env)
        phase = "compile" if self.calls == 1 else "run"
        with _obs.span(phase, plan=self._plan_h, backend=self.backend,
                       sharded="1"):
            out = self._jit(env)
        hp = self.halo_prog
        _obs.counter("race_shard_runs_total", plan=self._plan_h,
                     strategy=hp.strategy).inc()
        if hp.strategy == "exchange":
            _obs.counter("race_shard_halo_bytes_total",
                         plan=self._plan_h).inc(float(hp.halo_bytes))
        else:
            _obs.counter("race_shard_restack_bytes_total",
                         plan=self._plan_h).inc(float(hp.restack_bytes))
        return out

    __call__ = run

    # -- backward -------------------------------------------------------------

    def _adjoint_executor(self, spec, adj_env):
        """Sharded executor for one input's adjoint plan, memoized per
        (input, adjoint signature); single-device fallback on refusal."""
        from repro.core.executor import compile_plan, env_signature

        sig = env_signature(adj_env)
        key = (spec.input, sig)
        ex = self._adj_memo.get(key)
        if ex is None:
            req = self._requested
            res = spec.result()
            try:
                ex = compile_sharded(
                    res, sig, self.mesh, halo=req["halo"],
                    backend=req["backend"], block_rows=req["block_rows"],
                    block_cols=req["block_cols"],
                    block_inner=req["block_inner"], cache=self._cache)
            except ShardingUnavailable as err:
                if _obs.enabled():
                    _obs.event("shard_adjoint_fallback", plan=self._plan_h,
                               input=spec.input,
                               reasons=[str(r) for r in err.refusals])
                ex = compile_plan(res.plan, sig, req["backend"],
                                  block_rows=req["block_rows"],
                                  block_cols=req["block_cols"],
                                  block_inner=req["block_inner"],
                                  cache=self._cache)
            self._adj_memo[key] = ex
        return ex

    def _backward(self, env: Mapping, g: Mapping) -> dict:
        """Mirror of :func:`repro.core.adjoint.backward` with each adjoint
        plan running under this executor's own mesh partition."""
        from repro.core import adjoint as adj

        program = self.result.program
        if adj.adjoint_mode() == "autodiff" or not adj.adjoint_build(
                program).ok:
            if _obs.enabled():
                _obs.counter("race_adjoint_backward_total",
                             mode="autodiff-sharded").inc()
            return adj._autodiff_backward(program, env, g)
        build = adj.adjoint_build(program)
        grads = {}
        with _obs.span("adjoint_backward", sharded="1"):
            for spec in build.specs:
                adj_env = adj.assemble_adjoint_env(spec, env, g)
                ex = self._adjoint_executor(spec, adj_env)
                val = ex(adj_env)[spec.gu]
                grads[spec.input] = adj.finalize_adjoint(spec, env, val)
        if _obs.enabled():
            _obs.counter("race_adjoint_backward_total",
                         mode="stencil-sharded").inc()
        return {k: (grads[k] if k in grads else adj._zero_cotangent(v))
                for k, v in env.items()}

    # -- introspection ------------------------------------------------------

    def cache_info(self) -> dict:
        hp = self.halo_prog
        return dict(backend=self.backend, calls=self.calls,
                    trace_count=self.trace_count, strategy=hp.strategy,
                    halo_bytes=hp.halo_bytes, restack_bytes=hp.restack_bytes,
                    partition=self.partition.key(),
                    mesh=self.partition.mesh_axes,
                    local=self.local.cache_info())

    def __repr__(self) -> str:  # pragma: no cover - debugging sugar
        return (f"<ShardedRace {self.backend} plan={self._plan_h} "
                f"partition={self.partition.key()} "
                f"strategy={self.halo_prog.strategy} calls={self.calls}>")


def _local_program(program: Program, partition) -> Program:
    """The global program with each assigned level re-ranged to one chunk."""
    chunks = {a.level: a.chunk for a in partition.assignments}
    loops = tuple(
        Loop(lp.level, lp.var, lp.lo, lp.lo + chunks[lp.level] - 1)
        if lp.level in chunks else lp
        for lp in program.loops)
    return Program(loops, program.body, program.loc)


#: RaceResult.options knobs forwarded to the local (per-chunk) RACE build,
#: so the per-shard plan is shaped exactly like the global one.  "tune" is
#: deliberately excluded: the local build must be deterministic — the sharded
#: executor is keyed on the *global* plan hash, and a tuner swapping the
#: local plan underneath would break that identity.
_LOCAL_RACE_KNOBS = ("reassociate", "esr", "contraction", "cost_model",
                     "rewrite_sub", "rewrite_div", "max_rounds",
                     "mis_exact_limit")


def compile_sharded(result, env: Union[Mapping, tuple], mesh, *,
                    halo: str = "auto", backend: Optional[str] = None,
                    block_rows: int = 8, block_cols: int = 8,
                    block_inner: int = 0, cache=None) -> ShardedRace:
    """Fetch (or build) the sharded executor for (result, env, mesh).

    Raises :class:`ShardingUnavailable` — carrying every structured
    :class:`~repro.shard.partition.ShardRefusal` — when no mesh axis can be
    placed on any grid level; never falls back silently.  ``env`` is an
    environment mapping or a precomputed ``env_signature``.  ``halo`` is one
    of :data:`~repro.shard.halo.HALO_STRATEGIES` (``"auto"`` resolves by the
    roofline heuristic).  The entry lives in the process-wide executor cache
    under a mesh/partition/halo-qualified key.
    """
    from repro.core.executor import (ExecutorKey, compile_plan,
                                     default_backend, device_context,
                                     env_signature, executor_cache,
                                     plan_hash)
    from repro.core.race import race

    sig = env if isinstance(env, tuple) else env_signature(env)
    ph = plan_hash(result.plan)
    partition = plan_partition(result.program, mesh)
    if not partition.ok:
        if _obs.enabled():
            for r in partition.refusals:
                _obs.counter("race_shard_refusals_total", code=r.code).inc()
            _obs.event("shard_refusal", plan=ph,
                       mesh=str(partition.mesh_axes),
                       reasons=[str(r) for r in partition.refusals])
        raise ShardingUnavailable(partition)

    c = cache if cache is not None else executor_cache()
    key = ExecutorKey(
        ph, sig, backend or default_backend(),
        (block_rows, block_cols, block_inner), False,
        device=device_context(),
        mesh=(partition.mesh_axes,
              tuple(int(d.id) for d in mesh.devices.flat)),
        partition=partition.key(), halo=halo)

    def _build() -> ShardedRace:
        with _obs.span("shard_plan", plan=ph):
            local_prog = _local_program(result.program, partition)
            race_kw = {k: result.options[k] for k in _LOCAL_RACE_KNOBS
                       if k in result.options}
            local_res = race(local_prog,
                             backend=result.options.get("backend"),
                             **race_kw)
            with _obs.span("halo_exchange", plan=ph):
                hp = plan_halo(partition, local_res.plan, sig, strategy=halo)
            local_sig = tuple(
                (nm, tuple(hp.specs[nm].local_shape), dt,
                 weak if hp.specs[nm].mode in ("scalar", "replicated")
                 else False)
                for nm, _shape, dt, weak in sig)
            local_ex = compile_plan(
                local_res.plan, local_sig, backend, block_rows=block_rows,
                block_cols=block_cols, block_inner=block_inner,
                donate=False, cache=c)
        if _obs.enabled():
            _obs.event("shard_plan", plan=ph,
                       local_plan=plan_hash(local_res.plan),
                       mesh=str(partition.mesh_axes),
                       partition=str(partition.key()),
                       strategy=hp.strategy, halo_bytes=hp.halo_bytes,
                       restack_bytes=hp.restack_bytes,
                       backend=local_ex.backend,
                       refusals=[str(r) for r in partition.refusals])
        return ShardedRace(result, mesh, partition, hp, local_ex,
                           backend=backend, halo=halo, block_rows=block_rows,
                           block_cols=block_cols, block_inner=block_inner,
                           cache=c)

    return c.get_or_build(key, _build)
