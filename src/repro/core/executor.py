"""Plan-keyed compiled executor cache: hash-specialized jit reuse.

RACE's detection hashes expression structure to expose *computation* reuse
inside one program (paper Section 5).  This module applies the same idea one
level up, to the serving runtime itself: a canonical structural hash over the
executable :class:`~repro.core.depgraph.Plan` becomes the key of a
process-wide compiled-executor cache, so the reuse pattern of steady-state
serving — the same stencil executed again and again on same-shaped data —
pays trace, compile, and host-side prep costs exactly once.

Layers:

  * :func:`plan_fingerprint` / :func:`plan_hash` — canonical serialization of
    a plan's executable structure (loop ranges, statements, auxiliary
    definitions; loop *variable names* are cosmetic and excluded), memoized
    on the plan instance;
  * :class:`CompiledRace` — one specialization per ``(plan hash, env
    signature, backend, block config, iteration order)``: the XLA evaluator
    path jitted (the pre-PR-3 ``RaceResult.run`` re-jitted on *every*
    call), or the Pallas
    path specialized once against the dimension-generic lowering engine's
    :class:`~repro.lowering.LoweredStencil` artifact
    (:func:`repro.lowering.specialize_stencil`) with a jitted per-call data
    path; optional
    ``donate_argnums`` output-buffer reuse; a lazily-built ``jax.vmap``
    batch variant for throughput serving (:meth:`CompiledRace.run_batch`);
  * :class:`ExecutorCache` — thread-safe process-wide LRU with hit/miss/
    eviction stats; :func:`compile_plan` is the front door every consumer
    (``RaceResult.run``, the ``@race_kernel`` frontend, the differential
    harness, the serve runtime, the benchmark) goes through.

Zero-retrace guarantee: a second ``run()`` with the same signature is a
cache hit returning the *same* ``CompiledRace``, whose jitted callable hits
the jax jit cache — ``CompiledRace.trace_count`` (incremented only while
tracing) stays at 1; tests assert this on both backends.
"""
from __future__ import annotations

import hashlib
import os
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from fractions import Fraction
from typing import Callable, Mapping, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs as _obs
from repro.lowering.geometry import ORDER_AS_LAID

from .backend import BACKENDS, Selection, select_backend
from .depgraph import Plan
from .ir import Const, Expr, FuncName, Node, Program, Ref

#: env knobs for the serving layer (documented in README):
#:   RACE_EXECUTOR_CACHE_SIZE — LRU capacity of the process-wide cache;
#:   RACE_BACKEND             — default backend when a caller doesn't pick one.
ENV_CACHE_SIZE = "RACE_EXECUTOR_CACHE_SIZE"
ENV_BACKEND = "RACE_BACKEND"


def _env_cache_size(default: int = 128) -> int:
    raw = os.environ.get(ENV_CACHE_SIZE, "").strip()
    if not raw:
        return default
    try:
        size = int(raw)
    except ValueError:
        raise ValueError(
            f"{ENV_CACHE_SIZE}={raw!r} is not an integer") from None
    if size < 1:
        raise ValueError(f"{ENV_CACHE_SIZE} must be >= 1, got {size}")
    return size


def default_backend() -> str:
    """The backend used when no caller picks one: ``$RACE_BACKEND`` or
    ``"auto"``.  An unknown value raises rather than silently degrading."""
    b = os.environ.get(ENV_BACKEND, "").strip() or "auto"
    if b not in BACKENDS:
        raise ValueError(
            f"{ENV_BACKEND}={b!r} is not one of {BACKENDS}")
    return b

# ---------------------------------------------------------------------------
# canonical structural hash over plans
# ---------------------------------------------------------------------------


def _tok(e: Expr) -> tuple:
    """Canonical token tree of an expression (hash-stable across processes)."""
    if isinstance(e, Ref):
        return ("ref", e.name, tuple(
            (s.a, s.s, Fraction(s.b).numerator, Fraction(s.b).denominator)
            for s in e.subs))
    if isinstance(e, Const):
        return ("const", repr(float(e.val)))
    if isinstance(e, FuncName):
        return ("func", e.name)
    if isinstance(e, Node):
        return ("node", e.op) + tuple(_tok(k) for k in e.kids)
    raise TypeError(f"unknown expression node {e!r}")


def plan_fingerprint(plan: Plan) -> tuple:
    """Canonical nested-tuple serialization of a plan's executable structure.

    Covers exactly what the compiled artifact depends on: loop levels and
    ranges, the post-contraction main statements, and every materialized
    auxiliary (definition expression, levels, propagated ranges) in emission
    order.  Loop variable names are excluded — two plans differing only in
    spelling produce identical executables and must share a cache entry.
    """
    prog = plan.program
    return (
        "race-plan-v1",
        tuple((l.level, l.lo, l.hi) for l in prog.loops),
        tuple((_tok(st.lhs), _tok(st.rhs)) for st in plan.body),
        tuple((a.name, tuple(a.levels), _tok(plan.aux_exprs[a.name]),
               tuple(sorted(plan.ranges[a.name].items())))
              for a in plan.aux_order),
        tuple(sorted(plan.local)),
    )


def plan_hash(plan: Plan) -> str:
    """16-hex-digit structural hash of a plan, memoized on the instance."""
    h = getattr(plan, "_structural_hash", None)
    if h is None:
        h = hashlib.sha256(
            repr(plan_fingerprint(plan)).encode()).hexdigest()[:16]
        plan._structural_hash = h
    return h


def program_fingerprint(prog: Program) -> tuple:
    """Canonical serialization of an *untransformed* program: loop levels and
    ranges plus the statement expressions, loop variable names excluded.
    This is the identity the autotuner keys on — it must be stable *before*
    any reassociation level is chosen, since the level is one of the knobs
    being tuned (``plan_fingerprint`` already bakes the chosen plan in)."""
    return (
        "race-program-v1",
        tuple((l.level, l.lo, l.hi) for l in prog.loops),
        tuple((_tok(st.lhs), _tok(st.rhs)) for st in prog.body),
    )


def program_hash(prog: Program) -> str:
    """16-hex-digit structural hash of a program, memoized on the instance."""
    h = getattr(prog, "_structural_hash", None)
    if h is None:
        h = hashlib.sha256(
            repr(program_fingerprint(prog)).encode()).hexdigest()[:16]
        object.__setattr__(prog, "_structural_hash", h)
    return h


# ---------------------------------------------------------------------------
# environment signatures
# ---------------------------------------------------------------------------


def dtype_of(v) -> np.dtype:
    """Signature dtype of an env entry (no array-data copies)."""
    dt = getattr(v, "dtype", None)
    return np.dtype(dt) if dt is not None else np.asarray(v).dtype


def _dtype_name(v) -> str:
    return dtype_of(v).name


def _is_weak(v) -> bool:
    """jax weak-type flag of an env entry: weak and strong scalars of the
    same dtype trace differently under jit, so the flag must be in the key
    (or mixing them would silently retrace a cached executor)."""
    wt = getattr(v, "weak_type", None)
    if wt is not None:
        return bool(wt)
    return (isinstance(v, (bool, int, float, complex))
            and not isinstance(v, np.generic))


#: python scalar types whose signature is value-independent (ints are not:
#: an out-of-range int falls back to the generic path)
_PY_SCALAR_SIG = {bool: "bool", float: "float64", complex: "complex128"}

#: dtype -> .name memo: ``np.dtype.name`` is a *computed* string property,
#: too slow for the per-request serving path
_DTYPE_NAMES: dict = {}


def _dt_name(dt) -> str:
    name = _DTYPE_NAMES.get(dt)
    if name is None:
        name = _DTYPE_NAMES[dt] = np.dtype(dt).name
    return name


def env_signature(env: Mapping) -> tuple:
    """``((name, shape, dtype, weak_type), ...)`` sorted by name — the
    shapes/dtypes half of the executor key.  Cheap: never copies data.

    This sits on the per-request serving path, so the common entry kinds —
    numpy arrays/scalars, jax arrays, plain python scalars — are resolved
    from type checks and attributes alone: no ``np.asarray`` round trips,
    no computed ``dtype.name`` property calls."""
    out = []
    for nm in sorted(env):
        v = env[nm]
        tv = type(v)
        if tv is np.ndarray:
            out.append((nm, v.shape, _dt_name(v.dtype), False))
            continue
        name = _PY_SCALAR_SIG.get(tv)
        if name is not None:
            out.append((nm, (), name, True))
            continue
        shape = getattr(v, "shape", None)
        dt = getattr(v, "dtype", None)
        if shape is not None and dt is not None:
            out.append((nm, tuple(shape), _dt_name(dt),
                        bool(getattr(v, "weak_type", False))))
            continue
        out.append((nm, tuple(np.shape(v)), _dtype_name(v), _is_weak(v)))
    return tuple(out)


def stacked_signature(stacked: Mapping) -> tuple:
    """Per-example signature of a batch-stacked env (leading axis removed)."""
    sig = []
    for nm in sorted(stacked):
        shp = tuple(np.shape(stacked[nm]))
        if not shp:
            raise ValueError(
                f"stacked env entry {nm!r} is a bare scalar; every entry "
                f"needs a leading batch axis")
        sig.append((nm, shp[1:], _dtype_name(stacked[nm]),
                    _is_weak(stacked[nm])))
    return tuple(sig)


_DEVICE_CONTEXT: Optional[str] = None


def device_context() -> str:
    """``backend:device_kind:device_count`` of this process (memoized).

    Part of every :class:`ExecutorKey`: a compiled executor is specialized
    against concrete devices, so entries from different device contexts —
    and in particular sharded vs unsharded compiles of the same plan hash —
    must never serve each other."""
    global _DEVICE_CONTEXT
    if _DEVICE_CONTEXT is None:
        dev = jax.devices()[0]
        _DEVICE_CONTEXT = (f"{jax.default_backend()}:"
                           f"{getattr(dev, 'device_kind', '?')}:"
                           f"{jax.device_count()}")
    return _DEVICE_CONTEXT


@dataclass(frozen=True)
class ExecutorKey:
    """Full identity of one compiled specialization."""

    plan: str  # structural plan hash
    env: tuple  # env_signature
    backend: str  # resolved: "xla" | "pallas"
    #: (block_rows, block_cols, block_inner) | None (xla)
    blocks: Optional[tuple]
    donate: bool
    #: the Pallas kernel's iteration order (``repro.lowering.ORDERS``);
    #: "" for xla
    order: str = ""
    #: device context (``device_context()``); "" only on legacy keys
    device: str = ""
    #: sharded entries only: (((axis, size), ...), (device ids, ...))
    mesh: tuple = ()
    #: sharded entries only: partition spec ((level, axis, shards), ...)
    partition: tuple = ()
    #: sharded entries only: requested halo strategy
    halo: str = ""


# ---------------------------------------------------------------------------
# compiled executor
# ---------------------------------------------------------------------------


def _stack_column(vals: Sequence):
    """Stack one env entry across a batch, minimizing device dispatches.

    ``jnp.stack`` over a list of host values issues one python-dispatched
    transfer *per element* plus a concatenate — at serving batch sizes that
    dwarfs the batched compute itself.  When every element is a host
    (numpy) array or strongly-typed numpy scalar of one dtype, stack on the
    host and return the *numpy* stack: the jitted batch call's C++ argument
    path transfers one contiguous buffer orders of magnitude cheaper than
    an eager ``jnp.asarray`` would, and the result is bit-identical.
    Anything else (jax arrays already on device, python scalars with
    weak-type promotion semantics, mixed dtypes) takes the original jnp
    path, which preserves promotion behavior exactly.
    """
    first = vals[0]
    cls = type(first)
    if cls is not np.ndarray and isinstance(first, np.generic):
        # typed numpy scalars: type identity pins dtype and shape at once,
        # and np.array runs the conversion as one C loop — the generic
        # per-element dtype/shape comparison below costs more than the
        # batched compute for scalar-heavy envs at serving batch sizes
        if all(type(v) is cls for v in vals):
            return np.array(vals, dtype=first.dtype)
    if isinstance(first, (np.ndarray, np.generic)):
        dt, shp = first.dtype, np.shape(first)
        if all(isinstance(v, (np.ndarray, np.generic)) and v.dtype == dt
               and np.shape(v) == shp for v in vals):
            # preallocate + row-assign instead of np.stack: stack's
            # expand_dims-then-concatenate costs ~3x more python overhead
            # per column at serving batch sizes
            out = np.empty((len(vals),) + shp, dtype=dt)
            for i, v in enumerate(vals):
                out[i] = v
            return out
    return jnp.stack([jnp.asarray(v) for v in vals])


def stack_envs(envs: Sequence[Mapping]) -> dict:
    """Stack same-signature envs along a new leading batch axis, one
    :func:`_stack_column` per entry (the input :meth:`CompiledRace.run_batch`
    takes)."""
    envs = list(envs)
    if not envs:
        raise ValueError("run_batch needs at least one env")
    return {k: _stack_column([e[k] for e in envs]) for k in envs[0]}


class CompiledRace:
    """One compiled specialization of a plan: a reusable jitted callable.

    Built once per :class:`ExecutorKey` and cached process-wide; calling it
    with any same-signature env reuses the jitted computation without
    retracing.  ``trace_count`` increments only while jax traces the call
    path, so it is the retrace detector the tests assert on.
    """

    def __init__(self, plan: Plan, env_sig: tuple, selection: Selection, *,
                 block_rows: int = 8, block_cols: int = 8,
                 block_inner: int = 0, donate: bool = False,
                 order: str = ORDER_AS_LAID):
        self.plan = plan
        self.env_sig = env_sig
        self.selection = selection
        self.backend = selection.backend
        self.block_rows = block_rows
        self.block_cols = block_cols
        self.block_inner = block_inner
        self.order = order if self.backend == "pallas" else None
        self.donate = donate
        self.calls = 0
        self.batch_calls = 0
        self.trace_count = 0
        self.batch_trace_count = 0
        self._out_names = frozenset(st.lhs.name for st in plan.body)
        self._batch_lock = threading.Lock()
        self._batch_jit = None
        self._plan_h = plan_hash(plan)

        # zero cold start: the XLA compile this executor triggers on its
        # first call is served from (and persisted to) the on-disk
        # compilation cache.  Must happen before jit dispatch, hence here in
        # the builder.
        from . import compile_cache as _ccache

        _ccache.ensure_enabled()

        with _obs.span("lower", plan=self._plan_h, backend=self.backend):
            if self.backend == "pallas":
                from repro.lowering import specialize_stencil

                self.spec = specialize_stencil(
                    plan,
                    {nm: shp for nm, shp, *_ in env_sig},
                    {nm: np.dtype(dt) for nm, _, dt, *_ in env_sig},
                    block_rows=block_rows, block_cols=block_cols,
                    block_inner=block_inner, order=order)
                core = self.spec.apply
            else:
                from .codegen import build_plan_evaluator, interior

                self.spec = None
                plan_run = build_plan_evaluator(plan)
                core = lambda env: interior(plan, plan_run(env))  # noqa: E731
        self._core = core

        # differentiability: wrap the core in a custom_vjp whose backward
        # runs the RACE-optimized *adjoint-stencil* plans (repro.core.
        # adjoint) instead of autodiff through the forward internals (the
        # plan evaluator's optimization_barrier has no JVP; the Pallas
        # kernel is opaque to autodiff entirely).  The primal path is the
        # bare core, so non-grad callers are unaffected.
        from .adjoint import make_custom_vjp

        self._vjp_core = make_custom_vjp(core, plan.program)
        vjp_core = self._vjp_core

        def _call(env_in, env_out):
            self.trace_count += 1  # python side effect: fires at trace only
            return vjp_core({**env_in, **env_out})

        # a stable name per program and plan: the device trace shows the
        # module as jit_<name> (a bare _call would read jit__call for every
        # executor), and two builds of one plan still lower identically
        self.name = f"race_{plan.program.label}_{self._plan_h[:8]}"
        _call.__name__ = _call.__qualname__ = self.name
        jit_kw = dict(donate_argnums=(1,)) if donate else {}
        self._jit = jax.jit(_call, **jit_kw)

    # -- single-env path ----------------------------------------------------

    def _split(self, env: Mapping) -> tuple:
        """Separate output-named entries so they can be donated (arg 1)."""
        outs = {k: v for k, v in env.items() if k in self._out_names}
        ins = {k: v for k, v in env.items() if k not in self._out_names}
        return ins, outs

    def run(self, env: Mapping) -> dict:
        """Execute on the compiled path; returns interior-convention outputs."""
        self.calls += 1
        ins, outs = self._split(env)
        if not _obs.enabled():  # the RACE_OBS=0 fast path: one flag read
            return self._jit(ins, outs)
        return self._traced(self._jit, "0", ins, outs)

    def _traced(self, fn, batch: str, *args):
        """A call with observability on: the ``dispatch`` span times entry
        to the jit's return, which is the enqueue and not the device work;
        a call that specializes the jit also records a ``compile`` span
        inside it (:class:`~repro.core.compile_cache.specializing`)."""
        from .compile_cache import specializing

        labels = dict(plan=self._plan_h, backend=self.backend, batch=batch)
        with _obs.span("dispatch", **labels), specializing(**labels):
            return fn(*args)

    __call__ = run

    # -- batched path -------------------------------------------------------

    def run_batch(self, envs: Union[Mapping, Sequence[Mapping]]) -> dict:
        """vmap the compiled executor over a stacked batch dimension.

        ``envs`` is either a sequence of same-signature envs (stacked here)
        or an already-stacked env dict whose *every* entry carries a leading
        batch axis (scalars as ``(B,)`` arrays).  Returns ``{output name:
        (B, ...) array}`` — element ``[b]`` equals ``run(envs[b])[name]``.
        """
        if isinstance(envs, Mapping):
            # no eager conversion: the jit's C++ argument path ingests host
            # (numpy) columns far cheaper than a python-dispatched
            # jnp.asarray per column would
            stacked = dict(envs)
        else:
            stacked = stack_envs(envs)
        fn = self.batch_jit()
        self.batch_calls += 1
        if not _obs.enabled():
            return fn(stacked)
        size = np.shape(next(iter(stacked.values())))[0] if stacked else 0
        return self._traced(fn, str(size), stacked)

    def batch_jit(self):
        """The jitted ``vmap`` of the executor (built at first use), named
        ``race_batch_<program>_<plan8>``."""
        if self._batch_jit is None:
            with self._batch_lock:
                if self._batch_jit is None:
                    vjp_core = self._vjp_core

                    def _bcall(env):
                        self.batch_trace_count += 1
                        return vjp_core(env)

                    batched = jax.vmap(_bcall)
                    batched.__name__ = batched.__qualname__ = (
                        self.name.replace("race_", "race_batch_", 1))
                    self._batch_jit = jax.jit(batched)
        return self._batch_jit

    # -- sharded composition --------------------------------------------------

    @property
    def core_fn(self):
        """The raw primal core (``env -> interior outputs``): no jit, no
        custom_vjp.  The sharded executor (:mod:`repro.shard`) runs this
        inside ``shard_map`` — differentiation and jit happen once, at its
        own outer dispatch, so the inner wrapper must be bypassed."""
        return self._core

    # -- introspection ------------------------------------------------------

    def cache_info(self) -> dict:
        return dict(backend=self.backend, order=self.order, calls=self.calls,
                    batch_calls=self.batch_calls,
                    trace_count=self.trace_count,
                    batch_trace_count=self.batch_trace_count,
                    jit_cache_size=getattr(self._jit, "_cache_size",
                                           lambda: None)())

    def __repr__(self) -> str:  # pragma: no cover - debugging sugar
        return (f"<CompiledRace {self.backend} plan={plan_hash(self.plan)} "
                f"calls={self.calls} traces={self.trace_count}>")


# ---------------------------------------------------------------------------
# process-wide LRU cache
# ---------------------------------------------------------------------------


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def snapshot(self) -> dict:
        return dict(hits=self.hits, misses=self.misses,
                    evictions=self.evictions, hit_rate=self.hit_rate)


class ExecutorCache:
    """Thread-safe LRU of :class:`CompiledRace` executors.

    The build happens under the lock: specialization is milliseconds (the
    expensive XLA compile is lazy, at the executor's first call, and jax's
    own jit cache is thread-safe), and building inside guarantees exactly
    one miss and one executor per key under concurrent first calls.  The
    lock is reentrant because builders nest: a sharded executor's builder
    (:mod:`repro.shard`) compiles its per-shard local executor through this
    same cache.
    """

    def __init__(self, maxsize: Optional[int] = None):
        if maxsize is None:  # the documented env knob
            maxsize = _env_cache_size()
        self.maxsize = maxsize
        self._entries: OrderedDict = OrderedDict()
        self._lock = threading.RLock()
        self.stats = CacheStats()

    def get_or_build(self, key: ExecutorKey,
                     builder: Callable[[], CompiledRace]) -> CompiledRace:
        hit = True
        evicted = []
        with self._lock:
            ex = self._entries.get(key)
            if ex is not None:
                self._entries.move_to_end(key)
                self.stats.hits += 1
            else:
                hit = False
                self.stats.misses += 1
                ex = self._entries[key] = builder()
                while len(self._entries) > self.maxsize:
                    old_key, _ = self._entries.popitem(last=False)
                    evicted.append(old_key)
                    self.stats.evictions += 1
        # telemetry outside the lock: the JSONL event sink does file I/O and
        # must not serialize concurrent cache lookups
        if _obs.enabled():
            _obs.counter("race_executor_cache_total",
                         event="hit" if hit else "miss",
                         plan=key.plan).inc()
            _obs.gauge("race_executor_cache_size").set(len(self._entries))
            if not hit:
                _obs.event("executor_build", plan=key.plan,
                           backend=key.backend, donate=key.donate,
                           blocks=key.blocks)
            for old in evicted:
                _obs.counter("race_executor_cache_total", event="evict",
                             plan=old.plan).inc()
                _obs.event("executor_evict", plan=old.plan,
                           backend=old.backend,
                           currsize=len(self._entries),
                           maxsize=self.maxsize)
        return ex

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.stats = CacheStats()
        if _obs.enabled():
            _obs.gauge("race_executor_cache_size").set(0)

    def stats_snapshot(self) -> dict:
        """Atomic hit/miss/eviction snapshot taken under the cache lock.

        ``self.stats`` mutates field-by-field inside ``get_or_build``;
        reading it lock-free can observe a hit count and a miss count from
        *different* lookups (a torn read — hit_rate over totals that never
        coexisted).  Every stats consumer goes through here.
        """
        with self._lock:
            return self.stats.snapshot()

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: ExecutorKey) -> bool:
        return key in self._entries

    def keys(self) -> list:
        with self._lock:
            return list(self._entries)

    def cache_info(self) -> dict:
        """Stats plus the configured capacity (``RACE_EXECUTOR_CACHE_SIZE``),
        the distinct device contexts keyed, and how many entries are sharded
        executors (mesh-bearing keys from :mod:`repro.shard`)."""
        with self._lock:
            return dict(maxsize=self.maxsize, currsize=len(self._entries),
                        devices=sorted({k.device for k in self._entries
                                        if k.device}),
                        sharded=sum(1 for k in self._entries if k.mesh),
                        **self.stats.snapshot())


_CACHE = ExecutorCache()


def executor_cache() -> ExecutorCache:
    """The process-wide cache (shared by every ``RaceResult.run``)."""
    return _CACHE


def cache_stats() -> dict:
    return _CACHE.stats_snapshot()


def clear_cache() -> None:
    _CACHE.clear()


def configure_cache(maxsize: int) -> None:
    """Resize the process-wide cache (evicts LRU entries if shrinking)."""
    evicted = []
    with _CACHE._lock:
        _CACHE.maxsize = maxsize
        while len(_CACHE._entries) > maxsize:
            old_key, _ = _CACHE._entries.popitem(last=False)
            evicted.append(old_key)
            _CACHE.stats.evictions += 1
    if _obs.enabled():
        _obs.gauge("race_executor_cache_size").set(len(_CACHE._entries))
        for old in evicted:
            _obs.counter("race_executor_cache_total", event="evict",
                         plan=old.plan).inc()
            _obs.event("executor_evict", plan=old.plan, backend=old.backend,
                       currsize=len(_CACHE._entries), maxsize=maxsize)


# ---------------------------------------------------------------------------
# front door
# ---------------------------------------------------------------------------


def _resolve(plan: Plan, backend: str) -> Selection:
    """select_backend memoized per plan instance (probe is pure analysis)."""
    memo = getattr(plan, "_selection_memo", None)
    if memo is None:
        memo = plan._selection_memo = {}
    sel = memo.get(backend)
    if sel is None:
        sel = memo[backend] = select_backend(plan, backend)
    return sel


def _tuned_choice(plan: Plan, sig: tuple, order: str) -> Optional[dict]:
    """Consult the persistent autotuning store (``repro.tuning``) for this
    (plan, env signature, iteration order) on this device/jax version.
    Returns the recorded choice dict, or None — and *never* raises: a
    corrupt or stale store must degrade to the static default, not take the
    serving path down.

    Runs on every ``backend="auto"`` call — i.e. on the steady-state serving
    path — so the expensive key construction (JSON of the env signature plus
    the runtime fence) is memoized per plan instance; what remains per call
    is one ``os.stat`` freshness check inside the store, which keeps
    cross-process pickups live without re-reading anything."""
    try:
        from repro.tuning.store import plan_choice, record_key

        memo = getattr(plan, "_tuning_key_memo", None)
        if memo is None:
            memo = plan._tuning_key_memo = {}
        key = memo.get((sig, order))
        if key is None:
            key = memo[sig, order] = record_key("plan", plan_hash(plan), sig,
                                                order=order)
        choice = plan_choice(key)
        if not isinstance(choice, dict):
            return None
        if choice.get("backend") == "xla":
            return choice
        if (choice.get("backend") == "pallas"
                and _resolve(plan, "auto").backend == "pallas"):
            return choice
    except Exception:
        pass
    return None


def compile_plan(plan: Plan, env: Union[Mapping, tuple],
                 backend: Optional[str] = None, *, block_rows: int = 8,
                 block_cols: int = 8, block_inner: int = 0,
                 donate: Optional[bool] = None,
                 cache: Optional[ExecutorCache] = None,
                 order: str = ORDER_AS_LAID) -> CompiledRace:
    """Fetch (or build) the compiled executor for this (plan, env) pairing.

    ``env`` is either an environment mapping or a precomputed
    :func:`env_signature`.  ``backend=None`` resolves to ``$RACE_BACKEND``
    (default ``"auto"``).  The ``"auto"`` path consults the persistent
    autotuning store (:mod:`repro.tuning`) first: a correctness-gated,
    measured winner recorded for this exact (plan hash, env signature,
    device, jax version) — by this or *any earlier process* — supplies the
    backend and block config with zero re-measurement; otherwise the
    capability probe picks as before.  Explicit ``"xla"``/``"pallas"``
    requests bypass the store (that's how the tuner itself measures).

    ``donate=True`` opts into ``donate_argnums`` output-buffer reuse on
    accelerator backends: env entries named like plan outputs are *consumed*
    by every call, so the caller must re-supply fresh buffers each time —
    hence off by default (and forced off on CPU, which ignores donation and
    would warn per call).

    ``order`` is the Pallas kernel's iteration order
    (``repro.lowering.ORDERS``), part of the key and of the tuning record
    consulted.  One chip's executors iterate as laid (the default): their
    operands cross into the module as the caller laid them, so a
    level-major kernel would pay a transpose per operand and output.  The
    sharded executor (:func:`repro.shard.compile_sharded`) passes
    level-major for its local kernel, whose operands its halo prologue
    writes anew in the same module.
    """
    sig = env if isinstance(env, tuple) else env_signature(env)
    if backend is None:
        backend = default_backend()
    if backend == "auto":
        choice = _tuned_choice(plan, sig, order)
        if choice is not None:
            if choice["backend"] == "pallas":
                try:
                    return compile_plan(
                        plan, sig, "pallas",
                        block_rows=int(choice.get("block_rows", block_rows)),
                        block_cols=int(choice.get("block_cols", block_cols)),
                        block_inner=int(choice.get("block_inner",
                                                   block_inner)),
                        donate=donate, cache=cache, order=order)
                except ValueError:
                    # stale/corrupt stored block config (e.g. a block too
                    # small for the plan's halo spread, from a hand-edited
                    # or bit-rotted store): degrade to the probe-driven
                    # static default below — a bad record must re-tune, not
                    # take the serving path down
                    pass
            else:
                backend = "xla"
    sel = _resolve(plan, backend)
    if donate is None:
        donate = False
    elif donate and jax.default_backend() in ("cpu",):
        donate = False
    pallas = sel.backend == "pallas"
    blocks = (block_rows, block_cols, block_inner) if pallas else None
    key = ExecutorKey(plan_hash(plan), sig, sel.backend, blocks, bool(donate),
                      order=order if pallas else "", device=device_context())
    c = cache if cache is not None else _CACHE
    return c.get_or_build(key, lambda: CompiledRace(
        plan, sig, sel, block_rows=block_rows, block_cols=block_cols,
        block_inner=block_inner, donate=bool(donate), order=order))
