"""Persistent compiled-executable cache: zero cold start across processes.

The executor layer (:mod:`repro.core.executor`) already guarantees that a
*process* compiles each plan specialization exactly once — but a fresh
process still pays the full XLA compile on its first request.  This module
closes that gap by wiring JAX's persistent compilation cache: every XLA
executable the executor builds is serialized to disk keyed by its HLO hash,
and any later process (or a later rebuild in the same process, e.g. after an
executor-LRU eviction) deserializes it instead of recompiling.

Where it lives: ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX's own
variable; this module then picks no other path), else
``<checkout>/.jax-compile-cache`` — fixed relative to the package root, so
every process of one checkout finds the entries the last one wrote.  The
cache is always on.

The jitted call path in :class:`~repro.core.executor.CompiledRace` uses
stable function names, so two builds of the same plan specialization produce
byte-identical cache keys — the property the whole scheme rests on (pinned
by tests).

Accounting: JAX reports cache traffic through ``jax.monitoring`` events; a
process-wide listener mirrors them into plain counters (readable with
:func:`counts` whether or not observability is on) and — when ``RACE_OBS=1``
— into the ``race_compile_cache_total`` metric and ``compile_cache_hit`` /
``compile_cache_miss`` decision events, which is what the CI zero-cold-start
guard asserts on (``repro.obs.report --require-events compile_cache_hit``).

Compile spans: an executor call made under :class:`specializing` credits
to that executor JAX's own ``/jax/core/compile/*`` time spans (tracing,
lowering to MLIR, the XLA compile or the load from this cache) that the
call sets off.  Where there were any, one ``compile`` span covers them, from
the first start to the last end, and ``race_compile_cache_total`` counts
``event="compile"`` (and the call's cache hits and misses) under the
executor's labels; a call that specializes nothing records neither.

:func:`ensure_enabled` is safe to call repeatedly: configuration is applied
only when the resolved directory changes, so it costs one env read per
executor build.
"""
from __future__ import annotations

import os
import threading
import time
from pathlib import Path
from typing import Optional

from repro import obs as _obs

#: JAX's own variable naming the cache directory (documented in README)
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

#: the directory used when ``$JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax-compile-cache")

#: jax.monitoring event names for compilation-cache traffic (unknown events
#: are simply ignored)
_EV_HIT = "/jax/compilation_cache/cache_hits"
_EV_MISS = "/jax/compilation_cache/cache_misses"
_EV_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"

#: prefix of JAX's time spans for tracing, lowering and compiling a jit
_EV_COMPILE = "/jax/core/compile/"

_lock = threading.RLock()
_active_path: Optional[str] = None  # the currently-applied cache dir
_listener_registered = False
_counts = {"hits": 0, "misses": 0, "requests": 0}

#: the executor call in progress on this thread (``specializing``)
_current = threading.local()


class specializing:
    """Credit the compiles that the enclosed executor call sets off to it
    (observability-on path only; see the module docstring)."""

    __slots__ = ("labels", "t0", "t1", "prev")

    def __init__(self, **labels):
        self.labels = labels
        self.t0 = self.t1 = None

    def __enter__(self) -> "specializing":
        self.prev = getattr(_current, "call", None)
        _current.call = self
        return self

    def __exit__(self, *exc) -> None:
        _current.call = self.prev
        if self.t0 is None:
            return
        # JAX stamps its spans with time.time(); spans use perf_counter
        shift = time.perf_counter() - time.time()
        _obs.record_span("compile", self.t0 + shift, self.t1 + shift,
                         **self.labels)
        _obs.counter("race_compile_cache_total", event="compile",
                     **self.labels).inc()

    def saw(self, start: float, end: float) -> None:
        self.t0 = start if self.t0 is None else min(self.t0, start)
        self.t1 = end if self.t1 is None else max(self.t1, end)


def _on_monitoring_event(event: str, **kw) -> None:
    """jax.monitoring listener: count cache traffic, mirror to obs."""
    if event == _EV_HIT:
        _counts["hits"] += 1
        if _obs.enabled():
            _obs.counter("race_compile_cache_total", event="hit",
                         **_call_labels()).inc()
            _obs.event("compile_cache_hit", path=_active_path)
    elif event == _EV_MISS:
        _counts["misses"] += 1
        if _obs.enabled():
            _obs.counter("race_compile_cache_total", event="miss",
                         **_call_labels()).inc()
            _obs.event("compile_cache_miss", path=_active_path)
    elif event == _EV_REQUEST:
        _counts["requests"] += 1


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    """jax.monitoring listener: JAX's compile phases, for ``compile``
    spans."""
    if event.startswith(_EV_COMPILE):
        call = getattr(_current, "call", None)
        if call is not None:
            call.saw(start, end)


def _call_labels() -> dict:
    call = getattr(_current, "call", None)
    return call.labels if call is not None else {}


def _register_listener() -> None:
    global _listener_registered
    if not _listener_registered:
        import jax

        jax.monitoring.register_event_listener(_on_monitoring_event)
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        _listener_registered = True


def resolve_dir() -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` or
    :data:`DEFAULT_DIR`."""
    return os.environ.get(ENV_CACHE_DIR, "").strip() or DEFAULT_DIR


def _apply(path: str) -> None:
    """Point JAX's persistent compilation cache at ``path``.

    Entry-size and compile-time thresholds are dropped to "cache
    everything" — RACE plans are small programs whose compiles JAX would
    otherwise deem too cheap to persist, which is exactly the cold-start
    cost this cache exists to kill."""
    global _active_path
    import jax

    os.makedirs(path, exist_ok=True)
    _register_listener()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # jax latches its cache-in-use decision at the first compile
    # (compilation_cache._cache_checked): a process that compiled anything
    # before this call would silently never read or write the cache.
    # Resetting the latch makes mid-process (re)configuration take effect.
    from jax._src.compilation_cache import reset_cache

    reset_cache()
    _active_path = path
    if _obs.enabled():
        _obs.event("compile_cache_configure", path=path)


def ensure_enabled() -> str:
    """Apply :func:`resolve_dir` if it changed since last applied; returns
    the active directory.  The executor's per-build front door."""
    path = resolve_dir()
    if path != _active_path:
        with _lock:
            if path != _active_path:
                _apply(path)
    return path


def cache_dir() -> Optional[str]:
    """The applied directory (None before the first executor build)."""
    return _active_path


def counts() -> dict:
    """Snapshot of the process's persistent-cache traffic counters."""
    with _lock:
        return dict(_counts)


def info() -> dict:
    """One-stop status: directory, entry count, traffic."""
    n_entries = None
    if _active_path:
        try:
            n_entries = sum(
                len(files) for _, _, files in os.walk(_active_path))
        except OSError:  # pragma: no cover - unreadable cache dir
            n_entries = None
    return dict(path=_active_path, entries=n_entries, **counts())
