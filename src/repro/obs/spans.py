"""Lightweight phase spans: nested wall-time timing into span histograms.

``obs.span("detect")`` times a ``with`` block and records the duration into
the ``race_span_seconds`` histogram labeled with the *leaf* span name plus
the full nesting ``path`` (thread-local stack), so both "total time in
detect" and "detect inside race inside autotune" views exist:

    with obs.span("race"):
        with obs.span("detect"):       # span=detect, path=race/detect
            ...

Besides the histogram aggregate, every completed span also lands in a
bounded :class:`SpanLog` as one *timeline record* — leaf name, nesting
path, its own id and its parent span's id, wall-clock start offset from
the process origin, duration, and the recording thread — which is exactly
the information a Chrome-trace / Perfetto timeline needs
(:mod:`repro.obs.trace` renders it).

A span also opens a ``jax.profiler.TraceAnnotation`` named ``race.<name>``
(with its id, parent id, labels and attributes as arguments), so while a
``jax.profiler`` trace is being captured the span lands in the profiler's
host plane, on the device trace's clock.  :func:`record_span` records a
span after the fact (a compile timed by JAX, a request's wait in a queue):
histogram and timeline only, since the profiler takes no past events.

When observability is disabled, ``obs.span`` returns one shared no-op
context manager — no allocation, no clock read, no stack touch — which is
the whole overhead story of the ``RACE_OBS=0`` path.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque

#: default SpanLog capacity (records, not bytes); newest win
DEFAULT_SPAN_RING = 16384

#: process time origin: perf_counter reference plus the wall-clock epoch it
#: corresponds to, captured once at import so every span record's ``ts_us``
#: offset is on one shared, monotonic axis (and convertible to wall time)
_ORIGIN_PERF = time.perf_counter()
_ORIGIN_EPOCH = time.time()

_stack = threading.local()

#: span ids, unique in the process; 0 is "no parent"
_ids = itertools.count(1)

#: ``jax.profiler.TraceAnnotation``, imported at the first enabled span
#: (``None`` until then, ``False`` where JAX cannot be imported)
_annotation = None


def _frames() -> list:
    """This thread's open spans, outermost first: ``[(name, id), ...]``."""
    frames = getattr(_stack, "frames", None)
    if frames is None:
        frames = _stack.frames = []
    return frames


def current_path() -> str:
    """The active nesting path ("" at top level) — introspection for tests
    and for events that want to record which phase emitted them."""
    return "/".join(n for n, _ in _frames())


def _place(name: str) -> tuple:
    """``(path, parent id)`` of a span named ``name`` opened here now."""
    frames = _frames()
    return ("/".join([n for n, _ in frames] + [name]),
            frames[-1][1] if frames else 0)


def _trace_annotation():
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:  # pragma: no cover - obs works without jax
            TraceAnnotation = False
        _annotation = TraceAnnotation
    return _annotation


class SpanLog:
    """Bounded ring of completed-span timeline records (thread-safe).

    One record per finished span::

        {"name": "lower", "path": "race/lower", "id": 7, "parent": 3,
         "ts_us": 1234.5, "dur_us": 88.2, "tid": 140...,
         "thread": "MainThread",
         "labels": {"plan": "ab12...", "backend": "xla"}}

    ``parent`` is the id of the span that was open around it on the same
    thread (0 at top level); records with ``attrs`` carry those too.

    ``ts_us`` is microseconds since the process time origin (one shared
    monotonic axis across threads); :func:`epoch_of_origin` anchors it to
    wall-clock time for cross-process correlation.
    """

    def __init__(self, ring: int = DEFAULT_SPAN_RING):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, int(ring)))
        self.dropped = 0

    def record(self, rec: dict) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(rec)

    def records(self) -> list:
        with self._lock:
            return list(self._ring)

    def __len__(self) -> int:
        return len(self._ring)


def epoch_of_origin() -> float:
    """Wall-clock (``time.time``) epoch of the ``ts_us = 0`` origin."""
    return _ORIGIN_EPOCH


def _strs(d: dict) -> dict:
    return {str(k): str(v) for k, v in d.items()}


def _record(registry, log, name, path, span_id, parent, t0, dt, labels,
            attrs) -> None:
    registry.histogram("race_span_seconds", span=name, path=path,
                       **labels).observe(dt)
    if log is not None:
        th = threading.current_thread()
        log.record(dict(
            name=name, path=path, id=span_id, parent=parent,
            ts_us=(t0 - _ORIGIN_PERF) * 1e6, dur_us=dt * 1e6,
            tid=th.ident, thread=th.name, labels=_strs(labels),
            **({"attrs": _strs(attrs)} if attrs else {})))


def record_span(name: str, t0: float, t1: float, registry, log=None,
                attrs=None, **labels) -> int:
    """Record a span that ran from ``t0`` to ``t1`` (``time.perf_counter``
    seconds) as a child of this thread's innermost open span; returns its
    id."""
    span_id = next(_ids)
    path, parent = _place(name)
    _record(registry, log, name, path, span_id, parent, t0,
            max(0.0, t1 - t0), labels, attrs)
    return span_id


class Span:
    """One timed phase; records on exit (exceptions still record).

    ``labels`` key the histogram series; ``attrs`` (request ids and the
    like, too many distinct values for a series) go only into the timeline
    record and the profiler annotation."""

    __slots__ = ("name", "labels", "attrs", "registry", "log", "t0", "path",
                 "seconds", "id", "parent", "_ann")

    def __init__(self, name: str, registry, labels: dict, log=None,
                 attrs=None):
        self.name = name
        self.registry = registry
        self.labels = labels
        self.attrs = attrs
        self.log = log
        self.t0 = 0.0
        self.path = ""
        self.seconds = None
        self.id = 0
        self.parent = 0
        self._ann = None

    def __enter__(self) -> "Span":
        self.path, self.parent = _place(self.name)
        self.id = next(_ids)
        _frames().append((self.name, self.id))
        ann = _trace_annotation()
        if ann:
            self._ann = ann(f"race.{self.name}", id=self.id,
                            parent=self.parent, **self.labels,
                            **(self.attrs or {}))
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        dt = t1 - self.t0
        self.seconds = dt
        frames = _frames()
        if frames and frames[-1][1] == self.id:
            frames.pop()
        _record(self.registry, self.log, self.name, self.path, self.id,
                self.parent, self.t0, dt, self.labels, self.attrs)


class _NoopSpan:
    """Shared do-nothing span for the disabled path."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


NOOP_SPAN = _NoopSpan()
