"""Profiler capture and the reduction from a device trace to numbers.

A traced run wraps its window in :func:`capture`; the benchmark's own host
phases carry ``jax.profiler.TraceAnnotation`` names that start with
``bench.`` (``bench.window`` spans the whole traced window), so device idle
gaps can be attributed to what the host was doing.

The reduction works on plain event lists, ``(start_ns, end_ns, name,
kind)``, so that it can be checked on a small recorded trace
(``bench/tests``):

* busy time: the union of the intervals of the device's programs and
  operations inside the window, on each chip;
* idle share: 1 - busy / window, averaged over the chips used;
* collective time: the summed durations of collective operations
  (collective permutes, all-reduces, all-gathers, all-to-alls,
  reduce-scatters), per chip;
* breakdown: the operations that took most device time, and the idle gaps
  grouped by the host annotation that covered most of each gap.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import tempfile
from dataclasses import dataclass, field

#: lines of a TPU plane: one event per executed program, per operation,
#: and per asynchronous operation from its start to its done
LINES = {"XLA Modules": "module", "XLA Ops": "op", "Async XLA Ops": "async"}
WINDOW = "bench.window"
COLLECTIVE = re.compile(
    r"collective-permute|all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"ppermute|collective_permute|all_reduce|all_gather", re.I)


@dataclass
class Trace:
    """Device operations per chip and the benchmark's host annotations, on
    one clock (nanoseconds)."""

    #: plane -> [(start, end, name, kind)], kind one of LINES' values
    devices: dict = field(default_factory=dict)
    host: list = field(default_factory=list)  # [(s, e, name)] bench.* only
    window: tuple = (0, 0)  # (s, e) of bench.window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def to_json(self) -> dict:
        return dict(devices={k: [list(e) for e in v]
                             for k, v in self.devices.items()},
                    host=[list(e) for e in self.host],
                    window=list(self.window))

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                   [tuple(e) for e in d["host"]], tuple(d["window"]))


@contextlib.contextmanager
def capture(out: dict):
    """Profile the enclosed block; on exit ``out["trace"]`` holds the parsed
    :class:`Trace`.  The raw profile goes to a temporary directory (under
    ``$TMPDIR``) that is deleted once read."""
    import jax

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
        jax.profiler.start_trace(d)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()
        out["trace"] = load(d)


def load(log_dir: str) -> Trace:
    """Parse the ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``log_dir``."""
    import jax

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    tr = Trace()
    for path in files:
        pd = jax.profiler.ProfileData.from_file(path)
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                evs = tr.devices.setdefault(plane.name, [])
                for ln in plane.lines:
                    kind = LINES.get(ln.name)
                    if kind is not None:
                        evs.extend((int(e.start_ns), int(e.end_ns),
                                    _short(e.name), kind)
                                   for e in ln.events if e.duration_ns > 0)
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    for e in ln.events:
                        if e.name.startswith("bench."):
                            tr.host.append((int(e.start_ns), int(e.end_ns),
                                            e.name))
    wins = [h for h in tr.host if h[2] == WINDOW]
    if wins:
        tr.window = (min(w[0] for w in wins), max(w[1] for w in wins))
    return tr


def _short(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def summary(tr: Trace) -> dict:
    """Counts for a first look at a trace: events per chip, host spans."""
    return dict(devices={k: len(v) for k, v in tr.devices.items()},
                host=len(tr.host), window_s=tr.window_s)


def _clip(evs, window, kinds=("module", "op")) -> list:
    lo, hi = window
    return [(max(s, lo), min(e, hi), n) for s, e, n, k in evs
            if k in kinds and e > lo and s < hi]


def union(evs) -> list:
    """Merged ``(start, end)`` intervals of ``evs``."""
    out = []
    for s, e, *_ in sorted(evs):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def busy_s(tr: Trace) -> dict:
    """Seconds in which some operation ran, per chip, inside the window."""
    return {k: sum(e - s for s, e in union(_clip(v, tr.window))) * 1e-9
            for k, v in tr.devices.items()}


def idle_share(tr: Trace) -> float | None:
    """1 - busy/window, averaged over the chips (None without a device
    event or a window)."""
    busy = busy_s(tr)
    if not busy or tr.window_s <= 0:
        return None
    return 1.0 - sum(busy.values()) / len(busy) / tr.window_s


def collective_s(tr: Trace) -> dict:
    """Seconds of collective operations per chip, inside the window: the
    union of their intervals, asynchronous ones from start to done."""
    return {k: sum(e - s for s, e in union(
        ev for ev in _clip(v, tr.window, ("op", "async"))
        if COLLECTIVE.search(ev[2]))) * 1e-9
            for k, v in tr.devices.items()}


def top_ops(tr: Trace, n: int = 10) -> list:
    """``[[name, seconds], ...]``: the operations with most device time,
    averaged over the chips."""
    tot: dict = {}
    for v in tr.devices.values():
        for s, e, name in _clip(v, tr.window, ("op",)):
            tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
    k = max(1, len(tr.devices))
    return [[name, t / k] for name, t in
            sorted(tot.items(), key=lambda x: -x[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> list:
    """``[[host activity, seconds], ...]``: device idle time inside the
    window, each gap credited to the ``bench.*`` annotation (other than the
    window itself) that covers most of it, or ``"none"``; summed per
    activity, averaged over the chips, longest first."""
    spans = sorted(h for h in tr.host if h[2] != WINDOW)
    starts = [h[0] for h in spans]
    longest = max((he - hs for hs, he, _ in spans), default=0)
    tot: dict = {}
    for v in tr.devices.values():
        busy = union(_clip(v, tr.window))
        edges = [tr.window[0]] + [x for iv in busy for x in iv] + [
            tr.window[1]]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            best, cover = "none", 0
            lo = bisect.bisect_left(starts, s - longest)
            for hs, he, name in spans[lo:bisect.bisect_left(starts, e)]:
                c = min(e, he) - max(s, hs)
                if c > cover:
                    best, cover = name, c
            tot[best] = tot.get(best, 0.0) + (e - s) * 1e-9
    k = max(1, len(tr.devices))
    return [[name, t / k] for name, t in
            sorted(tot.items(), key=lambda x: -x[1])[:n]]


def breakdown(tr: Trace) -> dict:
    return dict(device_ops=top_ops(tr), idle_gaps=idle_gaps(tr))
