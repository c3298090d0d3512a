"""The general generator: the loops a traffic mix drives over the window.

A mix (``bench/traffic/<name>.json``) names its ``driver`` and gives its
parameters; nothing here knows a cell by name.

* ``sweep``: a dependent chain of sweeps over device-resident data, as fast
  as the device takes them, for ``--seconds``.  Dispatch runs at most two
  sweeps ahead of the device.  ``sweep_ms`` is the whole window over all
  completed sweeps, ending in ``block_until_ready``.  The check compares
  the last sweep, and ``check_steps`` more drawn from the seed among the
  first ``check_window``, against the plain reference.
* ``open_loop``: requests to the serve runtime at due times drawn from the
  seed: ``rate_per_s`` for ``--seconds``, with exponential gaps (the same
  set of gaps for every seed, in the seed's order) and kinds (the same
  number of each kind, in the seed's order).  A request's latency runs
  from its due time to its numpy outputs.  ``sample`` requests drawn from
  the seed are kept and checked once the window has closed.
"""
from __future__ import annotations

import math
import statistics
import threading
import time
from dataclasses import dataclass, field

import numpy as np

#: how long after the window the driver waits for answers still due
LATE_S = 60.0


@dataclass
class Outcome:
    """What a driver hands back to the harness."""

    setup_s: float
    e2e: dict
    attempted: int
    failed: int
    #: (control=False) -> {number: value}, with control=True also the
    #: control's numbers; frees the program's state first
    check: object
    unanswered: int = 0
    sweeps: int = 0
    sweep_bytes: int = 0
    counters: dict = field(default_factory=dict)
    compiles: dict = field(default_factory=dict)
    retraces: dict = field(default_factory=dict)
    notes: dict = field(default_factory=dict)
    trace: object = None


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, seed])


def _retraces(executors) -> dict:
    return {f"{i}": (getattr(ex, "trace_count", 0),
                     getattr(ex, "batch_trace_count", 0))
            for i, ex in enumerate(executors)}


def _delta(before: dict, after: dict) -> dict:
    return {k: [a - b for a, b in zip(after[k], before[k])]
            for k in after if after[k] != before.get(k)}


def _window(trace: bool):
    """The traced window's context (a profile) or a plain one."""
    import contextlib

    from bench.trace import capture

    holder: dict = {}
    return (capture(holder) if trace else contextlib.nullcontext()), holder


def sweep(fam, cell, seed, seconds, trace, devices, clock, compiles):
    import jax

    mix = cell.traffic
    cfg = cell.config
    prep = fam.prepare(cfg, mix, seed, devices, clock)
    rng = _rng(seed, 1)
    k = int(mix.get("check_steps", 0))
    early = set(rng.choice(int(mix.get("check_window", 16)), size=k,
                           replace=False).tolist()) if k else set()

    setup_s = clock.since_start()
    before = _retraces(prep.executors)
    pairs = []
    ahead = []
    state = prep.state0
    n = 0
    ctx, holder = _window(trace)
    with compiles.counting(), ctx:
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                nxt = prep.sweep(state)
            if n in early:
                pairs.append((state, nxt))
            prev, state = state, nxt
            n += 1
            ahead.append(state)
            if len(ahead) > 2:
                with jax.profiler.TraceAnnotation("bench.sync"):
                    jax.block_until_ready(ahead.pop(0))
            if time.perf_counter() >= deadline:
                break
        with jax.profiler.TraceAnnotation("bench.sync"):
            jax.block_until_ready(state)
        t1 = time.perf_counter()
    pairs.append((prev, state))
    retraces = _delta(before, _retraces(prep.executors))

    def check(control: bool = False):
        nonlocal pairs, state, prev, ahead
        host = prep.fetch(pairs)
        pairs = state = prev = ahead = prep.state0 = None
        prep.release()
        got = prep.compare(host)
        return (got, prep.control(host)) if control else got

    return Outcome(
        setup_s=setup_s, e2e=dict(sweep_ms=(t1 - t0) / n * 1e3),
        attempted=n, failed=0, check=check, sweeps=n,
        sweep_bytes=prep.sweep_bytes, compiles=dict(compiles.events),
        retraces=retraces, trace=holder.get("trace"),
        notes=dict(sweeps=n, window_s=t1 - t0,
                   checked_steps=sorted(early) + [n - 1], **prep.notes))


def _schedule(mix: dict, seed: int, seconds: float, kinds: int):
    """Due offsets (seconds from the window's start) and request kinds."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng = _rng(seed, 2)
    rng.shuffle(gaps)
    due = np.cumsum(gaps) - gaps[0]
    kind = np.arange(n) % kinds
    rng.shuffle(kind)
    return due, kind


def _warm_batches(rt, target, env, max_batch: int) -> None:
    """Every batch size the window can form, through the runtime: a burst
    of ``b`` requests coalesces into one batch of ``b``."""
    for b in range(1, max_batch + 1):
        for f in rt.submit_many(target, [env] * b):
            f.result()


def open_loop(fam, cell, seed, seconds, trace, devices, clock, compiles):
    import jax

    from repro.serve import ServeRejected, ServeRuntime

    mix = cell.traffic
    prep = fam.prepare(cell.config, mix, seed, devices, clock)
    envs = prep.request_envs
    due, kind = _schedule(mix, seed, seconds, len(envs))
    n = len(due)
    sample = set(_rng(seed, 3).choice(
        n, size=min(n, int(mix.get("sample", 64))), replace=False).tolist())

    rt = ServeRuntime(max_batch=mix.get("max_batch"),
                      window_us=mix.get("window_us"))
    done_at = np.full(n, np.nan)
    errors = np.zeros(n, bool)
    kept: dict = {}
    called = threading.Condition()
    n_called = 0

    def on_done(i):
        def cb(fut):
            nonlocal n_called
            t = time.perf_counter()
            with called:
                if fut.exception() is not None:
                    errors[i] = True
                else:
                    done_at[i] = t
                    if i in sample:
                        kept[i] = fut.result()
                n_called += 1
                called.notify_all()
        return cb

    try:
        with clock.span("warmup_s"):
            _warm_batches(rt, prep.target, envs[0], rt.max_batch)
        executors = prep.executors()
        setup_s = clock.since_start()
        before = _retraces(executors)
        s0 = rt.stats()
        late = np.zeros(n)
        futs = []
        rejected = 0
        ctx, holder = _window(trace)
        with compiles.counting(), ctx:
            t0 = time.perf_counter()
            for i in range(n):
                t_due = t0 + due[i]
                wait = t_due - time.perf_counter()
                if wait > 0:
                    with jax.profiler.TraceAnnotation("bench.arrival_wait"):
                        time.sleep(wait)
                with jax.profiler.TraceAnnotation("bench.submit"):
                    try:
                        f = rt.submit(prep.target, envs[kind[i]])
                    except ServeRejected:
                        rejected += 1
                        f = None
                late[i] = time.perf_counter() - t_due
                if f is not None:
                    f.add_done_callback(on_done(i))
                    futs.append(f)
            t_sent = time.perf_counter()
            # every answer still due, up to LATE_S past the last send
            with jax.profiler.TraceAnnotation("bench.drain"), called:
                called.wait_for(lambda: n_called == len(futs),
                                timeout=LATE_S)
            t_end = time.perf_counter()
        s1 = rt.stats()
        retraces = _delta(before, _retraces(executors))
    finally:
        rt.close(flush=False, timeout=60)

    stats = {k: s1[k] - s0[k] for k in ("completed", "batches", "coalesced",
                                        "rejected", "failed")}
    with called:
        answered = ~np.isnan(done_at)
        n_errors = int(errors.sum())
    failed = n - int(answered.sum())
    # a request that never came back counts as missing every limit: it
    # waited until the driver gave up
    lat = np.where(answered, done_at - (t0 + due), t_end - (t0 + due))
    lat_ms = sorted((lat * 1e3).tolist())
    e2e = dict(request_p50_ms=statistics.median(lat_ms),
               request_p95_ms=_quantile(lat_ms, 0.95))
    unanswered = failed - rejected - n_errors

    def check(control: bool = False):
        nonlocal kept
        with called:
            samples = [(int(kind[i]), kept[i]) for i in sorted(kept)]
        kept = None
        prep.release()
        got = prep.compare_requests(samples)
        return (got, prep.control_requests(samples)) if control else got

    return Outcome(
        setup_s=setup_s, e2e=e2e, attempted=n, failed=failed, check=check,
        unanswered=max(0, unanswered), counters=stats,
        compiles=dict(compiles.events), retraces=retraces,
        trace=holder.get("trace"),
        notes=dict(requests=n, rate_per_s=mix["rate_per_s"],
                   window_s=t_end - t0, send_s=t_sent - t0,
                   late_p50_ms=float(np.median(late) * 1e3),
                   late_p95_ms=_quantile(sorted((late * 1e3).tolist()), 0.95),
                   late_max_ms=float(late.max() * 1e3),
                   checked_requests=len(sample), **stats, **prep.notes))


def _quantile(sorted_vals: list, q: float) -> float:
    """Nearest-rank quantile of an ascending list."""
    i = max(0, math.ceil(q * len(sorted_vals)) - 1)
    return float(sorted_vals[i])


DRIVERS = {"sweep": sweep, "open_loop": open_loop}
