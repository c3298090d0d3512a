"""Serving benchmark: executor cache, compile cache, and batching queue.

Measures the three layers of the serving stack (PRs 3/10):

  * ``cold_ms``       first ``RaceResult.run`` on an empty executor cache;
  * ``us_per_call``   median steady-state per-call wall time (cache hot);
  * ``cold_over_steady``  the compile-amortization ratio;
  * ``recompile_ms``  rebuild after an executor-cache eviction — the cost
    the persistent compilation cache (:mod:`repro.core.compile_cache`) is
    there to kill: warm it and this collapses to deserialization;
  * ``compile_cache`` (cold/warm) stamped on **every** row: cold-ms
    populations with and without a warm compilation cache are incomparable,
    so history gating must never mix them (it is an identity field in
    ``repro.obs.history``);
  * ``hit_rate``/``retraces``  executor-cache hit rate over the steady
    phase and the executor's trace counter (must stay at 1: the zero-retrace
    guarantee);
  * ``batchB_us_per_item``/``batch_ips``  per-item cost and items/sec of
    ``run_batch`` vmapping one compiled executor over a B-stack;
  * queue rows (``tag="queue"``, via :class:`repro.serve.ServeRuntime`):
    ``first_request_us`` — first post-warmup request through the runtime
    (the zero-cold-start acceptance: within 2x the runtime's steady
    ``us_per_call``); ``queue_speedup_vs_sequential`` — coalesced batch-8
    submission throughput vs dispatching the same requests through the
    runtime one at a time (the dynamic-batching acceptance: >= 3x).

Pallas rows are interpreted on the CPU backend — correctness-plus-caching
signal only; kernel timings come from a TPU, where they run compiled.
"""
from __future__ import annotations

import time

import numpy as np

import jax

from repro import obs
from repro.apps.paper_kernels import get_case
from repro.core import compile_cache
from repro.core.backend import select_backend
from repro.core.executor import compile_plan, executor_cache, plan_hash
from repro.core.race import race
from repro.tuning.space import Config

from .common import build_env, csv_line

#: (case, grid size) pairs: one 2-D transcendental, one 2-D halo-heavy,
#: one 3-D — small enough that interpret-mode Pallas stays in budget
CASES = [("calc_tpoints", 64), ("gaussian", 64), ("psinv", 16)]

#: queue rows use smaller grids: dynamic batching targets the latency-bound
#: regime where per-request dispatch dominates per-request compute
#: (gaussian first: single-output kernels amortize best under vmap, so it
#: is the row quick/CI mode gates the coalescing acceptance on)
QUEUE_CASES = [("gaussian", 24), ("calc_tpoints", 16)]


def _compile_cache_state(delta_hits: int) -> str:
    """cold / warm for one measured compile, from the persistent cache's
    hits while it ran."""
    return "warm" if delta_hits > 0 else "cold"


def _bench_backend(res, case, backend, repeats, batch,
                   block_rows=8, block_cols=8, block_inner=0):
    # the exact candidate config this row ran under: BENCH_serving.json
    # entries stay comparable across PRs even once autotuning can move the
    # default (serving rows always pin an explicit backend, never "auto")
    config = Config(case.reassociate, backend, block_rows, block_cols,
                    block_inner)
    cache = executor_cache()
    cache.clear()
    env = build_env(case)

    cc0 = compile_cache.counts()
    t0 = time.perf_counter()
    jax.block_until_ready(res.run(env, backend))
    cold = time.perf_counter() - t0
    cc1 = compile_cache.counts()
    cc_state = _compile_cache_state(cc1["hits"] - cc0["hits"])

    s0 = cache.stats_snapshot()
    ts = []
    for _ in range(repeats):
        t1 = time.perf_counter()
        jax.block_until_ready(res.run(env, backend))
        ts.append(time.perf_counter() - t1)
    steady = float(np.median(ts))
    s1 = cache.stats_snapshot()
    served = (s1["hits"] + s1["misses"]) - (s0["hits"] + s0["misses"])
    hit_rate = (s1["hits"] - s0["hits"]) / served if served else 0.0

    ex = compile_plan(res.plan, env, backend, block_rows=block_rows,
                      block_cols=block_cols)
    envs = [build_env(case, seed=s) for s in range(batch)]
    jax.block_until_ready(ex.run_batch(envs))  # warm the batched trace
    t2 = time.perf_counter()
    jax.block_until_ready(ex.run_batch(envs))
    t_batch = time.perf_counter() - t2
    retraces = ex.trace_count

    # eviction-rebuild cost: what a fresh process (or an LRU victim) pays to
    # serve this plan again — the number the compile cache exists to kill
    cache.clear()
    t3 = time.perf_counter()
    jax.block_until_ready(res.run(env, backend))
    recompile = time.perf_counter() - t3

    return dict(
        case=case.name, backend=backend, cold_ms=cold * 1e3,
        us_per_call=steady * 1e6, cold_over_steady=cold / max(steady, 1e-12),
        recompile_ms=recompile * 1e3, compile_cache=cc_state,
        hit_rate=hit_rate, retraces=retraces, batch=batch,
        batch_us_per_item=t_batch / batch * 1e6,
        batch_ips=batch / max(t_batch, 1e-12),
        cache_entries=len(cache),
        config=dict(config.as_dict(), plan=plan_hash(res.plan)),
    )


def _bench_queue(res, case, repeats, batch=8):
    """Drive the ServeRuntime: warm-process latency + coalescing throughput.

    Latency phase (window 0: nothing holds a lone request): warmup, then
    the first request — the zero-cold-start number — and a steady median.
    Throughput phase: a sustained pipelined stream (``4 * batch`` requests
    in flight) against a windowed runtime vs the same requests dispatched
    through the runtime one at a time, each blocking before the next.
    Both sides pay the queue per request; only coalescing differs — the
    honest measure of what dynamic batching buys at sustained load.

    Estimator hygiene (the acceptance ratios are thin on a 1-core box):
    first-request samples come from *seven* fresh runtimes (the latency
    distribution has a heavy scheduler tail, so a median of three is
    itself noisy); a gen-2 ``gc.collect()`` precedes the throughput
    trials (collector pauses land on whichever phase happens to cross a
    threshold, which is allocation skew, not serving cost) but *not* the
    single-shot latency timings — a collection idles the worker thread
    long enough for a deep-sleep wake penalty to land on the one request
    being timed; and the sequential / coalesced trials are *interleaved*
    over two live runtimes so process drift (jit-cache growth, allocator
    state) ages both sides of the ratio equally instead of whichever
    phase ran last.
    """
    import gc

    from repro.serve import ServeRuntime

    backend = "xla"  # pinned: rows comparable across PRs, like other rows
    env = build_env(case)
    envs = [build_env(case, seed=s) for s in range(batch)]
    executor_cache().clear()

    # first-request latency: median over seven fresh warmed runtimes — one
    # shot per runtime is all "first" can ever be, so de-noise across
    # runtimes rather than pretending one sample is the distribution
    firsts = []
    cc_state = None
    for _ in range(7):
        with ServeRuntime(max_batch=batch, window_us=0, workers=1,
                          backend=backend) as rt:
            cc0 = compile_cache.counts()
            rt.warmup([(res.plan, env)], backend=backend)
            cc1 = compile_cache.counts()
            if cc_state is None:
                cc_state = _compile_cache_state(cc1["hits"] - cc0["hits"])
            t0 = time.perf_counter()
            rt.run(res.plan, env, timeout=120)
            firsts.append((time.perf_counter() - t0) * 1e6)
    first_us = float(np.median(firsts))

    from collections import deque

    n_seq = batch * 3
    total = batch * max(8, repeats)
    seq_trials = []
    q_trials = []
    with ServeRuntime(max_batch=batch, window_us=0, workers=1,
                      backend=backend) as rt_seq, \
         ServeRuntime(max_batch=batch, window_us=5000, workers=1,
                      backend=backend) as rt_q:
        rt_seq.run(res.plan, env, timeout=120)
        gc.collect()
        ts = []
        for _ in range(repeats):
            t1 = time.perf_counter()
            rt_seq.run(res.plan, env, timeout=120)
            ts.append(time.perf_counter() - t1)
        steady_us = float(np.median(ts)) * 1e6
        # warm wave: compiles the vmapped batch path once
        for f in rt_q.submit_many(res.plan, envs):
            f.result(timeout=300)
        for _ in range(3):
            # sequential dispatch: one in-flight request at a time
            gc.collect()
            t2 = time.perf_counter()
            for i in range(n_seq):
                rt_seq.run(res.plan, envs[i % batch], timeout=120)
            seq_trials.append((time.perf_counter() - t2) / n_seq * 1e6)
            # sustained load: burst-submit (one lock/wakeup per batch of
            # envs) and keep 4 batches in flight so the worker always finds
            # a full batch waiting — the regime dynamic batching exists for
            gc.collect()
            in_flight = deque()
            t3 = time.perf_counter()
            for _ in range(total // batch):
                in_flight.extend(rt_q.submit_many(res.plan, envs))
                while len(in_flight) >= 4 * batch:
                    in_flight.popleft().result(timeout=300)
            while in_flight:
                in_flight.popleft().result(timeout=300)
            q_trials.append((time.perf_counter() - t3) / total * 1e6)
        seq_us = float(np.median(seq_trials))
        queue_us = float(np.median(q_trials))
        stats = rt_q.stats()

    return dict(
        case=case.name, backend=backend, tag="queue", batch=batch,
        concurrency=batch,
        compile_cache=cc_state,
        first_request_us=first_us, us_per_call=steady_us,
        first_over_steady=first_us / max(steady_us, 1e-9),
        seq_us_per_item=seq_us, queue_us_per_item=queue_us,
        queue_ips=1e6 / max(queue_us, 1e-9),
        queue_speedup_vs_sequential=seq_us / max(queue_us, 1e-9),
        batches=stats["batches"], max_batch=stats["max_batch"],
        config=dict(plan=plan_hash(res.plan)),
    )


def _span_delta(before: dict, after: dict) -> dict:
    """Per-span {count, total_s} recorded between two ``obs.span_summary()``
    snapshots — the telemetry breakdown of one benchmark row."""
    out = {}
    for span, agg in after.items():
        prev = before.get(span, {"count": 0, "total_s": 0.0})
        d_count = agg["count"] - prev["count"]
        if d_count > 0:
            out[span] = dict(count=d_count,
                             total_s=agg["total_s"] - prev["total_s"])
    return out


def _span_tag(spans: dict) -> str:
    return "|".join(f"{k}:{v['count']}x{v['total_s'] * 1e6 / v['count']:.0f}us"
                    for k, v in sorted(spans.items()))


def run(print_fn=print, quick: bool = False, repeats: int = None,
        batch: int = None):
    """Returns one row per (case, backend) plus one queue row per case;
    CSV is printed en route.

    With ``RACE_OBS=1`` each row carries a ``spans`` breakdown — the
    per-phase (lower/compile/run/...) count and wall time recorded while
    that row executed — and a case that records *no* pipeline spans is a
    hard error: the instrumentation regressed, not the benchmark.
    """
    repeats = repeats or (5 if quick else 20)
    batch = batch or (4 if quick else 8)
    rows = []
    # queue rows first: they carry the serving acceptance numbers and are
    # allocation-heavy (futures, request objects), so they must not inherit
    # a process bloated by the interpret-mode rows' jit caches (gc drag
    # inflates the queue path far more than the jit dispatch path)
    for name, n in QUEUE_CASES[:1] if quick else QUEUE_CASES:
        case = get_case(name, n)
        res = race(case.program, reassociate=case.reassociate,
                   rewrite_div=case.rewrite_div)
        spans0 = obs.span_summary() if obs.enabled() else {}
        row = _bench_queue(res, case, repeats)
        derived = (f"first_request_us={row['first_request_us']:.0f}"
                   f";first_over_steady={row['first_over_steady']:.2f}x"
                   f";seq_us={row['seq_us_per_item']:.0f}"
                   f";queue_us={row['queue_us_per_item']:.0f}"
                   f";speedup={row['queue_speedup_vs_sequential']:.1f}x"
                   f";compile_cache={row['compile_cache']}")
        if obs.enabled():
            spans = _span_delta(spans0, obs.span_summary())
            if not spans.get("serve"):
                raise AssertionError(
                    f"serving.{name}.queue: RACE_OBS=1 but the runtime "
                    f"emitted zero serve spans — instrumentation regressed")
            row["spans"] = spans
            derived += f";spans={_span_tag(spans)}"
        print_fn(csv_line(f"serving.{name}.queue",
                          row["queue_us_per_item"], derived))
        rows.append(row)
    for name, n in CASES[:2] if quick else CASES:
        case = get_case(name, n)
        res = race(case.program, reassociate=case.reassociate,
                   rewrite_div=case.rewrite_div)
        backends = ["xla"]
        if select_backend(res.plan, "auto").backend == "pallas":
            backends.append("pallas")
        for backend in backends:
            spans0 = obs.span_summary() if obs.enabled() else {}
            row = _bench_backend(res, case, backend, repeats, batch)
            derived = (f"cold_ms={row['cold_ms']:.1f}"
                       f";cold_over_steady={row['cold_over_steady']:.0f}x"
                       f";recompile_ms={row['recompile_ms']:.1f}"
                       f";compile_cache={row['compile_cache']}"
                       f";hit_rate={row['hit_rate']:.2f}"
                       f";retraces={row['retraces']}"
                       f";batch{batch}_us_per_item="
                       f"{row['batch_us_per_item']:.1f}"
                       f";batch_ips={row['batch_ips']:.0f}"
                       f";cfg={Config.from_dict(row['config']).describe()}")
            if obs.enabled():
                spans = _span_delta(spans0, obs.span_summary())
                if not spans:
                    raise AssertionError(
                        f"serving.{name}.{backend}: RACE_OBS=1 but the case "
                        f"emitted zero pipeline spans — instrumentation "
                        f"regressed")
                row["spans"] = spans
                derived += f";spans={_span_tag(spans)}"
            print_fn(csv_line(f"serving.{name}.{backend}",
                              row["us_per_call"], derived))
            rows.append(row)
    return rows


def main(argv=None) -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser(
        description="executor-cache + serving-runtime benchmark")
    ap.add_argument("--quick", action="store_true", help="smaller sweep")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--json", nargs="?", const="BENCH_serving.json",
                    default=None, metavar="PATH",
                    help="write stamped structured rows (default "
                         "BENCH_serving.json)")
    args = ap.parse_args(argv)

    print("name,us_per_call,derived")
    from .common import bench_stamp, record_history

    stamp = bench_stamp()
    rows = run(quick=args.quick, repeats=args.repeats, batch=args.batch)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(dict(stamp=stamp, section="serving",
                           rows=rows), f, indent=1, default=str)
        print(csv_line("json.serving", 0.0, f"wrote={args.json}"))
    record_history("serving", rows, stamp)
    if obs.enabled():
        obs.dump("OBS_metrics.json")
        print(csv_line("obs", 0.0, "wrote=OBS_metrics.json"))


if __name__ == "__main__":
    main()
