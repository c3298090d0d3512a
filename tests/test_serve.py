"""Serving runtime (PR 10): dynamic batching correctness, coalescing,
backpressure, shutdown semantics, burst submission, eager warmup, tuning-
store replay, and the persistent compilation cache."""
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.apps.paper_kernels import get_case
from repro.core import compile_cache
from repro.core.executor import (compile_plan, env_signature, executor_cache,
                                 plan_hash)
from repro.core.race import race
from repro.serve import ServeRejected, ServeRuntime, synthetic_env, warmup
from repro.serve.runtime import ServeRuntime as _SR
from repro.testing.differential import build_env


@pytest.fixture(autouse=True)
def fresh_cache():
    executor_cache().clear()
    yield
    executor_cache().clear()


def _res(name="gaussian", n=12):
    case = get_case(name, n)
    return case, race(case.program, reassociate=case.reassociate,
                      rewrite_div=case.rewrite_div)


def _outputs_equal(got: dict, want: dict):
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# batching correctness
# ---------------------------------------------------------------------------


def test_coalesced_results_equal_direct_run():
    case, res = _res()
    envs = [build_env(case, seed=s) for s in range(6)]
    want = [res.run(e, "xla") for e in envs]
    with ServeRuntime(max_batch=4, window_us=20000, workers=1,
                      backend="xla") as rt:
        futs = [rt.submit(res.plan, e) for e in envs]
        got = [f.result(timeout=120) for f in futs]
        stats = rt.stats()
    for g, w in zip(got, want):
        _outputs_equal(g, w)
    # the window coalesced: fewer dispatches than requests
    assert stats["batches"] < stats["submitted"] == 6
    assert stats["completed"] == 6 and stats["max_batch"] >= 2


def test_single_and_batched_paths_return_host_arrays():
    case, res = _res()
    env = build_env(case)
    with ServeRuntime(max_batch=4, window_us=0, workers=1,
                      backend="xla") as rt:
        lone = rt.run(res.plan, env, timeout=120)
        futs = [rt.submit(res.plan, build_env(case, seed=s))
                for s in range(4)]
        rode = [f.result(timeout=120) for f in futs]
    for out in [lone] + rode:
        for v in out.values():
            assert isinstance(v, np.ndarray)


def test_submit_many_equals_per_submit():
    case, res = _res()
    envs = [build_env(case, seed=s) for s in range(5)]
    want = [res.run(e, "xla") for e in envs]
    with ServeRuntime(max_batch=8, window_us=10000, workers=1,
                      backend="xla") as rt:
        futs = rt.submit_many(res.plan, envs)
        assert len(futs) == 5
        for f, w in zip(futs, want):
            _outputs_equal(f.result(timeout=120), w)
        assert rt.submit_many(res.plan, []) == []


def test_accepts_race_result_and_bare_plan():
    case, res = _res()
    env = build_env(case)
    want = res.run(env, "xla")
    with ServeRuntime(window_us=0, backend="xla") as rt:
        _outputs_equal(rt.run(res, env, timeout=120), want)
        _outputs_equal(rt.run(res.plan, env, timeout=120), want)
    with pytest.raises(TypeError, match="Plan or RaceResult"):
        with ServeRuntime(window_us=0, backend="xla") as rt:
            rt.submit("nonsense", env)


def test_window_groups_stragglers_into_one_batch():
    case, res = _res()
    envs = [build_env(case, seed=s) for s in range(3)]
    with ServeRuntime(max_batch=8, window_us=50000, workers=1,
                      backend="xla") as rt:
        rt.run(res.plan, envs[0], timeout=120)  # prime executor + paths
        futs = [rt.submit(res.plan, e) for e in envs]
        for f in futs:
            f.result(timeout=120)
        stats = rt.stats()
    # 3 primed submits inside one 50ms window -> exactly one dispatch
    assert stats["batches"] == 2 and stats["max_batch"] == 3


# ---------------------------------------------------------------------------
# backpressure / failure / shutdown
# ---------------------------------------------------------------------------


def test_queue_full_rejects_with_structured_code(monkeypatch):
    monkeypatch.setattr(_SR, "_worker", lambda self: time.sleep(3600))
    case, res = _res()
    env = build_env(case)
    rt = ServeRuntime(max_batch=2, window_us=0, workers=1, queue_limit=3,
                      backend="xla")
    futs = [rt.submit(res.plan, env) for _ in range(3)]
    with pytest.raises(ServeRejected) as ei:
        rt.submit(res.plan, env)
    assert ei.value.code == "queue-full"
    # burst rejection is atomic: nothing partially queued
    with pytest.raises(ServeRejected):
        rt.submit_many(res.plan, [env, env])
    assert rt.stats()["queue_depth"] == 3
    rt.close(flush=False, timeout=0.1)
    for f in futs:
        with pytest.raises(ServeRejected):
            f.result(timeout=5)


def test_executor_failure_propagates_to_every_future():
    case, res = _res()
    good = build_env(case)
    bad = {k: v for k, v in good.items() if k != sorted(good)[0]}
    with ServeRuntime(max_batch=4, window_us=20000, workers=1,
                      backend="xla") as rt:
        futs = rt.submit_many(res.plan, [bad, bad])
        errs = [pytest.raises(Exception, f.result, 120) for f in futs]
        assert all(errs)
        stats = rt.stats()
        assert stats["failed"] == 2
        # the runtime survives a failed batch: a good request still works
        _outputs_equal(rt.run(res.plan, good, timeout=120),
                       res.run(good, "xla"))


def test_close_without_flush_rejects_pending(monkeypatch):
    monkeypatch.setattr(_SR, "_worker", lambda self: time.sleep(3600))
    case, res = _res()
    env = build_env(case)
    rt = ServeRuntime(max_batch=2, window_us=0, workers=1, backend="xla")
    futs = [rt.submit(res.plan, env) for _ in range(3)]
    rt.close(flush=False, timeout=0.1)
    for f in futs:
        with pytest.raises(ServeRejected) as ei:
            f.result(timeout=5)
        assert ei.value.code == "shutdown"
    with pytest.raises(ServeRejected) as ei:
        rt.submit(res.plan, env)
    assert ei.value.code == "shutdown"
    assert rt.stats()["rejected"] == 4


def test_close_with_flush_serves_queued_requests():
    case, res = _res()
    envs = [build_env(case, seed=s) for s in range(4)]
    want = [res.run(e, "xla") for e in envs]
    rt = ServeRuntime(max_batch=2, window_us=5000, workers=1, backend="xla")
    futs = [rt.submit(res.plan, e) for e in envs]
    rt.close(flush=True, timeout=120)
    for f, w in zip(futs, want):
        _outputs_equal(f.result(timeout=1), w)


# ---------------------------------------------------------------------------
# warmup / zero cold start
# ---------------------------------------------------------------------------


def test_synthetic_env_round_trips_signature():
    case, _ = _res("calc_tpoints", 12)
    env = build_env(case)
    sig = env_signature(env)
    assert env_signature(synthetic_env(sig)) == sig


def test_synthetic_env_round_trips_weak_scalars():
    sig = (("a", (4, 4), "float32", False), ("b", (), "float64", True),
           ("c", (), "int32", False), ("d", (), "bool", True))
    assert env_signature(synthetic_env(sig)) == sig


def test_warmup_reports_and_primes_executor():
    case, res = _res()
    env = build_env(case)
    reports = warmup([(res.plan, env), (res.plan, env_signature(env))],
                     backend="xla")
    assert len(reports) == 2
    for rep in reports:
        assert rep["plan"] == plan_hash(res.plan)
        assert rep["backend"] == "xla"
        assert rep["build_ms"] >= 0 and rep["first_ms"] >= 0
    # the executor is now cached: a fresh compile_plan is a hit
    before = executor_cache().stats_snapshot()
    compile_plan(res.plan, env, "xla")
    after = executor_cache().stats_snapshot()
    assert after["hits"] == before["hits"] + 1


def test_runtime_warmup_primes_single_and_batch_paths():
    case, res = _res()
    env = build_env(case)
    with ServeRuntime(max_batch=4, window_us=0, workers=1,
                      backend="xla") as rt:
        reports = rt.warmup([(res.plan, env)], backend="xla")
        assert reports[0]["queue_ms"] >= 0
        assert reports[0]["batch_ms"] >= 0
        ex = compile_plan(res.plan, env, "xla")
        assert ex.calls >= 1 and ex.batch_calls >= 1


def test_warm_from_store_replays_fabricated_record(tmp_path):
    from repro.serve import warm_from_store
    from repro.serve.warm import store_plan_keys
    from repro.tuning.store import TuningStore, record_key

    case, res = _res("gaussian", 12)
    env = build_env(case)
    sig = env_signature(env)
    store = TuningStore(tmp_path / "tuning.jsonl")
    store.put(dict(key=record_key("plan", plan_hash(res.plan), sig),
                   backend="xla", level=case.reassociate))
    store.put(dict(key=record_key("plan", plan_hash(res.plan), sig, batch=8),
                   backend="xla", level=case.reassociate, batch=8))
    keys = store_plan_keys(store)
    assert len(keys) == 2 and {k[2] for k in keys} == {0, 8}
    doc = warm_from_store(store, backend="xla")
    # both records describe one (plan, sig): replayed once, matched
    assert len(doc["warmed"]) == 1 and doc["unmatched"] == []
    assert doc["warmed"][0]["plan"] == plan_hash(res.plan)


def test_warm_from_store_reports_unmatched(tmp_path):
    from repro.serve import warm_from_store
    from repro.tuning.store import TuningStore, record_key

    case, _ = _res("gaussian", 12)
    sig = env_signature(build_env(case))
    store = TuningStore(tmp_path / "tuning.jsonl")
    store.put(dict(key=record_key("plan", "not-a-real-plan-hash", sig),
                   backend="xla"))
    doc = warm_from_store(store, backend="xla")
    assert doc["warmed"] == [] and doc["unmatched"] == ["not-a-real-plan-hash"]


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------


def test_compile_cache_serves_rebuild_after_eviction(tmp_path, monkeypatch):
    # every CompiledRace build re-applies $JAX_COMPILATION_CACHE_DIR, so the
    # env var is the authoritative placement
    monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, str(tmp_path / "cc"))
    case, res = _res()
    env = build_env(case)
    try:
        assert compile_cache.ensure_enabled() == str(tmp_path / "cc")
        res.run(env, "xla")  # populate the on-disk cache
        executor_cache().clear()  # evict: force a full rebuild
        c0 = compile_cache.counts()
        res.run(env, "xla")
        c1 = compile_cache.counts()
        assert c1["requests"] > c0["requests"]
        assert c1["hits"] > c0["hits"]  # deserialization, not recompilation
        info = compile_cache.info()
        assert info["path"] == str(tmp_path / "cc") and info["entries"] >= 1
    finally:
        monkeypatch.undo()
        compile_cache.ensure_enabled()
    assert compile_cache.cache_dir() != str(tmp_path / "cc")


def test_compile_cache_env_knob(tmp_path, monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, str(tmp_path / "envcc"))
    try:
        assert compile_cache.ensure_enabled() == str(tmp_path / "envcc")
        assert compile_cache.cache_dir() == str(tmp_path / "envcc")
        # unset, the cache lives at a fixed path in the checkout
        monkeypatch.delenv(compile_cache.ENV_CACHE_DIR)
        assert compile_cache.resolve_dir() == compile_cache.DEFAULT_DIR
        root = Path(compile_cache.DEFAULT_DIR).parent
        assert Path(compile_cache.DEFAULT_DIR).name == ".jax-compile-cache"
        assert (root / "src" / "repro" / "core" / "compile_cache.py").is_file()
    finally:
        monkeypatch.undo()
        compile_cache.ensure_enabled()
    assert compile_cache.cache_dir() != str(tmp_path / "envcc")


# ---------------------------------------------------------------------------
# env knobs
# ---------------------------------------------------------------------------


def test_runtime_env_knobs(monkeypatch):
    monkeypatch.setenv("RACE_SERVE_MAX_BATCH", "3")
    monkeypatch.setenv("RACE_SERVE_WINDOW_US", "123")
    monkeypatch.setenv("RACE_SERVE_QUEUE", "7")
    monkeypatch.setenv("RACE_SERVE_WORKERS", "2")
    rt = ServeRuntime(backend="xla")
    try:
        stats = rt.stats()
        assert stats["max_batch_limit"] == 3
        assert stats["window_us"] == pytest.approx(123)
        assert stats["queue_limit"] == 7
        assert stats["workers"] == 2
    finally:
        rt.close(timeout=5)
    monkeypatch.setenv("RACE_SERVE_MAX_BATCH", "0")
    with pytest.raises(ValueError, match="RACE_SERVE_MAX_BATCH"):
        ServeRuntime(backend="xla")
