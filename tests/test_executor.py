"""Executor-cache semantics (PR 3): structural plan hashing, hit/miss/
eviction accounting, key separation, the zero-retrace guarantee on both
backends, batched serving equality, thread safety, and the satellite fixes
(scalar-only-RHS guard, per-statement reference memoization)."""
import threading

import numpy as np
import pytest

import jax

from repro.apps.paper_kernels import get_case
from repro.core.backend import R_NO_BASE_ARRAY, select_backend
from repro.core.executor import (CompiledRace, ExecutorCache, compile_plan,
                                 env_signature, executor_cache,
                                 plan_fingerprint, plan_hash)
from repro.core.ir import Scalar, arr, loopnest, mul, program
from repro.core.race import race
from repro.lowering import ORDER_AS_LAID, ORDER_LEVEL_MAJOR, ORDERS
from repro.testing.differential import build_env, reference_plan


@pytest.fixture(autouse=True)
def fresh_cache():
    executor_cache().clear()
    yield
    executor_cache().clear()


def _case(name="gaussian", n=14):
    return get_case(name, n)


def _res(name="gaussian", n=14, **kw):
    case = _case(name, n)
    kw.setdefault("reassociate", case.reassociate)
    kw.setdefault("rewrite_div", case.rewrite_div)
    return case, race(case.program, **kw)


# ---------------------------------------------------------------------------
# structural plan hashing
# ---------------------------------------------------------------------------


def test_plan_hash_is_structural():
    """Two independent race() runs of the same program share one hash."""
    _, r1 = _res()
    _, r2 = _res()
    assert r1.plan is not r2.plan
    assert plan_hash(r1.plan) == plan_hash(r2.plan)
    assert plan_fingerprint(r1.plan) == plan_fingerprint(r2.plan)


def test_plan_hash_ignores_loop_variable_names():
    def prog(vi, vj):
        loops, (i, j) = loopnest((vi, 1, 10), (vj, 1, 10))
        u, out = arr("u"), arr("out")
        return program(loops, [(out[i, j], u[i - 1, j] + u[i + 1, j])])

    assert (plan_hash(race(prog("i", "j")).plan)
            == plan_hash(race(prog("p", "q")).plan))


def test_plan_hash_separates_structures():
    hashes = {
        plan_hash(race(_case("gaussian", n).program, reassociate=r).plan)
        for n in (12, 14) for r in (0, 3)
    }
    assert len(hashes) == 4  # ranges and plans all differ structurally
    assert plan_hash(race(_case("psinv", 10).program).plan) not in hashes


# ---------------------------------------------------------------------------
# cache accounting and key separation
# ---------------------------------------------------------------------------


def test_hit_miss_counting_and_identity():
    case, res = _res()
    env = build_env(case)
    cache = executor_cache()
    res.run(env, "xla")
    assert cache.stats.misses == 1 and cache.stats.hits == 0
    res.run(env, "xla")
    assert cache.stats.misses == 1 and cache.stats.hits == 1
    # same plan structure from a fresh race() hits the same entry
    _, res2 = _res()
    res2.run(env, "xla")
    assert cache.stats.misses == 1 and cache.stats.hits == 2
    assert len(cache) == 1


@pytest.mark.pallas
def test_distinct_keys_per_specialization():
    case, res = _res()
    env32 = build_env(case, dtype=np.float32)
    env64 = build_env(case, dtype=np.float64)
    exs = {
        id(compile_plan(res.plan, env32, "xla")),
        id(compile_plan(res.plan, env64, "xla")),       # dtype
        id(compile_plan(res.plan, env32, "pallas")),    # backend
        id(compile_plan(res.plan, env32, "pallas", block_rows=16)),  # blocks
    }
    assert len(exs) == 4
    # a different grid size is a different env signature (and plan)
    case2, res2 = _res(n=18)
    exs.add(id(compile_plan(res2.plan, build_env(case2), "xla")))
    assert len(exs) == 5
    assert executor_cache().stats.misses == 5
    # xla executors ignore block config in the key (no spurious misses)
    assert (compile_plan(res.plan, env32, "xla", block_rows=4)
            is compile_plan(res.plan, env32, "xla"))


def test_orders_never_share_an_executor():
    """The Pallas kernel's iteration order is part of the key: one chip's
    default (as laid) and a sharded local kernel's level-major are two
    executors, each reporting its order; XLA executors have none."""
    case, res = _res("psinv", 10)
    env = build_env(case)
    laid = compile_plan(res.plan, env, "pallas")
    lm = compile_plan(res.plan, env, "pallas", order=ORDER_LEVEL_MAJOR)
    assert laid is not lm
    assert laid is compile_plan(res.plan, env, "pallas", order=ORDER_AS_LAID)
    assert laid.cache_info()["order"] == laid.spec.order == ORDER_AS_LAID
    assert lm.cache_info()["order"] == lm.spec.order == ORDER_LEVEL_MAJOR
    assert {k.order for k in executor_cache().keys()} == set(ORDERS)
    xla = compile_plan(res.plan, env, "xla")
    assert xla is compile_plan(res.plan, env, "xla", order=ORDER_LEVEL_MAJOR)
    assert xla.cache_info()["order"] is None
    for k, want in xla(env).items():
        for ex in (laid, lm):
            np.testing.assert_allclose(np.asarray(ex(env)[k]),
                                       np.asarray(want), rtol=1e-5,
                                       atol=1e-6, err_msg=k)


def test_lru_eviction():
    case, res = _res()
    cache = ExecutorCache(maxsize=2)
    envs = [build_env(case, dtype=dt)
            for dt in (np.float32, np.float64, np.float16)]
    first = compile_plan(res.plan, envs[0], "xla", cache=cache)
    for env in envs[1:]:
        compile_plan(res.plan, env, "xla", cache=cache)
    assert len(cache) == 2 and cache.stats.evictions == 1
    # the evicted (LRU, float32) entry rebuilds as a miss
    assert compile_plan(res.plan, envs[0], "xla", cache=cache) is not first
    assert cache.stats.misses == 4


# ---------------------------------------------------------------------------
# the zero-retrace guarantee (acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["xla", pytest.param(
    "pallas", marks=pytest.mark.pallas)])
def test_second_run_zero_retrace(backend):
    case, res = _res()
    env = build_env(case)
    out1 = res.run(env, backend)
    ex = compile_plan(res.plan, env, backend)
    assert executor_cache().stats.hits == 1  # the line above was a hit
    assert ex.trace_count == 1
    out2 = res.run(env, backend)
    assert ex.trace_count == 1  # no retracing on the second call
    assert ex.calls == 2
    if hasattr(ex._jit, "_cache_size"):
        assert ex._jit._cache_size() == 1  # one jax compilation, reused
    for k in out1:
        np.testing.assert_array_equal(np.asarray(out1[k]),
                                      np.asarray(out2[k]))


@pytest.mark.parametrize("backend", ["xla", pytest.param(
    "pallas", marks=pytest.mark.pallas)])
def test_executor_matches_oracle(backend):
    case, res = _res()
    env = build_env(case)
    got = res.run(env, backend)
    want = reference_plan(res.plan, env)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k], np.float64),
                                   np.asarray(want[k], np.float64),
                                   rtol=1e-5, atol=1e-5, err_msg=k)


# ---------------------------------------------------------------------------
# batched serving
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,n", [("gaussian", 14), ("psinv", 10)])
@pytest.mark.parametrize("backend", ["xla", pytest.param(
    "pallas", marks=pytest.mark.pallas)])
def test_run_batch_equals_per_call_loop(name, n, backend):
    case, res = _res(name, n)
    envs = [build_env(case, seed=s) for s in range(3)]
    stacked = res.run_batch(envs, backend)
    for b, env in enumerate(envs):
        per = res.run(env, backend)
        for k in per:
            assert stacked[k].shape == (len(envs),) + per[k].shape
            np.testing.assert_allclose(
                np.asarray(stacked[k][b], np.float64),
                np.asarray(per[k], np.float64),
                rtol=1e-6, atol=1e-6, err_msg=f"{k}[{b}]")


def test_run_batch_accepts_stacked_dict():
    import jax.numpy as jnp

    case, res = _res()
    envs = [build_env(case, seed=s) for s in range(2)]
    stacked_env = {k: jnp.stack([jnp.asarray(e[k]) for e in envs])
                   for k in envs[0]}
    a = res.run_batch(envs, "xla")
    b = res.run_batch(stacked_env, "xla")
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    # both forms share one executor (and one batched trace)
    ex = compile_plan(res.plan, envs[0], "xla")
    assert ex.batch_trace_count == 1


def test_batch_reuses_single_executor():
    case, res = _res()
    envs = [build_env(case, seed=s) for s in range(2)]
    res.run(envs[0], "xla")
    res.run_batch(envs, "xla")
    cache = executor_cache()
    assert len(cache) == 1  # run and run_batch share the specialization
    ex = compile_plan(res.plan, envs[0], "xla")
    assert ex.trace_count == 1 and ex.batch_trace_count == 1


# ---------------------------------------------------------------------------
# thread safety
# ---------------------------------------------------------------------------


def test_concurrent_runs_on_one_result():
    case, res = _res()
    env = build_env(case)
    want = np.asarray(res.run(env, "xla")["gb"])  # warm: compile once
    results, errors = [], []

    def worker():
        try:
            for _ in range(5):
                results.append(np.asarray(res.run(env, "xla")["gb"]))
        except Exception as e:  # pragma: no cover - the assertion target
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(results) == 40
    for got in results:
        np.testing.assert_array_equal(got, want)
    cache = executor_cache()
    assert len(cache) == 1 and cache.stats.misses == 1
    assert compile_plan(res.plan, env, "xla").trace_count == 1


def test_threaded_stress_mixed_traffic_under_resizing(monkeypatch):
    """Serving-grade stress (PR 10): mixed run/run_batch traffic across
    several specializations races a thread that keeps resizing the cache
    (forcing evictions and rebuilds).  Invariants: everything joins (no
    deadlock), every executor construction is a recorded miss (the ledger
    proves no double-build escaped the lock), and hits + misses balances
    the lookup count exactly (stats_snapshot is torn-read-free)."""
    from repro.core.executor import configure_cache

    builds = []
    orig_init = CompiledRace.__init__

    def counting_init(self, *a, **kw):
        builds.append(1)
        return orig_init(self, *a, **kw)

    monkeypatch.setattr(CompiledRace, "__init__", counting_init)

    specs = []  # four distinct specializations: two sizes x two dtypes
    for n in (12, 14):
        case, res = _res(n=n)
        for dt in (np.float32, np.float64):
            specs.append((res, build_env(case, dtype=dt),
                          [build_env(case, seed=s, dtype=dt)
                           for s in range(2)]))

    n_threads, iters = 6, 8
    lookups = [0] * n_threads
    errors = []
    stop = threading.Event()

    def traffic(idx):
        try:
            res, env, envs = specs[idx % len(specs)]
            for i in range(iters):
                if i % 3 == 2:
                    res.run_batch(envs, "xla")
                else:
                    res.run(env, "xla")
                lookups[idx] += 1  # one cache lookup per run/run_batch
        except Exception as e:  # pragma: no cover - the assertion target
            errors.append(e)

    def resizer():
        try:
            import time

            while not stop.is_set():
                configure_cache(2)  # below the live specialization count
                executor_cache().stats_snapshot()  # reader under contention
                configure_cache(16)
                time.sleep(0.002)  # shrink spikes, not a busy spin
        except Exception as e:  # pragma: no cover
            errors.append(e)

    orig_size = executor_cache().maxsize
    threads = [threading.Thread(target=traffic, args=(i,))
               for i in range(n_threads)]
    churn = threading.Thread(target=resizer)
    try:
        churn.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        stop.set()
        churn.join(timeout=30)
        assert not any(t.is_alive() for t in threads + [churn]), "deadlock"
        assert not errors, errors
        snap = executor_cache().stats_snapshot()
        assert len(builds) == snap["misses"]  # every build was one miss
        assert snap["hits"] + snap["misses"] == sum(lookups)
        assert snap["evictions"] >= 1  # the resizer actually forced churn
        assert len(executor_cache()) <= 16
    finally:
        stop.set()
        configure_cache(orig_size)


def test_concurrent_cold_start_builds_one_executor():
    case, res = _res()
    env = build_env(case)
    barrier = threading.Barrier(6)
    errors = []

    def worker():
        try:
            barrier.wait()
            res.run(env, "xla")
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    cache = executor_cache()
    assert len(cache) == 1 and cache.stats.misses == 1


# ---------------------------------------------------------------------------
# satellite fixes
# ---------------------------------------------------------------------------


def _scalar_only_plan():
    loops, (i, j) = loopnest(("i", 1, 6), ("j", 1, 6))
    out = arr("out")
    return race(program(loops, [(out[i, j], mul(Scalar("s"), 2.0))]))


def test_scalar_only_rhs_probed_not_crashed():
    res = _scalar_only_plan()
    sel = select_backend(res.plan, "auto")
    assert sel.backend == "xla"
    assert any(r.code == R_NO_BASE_ARRAY for r in sel.capability.reasons)
    out = res.run({"s": np.float32(0.5)})  # auto falls back and runs
    np.testing.assert_allclose(np.asarray(out["out"]), 1.0)


def test_scalar_only_rhs_direct_kernel_call_clear_error():
    from repro.lowering import specialize_stencil

    res = _scalar_only_plan()
    with pytest.raises(ValueError, match="array operand"):
        specialize_stencil(res.plan, {"s": ()}, {"s": np.dtype(np.float32)})


def test_repeated_ref_sliced_once_per_statement():
    """codegen memoizes _eval_ref: three occurrences of u[i-1, j] emit one
    slice into the jaxpr, not three."""
    from repro.core.codegen import build_baseline_evaluator

    loops, (i, j) = loopnest(("i", 1, 6), ("j", 1, 6))
    u, out = arr("u"), arr("out")
    prog = program(
        loops,
        [(out[i, j], u[i - 1, j] * u[i - 1, j] + u[i - 1, j])])
    env = {"u": np.random.default_rng(0)
           .random((8, 8)).astype(np.float32)}
    jaxpr = jax.make_jaxpr(build_baseline_evaluator(prog))(env)
    n_slice = sum(1 for eq in jaxpr.jaxpr.eqns
                  if eq.primitive.name == "slice")
    assert n_slice == 1
    got = np.asarray(build_baseline_evaluator(prog)(env)["out"])[1:7, 1:7]
    w = env["u"][0:6, 1:7]
    np.testing.assert_allclose(got, w * w + w, rtol=1e-6)


def test_env_signature_orders_and_types():
    sig = env_signature({"b": np.zeros((2, 3), np.float32),
                         "a": np.float64(1.0), "c": 2.0})
    # python scalars are jax weak types; numpy scalars/arrays are strong
    assert sig == (("a", (), "float64", False),
                   ("b", (2, 3), "float32", False),
                   ("c", (), "float64", True))


def test_weak_and_strong_scalars_get_distinct_executors():
    """Mixing numpy (strong) and weak-typed scalar inputs must not silently
    retrace one cached executor — the weak_type flag is part of the key."""
    import jax.numpy as jnp

    case, res = _res("calc_tpoints", 12)
    env_strong = build_env(case)
    scalar_names = [k for k, v in env_strong.items() if np.ndim(v) == 0]
    assert scalar_names  # calc_tpoints has scalar operands
    env_weak = dict(env_strong)
    for k in scalar_names:
        env_weak[k] = jnp.asarray(float(env_strong[k]))  # weak-typed
        assert env_weak[k].weak_type
    ex_strong = compile_plan(res.plan, env_strong, "xla")
    ex_weak = compile_plan(res.plan, env_weak, "xla")
    assert ex_strong is not ex_weak
    ex_strong(env_strong)
    ex_strong(env_strong)
    ex_weak(env_weak)
    ex_weak(env_weak)
    assert ex_strong.trace_count == 1 and ex_weak.trace_count == 1


def test_frontend_run_batch_accepts_stacked_dict():
    import jax.numpy as jnp

    from repro.apps import frontend_kernels
    from repro.frontend import race_kernel

    kern = race_kernel(reassociate=3)(frontend_kernels.psinv)
    case = _case("psinv", 10)
    envs = [build_env(case, seed=s) for s in range(2)]
    a = kern.run_batch(envs, backend="xla")
    stacked = {k: jnp.stack([jnp.asarray(e[k]) for e in envs])
               for k in envs[0]}
    b = kern.run_batch(stacked, backend="xla")
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
