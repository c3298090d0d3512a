"""The autotuner subsystem (ISSUE 4): persistent store semantics (atomic,
corruption-tolerant, schema-versioned, concurrency-safe), the measured +
correctness-gated search, cross-process reuse of decisions with zero
re-measurement, the ``compile_plan(backend="auto")`` store consult, the
``tune`` wiring through ``race``/``RaceResult``/``@race_kernel``, the
executor-layer env knobs, and the innermost-tile (``block_inner``) axis."""
import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.apps.paper_kernels import get_case
from repro.core.executor import (ExecutorCache, compile_plan,
                                 default_backend, env_signature,
                                 executor_cache, plan_hash, program_hash)
from repro.core.ir import Scalar, arr, loopnest, mul, program
from repro.core.race import race
from repro.lowering import ORDER_AS_LAID, ORDER_LEVEL_MAJOR
from repro.testing.differential import build_env, run_case
from repro.tuning import (SCHEMA_VERSION, TuningStore, autotune,
                          default_store, record_key, runtime_fence,
                          store_file)

pytestmark = pytest.mark.tuning

SRC = str(Path(__file__).resolve().parent.parent / "src")


@pytest.fixture(autouse=True)
def fresh_executor_cache():
    executor_cache().clear()
    yield
    executor_cache().clear()


def _case(name="gaussian", n=12):
    return get_case(name, n)


def _rec(key, choice=None):
    return dict(key=key, kind="plan", hash="h", device="cpu", jax="x",
                choice=choice or dict(reassociate=0, backend="xla",
                                      block_rows=8, block_cols=8,
                                      block_inner=0))


QUICK = dict(levels=(0, 3), backends=("xla",), repeats=2, warmup=1)


# ---------------------------------------------------------------------------
# the persistent store
# ---------------------------------------------------------------------------


def test_store_roundtrip_across_instances(tmp_path):
    path = tmp_path / "t.jsonl"
    s1 = TuningStore(path)
    s1.put(_rec("a"))
    s1.put(_rec("b"))
    s2 = TuningStore(path)  # a fresh instance sees both records
    assert s2.get("a")["choice"]["backend"] == "xla"
    assert sorted(s2.keys()) == ["a", "b"]
    # every on-disk line is complete, schema-stamped JSON (atomic writes)
    for line in path.read_text().splitlines():
        assert json.loads(line)["schema"] == SCHEMA_VERSION


def test_put_overwrites_by_key(tmp_path):
    s = TuningStore(tmp_path / "t.jsonl")
    s.put(_rec("a"))
    s.put(_rec("a", choice=dict(reassociate=3, backend="xla")))
    assert len(s) == 1
    assert s.get("a")["choice"]["reassociate"] == 3


def test_corrupt_store_degrades_never_crashes(tmp_path):
    path = tmp_path / "t.jsonl"
    good = json.dumps(dict(_rec("good"), schema=SCHEMA_VERSION))
    path.write_text("not json at all\n" + good + "\n"
                    + good[: len(good) // 2])  # truncated mid-record
    s = TuningStore(path)
    assert s.get("good") is not None  # the intact record still loads
    assert len(s) == 1
    s.put(_rec("new"))  # writing through corruption works...
    s2 = TuningStore(path)
    assert sorted(s2.keys()) == ["good", "new"]
    for line in path.read_text().splitlines():  # ...and scrubs the file
        json.loads(line)


def test_binary_garbage_store_is_empty_not_fatal(tmp_path):
    path = tmp_path / "t.jsonl"
    path.write_bytes(b"\x00\xff\xfe garbage \x00" * 10)
    assert TuningStore(path).get("anything") is None


def test_schema_version_mismatch_ignored(tmp_path):
    path = tmp_path / "t.jsonl"
    path.write_text(json.dumps(dict(_rec("old"), schema=SCHEMA_VERSION + 1))
                    + "\n")
    s = TuningStore(path)
    assert s.get("old") is None  # future/old schema: re-tune, don't guess
    s.put(_rec("cur"))
    assert TuningStore(path).get("cur") is not None


def test_concurrent_writers_lose_no_records(tmp_path):
    path = tmp_path / "t.jsonl"
    errors = []

    def writer(wid):
        try:
            s = TuningStore(path)  # own instance == own fd == real contention
            for i in range(5):
                s.put(_rec(f"w{wid}-{i}"))
        except Exception as e:  # pragma: no cover - the assertion target
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(w,)) for w in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    final = TuningStore(path)
    assert len(final) == 40  # read-merge-replace under flock: nothing lost


def test_compact_keeps_newest_record_per_key(tmp_path):
    """An append-mode history (duplicate keys, stale schemas) compacts down
    to one line per live key, newest winning, under the atomic rewrite."""
    path = tmp_path / "t.jsonl"
    lines = []
    for gen in range(5):
        for k in range(4):
            lines.append(json.dumps(dict(
                schema=SCHEMA_VERSION, key=f"k{k}", gen=gen)))
    lines.append(json.dumps(dict(schema=SCHEMA_VERSION - 1, key="old")))
    lines.append("not json at all")
    path.write_text("\n".join(lines) + "\n")
    store = TuningStore(path)
    assert len(store) == 4  # live view already dedups (last line wins)
    removed = store.compact()
    # the other-schema record survives the rewrite (only dup/garbage lines
    # are reclaimed): 22 lines -> 4 live + 1 foreign
    assert removed == 22 - 4 - 1
    on_disk = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(on_disk) == 5
    assert {r["key"]: r["gen"] for r in on_disk
            if r["schema"] == SCHEMA_VERSION} == {
        f"k{k}": 4 for k in range(4)}
    assert any(r["schema"] == SCHEMA_VERSION - 1 and r["key"] == "old"
               for r in on_disk)
    # still a fully valid store afterwards
    assert TuningStore(path).get("k2")["gen"] == 4


def test_compact_auto_triggers_past_line_threshold(tmp_path):
    path = tmp_path / "t.jsonl"
    lines = [json.dumps(dict(schema=SCHEMA_VERSION, key=f"k{i % 3}", i=i))
             for i in range(40)]
    path.write_text("\n".join(lines) + "\n")
    store = TuningStore(path, compact_threshold=10)
    assert store.get("k0") is not None  # any read triggers the reload
    assert len(path.read_text().splitlines()) == 3  # rewritten compacted
    # below the threshold nothing rewrites (no gratuitous churn)
    small = tmp_path / "s.jsonl"
    small.write_text("\n".join(lines[:6]) + "\n")
    s2 = TuningStore(small, compact_threshold=10)
    assert s2.get("k1") is not None
    assert len(small.read_text().splitlines()) == 6


def test_compact_empty_and_missing_store(tmp_path):
    path = tmp_path / "missing.jsonl"
    store = TuningStore(path)
    assert store.compact() == 0  # no file: a no-op, never a crash
    assert not path.exists()  # ...and nothing fabricated on disk
    store.put(_rec("a"))
    mtime = path.stat().st_mtime_ns
    assert store.compact() == 0  # already compact: no gratuitous rewrite
    assert path.stat().st_mtime_ns == mtime


def test_compact_evicts_by_age(tmp_path, monkeypatch):
    """Records older than RACE_TUNING_MAX_AGE_DAYS (by their ``ts`` write
    stamp) are dropped during compact; fresh ones and foreign-schema lines
    survive the rewrite verbatim."""
    import time as _time

    from repro.tuning.store import eviction_limits

    now = _time.time()
    path = tmp_path / "t.jsonl"
    store = TuningStore(path)
    store.put(dict(_rec("fresh"), ts=now - 3600.0))
    store.put(dict(_rec("stale"), ts=now - 40 * 86400.0))
    store.put(dict(_rec("unstamped")))  # put() stamps ts=now itself
    with open(path, "a") as f:
        f.write(json.dumps(dict(schema=SCHEMA_VERSION - 1, key="old",
                                ts=now - 400 * 86400.0)) + "\n")

    monkeypatch.setenv("RACE_TUNING_MAX_AGE_DAYS", "30")
    assert eviction_limits() == (30 * 86400.0, None)
    s2 = TuningStore(path)
    removed = s2.compact(now=now)
    assert removed == 1
    assert s2.get("stale") is None
    assert s2.get("fresh") is not None and s2.get("unstamped") is not None
    # the ancient foreign line is untouched: not ours to age out
    on_disk = [json.loads(l) for l in path.read_text().splitlines()]
    assert any(r["key"] == "old" for r in on_disk)


def test_compact_evicts_by_size_keeping_newest(tmp_path, monkeypatch):
    import time as _time

    now = _time.time()
    path = tmp_path / "t.jsonl"
    store = TuningStore(path)
    for i in range(6):
        store.put(dict(_rec(f"k{i}"), ts=now - i * 100.0))  # k0 newest
    monkeypatch.setenv("RACE_TUNING_MAX_RECORDS", "2")
    s2 = TuningStore(path)
    removed = s2.compact(now=now)
    assert removed == 4
    assert sorted(TuningStore(path).keys()) == ["k0", "k1"]


def test_compact_unstamped_records_evict_first(tmp_path, monkeypatch):
    """Pre-PR-7 records carry no ``ts``: under a size cap they sort oldest
    (they re-tune once and come back stamped), never shadowing stamped
    records."""
    import time as _time

    now = _time.time()
    path = tmp_path / "t.jsonl"
    lines = [json.dumps(dict(_rec("legacy"), schema=SCHEMA_VERSION)),  # no ts
             json.dumps(dict(_rec("stamped"), schema=SCHEMA_VERSION,
                             ts=now))]
    path.write_text("\n".join(lines) + "\n")
    monkeypatch.setenv("RACE_TUNING_MAX_RECORDS", "1")
    store = TuningStore(path)
    assert store.compact(now=now) == 1
    assert store.get("stamped") is not None
    assert store.get("legacy") is None


def test_eviction_limits_validation(monkeypatch):
    from repro.tuning.store import eviction_limits

    assert eviction_limits() == (None, None)
    monkeypatch.setenv("RACE_TUNING_MAX_AGE_DAYS", "0.5")
    monkeypatch.setenv("RACE_TUNING_MAX_RECORDS", "100")
    assert eviction_limits() == (0.5 * 86400.0, 100)
    monkeypatch.setenv("RACE_TUNING_MAX_RECORDS", "zero")
    with pytest.raises(ValueError):
        eviction_limits()
    monkeypatch.setenv("RACE_TUNING_MAX_RECORDS", "-3")
    with pytest.raises(ValueError):
        eviction_limits()


def test_put_stamps_ts(tmp_path):
    path = tmp_path / "t.jsonl"
    store = TuningStore(path)
    store.put(_rec("a"))
    rec = json.loads(path.read_text().splitlines()[0])
    assert isinstance(rec["ts"], float) and rec["ts"] > 0


def test_store_file_env_knob(tmp_path, monkeypatch):
    monkeypatch.setenv("RACE_TUNING_CACHE", str(tmp_path / "d"))
    assert store_file() == tmp_path / "d" / "tuning.jsonl"
    monkeypatch.setenv("RACE_TUNING_CACHE", str(tmp_path / "f.jsonl"))
    assert store_file() == tmp_path / "f.jsonl"


# ---------------------------------------------------------------------------
# the measured, gated search
# ---------------------------------------------------------------------------


def test_autotune_winner_never_slower_than_default():
    case = _case()
    env = build_env(case)
    dec = autotune(case.program, env, **QUICK)
    assert not dec.from_cache and dec.search_seconds > 0
    assert dec.measurements and all(
        m.status in ("ok", "gated", "error") for m in dec.measurements)
    # the static default is always part of the measured space, so the
    # winner is never slower than it (the acceptance invariant)
    assert dec.tuned_us <= dec.default_us
    assert any(m.config == dec.choice and m.ok for m in dec.measurements)


def test_autotune_correctness_gate_rejects():
    """tolerance=0 keeps only bitwise-faithful candidates: reassociation
    changes summation order, so r3 must be gated and r0 must win."""
    case = _case("calc_tpoints", 12)
    env = build_env(case)
    dec = autotune(case.program, env, tolerance=0.0, **QUICK)
    assert dec.choice.reassociate == 0
    gated = [m for m in dec.measurements if m.status == "gated"]
    assert gated and all(m.rel_err > 0 for m in gated)
    assert all("baseline" in m.detail for m in gated)


def test_autotune_second_call_is_store_hit():
    case = _case()
    env = build_env(case)
    dec1 = autotune(case.program, env, **QUICK)
    dec2 = autotune(case.program, env, **QUICK)
    assert dec2.from_cache and not dec2.measurements
    assert dec2.choice == dec1.choice
    assert dec2.tuned_us == pytest.approx(dec1.tuned_us)
    # force=True re-measures in place
    dec3 = autotune(case.program, env, force=True, **QUICK)
    assert not dec3.from_cache and dec3.measurements


def test_autotune_key_separates_env_signatures():
    case12, case14 = _case(n=12), _case(n=14)
    assert program_hash(case12.program) != program_hash(case14.program)
    env = build_env(case12)
    autotune(case12.program, env, **QUICK)
    env64 = build_env(case12, dtype=np.float64)  # same program, new dtype
    dec = autotune(case12.program, env64, **QUICK)
    assert not dec.from_cache  # dtype is part of the key: fresh search


# ---------------------------------------------------------------------------
# cross-process persistence (the acceptance pin)
# ---------------------------------------------------------------------------

_CHILD = """
import json
import numpy as np
from repro.apps.paper_kernels import get_case
from repro.testing.differential import build_env
from repro.core.race import race
from repro.core.executor import compile_plan
from repro.tuning import autotune

case = get_case("gaussian", 12)
env = build_env(case)
dec = autotune(case.program, env, levels=(0, 3), backends=("xla",),
               repeats=2, warmup=1)
res = race(case.program, reassociate=dec.choice.reassociate)
ex = compile_plan(res.plan, env, "auto")
print(json.dumps(dict(from_cache=dec.from_cache,
                      n_measurements=len(dec.measurements),
                      choice=dec.choice.as_dict(),
                      consulted_backend=ex.backend)))
"""


def test_fresh_subprocess_reuses_decision_without_remeasuring():
    case = _case()
    env = build_env(case)
    dec = autotune(case.program, env, **QUICK)
    assert not dec.from_cache  # this process did the search...
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"},
        timeout=240)
    assert proc.returncode == 0, proc.stderr
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    # ...and a fresh process answers from the on-disk store: no measurement
    assert got["from_cache"] is True
    assert got["n_measurements"] == 0
    assert got["choice"] == dec.choice.as_dict()
    # the serving path applied the stored choice on backend="auto"
    assert got["consulted_backend"] == dec.choice.backend


# ---------------------------------------------------------------------------
# compile_plan consults the store on backend="auto"
# ---------------------------------------------------------------------------


@pytest.mark.pallas
def test_compile_plan_applies_stored_block_config():
    case = _case("gaussian", 14)
    res = race(case.program, reassociate=case.reassociate,
               rewrite_div=case.rewrite_div)
    env = build_env(case)
    sig = env_signature(env)
    key = record_key("plan", plan_hash(res.plan), sig, runtime_fence())
    default_store().put(_rec(key, choice=dict(
        reassociate=case.reassociate, backend="pallas", block_rows=16,
        block_cols=8, block_inner=8)))
    ex = compile_plan(res.plan, env, "auto")
    assert ex.backend == "pallas"
    assert (ex.block_rows, ex.block_inner) == (16, 8)
    # the tuned executor still computes the right answer
    want = compile_plan(res.plan, env, "xla")(env)
    got = ex(env)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-5, err_msg=k)
    # explicit backend requests bypass the store entirely
    assert compile_plan(res.plan, env, "pallas").block_rows == 8


@pytest.mark.pallas
def test_stored_choice_keyed_by_order():
    """The tuner measures one chip's executors, which iterate as laid: its
    record answers an as-laid compile and never a level-major one (a
    sharded executor's local kernel), which keeps the static default."""
    case = _case("gaussian", 14)
    res = race(case.program, reassociate=case.reassociate,
               rewrite_div=case.rewrite_div)
    env = build_env(case)
    sig = env_signature(env)
    key = record_key("plan", plan_hash(res.plan), sig, runtime_fence())
    assert key == record_key("plan", plan_hash(res.plan), sig,
                             runtime_fence(), order=ORDER_AS_LAID)
    assert key != record_key("plan", plan_hash(res.plan), sig,
                             runtime_fence(), order=ORDER_LEVEL_MAJOR)
    default_store().put(_rec(key, choice=dict(
        reassociate=case.reassociate, backend="pallas", block_rows=16,
        block_cols=8, block_inner=8)))
    laid = compile_plan(res.plan, env, "auto")
    assert (laid.order, laid.block_rows, laid.block_inner) == (
        ORDER_AS_LAID, 16, 8)
    lm = compile_plan(res.plan, env, "auto", order=ORDER_LEVEL_MAJOR)
    assert (lm.order, lm.block_rows, lm.block_inner) == (
        ORDER_LEVEL_MAJOR, 8, 0)


@pytest.mark.pallas
def test_compile_plan_degrades_on_stale_block_config():
    """A stored Pallas choice whose blocks cannot hold the plan's halo (a
    hand-edited or bit-rotted record) must degrade to the static default —
    the store contract is 'bad records re-tune', never a serving crash."""
    case = _case("gaussian", 14)
    res = race(case.program, reassociate=case.reassociate,
               rewrite_div=case.rewrite_div)
    env = build_env(case)
    sig = env_signature(env)
    key = record_key("plan", plan_hash(res.plan), sig, runtime_fence())
    default_store().put(_rec(key, choice=dict(
        reassociate=case.reassociate, backend="pallas", block_rows=1,
        block_cols=8, block_inner=0)))
    ex = compile_plan(res.plan, env, "auto")  # must not raise
    assert ex.backend == "pallas"
    assert ex.block_rows == 8  # the static default, not the stale record
    want = compile_plan(res.plan, env, "xla")(env)
    got = ex(env)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-5, err_msg=k)


def test_compile_plan_ignores_infeasible_stored_choice():
    """A stale/corrupt record claiming Pallas for an ineligible plan must
    degrade to the probe's choice, not crash the serving path."""
    loops, (i, j) = loopnest(("i", 1, 6), ("j", 1, 6))
    out = arr("out")
    res = race(program(loops, [(out[i, j], mul(Scalar("s"), 2.0))]))
    env = {"s": np.float32(0.5)}
    sig = env_signature(env)
    key = record_key("plan", plan_hash(res.plan), sig, runtime_fence())
    default_store().put(_rec(key, choice=dict(
        reassociate=0, backend="pallas", block_rows=8, block_cols=8,
        block_inner=0)))
    ex = compile_plan(res.plan, env, "auto")
    assert ex.backend == "xla"
    np.testing.assert_allclose(np.asarray(ex(env)["out"]), 1.0)


# ---------------------------------------------------------------------------
# the tune wiring: RaceResult.tune, race(tune=...), @race_kernel(tune=...)
# ---------------------------------------------------------------------------


def test_raceresult_tune_applies_winner():
    case = _case()
    env = build_env(case)
    res = race(case.program, reassociate=case.reassociate,
               rewrite_div=case.rewrite_div)
    dec = res.tune(env, **QUICK)
    assert dec.choice.reassociate in (0, 3)
    want = res.run(env, "xla")  # explicit backend: the untuned path
    got = res.run(env)  # no backend: the tuned winner
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k], np.float64),
                                   np.asarray(want[k], np.float64),
                                   rtol=1e-4, atol=1e-4, err_msg=k)


def test_race_tune_flag_tunes_on_first_run():
    case = _case()
    env = build_env(case)
    res = race(case.program, tune=dict(QUICK))
    got = res.run(env)  # triggers the search (or a store hit) transparently
    assert res._tuned  # the decision is remembered per env signature
    (dec, _target), = res._tuned.values()
    assert dec.choice.backend == "xla"
    want = race(case.program, reassociate=dec.choice.reassociate).run(
        env, "xla")
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k], np.float64),
                                   np.asarray(want[k], np.float64),
                                   rtol=1e-6, err_msg=k)
    # a second result for the same program answers from the store
    res2 = race(case.program, tune=dict(QUICK))
    res2.run(env)
    (dec2, _), = res2._tuned.values()
    assert dec2.from_cache


def test_race_kernel_tune_decorator():
    from repro.frontend import race_kernel

    @race_kernel(tune=dict(QUICK))
    def blur(u, out):
        n, m = u.shape
        for i in range(1, n - 1):
            for j in range(1, m - 1):
                out[i, j] = (u[i - 1, j] + u[i + 1, j]
                             + u[i, j - 1] + u[i, j + 1]) / 4.0

    rng = np.random.default_rng(0)
    env = {"u": rng.random((16, 16), dtype=np.float32),
           "out": np.zeros((16, 16), np.float32)}
    got = blur.run(env)
    want = blur.run(env, backend="xla")
    np.testing.assert_allclose(np.asarray(got["out"], np.float64),
                               np.asarray(want["out"], np.float64),
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# executor-layer env knobs
# ---------------------------------------------------------------------------


def test_executor_cache_size_env_knob(monkeypatch):
    monkeypatch.setenv("RACE_EXECUTOR_CACHE_SIZE", "2")
    cache = ExecutorCache()  # capacity comes from the env knob
    assert cache.maxsize == 2
    case = _case()
    res = race(case.program)
    for dt in (np.float32, np.float64, np.float16):
        compile_plan(res.plan, build_env(case, dtype=dt), "xla", cache=cache)
    info = cache.cache_info()
    assert info["maxsize"] == 2 and info["currsize"] == 2
    assert info["evictions"] == 1 and info["misses"] == 3


def test_executor_cache_size_env_knob_rejects_garbage(monkeypatch):
    monkeypatch.setenv("RACE_EXECUTOR_CACHE_SIZE", "zero")
    with pytest.raises(ValueError, match="RACE_EXECUTOR_CACHE_SIZE"):
        ExecutorCache()
    monkeypatch.setenv("RACE_EXECUTOR_CACHE_SIZE", "0")
    with pytest.raises(ValueError, match=">= 1"):
        ExecutorCache()


def test_race_backend_env_knob(monkeypatch):
    case = _case()
    monkeypatch.setenv("RACE_BACKEND", "xla")
    assert default_backend() == "xla"
    res = race(case.program)  # no explicit backend: the knob decides
    assert res.options["backend"] == "xla"
    assert res.select_backend().backend == "xla"
    # explicit caller choice always wins over the knob
    assert race(case.program, backend="auto").options["backend"] == "auto"
    monkeypatch.setenv("RACE_BACKEND", "vulkan")
    with pytest.raises(ValueError, match="RACE_BACKEND"):
        race(case.program)


# ---------------------------------------------------------------------------
# the innermost-tile axis (block_inner)
# ---------------------------------------------------------------------------


@pytest.mark.pallas
@pytest.mark.parametrize("name,n,bi", [("gaussian", 24, 8), ("psinv", 12, 4)])
def test_block_inner_differentially_correct(name, n, bi):
    report = run_case(get_case(name, n), reassociate_levels=(0, 3),
                      block_inner=bi)
    assert not report.failures()
    assert report.pallas_covered()


# ---------------------------------------------------------------------------
# regression pins for the five tuning-layer bugs (ISSUE 6)
# ---------------------------------------------------------------------------


def test_tune_on_esr_result_rebuilds_non_esr_target():
    """Bug 1: ``RaceResult.tune()`` on an ``esr=True`` result used to forward
    ``esr`` into the tuner's rebuilds, so the measured candidates (and the
    applied winner) silently ran the every-statement-reuse *baseline* instead
    of RACE proper.  The ESR flag is a comparison baseline, never a tuning
    dimension: tune must rebuild a non-ESR target."""
    case = _case()
    env = build_env(case)
    res = race(case.program, esr=True)
    dec = res.tune(env, **QUICK)  # must not raise, must not measure ESR
    assert dec.choice.backend == "xla"
    ((_, target),) = res._tuned.values()
    assert target.options["esr"] is False
    assert target is not res
    want = race(case.program, reassociate=dec.choice.reassociate).run(
        env, "xla")
    got = res.run(env)  # routed through the rebuilt non-ESR target
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k], np.float64),
                                   np.asarray(want[k], np.float64),
                                   rtol=1e-4, err_msg=k)


def test_run_batch_traceable_under_jit_and_grad():
    """Bug 2: ``run_batch`` eagerly host-transferred the stacked batch to
    build the tuning example (``np.asarray`` on a tracer), so any ``jit`` or
    ``grad`` around it raised ``TracerArrayConversionError``.  The example
    must be built lazily, only when a tune is actually triggered."""
    import jax
    import jax.numpy as jnp

    case = _case("gaussian", 12)
    res = race(case.program, reassociate=case.reassociate,
               rewrite_div=case.rewrite_div)
    env = build_env(case)
    stacked = {k: jnp.stack([jnp.asarray(v)] * 3) for k, v in env.items()}
    out = jax.jit(lambda s: res.run_batch(s, "xla"))(stacked)  # the pin
    want = res.run(env, "xla")
    for k in want:
        np.testing.assert_allclose(np.asarray(out[k][0]), np.asarray(want[k]),
                                   rtol=1e-6, err_msg=k)
    # gradients flow through the batched path too
    arr_key = next(k for k, v in env.items()
                   if np.asarray(v).ndim and k in want)

    def loss(s):
        return jnp.sum(jnp.asarray(res.run_batch(s, "xla")[arr_key]))

    g = jax.grad(lambda a: loss({**stacked, arr_key: a}))(stacked[arr_key])
    assert np.isfinite(np.asarray(g)).all()


def test_program_store_key_includes_search_options():
    """Bug 3: the program-level store key ignored the search-shaping options,
    so a narrowed search (``backends=("xla",)``, fewer levels, ...) recorded
    a decision that silently answered later full-space requests."""
    case = _case()
    env = build_env(case)
    d1 = autotune(case.program, env, levels=(0, 3), backends=None,
                  repeats=2, warmup=1, quick=True)
    assert not d1.from_cache
    d2 = autotune(case.program, env, **QUICK)  # narrower: backends=("xla",)
    assert not d2.from_cache  # must NOT be answered by the wider record
    assert d2.key != d1.key
    # both records persist independently and each re-hits its own search
    assert autotune(case.program, env, levels=(0, 3), backends=None,
                    repeats=2, warmup=1, quick=True).from_cache
    assert autotune(case.program, env, **QUICK).from_cache


def test_store_rewrites_preserve_foreign_schema_lines(tmp_path):
    """Bug 4: ``put``/``compact`` rewrote the file from the current-schema
    record view only, deleting every record owned by another library version
    sharing the store file.  Foreign-schema lines must round-trip verbatim
    (deduped by their own (schema, key))."""
    path = tmp_path / "t.jsonl"
    future_old = json.dumps(dict(schema=SCHEMA_VERSION + 1, key="f", gen=0))
    future_new = json.dumps(dict(schema=SCHEMA_VERSION + 1, key="f", gen=1))
    legacy = json.dumps(dict(schema=0, key="l", data="legacy"))
    path.write_text("\n".join([future_old, future_new, legacy]) + "\n")
    s = TuningStore(path)
    assert len(s) == 0  # foreign records stay invisible to this version...
    s.put(_rec("mine"))  # ...but a rewrite must not destroy them
    on_disk = [json.loads(x) for x in path.read_text().splitlines()]
    by_schema_key = {(r["schema"], r["key"]): r for r in on_disk}
    assert (SCHEMA_VERSION + 1, "f") in by_schema_key
    assert by_schema_key[(SCHEMA_VERSION + 1, "f")]["gen"] == 1  # deduped
    assert (0, "l") in by_schema_key
    assert (SCHEMA_VERSION, "mine") in by_schema_key
    assert len(on_disk) == 3
    # compaction keeps them too, and doesn't loop re-removing them
    assert s.compact() == 0
    assert TuningStore(path).get("mine") is not None
    assert len(path.read_text().splitlines()) == 3


def test_noise_margin_tie_rule_shared_by_program_and_plan_records():
    """Bug 5: the noise-margin tie fallback was duplicated between ``_pick``
    and the per-plan record loop (and had started to drift).  Both sites now
    share ``_prefer_default``: with a total noise margin every winner ties,
    so the program record AND every plan record must keep their defaults."""
    from repro.core.executor import env_signature, plan_hash
    from repro.tuning.measure import Measurement
    from repro.tuning.space import Config
    from repro.tuning.tuner import _prefer_default

    # the helper itself: beat-the-margin wins, tie keeps default
    fast = Measurement(Config(3, "xla"), "ok", us=50.0)
    dflt = Measurement(Config(0, "xla"), "ok", us=100.0)
    close = Measurement(Config(3, "xla"), "ok", us=99.0)
    assert _prefer_default(fast, dflt, dflt.config, 0.03) is fast
    assert _prefer_default(close, dflt, dflt.config, 0.03) is dflt
    assert _prefer_default(fast, None, dflt.config, 0.03) is fast

    # both call sites, end to end: noise_margin=1.0 makes every tie
    case = _case()
    env = build_env(case)
    dec = autotune(case.program, env, noise_margin=1.0, **QUICK)
    assert dec.choice == dec.default
    sig = env_signature(env)
    s = default_store()
    for lvl in (0, 3):
        res = race(case.program, reassociate=lvl)
        rec = s.get(record_key("plan", plan_hash(res.plan), sig,
                               runtime_fence()))
        if rec is not None:  # per-plan record: same conservative rule
            assert rec["choice"]["backend"] == "xla"
            assert rec["choice"]["reassociate"] == lvl


@pytest.mark.pallas
def test_block_inner_is_part_of_the_executor_key():
    case = _case("gaussian", 14)
    res = race(case.program, reassociate=case.reassociate,
               rewrite_div=case.rewrite_div)
    env = build_env(case)
    full = compile_plan(res.plan, env, "pallas")
    tiled = compile_plan(res.plan, env, "pallas", block_inner=8)
    assert full is not tiled  # distinct specializations, both cached
    assert compile_plan(res.plan, env, "pallas", block_inner=8) is tiled
    for k, v in full(env).items():
        np.testing.assert_allclose(np.asarray(tiled(env)[k]), np.asarray(v),
                                   rtol=1e-6, err_msg=k)
