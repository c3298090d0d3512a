"""The observability layer (PR 7): metrics, spans, structured events.

Four contracts under test:

  * the primitives — thread-safe registry, fixed log-bucket histograms,
    nested spans, bounded event ring + JSONL sink (degrading, never fatal);
  * the span timeline and its Chrome-trace export (``repro.obs.trace``,
    ``report --trace-out``) — a real pipeline's nested spans reconstruct as
    containment-consistent "X" events, the disabled path records nothing;
  * the zero-cost disabled path — with ``RACE_OBS`` unset every
    instrumentation site is a no-op: the shared ``NOOP_SPAN``, no registry
    series, no ring entries, and no measurable per-call cost added to
    ``CompiledRace.run``;
  * the "never silent" integration — every pipeline decision (capability
    fallback, adjoint refusal, frontend diagnostic, tuning gate, executor
    cache build/evict) emits exactly its structured event when enabled.
"""
import json
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.apps.paper_kernels import get_case
from repro.core.adjoint import adjoint_build
from repro.core.backend import select_backend
from repro.core.executor import (ExecutorCache, clear_cache, compile_plan,
                                 configure_cache, executor_cache, plan_hash,
                                 stack_envs)
from repro.core.ir import Program, arr, loopnest, program
from repro.core.race import race
from repro.frontend import D_CONTROL_FLOW, CaptureError, capture
from repro.obs import report
from repro.obs.metrics import DEFAULT_BUCKETS, Histogram, Registry
from repro.obs.trace import chrome_trace
from repro.testing.differential import build_env
from repro.tuning.measure import measure_candidate
from repro.tuning.space import Config

pytestmark = pytest.mark.obs


def _enable(**kw):
    obs.configure(enabled=True, **kw)


# ---------------------------------------------------------------------------
# primitives: registry, histogram, spans, events
# ---------------------------------------------------------------------------


def test_registry_counter_thread_safety():
    reg = Registry()
    n_threads, n_incs = 8, 1000

    def worker():
        for _ in range(n_incs):
            reg.counter("c", plan="p").inc()

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.counter("c", plan="p").value == n_threads * n_incs


def test_registry_series_identity_and_label_order():
    reg = Registry()
    a = reg.counter("c", x="1", y="2")
    b = reg.counter("c", y="2", x="1")  # label order must not matter
    assert a is b
    assert reg.counter("c", x="1", y="3") is not a


def test_histogram_bucket_edges():
    h = Histogram(edges=(1.0, 10.0, 100.0))
    # bisect_left places a value exactly on an edge in that edge's bucket
    for v in (0.5, 1.0):
        h.observe(v)
    h.observe(10.0)
    h.observe(99.0)
    h.observe(1e6)  # overflow
    assert h.bucket_counts() == [2, 1, 1, 1]
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["min"] == 0.5 and snap["max"] == 1e6
    # bucket-resolution estimate: the 3rd of 5 observations lands in the
    # (1.0, 10.0] bucket, whose upper edge is the reported quantile
    assert h.quantile(0.5) == 10.0


def test_default_buckets_span_1us_to_100s():
    assert DEFAULT_BUCKETS[0] == pytest.approx(1e-6)
    assert DEFAULT_BUCKETS[-1] == pytest.approx(100.0)
    assert list(DEFAULT_BUCKETS) == sorted(set(DEFAULT_BUCKETS))
    assert len(DEFAULT_BUCKETS) == 33  # quarter-decade over 8 decades


def test_histogram_rejects_unsorted_edges():
    with pytest.raises(ValueError):
        Histogram(edges=(1.0, 1.0, 2.0))


def test_span_nesting_records_leaf_and_path():
    _enable()
    with obs.span("a"):
        assert obs.current_path() == "a"
        with obs.span("b"):
            assert obs.current_path() == "a/b"
            time.sleep(0.001)
    assert obs.current_path() == ""  # the stack drains
    snap = obs.snapshot()
    series = snap["histograms"]
    assert any("span=a" in s for s in series)
    inner = [s for s in series if "span=b" in s]
    assert inner and all("path=a/b" in s for s in inner)
    summary = obs.span_summary()
    assert summary["b"]["count"] == 1
    assert summary["b"]["total_s"] >= 0.001


def test_span_records_on_exception():
    _enable()
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    assert obs.span_summary()["boom"]["count"] == 1
    assert obs.current_path() == ""


def test_event_jsonl_roundtrip(tmp_path):
    sink = tmp_path / "events.jsonl"
    _enable(events_path=str(sink))
    obs.event("tuning_gate", status="ok", plan="abcd", rel_err=1e-9)
    obs.event("backend_fallback", plan="abcd", reasons=["strided-aux: x"])
    ring = obs.events()
    assert [e["seq"] for e in ring] == [1, 2]
    loaded = obs.load_jsonl(sink)
    assert loaded == ring  # the sink is the ring, durably
    assert obs.events(kind="tuning_gate")[0]["status"] == "ok"
    assert obs.event_log().counts() == {"tuning_gate": 1,
                                        "backend_fallback": 1}


def test_event_ring_is_bounded(monkeypatch):
    monkeypatch.setenv(obs.ENV_RING, "4")
    monkeypatch.setenv(obs.ENV_OBS, "1")
    obs.reset()
    for i in range(10):
        obs.event("k", i=i)
    evs = obs.events()
    assert len(evs) == 4
    assert [e["i"] for e in evs] == [6, 7, 8, 9]
    assert evs[-1]["seq"] == 10  # seq keeps counting past evictions


def test_broken_sink_degrades_to_ring_only(tmp_path):
    _enable(events_path=str(tmp_path / "no-such-dir" / "e.jsonl"))
    obs.event("k", i=0)
    obs.event("k", i=1)
    log = obs.event_log()
    assert log.sink_errors == 1
    assert log.sink_path is None  # sink detached, not retried per event
    assert len(obs.events()) == 2  # the ring kept everything


def test_event_coerces_non_json_fields():
    _enable()
    ev = obs.event("k", arr=np.float32(1.5), tup=(1, 2), obj=object())
    assert ev["arr"] == "1.5" or ev["arr"] == 1.5
    assert ev["tup"] == [1, 2]
    assert isinstance(ev["obj"], str)
    json.dumps(ev)  # must be serializable as emitted


def test_prometheus_exposition():
    _enable()
    obs.counter("race_builds_total", reassociate="3").inc()
    obs.gauge("race_reduced_ops", plan="ab").set(0.5)
    obs.histogram("race_span_seconds", span="run", path="run").observe(1e-4)
    text = obs.render_prometheus()
    assert "# TYPE race_builds_total counter" in text
    assert 'race_builds_total{reassociate="3"} 1' in text
    assert "# TYPE race_reduced_ops gauge" in text
    assert "# TYPE race_span_seconds histogram" in text
    assert 'le="+Inf"} 1' in text
    assert "race_span_seconds_count" in text
    # cumulative buckets: the +Inf count equals _count
    doc = json.loads(obs.render_json())
    assert doc["counters"]['race_builds_total{reassociate=3}'] == 1


def test_prometheus_label_value_escaping():
    """Exposition-format escaping: backslash, double quote, newline.  Plan
    hashes, file paths, and diagnostic strings flow into label values — an
    unescaped quote or newline silently corrupts the whole scrape."""
    _enable()
    obs.counter("c", path='a"b\\c\nd').inc()
    text = obs.render_prometheus()
    assert 'c{path="a\\"b\\\\c\\nd"} 1' in text
    # the raw newline must never appear: every series stays on one line
    for line in text.splitlines():
        assert line.startswith(("#", "c{"))


def test_prometheus_histogram_buckets_are_cumulative_monotone():
    _enable()
    h = obs.histogram("race_span_seconds", span="run", path="run")
    for v in (5e-7, 1e-4, 1e-4, 0.5, 200.0):  # incl. under- and overflow
        h.observe(v)
    text = obs.render_prometheus()
    counts = []
    for line in text.splitlines():
        if line.startswith("race_span_seconds_bucket"):
            counts.append(int(line.rsplit(" ", 1)[1]))
    assert len(counts) >= 2
    assert counts == sorted(counts)  # cumulative => non-decreasing
    assert counts[-1] == 5  # le="+Inf" covers every observation
    assert "race_span_seconds_count" in text


def test_snapshot_label_filter():
    _enable()
    obs.counter("c", plan="a").inc()
    obs.counter("c", plan="b").inc(2)
    snap = obs.snapshot(label_filter={"plan": "a"})
    assert list(snap["counters"]) == ["c{plan=a}"]


def test_configure_keeps_history_reset_drops_it():
    _enable()
    obs.counter("c").inc()
    obs.event("k")
    obs.configure(ring=8)  # swap the log, keep history + metrics
    assert len(obs.events()) == 1
    assert obs.metrics().counter("c").value == 1
    obs.reset()
    assert obs.events() == []
    assert obs.metrics().counter("c").value == 0
    assert not obs.enabled()  # env is clean under the autouse fixture


# ---------------------------------------------------------------------------
# the disabled path is a no-op
# ---------------------------------------------------------------------------


def test_disabled_primitives_are_noops():
    assert not obs.enabled()
    s = obs.span("detect", plan="x")
    assert s is obs.NOOP_SPAN  # one shared object, no allocation
    assert obs.span("run") is s
    with s:
        pass
    assert obs.event("k", a=1) is None
    assert obs.events() == []
    snap = obs.snapshot()
    assert snap["histograms"] == {} and snap["counters"] == {}


def test_disabled_run_adds_no_telemetry_state():
    """``RACE_OBS=0`` end to end: a full compile + serve loop must leave the
    registry and the event ring exactly empty."""
    assert not obs.enabled()
    case = get_case("gaussian", 16)
    res = race(case.program, reassociate=case.reassociate)
    env = build_env(case)
    clear_cache()
    for _ in range(5):
        res.run(env, "xla")
    snap = obs.snapshot()
    assert snap["counters"] == {}
    assert snap["gauges"] == {}
    assert snap["histograms"] == {}
    assert obs.events() == []
    tel = res.telemetry()
    assert tel["obs_enabled"] is False
    assert "metrics" not in tel and "events" not in tel


def test_disabled_call_sites_are_cheap():
    """The per-call cost of a disabled instrumentation site stays in the
    sub-microsecond regime (generous 20us/call ceiling so a noisy CI box
    can't flake this, while a regression to real work — allocation, lock,
    clock read per call — still trips it)."""
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        if not obs.enabled():
            pass
    t_flag = (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("run", plan="x", backend="xla"):
            pass
        obs.event("k", a=1)
    t_site = (time.perf_counter() - t0) / n
    assert t_flag < 20e-6
    assert t_site < 20e-6


# ---------------------------------------------------------------------------
# integration: one structured event per pipeline decision
# ---------------------------------------------------------------------------


def _strided_program(tag: str):
    """A unique (per ``tag``) 1-D program with a strided read — refused by
    both the adjoint detector and the Pallas capability probe, and unique
    program hash so memoized paths still emit their event."""
    loops, (i,) = loopnest(("i", 1, 16))
    u, out = arr(f"u_{tag}"), arr(f"o_{tag}")
    return program(loops, [(out[i], u[2 * i] + u[i])])


def test_executor_cache_counters_and_events():
    _enable()
    case = get_case("gaussian", 16)
    res = race(case.program, reassociate=case.reassociate)
    env = build_env(case)
    clear_cache()
    ph = plan_hash(res.plan)
    compile_plan(res.plan, env, "xla")  # miss
    compile_plan(res.plan, env, "xla")  # hit
    snap = obs.snapshot()
    assert snap["counters"][
        f"race_executor_cache_total{{event=miss,plan={ph}}}"] == 1
    assert snap["counters"][
        f"race_executor_cache_total{{event=hit,plan={ph}}}"] == 1
    builds = obs.events(kind="executor_build")
    assert len(builds) == 1 and builds[0]["plan"] == ph
    assert builds[0]["backend"] == "xla"


def test_executor_evict_event():
    _enable()
    case = get_case("gaussian", 16)
    r0 = race(case.program, reassociate=0)
    r3 = race(case.program, reassociate=3)
    env = build_env(case)
    clear_cache()
    try:
        configure_cache(1)
        compile_plan(r0.plan, env, "xla")
        compile_plan(r3.plan, env, "xla")  # evicts the r0 executor
        evs = obs.events(kind="executor_evict")
        assert len(evs) == 1
        assert evs[0]["plan"] == plan_hash(r0.plan)
        snap = obs.snapshot()
        assert snap["counters"][
            "race_executor_cache_total"
            f"{{event=evict,plan={plan_hash(r0.plan)}}}"] == 1
    finally:
        configure_cache(128)
        clear_cache()


def test_executor_run_spans_and_counters():
    _enable()
    case = get_case("gaussian", 16)
    res = race(case.program, reassociate=case.reassociate)
    env = build_env(case)
    clear_cache()
    ex = compile_plan(res.plan, env, "xla")
    for _ in range(3):
        ex(env)
    summary = obs.span_summary()
    assert summary["lower"]["count"] == 1
    assert summary["compile"]["count"] == 1  # the one specialization
    assert summary["dispatch"]["count"] == 3  # every call
    ph = plan_hash(res.plan)
    snap = obs.snapshot()
    assert snap["counters"][
        f"race_compile_cache_total{{backend=xla,batch=0,event=compile,"
        f"plan={ph}}}"] == 1


def test_race_spans_gauges_and_telemetry():
    _enable()
    case = get_case("gaussian", 16)
    res = race(case.program, reassociate=case.reassociate)
    summary = obs.span_summary()
    assert summary["detect"]["count"] == 1
    assert summary["contract"]["count"] == 1
    tel = res.telemetry()
    assert tel["obs_enabled"] is True
    assert tel["plan"] == plan_hash(res.plan)
    assert 0.0 < tel["reduced_ops"] < 1.0
    gauges = tel["metrics"]["gauges"]
    assert any(s.startswith("race_reduced_ops") for s in gauges)
    # the label filter scopes the view to this plan alone
    for series in tel["metrics"]["counters"]:
        assert f"plan={tel['plan']}" in series or "plan=" not in series


def test_kernel_read_factor_gauge():
    """Specializing a Pallas kernel sets one ``race_kernel_read_factor``
    gauge per operand: the halo'd operand's window over its tile, 1.0 for
    the operand read at the centre only."""
    from repro.lowering import specialize_stencil

    _enable()
    case = get_case("psinv", 18)
    res = race(case.program, reassociate=case.reassociate)
    env = build_env(case, np.float32)
    spec = specialize_stencil(
        res.plan, {k: np.shape(v) for k, v in env.items()},
        {k: np.asarray(v).dtype for k, v in env.items()})
    gauges = obs.snapshot()["gauges"]
    label = res.plan.program.label
    for nm, factor in spec.read_factor.items():
        assert gauges[f"race_kernel_read_factor{{array={nm},program={label}}}"
                      ] == factor
    assert gauges[f"race_kernel_read_factor{{array=U,program={label}}}"] == 1.0


def test_kernel_level_major_gauge():
    """``race_kernel_level_major{program}`` says which order a Pallas
    specialization iterates in: 0 as laid (one chip's executors), 1
    level-major (a sharded executor's local kernel)."""
    from repro.lowering import ORDERS, specialize_stencil

    _enable()
    case = get_case("psinv", 18)
    res = race(case.program, reassociate=case.reassociate)
    env = build_env(case, np.float32)
    series = ("race_kernel_level_major"
              f"{{program={res.plan.program.label}}}")
    for order, want in zip(ORDERS, (0.0, 1.0)):
        spec = specialize_stencil(
            res.plan, {k: np.shape(v) for k, v in env.items()},
            {k: np.asarray(v).dtype for k, v in env.items()}, order=order)
        assert spec.order == order
        assert obs.snapshot()["gauges"][series] == want


def test_backend_fallback_event():
    _enable()
    res = race(_strided_program("bf"))
    sel = select_backend(res.plan, "auto")
    assert sel.backend == "xla"  # the probe refused pallas
    evs = obs.events(kind="backend_fallback")
    assert len(evs) == 1
    ev = evs[0]
    assert ev["plan"] == plan_hash(res.plan)
    assert ev["requested"] == "auto" and ev["backend"] == "xla"
    assert ev["codes"] and ev["reasons"]
    snap = obs.snapshot()
    assert snap["counters"][
        "race_backend_selections_total{backend=xla,requested=auto}"] == 1


def test_lowering_facts_event_on_eligible_plan():
    _enable()
    # a 1-D nest is eligible only through the depth-generalization
    # envelope — the probe records a "depth" fact, which must be emitted
    loops, (i,) = loopnest(("i", 2, 30))
    u, out = arr("u_lf"), arr("o_lf")
    res = race(program(loops, [(out[i], (u[i - 1] + u[i]) + u[i + 1])]),
               reassociate=3)
    sel = select_backend(res.plan, "auto")
    assert sel.backend == "pallas"
    evs = obs.events(kind="lowering_facts")
    assert evs and evs[-1]["plan"] == plan_hash(res.plan)
    assert "depth" in evs[-1]["codes"]


def test_adjoint_refusal_event():
    _enable()
    prog = _strided_program("adj")  # unique hash: the memo can't swallow it
    build = adjoint_build(prog)
    assert not build.ok
    evs = obs.events(kind="adjoint_refusal")
    assert len(evs) == 1
    # the event carries the detector's structured reason code verbatim
    assert evs[0]["reason"] and evs[0]["reason"] in build.reason
    snap = obs.snapshot()
    assert snap["counters"][
        "race_adjoint_builds_total{outcome=refused}"] == 1


def test_frontend_diagnostic_event():
    _enable()

    def bad(u, out):
        n, m = u.shape
        for i in range(1, n):
            for j in range(1, m):
                if j > 2:  # control flow: refused with D_CONTROL_FLOW
                    out[i, j] = u[i, j]

    with pytest.raises(CaptureError):
        capture(bad, {"u": (8, 8), "out": (8, 8)})
    evs = obs.events(kind="frontend_diagnostic")
    assert len(evs) == 1
    assert evs[0]["code"] == D_CONTROL_FLOW
    assert evs[0]["function"] == "bad"
    snap = obs.snapshot()
    assert snap["counters"][
        f"race_frontend_diagnostics_total{{code={D_CONTROL_FLOW}}}"] == 1


def test_frontend_capture_success_counts():
    _enable()

    def ok(u, out):
        n, m = u.shape
        for i in range(1, n - 1):
            for j in range(1, m - 1):
                out[i, j] = u[i - 1, j] + u[i + 1, j]

    capture(ok, {"u": (8, 8), "out": (8, 8)})
    snap = obs.snapshot()
    assert snap["counters"]["race_frontend_captures_total"] == 1
    assert obs.span_summary()["capture"]["count"] == 1


def test_tuning_gate_event():
    _enable()
    case = get_case("gaussian", 16)
    res = race(case.program, reassociate=0)
    env = build_env(case)
    truth = {k: np.asarray(v) + 1e3  # deliberately wrong baseline
             for k, v in compile_plan(res.plan, env, "xla")(env).items()}
    m = measure_candidate(res.plan, Config(0, "xla"), env, truth, 1e-6)
    assert m.status == "gated"
    evs = obs.events(kind="tuning_gate")
    assert len(evs) == 1
    assert evs[0]["status"] == "gated"
    assert evs[0]["plan"] == plan_hash(res.plan)
    snap = obs.snapshot()
    assert snap["counters"][
        "race_tuning_candidates_total{status=gated}"] == 1


# ---------------------------------------------------------------------------
# dump + report CLI
# ---------------------------------------------------------------------------


def test_dump_and_report_roundtrip(tmp_path, capsys):
    _enable()
    case = get_case("gaussian", 16)
    res = race(case.program, reassociate=case.reassociate)
    env = build_env(case)
    clear_cache()
    res.run(env, "xla")
    path = tmp_path / "dump.json"
    doc = obs.dump(path)
    assert doc["stamp"]["schema"] == obs.OBS_SCHEMA
    assert "T" in doc["stamp"]["ts"]  # ISO-8601 UTC
    on_disk = json.loads(path.read_text())
    assert on_disk["metrics"]["counters"] == doc["metrics"]["counters"]

    rc = report.main([str(path), "--require-spans",
                      "detect,lower,compile"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "require-spans ok" in out
    assert "detect" in out

    rc = report.main([str(path), "--require-spans", "no_such_span"])
    err = capsys.readouterr().err
    assert rc == 2
    assert "MISSING SPANS: no_such_span" in err


def test_report_span_table_merges_label_sets():
    metrics = {"histograms": {
        "race_span_seconds{path=run,span=run}": dict(
            count=2, sum=0.2, edges=[0.1, 1.0], counts=[1, 1, 0], max=0.5),
        "race_span_seconds{path=a/run,span=run}": dict(
            count=1, sum=0.05, edges=[0.1, 1.0], counts=[1, 0, 0], max=0.05),
    }}
    table = report.span_table(metrics)
    assert table["run"]["count"] == 3
    assert table["run"]["total"] == pytest.approx(0.25)
    # the latency columns the report renders all come from the merged
    # buckets; p95 (3 obs, all <= 1.0) resolves to the 1.0 edge
    assert table["run"]["p95"] == pytest.approx(1.0)


def test_run_stamp_fields():
    st = obs.run_stamp()
    assert st["schema"] == obs.OBS_SCHEMA
    assert st["ts"].endswith("+00:00")  # UTC
    assert ":" in st["device"]
    assert st["jax"] not in ("", None)
    # the host signature says where an artifact was made
    assert isinstance(st["host_cpu_count"], int) and st["host_cpu_count"] >= 1
    assert st["host"]


# ---------------------------------------------------------------------------
# spans on the profiler's clock, compile spans, stable device names
# ---------------------------------------------------------------------------


def _profiled_host_events(tmp_path, body) -> list:
    """``[(name, {stat: value})]`` of the ``race.*`` host events that a CPU
    ``jax.profiler`` trace of ``body()`` recorded."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    out = []
    for path in tmp_path.rglob("*.xplane.pb"):
        pd = jax.profiler.ProfileData.from_file(str(path))
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                out.extend((e.name, {k: v for k, v in e.stats})
                           for e in line.events
                           if e.name.startswith("race."))
    return out


def test_span_lands_in_profiler_trace_with_parent_id(tmp_path):
    _enable()

    def body():
        with obs.span("outer", plan="p"):
            with obs.span("inner", attrs=dict(requests="3 4")):
                pass

    events = dict(_profiled_host_events(tmp_path, body))
    assert set(events) == {"race.outer", "race.inner"}
    outer, inner = events["race.outer"], events["race.inner"]
    assert outer["parent"] == 0 and outer["plan"] == "p"
    assert inner["parent"] == outer["id"]
    assert inner["requests"] == "3 4"
    # the timeline records carry the same ids
    recs = {r["name"]: r for r in obs.span_records()}
    assert recs["outer"]["id"] == outer["id"]
    assert recs["inner"]["parent"] == recs["outer"]["id"]
    assert recs["inner"]["attrs"] == {"requests": "3 4"}
    assert "attrs" not in recs["outer"]


def test_disabled_span_reaches_no_profiler_trace(tmp_path):
    assert not obs.enabled()

    def body():
        with obs.span("outer"):
            pass

    assert _profiled_host_events(tmp_path, body) == []


def test_record_span_is_a_child_of_the_open_span():
    _enable()
    t = time.perf_counter()
    with obs.span("batch") as outer:
        rid = obs.record_span("queue", t - 0.002, t, attrs=dict(request=7),
                              plan="p")
    rec = next(r for r in obs.span_records() if r["name"] == "queue")
    assert rec["id"] == rid and rec["parent"] == outer.id
    assert rec["path"] == "batch/queue"
    assert rec["dur_us"] == pytest.approx(2000.0)
    assert rec["attrs"] == {"request": "7"} and rec["labels"] == {"plan": "p"}
    assert obs.span_summary()["queue"]["count"] == 1


def test_record_span_disabled_is_a_noop():
    assert obs.record_span("queue", 0.0, 1.0) is None
    assert obs.span_records() == [] and obs.span_summary() == {}


def _compile_records(ph):
    return [r for r in obs.span_records()
            if r["name"] == "compile" and r["labels"]["plan"] == ph]


def test_compile_span_once_per_specialization():
    """A ``compile`` span for each jit specialization (the single call and
    each batch size), none for a repeated signature, each inside the
    ``dispatch`` span of the call that set it off."""
    _enable()
    case = get_case("gaussian", 12)
    res = race(case.program, reassociate=case.reassociate)
    env = build_env(case)
    clear_cache()
    ex = compile_plan(res.plan, env, "xla")
    ph = plan_hash(res.plan)
    ex.run(env)
    ex.run(env)
    assert [r["labels"]["batch"] for r in _compile_records(ph)] == ["0"]
    ex.run_batch([env, env])
    ex.run_batch([env, env])
    ex.run_batch([env, env, env])
    recs = _compile_records(ph)
    assert [r["labels"]["batch"] for r in recs] == ["0", "2", "3"]
    dispatch = {r["id"]: r for r in obs.span_records()
                if r["name"] == "dispatch"}
    assert len(dispatch) == 5
    for r in recs:
        parent = dispatch[r["parent"]]
        assert parent["labels"]["batch"] == r["labels"]["batch"]
        assert 0 < r["dur_us"] <= parent["dur_us"]
    counters = obs.snapshot()["counters"]
    for b in ("0", "2", "3"):
        assert counters[f"race_compile_cache_total{{backend=xla,batch={b},"
                        f"event=compile,plan={ph}}}"] == 1


def test_stable_module_and_kernel_names():
    """The jitted steps are named after the program and the plan, so the
    XLA modules read ``jit_race_<program>_<plan8>``; the Pallas kernel is
    ``race_kernel_<program>``."""
    case = get_case("gaussian", 12)
    res = race(case.program, reassociate=case.reassociate)
    env = build_env(case)
    ph = plan_hash(res.plan)[:8]
    assert res.plan.program.label == "gaussian"
    ex = compile_plan(res.plan, env, "pallas", cache=ExecutorCache())
    assert ex.name == f"race_gaussian_{ph}"
    ins, outs = ex._split(env)
    low = ex._jit.lower(ins, outs)
    assert low.as_text().startswith(f"module @jit_race_gaussian_{ph} ")
    assert "race_kernel_gaussian" in low.as_text(debug_info=True)
    stacked = stack_envs([env, env])
    assert ex.batch_jit().lower(stacked).as_text().startswith(
        f"module @jit_race_batch_gaussian_{ph} ")


def test_program_label_falls_back_to_written_arrays():
    loops, (i,) = loopnest(("i", 1, 8))
    u, out = arr("u_lbl"), arr("o.lbl")
    prog = program(loops, [(out[i], u[i - 1] + u[i + 1])])
    assert prog.label == "o_lbl"
    named = get_case("psinv", 10).program
    # the name is metadata: equality ignores it
    assert named.label == "psinv"
    assert named == Program(named.loops, named.body)
    assert Program(named.loops, named.body).label == "U"


# ---------------------------------------------------------------------------
# span timeline + Chrome-trace export
# ---------------------------------------------------------------------------


#: run stamp of the hand-built trace documents below
STAMP = {"schema": 1, "device": "cpu:TestCpu", "jax": "0.0.test",
         "host_cpu_count": 1, "host": "test-host"}


def test_span_records_nest_on_shared_time_axis():
    _enable()
    with obs.span("race"):
        with obs.span("detect"):
            pass
        with obs.span("lower", plan="ab12", backend="xla"):
            pass
    recs = obs.span_records()
    assert [r["name"] for r in recs] == ["detect", "lower", "race"]
    by = {r["name"]: r for r in recs}
    assert by["detect"]["path"] == "race/detect"
    assert by["lower"]["labels"] == {"plan": "ab12", "backend": "xla"}
    # children are contained in the parent on the shared ts axis
    for child in ("detect", "lower"):
        c, p = by[child], by["race"]
        assert p["ts_us"] <= c["ts_us"]
        assert c["ts_us"] + c["dur_us"] <= p["ts_us"] + p["dur_us"] + 1e-3
    assert all(r["tid"] == recs[0]["tid"] for r in recs)


def test_span_log_disabled_records_nothing():
    assert not obs.enabled()
    with obs.span("race"):
        pass
    assert obs.span_records() == []


def test_span_log_is_bounded(monkeypatch):
    monkeypatch.setenv(obs.ENV_SPANS, "4")
    monkeypatch.setenv(obs.ENV_OBS, "1")
    obs.reset()
    for i in range(10):
        with obs.span(f"s{i}"):
            pass
    recs = obs.span_records()
    assert [r["name"] for r in recs] == ["s6", "s7", "s8", "s9"]
    assert obs.span_log().dropped == 6


def test_chrome_trace_structure_and_tolerance():
    recs = [
        dict(name="race", path="race", ts_us=0.0, dur_us=100.0, tid=7,
             thread="MainThread", labels={}),
        dict(name="detect", path="race/detect", ts_us=10.0, dur_us=20.0,
             tid=7, thread="MainThread", labels={"plan": "ab"}),
        {"corrupt": "record"},  # skipped, never fatal
    ]
    doc = chrome_trace(recs, stamp=STAMP, origin_epoch=123.0)
    assert doc["displayTimeUnit"] == "ms"
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["race", "detect"]  # ts-sorted
    assert all(e["pid"] == 1 and e["tid"] == 7 for e in xs)
    assert xs[1]["args"] == {"path": "race/detect", "plan": "ab"}
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(e["name"] == "thread_name"
               and e["args"]["name"] == "MainThread" for e in meta)
    assert doc["otherData"]["span_origin_epoch"] == 123.0
    assert doc["otherData"]["device"] == STAMP["device"]
    json.dumps(doc)  # loadable = serializable


def test_pipeline_trace_reconstructs_phase_hierarchy(tmp_path, capsys):
    """The acceptance scenario: a real detect -> lower -> compile -> dispatch
    pipeline dumped and exported via ``report --trace-out`` yields valid
    Chrome trace JSON whose span events carry the nesting paths."""
    from repro.apps.paper_kernels import get_case
    from repro.core.executor import clear_cache
    from repro.core.race import race
    from repro.testing.differential import build_env

    _enable()
    case = get_case("gaussian", 16)
    res = race(case.program, reassociate=case.reassociate)
    clear_cache()
    env = build_env(case)
    res.run(env, "xla")  # cold: lower + compile (inside dispatch) spans
    res.run(env, "xla")  # steady: dispatch span
    dump = tmp_path / "dump.json"
    obs.dump(dump)
    trace = tmp_path / "trace.json"
    rc = report.main([str(dump), "--trace-out", str(trace)])
    assert rc == 0
    assert "trace:" in capsys.readouterr().out
    doc = json.loads(trace.read_text())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    assert {"detect", "lower", "compile", "dispatch"} <= names
    # every event's path terminates in its own leaf name (hierarchy intact)
    for e in xs:
        assert e["args"]["path"].split("/")[-1] == e["name"]
        assert e["dur"] >= 0.0
    # executor events carry the plan-hash label for click-through
    lower = next(e for e in xs if e["name"] == "lower")
    assert lower["args"]["plan"]
    # telemetry() scopes the same records to one plan
    tel = res.telemetry()
    assert tel["spans"] and all(
        s["labels"]["plan"] == tel["plan"] for s in tel["spans"])


def test_report_trace_out_without_spans_exits_2(tmp_path, capsys):
    dump = tmp_path / "d.json"
    dump.write_text(json.dumps({"metrics": {}, "events": []}))
    rc = report.main([str(dump), "--trace-out", str(tmp_path / "t.json")])
    assert rc == 2
    assert "NO SPAN RECORDS" in capsys.readouterr().err
    assert not (tmp_path / "t.json").exists()


def test_require_spans_failure_prints_timing_context(tmp_path, capsys):
    _enable()
    with obs.span("detect"):
        pass
    dump = tmp_path / "d.json"
    obs.dump(dump)
    rc = report.main([str(dump), "--require-spans", "lower"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "MISSING SPANS: lower" in err
    assert "recorded spans" in err and "detect" in err and "p95" in err
