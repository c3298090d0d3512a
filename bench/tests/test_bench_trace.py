"""The reduction from a device trace to numbers, on a small trace recorded
on a TPU v5e (four NPB MG sweeps at 62^3, ``fixtures/trace_mg62.json``)
and on hand-made events."""
import json
from pathlib import Path

import pytest

from bench import trace
from bench.harness import Context, metric_reader

FIXTURE = Path(__file__).parent / "fixtures" / "trace_mg62.json"


@pytest.fixture(scope="module")
def recorded():
    d = json.loads(FIXTURE.read_text())
    return trace.Trace.from_json(d), d["sweeps"], d["sweep_bytes"]


def _ctx(tr, sweeps, sweep_bytes, chips=1):
    return Context(trace=tr, spans={}, counters={}, sweeps=sweeps,
                   sweep_bytes=sweep_bytes, chips=chips,
                   device_kind="TPU v5 lite")


def test_recorded_busy_and_idle(recorded):
    tr, _, _ = recorded
    busy = trace.busy_s(tr)
    assert list(busy) == ["/device:TPU:0"]
    # the union of programs and operations, never more than the window
    assert busy["/device:TPU:0"] == pytest.approx(512.858e-6, rel=1e-6)
    assert 0 < busy["/device:TPU:0"] < tr.window_s
    assert trace.idle_share(tr) == pytest.approx(
        1 - 512.858e-6 / tr.window_s)


def test_recorded_roofline_share(recorded):
    tr, sweeps, nbytes = recorded
    got = metric_reader("stencil_roofline")(_ctx(tr, sweeps, nbytes))
    least = nbytes / 819e9
    assert got == pytest.approx(100 * least / (512.858e-6 / sweeps))
    assert 0 < got < 100


def test_recorded_breakdown(recorded):
    tr, _, _ = recorded
    bd = trace.breakdown(tr)
    ops = dict(bd["device_ops"])
    assert len(bd["device_ops"]) <= 10 and ops["_call.1"] > 0
    assert sum(ops.values()) > 0
    # the host was dispatching through the gaps of this trace
    assert bd["idle_gaps"][0][0] == "bench.dispatch"
    total_idle = sum(s for _, s in bd["idle_gaps"])
    assert total_idle == pytest.approx(
        tr.window_s - sum(trace.busy_s(tr).values()), rel=1e-6)


def test_union_clip_and_collectives():
    ms = 1_000_000
    tr = trace.Trace(
        devices={
            "/device:TPU:0": [
                (0, 4 * ms, "jit_step", "module"),
                (1 * ms, 2 * ms, "fusion.1", "op"),
                (3 * ms, 5 * ms, "collective-permute-start.1", "async"),
                (5 * ms, 6 * ms, "collective-permute-done.1", "op"),
                (9 * ms, 12 * ms, "custom-call.2", "op"),
            ],
            "/device:TPU:1": [(2 * ms, 3 * ms, "fusion.1", "op")],
        },
        host=[(0, 10 * ms, "bench.window"), (6 * ms, 9 * ms, "bench.sync")],
        window=(0, 10 * ms))
    busy = trace.busy_s(tr)
    # chip 0: [0, 4) and [5, 6) and [9, 10) after clipping the window
    assert busy["/device:TPU:0"] == pytest.approx(6e-3)
    assert busy["/device:TPU:1"] == pytest.approx(1e-3)
    assert trace.idle_share(tr) == pytest.approx(1 - 3.5e-3 / 10e-3)
    coll = trace.collective_s(tr)
    assert coll["/device:TPU:0"] == pytest.approx(3e-3)  # [3, 6)
    assert coll["/device:TPU:1"] == 0
    got = metric_reader("collective_ms")(_ctx(tr, 3, 0, chips=2))
    assert got == pytest.approx(1e3 * 1.5e-3 / 3)
    # a gap goes to the host span that covers most of it: chip 0's [6, 9)
    # and chip 1's [3, 10) to bench.sync, the rest to none
    gaps = dict(trace.idle_gaps(tr))
    assert gaps["bench.sync"] == pytest.approx((3e-3 + 7e-3) / 2)
    assert gaps["none"] == pytest.approx((1e-3 + 2e-3) / 2)


def test_no_trace_reads_nothing():
    tr = trace.Trace()
    assert trace.idle_share(tr) is None
    ctx = _ctx(tr, 0, 0)
    for name in ("stencil_roofline", "device_idle_share.sweep",
                 "collective_ms"):
        assert metric_reader(name)(ctx) is None
