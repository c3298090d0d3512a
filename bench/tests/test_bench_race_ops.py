"""The readers of RACE's named device work, ``kernel_ms`` and
``layout_ms``, on hand-made events and on a trace recorded on a TPU v5e
with the program's names (four NPB MG sweeps at 62^3,
``fixtures/trace_mg62_named.json``)."""
import json
from pathlib import Path

import pytest

from bench import race_ops, trace
from bench.harness import Context, metric_reader

FIXTURE = Path(__file__).parent / "fixtures" / "trace_mg62_named.json"
MS = 1_000_000


def _ctx(tr, sweeps, chips=1):
    return Context(trace=tr, spans={}, counters={}, sweeps=sweeps,
                   sweep_bytes=0, chips=chips, device_kind="TPU v5 lite")


def _read(tr, sweeps, chips=1):
    ctx = _ctx(tr, sweeps, chips)
    return metric_reader("kernel_ms")(ctx), metric_reader("layout_ms")(ctx)


def _sweep_events(t0=0):
    """One resid-like RACE module, the benchmark's write-back module, and a
    vmapped RACE batch module, with their operations."""
    return [
        (t0, t0 + 10 * MS, "jit_race_resid_0123abcd", "module"),
        (t0 + 1 * MS, t0 + 2 * MS, "copy", "op"),
        (t0 + 2 * MS, t0 + 7 * MS, "race_kernel_resid.1", "op"),
        (t0 + 7 * MS, t0 + 9 * MS, "copy.1", "op"),
        (t0 + 3 * MS, t0 + 8 * MS, "slice-start", "async"),
        (t0 + 11 * MS, t0 + 14 * MS, "jit__lambda", "module"),
        (t0 + 11 * MS, t0 + 13 * MS, "pad_maximum_fusion", "op"),
        (t0 + 15 * MS, t0 + 20 * MS, "jit_race_batch_psinv_4567cdef",
         "module"),
        (t0 + 15 * MS, t0 + 18 * MS, "vmap_race_kernel_psinv_.1", "op"),
        (t0 + 18 * MS, t0 + 19 * MS, "slice_bitcast_fusion", "op"),
    ]


def test_kernel_and_layout_on_hand_made_events():
    tr = trace.Trace(devices={"/device:TPU:0": _sweep_events()},
                     host=[(0, 30 * MS, "bench.window")], window=(0, 30 * MS))
    assert race_ops.split_s(tr) == {
        "/device:TPU:0": pytest.approx((8e-3, 4e-3))}
    # two sweeps: kernels 5 + 3 ms, layout 1 + 2 + 1 ms; the write-back's
    # pad and the asynchronous slice count in neither
    kernel, layout = _read(tr, sweeps=2)
    assert kernel == pytest.approx(4.0)
    assert layout == pytest.approx(2.0)


def test_window_clips_and_chips_average():
    evs = _sweep_events()
    tr = trace.Trace(devices={"/device:TPU:0": evs,
                              "/device:TPU:1": _sweep_events(40 * MS)},
                     host=[], window=(3 * MS, 30 * MS))
    # chip 0 from 3 ms: the kernel's [3, 7), copy.1, and the batch module;
    # chip 1 ran outside the window and counts as nothing
    kernel, layout = _read(tr, sweeps=1, chips=2)
    assert kernel == pytest.approx((4 + 3) / 2)
    assert layout == pytest.approx((2 + 1) / 2)


def test_unnamed_program_reads_nothing():
    """A program whose modules and kernels carry no RACE names (as before
    they were given) reads as nothing, so the metric is left out."""
    evs = [(0, 10 * MS, "jit__call", "module"),
           (2 * MS, 7 * MS, "_call.1", "op"),
           (7 * MS, 9 * MS, "copy", "op")]
    tr = trace.Trace(devices={"/device:TPU:0": evs}, host=[],
                     window=(0, 10 * MS))
    assert race_ops.split_s(tr) == {}
    assert _read(tr, sweeps=1) == (None, None)
    assert _read(trace.Trace(), sweeps=0) == (None, None)


def test_xla_only_module_has_layout_but_no_kernel():
    evs = [(0, 10 * MS, "jit_race_rprj3_89abcdef", "module"),
           (1 * MS, 6 * MS, "fusion.2", "op")]
    tr = trace.Trace(devices={"/device:TPU:0": evs}, host=[],
                     window=(0, 10 * MS))
    assert _read(tr, sweeps=1) == (None, pytest.approx(5.0))


@pytest.fixture(scope="module")
def recorded():
    d = json.loads(FIXTURE.read_text())
    return trace.Trace.from_json(d), d


def test_recorded_named_modules_and_kernels(recorded):
    """On the chip the executors' modules read ``jit_race_<program>_<plan8>``
    (resid and psinv apart), and the kernel op ``race_kernel_<program>``."""
    tr, d = recorded
    (chip, evs), = tr.devices.items()
    modules = {n for _, _, n, k in evs if k == "module"}
    race_mods = sorted(n for n in modules if n.startswith("jit_race_"))
    assert [m.split("_")[2] for m in race_mods] == ["psinv", "resid"]
    assert not any(n.startswith("jit__call") for n in modules)
    ops = {n for _, _, n, k in evs if k == "op"}
    assert {"race_kernel_psinv.1", "race_kernel_resid.1"} <= ops
    assert not any(n.startswith("_call") for n in ops)
    sweeps = d["sweeps"]
    per_module = {}
    for _, _, n, k in evs:
        if k == "module":
            per_module[n] = per_module.get(n, 0) + 1
    assert all(per_module[m] == sweeps for m in race_mods)


def test_recorded_parts_sum_to_busy_time(recorded):
    """Kernel, layout passes and the benchmark's write-back make up the
    device's busy time per sweep: their operations fill RACE's and the
    write-back's modules, and nothing else ran."""
    tr, d = recorded
    sweeps = d["sweeps"]
    kernel, layout = _read(tr, sweeps)
    assert kernel > 0 and layout > 0
    lo, hi = tr.window
    (evs,) = tr.devices.values()
    mods = [(s, e) for s, e, n, k in evs
            if k == "module" and not n.startswith("jit_race_")]
    writeback = sum(min(e, hi) - max(s, lo) for s, e, n, k in evs
                    if k == "op" and any(ms <= s < me for ms, me in mods))
    writeback_ms = writeback * 1e-6 / sweeps
    busy_ms = 1e3 * sum(trace.busy_s(tr).values()) / sweeps
    parts = kernel + layout + writeback_ms
    assert parts <= busy_ms
    assert parts == pytest.approx(busy_ms, rel=0.03)


def test_recorded_program_spans_on_the_device_clock(recorded):
    """The fixture holds RACE's ``race.*`` host spans from the profiler's
    host plane: one ``race.dispatch`` per executor call, in order and
    inside the window, as many as RACE's modules ran."""
    tr, d = recorded
    disp = sorted(tuple(e) for e in d["program"] if e[2] == "race.dispatch")
    (evs,) = tr.devices.values()
    race_mods = [n for _, _, n, k in sorted(evs)
                 if k == "module" and n.startswith("jit_race_")]
    assert len(disp) == len(race_mods) == 2 * d["sweeps"]
    assert [m.split("_")[2] for m in race_mods[:2]] == ["resid", "psinv"]
    lo, hi = tr.window
    assert all(lo <= s < e <= hi for s, e, _ in disp)
    assert all(a[1] <= b[0] for a, b in zip(disp, disp[1:]))
