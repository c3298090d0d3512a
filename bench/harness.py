"""One run of one cell: resolve it by name, set up, measure, check, report.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the configuration as it is run; its
  ``family`` key names the module ``bench/configs/<family>.py`` that builds
  the cell's programs and data and holds its check (the plain reference
  sits beside it, ``<family>_ref.py``);
* ``bench/traffic/<traffic>.json``: the mix; its ``driver`` key names the
  loop in :mod:`bench.traffic` that the window runs;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric, with a
  ``read(ctx)`` that returns a number or ``None`` (nothing to read).
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent

#: the longest window a traced run profiles: a trace of every op of a long
#: window is too large to read back within a run's time
TRACE_S = 10.0


class Refused(Exception):
    """The run cannot measure: no chip, too few chips, a malformed spec."""


@dataclass
class Clock:
    """Host-clock spans of the run's set-up phases, from process start."""

    t0: float
    spans: dict = field(default_factory=dict)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.spans[name] = self.spans.get(name, 0.0) + time.perf_counter() - t

    def since_start(self) -> float:
        return time.perf_counter() - self.t0


@dataclass
class Cell:
    """A workload entry of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str, spec: dict) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" not in metric:  # an end-to-end metric: every cell
        return True
    # a per-layer metric without a list: every cell that reports the
    # metric it moves
    moved = next((m for m in spec["end_to_end"]
                  if m["name"] == metric.get("moves")), None)
    return moved is not None and _applies(moved, cell, spec)


def resolve(spec: dict, workload: str, root: Path) -> Cell:
    """The cell named ``workload``, with its configuration, mix and
    metrics."""
    cell = next((w for w in spec["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload, spec)]
    layer = [m for m in spec["per_layer"] if _applies(m, workload, spec)]
    return Cell(workload, int(cell["chips"]), config, traffic, e2e, layer)


def family(config: dict):
    return importlib.import_module(f"bench.configs.{config['family']}")


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def devices_for(chips: int, require_tpu: bool) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX's first device is "
                      f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts JAX compilations and traces (``/jax/core/compile/*``
    duration events) while open."""

    def __init__(self):
        import jax

        self.events: dict = {}
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event: str, duration: float, **kw) -> None:
        if self._on and event.startswith("/jax/core/compile/"):
            self.events[event] = self.events.get(event, 0) + 1

    @contextlib.contextmanager
    def counting(self):
        self._on = True
        try:
            yield
        finally:
            self._on = False


def emit(result: dict, checks: dict) -> None:
    """The result line (``checks`` last) on stdout, and each compared number
    beside its limit as the last lines on stderr."""
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(dict(result, checks=checks)), flush=True)


def note(**kw) -> None:
    """An earlier line of the run's stdout (never the last)."""
    print(json.dumps(kw, default=str), flush=True)


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t0: float, *, require_tpu: bool = True, spec: dict | None = None,
        cell: Cell | None = None, control: bool = False) -> dict:
    """Run one cell once; returns ``{"result": ..., "checks": ...}``.

    ``spec``/``cell`` let a test drive a cell that ``BENCHMARK.json`` does
    not hold (tiny sizes on the CPU, with ``require_tpu=False``).
    ``control=True`` also reads the control's numbers (``"control"``): the
    reference in bfloat16 in the program's place, on the same inputs."""
    from bench import traffic as drivers
    from bench.trace import breakdown, busy_s, summary

    spec = spec if spec is not None else load_json(root / "BENCHMARK.json")
    cell = cell if cell is not None else resolve(spec, workload, root)
    clock = Clock(t0)
    devices = devices_for(cell.chips, require_tpu)
    clock.spans["devices_s"] = clock.since_start()
    compiles = CompileCounter()
    fam = family(cell.config)
    driver = drivers.DRIVERS[cell.traffic["driver"]]
    if trace:
        seconds = min(seconds, TRACE_S)
    out = driver(fam, cell, seed, seconds, trace, devices, clock, compiles)
    note(phase="window", compiles_in_window=sum(out.compiles.values()),
         compile_events=out.compiles, trace_count_delta=out.retraces,
         setup=clock.spans, setup_s=out.setup_s, **out.notes)

    dev = devices[0]
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(devices), memory_peak_bytes=memory_peak(devices))
    if trace:
        tr = out.trace
        note(phase="trace", **summary(tr))
        busy = busy_s(tr)
        device["busy_s"] = sum(busy.values()) / max(1, len(busy))
        device["window_s"] = tr.window_s
        ctx = Context(trace=tr, spans=clock.spans,
                      counters=out.counters, sweeps=out.sweeps,
                      sweep_bytes=out.sweep_bytes, chips=cell.chips,
                      device_kind=dev.device_kind)
        values = {m["name"]: metric_reader(m["name"])(ctx)
                  for m in cell.per_layer}
        wanted = cell.per_layer
    else:
        values = dict(out.e2e, setup_s=out.setup_s)
        wanted = cell.end_to_end
    metrics = {m["name"]: dict(value=values[m["name"]], unit=m["unit"])
               for m in wanted if values.get(m["name"]) is not None}
    # the driver drops the program's state before the reference runs
    checks = out.check(control)
    if control:
        checks, low = checks
    limits = cell.config["limits"]
    checked = {k: dict(value=v, limit=limits[k]) for k, v in checks.items()}
    correct = bool(checked) and all(
        c["limit"] is not None and math.isfinite(c["value"])
        and c["value"] <= c["limit"]
        for c in checked.values()) and out.unanswered == 0
    result = dict(correct=correct, attempted=out.attempted,
                  failed=out.failed, metrics=metrics, device=device)
    if trace:
        result["breakdown"] = breakdown(out.trace)
    rep = dict(result=result, checks=checked, notes=out.notes)
    if control:
        rep["control"] = low
    return rep


@dataclass
class Context:
    """What a per-layer metric reader may read."""

    trace: object
    spans: dict
    counters: dict
    sweeps: int
    sweep_bytes: int
    chips: int
    device_kind: str


def main(argv, root: Path, t0: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        rep = run(root, args.workload, args.seed, args.seconds,
                  bool(args.trace), t0)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    emit(rep["result"], rep["checks"])
    return 0
