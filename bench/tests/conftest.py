"""Fixtures of the benchmark's CPU tests: tiny cells and an isolated
compilation cache and tuning store."""
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

#: sizes a test run can hold, per configuration family
TINY = {"npb_mg": dict(n=10), "pop_hdifft": dict(nx=14, ny=12, levels=4)}

#: the metrics of the serve mix, which no cell of ``BENCHMARK.json`` runs
#: yet (PERF.md, Open questions): its driver is kept tested for the cell a
#: later change adds
OPEN_LOOP = dict(
    end_to_end=[dict(name=n, unit=u) for n, u in (
        ("request_p50_ms", "ms"), ("request_p95_ms", "ms"),
        ("setup_s", "s"))],
    per_layer=[dict(name=n, unit=u) for n, u in (
        ("batch_size_mean", "req/batch"), ("race_s", "s"),
        ("warmup_s", "s"), ("device_idle_share.serve", "%"))])


@pytest.fixture(autouse=True, scope="session")
def _session_compile_cache(tmp_path_factory):
    """Compiled programs go to a session temp dir, never into the
    checkout."""
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR",
              str(tmp_path_factory.mktemp("jax-compile-cache")))
    yield
    mp.undo()


@pytest.fixture(autouse=True)
def _isolated_tuning_store(tmp_path, monkeypatch):
    monkeypatch.setenv("RACE_TUNING_CACHE", str(tmp_path / "tuning-store"))


@pytest.fixture(scope="session")
def spec():
    from bench import harness

    return harness.load_json(ROOT / "BENCHMARK.json")


@pytest.fixture
def tiny(spec):
    """``tiny(workload)``: the cell of ``BENCHMARK.json`` (or the
    ``<config>.<traffic>`` pair of its files) at a size the CPU runs in
    seconds, with its committed limits."""
    from bench import harness

    def make(workload: str):
        if any(w["name"] == workload for w in spec["workloads"]):
            cell = harness.resolve(spec, workload, ROOT)
        else:
            config, traffic = workload.split(".", 1)
            cell = harness.Cell(
                workload, 1,
                harness.load_json(ROOT / "bench" / "configs"
                                  / f"{config}.json"),
                harness.load_json(ROOT / "bench" / "traffic"
                                  / f"{traffic}.json"),
                OPEN_LOOP["end_to_end"], OPEN_LOOP["per_layer"])
        cell.config.update(TINY[cell.config["family"]])
        if cell.traffic["driver"] == "open_loop":
            cell.traffic["rate_per_s"] = 40
        return cell

    return make


@pytest.fixture
def run_tiny(spec, tiny):
    """``run_tiny(workload, seed=..., seconds=..., trace=..., rate=...)``:
    one run of the tiny cell on the CPU, the harness's look for a chip
    skipped."""
    from bench import harness

    def run(workload: str, seed: int = 2 ** 33 + 7, seconds: float = 0.5,
            trace: bool = False, rate: float | None = None) -> dict:
        cell = tiny(workload)
        if rate is not None:
            cell.traffic["rate_per_s"] = rate
        return harness.run(ROOT, workload, seed, seconds, trace,
                           time.perf_counter(), require_tpu=False, spec=spec,
                           cell=cell)

    return run
