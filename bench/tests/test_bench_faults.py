"""A run drives the whole cell with the timed path broken underneath, and
``correct`` comes out false: once for each fault the cell can have.  The
same runs unbroken come out correct, with the trace on and off.

Faults, planted in the program's executor (``CompiledRace``):

* a step that returns its state unchanged (the chained MG sweep);
* half of a batch left out, its answers those of the other half (every
  cell that stacks a batch);
* an answer altered where it is produced;
* the exchange between chips left out (class D's configuration over four
  virtual devices; no cell of the benchmark runs it yet).
"""
import jax.numpy as jnp
import pytest

from repro.core import executor

ORIG_RUN = executor.CompiledRace.run
ORIG_BATCH = executor.CompiledRace.run_batch


def state_unchanged(self, env):
    out = ORIG_RUN(self, env)
    return {k: (jnp.asarray(env[k])[tuple(slice(1, -1) for _ in v.shape)]
                if k in env else v) for k, v in out.items()}


def half_batch(self, envs):
    out = ORIG_BATCH(self, envs)
    half = {k: v.shape[0] // 2 for k, v in out.items()}
    return {k: v.at[half[k]:].set(v[:v.shape[0] - half[k]])
            if half[k] else v for k, v in out.items()}


def altered_run(self, env):
    out = ORIG_RUN(self, env)
    return {k: v.at[(1,) * v.ndim].add(1.0) for k, v in out.items()}


def altered_batch(self, envs):
    out = ORIG_BATCH(self, envs)
    return {k: v.at[(slice(None),) + (1,) * (v.ndim - 1)].add(1.0)
            for k, v in out.items()}


FAULTS = {
    "state_unchanged": dict(run=state_unchanged),
    "half_batch": dict(run_batch=half_batch),
    "altered": dict(run=altered_run, run_batch=altered_batch),
}

CASES = [
    ("npb_mg_b.smooth", "state_unchanged"),
    ("npb_mg_b.smooth", "altered"),
    ("pop_gx1v6.levels", "half_batch"),
    ("pop_gx1v6.levels", "altered"),
    ("pop_gx1v6.serve", "half_batch"),
    ("pop_gx1v6.serve", "altered"),
]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(monkeypatch, run_tiny, workload, fault):
    for name, fn in FAULTS[fault].items():
        monkeypatch.setattr(executor.CompiledRace, name, fn)
    # serve: enough load that requests coalesce into batches
    rep = run_tiny(workload, seconds=0.5,
                   rate=400 if workload.endswith(".serve") else None)
    assert rep["result"]["correct"] is False
    assert any(c["value"] > c["limit"] for c in rep["checks"].values())


@pytest.mark.parametrize("workload", [
    "npb_mg_b.smooth", "pop_gx1v6.levels", "pop_gx1v6.serve"])
@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(run_tiny, workload, trace):
    rep = run_tiny(workload, trace=trace)
    res = rep["result"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(rep["checks"]) and all(
        set(c) == {"value", "limit"} for c in rep["checks"].values())
    if trace:
        assert {"race_s", "warmup_s"} <= set(res["metrics"])
        assert "breakdown" in res and "window_s" in res["device"]
    else:
        assert "setup_s" in res["metrics"]
        assert ("sweep_ms" in res["metrics"]
                or "request_p95_ms" in res["metrics"])


def _run_2x2(fault: str) -> dict:
    """NPB MG class D's configuration at a tiny size over a 2x2 mesh of
    four virtual CPU devices, in a process of its own (the device count is
    fixed when JAX starts).  No cell runs it yet; this keeps its path and
    its check sound for the cell a later change adds."""
    import json
    import os
    import subprocess
    import sys

    from bench.tests.conftest import ROOT

    code = f"""
import json, sys, time
sys.path[:0] = [{str(ROOT / 'src')!r}, {str(ROOT)!r}]
import jax
from bench import harness
if {fault!r} == "no_exchange":
    jax.lax.ppermute = lambda x, axis_name, perm: x
root = harness.BENCH.parent
spec = harness.load_json(root / "BENCHMARK.json")
cfg = harness.load_json(root / "bench" / "configs" / "npb_mg_d.json")
cfg["n"] = 16
mix = harness.load_json(root / "bench" / "traffic" / "smooth_2x2.json")
e2e = [m for m in spec["end_to_end"] if m["name"] in ("sweep_ms", "setup_s")]
cell = harness.Cell("npb_mg_d.smooth_2x2", 4, cfg, mix, e2e, [])
rep = harness.run(root, cell.name, 5, 0.5, False, time.perf_counter(),
                  require_tpu=False, spec=spec, cell=cell)
print(json.dumps(dict(rep["result"], notes=rep["notes"])))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("no_exchange", False)])
def test_2x2_exchange_left_out_is_not_correct(fault, correct):
    res = _run_2x2(fault)
    assert res["device"]["count"] == 4
    assert res["notes"]["halo"] == ["exchange", "exchange"]
    assert res["correct"] is correct
