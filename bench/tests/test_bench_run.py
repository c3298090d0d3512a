"""``bench/run.py`` refuses, printing no result, where it cannot measure."""
import os
import shutil
import subprocess
import sys

from bench.tests.conftest import ROOT

ARGS = ["--workload", "npb_mg_b.smooth", "--seed", str(2 ** 33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(root, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                           *ARGS], cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)


def test_exits_nonzero_without_a_tpu(tmp_path):
    p = _run(ROOT, tmp_path)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_exits_nonzero_with_the_benchmark_alone(tmp_path):
    """A directory holding only ``BENCHMARK.json`` and the files under
    ``paths`` has no program to measure."""
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", alone)
    shutil.copytree(ROOT / "bench", alone / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(alone, tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
