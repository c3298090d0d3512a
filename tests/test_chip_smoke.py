"""``chip_smoke.py`` on the CPU backend: it refuses to report without a TPU,
and its serve and sharded phases pass at tiny sizes (Pallas interpreted)."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.race import race
from repro.launch.mesh import make_stencil_mesh
from repro.testing.differential import build_env, default_tolerances

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _has_result_line(text: str) -> bool:
    for line in text.splitlines():
        try:
            if json.loads(line).get("ok") is not None:
                return True
        except (ValueError, AttributeError):
            continue
    return False


def test_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert not _has_result_line(out.out)
    assert "needs a TPU" in out.err


def test_refuses_alone_in_a_directory(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "PYTHONPATH": ""})
    assert r.returncode != 0
    assert not _has_result_line(r.stdout)


@pytest.mark.parametrize("name,grid", [("psinv", (12,)),
                                       ("hdifft_gm", (40, 40))])
def test_serve_phase_checks_against_float64_baseline(smoke, name, grid):
    case = smoke.build_case(name, grid)
    res = race(case.program, reassociate=case.reassociate,
               rewrite_div=case.rewrite_div)
    envs = [build_env(case, seed=s) for s in range(smoke.ENVS)]
    truths = smoke.baseline_truth(case, res, envs)
    assert all(v.dtype == np.float64 for t in truths for v in t.values())
    tol = default_tolerances(np.float32)["baseline"]
    for backend, want in (("auto", "pallas"), ("xla", "xla")):
        rep = smoke.serve_phase(res, envs, truths, backend)
        assert rep["resolved"] == want
        # interpreted exactly on the CPU backend (compiled on a TPU)
        assert rep["interpret"] is (True if want == "pallas" else None)
        assert rep["requests"] == smoke.CLIENTS * smoke.REQUESTS_PER_CLIENT
        assert rep["coalesced"] > 0 and rep["max_rel_err"] <= tol


def test_serve_phase_raises_on_a_wrong_answer(smoke):
    case = smoke.build_case("psinv", (12,))
    res = race(case.program, reassociate=case.reassociate)
    envs = [build_env(case, seed=s) for s in range(smoke.ENVS)]
    wrong = [{k: v + 1.0 for k, v in t.items()}
             for t in smoke.baseline_truth(case, res, envs)]
    with pytest.raises(AssertionError, match="float64 baseline"):
        smoke.serve_phase(res, envs, wrong, "xla")


@pytest.mark.parametrize("halo", ["exchange", "recompute"])
def test_sharded_phase_matches_one_device(smoke, halo):
    mesh = make_stencil_mesh(1)
    case = smoke.build_case("psinv", (10,))
    res = race(case.program, reassociate=case.reassociate, mesh=mesh)
    env = build_env(case, seed=0)
    want = {k: np.asarray(v) for k, v in res.run(env, "auto").items()}
    rep = smoke.sharded_phase(res, env, mesh, halo, want)
    assert rep["strategy"] == halo and rep["resolved"] == "pallas"
    assert rep["max_abs_diff"] == 0.0
