"""Sharded giant-grid execution (``repro.shard``): partition, halo, executor.

Four contracts under test:

  * the **partitioner** — mesh axes land only on levels whose geometry
    admits a one-sided slab, and every impossibility is a pinned structured
    :class:`ShardRefusal` code, never a silent fallback (one negative
    fixture per code, mirroring the lowering capability-probe tests);
  * **cache identity** — a sharded executor and its single-device twin share
    the process-wide :class:`ExecutorCache` but can never collide: the
    mesh/partition/halo-qualified :class:`ExecutorKey` keeps them distinct,
    and ``cache_info()`` exposes the split;
  * **differential equality** — ``run_sharded`` must reproduce the
    single-device ``run`` bit-for-bit on a size-1 mesh in-process, and to
    float64 round-off on a forced multi-device host mesh (subprocess, so
    the ``--xla_force_host_platform_device_count`` flag never leaks into
    this process), for *both* halo strategies, across the whole
    ``paper_kernels`` registry and through ``jax.grad``;
  * **observability** — sharded runs/refusals emit their spans, counters
    and structured events.

The subprocess pattern follows ``test_grad_sync.py``: device-count flags
are process-global in XLA, and tier-1 must keep seeing one device.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.apps.paper_kernels import get_case
from repro.core.executor import ExecutorCache, compile_plan, executor_cache
from repro.core.ir import arr, loopnest, program
from repro.core.race import race
from repro.launch.mesh import make_stencil_mesh, stencil_mesh_shape
from repro.shard import (HALO_STRATEGIES, S_DIVISIBILITY, S_ENVELOPE,
                         S_GATHER, S_GEOMETRY, S_HALO, S_MIRRORED, S_NO_AXIS,
                         S_STRIDED, SHARD_REFUSAL_CODES, ShardingUnavailable,
                         compile_sharded, plan_halo, plan_partition)
from repro.shard.executor import _local_program
from repro.testing.differential import build_env

pytestmark = pytest.mark.shard

SRC = Path(__file__).resolve().parents[1] / "src"


class FakeMesh:
    """Duck-typed mesh for partition-only tests: ``plan_partition`` reads
    just ``axis_names`` + ``shape`` and never touches devices, so shard
    counts beyond this process's device count are testable in tier-1."""

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


def _codes(part):
    return {r.code for r in part.refusals}


# ---------------------------------------------------------------------------
# mesh factoring
# ---------------------------------------------------------------------------


def test_stencil_mesh_shape_near_square():
    assert stencil_mesh_shape(1, 2) == (1, 1)
    assert stencil_mesh_shape(2, 2) == (2, 1)
    assert stencil_mesh_shape(4, 2) == (2, 2)
    assert stencil_mesh_shape(6, 2) == (3, 2)
    assert stencil_mesh_shape(8, 2) == (4, 2)
    for n in range(1, 33):
        shape = stencil_mesh_shape(n, 2)
        assert shape[0] * shape[1] == n  # exact coverage, no devices dropped
        assert shape[0] >= shape[1]


def test_make_stencil_mesh_single_device():
    mesh = make_stencil_mesh(1, ("sx", "sy"))
    assert mesh.axis_names == ("sx", "sy")
    assert dict(mesh.shape) == {"sx": 1, "sy": 1}


# ---------------------------------------------------------------------------
# partitioner: positive placement
# ---------------------------------------------------------------------------


def test_partition_poisson_placement():
    case = get_case("poisson", 10)
    res = race(case.program, reassociate=case.reassociate)
    part = plan_partition(res.program, FakeMesh(sx=4, sy=2))
    assert part.ok
    assert part.key() == ((1, "sx", 4), (2, "sy", 2))
    a = part.by_level[1]
    assert (a.extent, a.chunk, a.halo) == (8, 2, 2)  # E=8, e=8/4, t=lo+off_hi
    assert "sharded" in part.explain()


def test_partition_single_axis_leftover_is_ok():
    # mirror_deriv: level 1 is mirrored, only level 2 shardable; the second
    # mesh axis finds no level but the plan still shards (informational
    # refusals, ok=True)
    case = get_case("mirror_deriv", 14)
    part = plan_partition(case.program, FakeMesh(sx=2, sy=2))
    assert part.ok
    assert part.key() == ((2, "sx", 2),)
    assert S_MIRRORED in _codes(part)


def test_refusal_codes_are_pinned_vocabulary():
    for nm, n in [("mirror_deriv", 14), ("rprj3", 12), ("diag2d", 14),
                  ("gaussian", 21)]:
        part = plan_partition(get_case(nm, n).program, FakeMesh(sx=2))
        assert _codes(part) <= SHARD_REFUSAL_CODES


# ---------------------------------------------------------------------------
# partitioner: one negative fixture per refusal code
# ---------------------------------------------------------------------------


def test_refusal_mirrored():
    part = plan_partition(get_case("mirror_deriv", 14).program,
                          FakeMesh(sx=2))
    refs = [r for r in part.refusals if r.code == S_MIRRORED]
    assert refs and refs[0].level == 1


def test_refusal_strided_and_no_axis():
    part = plan_partition(get_case("rprj3", 12).program, FakeMesh(sx=2))
    assert not part.ok
    assert S_STRIDED in _codes(part)
    assert S_NO_AXIS in _codes(part)  # whole-plan refusal is explicit


def test_refusal_gather():
    part = plan_partition(get_case("diag2d", 14).program, FakeMesh(sx=2))
    refs = [r for r in part.refusals if r.code == S_GATHER]
    assert refs  # the diagonal read gathers across one level


def test_refusal_divisibility():
    # poisson level extents are 8; a size-3 axis divides neither
    part = plan_partition(get_case("poisson", 10).program, FakeMesh(sx=3))
    assert not part.ok
    assert S_DIVISIBILITY in _codes(part)
    assert S_NO_AXIS in _codes(part)


def test_refusal_halo_exceeds_chunk():
    # 8 shards over extent 8 leave chunk 1 < halo 2: one ppermute hop
    # cannot supply the slab
    part = plan_partition(get_case("poisson", 10).program, FakeMesh(sx=8))
    assert not part.ok
    assert S_HALO in _codes(part)


def test_refusal_envelope():
    # u[i-2] at lo=1 reads left of any slab start: lo + off_lo = -1
    u, y = arr("u"), arr("y")
    loops, (i,) = loopnest(("i", 1, 6))
    prog = program(loops, [(y[i], u[i - 2] + u[i])])
    part = plan_partition(prog, FakeMesh(sx=2))
    assert not part.ok
    assert S_ENVELOPE in _codes(part)


def test_refusal_geometry():
    # mixed stride on one array leaves the program with no offset
    # envelopes at all: plan-wide S_GEOMETRY, empty verdicts
    u, y = arr("u"), arr("y")
    loops, (i,) = loopnest(("i", 1, 4))
    prog = program(loops, [(y[i], u[i] + u[2 * i])])
    part = plan_partition(prog, FakeMesh(sx=2))
    assert not part.ok
    assert _codes(part) == {S_GEOMETRY}
    assert part.verdicts == ()


def test_compile_sharded_raises_structured():
    case = get_case("rprj3", 12)
    res = race(case.program, reassociate=case.reassociate)
    env = build_env(case, np.float32, seed=0)
    with pytest.raises(ShardingUnavailable) as ei:
        compile_sharded(res, env, FakeMesh(sx=2), cache=ExecutorCache(8))
    assert any(r.code == S_STRIDED for r in ei.value.refusals)
    assert S_STRIDED in str(ei.value)  # the exception message explains


# ---------------------------------------------------------------------------
# halo program accounting
# ---------------------------------------------------------------------------


def test_halo_accounting_and_forced_strategy():
    case = get_case("poisson", 10)
    res = race(case.program, reassociate=case.reassociate)
    part = plan_partition(res.program, FakeMesh(sx=2, sy=2))
    assert part.ok
    local = race(_local_program(res.program, part),
                 reassociate=case.reassociate)
    env = build_env(case, np.float32, seed=0)
    from repro.core.executor import env_signature

    sig = env_signature(env)
    hx = plan_halo(part, local.plan, sig, strategy="exchange")
    hr = plan_halo(part, local.plan, sig, strategy="recompute")
    ha = plan_halo(part, local.plan, sig, strategy="auto")
    assert hx.strategy == "exchange" and hr.strategy == "recompute"
    assert ha.strategy in ("exchange", "recompute")
    # both cost models see real traffic, and exchange ships only halos —
    # strictly less than recompute's full replicated copies
    assert 0 < hx.halo_bytes < hr.restack_bytes
    # every slab array is halo-extended to chunk + t along its slab dims
    u = hx.specs["u"]
    assert u.mode == "slab"
    for sd in u.slabs:
        assert u.local_shape[sd.dim] == sd.chunk + sd.halo
    with pytest.raises(ValueError):
        plan_halo(part, local.plan, sig, strategy="teleport")
    assert set(HALO_STRATEGIES) == {"auto", "exchange", "recompute"}


def test_halo_auto_decides_by_device_kind(monkeypatch):
    """``auto`` takes the chip's published peaks by ``device_kind``; the CPU
    backend always exchanges, and an unlisted TPU kind is an error."""
    from types import SimpleNamespace

    from repro.core.executor import env_signature
    from repro.launch.mesh import PEAKS, chip_peaks

    case = get_case("poisson", 10)
    res = race(case.program, reassociate=case.reassociate)
    part = plan_partition(res.program, FakeMesh(sx=2, sy=2))
    local = race(_local_program(res.program, part),
                 reassociate=case.reassociate)
    sig = env_signature(build_env(case, np.float32, seed=0))
    assert plan_halo(part, local.plan, sig, "auto").strategy == "exchange"

    def on(kind):
        dev = SimpleNamespace(platform="tpu", device_kind=kind)
        monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])
        return plan_halo(part, local.plan, sig, "auto")

    v5e = chip_peaks("TPU v5 lite")
    assert v5e is PEAKS["TPU v5 lite"] and v5e["hbm_bw"] == 819e9
    hp = on("TPU v5 lite")
    want = ("exchange" if hp.halo_bytes / v5e["ici_bw_per_link"]
            <= hp.restack_bytes / v5e["hbm_bw"] else "recompute")
    assert hp.strategy == want
    with pytest.raises(ValueError, match="TPU v99"):
        on("TPU v99")


# ---------------------------------------------------------------------------
# cache identity
# ---------------------------------------------------------------------------


def test_sharded_cache_key_never_collides():
    case = get_case("poisson", 10)
    res = race(case.program, reassociate=case.reassociate,
               rewrite_div=case.rewrite_div, backend="xla")
    env = build_env(case, np.float32, seed=1)
    mesh = make_stencil_mesh(1, ("sx", "sy"))
    c = ExecutorCache(16)
    single = compile_plan(res.plan, env, "xla", cache=c)
    sharded = compile_sharded(res, env, mesh, backend="xla", cache=c)
    assert sharded is not single
    # on a size-1 mesh the local program equals the global one, so the
    # sharded build's inner compile_plan HITS the single-device entry:
    # exactly two entries, one of them mesh-keyed
    info = c.cache_info()
    assert info["currsize"] == 2
    assert info["sharded"] == 1
    assert info["devices"]  # device context is part of every key
    # same request -> same executor; different halo strategy -> new entry
    assert compile_sharded(res, env, mesh, backend="xla", cache=c) is sharded
    other = compile_sharded(res, env, mesh, backend="xla", halo="recompute",
                            cache=c)
    assert other is not sharded
    assert c.cache_info()["sharded"] == 2
    ci = sharded.cache_info()
    assert ci["strategy"] in ("exchange", "recompute")
    assert ci["partition"] == sharded.partition.key()


# ---------------------------------------------------------------------------
# differential: size-1 mesh in-process (full machinery, bitwise equality)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,n", [("poisson", 10), ("smooth1d", 24),
                                    ("blocked4d", 6)])
@pytest.mark.parametrize("strategy", ["exchange", "recompute"])
def test_sharded_matches_single_device_on_unit_mesh(name, n, strategy):
    case = get_case(name, n)
    res = race(case.program, reassociate=case.reassociate,
               rewrite_div=case.rewrite_div, backend="xla")
    env = build_env(case, np.float32, seed=3)
    base = res.run(env)
    mesh = make_stencil_mesh(1, ("sx", "sy"))
    got = res.run_sharded(dict(env), mesh, halo=strategy)
    assert set(got) == set(base)
    for k in base:
        # the size-1 local program IS the global program: same plan, same
        # executor core, so the shard_map wrapper must be exactly neutral
        assert np.array_equal(np.asarray(got[k]), np.asarray(base[k])), k


def test_race_mesh_option_routes_run():
    case = get_case("poisson", 10)
    env = build_env(case, np.float32, seed=5)
    mesh = make_stencil_mesh(1, ("sx", "sy"))
    obs.configure(enabled=True)
    res = race(case.program, reassociate=case.reassociate, mesh=mesh)
    base = race(case.program, reassociate=case.reassociate).run(env)
    got = res.run(dict(env))  # no explicit backend: delegates to sharded
    for k in base:
        assert np.array_equal(np.asarray(got[k]), np.asarray(base[k])), k
    counters = obs.dump()["metrics"]["counters"]
    assert any(k.startswith("race_shard_runs_total") for k in counters)
    # explicit backend= opts back into the single-device path
    before = sum(v for k, v in counters.items()
                 if k.startswith("race_shard_runs_total"))
    res.run(dict(env), "xla")
    counters = obs.dump()["metrics"]["counters"]
    after = sum(v for k, v in counters.items()
                if k.startswith("race_shard_runs_total"))
    assert after == before


def test_gradient_through_run_sharded_unit_mesh():
    case = get_case("poisson", 8)
    env = build_env(case, np.float32, seed=7)
    res = race(case.program, reassociate=case.reassociate, backend="xla")
    mesh = make_stencil_mesh(1, ("sx", "sy"))
    key = sorted(res.run(env))[0]

    def loss_single(u):
        return jnp.sum(res.run({**env, "u": u})[key])

    def loss_shard(u):
        return jnp.sum(res.run_sharded({**env, "u": u}, mesh)[key])

    u0 = jnp.asarray(env["u"])
    g1 = np.asarray(jax.grad(loss_single)(u0))
    g2 = np.asarray(jax.grad(loss_shard)(u0))
    assert np.allclose(g1, g2, rtol=1e-6, atol=1e-6)


def test_shard_refusal_event_and_counter():
    obs.configure(enabled=True)
    case = get_case("rprj3", 12)
    res = race(case.program, reassociate=case.reassociate)
    env = build_env(case, np.float32, seed=0)
    with pytest.raises(ShardingUnavailable):
        compile_sharded(res, env, FakeMesh(sx=2), cache=ExecutorCache(8))
    evs = obs.events("shard_refusal")
    assert evs and any(S_STRIDED in r for r in evs[-1]["reasons"])
    counters = obs.dump()["metrics"]["counters"]
    assert any(k.startswith("race_shard_refusals_total") for k in counters)


def test_shard_plan_span_and_event():
    obs.configure(enabled=True)
    case = get_case("poisson", 10)
    res = race(case.program, reassociate=case.reassociate, backend="xla")
    env = build_env(case, np.float32, seed=2)
    mesh = make_stencil_mesh(1, ("sx", "sy"))
    res.run_sharded(dict(env), mesh)
    spans = obs.span_summary()
    assert spans.get("shard_plan", {}).get("count", 0) >= 1
    assert spans.get("halo_plan", {}).get("count", 0) >= 1
    evs = obs.events("shard_plan")
    assert evs
    ev = evs[-1]
    assert ev["strategy"] in ("exchange", "recompute")
    assert ev["partition"] and ev["local_plan"]


# ---------------------------------------------------------------------------
# differential: forced multi-device host mesh (subprocess)
# ---------------------------------------------------------------------------

_SWEEP = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.apps.paper_kernels import get_case
from repro.core.race import race
from repro.launch.mesh import make_stencil_mesh
from repro.shard import ShardingUnavailable
from repro.testing.differential import build_env

assert jax.device_count() == 4, jax.device_count()
mesh = make_stencil_mesh(4, ("sx", "sy"))

# every registry case at a mesh-divisible size; refusals are pinned
SWEEP = [("poisson", 10), ("j3d27pt", 10), ("diffusion1", 10),
         ("diffusion2", 10), ("diffusion3", 10), ("psinv", 10),
         ("resid", 10), ("rhs_ph1", 10), ("rhs_ph2", 10),
         ("smooth1d", 24), ("hdifft_gm", 14), ("ocn_export", 14),
         ("mirror_deriv", 14), ("diag2d", 14), ("blocked4d", 6)]
REFUSED = [("gaussian", 21, "shard-divisibility"),
           ("calc_tpoints", 12, "shard-divisibility"),
           ("derivative", 11, "shard-divisibility"),
           ("rprj3", 12, "shard-strided")]

sharded = 0
for nm, n in SWEEP:
    case = get_case(nm, n)
    env = build_env(case, np.float64, seed=11)
    res = race(case.program, reassociate=case.reassociate,
               rewrite_div=case.rewrite_div, backend="xla")
    base = {k: np.asarray(v) for k, v in res.run(env).items()}
    scale = max(np.abs(v).max() for v in base.values())
    for strat in ("exchange", "recompute"):
        got = res.run_sharded(dict(env), mesh, halo=strat)
        err = max(float(np.abs(np.asarray(got[k]) - base[k]).max())
                  for k in base)
        assert err <= 1e-10 * scale, (nm, strat, err, scale)
    sharded += 1
assert sharded == len(SWEEP)

for nm, n, code in REFUSED:
    case = get_case(nm, n)
    env = build_env(case, np.float64, seed=11)
    res = race(case.program, reassociate=case.reassociate,
               rewrite_div=case.rewrite_div, backend="xla")
    try:
        res.run_sharded(dict(env), mesh)
        raise AssertionError(f"{nm}: expected ShardingUnavailable")
    except ShardingUnavailable as e:
        assert any(r.code == code for r in e.refusals), (nm, str(e))

# gradient through the sharded custom_vjp on a real multi-device mesh
case = get_case("poisson", 10)
env = build_env(case, np.float64, seed=11)
res = race(case.program, reassociate=case.reassociate, backend="xla")
key = sorted(res.run(env))[0]
loss_s = lambda u: jnp.sum(res.run({**env, "u": u})[key])
loss_m = lambda u: jnp.sum(res.run_sharded({**env, "u": u}, mesh)[key])
u0 = jnp.asarray(env["u"])
g1 = np.asarray(jax.grad(loss_s)(u0))
g2 = np.asarray(jax.grad(loss_m)(u0))
assert np.abs(g1 - g2).max() <= 1e-10 * np.abs(g1).max(), "grad mismatch"

# pallas local backend under shard_map (interpret mode on CPU)
env32 = build_env(case, np.float32, seed=11)
resp = race(case.program, reassociate=case.reassociate, backend="pallas")
basep = {k: np.asarray(v) for k, v in resp.run(env32).items()}
gotp = resp.run_sharded(dict(env32), mesh, halo="exchange",
                        backend="pallas")
errp = max(float(np.abs(np.asarray(gotp[k]) - basep[k]).max())
           for k in basep)
assert errp <= 1e-5, errp
print("OK sharded", sharded, "refused", len(REFUSED))
"""


def _run_forced(code: str) -> str:
    """Run ``code`` in a fresh interpreter (it forces its own host device
    count); its stdout."""
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""),
             "JAX_COMPILATION_CACHE_DIR":
                 os.environ["JAX_COMPILATION_CACHE_DIR"]}, timeout=540)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    return r.stdout


def test_forced_4device_registry_sweep_subprocess():
    assert "OK sharded 15 refused 4" in _run_forced(_SWEEP)


# ---------------------------------------------------------------------------
# named halo work and modelled halo bytes: forced 4-device host mesh
# ---------------------------------------------------------------------------

_NAMED = r"""
import json, os, re
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["RACE_OBS"] = "1"
import numpy as np
from repro import obs
from repro.apps.paper_kernels import get_case
from repro.core.executor import ExecutorCache
from repro.core.race import race
from repro.launch.mesh import make_stencil_mesh
from repro.shard import compile_sharded
from repro.testing.differential import build_env

assert obs.enabled()
mesh = make_stencil_mesh(4, ("sx", "sy"))
case = get_case("resid", 10)
res = race(case.program, reassociate=case.reassociate, mesh=mesh)
env = build_env(case, np.float32, seed=0)
ex = compile_sharded(res, env, mesh, cache=ExecutorCache(4))
text = ex._jit.lower(dict(env)).compile().as_text()
ops = []
for line in text.splitlines():
    m = re.search(r"=\s*\S+\s+([a-z][a-z-]*)\(.*op_name=\"([^\"]*)\"", line)
    if m:
        ops.append(m.groups())
info = ex.cache_info()
print(json.dumps(dict(
    ops=ops, info={k: info[k] for k in ("strategy", "halo_bytes")},
    label=res.plan.program.label, gauges=obs.snapshot()["gauges"])))
"""


@pytest.fixture(scope="module")
def named_4device():
    """One sharded MG resid executor over a forced 2x2 host mesh with
    ``RACE_OBS=1``: its compiled HLO's ops (opcode, metadata op_name), its
    ``cache_info()`` and the obs gauges."""
    return json.loads(_run_forced(_NAMED).strip().splitlines()[-1])


def test_halo_work_named_on_hlo(named_4device):
    """The exchange's collective permutes carry ``race_halo``; the core and
    tail slices before ``shard_map`` carry ``race_shard_in``."""
    from repro.shard.halo import HALO, SHARD_IN

    ops = named_4device["ops"]
    assert named_4device["info"]["strategy"] == "exchange"
    exchange = [n for op, n in ops if n.endswith("/ppermute")]
    assert exchange and all(f"/{HALO}/" in n for n in exchange)
    assert any(op.startswith("collective-permute") and f"/{HALO}/" in n
               for op, n in ops)
    shard_in = [n for _op, n in ops if f"/{SHARD_IN}/" in n]
    assert shard_in and all("shard_map" not in n for n in shard_in)
    assert any(n.endswith(f"/{SHARD_IN}/slice") for n in shard_in)


def test_shard_halo_bytes_gauge(named_4device):
    """Building the executor sets ``race_shard_halo_bytes`` to the halo
    bytes per call it models for the strategy it chose."""
    info = named_4device["info"]
    key = (f"race_shard_halo_bytes{{program={named_4device['label']},"
           f"strategy={info['strategy']}}}")
    assert info["halo_bytes"] > 0
    assert named_4device["gauges"][key] == info["halo_bytes"]
