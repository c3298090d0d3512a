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
    ``paper_kernels`` registry and through ``jax.grad``, under both
    exchange in-layouts (``"shard"``: the array as it lies, edge planes
    exchanged; ``"core_tail"``), each where the engagement rule puts it;
  * **observability** — sharded runs/refusals emit their spans, counters
    and structured events, and the compiled exchange moves exactly the
    bytes the halo program models.

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


def test_one_chip_as_laid_sharded_local_level_major():
    """One chip's executors iterate as laid: MG's psinv and resid and POP's
    hdifft_gm reach the kernel with no transpose of an operand or an
    output.  The sharded executor's local kernel (psinv on a size-1 CPU
    mesh) keeps level-major, transposing each operand by (2, 1, 0), since
    its halo prologue writes the operands anew.  Read from the
    specializations, with no compile."""
    from repro.lowering import ORDER_AS_LAID, ORDER_LEVEL_MAJOR
    from repro.shard import compile_sharded

    for name, n in (("psinv", 10), ("resid", 10), ("hdifft_gm", 14)):
        case = get_case(name, n)
        res = race(case.program, reassociate=case.reassociate)
        ex = compile_plan(res.plan, build_env(case), "pallas",
                          cache=ExecutorCache())
        assert ex.cache_info()["order"] == ex.spec.order == ORDER_AS_LAID
        assert [p.tperm for p in ex.spec.prep.values()] == [()] * len(
            ex.spec.prep), name
        assert set(ex.spec.out_axes.values()) == {()}, name
    case = get_case("psinv", 10)
    mesh = make_stencil_mesh(1, ("sx", "sy"))
    res = race(case.program, reassociate=case.reassociate, mesh=mesh)
    ex = compile_sharded(res, build_env(case), mesh, backend="pallas",
                         cache=ExecutorCache())
    spec = ex.local.spec
    assert ex.cache_info()["local"]["order"] == spec.order
    assert spec.order == ORDER_LEVEL_MAJOR
    assert {nm: p.tperm for nm, p in spec.prep.items()} == {
        "U": (2, 1, 0), "R": (2, 1, 0)}
    assert spec.out_axes == {"U": (2, 1, 0)}


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
from repro.shard import ShardingUnavailable, compile_sharded
from repro.testing.differential import build_env

assert jax.device_count() == 4, jax.device_count()
mesh = make_stencil_mesh(4, ("sx", "sy"))


def rule(spec):
    # the in-layout the engagement rule gives a slab operand's shape:
    # "shard" when along every sharded dim the shards divide the extent N
    # and each slab lies in its shard and the two neighbours' (kL, kR <= S)
    for sd in spec.slabs:
        n = spec.shape[sd.dim]
        size = n // sd.shards
        k_left = max(0, (sd.shards - 1) * (size - sd.chunk))
        k_right = max(0, sd.chunk + sd.halo - size)
        if n % sd.shards or k_left > size or k_right > size:
            return "core_tail"
    return "shard"


seen = {"shard": 0, "core_tail": 0}

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
        ex = compile_sharded(res, env, mesh, halo=strat, backend="xla")
        hp = ex.halo_prog
        slabs = [s for s in hp.specs.values() if s.mode == "slab"]
        assert set(hp.in_layout) == {s.name for s in slabs}, nm
        for s in slabs:
            want = rule(s) if strat == "exchange" else "replicated"
            assert hp.in_layout[s.name] == want, (nm, s.name, want)
            seen[want] = seen.get(want, 0) + (strat == "exchange")
        got = ex(dict(env))
        err = max(float(np.abs(np.asarray(got[k]) - base[k]).max())
                  for k in base)
        assert err <= 1e-10 * scale, (nm, strat, err, scale)
    sharded += 1
assert sharded == len(SWEEP)
# the registry's own shapes reach both exchange in-layouts
assert seen["shard"] and seen["core_tail"], seen

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


_MG = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.apps.paper_kernels import get_case
from repro.core.executor import ExecutorCache
from repro.core.race import race
from repro.shard import compile_sharded
from repro.testing.differential import build_env

N = 18  # E = 16: 2x2 gives 9-plane shards, 4x1 cannot split N = 18 by 4
INPUTS = {"resid": ("V", "U"), "psinv": ("U", "R")}
LAYOUT = {(2, 2): "shard", (4, 1): "core_tail"}
rng = np.random.default_rng(5)
runs = 0
for shape, layout in LAYOUT.items():
    mesh = Mesh(np.array(jax.devices()).reshape(shape), ("sx", "sy"))
    grid = NamedSharding(mesh, P(None, "sy", "sx"))
    wrap = jax.jit(lambda x: jnp.pad(x, 1, mode="wrap"))
    for name, inputs in INPUTS.items():
        case = get_case(name, N)
        res = race(case.program, reassociate=case.reassociate,
                   rewrite_div=case.rewrite_div, backend="xla")
        env = build_env(case, np.float64, seed=7)
        # whole (N, N, N) grids, as the benchmark holds them: periodic
        # wraps of an interior, once committed to P(None, 'sy', 'sx') the
        # way the wrap leaves them, once replicated host arrays
        inner = {k: rng.uniform(-1, 1, (N - 2,) * 3) for k in inputs}
        host = {k: np.pad(v, 1, mode="wrap") for k, v in inner.items()}
        committed = {k: wrap(jax.device_put(v, grid))
                     for k, v in inner.items()}
        if shape == (2, 2):
            assert all(v.sharding.is_equivalent_to(grid, 3)
                       for v in committed.values())
        base = {k: np.asarray(v) for k, v in res.run({**env, **host}).items()}
        for arrays in (committed, host):
            got = {}
            for strat in ("exchange", "recompute"):
                ex = compile_sharded(res, {**env, **arrays}, mesh,
                                     halo=strat, backend="xla",
                                     cache=ExecutorCache(4))
                want = layout if strat == "exchange" else "replicated"
                assert set(ex.halo_prog.in_layout.values()) == {want}, (
                    name, shape, strat, ex.halo_prog.in_layout)
                got[strat] = {k: np.asarray(v)
                              for k, v in ex({**env, **arrays}).items()}
            for k in base:
                # same slabs, same local executor: bitwise
                assert np.array_equal(got["exchange"][k],
                                      got["recompute"][k]), (name, shape, k)
                err = np.abs(got["exchange"][k] - base[k]).max()
                assert err <= 1e-12 * np.abs(base[k]).max(), (name, shape, k)
            runs += 1
print("OK mg", runs)
"""


def test_forced_4device_mg_in_layouts_subprocess():
    """MG resid and psinv on whole ghost-extended grids, as the class-D
    cell holds them: on a 2x2 mesh every slab operand crosses as laid
    (``"shard"``), on a 4x1 mesh (N = 18 not a multiple of 4) as core and
    tails.  Under each, with inputs committed to the in-spec sharding and
    with replicated ones, exchange equals recompute bitwise and both equal
    the single-device result."""
    assert "OK mg 8" in _run_forced(_MG)


# ---------------------------------------------------------------------------
# named halo work and modelled halo bytes: forced 4-device host mesh
# ---------------------------------------------------------------------------

_NAMED = r"""
import json, os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["RACE_OBS"] = "1"
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro import obs
from repro.apps.paper_kernels import get_case
from repro.core.executor import ExecutorCache
from repro.core.race import race
from repro.shard import compile_sharded
from repro.shard.halo import _edges
from repro.testing.differential import build_env

assert obs.enabled()
N = 10
out = {}
for layout, shape in (("shard", (2, 2)), ("core_tail", (4, 1))):
    mesh = Mesh(np.array(jax.devices()).reshape(shape), ("sx", "sy"))
    grid = NamedSharding(mesh, P(None, "sy", "sx"))
    wrap = jax.jit(lambda x: jnp.pad(x, 1, mode="wrap"))
    case = get_case("resid", N)
    res = race(case.program, reassociate=case.reassociate, mesh=mesh)
    env = build_env(case, np.float32, seed=0)
    rng = np.random.default_rng(0)
    for k in ("V", "U"):  # whole grids, laid out as the benchmark's wrap
        inner = rng.uniform(-1, 1, (N - 2,) * 3).astype(np.float32)
        env[k] = wrap(jax.device_put(inner, grid))
    ex = compile_sharded(res, env, mesh, cache=ExecutorCache(4))
    hp = ex.halo_prog
    info = ex.cache_info()
    out[layout] = dict(
        hlo=ex._jit.lower(dict(env)).compile().as_text(),
        info={k: info[k] for k in ("strategy", "halo_bytes", "in_layout")},
        mesh=dict(shape=list(shape), axes=list(mesh.axis_names)),
        edges={nm: [[sd.dim, sd.mesh_axis, *_edges(sd, s.shape[sd.dim])]
                    for sd in s.slabs]
               for nm, s in hp.specs.items() if s.mode == "slab"},
        shape=N, label=res.plan.program.label,
        gauges=obs.snapshot()["gauges"])
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def named_4device():
    """Sharded MG resid executors over forced 4-device host meshes with
    ``RACE_OBS=1``, one per exchange in-layout: ``"shard"`` on a 2x2 mesh,
    ``"core_tail"`` on a 4x1 mesh, both fed whole 10^3 grids committed as
    the benchmark's periodic wrap leaves them.  Per layout: the compiled
    HLO text, ``cache_info()``, each slab dim's ``(dim, mesh axis, S, kL,
    kR)`` and the obs gauges right after the build."""
    return json.loads(_run_forced(_NAMED).strip().splitlines()[-1])


def _hlo_ops(text: str) -> dict:
    """Compiled HLO text -> ``{(computation, name): op}``, each op a dict
    of ``comp``, ``name``, ``dims`` (array result; None for tuples),
    ``opcode``, ``operands``, ``op_name``, ``pairs`` (collective permutes),
    ``calls`` (fusions) and ``param`` (parameter number)."""
    import re

    ops = {}
    comp = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            comp = head.group(1)
            continue
        m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (\S+) ([a-z][a-z\-]*)\(",
                     line)
        if not m or comp is None:
            continue
        name, shape, opcode = m.groups()
        depth, i = 1, m.end()
        while depth:
            depth += {"(": 1, ")": -1}.get(line[i], 0)
            i += 1
        args = line[m.end():i - 1]
        dims = re.match(r"[a-z]\w*\[([\d,]*)\]", shape)
        pairs = re.search(r"source_target_pairs=\{(.*?)\}\}", line)
        calls = re.search(r"calls=%([\w.\-]+)", line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        ops[(comp, name)] = dict(
            comp=comp, name=name, opcode=opcode,
            dims=(tuple(int(d) for d in dims.group(1).split(",") if d)
                  if dims else None),
            operands=re.findall(r"%([\w.\-]+)", args),
            op_name=op_name.group(1) if op_name else "",
            pairs=(re.findall(r"\{(\d+),(\d+)", pairs.group(1) + "}")
                   if pairs else []),
            calls=calls.group(1) if calls else None,
            param=int(args) if opcode == "parameter" else None)
    return ops


def _upstream(ops: dict, start):
    """Every op whose value flows into ``start``, across fusion bounds."""
    callers = {}
    for op in ops.values():
        if op["calls"]:
            callers.setdefault(op["calls"], []).append(op)
    roots = {}
    for (comp, name), op in ops.items():
        roots[comp] = op  # the computation's last op is its ROOT
    seen, stack = set(), [start]
    while stack:
        op = stack.pop()
        key = (op["comp"], op["name"])
        if key in seen:
            continue
        seen.add(key)
        if op is not start:
            yield op
        if op["calls"]:
            stack.append(roots[op["calls"]])
        if op["param"] is not None:
            stack += [ops[(c["comp"], c["operands"][op["param"]])]
                      for c in callers.get(op["comp"], [])]
        stack += [ops[(op["comp"], o)] for o in op["operands"]
                  if (op["comp"], o) in ops]


def _permutes(ops: dict, scope: str) -> list:
    return [op for op in ops.values()
            if op["opcode"] in ("collective-permute",
                                "collective-permute-start")
            and f"/{scope}/" in op["op_name"]]


@pytest.mark.parametrize("layout", ["shard", "core_tail"])
def test_halo_work_named_on_hlo(named_4device, layout):
    """The exchange's collective permutes carry ``race_halo``.  As laid
    (``"shard"``), nothing before the halo's concatenates writes a whole
    shard and every permute moves edge planes only (at most
    ``max(kL, kR)`` along its dim); as core and tails, the core and tail
    slices before ``shard_map`` carry ``race_shard_in``."""
    from repro.shard.halo import HALO, SHARD_IN

    case = named_4device[layout]
    ops = _hlo_ops(case["hlo"])
    assert case["info"]["strategy"] == "exchange"
    assert set(case["info"]["in_layout"].values()) == {layout}
    names = [op["op_name"] for op in ops.values()]
    exchange = [n for n in names if n.endswith("/ppermute")]
    assert exchange and all(f"/{HALO}/" in n for n in exchange)
    assert _permutes(ops, HALO)
    shard_in = [n for n in names if f"/{SHARD_IN}/" in n]
    assert all("shard_map" not in n for n in shard_in)
    if layout == "core_tail":
        assert any(n.endswith(f"/{SHARD_IN}/slice") for n in shard_in)
        return
    n = case["shape"]
    whole = set()
    for dims in case["edges"].values():
        shard = [n, n, n]
        for dim, _axis, size, _kl, _kr in dims:
            shard[dim] = size
        whole.add(tuple(shard))
    joins = [op for op in ops.values() if op["opcode"] == "concatenate"
             and f"/{HALO}/" in op["op_name"]]
    assert joins
    for join in joins:
        for op in _upstream(ops, join):
            if f"/{SHARD_IN}/" in op["op_name"] or f"/{HALO}/" in op[
                    "op_name"]:
                assert op["dims"] not in whole, (op["name"], op["dims"])
    # a permute's mesh axis is where its source and target partitions
    # differ; along that axis' array dim it moves at most max(kL, kR)
    mesh_shape = case["mesh"]["shape"]
    widest = {}
    for dims in case["edges"].values():
        for dim, axis, _size, k_left, k_right in dims:
            widest[axis] = max(widest.get(axis, 0), k_left, k_right)
            widest[axis, "dim"] = dim
    for op in _permutes(ops, HALO):
        src, dst = (np.unravel_index(int(i), mesh_shape)
                    for i in op["pairs"][0])
        (ax,) = [i for i in range(len(mesh_shape)) if src[i] != dst[i]]
        axis = case["mesh"]["axes"][ax]
        assert op["dims"][widest[axis, "dim"]] <= widest[axis], op


@pytest.mark.parametrize("layout", ["shard", "core_tail"])
def test_halo_bytes_mirror_hlo(named_4device, layout):
    """The modelled halo bytes are the compiled exchange's: each permute
    under ``race_halo`` moves its result on every source-target pair, and
    the sum is ``cache_info()["halo_bytes"]``.  As laid, those are the only
    permutes: no reshard crosses a link."""
    from repro.shard.halo import HALO

    case = named_4device[layout]
    ops = _hlo_ops(case["hlo"])
    moved = sum(4 * int(np.prod(op["dims"])) * len(op["pairs"])
                for op in _permutes(ops, HALO))
    assert moved == case["info"]["halo_bytes"] > 0
    if layout == "shard":
        every = [op for op in ops.values()
                 if op["opcode"].startswith("collective-permute")
                 and not op["opcode"].endswith("-done")]
        assert len(every) == len(_permutes(ops, HALO))


@pytest.mark.parametrize("layout", ["shard", "core_tail"])
def test_shard_halo_bytes_gauge(named_4device, layout):
    """Building the executor sets ``race_shard_halo_bytes`` to the halo
    bytes per call it models for the strategy it chose, and
    ``race_shard_in_as_laid`` per slab operand to 1 where it crosses as
    laid and 0 where it crosses as core and tails."""
    case = named_4device[layout]
    info, gauges, label = case["info"], case["gauges"], case["label"]
    key = (f"race_shard_halo_bytes{{program={label},"
           f"strategy={info['strategy']}}}")
    assert info["halo_bytes"] > 0
    assert gauges[key] == info["halo_bytes"]
    assert set(info["in_layout"]) == {"U", "V"}
    for nm, got in info["in_layout"].items():
        key = f"race_shard_in_as_laid{{array={nm},program={label}}}"
        assert got == layout
        assert gauges[key] == float(layout == "shard")
