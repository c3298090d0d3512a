"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached, so these tests refuse on the CPU host what the chip
would refuse: a block not aligned to the (8, 128) tiling, a kernel that
needs more VMEM than it may use, a program that does not fit the device.
Inputs are shapes only (``jax.ShapeDtypeStruct``); nothing runs.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every xdist worker
imports this file.  All such compiles live in this one file so that one
worker holds the library.  The persistent compilation cache is off around
them: an entry written here cannot be read back without a chip.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.core.backend as backend
from repro.apps.paper_kernels import get_case
from repro.core.backend import probe_pallas
from repro.core.codegen import required_shapes
from repro.core.race import race
from repro.lowering import R_TPU_GATHER, R_TPU_STRIDED, specialize_stencil

pytestmark = pytest.mark.pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True, scope="module")
def _no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """The probe judges for a TPU: the host's default backend is the CPU,
    so the platform choice is steered here, in these tests only."""
    import repro.lowering.emit as emit

    monkeypatch.setattr(emit, "target_platform", lambda: "tpu")
    monkeypatch.setattr(backend, "target_platform", lambda: "tpu")


def _plan(name, n):
    case = get_case(name, n)
    return case, race(case.program, reassociate=case.reassociate,
                      rewrite_div=case.rewrite_div).plan


def _compile(plan, program, sharding, batch=0, **blocks):
    shapes = required_shapes(program)
    spec = specialize_stencil(plan, shapes,
                              {k: np.dtype(np.float32) for k in shapes},
                              interpret=False, **blocks)
    assert spec.interpret is False
    lead = (batch,) if batch else ()
    args = {k: jax.ShapeDtypeStruct(lead + tuple(s), jnp.float32,
                                    sharding=sharding)
            for k, s in shapes.items()}
    fn = jax.vmap(spec.apply) if batch else spec.apply
    compiled = jax.jit(fn).lower(args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not XLA
    return compiled


@pytest.mark.parametrize("name,n,batch", [
    ("psinv", 256, 0),        # NPB MG class B
    ("j3d27pt", 256, 0),
    ("gaussian", 2048, 0),
    ("psinv", 256, 4),        # the serving runtime's vmapped batch
    ("gaussian", 2048, 3),
    ("rhs_ph1", 256, 0),      # rank-1 window operands of a 3-D nest
    ("smooth1d", 65536, 0),   # a 1-D nest, lifted to rank 2
    ("smooth1d", 65536, 2),
])
def test_kernel_compiles_for_v5e(one_chip, on_tpu, name, n, batch):
    case, plan = _plan(name, n)
    assert probe_pallas(plan).eligible
    _compile(plan, case.program, one_chip, batch)


def test_tuning_grid_compiles_for_v5e(one_chip):
    """Every block configuration the autotuner proposes is one the TPU
    compiler accepts (the lane-aligned innermost tile included)."""
    from repro.tuning.space import block_grid

    case, plan = _plan("gaussian", 2048)
    grid = block_grid(plan)
    assert any(bi for _, _, bi in grid)
    for br, bc, bi in grid:
        _compile(plan, case.program, one_chip, block_rows=br, block_cols=bc,
                 block_inner=bi)


@pytest.mark.parametrize("name,code", [("rprj3", R_TPU_STRIDED),
                                       ("diag2d", R_TPU_GATHER)])
def test_probe_refuses_what_v5e_refuses(one_chip, monkeypatch, name, code):
    """The probe and the compiler agree: a plan the TPU compiler refuses is
    refused by the probe for a TPU, with its pinned reason code, and the
    compiled specialization raises the same reason instead of running."""
    case, plan = _plan(name, 32)
    assert probe_pallas(plan).eligible  # the CPU backend interprets it
    monkeypatch.setattr(backend, "target_platform", lambda: "tpu")
    cap = probe_pallas(plan)
    assert not cap.eligible and code in {r.code for r in cap.reasons}
    with pytest.raises(ValueError, match=code):
        _compile(plan, case.program, one_chip)


def test_sharded_path_compiles_for_v5e_2x2(topo, on_tpu):
    """``race(prog, mesh=...)`` on psinv at NPB MG class C (512^3) over the
    described 2x2 mesh, with both halo transports: the local kernel is the
    compiled one, and exchange moves halos with collective permutes."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core.executor import ExecutorCache
    from repro.shard import compile_sharded

    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("sx", "sy"))
    case = get_case("psinv", 512)
    res = race(case.program, reassociate=case.reassociate, mesh=mesh)
    rep = NamedSharding(mesh, PartitionSpec())
    args = {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=rep)
            for k, s in required_shapes(case.program).items()}
    for halo in ("exchange", "recompute"):
        ex = compile_sharded(res, args, mesh, halo=halo,
                             cache=ExecutorCache())
        assert ex.local.backend == "pallas" and ex.local.spec.interpret is False
        text = ex._jit.lower(args).compile().as_text()
        assert "tpu_custom_call" in text
        assert ("collective-permute" in text) == (halo == "exchange")
