"""Ahead-of-time compiles of every cell's kernels, at the cell's sizes, for
a described TPU v5e.

NPB MG class B's resid and psinv at 258^3, POP gx1v6's hdifft_gm on
320 x 384 at batches 1, 8 (the serve runtime's largest) and 60 (all
levels), and NPB MG class D's resid and psinv (1026^3) sharded over a 2x2
mesh with the halo exchange, which no single chip can hold.  The compiled
programs must hold the Pallas kernel (``tpu_custom_call``): the cells
measure it, not an XLA fallback.  Inputs are shapes only; nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around these
compiles: an entry written here cannot be read back without a chip.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp


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
    """The probe and the lowering judge for a TPU: the host's backend is
    the CPU, so the platform is steered here, in these tests only."""
    import repro.core.backend as backend
    import repro.lowering.emit as emit

    monkeypatch.setattr(emit, "target_platform", lambda: "tpu")
    monkeypatch.setattr(backend, "target_platform", lambda: "tpu")


def _compile(plan, shapes, scalars, sharding, batch=0):
    from repro.lowering import specialize_stencil

    names = {**{k: s for k, s in shapes.items()}, **{k: () for k in scalars}}
    spec = specialize_stencil(plan, names,
                              {k: np.dtype(np.float32) for k in names},
                              interpret=False)
    lead = (batch,) if batch else ()
    args = {k: jax.ShapeDtypeStruct(lead + tuple(s), jnp.float32,
                                    sharding=sharding)
            for k, s in names.items()}
    fn = jax.vmap(spec.apply) if batch else spec.apply
    text = jax.jit(fn).lower(args).compile().as_text()
    assert "tpu_custom_call" in text  # the kernel, not XLA


@pytest.mark.parametrize("name,inputs", [("resid", ("V", "U")),
                                         ("psinv", ("U", "R"))])
def test_npb_mg_b_kernels_compile(one_chip, on_tpu, name, inputs):
    from repro.apps.paper_kernels import get_case
    from repro.core.race import race

    case = get_case(name, 258)
    plan = race(case.program, reassociate=case.reassociate).plan
    _compile(plan, {k: (258, 258, 258) for k in inputs}, case.scalars,
             one_chip)


@pytest.mark.parametrize("batch", [0, 8, 60])
def test_pop_gx1v6_kernel_compiles(one_chip, on_tpu, batch):
    from repro.apps.paper_kernels import CASES
    from repro.core.race import race

    case = CASES["hdifft_gm"][0](320, 384)
    plan = race(case.program, reassociate=case.reassociate).plan
    _compile(plan, {"T": (320, 384), "S": (320, 384)}, (), one_chip, batch)


@pytest.mark.parametrize("name,inputs", [("resid", ("V", "U")),
                                         ("psinv", ("U", "R"))])
def test_npb_mg_d_2x2_compiles(topo, on_tpu, name, inputs):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.apps.paper_kernels import get_case
    from repro.core.executor import ExecutorCache
    from repro.core.race import race
    from repro.shard import compile_sharded

    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("sx", "sy"))
    case = get_case(name, 1026)
    res = race(case.program, reassociate=case.reassociate, mesh=mesh)
    grid = NamedSharding(mesh, P(None, "sy", "sx"))
    args = {k: jax.ShapeDtypeStruct((1026,) * 3, jnp.float32, sharding=grid)
            for k in inputs}
    args.update({k: jax.ShapeDtypeStruct((), jnp.float32,
                                         sharding=NamedSharding(mesh, P()))
                 for k in case.scalars})
    ex = compile_sharded(res, args, mesh, cache=ExecutorCache())
    compiled = ex._jit.lower(args).compile()
    text = compiled.as_text()
    assert ex.halo_prog.strategy == "exchange"
    assert "tpu_custom_call" in text and "collective-permute" in text
    mem = compiled.memory_analysis()
    # per chip: about 2.5 GB of arguments and 4.9 GB of temporaries
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
