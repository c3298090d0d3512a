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
from repro.apps.paper_kernels import CASES, get_case
from repro.core.backend import probe_pallas
from repro.core.codegen import required_shapes
from repro.core.ir import expr_refs
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
    """A registry case at size ``n``, or at ``(nx, ny)`` for a 2-D builder
    that takes both."""
    case = CASES[name][0](*n) if isinstance(n, tuple) else get_case(name, n)
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
    ("resid", 256, 0),
    ("hdifft_gm", (320, 384), 60),  # POP gx1v6, all 60 levels in a batch
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


def _layout_ops(text: str) -> tuple:
    """The whole-array ops of a compiled module's entry computation besides
    the kernel (a result at least 90% of the largest parameter), and those
    of them that lay an array out anew: a transpose, a result in another
    dim order than the parameters' row-major one, or a copy whose result
    is laid out otherwise than its operand."""
    import re

    entry = text[text.index("\nENTRY "):].split("\n}", 1)[0]
    ops = {}
    for name, shape, opcode, args in re.findall(
            r"\n\s+(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) "
            r"([a-z][a-z\-]*)\(([^)]*)\)", entry):
        arrays = [([int(d) for d in dims.split(",") if d], lay)
                  for dims, lay in re.findall(
                      r"[a-z]\w*\[([\d,]*)\]\{([\d,]*)", shape)]
        ops[name] = dict(
            opcode=opcode, arrays=arrays,
            operands=re.findall(r"%([\w.\-]+)", args),
            size=max((int(np.prod(d)) for d, _ in arrays), default=0))
    biggest = max(op["size"] for op in ops.values()
                  if op["opcode"] == "parameter")
    whole, relayout = [], []
    for name, op in ops.items():
        if op["size"] < 0.9 * biggest or op["opcode"] in (
                "parameter", "bitcast", "get-tuple-element", "tuple",
                "custom-call"):
            continue
        whole.append(name)
        src = ops.get(op["operands"][0]) if op["operands"] else None
        if (op["opcode"] == "transpose"
                or any(lay != ",".join(map(str, range(len(d) - 1, -1, -1)))
                       for d, lay in op["arrays"] if d)
                or (op["opcode"] in ("copy", "copy-start") and src
                    and src["arrays"][:1] != op["arrays"][:1])):
            relayout.append(name)
    return whole, relayout


@pytest.mark.parametrize("name,n,batch,at_most", [
    ("resid", 258, 0, 2),                 # NPB MG class B, the cell's size
    ("psinv", 258, 0, 2),
    ("hdifft_gm", (320, 384), 60, None),  # POP gx1v6, all 60 levels
])
def test_one_chip_modules_lay_out_nothing(one_chip, on_tpu, name, n, batch,
                                          at_most):
    """The one-chip executor's module at the benchmark cells' sizes: its
    kernel iterates as laid, so no whole operand or output is copied into
    another dim order or transposed around it, and MG keeps at most its
    input pad and its centre operand's slice outside the kernel.  The same
    module level-major holds five such ops in MG: the two operands' and the
    output's relayouts, the pad and the slice."""
    from repro.core.executor import ExecutorCache, compile_plan
    from repro.lowering import ORDER_AS_LAID, ORDER_LEVEL_MAJOR

    case, plan = _plan(name, n)
    shapes = required_shapes(case.program)
    if name != "hdifft_gm":  # MG holds every grid at 258^3 (NPB's comm3)
        shapes = {k: (n,) * 3 if s else () for k, s in shapes.items()}
    body = case.program.body
    outs = ({st.lhs.name for st in body}
            - {r.name for st in body for r in expr_refs(st.rhs)})
    one = {k: jax.ShapeDtypeStruct(tuple(s), jnp.float32, sharding=one_chip)
           for k, s in shapes.items() if k not in outs}
    texts = {}
    for order in (ORDER_AS_LAID, ORDER_LEVEL_MAJOR):
        ex = compile_plan(plan, one, "pallas", cache=ExecutorCache(),
                          order=order)
        if batch:
            lowered = ex.batch_jit().lower({
                k: jax.ShapeDtypeStruct((batch,) + v.shape, v.dtype,
                                        sharding=one_chip)
                for k, v in one.items()})
        else:
            lowered = ex._jit.lower(*ex._split(one))
        texts[order] = lowered.compile().as_text()
    whole, relayout = _layout_ops(texts[ORDER_AS_LAID])
    assert relayout == [], whole
    if at_most is not None:
        assert len(whole) <= at_most, whole
    whole, relayout = _layout_ops(texts[ORDER_LEVEL_MAJOR])
    assert relayout, whole  # the check sees what the order removes
    if at_most is not None:
        assert len(whole) == 5 and len(relayout) == 3, whole


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


def test_stable_names_compile_for_v5e(one_chip, on_tpu):
    """The executor's jitted steps compile to modules named after the
    program and the plan, and the compiled Pallas kernel's op (the name
    the device trace's ``XLA Ops`` line shows) is ``race_kernel_<program>``,
    ``vmap_race_kernel_<program>_`` in the batch."""
    from repro.core.executor import ExecutorCache, compile_plan, plan_hash

    case, plan = _plan("psinv", 34)
    args = {k: jax.ShapeDtypeStruct(tuple(s), jnp.float32, sharding=one_chip)
            for k, s in required_shapes(case.program).items()}
    ex = compile_plan(plan, args, "pallas", cache=ExecutorCache())
    assert ex.spec.interpret is False
    ph = plan_hash(plan)[:8]
    text = ex._jit.lower(*ex._split(args)).compile().as_text()
    assert text.startswith(f"HloModule jit_race_psinv_{ph},")
    assert "%race_kernel_psinv." in text and "%_call." not in text
    batch = {k: jax.ShapeDtypeStruct((3,) + v.shape, v.dtype,
                                     sharding=one_chip)
             for k, v in args.items()}
    text = ex.batch_jit().lower(batch).compile().as_text()
    assert text.startswith(f"HloModule jit_race_batch_psinv_{ph},")
    assert "%vmap_race_kernel_psinv_" in text
