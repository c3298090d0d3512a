"""Pallas RACE-stencil kernel, run through ``compile_plan(..., "pallas")``,
vs the pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.apps.paper_kernels import (get_case, pop_calc_tpoints,
                                      stencil_gaussian, stencil_j3d27pt,
                                      stencil_poisson)
from repro.core.codegen import required_shapes
from repro.core.executor import compile_plan
from repro.core.race import race
from repro.testing import reference, reference_plan


def _env(case, dtype, seed=0):
    rng = np.random.default_rng(seed)
    env = {}
    for nm, shp in required_shapes(case.program).items():
        if nm in case.scalars or shp == ():
            env[nm] = dtype(rng.uniform(0.25, 1.0))
        else:
            env[nm] = rng.uniform(-1, 1, shp).astype(dtype)
    return env


def _run(case, dtype=np.float32, block_rows=8, reassociate=None, rtol=None):
    res = race(case.program,
               reassociate=case.reassociate if reassociate is None else reassociate)
    env = _env(case, dtype)
    got = compile_plan(res.plan, env, "pallas", block_rows=block_rows).run(env)
    want = reference(res.plan, env)
    rtol = rtol or (2e-2 if dtype == np.float16 else 2e-4)
    for k in want:
        np.testing.assert_allclose(
            np.asarray(got[k], np.float64), np.asarray(want[k], np.float64),
            rtol=rtol, atol=rtol, err_msg=k)
    # also agree with the XLA realization of the same plan (tight: same order)
    want2 = reference_plan(res.plan, env)
    for k in want2:
        np.testing.assert_allclose(
            np.asarray(got[k], np.float64), np.asarray(want2[k], np.float64),
            rtol=1e-5, atol=1e-5, err_msg=k)


@pytest.mark.parametrize("n", [12, 20, 33])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_gaussian_2d_sweep(n, dtype):
    _run(stencil_gaussian(n), dtype=dtype)


@pytest.mark.parametrize("n,block_rows", [(10, 4), (14, 8), (18, 5)])
def test_j3d27pt_3d_sweep(n, block_rows):
    _run(stencil_j3d27pt(n), block_rows=block_rows)


def test_poisson_3d():
    _run(stencil_poisson(12))


def test_pop_calc_tpoints_transcendental():
    # sin/cos in-kernel; binary (bitwise-faithful) plan
    _run(pop_calc_tpoints(18, 14), reassociate=0)


def test_block_not_dividing_rows():
    # extents deliberately not a multiple of block_rows
    _run(stencil_gaussian(23), block_rows=8)


def test_diffusion_reconstruction():
    _run(get_case("diffusion1", 12))


def test_vmem_contraction_no_hbm_aux():
    """Structural: the kernel's HBM operands are only the base arrays,
    scalars and outputs — no auxiliary array buffers (the contraction
    claim)."""
    case = stencil_gaussian(16)
    res = race(case.program, reassociate=3)
    assert res.n_aux_materialized() > 0  # plan does have auxs...
    import jax

    env = _env(case, np.float32)
    ex = compile_plan(res.plan, env, "pallas")
    txt = jax.jit(ex.core_fn).lower(env).as_text()
    for aux in res.plan.aux_order:
        assert f"{aux.name}" not in txt  # ...but none ever named in HLO I/O
