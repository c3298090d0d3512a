"""Dimension-generic lowering engine (``repro.lowering``): the closed
capability envelope, probe/engine agreement, and the remaining (genuinely
out-of-model) fallback codes.

Three contracts pinned here:

  * **retired codes lower** — every program class that used to fall back
    with ``depth`` / ``negative-coefficient`` / ``repeated-level`` (and
    ``constant-dim``) now runs on Pallas and matches the XLA realization of
    the same plan at the differential harness's unchanged tolerances;
  * **probe == engine** — ``probe_pallas`` re-derives its verdict from the
    engine's own analysis, so across the full registry plus every negative
    fixture: an eligible probe means ``specialize_stencil`` succeeds (at
    block sizes holding the halo spread — the agreement test runs the
    defaults, where every fixture fits), an ineligible one means it raises
    ``LoweringError`` carrying the *same* structured reasons (the
    stale-fallback-drift regression);
  * **remaining codes reachable** — each still-active fallback code has a
    dedicated negative fixture, so the envelope cannot silently shrink or
    grow without a test noticing.
"""
from fractions import Fraction

import numpy as np
import pytest

from repro.apps.paper_kernels import CASES, Case, get_case
from repro.core.backend import probe_pallas, select_backend
from repro.core.depgraph import finalize
from repro.core.detect import AuxDef, Transformed
from repro.core.executor import clear_cache, compile_plan, dtype_of
from repro.core.ir import Scalar, arr, loopnest, program
from repro.core.race import race
from repro.kernels.ref import reference, reference_plan
from repro.lowering import (R_FRACTIONAL_OFFSET, R_INCONSISTENT_LAYOUT,
                            R_LHS_FORM, R_MIXED_STRIDE, R_NO_BASE_ARRAY,
                            R_STRIDED_AUX, R_ZERO_COEF, RETIRED_CODES,
                            LoweringError, analyze_plan, specialize_stencil)
from repro.testing import build_env, coverage_matrix, run_case
from repro.testing.differential import SWEEP_SIZES

pytestmark = [pytest.mark.pallas, pytest.mark.lowering]


def _sig(env):
    return ({nm: np.shape(v) for nm, v in env.items()},
            {nm: dtype_of(v) for nm, v in env.items()})


def _sig_for(case):
    """(shapes, dtypes) for a case — via build_env when the program is
    evaluable, else a plausible fabricated signature (fractional subscripts
    defeat required_shapes; the engine must reject on structure alone)."""
    try:
        return _sig(build_env(case, np.float32))
    except Exception:
        from repro.core.ir import expr_refs

        names = set()
        for st in case.program.body:
            names.add((st.lhs.name, len(st.lhs.subs)))
            for r in expr_refs(st.rhs):
                names.add((r.name, len(r.subs)))
        shapes = {nm: (12,) * nd for nm, nd in names}
        return shapes, {nm: np.float32 for nm in shapes}


def _check_case(case, **kw):
    """Differential-verify a synthetic case at unchanged tolerances and
    require Pallas coverage (no reasoned fallback either)."""
    report = run_case(case, reassociate_levels=(0, case.reassociate), **kw)
    assert not report.failures(), coverage_matrix([report])
    assert report.pallas_covered(), coverage_matrix([report])
    return report


# ---------------------------------------------------------------------------
# retired codes: the widened envelope runs on Pallas
# ---------------------------------------------------------------------------


def test_registry_zero_retired_fallbacks():
    """Acceptance: probe_pallas reports zero depth / negative-coefficient /
    repeated-level (and constant-dim) fallbacks across the full registry —
    every case is eligible, with no reasons at all."""
    for name in sorted(CASES):
        case = get_case(name, SWEEP_SIZES.get(name))
        for lvl in sorted({0, case.reassociate}):
            res = race(case.program, reassociate=lvl,
                       rewrite_div=case.rewrite_div)
            cap = probe_pallas(res.plan)
            assert cap.eligible, (name, lvl, cap.explain())
            assert not cap.reasons, (name, lvl)
            assert not any(r.code in RETIRED_CODES for r in cap.reasons)


def test_registry_envelope_cases_present():
    """The four envelope rows are full registry members (and therefore get
    swept by test_registry_differential like every Table 1 case)."""
    for name in ("smooth1d", "blocked4d", "mirror_deriv", "diag2d"):
        assert name in CASES


def test_1d_depth_lowers():
    loops, (i,) = loopnest(("i", 2, 30))
    u, out = arr("u"), arr("o1")
    s3 = (u[i - 1] + u[i]) + u[i + 1]
    case = Case("depth1", "synthetic",
                program(loops, [(out[i], s3 + u[i + 2])]), reassociate=3)
    _check_case(case)
    res = race(case.program, reassociate=3)
    cap = probe_pallas(res.plan)
    assert any(f.code == "depth" for f in cap.facts)


def test_1d_block_inner_tiles_single_level():
    """For a 1-D nest block_inner overrides block_rows as the level tile."""
    loops, (i,) = loopnest(("i", 1, 40))
    u, out = arr("u"), arr("o1i")
    case = Case("depth1i", "synthetic",
                program(loops, [(out[i], (u[i - 1] + u[i]) + u[i + 1])]),
                reassociate=3)
    _check_case(case, block_inner=16)


def test_4d_depth_lowers():
    loops, (h, d, j, i) = loopnest(("h", 1, 4), ("d", 1, 4), ("j", 1, 5),
                                   ("i", 1, 5))
    T, out = arr("T"), arr("o4s")
    pair = lambda dj: T[h, d, j + dj, i] + T[h, d, j + dj, i + 1]  # noqa: E731
    case = Case("depth4", "synthetic",
                program(loops, [(out[h, d, j, i], pair(0) + pair(-1))]),
                reassociate=3)
    _check_case(case)
    res = race(case.program, reassociate=3)
    assert any(f.code == "depth" for f in probe_pallas(res.plan).facts)


def test_negative_coefficient_mirrored_window():
    """All-mirrored references lower through the flipped-origin window."""
    loops, (i, j) = loopnest(("i", 1, 9), ("j", 1, 9))
    u, out = arr("u"), arr("on")
    M = 10
    pair = lambda dj: u[-i + M, j + dj] + u[-i + (M - 1), j + dj]  # noqa: E731
    case = Case("negc", "synthetic",
                program(loops, [(out[i, j], pair(0) + pair(-1))]),
                reassociate=3)
    _check_case(case)
    res = race(case.program, reassociate=3)
    cap = probe_pallas(res.plan)
    assert any(f.code == "negative-coefficient" for f in cap.facts)


def test_negative_strided_coefficient():
    """|a| = 2 mirrored references: flip + stride normalization compose."""
    loops, (i, j) = loopnest(("i", 1, 6), ("j", 1, 9))
    u, out = arr("u"), arr("ons")
    K = 14
    pair = lambda dj: u[-2 * i + K, j + dj] + u[-2 * i + (K - 1), j + dj]  # noqa: E731
    case = Case("negs", "synthetic",
                program(loops, [(out[i, j], pair(0) + pair(-1))]),
                reassociate=3)
    _check_case(case)


def test_negative_coefficient_inner_level():
    """Mirrored *innermost* (unblocked) level — the pad/halo side."""
    loops, (i, j) = loopnest(("i", 1, 9), ("j", 1, 9))
    u, out = arr("u"), arr("oni")
    M = 10
    pair = lambda di: u[i + di, -j + M] + u[i + di, -j + (M - 1)]  # noqa: E731
    case = Case("negi", "synthetic",
                program(loops, [(out[i, j], pair(0) + pair(-1))]),
                reassociate=3)
    _check_case(case)


def test_repeated_level_gather():
    loops, (i, j) = loopnest(("i", 1, 9), ("j", 1, 9))
    g, u, out = arr("g"), arr("u"), arr("orp")
    t = lambda dj: g[i, i] * u[i, j + dj]  # noqa: E731
    case = Case("repl", "synthetic",
                program(loops, [(out[i, j], t(0) + t(-1))]), reassociate=3)
    _check_case(case)
    res = race(case.program, reassociate=3)
    assert any(f.code == "repeated-level"
               for f in probe_pallas(res.plan).facts)


def test_constant_dim_gather():
    loops, (i, j) = loopnest(("i", 1, 9), ("j", 1, 9))
    c, u, out = arr("c"), arr("u"), arr("ocd")
    t = lambda dj: c[i, 0] * u[i, j + dj]  # noqa: E731
    case = Case("cdim", "synthetic",
                program(loops, [(out[i, j], t(0) + t(-1))]), reassociate=3)
    _check_case(case)
    res = race(case.program, reassociate=3)
    assert any(f.code == "constant-dim" for f in probe_pallas(res.plan).facts)


def test_repeated_level_3d_both_grid_axes():
    """A diagonal over the two *blocked* levels of a 3-D nest: the gather's
    program_id arithmetic must track both grid axes."""
    loops, (j, k, i) = loopnest(("j", 1, 10), ("k", 1, 10), ("i", 1, 10))
    g, u, out = arr("g3"), arr("u"), arr("od3")
    t = lambda di: g[j, j, k] * u[i + di, k, j]  # noqa: E731
    case = Case("repl3", "synthetic",
                program(loops, [(out[i, k, j], t(0) + t(1))]), reassociate=3)
    _check_case(case, block_rows=4, block_cols=4)


def test_mixed_dim_level_order_transpose():
    """A 3-D operand referenced as ``mx[k, i, j]`` in a (j, k, i) nest: the
    dim->level permutation is neither identity nor full reversal, so the
    input transpose must be the true argsort (a latent bug in the pre-engine
    kernel, which used its inverse — indistinguishable on the registry's
    involution orders)."""
    loops, (j, k, i) = loopnest(("j", 1, 7), ("k", 1, 7), ("i", 1, 7))
    mx, out = arr("mx"), arr("omx")
    t = lambda dk: mx[k + dk, i, j]  # noqa: E731
    case = Case("mixorder", "synthetic",
                program(loops, [(out[i, k, j], t(0) + t(1))]), reassociate=0)
    _check_case(case)


# ---------------------------------------------------------------------------
# probe == engine: the stale-fallback-drift regression
# ---------------------------------------------------------------------------


def _negative_fixtures():
    """(case, expected code) for every still-active fallback code."""
    fixtures = []
    loops2 = lambda: loopnest(("i", 1, 6), ("j", 1, 6))  # noqa: E731
    u = arr("u")

    loops, (i, j) = loops2()
    out = arr("f_lhs")
    fixtures.append((Case("lhsform", "synthetic", program(
        loops, [(out[i, i], u[i, j] + u[i, j - 1])]), reassociate=0),
        R_LHS_FORM))

    loops, (i, j) = loops2()
    out = arr("f_zero")
    fixtures.append((Case("zerocoef", "synthetic", program(
        loops, [(out[i, j], u[0 * i + 3, j] + u[0 * i + 3, j - 1])]),
        reassociate=0), R_ZERO_COEF))

    loops, (i, j) = loops2()
    out = arr("f_frac")
    fixtures.append((Case("fracoff", "synthetic", program(
        loops, [(out[i, j], u[i + Fraction(1, 2), j] + u[i, j])]),
        reassociate=0), R_FRACTIONAL_OFFSET))

    loops, (i, j) = loops2()
    out = arr("f_mix")
    fixtures.append((Case("mixstride", "synthetic", program(
        loops, [(out[i, j], u[2 * i, j] + u[i, j])]), reassociate=0),
        R_MIXED_STRIDE))

    loops, (i, j) = loops2()
    out = arr("f_lay")
    fixtures.append((Case("inclayout", "synthetic", program(
        loops, [(out[i, j], u[i, j] + u[j, i])]), reassociate=0),
        R_INCONSISTENT_LAYOUT))

    loops, (i, j) = loops2()
    out = arr("f_scal")
    fixtures.append((Case("nobase", "synthetic", program(
        loops, [(out[i, j], Scalar("s") * 2.0)]), reassociate=0,
        scalars=("s",)), R_NO_BASE_ARRAY))
    return fixtures


def _strided_aux_plan():
    """Hand-built plan whose auxiliary is referenced with a non-unit
    coefficient (detection never emits this; the probe guards it anyway)."""
    loops, (i, j) = loopnest(("i", 2, 6), ("j", 2, 6))
    u, aa, out = arr("u"), arr("aa"), arr("f_aux")
    prog = program(loops, [(out[i, j], u[i, j])])
    body = (program(loops, [(out[i, j], aa[2 * i, j] + aa[i, j])]).body)
    t = Transformed(prog, [AuxDef("aa", (1, 2), u[i, j] + u[i, j - 1],
                                  round=1, eri_key=(), n_members=2)],
                    body, rounds=1)
    return finalize(t, contraction=False)


@pytest.mark.parametrize("case,code",
                         _negative_fixtures(),
                         ids=lambda v: v if isinstance(v, str) else v.name)
def test_remaining_fallback_code_reachable(case, code):
    res = race(case.program)
    cap = probe_pallas(res.plan)
    assert not cap.eligible
    assert code in {r.code for r in cap.reasons}, cap.explain()
    assert not any(r.code in RETIRED_CODES for r in cap.reasons)
    # and the engine refuses with the same reasons (never a crash elsewhere)
    with pytest.raises(LoweringError) as exc:
        specialize_stencil(res.plan, *_sig_for(case))
    assert set(exc.value.codes) == {r.code for r in cap.reasons}


def test_strided_aux_reachable():
    plan = _strided_aux_plan()
    cap = probe_pallas(plan)
    assert not cap.eligible
    assert R_STRIDED_AUX in {r.code for r in cap.reasons}
    with pytest.raises(LoweringError):
        specialize_stencil(plan, {"u": (8, 8), "f_aux": (8, 8)},
                           {"u": np.float32, "f_aux": np.float32})


def test_probe_engine_agreement_full_registry():
    """Regression (stale-fallback drift): capability() is re-derived from
    the lowering engine, so across the full registry + every negative
    fixture, probe verdict and specialize outcome must agree exactly."""
    plans = []
    for name in sorted(CASES):
        case = get_case(name, SWEEP_SIZES.get(name))
        res = race(case.program, reassociate=case.reassociate,
                   rewrite_div=case.rewrite_div)
        plans.append((name, res.plan, _sig_for(case)))
    for case, _ in _negative_fixtures():
        res = race(case.program)
        plans.append((case.name, res.plan, _sig_for(case)))
    for name, plan, sig in plans:
        cap = probe_pallas(plan)
        if cap.eligible:
            spec = specialize_stencil(plan, *sig)  # must not raise
            assert spec.analysis.eligible
        else:
            with pytest.raises(LoweringError) as exc:
                specialize_stencil(plan, *sig)
            assert set(exc.value.codes) == {r.code for r in cap.reasons}, name


def test_capability_reports_facts():
    case = get_case("mirror_deriv", SWEEP_SIZES["mirror_deriv"])
    res = race(case.program, reassociate=case.reassociate)
    cap = res.capability()
    assert cap.eligible
    assert any(f.code == "negative-coefficient" for f in cap.facts)
    assert "mirrored-origin" in cap.explain()


# ---------------------------------------------------------------------------
# engine artifacts through the serving layers
# ---------------------------------------------------------------------------


def test_envelope_case_through_executor_cache():
    """An envelope case runs through compile_plan/CompiledRace against the
    LoweredStencil artifact with the zero-retrace guarantee intact."""
    case = get_case("diag2d", SWEEP_SIZES["diag2d"])
    res = race(case.program, reassociate=case.reassociate)
    env = build_env(case, np.float32)
    clear_cache()
    ex = compile_plan(res.plan, env, "pallas")
    out1 = ex(env)
    out2 = ex(env)
    assert ex.trace_count == 1
    assert compile_plan(res.plan, env, "pallas") is ex
    want = reference_plan(res.plan, env)
    for k in want:
        np.testing.assert_allclose(np.asarray(out1[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(out2[k]), np.asarray(out1[k]))


def test_envelope_case_run_batch():
    """The gather path (program_id indexing) must stay vmap-batchable."""
    case = get_case("diag2d", SWEEP_SIZES["diag2d"])
    res = race(case.program, reassociate=case.reassociate)
    envs = [build_env(case, np.float32, seed=s) for s in range(3)]
    got = res.run_batch(envs, "pallas")
    for b, env in enumerate(envs):
        want = res.run(env, "pallas")
        for k in want:
            np.testing.assert_allclose(np.asarray(got[k][b]),
                                       np.asarray(want[k]), rtol=1e-6)


def test_mirrored_case_run_backend_auto():
    case = get_case("mirror_deriv", SWEEP_SIZES["mirror_deriv"])
    res = race(case.program, reassociate=case.reassociate)
    sel = select_backend(res.plan, "auto")
    assert sel.backend == "pallas" and not sel.fell_back
    env = build_env(case, np.float32)
    got = res.run(env, "auto")
    want = reference(res.plan, env)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-4, atol=1e-5)


def test_shim_reexports():
    """kernels.race_stencil is a thin compatibility shim over the engine."""
    import repro.kernels.race_stencil as shim
    import repro.lowering as lowering

    assert shim.specialize_stencil is lowering.specialize_stencil
    assert shim.race_stencil_call is lowering.race_stencil_call
    assert shim.StencilSpec is lowering.LoweredStencil
    assert shim.plan_geometry is lowering.plan_geometry


def test_block_grid_generic_depths():
    from repro.tuning.space import block_grid

    case1 = get_case("smooth1d", 600)
    plan1 = race(case1.program, reassociate=3).plan
    grid1 = block_grid(plan1)
    assert grid1 == [(8, 8, 0)]  # 1-D: the lane axis is one whole block

    case2 = get_case("gaussian", 600)
    plan2 = race(case2.program, reassociate=3).plan
    grid2 = block_grid(plan2)
    assert (8, 8, 0) in grid2 and (16, 8, 0) in grid2
    inner = [bi for _, _, bi in grid2 if bi]
    assert inner and all(bi % 128 == 0 for bi in inner)  # lane-aligned

    case4 = get_case("blocked4d", 14)
    plan4 = race(case4.program, reassociate=3).plan
    grid4 = block_grid(plan4)
    assert (8, 8, 0) in grid4 and (8, 16, 0) in grid4  # middle levels


def test_halo_error_names_knob():
    """An offset spread no block can hold still raises the actionable
    message naming the knob to raise."""
    loops, (i, j) = loopnest(("i", 9, 40), ("j", 1, 40))
    u, out = arr("u"), arr("oh")
    case = Case("halo", "synthetic", program(
        loops, [(out[i, j], u[i - 9, j] + u[i + 9, j])]), reassociate=0)
    res = race(case.program)
    env = build_env(case, np.float32)
    with pytest.raises(ValueError, match="block_rows"):
        specialize_stencil(res.plan, *_sig(env), block_rows=8)
    # a block that holds the spread lowers and verifies
    _check_case(case, block_rows=16)
