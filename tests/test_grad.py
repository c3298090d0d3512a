"""Differentiable RACE (ISSUE 6): ``jax.grad`` through the optimized
serving path.

The executor wraps every compiled program in a ``jax.custom_vjp`` whose
backward rule is a *transposed stencil program* (``repro.core.adjoint``):
read/write roles swapped, offsets negated, coefficients transposed — then
pushed back through the RACE detector and backend layer, so the VJP itself
gets auxiliary-array elimination, plan-keyed executor caching, and (where
eligible) Pallas lowering.  Pinned here:

  * gradients through ``res.run`` match ``jax.grad`` of the naive baseline
    across cases, reassociation levels, and both forward backends;
  * cases the adjoint detector refuses (strided reads, repeated levels)
    carry their refusal reason and still differentiate via the autodiff
    fallback — refusal is never silent and never wrong;
  * adjoint plans are first-class executor-cache citizens: distinct keys
    from the forward plan, eliminated auxiliaries (``reduced_ops > 0``),
    cache hits (zero retraces) from the second step on;
  * the lowering probe rejects rank-0 (loop-invariant) auxiliaries that
    adjoint plans can produce (``R_SCALAR_AUX``) instead of crashing the
    Pallas emitter;
  * ``@race_kernel`` functions and ``run_batch`` (vmap) differentiate;
  * ``$RACE_ADJOINT`` / ``$RACE_ADJOINT_REASSOCIATE`` knobs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.paper_kernels import get_case
from repro.core.adjoint import (ADJOINT_PREFIX, REPEATED_LEVEL, STRIDED_READ,
                                adjoint_build, adjoint_mode,
                                adjoint_reassociate, backward)
from repro.core.codegen import interior
from repro.core.executor import executor_cache, plan_hash
from repro.core.race import race
from repro.testing.differential import (build_env, default_tolerances,
                                        run_grad_case)

pytestmark = pytest.mark.grad


@pytest.fixture(autouse=True)
def fresh_executor_cache():
    executor_cache().clear()
    yield
    executor_cache().clear()


def _loss_grads(res, env, diff_keys, backend="xla"):
    """Gradient of a fixed cosine-projection loss through ``res.run``."""
    params = {k: jnp.asarray(env[k]) for k in diff_keys}

    def loss(p):
        outs = res.run({**env, **p}, backend)
        return sum(jnp.sum(jnp.asarray(v)
                           * jnp.cos(jnp.arange(v.size,
                                                dtype=v.dtype)).reshape(
                               v.shape))
                   for v in outs.values())

    return jax.grad(loss)(params)


# ---------------------------------------------------------------------------
# gradient correctness across the registry slice named by the acceptance
# criteria — both backends, reassociate in {0, 3, 4}
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,n", [
    ("psinv", 8), ("resid", 8), ("diffusion3", 8), ("smooth1d", 20),
    ("mirror_deriv", 12),
])
def test_grad_matches_baseline(name, n):
    report = run_grad_case(get_case(name, n), reassociate_levels=(0, 3, 4))
    assert not report.failures(), [
        (c.reassociate, c.backend, c.status, c.reason)
        for c in report.failures()]
    tol = default_tolerances(np.float32)["grad"]
    oks = [c for c in report.combos if c.ok]
    assert oks and all(c.max_rel_err <= tol for c in oks)
    # every case in this slice has a detectable adjoint stencil
    assert adjoint_build(get_case(name, n).program).ok


@pytest.mark.parametrize("name,n,code", [
    ("rprj3", 10, STRIDED_READ), ("diag2d", 12, REPEATED_LEVEL),
])
def test_grad_fallback_cases_still_differentiate(name, n, code):
    """The adjoint detector refuses these shapes — with a structured reason
    — and the VJP falls back to autodiff of the baseline.  Gradients must
    still match; the refusal must be visible on the combo."""
    case = get_case(name, n)
    build = adjoint_build(case.program)
    assert not build.ok
    assert code in build.reason
    report = run_grad_case(case, reassociate_levels=(0, 3))
    assert not report.failures()
    assert all(code in c.reason for c in report.combos if c.ok)


# ---------------------------------------------------------------------------
# the adjoint plan is a first-class executor citizen
# ---------------------------------------------------------------------------


def test_adjoint_plans_cache_separately_and_hit_on_second_step():
    case = get_case("psinv", 8)
    env = build_env(case)
    res = race(case.program, reassociate=3)
    diff_keys = sorted(k for k, v in env.items()
                       if np.issubdtype(np.asarray(v).dtype, np.floating))

    cache = executor_cache()
    before = cache.cache_info()
    g1 = _loss_grads(res, env, diff_keys)
    mid = cache.cache_info()
    assert mid["misses"] > before["misses"]

    fwd_h = plan_hash(res.plan)
    cached_hashes = {k.plan for k in cache.keys()}
    assert fwd_h in cached_hashes  # the forward plan is cached...
    build = adjoint_build(case.program)
    assert build.ok
    adj_hashes = {plan_hash(s.result().plan) for s in build.specs}
    assert adj_hashes and fwd_h not in adj_hashes
    assert adj_hashes <= cached_hashes  # ...and so is every adjoint spec

    # the adjoint stencils went through RACE elimination, not just transposal
    u_spec = build.spec_for("R")  # psinv's residual input
    assert u_spec is not None
    assert u_spec.result().reduced_ops() > 0
    assert u_spec.gu.startswith(ADJOINT_PREFIX)

    # second step: pure cache hits, no new executor builds
    g2 = _loss_grads(res, env, diff_keys)
    after = cache.cache_info()
    assert after["misses"] == mid["misses"]
    assert after["hits"] > mid["hits"]
    for k in g1:
        np.testing.assert_allclose(np.asarray(g2[k]), np.asarray(g1[k]),
                                   rtol=0, atol=0, err_msg=k)


def test_grad_works_under_jit_and_on_weak_scalars():
    case = get_case("psinv", 8)
    env = build_env(case)
    res = race(case.program, reassociate=3)
    def loss(a, w0):
        outs = res.run({**env, "R": a, "w0": w0}, "xla")
        return sum(jnp.sum(v) for v in outs.values())

    ge = jax.grad(loss, argnums=(0, 1))(jnp.asarray(env["R"]), 0.5)
    gj = jax.jit(jax.grad(loss, argnums=(0, 1)))(jnp.asarray(env["R"]), 0.5)
    for a, b in zip(ge, gj):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-6, atol=1e-7)
    assert np.asarray(ge[1]).dtype == np.float32  # weak python scalar: fine


def test_run_batch_vmap_grad():
    case = get_case("psinv", 8)
    env = build_env(case)
    res = race(case.program, reassociate=3)
    stacked = {k: jnp.stack([jnp.asarray(v)] * 3) for k, v in env.items()}

    def loss(r):
        return jnp.sum(jnp.asarray(
            res.run_batch({**stacked, "R": r}, "xla")["U"]))

    g = jax.grad(loss)(stacked["R"])
    # per-example gradient equals the unbatched gradient
    gs = jax.grad(lambda r: jnp.sum(jnp.asarray(
        res.run({**env, "R": r}, "xla")["U"])))(jnp.asarray(env["R"]))
    for b in range(3):
        np.testing.assert_allclose(np.asarray(g[b]), np.asarray(gs),
                                   rtol=1e-6, atol=1e-7)


def test_race_kernel_function_differentiates():
    from repro.frontend import race_kernel

    @race_kernel(reassociate=3)
    def blur(u, out):
        n, m = u.shape
        for i in range(1, n - 1):
            for j in range(1, m - 1):
                out[i, j] = (u[i - 1, j] + u[i + 1, j]
                             + u[i, j - 1] + u[i, j + 1]) / 4.0

    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.random((12, 12), dtype=np.float32))
    env = {"u": u, "out": jnp.zeros((12, 12), jnp.float32)}

    g = jax.grad(lambda u_: jnp.sum(jnp.asarray(
        blur.run({**env, "u": u_}, backend="xla")["out"]) ** 2))(u)

    def naive(u_):
        out = (u_[:-2, 1:-1] + u_[2:, 1:-1] + u_[1:-1, :-2]
               + u_[1:-1, 2:]) / 4.0
        return jnp.sum(out ** 2)

    gn = jax.grad(naive)(u)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gn),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# backward() plumbing and the lowering-probe gate
# ---------------------------------------------------------------------------


def test_backward_fills_zero_cotangents_for_unread_keys():
    case = get_case("psinv", 8)
    env = build_env(case)
    res = race(case.program)
    truth = interior(res.plan, res.baseline_evaluator()(env))
    g = {k: jnp.ones_like(jnp.asarray(v)) for k, v in truth.items()}
    grads = backward(case.program, env, g)
    assert set(grads) == set(env)  # one cotangent per env leaf, always
    for k, v in grads.items():
        assert np.shape(v) == np.shape(env[k]), k


def test_scalar_aux_gate_rejects_rank0_auxiliaries_from_pallas():
    """mirror_deriv's u-adjoint plan materializes a loop-invariant (rank-0)
    auxiliary; the emitter's scalar path can't address it.  The capability
    probe must route such plans to XLA with the R_SCALAR_AUX reason rather
    than letting the emitter crash."""
    from repro.core.backend import select_backend
    from repro.lowering import R_SCALAR_AUX, analyze_plan

    case = get_case("mirror_deriv", 12)
    build = adjoint_build(case.program)
    assert build.ok
    spec = build.spec_for("u")
    plan = spec.result().plan
    assert any(not a.levels for a in plan.aux_order)  # the rank-0 aux
    analysis = analyze_plan(plan)
    assert any(r.code == R_SCALAR_AUX for r in analysis.reasons)
    assert select_backend(plan, "auto").backend == "xla"
    # and the gradient built on that plan is still right (test above runs
    # the full case; here we just pin the probe's verdict)


def test_adjoint_env_knobs(monkeypatch):
    assert adjoint_mode() == "stencil"
    monkeypatch.setenv("RACE_ADJOINT", "autodiff")
    assert adjoint_mode() == "autodiff"
    monkeypatch.setenv("RACE_ADJOINT", "nonsense")
    with pytest.raises(ValueError, match="RACE_ADJOINT"):
        adjoint_mode()
    monkeypatch.delenv("RACE_ADJOINT")
    monkeypatch.setenv("RACE_ADJOINT_REASSOCIATE", "4")
    assert adjoint_reassociate() == 4

    # autodiff mode computes the same gradients as the stencil adjoint
    case = get_case("smooth1d", 16)
    env = build_env(case)
    res = race(case.program, reassociate=3)
    ws = jnp.asarray(env["ws"])

    def loss(w):
        return jnp.sum(jnp.asarray(res.run({**env, "ws": w}, "xla")["sm1"]))

    monkeypatch.setenv("RACE_ADJOINT", "autodiff")
    g_auto = jax.grad(loss)(ws)
    monkeypatch.delenv("RACE_ADJOINT")
    g_sten = jax.grad(loss)(ws)
    np.testing.assert_allclose(np.asarray(g_auto), np.asarray(g_sten),
                               rtol=1e-5, atol=1e-7)
