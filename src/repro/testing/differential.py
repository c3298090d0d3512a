"""Full-registry differential verification: baseline vs RACE-XLA vs RACE-Pallas.

The paper's correctness claim is that RACE-generated code with auxiliary
arrays computes the same values as the original loop nest.  This harness
systematically checks that claim across every case in
``repro.apps.paper_kernels``:

  * the **baseline evaluator** (untransformed program) is ground truth;
  * each requested ``reassociate`` level produces a plan, executed on the
    **XLA** whole-array evaluator and — when the capability probe passes —
    on the **Pallas** blocked kernel;
  * outputs are compared with per-dtype tolerances; Pallas outputs are
    additionally compared against the XLA realization of the *same* plan
    (same association order, so the tolerance is much tighter);
  * ineligible (case, backend) combos are recorded as explicit fallbacks
    carrying the probe's structured reasons — a fallback without a reason is
    a harness failure, so no case can silently drop off the Pallas path.

Typical use::

    from repro.testing import sweep_registry, coverage_matrix
    reports = sweep_registry()
    print(coverage_matrix(reports))
    assert not [f for r in reports for f in r.failures()]
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Iterable, Optional

import numpy as np

from repro.apps.paper_kernels import CASES, get_case
from repro.core.backend import select_backend
from repro.core.codegen import required_shapes
from repro.core.race import race
from repro.kernels.ref import interior

#: grid sizes keeping a full CPU interpret-mode sweep under a minute
SWEEP_SIZES = {
    "calc_tpoints": 14, "hdifft_gm": 14, "ocn_export": 14, "gaussian": 18,
    "rhs_ph1": 10, "rhs_ph2": 10, "diffusion1": 10, "diffusion2": 10,
    "diffusion3": 10, "psinv": 10, "resid": 10, "rprj3": 12,
    "j3d27pt": 10, "poisson": 10, "derivative": 10,
    # envelope cases (repro.lowering mechanisms: 1-D/4-D, mirrored, gather)
    "smooth1d": 24, "blocked4d": 7, "mirror_deriv": 14, "diag2d": 14,
}


def default_tolerances(dtype) -> dict:
    """(rtol vs baseline, rtol Pallas-vs-XLA-plan, rtol of gradients vs the
    autodiff'd baseline) per dtype.

    Reassociation changes summation order, so the baseline comparison needs
    headroom; the two realizations of the *same* plan share an association
    order and are held much tighter.  Gradients accumulate one extra
    reduction (the adjoint contraction), so they get another factor of
    headroom over the forward tolerance."""
    dt = np.dtype(dtype)
    return {
        np.dtype(np.float64): dict(baseline=1e-9, plan=1e-12, grad=1e-8),
        np.dtype(np.float32): dict(baseline=1e-4, plan=1e-5, grad=2e-4),
        np.dtype(np.float16): dict(baseline=2e-2, plan=1e-2, grad=4e-2),
    }[dt]


def build_env(case, dtype=np.float32, seed: int = 0) -> dict:
    """Random inputs covering every access of the case's program.  Scalars
    draw from [0.25, 1] so divisions and quotient rewrites stay well
    conditioned; arrays draw from [-1, 1]."""
    rng = np.random.default_rng(seed)
    env = {}
    for nm, shp in required_shapes(case.program).items():
        if nm in case.scalars or shp == ():
            env[nm] = dtype(rng.uniform(0.25, 1.0))
        else:
            env[nm] = rng.uniform(-1, 1, shp).astype(dtype)
    return env


@dataclass
class ComboResult:
    """One (case, reassociate, backend) execution."""

    case: str
    reassociate: int
    backend: str  # "xla" | "pallas"
    status: str  # "ok" | "fallback" | "mismatch" | "error"
    reason: str = ""  # fallback reasons or error text
    max_rel_err: Optional[float] = None  # vs baseline evaluator
    max_rel_err_plan: Optional[float] = None  # pallas vs same-plan XLA
    n_aux: int = 0

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    @property
    def explicit_fallback(self) -> bool:
        return self.status == "fallback" and bool(self.reason)


@dataclass
class CaseReport:
    case: str
    combos: list = field(default_factory=list)

    def failures(self) -> list:
        """Mismatches, errors, and *silent* fallbacks (no reason attached)."""
        return [c for c in self.combos
                if c.status in ("mismatch", "error")
                or (c.status == "fallback" and not c.reason)]

    def pallas_covered(self) -> bool:
        return any(c.backend == "pallas" and c.ok for c in self.combos)


def rel_err(got: dict, want: dict) -> float:
    """Worst relative error across outputs — the harness's single metric,
    shared by the autotuner's correctness gate (``repro.tuning.measure``)."""
    worst = 0.0
    for k in want:
        g = np.asarray(got[k], np.float64)
        w = np.asarray(want[k], np.float64)
        denom = max(float(np.abs(w).max()), 1e-30)
        worst = max(worst, float(np.abs(g - w).max()) / denom)
    return worst


_rel_err = rel_err


def run_case(case, reassociate_levels: Iterable[int] = (0, 3, 4),
             backends: Iterable[str] = ("xla", "pallas"),
             dtype=np.float32, seed: int = 0, block_rows: int = 8,
             block_cols: int = 8, block_inner: int = 0,
             tolerances: Optional[dict] = None) -> CaseReport:
    """Differential-verify one case across plans and backends."""
    tol = tolerances or default_tolerances(dtype)
    with _x64_ctx(dtype):
        return _run_case_impl(case, reassociate_levels, backends, dtype, seed,
                              block_rows, block_cols, block_inner, tol)


def _x64_ctx(dtype):
    """Scoped x64 so f64 sweeps don't silently downcast to f32."""
    import contextlib

    import jax

    if np.dtype(dtype) != np.float64:
        return contextlib.nullcontext()
    return jax.enable_x64(True)


def _run_case_impl(case, reassociate_levels, backends, dtype, seed,
                   block_rows, block_cols, block_inner, tol) -> CaseReport:
    env = build_env(case, dtype=dtype, seed=seed)
    report = CaseReport(case.name)

    base_res = race(case.program)  # plan only used for its program/interior
    truth = interior(base_res.plan, base_res.baseline_evaluator()(env))

    for lvl in reassociate_levels:
        res = race(case.program, reassociate=lvl,
                   rewrite_div=case.rewrite_div)
        xla_out = None
        for backend in backends:
            combo = ComboResult(case.name, lvl, backend, "ok",
                                n_aux=res.n_aux_materialized())
            try:
                if backend == "xla":
                    # through the compiled-executor cache: repeated sweeps of
                    # structurally identical plans reuse the jitted evaluator
                    out = res.run(env, "xla")
                    xla_out = out
                else:
                    sel = select_backend(res.plan, "auto")
                    if sel.backend != "pallas":
                        combo.status = "fallback"
                        combo.reason = sel.capability.explain()
                        report.combos.append(combo)
                        continue
                    out = res.run(env, "pallas", block_rows=block_rows,
                                  block_cols=block_cols,
                                  block_inner=block_inner)
                combo.max_rel_err = _rel_err(out, truth)
                if combo.max_rel_err > tol["baseline"]:
                    combo.status = "mismatch"
                    combo.reason = (f"vs baseline: {combo.max_rel_err:.2e} > "
                                    f"{tol['baseline']:.0e}")
                if backend == "pallas" and xla_out is not None:
                    combo.max_rel_err_plan = _rel_err(out, xla_out)
                    if combo.max_rel_err_plan > tol["plan"]:
                        combo.status = "mismatch"
                        combo.reason = (combo.reason + " " if combo.reason
                                        else "") + (
                            f"vs XLA plan: {combo.max_rel_err_plan:.2e} > "
                            f"{tol['plan']:.0e}")
            except Exception as e:  # noqa: BLE001 - reported, not swallowed
                combo.status = "error"
                combo.reason = f"{type(e).__name__}: {e}"
            report.combos.append(combo)
    return report


# ---------------------------------------------------------------------------
# gradient sweep — jax.grad through the RACE executor vs through the baseline
# ---------------------------------------------------------------------------


def run_grad_case(case, reassociate_levels: Iterable[int] = (0, 3, 4),
                  backends: Iterable[str] = ("xla", "pallas"),
                  dtype=np.float32, seed: int = 0,
                  tolerances: Optional[dict] = None) -> CaseReport:
    """Differential-verify ``jax.grad`` through the RACE serving path.

    For each (reassociate level, forward backend) combo, takes the gradient
    of a fixed cosine-projection loss over the interior outputs — once
    through ``res.run`` (which carries the adjoint-stencil ``custom_vjp``)
    and once through plain autodiff of the untransformed baseline evaluator
    — and compares the gradients w.r.t. every inexact input at the per-dtype
    ``grad`` tolerance.  Pallas combos are gated by the capability probe
    exactly like :func:`run_case`; cases whose adjoint stencil cannot be
    built (the detector refuses: strided reads, repeated levels, ...) still
    run — the VJP falls back to autodiff — and the combo carries the
    refusal reason for visibility.
    """
    tol = tolerances or default_tolerances(dtype)
    with _x64_ctx(dtype):
        return _run_grad_case_impl(case, reassociate_levels, backends, dtype,
                                   seed, tol)


def _run_grad_case_impl(case, reassociate_levels, backends, dtype, seed,
                        tol) -> CaseReport:
    import jax
    import jax.numpy as jnp

    from repro.core.adjoint import adjoint_build

    env = build_env(case, dtype=dtype, seed=seed)
    report = CaseReport(case.name)

    base_res = race(case.program)
    base_eval = base_res.baseline_evaluator()
    truth_out = interior(base_res.plan, base_eval(env))
    # fixed, deterministic projection: every output element contributes with
    # a distinct weight, so a gradient error anywhere shows up in the loss
    weights = {k: jnp.asarray(
        np.cos(np.arange(v.size)).reshape(np.shape(v)).astype(dtype))
        for k, v in truth_out.items()}
    diff_keys = sorted(k for k, v in env.items()
                       if np.issubdtype(np.asarray(v).dtype, np.floating))
    params0 = {k: env[k] for k in diff_keys}

    def loss_of(outs):
        return sum(jnp.sum(jnp.asarray(outs[k]) * w)
                   for k, w in weights.items())

    truth_grads = jax.grad(lambda p: loss_of(interior(
        base_res.plan, base_eval({**env, **p}))))(params0)

    build = adjoint_build(case.program)
    adjoint_note = "" if build.ok else f"adjoint-autodiff: {build.reason}"

    for lvl in reassociate_levels:
        res = race(case.program, reassociate=lvl,
                   rewrite_div=case.rewrite_div)
        for backend in backends:
            combo = ComboResult(case.name, lvl, backend, "ok",
                                reason=adjoint_note,
                                n_aux=res.n_aux_materialized())
            try:
                if backend == "pallas":
                    sel = select_backend(res.plan, "auto")
                    if sel.backend != "pallas":
                        combo.status = "fallback"
                        combo.reason = sel.capability.explain()
                        report.combos.append(combo)
                        continue
                grads = jax.grad(lambda p: loss_of(res.run(
                    {**env, **p}, backend)))(params0)
                combo.max_rel_err = _rel_err(grads, truth_grads)
                if combo.max_rel_err > tol["grad"]:
                    combo.status = "mismatch"
                    combo.reason = (f"grads vs baseline: "
                                    f"{combo.max_rel_err:.2e} > "
                                    f"{tol['grad']:.0e}")
            except Exception as e:  # noqa: BLE001 - reported, not swallowed
                combo.status = "error"
                combo.reason = f"{type(e).__name__}: {e}"
            report.combos.append(combo)
    return report


def grad_sweep_registry(names: Optional[Iterable[str]] = None,
                        sizes: Optional[dict] = None, **kw) -> list:
    """Run :func:`run_grad_case` over (a subset of) the kernel registry."""
    sizes = {**SWEEP_SIZES, **(sizes or {})}
    if names is None:
        names = list(CASES)
    return [run_grad_case(get_case(n, sizes.get(n)), **kw) for n in names]


def sweep_registry(names: Optional[Iterable[str]] = None,
                   sizes: Optional[dict] = None, via: str = "dsl",
                   **kw) -> list:
    """Run :func:`run_case` over (a subset of) the paper-kernel registry.

    ``via="frontend"`` swaps every case's program for the one captured from
    its plain-Python twin (``repro.apps.frontend_kernels``) — capture
    equality is checked en route, so the sweep then differentially verifies
    the frontend entry path end to end.  With ``names=None`` the frontend
    sweep covers the twinned subset rather than erroring on cases without a
    twin yet.
    """
    sizes = {**SWEEP_SIZES, **(sizes or {})}
    if names is None:
        names = list(CASES)
        if via == "frontend":
            from repro.apps.frontend_kernels import TWINS

            names = [n for n in names if n in TWINS]
    reports = []
    for name in names:
        case = get_case(name, sizes.get(name), via=via)
        reports.append(run_case(case, **kw))
    return reports


def coverage_matrix(reports: Iterable[CaseReport]) -> str:
    """Human-readable case x (reassociate, backend) status matrix, with the
    fallback/mismatch reasons listed below the table."""
    reports = list(reports)
    combos = sorted({(c.reassociate, c.backend)
                     for r in reports for c in r.combos})
    head = ["case".ljust(14)] + [f"r{l}/{b}".ljust(12) for l, b in combos]
    lines = ["  ".join(head)]
    notes = []
    for r in reports:
        by_key = {(c.reassociate, c.backend): c for c in r.combos}
        row = [r.case.ljust(14)]
        for key in combos:
            c = by_key.get(key)
            if c is None:
                cell = "-"
            elif c.ok:
                cell = f"ok {c.max_rel_err:.0e}"
            elif c.status == "fallback":
                code = c.reason.split(":", 1)[0] if c.reason else "SILENT"
                cell = f"xla[{code}]"
                notes.append(f"{r.case} r{key[0]}: fallback — {c.reason}")
            else:
                cell = c.status.upper()
                notes.append(f"{r.case} r{key[0]}/{key[1]}: {c.status} — "
                             f"{c.reason}")
            row.append(cell.ljust(12))
        lines.append("  ".join(row))
    if notes:
        lines.append("")
        lines.extend(notes)
    return "\n".join(lines)
