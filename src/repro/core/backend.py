"""Execution-backend selection for RACE plans.

Two realizations exist for an executable :class:`~repro.core.depgraph.Plan`:

  * ``"xla"``    — the whole-array JAX evaluator (``codegen``); handles every
                   program in the paper's scope;
  * ``"pallas"`` — the blocked kernel built by the dimension-generic lowering
                   engine (``repro.lowering``); faster on streaming stencils.

Since the lowering engine became generic over nest depth and window shape,
the two paths cover the *same* structural envelope for well-formed programs:
1-D and ≥4-D nests (N-D grid construction), negative coefficients
(mirrored-origin windows), repeated levels and constant dims (in-kernel
gather) all lower — the probe reports them as lowering *facts*, not
fallbacks.  What remains on XLA are genuinely out-of-model programs only:
malformed writes, zero-coefficient or fractional subscripts, per-array
layout/stride inconsistencies, non-unit auxiliary references, and
scalar-only data — plus, on a TPU, the two mechanisms its compiler refuses
(in-kernel gather and strided windows).

This module no longer *knows* the restrictions — it delegates to
:func:`repro.lowering.geometry.analyze_plan`, the same analysis the engine
itself specializes against, so the probe can never disagree with what
actually lowers.  The probe never raises on an ineligible plan — it returns
a :class:`Capability` whose ``reasons`` say *why* the plan must stay on XLA,
so callers (the ``auto`` backend, the differential harness, the coverage
matrix) can report fallbacks instead of silently degrading.

The probe is pure plan analysis: the analysis modules import neither
``jax.experimental.pallas`` nor the kernel emitter (``repro.lowering``
loads those lazily), so asking "would this lower?" is free.
"""
from __future__ import annotations

from dataclasses import dataclass
from repro import obs as _obs
from repro.lowering.facts import (  # noqa: F401  (stable re-exports)
    FALLBACK_CODES, RETIRED_CODES, R_CONSTANT_DIM, R_DEPTH,
    R_FRACTIONAL_OFFSET, R_INCONSISTENT_LAYOUT, R_LHS_FORM, R_MIXED_STRIDE,
    R_NEGATIVE_COEF, R_NO_BASE_ARRAY, R_PLATFORM, R_REPEATED_LEVEL,
    R_STRIDED_AUX, R_TPU_GATHER, R_TPU_STRIDED, R_ZERO_COEF, FallbackReason,
    LoweringFact)
from repro.lowering.geometry import (analyze_plan, platform_reasons,
                                     target_platform)

from .depgraph import Plan

BACKENDS = ("xla", "pallas", "auto")


@dataclass(frozen=True)
class Capability:
    """Result of probing a plan for Pallas eligibility.

    ``reasons`` are the structural obstacles (empty when eligible);
    ``facts`` are the envelope-widening mechanisms the lowering engages
    (mirrored-origin windows, in-kernel gather, N-D grid) — informational,
    never blocking."""

    eligible: bool
    reasons: tuple = ()
    facts: tuple = ()

    def explain(self) -> str:
        if self.eligible:
            if self.facts:
                return "pallas-eligible (" + "; ".join(
                    str(f) for f in self.facts) + ")"
            return "pallas-eligible"
        return "; ".join(str(r) for r in self.reasons)


@dataclass(frozen=True)
class Selection:
    """A resolved backend choice plus the probe that justified it."""

    backend: str  # "xla" | "pallas"
    requested: str
    capability: Capability

    @property
    def fell_back(self) -> bool:
        return self.requested in ("pallas", "auto") and self.backend == "xla"


class BackendUnavailable(RuntimeError):
    """Raised when ``backend="pallas"`` is demanded for an ineligible plan."""

    def __init__(self, capability: Capability):
        self.capability = capability
        super().__init__(
            f"plan cannot take the Pallas path: {capability.explain()}"
        )


def probe_pallas(plan: Plan) -> Capability:
    """Probe a plan against the lowering engine's own analysis, for the
    platform its kernels run on (jax's default backend).

    The verdict is *re-derived from the engine* — this is literally the
    analysis ``repro.lowering.specialize_stencil`` builds kernels from
    (memoized per plan instance), so reported reasons always agree with
    what lowers: an ineligible probe means ``specialize_stencil`` raises a
    ``LoweringError`` carrying these same structured reasons; an eligible
    one means it succeeds for any block configuration whose input blocks
    hold the plan's halo spread — that per-(array, level) capacity check is
    the one *shape-dependent* failure left at specialize time, and its
    error names the block knob to raise.

    On a TPU the kernel is compiled, and the probe also refuses what the
    TPU compiler refuses (``tpu-gather``, ``tpu-strided``), so ``auto``
    never picks a kernel that cannot compile there.
    """
    a = analyze_plan(plan)
    reasons = a.reasons or platform_reasons(a, target_platform())
    return Capability(eligible=not reasons, reasons=reasons, facts=a.facts)


def select_backend(plan: Plan, requested: str = "auto") -> Selection:
    """Resolve ``requested`` against the plan's capability.

    ``"auto"`` prefers Pallas when eligible, else falls back to XLA (the
    fallback reasons travel in the returned Selection).  ``"pallas"`` raises
    :class:`BackendUnavailable` on an ineligible plan.
    """
    if requested not in BACKENDS:
        raise ValueError(f"unknown backend {requested!r}; choose from {BACKENDS}")
    cap = probe_pallas(plan)
    if requested == "xla":
        return Selection("xla", requested, cap)
    if requested == "pallas":
        if not cap.eligible:
            _emit_selection(plan, requested, "unavailable", cap)
            raise BackendUnavailable(cap)
        return _emit_selection(plan, requested, "pallas", cap)
    return _emit_selection(
        plan, requested, "pallas" if cap.eligible else "xla", cap)


def _emit_selection(plan: Plan, requested: str, backend: str,
                    cap: Capability):
    """Record the probe's verdict: a counter per (requested, resolved) pair,
    a ``backend_fallback`` event carrying the structured reasons whenever a
    Pallas-wanting request lands on XLA (or is refused outright), and a
    ``lowering_facts`` event when an eligible plan engages envelope-widening
    mechanisms — the decisions the capability matrix is built from."""
    if _obs.enabled():
        from .executor import plan_hash

        ph = plan_hash(plan)
        _obs.counter("race_backend_selections_total", requested=requested,
                     backend=backend).inc()
        if backend in ("xla", "unavailable") and cap.reasons:
            _obs.event("backend_fallback", plan=ph, requested=requested,
                       backend=backend,
                       reasons=[str(r) for r in cap.reasons],
                       codes=[r.code for r in cap.reasons])
        elif cap.facts:
            _obs.event("lowering_facts", plan=ph, backend=backend,
                       facts=[str(f) for f in cap.facts],
                       codes=[f.code for f in cap.facts])
    if backend == "unavailable":
        return None
    return Selection(backend, requested, cap)
