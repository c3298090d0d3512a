"""Structured lowering verdicts: fallback reasons, lowering facts, errors.

This module is the shared vocabulary between the capability probe
(``repro.core.backend``) and the lowering engine (``repro.lowering``): both
sides speak in the same ``(code, detail)`` pairs, so what the probe promises
and what the engine does can never drift apart — the probe literally calls
the engine's analysis (:func:`repro.lowering.geometry.analyze_plan`).

Two kinds of verdicts share the shape:

  * **fallback reasons** — structural obstacles that keep a plan on the XLA
    evaluator path.  Since the dimension-generic engine landed these are the
    genuinely out-of-model programs only (malformed writes, zero/fractional
    subscripts, per-array inconsistencies, scalar-only data);
  * **lowering facts** — properties that *used to be* fallbacks but are now
    handled by a dedicated mechanism, reported so callers can see which
    machinery a plan engages: 1-D / ≥4-D nests (N-D grid construction),
    negative coefficients (mirrored-origin windows), repeated levels and
    constant dims (in-kernel index gather).

Everything here is pure data — importing it never touches jax or Pallas.
"""
from __future__ import annotations

from dataclasses import dataclass

# --- machine-readable codes (stable API for tests / the harness) -----------
#
# Still-active fallback codes: plans carrying one of these stay on XLA.
R_LHS_FORM = "lhs-form"
R_ZERO_COEF = "zero-coefficient"
R_FRACTIONAL_OFFSET = "fractional-offset"
R_MIXED_STRIDE = "mixed-stride"
R_INCONSISTENT_LAYOUT = "inconsistent-layout"
R_STRIDED_AUX = "strided-aux"
R_SCALAR_AUX = "scalar-aux"
R_NO_BASE_ARRAY = "no-base-array"
#: Platform codes: the plan lowers, but not for the executing platform.
R_TPU_GATHER = "tpu-gather"  # in-kernel index gather: Mosaic has no lowering
R_TPU_STRIDED = "tpu-strided"  # stride > 1 window slice lowers to a gather
R_PLATFORM = "pallas-platform"  # neither a TPU (compiled) nor CPU (interpret)

#: Retired fallback codes: since the dimension-generic lowering engine these
#: never appear as fallback *reasons* — they appear as lowering *facts*
#: naming the mechanism that absorbs them (kept under the same names so the
#: fallback→fact promotion is visible in diffs and dashboards).
R_DEPTH = "depth"  # 1-D / ≥4-D nests → N-D grid construction
R_NEGATIVE_COEF = "negative-coefficient"  # → mirrored-origin windows
R_REPEATED_LEVEL = "repeated-level"  # → in-kernel index gather
R_CONSTANT_DIM = "constant-dim"  # → in-kernel index gather

#: The codes that can still appear in ``Capability.reasons``.
FALLBACK_CODES = (R_LHS_FORM, R_ZERO_COEF, R_FRACTIONAL_OFFSET,
                  R_MIXED_STRIDE, R_INCONSISTENT_LAYOUT, R_STRIDED_AUX,
                  R_SCALAR_AUX, R_NO_BASE_ARRAY, R_TPU_GATHER, R_TPU_STRIDED,
                  R_PLATFORM)

#: The codes that appear only as lowering facts now.
RETIRED_CODES = (R_DEPTH, R_NEGATIVE_COEF, R_REPEATED_LEVEL, R_CONSTANT_DIM)


@dataclass(frozen=True)
class FallbackReason:
    """One structural obstacle to the Pallas path."""

    code: str
    detail: str

    def __str__(self) -> str:  # pragma: no cover - repr sugar
        return f"{self.code}: {self.detail}"


@dataclass(frozen=True)
class LoweringFact:
    """One envelope-widening mechanism a plan engages (not an obstacle).

    ``code`` reuses the retired fallback code the mechanism absorbed, so a
    dashboard diffing probe output across versions sees the same identifier
    move from the reasons column to the facts column."""

    code: str
    detail: str

    def __str__(self) -> str:  # pragma: no cover - repr sugar
        return f"{self.code}: {self.detail}"


class LoweringError(ValueError):
    """Raised when the lowering engine is asked to specialize an ineligible
    plan; carries the same structured reasons the capability probe reports,
    so engine and probe can be asserted to agree."""

    def __init__(self, reasons, message: str = ""):
        self.reasons = tuple(reasons)
        super().__init__(
            message or "; ".join(str(r) for r in self.reasons)
            or "plan is outside the Pallas lowering model")

    @property
    def codes(self) -> tuple:
        return tuple(r.code for r in self.reasons)
