"""Dimension-generic Pallas lowering engine for RACE plans.

The paper's claim is that hash-based redundancy detection is
*pattern-agnostic*; this package makes the fast execution path equally so.
It replaces the former 2-D/3-D special-case kernel
(``repro.kernels.race_stencil``, now a compatibility shim) with per-concern
modules generic over nest depth and window shape:

  * :mod:`repro.lowering.facts`    — structured fallback reasons / lowering
    facts shared with the capability probe (pure data);
  * :mod:`repro.lowering.geometry` — plan analysis: eligibility, aux tile
    extensions, offset envelopes, mirrored-origin normalization for negative
    coefficients (pure; imports no jax — the probe delegates here);
  * :mod:`repro.lowering.blocks`   — N-D BlockSpec/grid construction for any
    nest depth (1-D scans through ≥4-D tensors);
  * :mod:`repro.lowering.gather`   — in-kernel index gather for
    repeated-level and constant-dim references;
  * :mod:`repro.lowering.emit`     — the traceable kernel body plus
    :class:`LoweredStencil`, the one-time specialization artifact the
    executor caches, and :func:`pallas_interpret`, the one place the Pallas
    mode is chosen: compiled on a TPU, interpreted on the CPU backend.

Importing ``repro.lowering`` itself stays jax-free: the emit-side symbols
(``specialize_stencil``, ``LoweredStencil``, ``race_stencil_call``, ...)
load lazily on first access, so ``repro.core.backend`` can probe plans
without touching Pallas.
"""
from __future__ import annotations

from .facts import (FALLBACK_CODES, RETIRED_CODES, R_CONSTANT_DIM, R_DEPTH,
                    R_FRACTIONAL_OFFSET, R_INCONSISTENT_LAYOUT, R_LHS_FORM,
                    R_MIXED_STRIDE, R_NEGATIVE_COEF, R_NO_BASE_ARRAY,
                    R_PLATFORM, R_REPEATED_LEVEL, R_SCALAR_AUX,
                    R_STRIDED_AUX, R_TPU_GATHER, R_TPU_STRIDED, R_ZERO_COEF,
                    FallbackReason, LoweringError, LoweringFact)
from .geometry import (K_GATHER, K_WINDOW, ArrayInfo, LoweringAnalysis,
                       analyze_plan, analyze_program, offset_envelopes,
                       plan_geometry, platform_reasons, program_envelopes,
                       target_platform)

#: emit-side symbols resolved lazily (they import jax + Pallas)
_EMIT = ("LoweredStencil", "StencilSpec", "specialize_stencil",
         "pallas_interpret", "race_stencil_call", "build_kernel")
_BLOCKS = ("ArrayPrep", "Layout", "build_layout", "level_blocks")
_GATHER = ("gather_ref",)

__all__ = [
    "FALLBACK_CODES", "RETIRED_CODES", "R_CONSTANT_DIM", "R_DEPTH",
    "R_FRACTIONAL_OFFSET", "R_INCONSISTENT_LAYOUT", "R_LHS_FORM",
    "R_MIXED_STRIDE", "R_NEGATIVE_COEF", "R_NO_BASE_ARRAY",
    "R_PLATFORM", "R_REPEATED_LEVEL", "R_SCALAR_AUX", "R_STRIDED_AUX",
    "R_TPU_GATHER", "R_TPU_STRIDED", "R_ZERO_COEF",
    "FallbackReason", "LoweringError", "LoweringFact",
    "K_GATHER", "K_WINDOW", "ArrayInfo", "LoweringAnalysis",
    "analyze_plan", "analyze_program", "offset_envelopes",
    "plan_geometry", "platform_reasons", "program_envelopes",
    "target_platform",
    *_EMIT, *_BLOCKS, *_GATHER,
]


def __getattr__(name: str):
    if name in _EMIT:
        from . import emit

        return getattr(emit, name)
    if name in _BLOCKS:
        from . import blocks

        return getattr(blocks, name)
    if name in _GATHER:
        from . import gather

        return getattr(gather, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
