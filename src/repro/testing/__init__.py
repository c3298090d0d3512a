"""Differential-verification harness for the RACE execution backends."""
from .differential import (CaseReport, ComboResult, build_env,
                           coverage_matrix, default_tolerances,
                           grad_sweep_registry, reference, reference_plan,
                           run_case, run_grad_case, sweep_registry)

__all__ = [
    "CaseReport", "ComboResult", "build_env", "coverage_matrix",
    "default_tolerances", "grad_sweep_registry", "reference",
    "reference_plan", "run_case", "run_grad_case", "sweep_registry",
]
