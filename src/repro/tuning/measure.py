"""Correctness-gated measurement of one tuning candidate.

Every candidate runs through the *same* plan-keyed compiled-executor path
that serves production traffic (``repro.core.executor.compile_plan`` with an
explicit backend — never ``"auto"``, which would consult the store the tuner
is about to write).  A candidate must first reproduce the ``reassociate=0``
XLA baseline within the differential-harness tolerance for its dtype; only
then is it timed (warmup + repeats, median wall time).  Gated or erroring
candidates are recorded with their reason, never silently dropped.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Mapping, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.depgraph import Plan
from repro.core.executor import compile_plan
# the gate uses the differential harness's own error metric, not a copy
from repro.testing.differential import rel_err

from .space import Config


@dataclass
class Measurement:
    """One candidate's fate: timed, correctness-gated, or errored.

    ``batch == 0`` is the per-call path; ``batch > 0`` means the candidate
    was measured on the *batched* (vmapped) executor at that batch size, with
    ``us`` normalized to per-item so populations stay comparable.
    """

    config: Config
    status: str  # "ok" | "gated" | "error"
    us: Optional[float] = None  # median steady-state wall time, µs (per item)
    rel_err: Optional[float] = None  # vs the reassociate=0 XLA baseline
    detail: str = ""
    batch: int = 0

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    def as_dict(self) -> dict:
        return dict(config=self.config.as_dict(), status=self.status,
                    us=self.us, rel_err=self.rel_err, detail=self.detail,
                    batch=self.batch)


def time_executor(ex, env: Mapping, repeats: int = 5,
                  warmup: int = 2) -> float:
    """Median wall time of an already-built executor, microseconds."""
    out = None
    for _ in range(max(warmup, 1)):
        out = ex(env)
    jax.block_until_ready(out)
    ts = []
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(ex(env))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def time_executor_batch(ex, env: Mapping, batch: int, repeats: int = 5,
                        warmup: int = 2) -> float:
    """Median *per-item* wall time of the batched executor, microseconds.

    Stacks ``env`` to batch ``batch`` once up front (the serving runtime
    dispatches pre-coalesced batches, so stacking cost is not what this
    measures) and times ``run_batch`` on the stacked dict.
    """
    stacked = {k: jnp.stack([jnp.asarray(v)] * batch)
               for k, v in env.items()}
    out = None
    for _ in range(max(warmup, 1)):
        out = ex.run_batch(stacked)
    jax.block_until_ready(out)
    ts = []
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(ex.run_batch(stacked))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6 / batch


def measure_candidate(plan: Plan, config: Config, env: Mapping,
                      truth: Mapping, tolerance: float, *,
                      repeats: int = 5, warmup: int = 2,
                      batch: int = 0) -> Measurement:
    """Gate then time one candidate; exceptions become ``status="error"``.

    Infeasible configs (e.g. a halo larger than the requested input block)
    raise inside specialization and are reported here as errors — the tuner
    treats them as non-candidates rather than crashing the search.

    ``batch > 0`` measures the *batched* (vmapped) executor instead: the env
    is replicated to that batch size, element 0 of the stacked output is
    gated against ``truth``, and ``us`` is per-item — what the serving
    runtime's coalesced dispatch actually pays.
    """
    from repro import obs

    try:
        with obs.span("measure", config=config.describe(),
                      batch=str(batch)):
            ex = compile_plan(
                plan, env, config.backend, block_rows=config.block_rows,
                block_cols=config.block_cols,
                block_inner=config.block_inner)
            if batch > 0:
                out = ex.run_batch([env] * batch)
                first = {k: v[0] for k, v in out.items()}
                err = rel_err(first, truth)
            else:
                out = ex(env)
                err = rel_err(out, truth)
            if err > tolerance:
                m = Measurement(
                    config, "gated", rel_err=err, batch=batch,
                    detail=f"vs r0/xla baseline: {err:.2e} > "
                           f"{tolerance:.0e}")
            elif batch > 0:
                us = time_executor_batch(ex, env, batch, repeats=repeats,
                                         warmup=warmup)
                m = Measurement(config, "ok", us=us, rel_err=err,
                                batch=batch)
            else:
                us = time_executor(ex, env, repeats=repeats, warmup=warmup)
                m = Measurement(config, "ok", us=us, rel_err=err)
    except Exception as e:  # noqa: BLE001 - reported, not swallowed
        m = Measurement(config, "error", batch=batch,
                        detail=f"{type(e).__name__}: {e}")
    if obs.enabled():
        # one event per candidate verdict: gate passes are as much a
        # decision as gate failures (the tuner's audit trail)
        from repro.core.executor import plan_hash

        obs.counter("race_tuning_candidates_total", status=m.status).inc()
        obs.event("tuning_gate", plan=plan_hash(plan),
                  config=config.describe(), status=m.status,
                  rel_err=m.rel_err, us=m.us, detail=m.detail,
                  batch=m.batch)
    return m
