"""``autotune`` — measured, correctness-gated, persisted strategy selection.

The front door of :mod:`repro.tuning`: given a :class:`~repro.core.ir.
Program` and a concrete environment, enumerate the candidate space
(:mod:`.space`), measure every candidate through the compiled-executor
serving path (:mod:`.measure`), gate each against the ``reassociate=0`` XLA
baseline, and persist the winner (:mod:`.store`) keyed by (structural hash,
env signature, device kind, jax version) — so the search runs once per
machine and every later process reuses the decision with zero re-measurement.

Selection is conservative by construction: the static default config is
always part of the space, and the winner must beat it by more than
``noise_margin`` or the default is kept — a tuned selection is never slower
than the static default up to measurement noise (pinned by tests and the
``benchmarks/tuning.py`` sweep).
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Iterable, Mapping, Optional, Sequence

import numpy as np

from repro.core.backend import select_backend
from repro.core.executor import (compile_plan, env_signature, plan_hash,
                                 program_hash)
from repro.core.ir import Program
from repro.core.race import race

from .measure import Measurement, measure_candidate
from .space import REASSOCIATE_LEVELS, Config, candidate_configs
from .store import (TuningStore, default_store, program_record, record_key,
                    runtime_fence)


@dataclass
class TuningDecision:
    """The tuner's answer for one (program, env signature, device, jax)."""

    choice: Config  # the winner (what serving should run)
    default: Config  # the static default it was measured against
    default_us: Optional[float]  # measured static-default time
    tuned_us: Optional[float]  # measured winner time
    search_seconds: float  # wall time of *this* call (0.0 on a store hit)
    from_cache: bool  # True: answered from the persistent store
    key: str  # the program-level store key
    measurements: list = field(default_factory=list)  # [] on a store hit

    @property
    def speedup(self) -> Optional[float]:
        if self.default_us and self.tuned_us:
            return self.default_us / self.tuned_us
        return None

    def as_dict(self) -> dict:
        return dict(choice=self.choice.as_dict(),
                    default=self.default.as_dict(),
                    default_us=self.default_us, tuned_us=self.tuned_us,
                    search_seconds=self.search_seconds,
                    from_cache=self.from_cache, key=self.key,
                    measurements=[m.as_dict() for m in self.measurements])


def _baseline_tolerance(env: Mapping) -> float:
    """The differential harness's per-dtype baseline tolerance for env."""
    dts = [np.dtype(getattr(v, "dtype", None) or np.asarray(v).dtype)
           for v in env.values()]
    dt = np.result_type(*dts) if dts else np.dtype(np.float32)
    try:
        from repro.testing.differential import default_tolerances

        return default_tolerances(dt)["baseline"]
    except KeyError:
        return 1e-4


def _find(measurements: Iterable[Measurement],
          config: Config) -> Optional[Measurement]:
    for m in measurements:
        if m.config == config:
            return m
    return None


def _default_backend_for(plan, backends: Optional[Sequence[str]]) -> str:
    """The static default's backend: the capability probe's auto choice,
    clamped to the allowed backend set (a ``backends=("xla",)`` search must
    not measure a Pallas default just because the plan is eligible)."""
    b = select_backend(plan, "auto").backend
    if backends is not None and b not in backends:
        b = "xla" if "xla" in backends else tuple(backends)[0]
    return b


def _prefer_default(winner: Measurement, default_m: Optional[Measurement],
                    default: Config, noise_margin: float) -> Measurement:
    """The conservative tie rule, shared by the program-level pick and the
    per-plan records: a non-default winner must beat the measured default by
    more than ``noise_margin`` or the default is kept."""
    if (default_m is not None and winner.config != default
            and winner.us >= default_m.us * (1.0 - noise_margin)):
        return default_m  # tie / inside noise: keep the static default
    return winner


def _pick(measurements: Sequence[Measurement], default: Config,
          noise_margin: float) -> tuple:
    """(winner Measurement, default Measurement|None) with tie fallback."""
    ok = [m for m in measurements if m.ok]
    if not ok:
        details = "; ".join(
            f"{m.config.describe()}: {m.status} {m.detail}".strip()
            for m in measurements)
        raise RuntimeError(
            f"autotune: no candidate survived the correctness gate "
            f"({details})")
    default_m = _find(ok, default)
    winner = _prefer_default(min(ok, key=lambda m: m.us), default_m,
                             default, noise_margin)
    return winner, default_m


def _opts_token(v):
    """JSON-able view of one search option (non-JSON values — e.g. a cost
    model instance in ``race_opts`` — degrade to their class name)."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, Mapping):
        return {str(k): _opts_token(v[k]) for k in sorted(v, key=str)}
    if isinstance(v, (list, tuple, set, frozenset)):
        items = [_opts_token(x) for x in v]
        return sorted(items, key=repr) if isinstance(
            v, (set, frozenset)) else items
    return type(v).__name__


def search_signature(*, levels, backends, grid, quick, default_reassociate,
                     rewrite_div, race_opts, tolerance,
                     noise_margin, batch_sizes=()) -> str:
    """Canonical token of every option that shapes the candidate space or
    the selection rule.  Part of the program-level store key: a decision
    from a narrower search (say ``backends=("xla",)``) must not answer a
    later full-space ``autotune`` call for the same program + env."""
    opts = dict(
        levels=sorted(set(levels)), backends=backends, grid=grid,
        quick=quick, default_reassociate=default_reassociate,
        rewrite_div=rewrite_div, race_opts=dict(race_opts or {}),
        tolerance=tolerance, noise_margin=noise_margin,
    )
    if batch_sizes:
        # only batch-aware searches carry the key: the default token (and
        # thus every record written before batch-aware tuning existed)
        # stays byte-identical
        opts["batch_sizes"] = sorted(set(int(b) for b in batch_sizes))
    return json.dumps(_opts_token(opts), sort_keys=True,
                      separators=(",", ":"))


def autotune(program: Program, env: Mapping, *,
             levels: Sequence[int] = REASSOCIATE_LEVELS,
             backends: Optional[Sequence[str]] = None,
             grid: Optional[Iterable[tuple]] = None, quick: bool = False,
             repeats: int = 5, warmup: int = 2,
             default_reassociate: int = 0, rewrite_div: bool = False,
             race_opts: Optional[Mapping] = None,
             tolerance: Optional[float] = None, noise_margin: float = 0.03,
             store: Optional[TuningStore] = None, force: bool = False,
             write: bool = True,
             batch_sizes: Sequence[int] = ()) -> TuningDecision:
    """Pick (and persist) the fastest correct config for ``program`` + ``env``.

    Consults the persistent store first: a record for this exact (program
    hash, env signature, device kind, jax version, search options) answers
    with zero measurement (``from_cache=True``) unless ``force=True`` — the
    search-shaping options (``levels``, ``backends``, ``grid``, ``quick``,
    ``rewrite_div``, ...) are part of the key via :func:`search_signature`,
    so a narrowed search never shadows a full one.  Otherwise the
    full space is measured — ``levels`` x eligible ``backends`` x the block
    ``grid`` — every candidate correctness-gated against the
    ``reassociate=0`` XLA baseline at the differential-harness ``tolerance``
    for the env's dtype, and the winner written back (program-level record
    plus one plan-level record per reassociation level, which is what
    ``compile_plan(..., backend="auto")`` consults).

    The static default — ``default_reassociate`` on the capability probe's
    backend with the default block config — is always measured too, and wins
    ties within ``noise_margin``.
    """
    grid = list(grid) if grid is not None else None
    sig = env_signature(env)
    s = store if store is not None else default_store()
    prog_h = program_hash(program)
    fence = runtime_fence()
    batch_sizes = tuple(sorted(set(int(b) for b in batch_sizes if b > 1)))
    search = search_signature(
        levels=levels, backends=backends, grid=grid, quick=quick,
        default_reassociate=default_reassociate, rewrite_div=rewrite_div,
        race_opts=race_opts, tolerance=tolerance, noise_margin=noise_margin,
        batch_sizes=batch_sizes)
    key = record_key("program", prog_h, sig, fence, opts=search)

    from repro import obs

    if not force:
        rec = program_record(prog_h, sig, store=s, opts=search)
        if rec is not None and isinstance(rec.get("choice"), dict):
            stats = rec.get("stats") or {}
            if obs.enabled():
                obs.counter("race_tuning_lookups_total",
                            outcome="store-hit").inc()
                obs.event("tuning_store_hit", program=prog_h,
                          choice=rec["choice"])
            return TuningDecision(
                choice=Config.from_dict(rec["choice"]),
                default=Config.from_dict(rec.get("default", rec["choice"])),
                default_us=stats.get("default_us"),
                tuned_us=stats.get("tuned_us"),
                search_seconds=0.0, from_cache=True, key=key)
    if obs.enabled():
        obs.counter("race_tuning_lookups_total", outcome="search").inc()

    t0 = time.perf_counter()
    opts = dict(race_opts or {})
    opts.pop("tune", None)  # the tuner must not recurse into itself
    opts["rewrite_div"] = rewrite_div

    want_levels = sorted(set(levels) | {default_reassociate})
    results = {lvl: race(program, reassociate=lvl, **opts)
               for lvl in want_levels}
    if 0 not in results:  # the correctness oracle is always r0/xla
        results[0] = race(program, reassociate=0, **opts)

    truth_ex = compile_plan(results[0].plan, env, "xla")
    truth = {k: np.asarray(v) for k, v in truth_ex(env).items()}
    tol = tolerance if tolerance is not None else _baseline_tolerance(env)

    plans = {lvl: results[lvl].plan for lvl in want_levels}
    configs = candidate_configs(plans, backends=backends, grid=grid,
                                quick=quick)
    default = Config(default_reassociate,
                     _default_backend_for(plans[default_reassociate],
                                          backends))
    if default not in configs:
        configs.append(default)

    with obs.span("autotune", program=prog_h):
        measurements = [
            measure_candidate(plans[c.reassociate], c, env, truth, tol,
                              repeats=repeats, warmup=warmup)
            for c in configs]
        winner, default_m = _pick(measurements, default, noise_margin)
        # batch-aware pass: the batched (vmapped) executor has different
        # economics, so the per-call survivors are re-measured at each
        # representative batch size and recorded separately below (what the
        # serving runtime's coalesced dispatch consults)
        if batch_sizes:
            ok_configs = [m.config for m in measurements if m.ok]
            measurements.extend(
                measure_candidate(plans[c.reassociate], c, env, truth, tol,
                                  repeats=repeats, warmup=warmup, batch=b)
                for b in batch_sizes for c in ok_configs)
    search_s = time.perf_counter() - t0
    if obs.enabled():
        obs.event("tuning_decision", program=prog_h,
                  choice=winner.config.describe(),
                  default=default.describe(),
                  default_us=default_m.us if default_m else None,
                  tuned_us=winner.us, search_s=search_s,
                  n_candidates=len(measurements),
                  n_ok=sum(m.ok for m in measurements),
                  n_gated=sum(m.status == "gated" for m in measurements),
                  persisted=bool(write))

    if write:
        stats = dict(
            default_us=default_m.us if default_m else None,
            tuned_us=winner.us, search_s=search_s,
            n_candidates=len(measurements),
            n_ok=sum(m.ok for m in measurements),
            n_gated=sum(m.status == "gated" for m in measurements))
        s.put(dict(key=key, kind="program", hash=prog_h, device=fence["device"],
                   jax=fence["jax"], search=search,
                   choice=winner.config.as_dict(),
                   default=default.as_dict(), stats=stats))
        for lvl, plan in plans.items():
            level_default = Config(lvl, _default_backend_for(plan, backends))
            # one plan record per measured batch population: 0 (the per-call
            # path compile_plan consults) plus each tuned batch size (what
            # the serving runtime's coalesced dispatch consults)
            for b in (0,) + batch_sizes:
                level_ms = [m for m in measurements
                            if m.ok and m.config.reassociate == lvl
                            and m.batch == b]
                if not level_ms:
                    continue
                ld_m = _find(level_ms, level_default)
                best = _prefer_default(min(level_ms, key=lambda m: m.us),
                                       ld_m, level_default, noise_margin)
                rec = dict(
                    key=record_key("plan", plan_hash(plan), sig, fence,
                                   batch=b),
                    kind="plan", hash=plan_hash(plan),
                    device=fence["device"], jax=fence["jax"],
                    choice=best.config.as_dict(),
                    stats=dict(us=best.us,
                               default_us=ld_m.us if ld_m else None))
                if b:
                    rec["batch"] = b
                s.put(rec)

    return TuningDecision(
        choice=winner.config, default=default,
        default_us=default_m.us if default_m else None, tuned_us=winner.us,
        search_seconds=search_s, from_cache=False, key=key,
        measurements=measurements)
