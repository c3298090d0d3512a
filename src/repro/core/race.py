"""RACE driver: the public API tying detection, contraction, analysis, and
code generation together (paper Fig. 3 workflow).

    result = race(program)                      # binary, bitwise-faithful
    result = race(program, reassociate=3)       # n-ary path (Section 7)
    result = race(program, esr=True)            # ESR(+) comparison baseline

``reassociate`` levels follow Section 7.1:
    0  no reassociation (binary detection; preserves FP results exactly)
    2  respect parentheses as written (flatten only explicit same-op chains
       the programmer parenthesized together — our IR has no parens, so this
       flattens nothing and equals level 0 + pair-graph detection)
    3  flatten nested same-operator chains (+ into +, * into *)
    4  additionally distribute loop-invariant scalar/const multiplications
       over sums (cautious distributive law; may add ops, so gated by profit)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from repro import obs as _obs

from . import analysis
from .backend import BACKENDS, Capability, Selection, probe_pallas, select_backend
from .codegen import build_baseline_evaluator, build_plan_evaluator
from .depgraph import Plan, finalize, materialized_elements
from .detect import PaperCost, RooflineCost, Transformed, detect_binary
from .ir import Program, fmt_expr, fmt_ref


@dataclass
class RaceResult:
    program: Program
    plan: Plan
    transformed: Transformed
    options: dict
    # per-env-signature tuned delegation: sig -> (TuningDecision, RaceResult)
    _tuned: dict = field(default_factory=dict, repr=False)

    # --- analysis ----------------------------------------------------------
    def profit(self):
        return analysis.profit(self.plan)

    def op_table(self, base: bool = False):
        return analysis.op_table(self.program, None if base else self.plan)

    def reduced_ops(self) -> float:
        return analysis.reduced_ops_fraction(self.program, self.plan)

    def n_aux(self) -> int:
        """Auxiliary arrays *found* (paper Table 1 'AA Num'); contraction may
        inline some of them away (see n_aux_materialized)."""
        return len(self.transformed.aux)

    def n_aux_materialized(self) -> int:
        return len(self.plan.aux_order)

    def rounds(self) -> int:
        return self.plan.rounds

    def materialized_elements(self, contracted: bool = True) -> int:
        return materialized_elements(self.plan, contracted)

    # --- execution ---------------------------------------------------------
    def evaluator(self):
        return build_plan_evaluator(self.plan)

    def baseline_evaluator(self):
        return build_baseline_evaluator(self.program)

    def capability(self) -> Capability:
        """Pallas-eligibility verdict, re-derived from the lowering engine.

        ``probe_pallas`` delegates to the engine's own analysis
        (:func:`repro.lowering.geometry.analyze_plan`), so the structured
        fallback ``reasons`` (and the lowering ``facts`` — mirrored-origin
        windows, in-kernel gather, N-D grid depth) always agree with what
        :meth:`run` actually lowers."""
        return probe_pallas(self.plan)

    def select_backend(self, backend: Optional[str] = None) -> Selection:
        """Resolve a backend request (default: the one given to ``race``)."""
        return select_backend(self.plan, backend or self.options.get("backend", "auto"))

    def tune(self, env: dict, **autotune_kw):
        """Measure-and-pick the best (reassociate, backend, blocks) for
        ``env`` via :func:`repro.tuning.autotune` (or the persistent store,
        when this machine already tuned this program + signature).

        The decision is remembered on this result: later :meth:`run` /
        :meth:`run_batch` calls with the same env signature and no explicit
        backend execute the winner — including a different reassociation
        level's plan when that measured faster.  Returns the
        :class:`~repro.tuning.TuningDecision`.
        """
        from repro.tuning import autotune

        from .executor import env_signature

        opts = self.options
        # rebuild each level with the same plan-shaping knobs as this result,
        # so the plans the tuner measures are the plans run() will execute.
        # "esr" is deliberately excluded: ESR(+) is a paper-comparison
        # baseline that restricts detection to the innermost level, and
        # forwarding it would make the tuner measure (and persist) those
        # handicapped plans as the winners for the *unrestricted* search
        race_opts = {k: opts[k]
                     for k in ("contraction", "cost_model",
                               "rewrite_sub", "max_rounds",
                               "mis_exact_limit")
                     if k in opts}
        kw = dict(autotune_kw)
        kw.setdefault("default_reassociate", opts.get("reassociate", 0))
        kw.setdefault("rewrite_div", opts.get("rewrite_div", False))
        kw.setdefault("race_opts", race_opts)
        dec = autotune(self.program, env, **kw)
        ch = dec.choice
        if (ch.reassociate == opts.get("reassociate", 0)
                and not opts.get("esr")):
            target = self
        else:
            # an ESR result always rebuilds, even at its own level: the
            # tuner measured unrestricted plans, so serving must run them
            target = race(self.program, reassociate=ch.reassociate,
                          rewrite_div=opts.get("rewrite_div", False),
                          backend=opts.get("backend"), **race_opts)
        self._tuned[env_signature(env)] = (dec, target)
        return dec

    def _tuned_entry(self, env, sig):
        """(decision, target result) for sig, auto-tuning when requested.
        ``env`` may be a zero-arg callable producing the example env, so
        callers can defer expensive materialization (run_batch slices the
        stacked batch) to the one path that needs concrete values."""
        from .executor import env_signature

        entry = self._tuned.get(sig)
        if entry is None and self.options.get("tune") is not None:
            if callable(env):
                env = env()
            # race(tune=True) stores {}; race(tune={...}) forwards the kwargs
            self.tune(dict(env), **self.options["tune"])
            entry = self._tuned.get(sig) or self._tuned.get(
                env_signature(env))
            if entry is not None:  # normalization drift (e.g. weak types
                self._tuned[sig] = entry  # sliced out of a stacked batch)
        return entry

    def run(self, env: dict, backend: Optional[str] = None, *,
            block_rows: int = 8, block_cols: int = 8, block_inner: int = 0,
            donate: Optional[bool] = None):
        """Execute the plan on the selected backend.

        Both backends return the *interior* convention — ``{output name:
        array over the statement ranges}`` — so results are directly
        comparable across backends.  ``backend=None`` uses the request
        recorded by :func:`race` (``"auto"`` prefers Pallas when eligible,
        after consulting the persistent autotuning store).

        Execution goes through the plan-keyed compiled-executor cache
        (:mod:`repro.core.executor`): the first call per (plan structure,
        shapes/dtypes, backend, block config) specializes and jits; every
        later same-signature call — including calls on a *different*
        ``RaceResult`` holding a structurally identical plan — reuses the
        compiled executor with zero retracing.

        With ``race(..., tune=True)`` (or after an explicit :meth:`tune`),
        calls without an explicit ``backend`` run the tuned winner for the
        env's signature; the first such call pays the search unless the
        persistent store already has the decision.
        """
        from .executor import compile_plan, env_signature

        if backend is None and self.options.get("mesh") is not None:
            # race(..., mesh=...) makes sharded the default execution path;
            # an explicit backend= on run() opts back into single-device
            return self.run_sharded(
                env, block_rows=block_rows, block_cols=block_cols,
                block_inner=block_inner)
        if backend is None and (self._tuned
                                or self.options.get("tune") is not None):
            entry = self._tuned_entry(env, env_signature(env))
            if entry is not None:
                dec, target = entry
                ch = dec.choice
                ex = compile_plan(
                    target.plan, env, ch.backend, block_rows=ch.block_rows,
                    block_cols=ch.block_cols, block_inner=ch.block_inner,
                    donate=donate)
                return ex(env)
        ex = compile_plan(
            self.plan, env, backend or self.options.get("backend", "auto"),
            block_rows=block_rows, block_cols=block_cols,
            block_inner=block_inner, donate=donate)
        return ex(env)

    def run_sharded(self, env: dict, mesh=None, backend: Optional[str] = None,
                    *, halo: Optional[str] = None, block_rows: int = 8,
                    block_cols: int = 8, block_inner: int = 0):
        """Execute spatially partitioned over a device mesh.

        The plan's iteration box is split across ``mesh`` (falling back to
        the mesh given to :func:`race`), each shard runs the ordinary
        compiled executor on its chunk under ``jax.shard_map``, and halos
        sized by the geometry envelopes travel between neighbors — see
        :mod:`repro.shard`.  Outputs are the same interior convention as
        :meth:`run` (differentially identical to single-device execution),
        and gradients flow through a ``custom_vjp`` that re-partitions the
        adjoint-stencil plans under the same mesh.

        Raises :class:`repro.shard.ShardingUnavailable` with structured
        refusal reasons when no mesh axis can be placed on any grid level.
        ``halo`` picks the transport strategy (``"auto"`` | ``"exchange"`` |
        ``"recompute"``), defaulting to the one recorded by :func:`race`.
        """
        from repro.shard import compile_sharded

        mesh = mesh if mesh is not None else self.options.get("mesh")
        if mesh is None:
            raise ValueError(
                "run_sharded needs a device mesh: pass mesh= here or to "
                "race(..., mesh=...)")
        ex = compile_sharded(
            self, env, mesh,
            halo=halo if halo is not None
            else self.options.get("halo", "auto"),
            backend=backend or self.options.get("backend", "auto"),
            block_rows=block_rows, block_cols=block_cols,
            block_inner=block_inner)
        return ex(env)

    def run_batch(self, envs, backend: Optional[str] = None, *,
                  block_rows: int = 8, block_cols: int = 8,
                  block_inner: int = 0, donate: Optional[bool] = None):
        """Batched execution: one compiled executor vmapped over ``envs``.

        ``envs`` is a sequence of same-signature environments, or an
        already-stacked env dict whose every entry carries a leading batch
        axis (scalars as ``(B,)`` arrays).  Returns ``{output name: (B, ...)
        array}`` with ``out[name][b] == run(envs[b])[name]``.  A tuned
        decision for the per-example signature (see :meth:`tune`) is applied
        the same way as in :meth:`run`.
        """
        from .executor import compile_plan, env_signature, stacked_signature

        import numpy as _np

        if isinstance(envs, dict):
            sig = stacked_signature(envs)
            # per-example env (batch element 0) for a possible tune trigger
            # — built *lazily*: slicing element 0 host-transfers the whole
            # stacked batch (and breaks under jit tracing), so it must only
            # happen if an actual tune run needs concrete data
            example = lambda: {k: _np.asarray(v)[0]  # noqa: E731
                               for k, v in envs.items()}
        else:
            envs = list(envs)
            if not envs:
                raise ValueError("run_batch needs at least one env")
            sig = env_signature(envs[0])
            example = envs[0]
        if backend is None and (self._tuned
                                or self.options.get("tune") is not None):
            entry = self._tuned_entry(example, sig)
            if entry is not None:
                dec, target = entry
                ch = dec.choice
                ex = compile_plan(
                    target.plan, sig, ch.backend, block_rows=ch.block_rows,
                    block_cols=ch.block_cols, block_inner=ch.block_inner,
                    donate=donate)
                return ex.run_batch(envs)
        ex = compile_plan(
            self.plan, sig, backend or self.options.get("backend", "auto"),
            block_rows=block_rows, block_cols=block_cols,
            block_inner=block_inner, donate=donate)
        return ex.run_batch(envs)

    # --- observability ------------------------------------------------------
    def telemetry(self) -> dict:
        """Everything observable about this result in one dict: structural
        identities (program/plan hashes), the static analysis verdicts
        (reduced-ops fraction, auxiliary counts, capability probe), the
        process-wide executor-cache stats, and — when ``RACE_OBS=1`` — the
        metrics series and decision events carrying this plan's hash.

        This is the per-result view of the process-wide telemetry in
        :mod:`repro.obs`; serving dashboards and the benchmarks read it
        instead of poking at internals."""
        from .executor import executor_cache, plan_hash, program_hash

        ph = plan_hash(self.plan)
        cap = self.capability()
        out = dict(
            program=program_hash(self.program),
            plan=ph,
            options={k: v for k, v in self.options.items()
                     if isinstance(v, (bool, int, float, str))},
            reduced_ops=self.reduced_ops(),
            n_aux=self.n_aux(),
            n_aux_materialized=self.n_aux_materialized(),
            rounds=self.rounds(),
            capability=dict(eligible=cap.eligible,
                            reasons=[str(r) for r in cap.reasons],
                            facts=[str(f) for f in cap.facts]),
            executor_cache=executor_cache().cache_info(),
            obs_enabled=_obs.enabled(),
        )
        if _obs.enabled():
            out["metrics"] = _obs.snapshot(label_filter={"plan": ph})
            out["events"] = [e for e in _obs.events()
                             if e.get("plan") == ph]
            # this plan's slice of the span timeline (Chrome-trace ready:
            # repro.obs.trace.chrome_trace renders these records directly)
            out["spans"] = [s for s in _obs.span_records()
                            if s.get("labels", {}).get("plan") == ph]
        return out

    # --- pretty ------------------------------------------------------------
    def to_source(self) -> str:
        vn = {l.level: l.var for l in self.program.loops}
        lines = []
        for circle_key, names in self.plan.circles:
            rng = dict(circle_key)
            hdr = " ".join(
                f"for {vn.get(l, f'i{l}')} in [{lo},{hi}]" for l, (lo, hi) in rng.items()
            )
            lines.append(f"# circle {hdr}")
            for nm in names:
                aux = next(a for a in self.plan.aux_order if a.name == nm)
                lines.append(
                    f"  {fmt_ref(aux.lhs(), vn)} = {fmt_expr(self.plan.aux_exprs[nm], vn)}"
                )
        hdr = " ".join(f"for {l.var} in [{l.lo},{l.hi}]" for l in self.program.loops)
        lines.append(f"# main {hdr}")
        for st in self.plan.body:
            lines.append(f"  {fmt_ref(st.lhs, vn)} = {fmt_expr(st.rhs, vn)}")
        return "\n".join(lines)


def race(
    program: Program,
    reassociate: int = 0,
    esr: bool = False,
    contraction: bool = True,
    cost_model: Optional[object] = None,
    rewrite_sub: bool = True,
    rewrite_div: bool = False,
    max_rounds: int = 64,
    mis_exact_limit: int = 40,
    backend: Optional[str] = None,
    tune=False,
    mesh=None,
    halo: str = "auto",
) -> RaceResult:
    """Run RACE on a program.  See module docstring for knobs.

    ``backend`` records the execution-backend request honored by
    :meth:`RaceResult.run`: ``"xla"`` (whole-array evaluator), ``"pallas"``
    (blocked TPU kernel; raises ``BackendUnavailable`` at run/selection time
    when the plan is ineligible), or ``"auto"`` (Pallas when the capability
    probe passes — after consulting the persistent autotuning store — XLA
    otherwise, never silently: the Selection carries the fallback reasons).
    ``backend=None`` resolves to ``$RACE_BACKEND`` or ``"auto"``.

    ``tune=True`` defers the strategy/backend/block choice to the autotuner
    (:mod:`repro.tuning`): the first :meth:`RaceResult.run` per env
    signature measures the candidate space (or answers from the persistent
    store) and every later call runs the winner.  Pass a dict instead of
    True to forward keyword options to :func:`repro.tuning.autotune`,
    e.g. ``tune=dict(levels=(0, 3), backends=("xla",))``.

    ``mesh`` (a ``jax.sharding.Mesh``, e.g. from
    :func:`repro.launch.mesh.make_stencil_mesh`) makes sharded execution the
    default: :meth:`RaceResult.run` delegates to :meth:`RaceResult.run_sharded`
    when no explicit backend is passed.  ``halo`` records the transport
    strategy for that path (see :data:`repro.shard.HALO_STRATEGIES`).
    """
    if backend is None:
        from .executor import default_backend

        backend = default_backend()
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; choose from {BACKENDS}")
    if reassociate and esr:
        # ESR+ = ESR with reassociation (paper's strongest baseline)
        pass
    with _obs.span("detect", reassociate=str(reassociate)):
        if reassociate:
            from .nary import detect_nary

            transformed = detect_nary(
                program,
                level=reassociate,
                cost_model=cost_model or PaperCost(),
                rewrite_sub=rewrite_sub,
                rewrite_div=rewrite_div,
                max_rounds=max_rounds,
                restrict_innermost=esr,
                mis_exact_limit=mis_exact_limit,
            )
        else:
            transformed = detect_binary(
                program,
                cost_model=cost_model or PaperCost(),
                max_rounds=max_rounds,
                restrict_innermost=esr,
            )
    with _obs.span("contract"):
        plan = finalize(transformed, contraction=contraction)
    if _obs.enabled():
        from .executor import plan_hash, program_hash

        _obs.counter("race_builds_total",
                     reassociate=str(reassociate)).inc()
        _obs.gauge("race_reduced_ops", program=program_hash(program),
                   plan=plan_hash(plan)).set(
            analysis.reduced_ops_fraction(program, plan))
        _obs.gauge("race_aux_materialized", plan=plan_hash(plan)).set(
            len(plan.aux_order))
    return RaceResult(
        program,
        plan,
        transformed,
        dict(
            reassociate=reassociate,
            esr=esr,
            contraction=contraction,
            backend=backend,
            rewrite_div=rewrite_div,
            # plan-shaping knobs, recorded so RaceResult.tune() measures
            # plans built with *these* options, not the defaults
            cost_model=cost_model,
            rewrite_sub=rewrite_sub,
            max_rounds=max_rounds,
            mis_exact_limit=mis_exact_limit,
            tune=(dict(tune) if isinstance(tune, dict)
                  else {} if tune else None),
            mesh=mesh,
            halo=halo,
        ),
    )


def race_from_fn(fn, shapes, consts=None, **race_opts) -> RaceResult:
    """Run RACE on a plain-Python loop nest (the capture frontend).

    ``fn`` is an ordinary function written as nested ``for`` loops over
    NumPy-style arrays (or an ``@race_kernel``-wrapped one); ``shapes`` maps
    each parameter to ``()`` (scalar) or an array shape; ``consts`` supplies
    capture-time values for free names.  Remaining keywords go to
    :func:`race`.  Raises ``repro.frontend.CaptureError`` with a structured
    diagnostic when ``fn`` is outside the capturable scope.

        res = race_from_fn(blur, {"u": (64, 64), "out": (64, 64)},
                           reassociate=3)
        out = res.run({"u": u})
    """
    from repro.frontend import capture

    return race(capture(fn, shapes, consts), **race_opts)
