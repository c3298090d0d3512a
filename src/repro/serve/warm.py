"""Eager warmup: build executors before traffic, replay the tuning store.

Zero cold start has two halves.  The persistent compilation cache
(:mod:`repro.core.compile_cache`) makes a *rebuild* cheap — XLA executables
deserialize from disk instead of recompiling — but something still has to
trigger that rebuild before the first real request arrives.  This module is
that something:

  * :func:`warmup` — eagerly build + first-call a list of (plan | RaceResult,
    env | signature) pairs, reporting per-item build and first-call wall
    times plus the persistent-cache traffic they generated;
  * :func:`synthetic_env` — fabricate a valid environment from a bare
    :func:`~repro.core.executor.env_signature` (what the tuning store
    records), so warmup needs no real data;
  * :func:`warm_from_store` / the ``python -m repro.serve.warm`` CLI — replay
    the tuning store's plan-kind records: each records the exact (plan hash,
    env signature) a past process served, and the registry
    (:mod:`repro.apps.paper_kernels`) lets us rebuild the matching program
    so a fresh process reaches steady-state latency before opening its
    queue.

The store records only hashes, not programs — replay works by re-deriving
candidate programs from the registry at sizes inferred from the stored
signatures and matching structural hashes.  Records whose program is not in
the registry (user-defined kernels) are reported as ``unmatched``; warm
those through :func:`warmup` with the live objects instead.
"""
from __future__ import annotations

import time
from typing import Mapping, Optional, Sequence, Tuple, Union

import numpy as np

import jax

from repro import obs as _obs
from repro.core import compile_cache
from repro.core.depgraph import Plan
from repro.core.executor import compile_plan, env_signature, plan_hash

#: reassociation levels replay tries when matching a stored plan hash
REPLAY_LEVELS = (0, 3, 4)


def synthetic_env(sig: Sequence[tuple]) -> dict:
    """A valid environment fabricated from an env signature.

    Every array is 0.5-valued (safely inside the well-conditioned range the
    differential harness draws from); weak-typed scalars come back as python
    scalars so the fabricated env round-trips to *exactly* the input
    signature — the executor key must match the one real traffic will use.
    """
    env = {}
    for nm, shape, dtype, weak in sig:
        dt = np.dtype(dtype)
        if weak and shape == ():
            if dt.kind in "iu":
                env[nm] = 1
            elif dt.kind == "b":
                env[nm] = True
            elif dt.kind == "c":
                env[nm] = 0.5 + 0j
            else:
                env[nm] = 0.5
        elif shape == ():
            env[nm] = dt.type(1 if dt.kind in "iub" else 0.5)
        else:
            env[nm] = np.full(shape, 1 if dt.kind in "iub" else 0.5,
                              dtype=dt)
    return env


def _as_plan(target: Union[Plan, "object"]) -> Plan:
    plan = getattr(target, "plan", target)
    if not isinstance(plan, Plan):
        raise TypeError(f"warmup target must be a Plan or RaceResult, got "
                        f"{type(target).__name__}")
    return plan


def warmup(items: Sequence[Tuple[object, Union[Mapping, tuple]]], *,
           backend: Optional[str] = None, run: bool = True) -> list:
    """Eagerly build the executor for each (target, env-or-signature) pair.

    Each item's first call triggers the XLA compile — served from the
    persistent compilation cache when it is warm — so
    the first *real* request finds both the executor cache and the jit
    cache hot.  Returns one report dict per item: ``build_ms`` (executor
    specialization), ``first_ms`` (first call, the compile), and the
    persistent-cache hits/misses the item generated.
    """
    reports = []
    for target, env in items:
        plan = _as_plan(target)
        if isinstance(env, tuple):
            env = synthetic_env(env)
        c0 = compile_cache.counts()
        t0 = time.perf_counter()
        ex = compile_plan(plan, env, backend)
        build_ms = (time.perf_counter() - t0) * 1e3
        first_ms = None
        if run:
            t1 = time.perf_counter()
            jax.block_until_ready(ex.run(env))
            first_ms = (time.perf_counter() - t1) * 1e3
        c1 = compile_cache.counts()
        rep = dict(plan=plan_hash(plan), backend=ex.backend,
                   build_ms=round(build_ms, 3),
                   first_ms=None if first_ms is None else round(first_ms, 3),
                   cache_hits=c1["hits"] - c0["hits"],
                   cache_misses=c1["misses"] - c0["misses"])
        if _obs.enabled():
            _obs.event("serve_warmup", **rep)
        reports.append(rep)
    return reports


# ---------------------------------------------------------------------------
# tuning-store replay
# ---------------------------------------------------------------------------


def store_plan_keys(store=None) -> list:
    """``(plan_hash, env signature, batch)`` for every plan-kind record in
    the tuning store matching this process's runtime fence.  Tolerant of
    malformed keys (skipped) and a missing store (empty list)."""
    from repro.tuning.store import default_store, runtime_fence, sig_json

    try:
        s = store if store is not None else default_store()
        fence = runtime_fence()
        out = []
        for key in s.keys():
            parts = key.split("|")
            if len(parts) < 5 or parts[0] != "plan":
                continue
            if parts[3] != str(fence["device"]) or parts[4] != str(
                    fence["jax"]):
                continue
            batch = 0
            if len(parts) >= 6 and parts[5].startswith("batch="):
                try:
                    batch = int(parts[5][len("batch="):])
                except ValueError:
                    continue
            try:
                import json

                sig = tuple((nm, tuple(shape), dt, bool(weak))
                            for nm, shape, dt, weak in json.loads(parts[2]))
            except Exception:
                continue
            if sig_json(sig) != parts[2]:  # round-trip guard
                continue
            out.append((parts[1], sig, batch))
        return out
    except Exception:
        return []


def _candidate_sizes(sig: tuple, max_halo: int = 6) -> list:
    """Grid sizes that could have produced these array dims: every stored
    dimension minus a plausible halo margin (stencil halos are small)."""
    dims = sorted({d for _, shape, _, _ in sig for d in shape})
    return sorted({d - k for d in dims for k in range(max_halo + 1)
                   if d - k >= 2}, reverse=True)


def _match_record(ph: str, sig: tuple, *, levels=REPLAY_LEVELS,
                  _memo: Optional[dict] = None) -> Optional[Plan]:
    """Rebuild the registry program whose plan hashes to ``ph`` at ``sig``.

    For each registry case at each candidate size, the fabricated env's
    signature must equal the stored one (names + shapes + dtypes — cheap,
    no compilation), and only then are plans derived at each replay level
    and hash-compared.  Returns the matching plan or None.
    """
    from repro.apps.paper_kernels import CASES, get_case
    from repro.core.codegen import required_shapes
    from repro.core.race import race

    dtypes = {np.dtype(dt) for _, shape, dt, _ in sig if shape != ()}
    dtype = dtypes.pop() if len(dtypes) == 1 else np.dtype(np.float32)
    want_shapes = {nm: shape for nm, shape, _, _ in sig}
    for name in CASES:
        for n in _candidate_sizes(sig):
            memo_key = (name, n)
            if _memo is not None and memo_key in _memo:
                case = _memo[memo_key]
            else:
                try:
                    case = get_case(name, n)
                except Exception:
                    case = None
                if _memo is not None:
                    _memo[memo_key] = case
            if case is None:
                continue
            try:
                if required_shapes(case.program) != want_shapes:
                    continue
                env = _case_env(case, dtype)
                if env_signature(env) != sig:
                    continue
                for lvl in dict.fromkeys(
                        (case.reassociate,) + tuple(levels)):
                    res = race(case.program, reassociate=lvl,
                               rewrite_div=case.rewrite_div)
                    if plan_hash(res.plan) == ph:
                        return res.plan
            except Exception:
                continue
    return None


def _case_env(case, dtype) -> dict:
    """build_env with the signature's dtype (scalars stay strongly typed,
    matching what the benchmark/tuning paths feed the executor)."""
    from repro.testing.differential import build_env

    return build_env(case, dtype=dtype.type)


def warm_from_store(store=None, *, backend: Optional[str] = None,
                    levels=REPLAY_LEVELS) -> dict:
    """Replay every fence-matching plan record: rebuild + first-call each.

    Returns ``{warmed: [report...], unmatched: [plan hash...]}`` — an
    unmatched hash is a plan whose program is not derivable from the
    registry (a user-defined kernel tuned in some earlier process).
    """
    records = store_plan_keys(store)
    seen = set()
    items = []
    unmatched = []
    memo: dict = {}
    for ph, sig, _batch in records:
        if (ph, sig) in seen:
            continue
        seen.add((ph, sig))
        plan = _match_record(ph, sig, levels=levels, _memo=memo)
        if plan is None:
            unmatched.append(ph)
        else:
            items.append((plan, synthetic_env(sig)))
    reports = warmup(items, backend=backend)
    if _obs.enabled():
        _obs.event("serve_warm_replay", records=len(records),
                   warmed=len(reports), unmatched=len(unmatched))
    return dict(warmed=reports, unmatched=sorted(set(unmatched)))


def main(argv=None) -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser(
        description="eager executor warmup (zero cold start). Default: "
                    "replay the tuning store's plan records; --cases warms "
                    "named registry kernels directly.")
    ap.add_argument("--cases", default=None,
                    help="comma list of registry case names to warm "
                         "(instead of store replay)")
    ap.add_argument("--sizes", default=None,
                    help="comma list of grid sizes for --cases "
                         "(default: each case's registry default)")
    ap.add_argument("--levels", default=None,
                    help="comma list of reassociation levels for --cases "
                         "(default: each case's own level)")
    ap.add_argument("--backend", default=None,
                    help="backend to warm (default $RACE_BACKEND/auto)")
    ap.add_argument("--json", nargs="?", const="-", default=None,
                    metavar="PATH", help="structured report to stdout or PATH")
    args = ap.parse_args(argv)

    if args.cases:
        from repro.apps.paper_kernels import get_case
        from repro.core.race import race
        from repro.testing.differential import build_env

        sizes = ([int(s) for s in args.sizes.split(",")]
                 if args.sizes else [None])
        items = []
        for name in args.cases.split(","):
            for n in sizes:
                case = get_case(name.strip(), n)
                levels = ([int(v) for v in args.levels.split(",")]
                          if args.levels else [case.reassociate])
                for lvl in levels:
                    res = race(case.program, reassociate=lvl,
                               rewrite_div=case.rewrite_div)
                    items.append((res.plan, build_env(case)))
        doc = dict(warmed=warmup(items, backend=args.backend), unmatched=[])
    else:
        doc = warm_from_store(backend=args.backend)

    doc["compile_cache"] = compile_cache.info()
    n_w, n_u = len(doc["warmed"]), len(doc["unmatched"])
    if args.json:
        out = json.dumps(doc, indent=1)
        if args.json == "-":
            print(out)
        else:
            with open(args.json, "w") as f:
                f.write(out + "\n")
            print(f"wrote {args.json}")
    else:
        for rep in doc["warmed"]:
            print(f"warm plan={rep['plan']} backend={rep['backend']} "
                  f"build={rep['build_ms']}ms first={rep['first_ms']}ms "
                  f"cache_hits={rep['cache_hits']}")
        print(f"warmed={n_w} unmatched={n_u} "
              f"compile_cache={doc['compile_cache']}")


if __name__ == "__main__":
    main()
