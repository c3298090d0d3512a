"""Smoke run of RACE's serving path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the sharded path only

One chip: NPB MG ``psinv`` at class B (256^3, float32) and the POP
``hdifft_gm`` tracer stencil on a 2048 x 2048 grid are RACE-optimized with
``race()``, their executors are built by ``ServeRuntime.warmup``, and then
several client threads send requests through the runtime, so that some of
them coalesce into one vmapped batch.  That happens once on
``backend="auto"``, which must pick the compiled Pallas kernel, and once on
``"xla"``.  Every output is checked against the unoptimized baseline program
evaluated in float64 on the host CPU backend.

Four chips: ``psinv`` at 512^3 runs under ``race(prog, mesh=...)`` on a 2x2
mesh, once with halo exchange and once with halo recompute, and each result
is compared with the same plan run on one chip.

The script exits non-zero, and prints no result line, unless JAX's first
device is a TPU and every check passes.  Its last line on stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Earlier lines give smoke timings per phase (compile as set-up, then the
first request and the median request), not benchmark numbers, and the
persistent compilation cache's traffic (:mod:`repro.core.compile_cache`).
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: (case, grid) served on one chip: NPB MG class B, and a POP 2-D field
SERVE_CASES = (("psinv", (256,)), ("hdifft_gm", (2048, 2048)))
#: the sharded case: NPB MG class C over four chips
SHARD_CASE = ("psinv", (512,))
CLIENTS = 4
REQUESTS_PER_CLIENT = 4
ENVS = 4  # distinct inputs, each checked against its own baseline


def build_case(name: str, grid: tuple):
    """A registry case at ``grid``: ``(n,)`` for a cube, ``(nx, ny)`` for a
    POP horizontal field."""
    from repro.apps.paper_kernels import CASES, get_case

    if len(grid) == 1:
        return get_case(name, grid[0])
    fn, _, _ = CASES[name]
    return fn(*grid)


def baseline_truth(case, res, envs: list) -> list:
    """The unoptimized program on the host CPU backend in float64, one
    output dict (numpy) per env."""
    import jax
    import numpy as np

    from repro.core.codegen import interior

    run = jax.jit(lambda e: interior(res.plan, res.baseline_evaluator()(e)))
    cpu = jax.devices("cpu")[0]
    out = []
    with jax.enable_x64(True), jax.default_device(cpu):
        for env in envs:
            env64 = {k: np.asarray(v, np.float64) for k, v in env.items()}
            out.append({k: np.asarray(v) for k, v in run(env64).items()})
    return out


def serve_phase(res, envs: list, truths: list, backend: str, *,
                clients: int = CLIENTS,
                per_client: int = REQUESTS_PER_CLIENT) -> dict:
    """Warm up, then serve ``clients * per_client`` requests through one
    :class:`~repro.serve.ServeRuntime`; check each output against its
    env's baseline.  Returns the phase report (raises on any failure)."""
    import numpy as np

    from repro.core import compile_cache
    from repro.core.executor import compile_plan
    from repro.serve import ServeRuntime
    from repro.testing.differential import default_tolerances, rel_err

    tol = default_tolerances(np.float32)["baseline"]
    cc0 = compile_cache.counts()
    rt = ServeRuntime(max_batch=clients, window_us=20000, backend=backend)
    try:
        t0 = time.perf_counter()
        rt.warmup([(res.plan, envs[0])], backend=backend)
        compile_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        first = rt.run(res.plan, envs[0], timeout=600)
        first_s = time.perf_counter() - t0
        worst = rel_err(first, truths[0])
        s0 = rt.stats()

        lat: list = []
        got: list = []
        errors: list = []
        lock = threading.Lock()
        start = threading.Barrier(clients)

        def client(idx: int) -> None:
            try:
                start.wait(timeout=600)
                for i in range(per_client):
                    k = (idx + i) % len(envs)
                    t = time.perf_counter()
                    out = rt.run(res.plan, envs[k], timeout=600)
                    with lock:
                        lat.append(time.perf_counter() - t)
                        got.append((k, out))
            except Exception as e:  # noqa: BLE001 - re-raised below
                with lock:
                    errors.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=1200)
        if any(t.is_alive() for t in threads):
            raise TimeoutError(f"{backend}: a client never finished")
        if errors:
            raise errors[0]
        s1 = rt.stats()
    finally:
        rt.close(timeout=60)

    for k, out in got:
        worst = max(worst, rel_err(out, truths[k]))
    if worst > tol:
        raise AssertionError(
            f"{backend}: max relative error {worst:.3e} vs the float64 "
            f"baseline exceeds {tol:.0e}")
    n = clients * per_client
    stats = {k: s1[k] - s0[k] for k in ("completed", "failed", "rejected",
                                         "batches", "coalesced")}
    if len(got) != n or stats["completed"] != n or stats["failed"] \
            or stats["rejected"]:
        raise AssertionError(f"{backend}: {len(got)}/{n} served, {stats}")
    if stats["coalesced"] == 0:
        raise AssertionError(f"{backend}: no request rode a batch: {stats}")
    ex = compile_plan(res.plan, envs[0], backend)  # the executor it served
    cc1 = compile_cache.counts()
    return dict(
        backend=backend, resolved=ex.backend,
        interpret=getattr(ex.spec, "interpret", None),
        compile_s=compile_s, first_request_s=first_s,
        median_request_s=statistics.median(lat), requests=n,
        batches=stats["batches"], coalesced=stats["coalesced"],
        max_batch=s1["max_batch"], max_rel_err=worst,
        cache_hits=cc1["hits"] - cc0["hits"],
        cache_misses=cc1["misses"] - cc0["misses"])


def one_chip() -> None:
    from repro.core.race import race
    from repro.testing.differential import build_env

    for name, grid in SERVE_CASES:
        case = build_case(name, grid)
        res = race(case.program, reassociate=case.reassociate,
                   rewrite_div=case.rewrite_div)
        envs = [build_env(case, seed=s) for s in range(ENVS)]
        truths = baseline_truth(case, res, envs)
        for backend, want in (("auto", "pallas"), ("xla", "xla")):
            rep = serve_phase(res, envs, truths, backend)
            print(json.dumps(dict(phase="serve", case=name, grid=grid,
                                  **rep)), flush=True)
            if rep["resolved"] != want:
                raise AssertionError(
                    f"{name}: backend={backend} served on {rep['resolved']}, "
                    f"expected {want}")
            if want == "pallas" and rep["interpret"] is not False:
                raise AssertionError(f"{name}: Pallas kernel interpreted")


def sharded_phase(res, env: dict, mesh, halo: str, want: dict) -> dict:
    """Run ``res`` sharded over ``mesh`` with ``halo`` transport; compare
    with ``want`` (the same plan on one chip)."""
    import jax
    import numpy as np

    from repro.shard import compile_sharded
    from repro.testing.differential import default_tolerances, rel_err

    t0 = time.perf_counter()
    ex = compile_sharded(res, env, mesh, halo=halo)
    out = jax.block_until_ready(ex(env))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(ex(env))
    run_s = time.perf_counter() - t0
    err = rel_err(out, want)
    max_abs = max(float(np.abs(np.asarray(out[k]) - want[k]).max())
                  for k in want)
    tol = default_tolerances(np.float32)["plan"]
    if err > tol:
        raise AssertionError(
            f"sharded {halo}: relative error {err:.3e} vs one chip exceeds "
            f"{tol:.0e}")
    return dict(halo=halo, strategy=ex.halo_prog.strategy,
                resolved=ex.local.backend,
                interpret=getattr(ex.local.spec, "interpret", None),
                compile_s=compile_s, run_s=run_s, rel_err=err,
                max_abs_diff=max_abs)


def four_chips() -> None:
    import jax
    import numpy as np

    from repro.core.race import race
    from repro.launch.mesh import make_stencil_mesh
    from repro.testing.differential import build_env

    if len(jax.devices()) < 4:
        raise SystemExit(f"--chips 4 needs four devices, JAX found "
                         f"{len(jax.devices())}")
    mesh = make_stencil_mesh(4)
    name, grid = SHARD_CASE
    case = build_case(name, grid)
    res = race(case.program, reassociate=case.reassociate,
               rewrite_div=case.rewrite_div, mesh=mesh)
    env = build_env(case, seed=0)
    t0 = time.perf_counter()
    want = {k: np.asarray(v) for k, v in res.run(env, "auto").items()}
    print(json.dumps(dict(phase="one_chip", case=name, grid=grid,
                          seconds=time.perf_counter() - t0)), flush=True)
    for halo in ("exchange", "recompute"):
        rep = sharded_phase(res, env, mesh, halo, want)
        print(json.dumps(dict(phase="sharded", case=name, grid=grid,
                              **rep)), flush=True)
        if rep["strategy"] != halo or rep["interpret"] is not False:
            raise AssertionError(f"sharded {halo}: {rep}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path on a 2x2 mesh")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()))
    print(json.dumps(dict(phase="device", **device)), flush=True)

    from repro.core import compile_cache

    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps(dict(phase="compile_cache", **compile_cache.info())),
          flush=True)
    print(json.dumps(dict(ok=True, device=device)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
