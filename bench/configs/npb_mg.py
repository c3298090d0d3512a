"""NPB MG's finest-level smoothing step through RACE, for the ``sweep``
driver.

A sweep is NPB's ``resid`` then ``psinv`` on the ``(n+2)^3`` arrays (one
ghost layer), each through the program's normal path: ``race()``, then
``compile_plan(...).run`` on one chip or ``compile_sharded`` over a 2x2
mesh when the cell has four.  The benchmark's jitted write-back puts each
interior result back into a full array with NPB's periodic ghost layer
(``comm3``), so the next program reads it.

Data comes from the seed, on the device: the interior of V is uniform in
[-1, 1] with its mean taken out (so the periodic problem has a solution),
U starts uniform in [-1, 1].
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from bench.configs import npb_mg_ref as ref
from bench.yardstick import compulsory_bytes, rel_gap, seed_key


def footprints(n: int) -> dict:
    """What each program reads and writes on an ``n^3`` interior: V and the
    centre of U are read over the interior, the 27-point operand over the
    interior and its ghost layer; the output is written over the
    interior."""
    inner, full = (n, n, n), (n + 2, n + 2, n + 2)
    return {"resid": {"read": {"V": inner, "U": full},
                      "write": {"Rr": inner}},
            "psinv": {"read": {"U": inner, "R": full},
                      "write": {"U": inner}}}


def prepare(cfg: dict, mix: dict, seed: int, devices: list, clock):
    import jax
    import jax.numpy as jnp

    from repro.apps.paper_kernels import get_case
    from repro.core.executor import compile_plan
    from repro.core.race import race

    n = int(cfg["n"])
    dtype = np.dtype(cfg["dtype"])
    mesh = None
    if len(devices) > 1:
        from repro.launch.mesh import make_stencil_mesh

        mesh = make_stencil_mesh(len(devices))

    with clock.span("race_s"):
        res = {}
        for name in cfg["kernels"]:
            case = get_case(name, n + 2)
            res[name] = race(case.program, reassociate=case.reassociate,
                             rewrite_div=case.rewrite_div, mesh=mesh)

    wrap = jax.jit(lambda x: jnp.pad(x, 1, mode="wrap"))
    inner = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        inner = NamedSharding(mesh, P(None, *mesh.axis_names[::-1]))

    def make(key):
        kv, ku = jax.random.split(key)
        v = jax.random.uniform(kv, (n, n, n), dtype, -1.0, 1.0)
        u = jax.random.uniform(ku, (n, n, n), dtype, -1.0, 1.0)
        return v - jnp.mean(v), u

    shard = {} if inner is None else dict(out_shardings=(inner, inner))
    with clock.span("data_s"):
        v_int, u_int = jax.jit(make, **shard)(seed_key(seed))
        V, U0 = jax.block_until_ready((wrap(v_int), wrap(u_int)))
        del v_int, u_int
    a = {f"a{i}": dtype.type(x) for i, x in enumerate(cfg["a"])}
    c = {f"w{i}": dtype.type(x) for i, x in enumerate(cfg["c"])}

    def build(name, env):
        if mesh is None:
            return compile_plan(res[name].plan, env, "auto")
        from repro.shard import compile_sharded

        return compile_sharded(res[name], env, mesh)

    live = dict(V=V)
    with clock.span("warmup_s"):
        ex_r = build("resid", dict(V=V, U=U0, **a))
        ex_p = build("psinv", dict(U=U0, R=U0, **c))

        def sweep(U):
            r = ex_r.run(dict(V=live["V"], U=U, **a))["Rr"]
            u = ex_p.run(dict(U=U, R=wrap(r), **c))["U"]
            return wrap(u)

        jax.block_until_ready(sweep(U0))
    del V

    runs = _runs(_planes(cfg, mix, seed, n))

    def rows(x, lo, hi):
        """Interior planes ``lo .. hi-1`` (wrapped) of a full array."""
        idx = 1 + np.arange(lo, hi) % n
        return np.asarray(x[idx])[:, 1:-1, 1:-1]

    def fetch(pairs) -> list:
        """Host copies of what the check needs: per checked step and run of
        sampled planes, the U slab before the step (two planes more on
        each side), the V planes (one more) and the planes after it."""
        v = [rows(live["V"], p0 - 1, p1 + 2) for p0, p1 in runs]
        return [(rows(u_in, p0 - 2, p1 + 3), v[i], rows(u_out, p0, p1 + 1))
                for u_in, u_out in pairs for i, (p0, p1) in enumerate(runs)]

    def step(u, v, dt):
        return ref.step(u, v, cfg["a"], cfg["c"], dt)[1]

    def compare(host) -> dict:
        return {"u_rel_gap": max(rel_gap(got, step(u, v, np.float64))
                                 for u, v, got in host)}

    def control(host) -> dict:
        """The same number with the reference in bfloat16 in the program's
        place."""
        import ml_dtypes

        return {"u_rel_gap": max(
            rel_gap(step(u, v, ml_dtypes.bfloat16), step(u, v, np.float64))
            for u, v, _ in host)}

    return SimpleNamespace(
        state0=U0, sweep=sweep, executors=[ex_r, ex_p],
        sweep_bytes=compulsory_bytes(footprints(n), dtype.itemsize),
        fetch=fetch, compare=compare, control=control,
        release=live.clear,
        notes=dict(backend=[getattr(ex_r, "backend", None),
                            getattr(ex_p, "backend", None)],
                   halo=[getattr(getattr(ex, "halo_prog", None), "strategy",
                                 None) for ex in (ex_r, ex_p)],
                   checked_planes=sum(b - a + 1 for a, b in runs)))


def _planes(cfg: dict, mix: dict, seed: int, n: int) -> list:
    """The interior planes (along the first array axis) that the check
    compares: every plane, or ``check_planes`` drawn from the seed plus the
    first, last and middle planes, where a 2x2 shard boundary or the
    periodic wrap lies."""
    k = mix.get("check_planes")
    if k is None or k >= n:
        return list(range(n))
    rng = np.random.default_rng([4, seed])
    fixed = {0, n // 2 - 1, n // 2, n - 1}
    drawn = rng.choice(n, size=int(k), replace=False).tolist()
    return sorted(fixed | set(drawn))


def _runs(planes: list) -> list:
    """Sorted planes as ``[(first, last), ...]`` runs of consecutive
    planes."""
    out = []
    for p in planes:
        if out and p == out[-1][1] + 1:
            out[-1][1] = p
        else:
            out.append([p, p])
    return [tuple(r) for r in out]
