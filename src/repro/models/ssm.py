"""Recurrent sequence mixers: Mamba-1 selective SSM and Griffin's RG-LRU.

Both recurrences have the diagonal affine form  h_t = a_t * h_{t-1} + b_t,
solved with ``jax.lax.associative_scan`` inside fixed-size time chunks and a
``lax.scan`` carrying the state across chunks.

Memory discipline (the whole point of chunking): for Mamba, the discretized
(B, S, d_inner, N) tensors dA/dBx and the hidden sequence h must NEVER
materialize over full S — they are built and consumed *inside* the chunk body
(fused with the C-projection), bounding the working set to one
(B, chunk, d_inner, N) tile.  This is the VMEM-blocking idea of the paper's
array contraction applied to the SSM state (DESIGN.md section 2, rule 3).
The chunk loop unrolls in dry-run probe mode for exact FLOP accounting.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import ExecConfig, dense_init
from .config import ModelConfig


def _n_chunks(S: int, exec_cfg: ExecConfig):
    if exec_cfg.unroll_scans:
        n = min(exec_cfg.probe_chunks, S)
        unroll = True
    else:
        n = max(1, S // max(1, min(exec_cfg.ssm_chunk, S)))
        unroll = 1
    while S % n:
        n -= 1
    return n, unroll


def _chunked(x, n):
    """(B, S, ...) -> (n, B, S/n, ...)"""
    B, S = x.shape[:2]
    return x.reshape((B, n, S // n) + x.shape[2:]).swapaxes(0, 1)


def _scan_recurrence(h0, chunk_fn, xs, exec_cfg: ExecConfig, S: int):
    """Carry h across time chunks.  ``chunk_fn(h, *xs_chunk) -> (y_chunk,
    h_last)``; xs are (B, S, ...) tensors chunked along time."""
    n, unroll = _n_chunks(S, exec_cfg)
    xs_c = tuple(_chunked(x, n) for x in xs)

    def body(h, xc):
        y, h_last = chunk_fn(h, *xc)
        return h_last, y

    h_last, ys = jax.lax.scan(body, h0, xs_c, unroll=unroll)
    y = ys.swapaxes(0, 1)
    return y.reshape((y.shape[0], S) + y.shape[3:]), h_last


def _assoc(a, b, h0):
    """Associative solve of h_t = a_t h_{t-1} + b_t within one chunk
    (axis 1); h0 folded into b_0."""

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    b = b.at[:, 0].add(a[:, 0] * h0)
    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


def _causal_conv1d(x, w, state=None):
    """Depthwise causal conv.  x: (B, S, C); w: (K, C).  With ``state``
    ((B, K-1, C), decode) returns (y, new_state)."""
    K = w.shape[0]
    if state is not None:
        buf = jnp.concatenate([state, x], axis=1)  # (B, K-1+S, C)
        new_state = buf[:, -(K - 1):]
        y = sum(buf[:, i:i + x.shape[1]] * w[i] for i in range(K))
        return y, new_state
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(pad[:, i:i + x.shape[1]] * w[i] for i in range(K))
    return y, None


# ---------------------------------------------------------------------------
# RACE-optimized causal FIR mixer (the differentiable-RACE integration point)
# ---------------------------------------------------------------------------

#: memoized RACE results per (seq_len, channels, radius) — detection and
#: planning run once per shape; every train step reuses the compiled executor
_smooth_results: dict = {}


def _smooth_result(S: int, C: int, R: int):
    key = (S, C, R)
    res = _smooth_results.get(key)
    if res is None:
        from repro.core.ir import Scalar, arr, loopnest, program
        from repro.core.race import race

        loops, (s, c) = loopnest(("s", 0, S - 1), ("c", 0, C - 1))
        xs, ys = arr("sx"), arr("sy")

        def box(t):  # the 3-point partial sum RACE detects and reuses
            return (xs[t, c] + xs[t + 1, c]) + xs[t + 2, c]

        expr = Scalar("sw0") * box(s + R)
        for d in range(1, R + 1):
            expr = expr + Scalar(f"sw{d}") * box(s + R - d)
        res = _smooth_results[key] = race(program(loops, [(ys[s, c], expr)]),
                                          reassociate=3)
    return res


def race_smooth(x, taps, *, radius: int, backend: str = "xla"):
    """Causal FIR residual mixer over the token stream, computed — forward
    *and* backward — through the RACE pipeline.

    ``y[s] = sum_d taps[d] * b(s + R - d)`` with ``b(t)`` a 3-point box sum
    of the left-padded stream: consecutive taps at consecutive positions
    share their box sums, which RACE detects and materializes once (the
    same staggered-sum shape as the paper's hdifft_gm).  The gradient
    w.r.t. ``x`` and ``taps`` flows through the executor's adjoint-stencil
    ``custom_vjp``, so training exercises RACE end to end.

    x: (B, S, C); taps: (radius+1,) — zero taps make this the identity
    residual, so enabling the mixer never perturbs a fresh model.
    """
    B, S, C = x.shape
    R = int(radius)
    P = R + 2  # left pad: deepest reach of box(s + R - R) .. box(s + R) + 2
    res = _smooth_result(S, B * C, R)
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (P, 0), (0, 0)))
    env = {"sx": xp.transpose(1, 0, 2).reshape(S + P, B * C),
           "sy": jnp.zeros((S, B * C), jnp.float32)}
    for d in range(R + 1):
        env[f"sw{d}"] = taps[d].astype(jnp.float32)
    y = res.run(env, backend)["sy"]
    return y.reshape(S, B, C).transpose(1, 0, 2).astype(x.dtype)


# ---------------------------------------------------------------------------
# Mamba-1
# ---------------------------------------------------------------------------


def init_mamba(key, cfg: ModelConfig):
    D, di, N, R, K = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                      cfg.dt_rank, cfg.ssm_conv)
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 7)
    return {
        "in_proj": dense_init(ks[0], (D, 2 * di), dt),
        "conv_w": dense_init(ks[1], (K, di), dt, scale=3.0),
        "x_proj": dense_init(ks[2], (di, R + 2 * N), dt),
        "dt_proj": dense_init(ks[3], (R, di), dt),
        "dt_bias": jnp.full((di,), -4.0, jnp.float32),  # softplus ~ 0.018
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, N + 1, dtype=jnp.float32), (di, N)).copy()),
        "D_skip": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(ks[6], (di, D), dt),
    }


def _mamba_core(xz, p, cfg: ModelConfig, conv_state, h0, exec_cfg):
    """Shared train/decode core.  xz: (B, S, 2*di).  The (B, C, di, N)
    discretization lives only inside the chunk body.

    §Perf (EXPERIMENTS.md, falcon train cell): every (B, S, di)-sized
    intermediate is pinned to the same (batch, -, 'model') layout so XLA
    never round-trips them through all-gathers between the projections —
    only in/out projections communicate."""
    di, N = cfg.d_inner, cfg.ssm_state
    B, S, _ = xz.shape

    def pin(t):  # (B, S, di-like) tensors stay di-sharded on 'model'
        if not getattr(exec_cfg, "ssm_pin", True):
            return t
        return exec_cfg.constrain(t, exec_cfg.batch_axes(), None, "model")

    xz = pin(xz)
    xin, z = jnp.split(xz, 2, axis=-1)
    xc, new_conv = _causal_conv1d(xin, p["conv_w"], conv_state)
    xc = pin(jax.nn.silu(xc))
    proj = xc @ p["x_proj"]
    dt_low, Bm, Cm = jnp.split(proj, [cfg.dt_rank, cfg.dt_rank + N], axis=-1)
    dt = pin(jax.nn.softplus(
        (dt_low @ p["dt_proj"]).astype(jnp.float32) + p["dt_bias"]))
    A = -jnp.exp(p["A_log"])  # (di, N)

    def chunk_fn(h, dt_c, Bm_c, Cm_c, x_c):
        dA = jnp.exp(dt_c[..., None] * A)                       # (B,C,di,N)
        dBx = (dt_c * x_c)[..., None] * Bm_c[..., None, :].astype(jnp.float32)
        hs = _assoc(dA, dBx, h)
        y = jnp.einsum("bsdn,bsn->bsd", hs, Cm_c.astype(jnp.float32))
        return y, hs[:, -1]

    y, h_last = _scan_recurrence(
        h0, chunk_fn, (dt, Bm, Cm, xc.astype(jnp.float32)), exec_cfg, S)
    if getattr(exec_cfg, "ssm_bf16", False):
        # §Perf B2: the post-scan gating chain (and hence its gradient
        # all-reduces, the cell's dominant collective) runs in bf16; the
        # recurrence itself stays f32 inside the chunks
        y = pin((y.astype(xz.dtype) + (p["D_skip"].astype(xz.dtype) * xc)))
        y = y * jax.nn.silu(z)
    else:
        y = pin(y + p["D_skip"] * xc.astype(jnp.float32))
        y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(xz.dtype)
    return y @ p["out_proj"], new_conv, h_last


def mamba_block(x, p, cfg: ModelConfig, exec_cfg: ExecConfig):
    B = x.shape[0]
    h0 = jnp.zeros((B, cfg.d_inner, cfg.ssm_state), jnp.float32)
    y, _, _ = _mamba_core(x @ p["in_proj"], p, cfg, None, h0, exec_cfg)
    return y


def mamba_decode(x, p, cfg: ModelConfig, cache: dict, exec_cfg: ExecConfig):
    """x: (B, 1, D); cache: {'conv': (B, K-1, di), 'h': (B, di, N)}."""
    y, new_conv, h_last = _mamba_core(
        x @ p["in_proj"], p, cfg, cache["conv"], cache["h"], exec_cfg)
    return y, {"conv": new_conv, "h": h_last}


def init_mamba_cache(cfg: ModelConfig, batch: int):
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, cfg.d_inner), jnp.dtype(cfg.dtype)),
        "h": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32),
    }


# ---------------------------------------------------------------------------
# RG-LRU (Griffin / RecurrentGemma recurrent block)
# ---------------------------------------------------------------------------

_LRU_C = 8.0


def init_rglru(key, cfg: ModelConfig):
    D = cfg.d_model
    W = cfg.lru_width or D
    K = cfg.ssm_conv
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    return {
        "in_proj": dense_init(ks[0], (D, 2 * W), dt),   # x branch + gate branch
        "conv_w": dense_init(ks[1], (K, W), dt, scale=3.0),
        "w_input_gate": dense_init(ks[2], (W, W), dt),
        "w_rec_gate": dense_init(ks[3], (W, W), dt),
        "lambda_p": jnp.full((W,), 2.0, jnp.float32),  # a ~ exp(-8*sig(r)*softplus)
        "out_proj": dense_init(ks[5], (W, D), dt),
    }


def _rglru_core(x2, p, cfg: ModelConfig, conv_state, h0, exec_cfg):
    B, S, _ = x2.shape
    x_br, gate_br = jnp.split(x2, 2, axis=-1)
    xc, new_conv = _causal_conv1d(x_br, p["conv_w"], conv_state)
    i_t = jax.nn.sigmoid((xc @ p["w_input_gate"]).astype(jnp.float32))
    r_t = jax.nn.sigmoid((xc @ p["w_rec_gate"]).astype(jnp.float32))
    log_a = -_LRU_C * r_t * jax.nn.softplus(p["lambda_p"])
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6)) \
        * (i_t * xc.astype(jnp.float32))

    def chunk_fn(h, a_c, b_c):
        hs = _assoc(a_c, b_c, h)
        return hs, hs[:, -1]

    h, h_last = _scan_recurrence(h0, chunk_fn, (a, b), exec_cfg, S)
    y = (h * jax.nn.gelu(gate_br.astype(jnp.float32))).astype(x2.dtype)
    return y @ p["out_proj"], new_conv, h_last


def rglru_block(x, p, cfg: ModelConfig, exec_cfg: ExecConfig):
    B = x.shape[0]
    W = cfg.lru_width or cfg.d_model
    h0 = jnp.zeros((B, W), jnp.float32)
    y, _, _ = _rglru_core(x @ p["in_proj"], p, cfg, None, h0, exec_cfg)
    return y


def rglru_decode(x, p, cfg: ModelConfig, cache: dict, exec_cfg: ExecConfig):
    y, new_conv, h_last = _rglru_core(
        x @ p["in_proj"], p, cfg, cache["conv"], cache["h"], exec_cfg)
    return y, {"conv": new_conv, "h": h_last}


def init_rglru_cache(cfg: ModelConfig, batch: int):
    W = cfg.lru_width or cfg.d_model
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, W), jnp.dtype(cfg.dtype)),
        "h": jnp.zeros((batch, W), jnp.float32),
    }
