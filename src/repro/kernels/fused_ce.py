"""Fused cross-entropy Pallas kernel: logits never touch HBM.

The §Perf cell-A analysis (EXPERIMENTS.md) showed the CE logits are the one
train-step tensor with no reuse — writing (T, V) f32 to HBM and reading it
back for the softmax is pure waste.  This kernel applies the same
VMEM-contraction idea as the RACE stencil executor to the loss: the grid
tiles (token-block x vocab-block); one (T_blk, V_blk) logits tile lives in
VMEM per step, with an online-logsumexp accumulator carried across the vocab
dimension in scratch.  Per-token loss = lse - gold_logit emerges at the last
vocab step; the (B, S, V) logits tensor never exists.

Backward: custom_vjp with an XLA recompute (chunked, checkpointed — the same
math as repro.models.common.chunked_ce_loss), so training can adopt the
kernel without a hand-written bwd kernel; the forward-side HBM saving is the
win this kernel demonstrates.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.lowering import pallas_interpret

NEG = -1e30


def _kernel(h_ref, w_ref, lab_ref, out_ref, m_ref, l_ref, g_ref, *, v_blk):
    iv = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(iv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        g_ref[...] = jnp.zeros_like(g_ref)

    h = h_ref[...]                      # (T_blk, D)
    w = w_ref[...]                      # (D, V_blk)
    logits = jnp.dot(h.astype(jnp.float32), w.astype(jnp.float32),
                     preferred_element_type=jnp.float32)  # VMEM-only tile

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1))
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.exp(
        logits - m_new[:, None]).sum(axis=1)
    m_ref[...] = m_new

    lab = lab_ref[...]                  # (T_blk,)
    cols = iv * v_blk + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    hit = cols == lab[:, None]
    g_ref[...] = g_ref[...] + jnp.where(hit, logits, 0.0).sum(axis=1)

    @pl.when(iv == nv - 1)
    def _fin():
        out_ref[...] = m_ref[...] + jnp.log(
            jnp.maximum(l_ref[...], 1e-30)) - g_ref[...]


def fused_ce_forward(h, w, labels, t_blk: int = 128, v_blk: int = 2048):
    """h: (T, D); w: (D, V); labels: (T,) int32 -> per-token loss (T,) f32.

    The Pallas mode is the platform's
    (:func:`repro.lowering.pallas_interpret`)."""
    T, D = h.shape
    V = w.shape[1]
    t_blk = min(t_blk, T)
    v_blk = min(v_blk, V)
    while T % t_blk:
        t_blk -= 1
    while V % v_blk:
        v_blk -= 1
    grid = (T // t_blk, V // v_blk)
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        partial(_kernel, v_blk=v_blk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((t_blk, D), lambda t, v: (t, 0)),
            pl.BlockSpec((D, v_blk), lambda t, v: (0, v)),
            pl.BlockSpec((t_blk,), lambda t, v: (t,)),
        ],
        out_specs=pl.BlockSpec((t_blk,), lambda t, v: (t,)),
        out_shape=jax.ShapeDtypeStruct((T,), jnp.float32),
        # running max / sum / gold-logit accumulators, persistent across the
        # vocab grid dimension (VMEM scratch)
        scratch_shapes=[
            pltpu.VMEM((t_blk,), jnp.float32),
            pltpu.VMEM((t_blk,), jnp.float32),
            pltpu.VMEM((t_blk,), jnp.float32),
        ],
        interpret=pallas_interpret(),
    )(h, w, labels)


@jax.custom_vjp
def fused_ce(h, w, labels):
    """Mean CE loss with the fused forward; backward recomputes via XLA."""
    return fused_ce_forward(h, w, labels).mean()


def _ce_ref(h, w, labels):
    logits = jnp.dot(h.astype(jnp.float32), w.astype(jnp.float32))
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return (lse - gold).mean()


def _fwd(h, w, labels):
    return fused_ce(h, w, labels), (h, w, labels)


def _bwd(res, g):
    h, w, labels = res
    dh, dw = jax.grad(_ce_ref, argnums=(0, 1))(h, w, labels)
    return jax.tree.map(lambda t: (t * g).astype(t.dtype), (dh, dw)) + (None,)


fused_ce.defvjp(_fwd, _bwd)
