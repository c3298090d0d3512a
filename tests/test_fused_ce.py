"""Fused cross-entropy Pallas kernel vs the dense oracle (interpret mode):
shape sweeps, non-dividing blocks, gradients through the custom VJP."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models.fused_ce import _ce_ref, fused_ce, fused_ce_forward


def _data(T, D, V, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((T, D)), dtype)
    w = jnp.asarray(rng.standard_normal((D, V)) * 0.05, dtype)
    labels = jnp.asarray(rng.integers(0, V, (T,)), jnp.int32)
    return h, w, labels


@pytest.mark.parametrize("T,D,V,tb,vb", [
    (64, 32, 256, 16, 64),
    (32, 16, 100, 8, 25),      # non-power-of-two vocab blocks
    (48, 64, 512, 48, 512),    # single tile
    (128, 8, 64, 32, 16),
])
def test_fused_ce_matches_dense(T, D, V, tb, vb):
    h, w, labels = _data(T, D, V)
    got = fused_ce_forward(h, w, labels, t_blk=tb, v_blk=vb)
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(lse - gold),
                               rtol=1e-5, atol=1e-5)


def test_fused_ce_bf16_inputs():
    h, w, labels = _data(64, 32, 256, seed=1, dtype=jnp.bfloat16)
    got = fused_ce_forward(h, w, labels, t_blk=16, v_blk=64)
    want = _ce_ref(h, w, labels)
    np.testing.assert_allclose(float(np.asarray(got).mean()), float(want),
                               rtol=2e-2)


def test_fused_ce_grads():
    h, w, labels = _data(32, 16, 128, seed=2)
    g1 = jax.grad(lambda h, w: fused_ce(h, w, labels))(h, w)
    g2 = jax.grad(lambda h, w: _ce_ref(h, w, labels))(h, w)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
