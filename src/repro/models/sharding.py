"""Sharding rules: param-name-based PartitionSpecs with divisibility guards.

Layout (DESIGN.md section 6): 'data' (plus 'pod' when present) is the FSDP
axis — parameters, gradients and optimizer state are sharded over it; 'model'
carries tensor parallelism (attention projections / FFN / expert FFN slices /
vocab) and the sequence dimension of decode KV caches (flash-decoding-style
split-K, which is how a 32k-KV decode fits and parallelizes).

Every rule passes through ``_fit``: a dimension only gets mesh axes whose
total size divides it (jit input shardings must divide evenly; e.g. granite's
vocab 49155 falls back to replicated on that dim while its d_model shards).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.shard.partition import divides

from .config import ModelConfig


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def fsdp_axes(mesh) -> tuple:
    # FSDP shards params over the data axes; 'model' already shards via TP
    return batch_axes(mesh)


def _fit(mesh, shape, spec) -> P:
    """Drop axes from dims they don't divide."""
    out = []
    for dim, axes in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axes is not None and divides(mesh, dim, axes):
            out.append(axes)
        else:
            out.append(None)
    return P(*out)


# suffix-match rules: (names, spec builder); 'F' = fsdp, 'T' = model/tensor
_COL = {"wq", "wk", "wv", "w_gate", "w_up", "in_proj", "x_proj",
        "w_input_gate", "w_rec_gate", "head", "embed"}
_ROW = {"wo", "w_down", "out_proj", "dt_proj"}


def param_spec(path: tuple, shape: tuple, mesh, cfg: ModelConfig) -> P:
    name = str(path[-1])
    F, T = fsdp_axes(mesh), "model"
    ndim = len(shape)
    lead = ndim - 2  # scan-stacked L and/or expert E leading axes

    def with_lead(*tail):
        return P(*([None] * lead), *tail)

    if name == "embed":
        return _fit(mesh, shape, P(T, F))
    if name == "head":
        return _fit(mesh, shape, P(F, T))
    if name == "router":
        return _fit(mesh, shape, with_lead(F, None))
    if name in _COL and ndim >= 2:
        return _fit(mesh, shape, with_lead(F, T))
    if name in _ROW and ndim >= 2:
        return _fit(mesh, shape, with_lead(T, F))
    if name == "conv_w":
        return _fit(mesh, shape, P(*([None] * (ndim - 1)), T))
    if name in ("A_log", "D_skip", "dt_bias", "lambda_p"):
        return _fit(mesh, shape, P(*([None] * (ndim - 2) if ndim >= 2 else []),
                                   T, *([None] if ndim >= 2 else [])))
    # norms, biases, scalars: replicated
    return P(*([None] * ndim))


def params_shardings(params, mesh, cfg: ModelConfig):
    def spec(path, leaf):
        keys = tuple(
            k.key if hasattr(k, "key") else (k.idx if hasattr(k, "idx") else k)
            for k in path
        )
        return NamedSharding(mesh, param_spec(keys, leaf.shape, mesh, cfg))

    return jax.tree_util.tree_map_with_path(spec, params)


def batch_shardings(batch_tree, mesh, cfg: ModelConfig):
    """tokens/labels (B, S); embeds (B, S, E); vision (B, T, Dv)."""
    B_ax = batch_axes(mesh)

    def spec(leaf):
        sp = [B_ax] + [None] * (leaf.ndim - 1)
        return NamedSharding(mesh, _fit(mesh, leaf.shape, P(*sp)))

    return jax.tree.map(spec, batch_tree)


def cache_shardings(cache_tree, mesh, cfg: ModelConfig):
    """Decode-state shardings, keyed by leaf name (leaves may carry a stacked
    leading L axis):
      k/v   ([L], B, S, KV, dh): batch on data axes, cache *sequence* on
            'model' — flash-decoding-style split-K; how 32k-KV decode both
            fits and parallelizes;
      conv  ([L], B, K-1, C):    channels on 'model';
      h     ([L], B, di, N) or ([L], B, W): state width on 'model'."""
    B_ax = batch_axes(mesh)

    def spec(path, leaf):
        name = next(
            (k.key for k in reversed(path) if hasattr(k, "key")), "")
        nd = leaf.ndim
        if name in ("k", "v"):
            sp = [None] * (nd - 4) + [B_ax, "model", None, None]
        elif name in ("k_scale", "v_scale"):
            sp = [None] * (nd - 3) + [B_ax, "model", None]
        elif name == "conv":
            sp = [None] * (nd - 3) + [B_ax, None, "model"]
        elif name == "h":
            if leaf.shape[-1] <= 64 and nd >= 3:  # mamba (B, di, N)
                sp = [None] * (nd - 3) + [B_ax, "model", None]
            else:  # rg-lru (B, W)
                sp = [None] * (nd - 2) + [B_ax, "model"]
        else:
            sp = [None] * nd
        return NamedSharding(mesh, _fit(mesh, leaf.shape, P(*sp)))

    return jax.tree_util.tree_map_with_path(spec, cache_tree)


def replicated(mesh):
    return NamedSharding(mesh, P())


def activation_spec(mesh):
    return NamedSharding(mesh, P(batch_axes(mesh), None, None))
