"""Parameter / cache / batch PartitionSpec policies.

FSDP+TP ("2D") scheme in MaxText style:
  * every weight matrix shards its input-ish dim over `data` (FSDP) and its
    output-ish dim over `model` (TP); optimizer moments inherit => ZeRO.
  * experts shard over `model` (EP), their inner dims over `data`.
  * the `pod` axis is pure DP/2.5D-replication: parameters are replicated
    across pods, gradients cross pods once per step.

Axes are applied only when they divide the dim (``_fit``): vocab sizes like
51865 or 92553 simply fall back to replication for that dim instead of
relying on XLA's uneven-sharding padding — keeps memory accounting exact.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis in auto (compiler-chosen) mode."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         (jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


# key -> (logical spec per trailing dims of the UNSTACKED param)
_PARAM_RULES = {
    # projections [in, out]
    "wq": ("fsdp", "tensor"), "wk": ("fsdp", "tensor"),
    "wv": ("fsdp", "tensor"), "wi": ("fsdp", "tensor"),
    "wg": ("fsdp", "tensor"), "wx": ("fsdp", "tensor"),
    "in_proj": ("fsdp", "tensor"),
    "vision_proj": ("fsdp", "tensor"),
    "lm_head": ("fsdp", "tensor"),
    "router": ("fsdp", None),
    # output projections [in, d]
    "wo": ("tensor", "fsdp"), "out": ("tensor", "fsdp"),
    "out_proj": ("tensor", "fsdp"),
    # embedding [V, d]
    "embed": ("tensor", "fsdp"),
    # experts
    "w_in": ("expert", "fsdp", None), "w_gate": ("expert", "fsdp", None),
    "w_out": ("expert", None, "fsdp"),
    # biases / vectors
    "bq": ("tensor",), "bk": ("tensor",), "bv": ("tensor",),
    "conv_w": (None, "tensor"), "conv_b": ("tensor",),
    "A_log": ("tensor",), "D": ("tensor",), "dt_bias": ("tensor",),
    "lam": ("tensor",), "ga_b": ("tensor",), "gi_b": ("tensor",),
    "ga_w": ("tensor", None), "gi_w": ("tensor", None),
    "norm": ("tensor",),
    # norms (replicated)
    "ln1": (None,), "ln2": (None,), "lnx": (None,), "final_ln": (None,),
    "ba": ("tensor",), "bi": ("tensor",),
    "wa": ("tensor", None),
}

_LOGICAL = {
    "fsdp": ("data",),
    "tensor": ("model",),
    "expert": ("model",),
    "dp": ("pod", "data"),
}


def _fit(dim: int, axes: Optional[Tuple[str, ...]], mesh: Mesh):
    """Return axes (possibly trimmed) only if their product divides dim."""
    if axes is None:
        return None
    names = [a for a in axes if a in mesh.axis_names]
    while names:
        prod = math.prod(mesh.shape[a] for a in names)
        if dim % prod == 0:
            return tuple(names) if len(names) > 1 else names[0]
        names = names[:-1]
    return None


def _resolve(logical: Optional[str], mesh: Mesh):
    if logical is None:
        return None
    return _LOGICAL.get(logical, (logical,))


def param_spec(path, leaf, mesh: Mesh) -> P:
    keys = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
    key = keys[-1] if keys else None
    rule = _PARAM_RULES.get(key)
    if rule is None:
        return P()
    # ZeRO-1 across pods: optimizer moments (under opt/m, opt/v) addition-
    # ally shard their fsdp dim over `pod` — parameters stay pod-replicated
    # (cheap to read every step), moments are touched once per step so the
    # cross-pod gather/scatter is amortizable.  Needed for 400B-class
    # models whose f32 moments alone exceed a pod's HBM.
    zero1 = any(k in ("m", "v") for k in keys[:-1]) or key in ("m", "v")
    fsdp_axes = ("pod", "data") if zero1 else ("data",)
    ndim = getattr(leaf, "ndim", len(leaf.shape))
    shape = leaf.shape
    pad = ndim - len(rule)
    entries = [None] * pad
    for i, logical in enumerate(rule):
        axes = _resolve(logical, mesh)
        if logical == "fsdp":
            axes = fsdp_axes
        entries.append(_fit(shape[pad + i], axes, mesh))
    return P(*entries)


def param_sharding_tree(params, mesh: Mesh):
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(
        treedef,
        [NamedSharding(mesh, param_spec(path, leaf, mesh))
         for path, leaf in flat])


# ---------------------------------------------------------------------------
# Batch / cache shardings per shape kind
# ---------------------------------------------------------------------------


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def kv_seq_axes(mesh: Mesh, shape: ShapeConfig) -> Optional[Tuple[str, ...]]:
    if shape.name == "long_500k":
        # batch=1: spread the 500k cache over every axis available
        return tuple(mesh.axis_names)
    return ("model",)


def batch_spec(mesh: Mesh, ndim: int, *, batch_divisible=True) -> P:
    ax = batch_axes(mesh)
    first = ax if batch_divisible else None
    return P(first, *([None] * (ndim - 1)))


def data_sharding_tree(batch, mesh: Mesh, global_batch: int):
    ax = batch_axes(mesh)
    n = math.prod(mesh.shape[a] for a in ax)
    ok = global_batch % n == 0 and global_batch >= n

    def spec(leaf):
        nd = getattr(leaf, "ndim", len(leaf.shape))
        return NamedSharding(mesh, batch_spec(mesh, nd, batch_divisible=ok))

    return jax.tree_util.tree_map(spec, batch)


def cache_spec(path, leaf, mesh: Mesh, cfg: ModelConfig,
               shape: ShapeConfig) -> P:
    """Sharding for KV / state caches (decode cells)."""
    key = None
    for p in reversed(path):
        if isinstance(p, jax.tree_util.DictKey):
            key = p.key
            break
    nd = getattr(leaf, "ndim", len(leaf.shape))
    bax = batch_axes(mesh)
    nb = math.prod(mesh.shape[a] for a in bax)
    b_ok = shape.global_batch % nb == 0 and shape.global_batch >= nb
    b_entry = bax if b_ok else None
    stacked = nd >= 1 and any(
        isinstance(p, jax.tree_util.DictKey) and p.key == "periods"
        for p in path)
    pad = (None,) if stacked else ()

    kvax = kv_seq_axes(mesh, shape)

    if key in ("k", "v"):  # [B, S, hkv, hd]
        s_dim = leaf.shape[-3]
        return P(*pad, b_entry, _fit(s_dim, kvax, mesh), None, None)
    if key == "kpos":  # [B, S]
        s_dim = leaf.shape[-1]
        return P(*pad, b_entry, _fit(s_dim, kvax, mesh))
    if key in ("enc_k", "enc_v"):  # [B, Se, hkv, hd]
        return P(*pad, b_entry, None, None, None)
    if key == "state":  # [B, nh, hd, ds]
        return P(*pad, b_entry, _fit(leaf.shape[-3], ("model",), mesh),
                 None, None)
    if key == "conv":  # [B, cw-1, C]
        return P(*pad, b_entry, None,
                 _fit(leaf.shape[-1], ("model",), mesh))
    if key == "h":  # [B, w]
        return P(*pad, b_entry, _fit(leaf.shape[-1], ("model",), mesh))
    if key == "pos":
        return P()
    return P()


def cache_sharding_tree(cache, mesh: Mesh, cfg: ModelConfig,
                        shape: ShapeConfig):
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    return jax.tree_util.tree_unflatten(
        treedef,
        [NamedSharding(mesh, cache_spec(path, leaf, mesh, cfg, shape))
         for path, leaf in flat])
