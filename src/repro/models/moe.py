"""Mixture-of-Experts FFN with sort-based capacity dispatch.

DESIGN.md §4: top-1 routing *is* the paper's SpMM with a one-nonzero-per-
row dispatch matrix A (tokens x expert-slots) — the hyper-sparse regime
where the paper measures the CS-3 losing to CPU because data movement
dominates useful FLOPs.  The communication-optimal realization of that
SpMM on a TPU mesh is therefore NOT a masked dense matmul (which would
stream the full zero-padded A, the paper's Fig. 8 worst case) but a
sort-based dispatch: group tokens by expert (the sort plays the role of
the paper's router re-bucketing), truncate to capacity, and run one
batched matmul per local expert.

Expert parallelism: experts shard over `model`; activations entering the
block are replicated across the TP group (the Megatron-SP gather point),
so each model-rank locally selects the tokens routed to ITS experts and
the partial outputs fold with the same psum a TP FFN needs — dispatch
costs zero extra collectives.  Crucially the dispatch sort/scatter runs
*inside shard_map*, per device: a global (pjit-level) sort of the token
stream would lower to a cross-chip sort network — measured at 269s of
collective time for llama4-scout train_4k before this restructure
(EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import _he, activation, init_mlp, mlp
from repro.sharding import ctx as shard_ctx


def init_moe(key, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": _he(ks[0], (d, e)),
        "w_in": _he(ks[1], (e, d, f), scale_dim=d),
        "w_out": _he(ks[2], (e, f, d), scale_dim=f),
    }
    if cfg.gated_mlp:
        p["w_gate"] = _he(ks[3], (e, d, f), scale_dim=d)
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, f=cfg.d_ff * cfg.n_shared_experts)
    return p


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    cap = int(np.ceil(n_tokens * cfg.top_k / cfg.n_experts
                      * cfg.capacity_factor))
    return max(8, -(-cap // 8) * 8)  # round up to 8 for layout friendliness


def _dispatch_compute(p_router, w_in, w_gate, w_out, xf, cfg: ModelConfig,
                      e_offset, e_local: int, cap: int):
    """Local sort-based dispatch over xf [T,d] for experts
    [e_offset, e_offset + e_local).  Returns (y [T,d] f32, aux scalar)."""
    t, d = xf.shape
    e = cfg.n_experts
    router_logits = (xf.astype(jnp.float32)
                     @ p_router.astype(jnp.float32))  # [T, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_all, eid_all = jax.lax.top_k(probs, cfg.top_k)
    one_hot = jax.nn.one_hot(eid_all[:, 0], e, dtype=jnp.float32)
    aux = e * e * jnp.mean(one_hot.mean(0) * probs.mean(0))

    y = jnp.zeros((t, d), jnp.float32)
    for slot in range(cfg.top_k):
        eid = eid_all[:, slot] - e_offset  # local expert id (may be OOR)
        gate = gate_all[:, slot]
        mine = (eid >= 0) & (eid < e_local)
        eid_c = jnp.where(mine, eid, e_local)  # OOR -> overflow bin
        # --- local sort-based grouping (the paper's router re-bucketing) --
        order = jnp.argsort(eid_c * t + jnp.arange(t))
        eid_s = eid_c[order]
        counts = jnp.bincount(eid_c, length=e_local + 1)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(t) - starts[eid_s]
        keep = (eid_s < e_local) & (rank < cap)
        dest = jnp.where(keep, eid_s * cap + rank, e_local * cap)
        buf = jnp.zeros((e_local * cap + 1, d), xf.dtype)
        buf = buf.at[dest].set(xf[order])
        buf = buf[: e_local * cap].reshape(e_local, cap, d)
        # --- expert compute (batched over local experts) -------------------
        h = jnp.einsum("ecd,edf->ecf", buf, w_in.astype(buf.dtype))
        h = activation(h, cfg.act)
        if w_gate is not None:
            h = h * jnp.einsum("ecd,edf->ecf", buf,
                               w_gate.astype(buf.dtype))
        out = jnp.einsum("ecf,efd->ecd", h, w_out.astype(buf.dtype))
        # --- combine (inverse scatter, gate-weighted) -----------------------
        flat = out.reshape(e_local * cap, d)
        src = jnp.where(keep, eid_s * cap + rank, 0)
        ys = jnp.where(keep[:, None], flat[src], 0).astype(jnp.float32)
        inv = jnp.zeros((t,), jnp.int32).at[order].set(
            jnp.arange(t, dtype=jnp.int32))
        y = y + (ys * gate[order][:, None])[inv]
    return y, aux


def moe_ffn(p, x, cfg: ModelConfig):
    """x: [B, S, d] -> ([B, S, d], aux load-balance loss)."""
    b, s, d = x.shape
    mesh = shard_ctx.current_mesh()
    ep_ok = (mesh is not None and "model" in mesh.axis_names
             and cfg.n_experts % mesh.shape["model"] == 0)

    if not ep_ok:
        xf = x.reshape(-1, d)
        cap = _capacity(xf.shape[0], cfg)
        y, aux = _dispatch_compute(
            p["router"], p["w_in"], p.get("w_gate"), p["w_out"], xf, cfg,
            e_offset=jnp.zeros((), jnp.int32), e_local=cfg.n_experts,
            cap=cap)
    else:
        tp = mesh.shape["model"]
        e_local = cfg.n_experts // tp
        batch_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        nb = int(np.prod([mesh.shape[a] for a in batch_ax])) if batch_ax \
            else 1
        b_ok = b % nb == 0 and b >= nb
        bspec = batch_ax if b_ok else None
        t_local = (b // nb if b_ok else b) * s
        cap = _capacity(t_local, cfg)

        has_gate = "w_gate" in p
        # experts stacked on a leading grouped axis for the model shards
        ws = [p["w_in"].reshape(tp, e_local, d, cfg.d_ff),
              p["w_out"].reshape(tp, e_local, cfg.d_ff, d)]
        if has_gate:
            ws.append(p["w_gate"].reshape(tp, e_local, d, cfg.d_ff))

        def local_fn(router, x_local, *ws_local):
            w_in = ws_local[0][0]
            w_out = ws_local[1][0]
            w_gate = ws_local[2][0] if has_gate else None
            rank = jax.lax.axis_index("model")
            xf = x_local.reshape(-1, d)
            yl, aux = _dispatch_compute(
                router, w_in, w_gate, w_out, xf, cfg,
                e_offset=rank * e_local, e_local=e_local, cap=cap)
            # fold partial expert outputs.  bf16 on the wire is ~lossless
            # here: with top-1 routing each token has exactly ONE nonzero
            # contribution across ranks, so the sum incurs a single
            # rounding — and halves the EP psum bytes (§Perf P3).
            # NB: the result must STAY bf16 downstream — an immediate
            # f32 upcast lets XLA's simplifier elide the convert pair and
            # run the all-reduce in f32 (P3 first attempt, refuted).
            yl = jax.lax.psum(yl.astype(x_local.dtype), "model")
            aux = jax.lax.pmean(aux, mesh.axis_names)
            return yl.reshape(x_local.shape[0], s, d), aux

        fn = jax.shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(P(), P(bspec, None, None))
            + tuple(P("model") for _ in ws),
            out_specs=(P(bspec, None, None), P()),
            check_vma=False,
        )
        y3, aux = fn(p["router"], x, *ws)
        y = y3.reshape(-1, d)

    y = y.astype(x.dtype)  # (already x.dtype on the EP path — stays bf16)
    if "shared" in p:
        y = y + mlp(p["shared"], x.reshape(-1, d), cfg)
    return y.reshape(b, s, d), aux
