"""Fused block-sparse FlashAttention Pallas TPU kernel.

This is the paper's §5 future-work item (3) realized: SDDMM (scores only
at nonzero mask blocks), softmax, and SpMM (scores x V) fused into a
single VMEM pass, so the sampled score matrix never round-trips HBM.

Block sparsity is carried exactly like the SpMM kernel's SELLPACK-like
format: each q block-row has a fixed-width (ELL) list of kv block ids,
padded with invalid slots — uniform streams, static grid.  Within a
block, the causal/window predicate is evaluated from absolute positions,
so diagonal (partially masked) blocks need no special casing.

Grid: (BH, n_q_blocks, W)   [W innermost => online-softmax accumulation]
  q:   [BH, S, D]    -> tile (1, bq, D)  at (bh, qi, 0)
  k/v: [BHkv, S, D]  -> tile (1, bk, D)  at (bh // group, idx[qi, w], 0)
  out: [BH, S, D]    -> tile (1, bq, D)  at (bh, qi, 0), revisited over W
Scratch: acc [bq, D] f32, m/l [bq] f32 (flash statistics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _bsattn_kernel(idx_ref, valid_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   n_slots: int, block_q: int, block_kv: int, scale: float,
                   causal: bool, window: int):
    qi = pl.program_id(1)
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ki = idx_ref[qi, w]
    is_valid = valid_ref[qi, w] > 0

    q_blk = q_ref[0, :, :]
    k_blk = k_ref[0, :, :]
    s = jax.lax.dot_general(
        q_blk, k_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [bq, bk]

    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    kpos = ki * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    mask = jnp.full((block_q, block_kv), is_valid)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0, :, :],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(w == n_slots - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_kv", "causal", "window", "scale",
                     "interpret"),
)
def bsattn_kernel(
    ell_idx,  # int32[nq, W] kv block ids
    valid,  # int32[nq, W] 1 = real slot, 0 = padding
    q,  # [BH, S, D]
    k,  # [BHkv, S, D]
    v,  # [BHkv, S, D]
    *,
    block_q: int = 512,
    block_kv: int = 512,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    interpret: bool = False,
):
    bh, s, d = q.shape
    bkv = k.shape[0]
    group = bh // bkv
    nq, n_slots = ell_idx.shape
    assert s % block_q == 0 and s % block_kv == 0
    assert nq == s // block_q
    scale = scale if scale is not None else float(1.0 / np.sqrt(d))

    grid = (bh, nq, n_slots)
    kernel = functools.partial(
        _bsattn_kernel, n_slots=n_slots, block_q=block_q,
        block_kv=block_kv, scale=scale, causal=causal, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda bh_, qi, w, idx, val: (bh_, qi, 0)),
                pl.BlockSpec(
                    (1, block_kv, d),
                    lambda bh_, qi, w, idx, val, g=group:
                    (bh_ // g, idx[qi, w], 0)),
                pl.BlockSpec(
                    (1, block_kv, d),
                    lambda bh_, qi, w, idx, val, g=group:
                    (bh_ // g, idx[qi, w], 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, block_q, d), lambda bh_, qi, w, idx, val: (bh_, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q,), jnp.float32),
                pltpu.VMEM((block_q,), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="block_sparse_flash_attention",
    )(ell_idx, valid, q, k, v)
