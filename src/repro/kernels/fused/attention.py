"""One-pass fused graph attention: SDDMM → edge act → softmax → SpMM.

The unfused GAT layer runs three dispatches and materializes the
E-length edge-score vector twice (scores, then attention weights).  The
kernels here stream each live tile of the topology exactly once and keep
the softmax statistics (running row max ``m`` and exp-sum ``l``) plus
the output accumulator resident in VMEM — the edge scores never exist in
HBM at all:

  sweep over a row's live tiles:
      s   = act(q_tile @ kT_tile)          # SDDMM piece, in-register
      m'  = max(m, rowmax(s));  scale = exp(m - m')
      l   = l * scale + rowsum(exp(s - m'))
      acc = acc * scale + exp(s - m') @ V_tile
  flush: out = acc / max(l, eps)

This is the max/sum two-sweep online softmax in streaming form: the
first "sweep" (the running max) and the second (exp-sum + weighted
accumulation) advance together, with the ``scale`` factor retroactively
correcting earlier tiles — algebraically identical to two passes over
the row, matching ``models.gnn._segment_softmax`` to float tolerance.

The kernels take the key factor N-major (``k``: [N, dk]) and contract
it on its minor axis, so a score tile reads a (bn, dk) slab: a (dk, bn)
tile of kᵀ would put bn = 64 on the minor axis, which the TPU lowering
refuses.  The wrappers keep the [dk, N] ``kt`` interface of the
references and transpose once.

Layouts:
  * Block-ELL — grid (nbr, W), W innermost; the structural mask comes
    from A's blocks (padding slots are all-zero and mask out).
  * SELL-C-σ — grid (T,) over live tiles, flush on row change; q is
    pre-gathered into packed row order, the epilogue gather un-permutes
    and re-inserts pruned (edge-less => zero) rows.
  * csr / dense — jnp reference compositions (element paths are
    E-granular by construction; they are the oracle, not the fused
    target).

Every layout's jnp reference here IS the two-sweep (explicit max pass,
then exp/sum/accumulate pass) so kernel-vs-reference parity also pins
the online-rescaling algebra.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import BlockELL, SellCS
from repro.kernels.fused.epilogue import apply_act

NEG_INF = -1e30   # finite: masked - masked stays nan-free
EPS = 1e-12       # the _segment_softmax denominator guard


# ---------------------------------------------------------------------------
# Block-ELL fused attention
# ---------------------------------------------------------------------------


def _ell_attn_kernel(idx_ref, a_ref, q_ref, k_ref, v_ref, o_ref,
                     acc_ref, m_ref, l_ref, *, n_slots: int, act: str,
                     slope: float):
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    mask = a_ref[0, 0, :, :] != 0
    s = jax.lax.dot_general(
        q_ref[...], k_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bm, bn]
    s = jnp.where(mask, apply_act(s, act, slope), NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    scale = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    l_ref[...] = l_ref[...] * scale + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * scale[:, None] + jax.lax.dot_general(
        p, v_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(w == n_slots - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], EPS)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("act", "slope", "out_dtype", "interpret")
)
@jax.named_scope("sparse.kernel.fused_graph_attention_blockell")
def fused_attn_blockell_kernel(
    indices,  # int32[nbr, W]
    blocks,  # dtype[nbr, W, bm, bn]  structural mask source
    q,  # dtype[nbr*bm, dk]
    k,  # dtype[Np, dk]
    v,  # dtype[Np, D]
    *,
    act: str = "leaky_relu",
    slope: float = 0.2,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    nbr, w, bm, bn = blocks.shape
    mp, dk = q.shape
    n, d = v.shape
    assert mp == nbr * bm, (mp, nbr, bm)
    assert n % bn == 0, (n, bn)

    grid = (nbr, w)
    kernel = functools.partial(_ell_attn_kernel, n_slots=w, act=act,
                               slope=slope)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bm, bn),
                             lambda i, s, idx: (i, s, 0, 0)),
                pl.BlockSpec((bm, dk), lambda i, s, idx: (i, 0)),
                pl.BlockSpec((bn, dk), lambda i, s, idx: (idx[i, s], 0)),
                pl.BlockSpec((bn, d), lambda i, s, idx: (idx[i, s], 0)),
            ],
            out_specs=pl.BlockSpec((bm, d), lambda i, s, idx: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bm, d), jnp.float32),
                pltpu.VMEM((bm,), jnp.float32),
                pltpu.VMEM((bm,), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((mp, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="fused_graph_attention_blockell",
    )(indices, blocks, q, k, v)


@jax.named_scope("sparse.xla.fused_attn_blockell_ref")
def fused_attn_blockell_ref(ell: BlockELL, q, kt, v, *,
                            act: str = "leaky_relu", slope: float = 0.2,
                            out_dtype=jnp.float32):
    """Blocked two-sweep jnp oracle (sweep 1: row max; sweep 2: exp/sum
    + accumulate).  Works tile-granularly — the only intermediates are
    blocked [nbr, W, bm, bn] score tiles, never an E-length vector."""
    nbr, w = ell.indices.shape
    bm, bn = ell.bm, ell.bn
    mp, np_ = ell.shape
    dk = q.shape[1]
    d = v.shape[1]
    qb = q.reshape(nbr, bm, dk).astype(jnp.float32)
    ktb = kt.reshape(dk, np_ // bn, bn).transpose(1, 0, 2)[ell.indices]
    vb = v.reshape(np_ // bn, bn, d)[ell.indices]  # [nbr, W, bn, d]
    s = jnp.einsum("imk,iwkn->iwmn", qb, ktb.astype(jnp.float32))
    mask = ell.blocks != 0
    s = jnp.where(mask, apply_act(s, act, slope), NEG_INF)
    mx = s.max(axis=(1, 3))                      # sweep 1: [nbr, bm]
    p = jnp.where(mask, jnp.exp(s - mx[:, None, :, None]), 0.0)
    den = p.sum(axis=(1, 3))                     # sweep 2 statistics
    y = jnp.einsum("iwmn,iwnd->imd", p, vb.astype(jnp.float32))
    y = y / jnp.maximum(den, EPS)[:, :, None]
    return y.reshape(mp, d).astype(out_dtype)


def fused_attn_blockell(ell: BlockELL, q, kt, v, *,
                        act: str = "leaky_relu", slope: float = 0.2,
                        out_dtype=None, use_kernel: bool = False,
                        interpret: bool = False):
    """Fused attention over a Block-ELL topology (padded output rows).

    ``q``: [M, dk] row scores, ``kt``: [dk, N], ``v``: [N, D] — logical
    shapes; padding to the block grid happens here, the caller trims the
    output to the logical row count.
    """
    from repro.sparse.paths import pad_cols, pad_rows

    out_dtype = out_dtype or jnp.result_type(q.dtype, v.dtype)
    mp, np_ = ell.shape
    q, kt, v = pad_rows(q, mp), pad_cols(kt, np_), pad_rows(v, np_)
    if use_kernel or interpret:
        return fused_attn_blockell_kernel(
            ell.indices, ell.blocks, q, kt.T, v, act=act, slope=slope,
            out_dtype=out_dtype, interpret=interpret)
    return fused_attn_blockell_ref(ell, q, kt, v, act=act, slope=slope,
                                   out_dtype=out_dtype)


@jax.named_scope("sparse.xla.fused_attn_blockcoo_ref")
def fused_attn_blockcoo_ref(coo, q, kt, v, *, act: str = "leaky_relu",
                            slope: float = 0.2, out_dtype=jnp.float32):
    """Blocked two-sweep over Block-COO (the transposed-ELL layout).

    Same algebra as the ELL reference, with segment reductions over the
    block-row coordinate instead of a dense slot axis.  Inputs are
    already padded to the block grid.
    """
    nnzb, bm, bn = coo.blocks.shape
    mp, np_ = coo.shape
    nbr = mp // bm
    dk = q.shape[1]
    d = v.shape[1]
    qb = q.reshape(nbr, bm, dk).astype(jnp.float32)[coo.rows]
    ktb = kt.reshape(dk, np_ // bn, bn).transpose(1, 0, 2)[coo.cols]
    vb = v.reshape(np_ // bn, bn, d)[coo.cols]
    s = jnp.einsum("emk,ekn->emn", qb, ktb.astype(jnp.float32))
    mask = coo.blocks != 0
    s = jnp.where(mask, apply_act(s, act, slope), NEG_INF)
    mx = jax.ops.segment_max(s.max(axis=2), coo.rows,
                             num_segments=nbr)       # sweep 1
    p = jnp.where(mask, jnp.exp(s - mx[coo.rows][:, :, None]), 0.0)
    den = jax.ops.segment_sum(p.sum(axis=2), coo.rows, num_segments=nbr)
    y = jax.ops.segment_sum(
        jnp.einsum("emn,end->emd", p, vb.astype(jnp.float32)),
        coo.rows, num_segments=nbr)                  # sweep 2
    y = y / jnp.maximum(den, EPS)[:, :, None]
    return y.reshape(mp, d).astype(out_dtype)


# ---------------------------------------------------------------------------
# SELL-C-σ fused attention
# ---------------------------------------------------------------------------


def _sell_attn_kernel(rows_ref, cols_ref, mask_ref, q_ref, k_ref, v_ref,
                      o_ref, acc_ref, m_ref, l_ref, *, n_tiles: int,
                      act: str, slope: float):
    t = pl.program_id(0)
    row = rows_ref[t]
    prev = rows_ref[jnp.maximum(t - 1, 0)]
    nxt = rows_ref[jnp.minimum(t + 1, n_tiles - 1)]

    @pl.when((t == 0) | (row != prev))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    mask = mask_ref[0, :, :] != 0
    s = jax.lax.dot_general(
        q_ref[...], k_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s = jnp.where(mask, apply_act(s, act, slope), NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    scale = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    l_ref[...] = l_ref[...] * scale + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * scale[:, None] + jax.lax.dot_general(
        p, v_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when((t == n_tiles - 1) | (row != nxt))
    def _flush():
        l = jnp.maximum(l_ref[...], EPS)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_live_block_rows", "act", "slope", "out_dtype",
                     "interpret"),
)
@jax.named_scope("sparse.kernel.fused_graph_attention_sell")
def fused_attn_sell_kernel(
    tile_rows,  # int32[T]
    tile_cols,  # int32[T]
    mask_blocks,  # dtype[T, bm, bn]  0/1 structural pattern
    q_perm,  # dtype[n_live*bm, dk]  q gathered into packed row order
    k,  # dtype[Np, dk]
    v,  # dtype[Np, D]
    *,
    n_live_block_rows: int,
    act: str = "leaky_relu",
    slope: float = 0.2,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    t_count, bm, bn = mask_blocks.shape
    mp, dk = q_perm.shape
    n, d = v.shape
    assert mp == n_live_block_rows * bm, (mp, n_live_block_rows, bm)
    assert n % bn == 0, (n, bn)

    grid = (t_count,)
    kernel = functools.partial(_sell_attn_kernel, n_tiles=t_count,
                               act=act, slope=slope)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, bn),
                             lambda t, rows, cols: (t, 0, 0)),
                pl.BlockSpec((bm, dk), lambda t, rows, cols: (rows[t], 0)),
                pl.BlockSpec((bn, dk), lambda t, rows, cols: (cols[t], 0)),
                pl.BlockSpec((bn, d), lambda t, rows, cols: (cols[t], 0)),
            ],
            out_specs=pl.BlockSpec(
                (bm, d), lambda t, rows, cols: (rows[t], 0)),
            scratch_shapes=[
                pltpu.VMEM((bm, d), jnp.float32),
                pltpu.VMEM((bm,), jnp.float32),
                pltpu.VMEM((bm,), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((mp, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="fused_graph_attention_sell",
    )(tile_rows, tile_cols, mask_blocks, q_perm, k, v)


def fused_attn_sell(sell: SellCS, q, kt, v, *, act: str = "leaky_relu",
                    slope: float = 0.2, out_dtype=None,
                    use_kernel: bool = False, interpret: bool = False):
    """Fused attention over a SELL-packed topology (logical [M, D] out).

    The kernel walks live tiles only; rows in pruned slices have no
    edges, so their attention output is exactly zero and the epilogue
    gather's appended zero row restores them for free.
    """
    out_dtype = out_dtype or jnp.result_type(q.dtype, v.dtype)
    m, n = sell.shape
    d = v.shape[1]
    if not (use_kernel or interpret):
        return fused_attn_sell_slots_ref(sell, q, kt, v, act=act,
                                         slope=slope, out_dtype=out_dtype)
    if sell.n_tiles == 0:
        return jnp.zeros((m, d), out_dtype)

    from repro.kernels.spmm.sell import (permute_rows, sell_tile_blocks,
                                         unpermute_rows)
    from repro.sparse.paths import pad_rows

    n_pad = -(-n // sell.bn) * sell.bn
    q_perm = permute_rows(sell, q)  # [n_live*bm, dk]
    k = pad_rows(kt.T, n_pad)
    v = pad_rows(v, n_pad)
    with jax.named_scope("sparse.layout.tile_values"):
        mask = (sell_tile_blocks(sell) != 0).astype(jnp.float32)
    y = fused_attn_sell_kernel(
        sell.tile_rows, sell.tile_cols, mask, q_perm, k, v,
        n_live_block_rows=sell.n_live_block_rows, act=act, slope=slope,
        out_dtype=out_dtype, interpret=interpret)
    return unpermute_rows(sell, y)


def fused_attn_sell_slots_ref(sell: SellCS, q, kt, v, *,
                              act: str = "leaky_relu", slope: float = 0.2,
                              out_dtype=jnp.float32):
    """Slot-granular reference over the packed slots.

    The slot triplet is an element layout (padding slots carry zero
    values and mask out against the structural pattern), so this is the
    element reference evaluated at the slot coordinates.
    """
    return fused_attn_elements(sell.slot_rows, sell.slot_cols,
                               sell.slot_vals, q, kt, v, sell.shape[0],
                               act=act, slope=slope, out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# Element (csr) and dense reference paths
# ---------------------------------------------------------------------------


@jax.named_scope("sparse.xla.fused_attn_elements")
def fused_attn_elements(row_ids, col_ids, values, q, kt, v, m: int, *,
                        act: str = "leaky_relu", slope: float = 0.2,
                        out_dtype=None):
    """The csr reference path (element-granular, E-length by nature)."""
    from repro.sparse.paths import sddmm_element_dots, spmm_elements

    out_dtype = out_dtype or jnp.result_type(q.dtype, v.dtype)
    dots = sddmm_element_dots(row_ids, col_ids, q, kt)
    mask = values != 0
    e = jnp.where(mask, apply_act(dots.astype(jnp.float32), act, slope),
                  NEG_INF)
    mx = jax.ops.segment_max(e, row_ids, num_segments=m)
    ex = jnp.where(mask, jnp.exp(e - mx[row_ids]), 0.0)
    den = jax.ops.segment_sum(ex, row_ids, num_segments=m)
    alpha = ex / jnp.maximum(den[row_ids], EPS)
    y = spmm_elements(row_ids, col_ids, alpha.astype(v.dtype), v, m)
    return y.astype(out_dtype)


@jax.named_scope("sparse.xla.fused_attn_dense")
def fused_attn_dense(a_dense, q, kt, v, *, act: str = "leaky_relu",
                     slope: float = 0.2, out_dtype=None):
    """Densified fallback: masked row softmax over the full product."""
    out_dtype = out_dtype or jnp.result_type(q.dtype, v.dtype)
    s = q.astype(jnp.float32) @ kt.astype(jnp.float32)
    mask = a_dense != 0
    e = jnp.where(mask, apply_act(s, act, slope), NEG_INF)
    mx = e.max(axis=1, keepdims=True)
    p = jnp.where(mask, jnp.exp(e - mx), 0.0)
    den = jnp.maximum(p.sum(axis=1, keepdims=True), EPS)
    return ((p / den) @ v.astype(jnp.float32)).astype(out_dtype)
