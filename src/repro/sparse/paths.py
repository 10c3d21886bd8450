"""Traceable execution paths shared by the unified sparse front-end.

Every path here is pure jnp (or routes to the Pallas kernels), takes
device arrays, and is safe to call at ``jax.jit`` trace time — planning
(which path to run) is host logic and lives in ``repro.sparse.ops``; the
functions below only *execute*.

Path vocabulary matches the dispatch layer (see dispatch/policy.py):

  * ``ell``   — blocked streaming: Block-ELL SpMM / Block-COO SDDMM
                (Pallas kernel on TPU, jnp reference elsewhere), plus a
                blocked-COO SpMM used for transposed Block-ELL operands.
  * ``sell``  — SELL-C-σ: width-adaptive row-sorted slices.  The jnp
                reference runs one scatter-free batched contraction per
                width bucket (the slice descriptor is static aux, so the
                loop unrolls at trace time); the kernel route iterates
                live tiles only (see kernels/spmm/sell.py).
  * ``csr``   — element-granular: gather + segment-sum SpMM, per-edge
                dot SDDMM.  Exact nnz work, no MXU.
  * ``dense`` — densify (device scatter) and run the dense matmul /
                full-product sample.

The jnp products here name their device ops ``sparse.xla.<fn>`` and the
layout conversions ``sparse.layout.<what>`` (``jax.named_scope``), so a
profile separates XLA-lowered product work from layout work.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import CSR, BlockCOO, BlockELL, SellCS

Array = Any


# ---------------------------------------------------------------------------
# Element-granular ("csr") paths
# ---------------------------------------------------------------------------


def csr_to_device_arrays(csr: CSR) -> Tuple[Array, Array, Array]:
    """Expand host CSR to (row_ids, col_ids, values) int32 device arrays."""
    row_ids = np.repeat(
        np.arange(csr.shape[0], dtype=np.int32), np.diff(csr.indptr)
    )
    return (
        jnp.asarray(row_ids),
        jnp.asarray(csr.indices.astype(np.int32, copy=False)),
        jnp.asarray(csr.values),
    )


@jax.named_scope("sparse.xla.spmm_elements")
def spmm_elements(row_ids, col_ids, values, h, num_rows: int):
    """Y = A @ H via gather + segment-sum (element-granular)."""
    gathered = values[:, None].astype(jnp.float32) * h[col_ids].astype(
        jnp.float32
    )
    out = jax.ops.segment_sum(gathered, row_ids, num_segments=num_rows)
    return out.astype(h.dtype)


@jax.named_scope("sparse.xla.sddmm_element_dots")
def sddmm_element_dots(row_ids, col_ids, b, c):
    """out[e] = b[row[e]] . c[:, col[e]] — the per-edge dot products.

    b: [M, K]; c: [K, N] -> dots[e] for each coordinate.
    """
    bs = b[row_ids].astype(jnp.float32)  # [nnz, K]
    cs = c.T[col_ids].astype(jnp.float32)  # [nnz, K]
    return jnp.sum(bs * cs, axis=-1).astype(b.dtype)


@jax.named_scope("sparse.xla.sddmm_elements")
def sddmm_elements(row_ids, col_ids, values, b, c):
    """values ⊙ (B @ C) sampled at the element coordinates."""
    dots = sddmm_element_dots(row_ids, col_ids, b, c)
    return (values.astype(jnp.float32)
            * dots.astype(jnp.float32)).astype(values.dtype)


# ---------------------------------------------------------------------------
# SpMV (d = 1) paths — vector fast lane, no SpMM tile machinery
# ---------------------------------------------------------------------------
#
# y = A @ x for a [N] vector.  The SpMM paths would run these as [N, 1]
# matrices through the blocked tile pipeline (kernel grids, D-padding,
# epilogue plumbing); with one output column none of that pays for
# itself, so each layout gets a direct reduction instead.


@jax.named_scope("sparse.xla.spmv_elements")
def spmv_elements(row_ids, col_ids, values, x, num_rows: int):
    """y = A @ x via gather + segment-sum (element-granular)."""
    prod = values.astype(jnp.float32) * x[col_ids].astype(jnp.float32)
    out = jax.ops.segment_sum(prod, row_ids, num_segments=num_rows)
    return out.astype(x.dtype)


@jax.named_scope("sparse.xla.spmv_ell")
def spmv_ell(ell: BlockELL, x, *, out_dtype=None):
    """y = A @ x with A in Block-ELL; x already padded to ell.shape[1].

    One einsum over the gathered x-blocks — the block columns each slot
    points at — contracting both the slot axis and the in-block column.
    """
    bn = ell.bn
    x_blocks = x.reshape(ell.shape[1] // bn, bn)
    gathered = x_blocks[ell.indices]  # [nbr, W, bn]
    y = jnp.einsum("rwmn,rwn->rm", ell.blocks.astype(jnp.float32),
                   gathered.astype(jnp.float32))
    out_dtype = out_dtype or jnp.result_type(ell.blocks.dtype, x.dtype)
    return y.reshape(ell.shape[0]).astype(out_dtype)


@jax.named_scope("sparse.xla.spmv_coo")
def spmv_coo(coo: BlockCOO, x, *, out_dtype=None):
    """y = A @ x with A in Block-COO (scatter-add over nonzero blocks)."""
    bm, bn = coo.bm, coo.bn
    x_blocks = x.reshape(coo.shape[1] // bn, bn)
    prods = jnp.einsum("emn,en->em", coo.blocks.astype(jnp.float32),
                       x_blocks[coo.cols].astype(jnp.float32))
    out = jnp.zeros((coo.shape[0] // bm, bm), jnp.float32) \
        .at[coo.rows].add(prods)
    out_dtype = out_dtype or jnp.result_type(coo.blocks.dtype, x.dtype)
    return out.reshape(coo.shape[0]).astype(out_dtype)


@jax.named_scope("sparse.xla.spmv_sell")
def spmv_sell(sell: SellCS, x, *, out_dtype=None):
    """y = A @ x with A in SELL-C-σ — scatter-free per-bucket reduction.

    Each width bucket is one [rows, w] elementwise product + row sum;
    the epilogue gather un-permutes rows exactly like spmm_sell_ref
    (the appended zero covers pruned all-zero rows).
    """
    m, _ = sell.shape
    out_dtype = out_dtype or jnp.result_type(sell.slot_vals.dtype, x.dtype)
    if not sell.buckets:
        return jnp.zeros((m,), out_dtype)
    outs = []
    off = 0
    for _, rows, width in sell.buckets:
        cols = sell.slot_cols[off:off + rows * width].reshape(rows, width)
        vals = sell.slot_vals[off:off + rows * width].reshape(rows, width)
        outs.append((vals.astype(jnp.float32)
                     * x[cols].astype(jnp.float32)).sum(axis=-1))
        off += rows * width
    packed = jnp.concatenate(outs + [jnp.zeros((1,), jnp.float32)])
    return packed[sell.out_gather].astype(out_dtype)


# ---------------------------------------------------------------------------
# Blocked ("ell") paths
# ---------------------------------------------------------------------------


def spmm_ell(ell: BlockELL, h, *, use_kernel: bool = False,
             interpret: bool = False, bd: Optional[int] = None,
             out_dtype=None):
    """Y = A @ H with A in Block-ELL; H already padded to ell.shape[1]."""
    from repro.kernels.spmm.ops import spmm_blockell

    return spmm_blockell(ell, h, bd=bd, out_dtype=out_dtype,
                         use_kernel=use_kernel or interpret,
                         interpret=interpret)


@jax.named_scope("sparse.xla.spmm_coo")
def spmm_coo(coo: BlockCOO, h, *, out_dtype=None):
    """Y = A @ H with A in Block-COO (scatter-add over nonzero blocks).

    The blocked path for transposed Block-ELL operands: ELL transposes
    into COO without host re-bucketing, and this scatter is its SpMM.
    Padded entries carry zero blocks, so duplicate coordinates are
    harmless under the add.
    """
    nnzb, bm, bn = coo.blocks.shape
    mp, np_ = coo.shape
    n, d = h.shape
    h_blocks = h.reshape(np_ // bn, bn, d)
    prods = jnp.einsum(
        "emn,end->emd",
        coo.blocks.astype(jnp.float32),
        h_blocks[coo.cols].astype(jnp.float32),
    )
    out = jnp.zeros((mp // bm, bm, d), jnp.float32).at[coo.rows].add(prods)
    out_dtype = out_dtype or jnp.result_type(coo.blocks.dtype, h.dtype)
    return out.reshape(mp, d).astype(out_dtype)


def sddmm_blocked(coo: BlockCOO, b, c, *, use_kernel: bool = False,
                  interpret: bool = False, bk: Optional[int] = None,
                  out_dtype=None) -> BlockCOO:
    """coo.blocks ⊙ (B @ C) at the nonzero blocks; B/C already padded."""
    from repro.kernels.sddmm.ops import sddmm_blockcoo

    return sddmm_blockcoo(coo, b, c, bk=bk, out_dtype=out_dtype,
                          use_kernel=use_kernel or interpret,
                          interpret=interpret)


@jax.named_scope("sparse.layout.ell_to_coo")
def ell_to_coo(ell: BlockELL) -> BlockCOO:
    """Flatten Block-ELL slots into Block-COO (traceable, no host work).

    Padded slots become zero blocks at duplicated coordinates — exactly
    the Block-COO padding contract.
    """
    nbr, w = ell.indices.shape
    bm, bn = ell.bm, ell.bn
    rows = jnp.repeat(jnp.arange(nbr, dtype=jnp.int32), w)
    cols = ell.indices.reshape(-1).astype(jnp.int32)
    blocks = ell.blocks.reshape(nbr * w, bm, bn)
    return BlockCOO(rows=rows, cols=cols, blocks=blocks, shape=ell.shape)


@jax.named_scope("sparse.layout.transpose")
def transpose_coo(coo: BlockCOO) -> BlockCOO:
    """A.T in Block-COO: swap coordinates, transpose each block."""
    return BlockCOO(
        rows=coo.cols,
        cols=coo.rows,
        blocks=coo.blocks.transpose(0, 2, 1),
        shape=(coo.shape[1], coo.shape[0]),
    )


# ---------------------------------------------------------------------------
# SELL-C-σ ("sell") paths
# ---------------------------------------------------------------------------


@jax.named_scope("sparse.xla.spmm_sell_ref")
def spmm_sell_ref(sell: SellCS, h, *, out_dtype=None):
    """Y = A @ H with A in SELL-C-σ — the scatter-free reference.

    One batched ``[rows, 1, w] @ [rows, w, D]`` contraction per width
    bucket (slices of equal width are contiguous), then a single epilogue
    gather that un-permutes rows and re-inserts the pruned all-zero rows.
    Work is proportional to the *packed slot* count — there is no global
    ELL width to pad to and no segment-sum scatter.
    """
    m, n = sell.shape
    d = h.shape[1]
    out_dtype = out_dtype or jnp.result_type(sell.slot_vals.dtype, h.dtype)
    if not sell.buckets:
        return jnp.zeros((m, d), out_dtype)
    outs = []
    off = 0
    for _, rows, width in sell.buckets:
        cols = sell.slot_cols[off:off + rows * width].reshape(rows, width)
        vals = sell.slot_vals[off:off + rows * width].reshape(rows, width)
        gathered = h[cols].astype(jnp.float32)  # [rows, w, D]
        out = jax.lax.dot_general(
            vals[:, None, :].astype(jnp.float32),
            gathered,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        )  # [rows, 1, D]
        outs.append(out.reshape(rows, d))
        off += rows * width
    packed = jnp.concatenate(outs + [jnp.zeros((1, d), jnp.float32)])
    return packed[sell.out_gather].astype(out_dtype)


def spmm_sell(sell: SellCS, h, *, use_kernel: bool = False,
              interpret: bool = False, bd: Optional[int] = None,
              out_dtype=None):
    """Y = A @ H with A in SELL-C-σ; h carries the logical N rows."""
    if use_kernel or interpret:
        from repro.kernels.spmm.sell import spmm_sell_blocked

        return spmm_sell_blocked(sell, h, bd=bd, out_dtype=out_dtype,
                                 interpret=interpret)
    return spmm_sell_ref(sell, h, out_dtype=out_dtype)


def sample_sell(sell: SellCS, b, c, *, use_kernel: bool = False,
                interpret: bool = False, bk: Optional[int] = None):
    """Raw dots of B @ C at the packed slots (slot order).

    Padding slots sample at their repeated coordinates on the element
    route and read the appended zero cell on the tile route; either way
    the caller masks them against the structural values.
    """
    if use_kernel or interpret:
        from repro.kernels.sddmm.sell import sample_sell_blocked

        return sample_sell_blocked(sell, b, c, bk=bk, interpret=interpret)
    return sddmm_element_dots(sell.slot_rows, sell.slot_cols, b, c)


@jax.named_scope("sparse.layout.densify")
def densify_sell(sell: SellCS):
    """Device scatter of the slots (padding slots add zeros)."""
    m, n = sell.shape
    return jnp.zeros((m, n), sell.slot_vals.dtype) \
        .at[sell.slot_rows, sell.slot_cols].add(sell.slot_vals)


# ---------------------------------------------------------------------------
# Densify ("dense") paths — device scatter, trace-safe for every format
# ---------------------------------------------------------------------------


@jax.named_scope("sparse.layout.densify")
def densify_elements(row_ids, col_ids, values, shape: Tuple[int, int]):
    m, n = shape
    return jnp.zeros((m, n), values.dtype).at[row_ids, col_ids].add(values)


@jax.named_scope("sparse.layout.densify")
def densify_ell(ell: BlockELL):
    nbr, w, bm, bn = ell.blocks.shape
    nbc = ell.shape[1] // bn
    out = jnp.zeros((nbr, nbc, bm, bn), ell.blocks.dtype)
    out = out.at[jnp.arange(nbr)[:, None], ell.indices].add(ell.blocks)
    return out.transpose(0, 2, 1, 3).reshape(ell.shape)


@jax.named_scope("sparse.layout.densify")
def densify_coo(coo: BlockCOO):
    bm, bn = coo.bm, coo.bn
    nbr, nbc = coo.shape[0] // bm, coo.shape[1] // bn
    out = jnp.zeros((nbr, nbc, bm, bn), coo.blocks.dtype)
    out = out.at[coo.rows, coo.cols].add(coo.blocks)
    return out.transpose(0, 2, 1, 3).reshape(coo.shape)


@jax.named_scope("sparse.xla.spmm_dense")
def spmm_dense(a_dense, h):
    """Dense baseline (the paper's Fig. 2 failure mode)."""
    return a_dense @ h


@jax.named_scope("sparse.layout.sample_blocks")
def sample_blocks(full, rows, cols, bm: int, bn: int):
    """Gather (bm, bn) tiles of a full [M, N] product at block coords."""
    m, n = full.shape
    tiles = full.reshape(m // bm, bm, n // bn, bn).transpose(0, 2, 1, 3)
    return tiles[rows, cols]  # [nnzb, bm, bn]


@jax.named_scope("sparse.layout.pad")
def pad_rows(x, target: int):
    """Zero-pad x's leading dim up to ``target`` (no-op when equal)."""
    if x.shape[0] == target:
        return x
    return jnp.zeros((target,) + x.shape[1:], x.dtype).at[: x.shape[0]].set(x)


@jax.named_scope("sparse.layout.pad")
def pad_cols(x, target: int):
    if x.shape[1] == target:
        return x
    return jnp.zeros((x.shape[0], target), x.dtype) \
        .at[:, : x.shape[1]].set(x)
