"""Tile-pruned SELL-C-σ SDDMM Pallas TPU kernel.

Samples B @ C only at the live tiles of a SELL-packed operand: the grid
walks the flat live-tile descriptor (scalar-prefetched), streams the
(bm x bk) B tile and (bn x bk) Cᵀ tile each live tile needs, contracts
over K with the accumulator resident in VMEM, and masks with the tile's
structural pattern at the flush — all-zero row slices were pruned at
pack time, so no grid step ever samples a dead tile.  C arrives N-major
(``c_t``), for the same tiling reason as the Block-COO kernel.

Because SELL packs *permuted* rows, the caller passes B already gathered
into packed row order (``b[perm]`` — the row gather the descriptor
records); the slot extraction afterwards folds the tile output back to
slot (element) order.

Grid: (T, K/bk)   [K innermost => sequential accumulation]
  B_perm: [L*bm, K]     -> tile (bm, bk)    at (rows[t], k)
  Cᵀ:     [Np, K]       -> tile (bn, bk)    at (cols[t], k)
  mask:   [T, bm, bn]   -> tile (1, bm, bn) at (t, 0, 0)
  Y:      [T, bm, bn]   -> tile (1, bm, bn) at (t, 0, 0), revisited in k
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import SellCS


def _sell_sddmm_kernel(rows_ref, cols_ref, b_ref, ct_ref, mask_ref, o_ref,
                       acc_ref, *, n_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        b_ref[...],
        ct_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _sample():
        mask = mask_ref[0, :, :].astype(jnp.float32)
        o_ref[0, :, :] = (mask * acc_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bk", "out_dtype", "interpret")
)
@jax.named_scope("sparse.kernel.sddmm_sell")
def sddmm_sell_kernel(
    tile_rows,  # int32[T] compact live block-row per tile
    tile_cols,  # int32[T] block-column per tile
    mask_blocks,  # dtype[T, bm, bn] structural 0/1 pattern of each tile
    b_perm,  # dtype[L*bm, K]  B gathered into packed row order
    c_t,  # dtype[Np, K]  (C transposed: N-major)
    *,
    bk: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    t_count, bm, bn = mask_blocks.shape
    m, k = b_perm.shape
    n, k2 = c_t.shape
    assert k == k2 and k % bk == 0, (k, bk)

    grid = (t_count, k // bk)
    kernel = functools.partial(_sell_sddmm_kernel, n_k=k // bk)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (bm, bk), lambda t, kk, rows, cols: (rows[t], kk)
                ),
                pl.BlockSpec(
                    (bn, bk), lambda t, kk, rows, cols: (cols[t], kk)
                ),
                pl.BlockSpec(
                    (1, bm, bn), lambda t, kk, rows, cols: (t, 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, bm, bn), lambda t, kk, rows, cols: (t, 0, 0)
            ),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t_count, bm, bn), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="sddmm_sell",
    )(tile_rows, tile_cols, b_perm, c_t, mask_blocks)
    return out


def sample_sell_blocked(sell: SellCS, b, c, *, bk: int | None = None,
                        interpret: bool = False):
    """Raw dots (B @ C) at the live structural slots, in slot order.

    ``b``: [M, K] logical rows; ``c``: [K, N] logical columns.  Output:
    float32[n_slots] — padding slots read 0.
    """
    from repro.kernels.sddmm.ops import _pick_bk
    from repro.kernels.spmm.sell import permute_rows
    from repro.sparse.paths import pad_rows

    m, n = sell.shape
    k = b.shape[1]
    n_slots = sell.n_slots
    if sell.n_tiles == 0:
        return jnp.zeros((n_slots,), jnp.float32)
    b_perm = permute_rows(sell, b)  # [n_live*bm, K]; padding rows zero
    c_t = pad_rows(c.T, -(-n // sell.bn) * sell.bn)
    with jax.named_scope("sparse.layout.tile_mask"):
        mask = (sell.tile_slot_map < n_slots).astype(b.dtype)
    tiles = sddmm_sell_kernel(
        sell.tile_rows, sell.tile_cols, mask, b_perm, c_t,
        bk=bk or _pick_bk(k), out_dtype=jnp.float32, interpret=interpret)
    return tile_slots(sell, tiles)


@jax.named_scope("sparse.layout.tile_slots")
def tile_slots(sell: SellCS, tiles):
    """Each slot's value read from its cell of the ``[T, bm, bn]`` tile
    output; padding and deleted slots (position ``T*bm*bn``) read 0.

    The read is indexed by (tile, row, column), so the tile output is
    neither flattened nor copied: only the slots' cells are touched.
    """
    t, ij = jnp.divmod(sell.slot_tile_pos, sell.bm * sell.bn)
    i, j = jnp.divmod(ij, sell.bn)
    return tiles.at[t, i, j].get(mode="fill", fill_value=0)
