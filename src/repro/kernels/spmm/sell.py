"""Tile-pruned SELL-C-σ SpMM Pallas TPU kernel.

The Block-ELL kernel pads every block-row to one global width W, so past
~99 % sparsity nearly all of its grid steps multiply zero padding — the
paper's hyper-sparsity cliff.  This kernel iterates a *flat list of live
tiles* instead (the SELL slice descriptor, scalar-prefetched):

  * the grid's sequential axis walks only tiles that exist — all-zero
    row slices were pruned at pack time and are never launched;
  * tiles are ordered block-row-major, so the output tile stays resident
    in VMEM while consecutive grid steps accumulate into it; the flush
    happens when the scalar-prefetched ``tile_rows`` descriptor changes
    (width-adaptive: each block-row owns exactly as many steps as it has
    live tiles);
  * the output is *compacted* — only live block-rows are written — and
    the caller's epilogue gather applies the inverse row permutation,
    re-inserts pruned (all-zero) rows, and trims padding in one pass.

Grid: (D/bd, T)   [T innermost => sequential accumulate/flush]
  A tiles: [T, bm, bn] -> tile (1, bm, bn)  at (t, 0, 0)
  H:       [Np, D]     -> tile (bn, bd)     at (cols[t], j)
  Y:       [L*bm, D]   -> tile (bm, bd)     at (rows[t], j), revisited
                          while rows[t] stays constant
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import SellCS


def _sell_spmm_kernel(rows_ref, cols_ref, a_ref, h_ref, o_ref, acc_ref,
                      *, n_tiles: int):
    """One live tile: acc += A_tile @ H[cols[t]]; flush on row change."""
    t = pl.program_id(1)
    row = rows_ref[t]
    prev = rows_ref[jnp.maximum(t - 1, 0)]
    nxt = rows_ref[jnp.minimum(t + 1, n_tiles - 1)]

    @pl.when((t == 0) | (row != prev))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[0, :, :],
        h_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when((t == n_tiles - 1) | (row != nxt))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_live_block_rows", "bd", "out_dtype", "interpret"),
)
@jax.named_scope("sparse.kernel.spmm_sell")
def spmm_sell_kernel(
    tile_rows,  # int32[T]  compact live block-row per tile (ascending)
    tile_cols,  # int32[T]  block-column per tile
    tile_blocks,  # dtype[T, bm, bn]  live tile data
    h,  # dtype[Np, D]  (rows padded to the block-column grid)
    *,
    n_live_block_rows: int,
    bd: int = 256,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    """Compact Y for the live block-rows only: [n_live*bm, D]."""
    t_count, bm, bn = tile_blocks.shape
    n, d = h.shape
    assert d % bd == 0, (d, bd)
    assert n % bn == 0, (n, bn)

    grid = (d // bd, t_count)
    kernel = functools.partial(_sell_spmm_kernel, n_tiles=t_count)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, bm, bn), lambda j, t, rows, cols: (t, 0, 0)
                ),
                pl.BlockSpec(
                    (bn, bd), lambda j, t, rows, cols: (cols[t], j)
                ),
            ],
            out_specs=pl.BlockSpec(
                (bm, bd), lambda j, t, rows, cols: (rows[t], j)
            ),
            scratch_shapes=[pltpu.VMEM((bm, bd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_live_block_rows * bm, d),
                                       out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="spmm_sell",
    )(tile_rows, tile_cols, tile_blocks, h)
    return out


def spmm_sell_tiles_ref(tile_rows, tile_cols, tile_blocks, h,
                        *, n_live_block_rows: int, out_dtype=jnp.float32):
    """Pure-jnp oracle of the kernel's compact output (tile granular)."""
    t_count, bm, bn = tile_blocks.shape
    n, d = h.shape
    h_blocks = h.reshape(n // bn, bn, d)
    prods = jnp.einsum(
        "tmn,tnd->tmd",
        tile_blocks.astype(jnp.float32),
        h_blocks[tile_cols].astype(jnp.float32),
    )
    out = jax.ops.segment_sum(prods, tile_rows,
                              num_segments=n_live_block_rows)
    return out.reshape(n_live_block_rows * bm, d).astype(out_dtype)


@jax.named_scope("sparse.layout.tile_values")
def sell_tile_blocks(sell: SellCS):
    """Scatter the slot values into the live-tile data (trace-safe).

    Values live exactly once (``slot_vals``); each slot lands at its
    ``slot_tile_pos`` cell of a zeroed ``[T, bm, bn]`` buffer, and the
    padding and deleted slots (position ``T*bm*bn``) are dropped.  The
    indexed work scales with the slots, not with the tile cells.  The
    scatter is flat: the TPU compiler rewrites a (tile, row, column)
    scatter without its scope, and it is no faster on a v5e.
    """
    shape = (sell.n_tiles, sell.bm, sell.bn)
    cells = jnp.zeros((math.prod(shape),), sell.slot_vals.dtype)
    return cells.at[sell.slot_tile_pos].set(
        sell.slot_vals, mode="drop").reshape(shape)


def spmm_sell_blocked(sell: SellCS, h, *, bd: int | None = None,
                      out_dtype=None, interpret: bool = False):
    """Y = A @ H through the tile-pruned kernel, epilogue applied.

    ``h`` carries the logical N rows; it is padded to the block-column
    grid here.  The epilogue gather un-permutes rows, re-inserts the
    pruned all-zero rows, and trims to the logical row count.
    """
    from repro.kernels.spmm.ops import _pick_bd
    from repro.sparse.paths import pad_rows

    out_dtype = out_dtype or jnp.result_type(sell.slot_vals.dtype, h.dtype)
    m, n = sell.shape
    d = h.shape[1]
    if sell.n_live_block_rows == 0:
        return jnp.zeros((m, d), out_dtype)
    bn = sell.bn
    n_pad = -(-n // bn) * bn
    h = pad_rows(h, n_pad)
    y = spmm_sell_kernel(
        sell.tile_rows, sell.tile_cols, sell_tile_blocks(sell), h,
        n_live_block_rows=sell.n_live_block_rows,
        bd=bd or _pick_bd(d), out_dtype=out_dtype, interpret=interpret)
    return unpermute_rows(sell, y)


@jax.named_scope("sparse.layout.permute")
def permute_rows(sell: SellCS, x):
    """Logical rows of ``x`` gathered into packed row order; padding
    rows read the appended zero row."""
    x_ext = jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)])
    return x_ext[sell.perm]


@jax.named_scope("sparse.layout.unpermute")
def unpermute_rows(sell: SellCS, y):
    """Compact kernel rows back to logical order: the epilogue gather
    un-permutes rows and reads the appended zero row for the pruned
    all-zero rows and the padding."""
    y_ext = jnp.concatenate([y, jnp.zeros((1, y.shape[1]), y.dtype)])
    return y_ext[sell.tile_out_gather]
