"""SpMM + epilogue fused Pallas TPU kernels (Block-ELL and SELL-C-σ).

Both kernels are the repo's streaming SpMM kernels with the elementwise
tail — ``act(y + bias + residual)`` — applied to the VMEM accumulator at
the single output flush, so the raw product never round-trips HBM just
to have a bias added and a relu applied (the paper's
intermediate-materialization tax, killed at the kernel level).

  * Block-ELL grid: (nbr, D/bd, W) exactly like ``kernels/spmm/kernel``;
    the epilogue runs inside the ``w == W-1`` flush.  Bias streams as a
    (1, bd) tile of the [1, D] vector, the residual as the output-shaped
    (bm, bd) tile — both only when the spec says they participate, so an
    epilogue-free call builds the identical pipeline as before.
  * SELL grid: (D/bd, T) over live tiles like ``kernels/spmm/sell``;
    the epilogue runs at every row-change flush.  The residual is
    pre-gathered into *packed* row order by the wrapper (``perm``), and
    rows living in pruned (all-zero) slices — which the kernel never
    touches — get their ``act(bias + residual)`` background re-inserted
    by the epilogue gather, keeping the semantics identical to the
    logical ``act(A @ H + bias + residual)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import SellCS
from repro.kernels.fused.epilogue import Epilogue, apply_act, apply_epilogue


def _finish(acc, epi: Epilogue, bias_blk, res_blk):
    z = acc
    if epi.has_bias:
        z = z + bias_blk.astype(jnp.float32)
    if epi.has_residual:
        z = z + res_blk.astype(jnp.float32)
    return apply_act(z, epi.act, epi.negative_slope)


# ---------------------------------------------------------------------------
# Block-ELL SpMM + epilogue
# ---------------------------------------------------------------------------


def _ell_fused_kernel(idx_ref, a_ref, h_ref, *rest, n_slots: int,
                      epi: Epilogue):
    """o[i, j] = act(sum_k A[i, k] @ H[idx[i, k], j] + bias + res)."""
    refs = list(rest)
    bias_ref = refs.pop(0) if epi.has_bias else None
    res_ref = refs.pop(0) if epi.has_residual else None
    o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[0, 0, :, :],
        h_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_slots - 1)
    def _flush():
        bias_blk = bias_ref[0, :] if epi.has_bias else None
        res_blk = res_ref[...] if epi.has_residual else None
        o_ref[...] = _finish(acc_ref[...], epi, bias_blk,
                             res_blk).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("epi", "bd", "out_dtype", "interpret"),
)
@jax.named_scope("sparse.kernel.spmm_blockell_epilogue")
def spmm_blockell_epilogue_kernel(
    indices,  # int32[nbr, W]
    blocks,  # dtype[nbr, W, bm, bn]
    h,  # dtype[N, D]
    bias,  # dtype[1, D] (zeros-shaped dummy never built: pass None-free)
    res,  # dtype[nbr*bm, D]
    *,
    epi: Epilogue,
    bd: int = 256,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    nbr, w, bm, bn = blocks.shape
    n, d = h.shape
    assert d % bd == 0, (d, bd)
    assert n % bn == 0, (n, bn)

    grid = (nbr, d // bd, w)
    kernel = functools.partial(_ell_fused_kernel, n_slots=w, epi=epi)
    in_specs = [
        pl.BlockSpec((1, 1, bm, bn), lambda i, j, k, idx: (i, k, 0, 0)),
        pl.BlockSpec((bn, bd), lambda i, j, k, idx: (idx[i, k], j)),
    ]
    operands = [blocks, h]
    if epi.has_bias:
        in_specs.append(pl.BlockSpec((1, bd), lambda i, j, k, idx: (0, j)))
        operands.append(bias)
    if epi.has_residual:
        in_specs.append(pl.BlockSpec((bm, bd), lambda i, j, k, idx: (i, j)))
        operands.append(res)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bd), lambda i, j, k, idx: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nbr * bm, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="spmm_blockell_epilogue",
    )(indices, *operands)


def spmm_blockell_fused(ell, h, epi: Epilogue, bias=None, residual=None,
                        *, bd=None, out_dtype=None, use_kernel: bool = False,
                        interpret: bool = False):
    """Y = act(A @ H + bias + residual) with A in Block-ELL.

    ``h`` is already padded to ``ell.shape[1]`` rows (the SpMM-path
    contract); the output carries the padded ``nbr*bm`` rows — callers
    trim to the logical row count like the unfused path.  ``residual``
    carries *logical* rows and is zero-padded here.
    """
    from repro.kernels.spmm.ops import _pick_bd, spmm_blockell
    from repro.sparse.paths import pad_rows

    out_dtype = out_dtype or jnp.result_type(ell.blocks.dtype, h.dtype)
    if not (use_kernel or interpret):
        y = spmm_blockell(ell, h, bd=bd, out_dtype=out_dtype,
                          use_kernel=False)
        res = None if residual is None else pad_rows(residual, y.shape[0])
        return apply_epilogue(y, epi, bias, res)
    d = h.shape[1]
    mp = ell.n_block_rows * ell.bm
    bias2d = None
    if epi.has_bias:
        bias2d = jnp.asarray(bias).reshape(1, d)
    res = pad_rows(residual, mp) if epi.has_residual else None
    return spmm_blockell_epilogue_kernel(
        ell.indices, ell.blocks, h, bias2d, res,
        epi=epi, bd=bd or _pick_bd(d), out_dtype=out_dtype,
        interpret=interpret)


# ---------------------------------------------------------------------------
# SELL-C-σ SpMM + epilogue
# ---------------------------------------------------------------------------


def _sell_fused_kernel(rows_ref, cols_ref, a_ref, h_ref, *rest,
                       n_tiles: int, epi: Epilogue):
    refs = list(rest)
    bias_ref = refs.pop(0) if epi.has_bias else None
    res_ref = refs.pop(0) if epi.has_residual else None
    o_ref, acc_ref = refs
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
        bias_blk = bias_ref[0, :] if epi.has_bias else None
        res_blk = res_ref[...] if epi.has_residual else None
        o_ref[...] = _finish(acc_ref[...], epi, bias_blk,
                             res_blk).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("epi", "n_live_block_rows", "bd", "out_dtype",
                     "interpret"),
)
@jax.named_scope("sparse.kernel.spmm_sell_epilogue")
def spmm_sell_epilogue_kernel(
    tile_rows,  # int32[T]
    tile_cols,  # int32[T]
    tile_blocks,  # dtype[T, bm, bn]
    h,  # dtype[Np, D]
    bias,  # dtype[1, D] or None
    res_perm,  # dtype[n_live*bm, D] residual in packed row order, or None
    *,
    epi: Epilogue,
    n_live_block_rows: int,
    bd: int = 256,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    t_count, bm, bn = tile_blocks.shape
    n, d = h.shape
    assert d % bd == 0, (d, bd)
    assert n % bn == 0, (n, bn)

    grid = (d // bd, t_count)
    kernel = functools.partial(_sell_fused_kernel, n_tiles=t_count, epi=epi)
    in_specs = [
        pl.BlockSpec((1, bm, bn), lambda j, t, rows, cols: (t, 0, 0)),
        pl.BlockSpec((bn, bd), lambda j, t, rows, cols: (cols[t], j)),
    ]
    operands = [tile_blocks, h]
    if epi.has_bias:
        in_specs.append(
            pl.BlockSpec((1, bd), lambda j, t, rows, cols: (0, j)))
        operands.append(bias)
    if epi.has_residual:
        in_specs.append(
            pl.BlockSpec((bm, bd), lambda j, t, rows, cols: (rows[t], j)))
        operands.append(res_perm)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (bm, bd), lambda j, t, rows, cols: (rows[t], j)),
            scratch_shapes=[pltpu.VMEM((bm, bd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_live_block_rows * bm, d),
                                       out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="spmm_sell_epilogue",
    )(tile_rows, tile_cols, *operands)


def spmm_sell_fused(sell: SellCS, h, epi: Epilogue, bias=None,
                    residual=None, *, bd=None, out_dtype=None,
                    use_kernel: bool = False, interpret: bool = False):
    """Y = act(A @ H + bias + residual) with A in SELL-C-σ.

    ``h`` carries the logical N rows.  Rows the tile-pruned kernel never
    computes (all-zero rows in pruned slices) still owe their epilogue
    background ``act(bias + residual)``, which the final gather
    re-inserts — with no bias/residual that background is exactly zero
    (every supported act fixes 0), so the cheap path is unchanged.
    """
    from repro.kernels.spmm.ops import _pick_bd
    from repro.sparse.paths import spmm_sell_ref

    out_dtype = out_dtype or jnp.result_type(sell.slot_vals.dtype, h.dtype)
    m, n = sell.shape
    d = h.shape[1]
    if not (use_kernel or interpret):
        y = spmm_sell_ref(sell, h, out_dtype=out_dtype)
        return apply_epilogue(y, epi, bias, residual)
    if sell.n_live_block_rows == 0:
        y = jnp.zeros((m, d), out_dtype)
        return apply_epilogue(y, epi, bias, residual)

    from repro.kernels.spmm.sell import (permute_rows, sell_tile_blocks,
                                         unpermute_rows)
    from repro.sparse.paths import pad_rows

    bn = sell.bn
    h = pad_rows(h, -(-n // bn) * bn)
    bias2d = jnp.asarray(bias).reshape(1, d) if epi.has_bias else None
    res_perm = permute_rows(sell, residual) if epi.has_residual else None
    y = spmm_sell_epilogue_kernel(
        sell.tile_rows, sell.tile_cols, sell_tile_blocks(sell), h,
        bias2d, res_perm, epi=epi,
        n_live_block_rows=sell.n_live_block_rows,
        bd=bd or _pick_bd(d), out_dtype=out_dtype, interpret=interpret)
    out = unpermute_rows(sell, y)
    if epi.has_bias or epi.has_residual:
        # pruned rows (A row all-zero): out = act(bias + residual[row])
        zero = jnp.zeros((m, d), jnp.float32)
        bg = apply_epilogue(zero, epi, bias, residual).astype(out.dtype)
        live = (sell.tile_out_gather < sell.n_live_block_rows * sell.bm)
        out = jnp.where(live[:, None], out, bg)
    return out
