"""Block-COO SDDMM Pallas TPU kernel:  Y_blk = A_blk ⊙ (B_row · C_col).

CS-3 -> TPU adaptation (DESIGN.md §2): the paper keeps the nonzero tile of A
stationary on each worker PE and streams columns of B / rows of C through
the grid.  On TPU the nonzero-block list is scalar-prefetched, and the
pipeline streams the (bm x bk) B tile and (bk x bn) C tile each block needs
from HBM; the contraction over K happens across the innermost grid dim with
the accumulator resident in VMEM (the stationary-output dataflow).

Grid: (nnzb, K/bk)   [K innermost => sequential accumulation]
  B:      [M, K]           -> tile (bm, bk)     at (rows[e], k)
  C:      [K, N]           -> tile (bk, bn)     at (k, cols[e])
  A mask: [nnzb, bm, bn]   -> tile (1, bm, bn)  at (e, 0, 0)
  Y:      [nnzb, bm, bn]   -> tile (1, bm, bn)  at (e, 0, 0), revisited in k
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sddmm_kernel(rows_ref, cols_ref, b_ref, ct_ref, a_ref, o_ref, acc_ref,
                  *, n_k: int):
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
        mask = a_ref[0, :, :].astype(jnp.float32)
        o_ref[0, :, :] = (mask * acc_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bk", "out_dtype", "interpret")
)
@jax.named_scope("sparse.kernel.sddmm_blockcoo")
def sddmm_blockcoo_kernel(
    rows,  # int32[nnzb]
    cols,  # int32[nnzb]
    mask_blocks,  # dtype[nnzb, bm, bn]
    b,  # dtype[M, K]
    c_t,  # dtype[N, K]  (C transposed: N-major)
    *,
    bk: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    nnzb, bm, bn = mask_blocks.shape
    m, k = b.shape
    n, k2 = c_t.shape
    assert k == k2 and k % bk == 0, (k, bk)

    grid = (nnzb, k // bk)
    kernel = functools.partial(_sddmm_kernel, n_k=k // bk)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda e, kk, rows, cols: (rows[e], kk)),
                pl.BlockSpec((bn, bk), lambda e, kk, rows, cols: (cols[e], kk)),
                pl.BlockSpec((1, bm, bn), lambda e, kk, rows, cols: (e, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, bm, bn), lambda e, kk, rows, cols: (e, 0, 0)
            ),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nnzb, bm, bn), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="sddmm_blockcoo",
    )(rows, cols, b, c_t, mask_blocks)
    return out
