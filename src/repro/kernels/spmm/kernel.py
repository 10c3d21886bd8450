"""Block-ELL SpMM Pallas TPU kernel.

Design (CS-3 -> TPU adaptation, see DESIGN.md §2):

  * The paper's router PEs pre-filter the stream of (col_idx, value) pairs so
    each worker row only sees nonzeros in its column range.  Here that
    filtering is done once at format-construction time (Block-ELL), and the
    *scalar-prefetched* block-column indices drive the Pallas pipeline's
    `index_map`, so the HBM->VMEM DMA engine fetches exactly the H tile each
    A block needs — the dataflow "router" realized as prefetch-driven DMA.

  * The paper pads every stream to equal length (NULL wavelets) so I/O
    channels stay uniform.  Here every block-row is padded to the same ELL
    width W, so the grid is static and each step does identical work; padded
    slots carry zero blocks and clipped indices and contribute exactly 0.

  * The paper's north->south partial-sum folding maps to output-block
    revisiting: the innermost grid dimension walks the W nonzero slots while
    the output tile stays resident in VMEM and accumulates.

Grid: (num_block_rows, D/bd, W)   [W innermost => sequential accumulation]
  A blocks: [nbr, W, bm, bn] -> tile (1, 1, bm, bn) at (i, k, 0, 0)
  H:        [N, D]           -> tile (bn, bd)       at (idx[i, k], j)
  Y:        [M, D]           -> tile (bm, bd)       at (i, j), revisited in k
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _spmm_kernel(idx_ref, a_ref, h_ref, o_ref, acc_ref, *, n_slots: int):
    """One grid step: o[i, j] += A[i, k] @ H[idx[i, k], j]."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_blk = a_ref[0, 0, :, :]
    h_blk = h_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        a_blk,
        h_blk,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_slots - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bd", "out_dtype", "interpret"),
)
@jax.named_scope("sparse.kernel.spmm_blockell")
def spmm_blockell_kernel(
    indices,  # int32[nbr, W]
    blocks,  # dtype[nbr, W, bm, bn]
    h,  # dtype[N, D]
    *,
    bd: int = 256,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    nbr, w, bm, bn = blocks.shape
    n, d = h.shape
    assert d % bd == 0, (d, bd)
    assert n % bn == 0, (n, bn)

    grid = (nbr, d // bd, w)

    kernel = functools.partial(_spmm_kernel, n_slots=w)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, 1, bm, bn), lambda i, j, k, idx: (i, k, 0, 0)
                ),
                pl.BlockSpec((bn, bd), lambda i, j, k, idx: (idx[i, k], j)),
            ],
            out_specs=pl.BlockSpec((bm, bd), lambda i, j, k, idx: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nbr * bm, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="spmm_blockell",
    )(indices, blocks, h)
    return out
