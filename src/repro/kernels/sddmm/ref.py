"""Pure-jnp oracle for Block-COO SDDMM: Y = A ⊙ (B @ C)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.formats import BlockCOO


@jax.named_scope("sparse.xla.sddmm_blockcoo_ref")
def sddmm_blockcoo_ref(coo: BlockCOO, b, c, *, out_dtype=None):
    """Reference SDDMM.

    coo.blocks are the sampling values of A (for a 0/1 mask this returns the
    sampled product; for weighted A it returns A ⊙ (B@C)).
    b: [M, K]; c: [K, N].  Output: BlockCOO with the same coordinates.
    Padded entries carry zero mask blocks so their output is zero.
    """
    bm, bn = coo.bm, coo.bn
    m, k = b.shape
    k2, n = c.shape
    assert k == k2, (b.shape, c.shape)
    b_blocks = b.reshape(m // bm, bm, k)[coo.rows]  # [nnzb, bm, K]
    c_blocks = c.reshape(k, n // bn, bn).transpose(1, 0, 2)[coo.cols]
    prod = jnp.einsum(
        "emk,ekn->emn",
        b_blocks.astype(jnp.float32),
        c_blocks.astype(jnp.float32),
    )
    out_dtype = out_dtype or jnp.result_type(coo.blocks.dtype, b.dtype)
    out_blocks = (coo.blocks.astype(jnp.float32) * prod).astype(out_dtype)
    return BlockCOO(
        rows=coo.rows, cols=coo.cols, blocks=out_blocks, shape=coo.shape
    )
