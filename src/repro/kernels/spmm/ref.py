"""Pure-jnp oracle for Block-ELL SpMM: Y = A @ H."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.formats import BlockELL


@jax.named_scope("sparse.xla.spmm_blockell_ref")
def spmm_blockell_ref(ell: BlockELL, h, *, out_dtype=None):
    """Reference Y = A @ H with A in Block-ELL.

    ell.blocks: [nbr, W, bm, bn]; ell.indices: [nbr, W]; h: [N, D].
    Padded ELL slots carry zero blocks, so gathering an arbitrary (valid)
    H tile for them is harmless — same contract as the Pallas kernel.
    """
    nbr, w, bm, bn = ell.blocks.shape
    n, d = h.shape
    assert n == ell.shape[1], (n, ell.shape)
    h_blocks = h.reshape(n // bn, bn, d)
    gathered = h_blocks[ell.indices]  # [nbr, W, bn, D]
    acc = jnp.einsum(
        "rwmn,rwnd->rmd",
        ell.blocks.astype(jnp.float32),
        gathered.astype(jnp.float32),
    )
    out_dtype = out_dtype or ell.blocks.dtype
    return acc.reshape(nbr * bm, d).astype(out_dtype)
