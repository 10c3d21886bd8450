"""Public jit'd wrapper for Block-COO SDDMM."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.formats import BlockCOO
from repro.kernels.sddmm.kernel import sddmm_blockcoo_kernel
from repro.kernels.sddmm.ref import sddmm_blockcoo_ref


def _pick_bk(k: int) -> int:
    for cand in (512, 256, 128):
        if k % cand == 0:
            return cand
    return k  # tiny contraction dim (paper uses d=2 for GAT scores)


def sddmm_blockcoo(
    coo: BlockCOO,
    b,
    c,
    *,
    bk: int | None = None,
    out_dtype=None,
    use_kernel: bool = True,
    interpret: bool = False,
) -> BlockCOO:
    """Y = A ⊙ (B @ C), computed only at A's nonzero blocks."""
    out_dtype = out_dtype or jnp.result_type(coo.blocks.dtype, b.dtype)
    if not use_kernel:
        return sddmm_blockcoo_ref(coo, b, c, out_dtype=out_dtype)
    k = b.shape[1]
    bk = bk or _pick_bk(k)
    if k % bk != 0:
        raise ValueError(f"K={k} not divisible by bk={bk}")
    out_blocks = sddmm_blockcoo_kernel(
        coo.rows, coo.cols, coo.blocks, b, c.T,
        bk=bk, out_dtype=out_dtype, interpret=interpret,
    )
    return BlockCOO(
        rows=coo.rows, cols=coo.cols, blocks=out_blocks, shape=coo.shape
    )
