"""Paper Fig. 9: SpMM performance across density and N (d = 256).

The paper compares CS-3 CSL kernels against CPU (PyTorch sparse / SciPy).
Here the CPU baseline is SciPy CSR SpMM; the accelerator-format path is
the Block-ELL implementation (jnp reference math on CPU — the Pallas
kernel is the TPU target, validated by tests in interpret mode; its
roofline-projected time is derived from the byte/FLOP model).

Derived fields per cell:
  speedup      — SciPy CSR time / Block-ELL time on this CPU
  tpu_roofline — projected TPU time for the Block-ELL kernel:
                 max(flops/197TF, bytes/819GBs) with bytes from the padded
                 Block-ELL layout (the paper's footprint effect shows up
                 here exactly as its Fig. 9 hyper-sparsity cliff).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from benchmarks.common import emit, time_fn
from repro.core.formats import BlockELL
from repro.data.pipeline import random_sparse_dense
from repro.kernels.spmm.ref import spmm_blockell_ref
from repro.launch.roofline import HBM_BW, PEAK_FLOPS
from repro.sparse.paths import spmm_dense

D = 256  # paper §4.1


def tpu_projection(ell: BlockELL, d: int) -> float:
    """Roofline-projected kernel time on one v5e chip (seconds)."""
    nbr, w, bm, bn = ell.blocks.shape
    flops = 2.0 * nbr * w * bm * bn * d  # padded blocks compute too
    bytes_ = (ell.blocks.size * ell.blocks.dtype.itemsize
              + ell.indices.size * 4
              + nbr * w * bn * d * 2  # gathered H tiles (bf16)
              + nbr * bm * d * 4)  # f32 output
    return max(flops / PEAK_FLOPS, bytes_ / HBM_BW)


def run(quick: bool = True, policy: str = "auto", cost_model=None):
    from repro.dispatch import last_plan
    from repro.sparse import SparseMatrix, matmul

    ns = [2048, 4096] if quick else [2048, 4096, 8192, 16384]
    # sparsities 0.999 / 0.99 / 0.9 / 0.5 — the BENCH_kernels.json axis
    densities = [1e-3, 1e-2, 1e-1, 0.5]
    for n in ns:
        h = random_sparse_dense(n, 1.0, seed=7, m=n)[:, :D].copy()
        for density in densities:
            if density >= 0.5 and n > 2048 and quick:
                continue  # near-dense points stay small in quick mode
            dense = random_sparse_dense(n, density, seed=13)
            csr = sp.csr_matrix(dense)
            ell = BlockELL.from_dense(dense, bm=64, bn=64)

            t_csr = time_fn(lambda: csr @ h, warmup=1, iters=5)
            jh = jnp.asarray(h)
            blocked = jax.jit(lambda e, hh: spmm_blockell_ref(e, hh))
            t_ell = time_fn(blocked, ell, jh, warmup=2, iters=5)
            jd = jnp.asarray(dense)
            t_dense = time_fn(jax.jit(spmm_dense), jd, jh, warmup=1,
                              iters=3)
            proj = tpu_projection(ell, D)
            emit(f"spmm_n{n}_d{density:g}_csr_cpu", t_csr, "")
            emit(f"spmm_n{n}_d{density:g}_blockell_cpu", t_ell,
                 f"speedup_vs_csr={t_csr / t_ell:.2f};"
                 f"occupancy={ell.occupancy():.3f}")
            if density <= 1e-2:
                # the hyper-sparse regime the SELL-C-σ path targets
                from repro.core.formats import SellCS
                from repro.sparse.paths import spmm_sell_ref

                sell = SellCS.from_dense(dense, block=(64, 64))
                t_sell = time_fn(jax.jit(spmm_sell_ref), sell, jh,
                                 warmup=2, iters=5)
                emit(f"spmm_n{n}_d{density:g}_sell_cpu", t_sell,
                     f"speedup_vs_blockell={t_ell / t_sell:.2f};"
                     f"slots={sell.n_slots}")
            emit(f"spmm_n{n}_d{density:g}_dense_cpu", t_dense,
                 f"speedup_vs_dense={t_dense / t_ell:.2f}")
            emit(f"spmm_n{n}_d{density:g}_blockell_tpu_projected",
                 proj * 1e6,
                 f"projected_speedup_vs_cpu_csr={t_csr / (proj * 1e6):.1f}")

            # the dispatch layer's pick under the requested policy; the
            # steady state is the plan-cache hit path (plan once, then
            # execute)
            A = SparseMatrix.from_dense(dense, formats=("ell", "csr"))
            t_disp = time_fn(
                lambda: matmul(A, jh, policy=policy, cost_model=cost_model),
                warmup=1, iters=5)
            plan = last_plan("spmm")
            emit(f"spmm_n{n}_d{density:g}_dispatch_{policy}_sparse", t_disp,
                 f"chosen={plan.path};policy={plan.policy}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--policy", default="auto",
                    choices=["auto", "autotune", "ell", "sell", "csr",
                             "dense"])
    args = ap.parse_args()
    run(quick=args.quick, policy=args.policy)
