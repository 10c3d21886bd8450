"""Paper Fig. 10: SDDMM speedup vs density, with the mnz (max_nonzeros
per worker tile) sensitivity — here the Block-COO ``pad_to`` analog.

The paper's GAT setting: d=2 (source/destination attention scores),
64x64 tiles per worker.  CPU baseline = dense B@C then mask (SciPy);
accelerator path = element-COO SDDMM (compute only sampled entries).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core.formats import BlockCOO
from repro.core.sddmm import sddmm_coo
from repro.data.pipeline import random_sparse_dense
from repro.launch.roofline import HBM_BW, PEAK_FLOPS

D = 2  # paper §4.4: GAT attention-score dimension


def run(quick: bool = True, policy: str = "auto", api: str = "sparse",
        cost_model=None):
    from repro.dispatch import last_plan
    from repro.dispatch.dispatcher import dispatch_sddmm
    from repro.sparse import SparseMatrix
    from repro.sparse import sddmm as sparse_sddmm

    ns = [2048, 4096] if quick else [2048, 4096, 8192]
    # sparsities 0.999 / 0.99 / 0.9 / 0.5 — the BENCH_kernels.json axis
    densities = [1e-3, 1e-2, 1e-1, 0.5]
    for n in ns:
        b = random_sparse_dense(n, 1.0, seed=3, m=n)[:, :D].copy()
        c = random_sparse_dense(n, 1.0, seed=4, m=D)[:D, :].copy()
        for density in densities:
            if density >= 0.5 and n > 2048 and quick:
                continue  # near-dense points stay small in quick mode
            mask = random_sparse_dense(n, density, seed=23) != 0
            rows, cols = np.nonzero(mask)
            jb, jc = jnp.asarray(b), jnp.asarray(c)
            jr = jnp.asarray(rows.astype(np.int32))
            jcl = jnp.asarray(cols.astype(np.int32))

            def dense_sample():
                return np.where(mask, b @ c, 0.0)

            t_cpu = time_fn(dense_sample, warmup=1, iters=3)
            f = jax.jit(lambda r, cc, bb, ccm: sddmm_coo(r, cc, bb, ccm))
            t_coo = time_fn(f, jr, jcl, jb, jc, warmup=2, iters=5)
            emit(f"sddmm_n{n}_d{density:g}_dense_cpu", t_cpu, "")
            emit(f"sddmm_n{n}_d{density:g}_coo_cpu", t_coo,
                 f"speedup_vs_dense={t_cpu / t_coo:.2f}")

            # the dispatch layer's pick under the requested policy
            if api == "legacy":
                coo_a = BlockCOO.from_dense(mask.astype(np.float32), 64, 64)
                t_disp = time_fn(
                    lambda: dispatch_sddmm(coo_a, jb, jc,
                                           policy=policy).blocks,
                    warmup=1, iters=5)
            else:
                A = SparseMatrix.from_dense(mask.astype(np.float32),
                                            formats=("coo", "csr"))
                t_disp = time_fn(
                    lambda: sparse_sddmm(A, jb, jc, policy=policy,
                                         cost_model=cost_model).data,
                    warmup=1, iters=5)
            plan = last_plan("sddmm")
            emit(f"sddmm_n{n}_d{density:g}_dispatch_{policy}_{api}", t_disp,
                 f"chosen={plan.path};policy={plan.policy}")

            # mnz sensitivity: Block-COO tile padding overhead (paper: a
            # larger mnz means more device->host bytes for the same work)
            nnz = len(rows)
            for mnz_factor in (1.0, 2.0):
                coo = BlockCOO.from_dense(
                    mask.astype(np.float32), 64, 64,
                    pad_to=int(max(1, mask.reshape(
                        n // 64, 64, n // 64, 64).transpose(0, 2, 1, 3)
                        .reshape(n // 64, n // 64, -1).any(-1).sum()
                        * mnz_factor)))
                bytes_ = coo.blocks.size * 4 + coo.rows.size * 8
                flops = 2.0 * coo.nnzb * 64 * 64 * D
                proj = max(flops / PEAK_FLOPS, bytes_ / HBM_BW)
                emit(f"sddmm_n{n}_d{density:g}_mnzx{mnz_factor:g}"
                     "_tpu_projected", proj * 1e6,
                     f"nnzb={coo.nnzb};bytes={bytes_}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--policy", default="auto",
                    choices=["auto", "autotune", "ell", "sell", "csr",
                             "dense"])
    ap.add_argument("--api", default="sparse", choices=["legacy", "sparse"],
                    help="dispatch surface: legacy free functions or the "
                         "unified SparseMatrix front-end")
    args = ap.parse_args()
    run(quick=args.quick, policy=args.policy, api=args.api)
