"""Unified differentiable sparse-matrix API.

One pytree-registered array type over the repo's sparse formats, with
operator dispatch through the sparsity-adaptive cost-model/autotune
machinery and ``custom_vjp`` gradients that realize the paper's kernel
duality (SpMM's backward is SDDMM and vice versa):

    from repro.sparse import SparseMatrix, sample

    A = SparseMatrix.from_dense(a, format="auto")   # measured structure
    y = A @ h                                       # SpMM, planned once
    s = sample(A.pattern(), b, c)                   # SDDMM at A's nnz
    g = jax.grad(lambda v: loss(A.with_data(v) @ h))(A.data)

This is the one sparse entry point: ``repro.dispatch`` only plans.
See DESIGN.md "Public API" for the conversion table, operator
semantics and gradient rules.
"""
from repro.kernels.fused.epilogue import Epilogue
from repro.sparse.matrix import FORMATS, SparseMatrix
from repro.sparse.ops import (available_paths, fused_graph_attention,
                              matmul, sample, sddmm, spmv)
from repro.sparse.plan import PlanCache, plan_cache_stats

__all__ = [
    "Epilogue", "FORMATS", "SparseMatrix",
    "available_paths", "fused_graph_attention", "matmul", "sample",
    "sddmm", "spmv",
    "PlanCache", "plan_cache_stats",
]
