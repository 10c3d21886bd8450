"""Sparsity-adaptive planning for the sparse ops (the paper's crossover).

This package plans; ``repro.sparse`` (``SparseMatrix`` and
``repro.sparse.ops``) is the one entry point that executes.  See
DESIGN.md for the policy, cost-model inputs, and autotune cache key.
"""
from repro.dispatch.autotune import (AutotuneCache, GLOBAL_CACHE, calibrate,
                                     make_key, measure)
from repro.dispatch.cost_model import CostModel, DEFAULT_COST_MODEL
from repro.dispatch.dispatcher import (Plan, clear_log, dispatch_log,
                                       last_plan, log_capacity,
                                       plan_fused_attention, plan_sddmm,
                                       plan_spmm, record_plan,
                                       set_log_capacity)
from repro.dispatch.policy import (DEFAULT_CONFIG, DispatchConfig, PATHS,
                                   PATH_CSR, PATH_DENSE, PATH_ELL,
                                   PATH_FUSED_ATTN, PATH_SELL, POLICIES,
                                   POLICY_AUTO, POLICY_AUTOTUNE,
                                   normalize_policy)
from repro.dispatch.stats import MatrixStats, sparsity_bucket

__all__ = [
    "AutotuneCache", "GLOBAL_CACHE", "calibrate", "make_key", "measure",
    "CostModel", "DEFAULT_COST_MODEL",
    "Plan", "clear_log", "dispatch_log", "last_plan", "log_capacity",
    "plan_fused_attention", "plan_sddmm", "plan_spmm", "record_plan",
    "set_log_capacity",
    "DEFAULT_CONFIG", "DispatchConfig", "PATHS", "PATH_CSR", "PATH_DENSE",
    "PATH_ELL", "PATH_FUSED_ATTN", "PATH_SELL", "POLICIES", "POLICY_AUTO",
    "POLICY_AUTOTUNE", "normalize_policy",
    "MatrixStats", "sparsity_bucket",
]
