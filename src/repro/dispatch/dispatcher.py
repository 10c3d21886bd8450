"""Sparsity-adaptive planning for SpMM, SpMV, SDDMM and fused attention.

The planner behind ``repro.sparse.ops``, the one sparse front end.  A
plan is made in two steps:

  1. **Stats** — host-side structure statistics of the sparse operand
     (density, stored/padded stream volume, ELL occupancy), carried as
     static ``MatrixStats`` by every ``SparseMatrix``.
  2. **Plan** — a ``Plan`` naming the execution path, chosen by (a) an
     explicit policy ("ell" / "sell" / "csr" / "dense") or (b) the
     analytic cost model ("auto").  The timed autotune pass
     ("autotune") lives in ``repro.sparse.ops``, which owns the
     operands it times; pure planning prices it with the cost model.

Planning reads only static stats, so it is safe at ``jax.jit`` trace
time: the GNN layer plans inside its traced program.
``repro.sparse.ops`` executes the plan and appends it to the dispatch
log kept here.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Dict, Optional, Tuple

import jax

from repro import obs

from repro.dispatch.cost_model import (DEFAULT_COST_MODEL,
                                       DEVICE_COST_MODELS, CostModel)
from repro.dispatch.policy import (DEFAULT_CONFIG, DispatchConfig, PATHS,
                                   PATH_CSR, PATH_DENSE, PATH_ELL,
                                   PATH_FUSED_ATTN, PATH_SELL, POLICY_AUTO,
                                   POLICY_AUTOTUNE, normalize_policy)
from repro.dispatch.stats import MatrixStats


@dataclasses.dataclass(frozen=True)
class Plan:
    """One resolved dispatch decision (also the reporting record)."""

    op: str                      # "spmm" | "sddmm" | "fused_attn"
    path: str                    # ell | sell | csr | dense
    policy: str                  # policy that produced this plan
    reason: str                  # human-readable why
    use_kernel: bool             # ell path only: Pallas kernel vs jnp ref
    interpret: bool
    costs: Optional[Dict[str, float]] = None       # analytic model output
    timings_us: Optional[Dict[str, float]] = None  # autotune output
    stats: Optional[MatrixStats] = None
    # fused-pipeline tag: the epilogue description for a fused SpMM
    # ("relu+bias"), "attn" for the one-pass attention; None = unfused
    fused: Optional[str] = None

    def describe(self) -> str:
        extra = ""
        if self.fused is not None:
            extra += f" fused={self.fused}"
        if self.stats is not None:
            extra += (f" density={self.stats.density:.2e}"
                      f" blowup={self.stats.padded_stream_blowup:.1f}")
        return f"{self.op}->{self.path} [{self.policy}: {self.reason}]{extra}"


# Bounded record of recent decisions, for benchmarks / engines to report.
# Serving worker threads append concurrently with benchmark readers, so
# every access goes through the lock; the ring's capacity is explicit
# and adjustable (shrinking drops the oldest entries).
DEFAULT_LOG_CAPACITY = 256

_LOG_LOCK = threading.Lock()
_LOG: "collections.deque[Plan]" = collections.deque(
    maxlen=DEFAULT_LOG_CAPACITY)


def dispatch_log() -> Tuple[Plan, ...]:
    with _LOG_LOCK:
        return tuple(_LOG)


def last_plan(op: Optional[str] = None) -> Optional[Plan]:
    with _LOG_LOCK:
        for plan in reversed(_LOG):
            if op is None or plan.op == op:
                return plan
    return None


def clear_log() -> None:
    with _LOG_LOCK:
        _LOG.clear()


def log_capacity() -> int:
    return _LOG.maxlen or 0


def set_log_capacity(capacity: int) -> None:
    """Resize the plan ring (keeps the newest entries that still fit)."""
    global _LOG
    capacity = int(capacity)
    if capacity < 1:
        raise ValueError(f"log capacity must be >= 1, got {capacity}")
    with _LOG_LOCK:
        _LOG = collections.deque(_LOG, maxlen=capacity)


def record_plan(plan: Plan) -> Plan:
    """Append an executed plan to the dispatch log (reporting)."""
    with _LOG_LOCK:
        _LOG.append(plan)
    obs.counter("dispatch_plans_total", op=plan.op, path=plan.path,
                policy=plan.policy).inc()
    return plan


def default_use_kernel(config: DispatchConfig = DEFAULT_CONFIG) -> bool:
    """The Pallas kernel on TPU, the jnp reference elsewhere, unless the
    config forces one (the one backend rule every sparse path follows)."""
    if config.use_kernel is not None:
        return config.use_kernel
    return jax.default_backend() == "tpu"


def default_cost_model() -> CostModel:
    """The cost constants fitted on the running device's kind
    (``DEVICE_COST_MODELS``), the shipped constants on any other."""
    return DEVICE_COST_MODELS.get(jax.devices()[0].device_kind,
                                  DEFAULT_COST_MODEL)


# ---------------------------------------------------------------------------
# Planning (pure decision; usable at trace time from static stats)
# ---------------------------------------------------------------------------


def plan_spmm(
    stats: MatrixStats,
    d: int,
    *,
    policy: str = POLICY_AUTO,
    cost_model: Optional[CostModel] = None,
    config: DispatchConfig = DEFAULT_CONFIG,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
    candidates: Optional[Tuple[str, ...]] = None,
) -> Plan:
    """Pure planning from static stats (safe at jit trace time).

    ``candidates`` restricts the choice to the paths the caller can
    actually execute (e.g. a Graph carries only the ell + csr forms).
    ``cost_model`` None prices with ``default_cost_model()``.
    """
    return _plan("spmm",
                 lambda cm, tiles: cm.spmm_costs(stats, d, sell_tiles=tiles),
                 stats, cost_model=cost_model, policy=policy, config=config,
                 use_kernel=use_kernel, interpret=interpret,
                 candidates=candidates)


def plan_spmv(
    stats: MatrixStats,
    *,
    policy: str = POLICY_AUTO,
    cost_model: Optional[CostModel] = None,
    config: DispatchConfig = DEFAULT_CONFIG,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
    candidates: Optional[Tuple[str, ...]] = None,
) -> Plan:
    """Plan y = A @ x for a vector operand (SpMM at d = 1).

    The cost surface is the SpMM one evaluated at unit feature width —
    with no D to amortize the stream over, the scalar paths close most
    of their per-element disadvantage and hyper-sparse operands tip to
    csr much earlier.  A dedicated op tag keeps the dispatch log honest
    about which front-end ran.  SpMV's sell lane (``paths.spmv_sell``)
    reduces slot by slot on every backend, so it is priced by slots.
    """
    return _plan("spmv", lambda cm, _tiles: cm.spmm_costs(stats, 1),
                 stats, cost_model=cost_model, policy=policy, config=config,
                 use_kernel=use_kernel, interpret=interpret,
                 candidates=candidates, sell_tiles=False)


def plan_sddmm(
    stats: MatrixStats,
    k: int,
    *,
    policy: str = POLICY_AUTO,
    cost_model: Optional[CostModel] = None,
    config: DispatchConfig = DEFAULT_CONFIG,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
    candidates: Optional[Tuple[str, ...]] = None,
) -> Plan:
    return _plan("sddmm",
                 lambda cm, tiles: cm.sddmm_costs(stats, k, sell_tiles=tiles),
                 stats, cost_model=cost_model, policy=policy, config=config,
                 use_kernel=use_kernel, interpret=interpret,
                 candidates=candidates)


def plan_fused_attention(
    stats: MatrixStats,
    k: int,
    d: int,
    *,
    policy: str = POLICY_AUTO,
    cost_model: Optional[CostModel] = None,
    config: DispatchConfig = DEFAULT_CONFIG,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
    candidates: Optional[Tuple[str, ...]] = None,
) -> Plan:
    """Plan the one-pass fused SDDMM→softmax→SpMM attention pipeline.

    ``k`` is the score inner width (the SDDMM's K), ``d`` the value
    feature width (the SpMM's D).  The fused pipeline streams the
    topology once at combined width ``k + d`` — see
    ``CostModel.fused_attn_costs`` — instead of the unfused
    composition's three passes, so the layout choice is made on the
    single-stream cost surface.
    """
    plan = _plan(PATH_FUSED_ATTN,
                 lambda cm, tiles: cm.fused_attn_costs(stats, k, d,
                                                       sell_tiles=tiles),
                 stats, cost_model=cost_model, policy=policy, config=config,
                 use_kernel=use_kernel, interpret=interpret,
                 candidates=candidates)
    return dataclasses.replace(
        plan, fused="attn",
        reason=plan.reason if plan.policy in PATHS
        else f"one-stream fused pricing (k={k}, d={d}): {plan.reason}")


def _sell_basis(stats: MatrixStats, tiles: bool) -> str:
    """What the sell price counted, for the plan's reason."""
    if not tiles:
        return f"{stats.sell_stored_elements} slots"
    n = stats.sell_tile_elements // max(stats.block_m * stats.block_n, 1)
    return f"{n} tiles" if stats.sell_live_tiles > 0 else f"<={n} tiles"


def _plan(op, price, stats, *, cost_model, policy, config, use_kernel,
          interpret, candidates=None, sell_tiles=None) -> Plan:
    """``price(cost_model, sell_tiles)`` gives the per-path costs; the
    sell path is priced by its tiles when it runs the Pallas kernel
    (``sell_tiles`` None: whenever the kernel or interpret mode runs)."""
    policy = normalize_policy(policy)
    if policy == POLICY_AUTOTUNE:
        # pure planning cannot time candidates; be honest about what ran
        policy = POLICY_AUTO
    uk = use_kernel if use_kernel is not None \
        else default_use_kernel(config)
    if sell_tiles is None:
        sell_tiles = bool(uk or interpret)
    cm = cost_model if cost_model is not None else default_cost_model()
    costs = price(cm, sell_tiles)
    if candidates:
        costs = {p: c for p, c in costs.items() if p in candidates}
    if policy in (PATH_ELL, PATH_SELL, PATH_CSR, PATH_DENSE):
        if candidates and policy not in candidates:
            raise ValueError(
                f"policy {policy!r} not among available paths {candidates}")
        return Plan(op=op, path=policy, policy=policy, reason="forced",
                    use_kernel=uk, interpret=interpret, costs=costs,
                    stats=stats)
    path = CostModel.pick(costs)
    reason = (f"cost model: {path} cheapest of "
              + ", ".join(f"{p}={c:.3g}"
                          + (f" ({_sell_basis(stats, sell_tiles)})"
                             if p == PATH_SELL else "")
                          for p, c in sorted(costs.items())))
    return Plan(op=op, path=path, policy=policy, reason=reason,
                use_kernel=uk, interpret=interpret, costs=costs, stats=stats)
