"""Sparsity-adaptive dispatch for SpMM and SDDMM.

Every public sparse matmul in the repo routes through here.  A call is
resolved in three steps:

  1. **Stats** — host-side structure statistics of the sparse operand
     (density, stored/padded stream volume, ELL occupancy).
  2. **Plan** — a ``Plan`` naming the execution path, chosen by (a) an
     explicit policy ("ell" / "csr" / "dense"), (b) the analytic cost
     model ("auto"), or (c) a timed autotune pass with a per-(shape,
     dtype, sparsity-bucket) cache ("autotune").
  3. **Execute** — run the chosen path.  The blocked path further
     resolves kernel-vs-reference: the Pallas kernel on TPU backends (or
     when explicitly requested / interpreted), the jnp reference
     elsewhere.

Plans are host decisions: under ``jax.jit`` the operand's arrays are
tracers, so callers either dispatch outside jit (the serving engine
does) or plan once from static ``MatrixStats`` carried in pytree aux
metadata (the GNN layer does).  A traced operand with policy "auto"
falls back to the blocked path — the only one that needs no host
conversion — and records the fallback in the plan's reason.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from repro.core.formats import BlockCOO, BlockELL
from repro.dispatch import autotune as autotune_mod
from repro.dispatch._forms import LazyForms
from repro.dispatch.autotune import AutotuneCache, make_key, measure
from repro.dispatch.cost_model import (DEFAULT_COST_MODEL,
                                       DEVICE_COST_MODELS, CostModel)
from repro.dispatch.policy import (DEFAULT_CONFIG, DispatchConfig, PATHS,
                                   PATH_CSR, PATH_DENSE, PATH_ELL,
                                   PATH_FUSED_ATTN, PATH_SELL, POLICY_AUTO,
                                   POLICY_AUTOTUNE, normalize_policy)
from repro.dispatch.stats import MatrixStats

Array = Any


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


def _record(plan: Plan) -> Plan:
    with _LOG_LOCK:
        _LOG.append(plan)
    obs.counter("dispatch_plans_total", op=plan.op, path=plan.path,
                policy=plan.policy).inc()
    return plan


def record_plan(plan: Plan) -> Plan:
    """Append an externally-made plan to the dispatch log (reporting)."""
    return _record(plan)


def _audit_run(plan: Plan, run):
    """Execute ``run()`` and record predicted-vs-measured in the audit.

    Timing blocks on the result (cheap: callers materialize it anyway);
    traced outputs (a concrete operand dispatched under jit over the
    dense side) cannot be timed and are skipped.
    """
    t0 = time.perf_counter()
    out = run()
    if not _is_traced(*jax.tree_util.tree_leaves(out)):
        try:
            jax.block_until_ready(out)
        except Exception:  # non-array leaves: time without the barrier
            pass
        obs.AUDIT.record(plan, (time.perf_counter() - t0) * 1e3)
    return out


def _is_traced(*arrays) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in arrays)


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


# ---------------------------------------------------------------------------
# SpMM dispatch
# ---------------------------------------------------------------------------


def _as_spmm_operand(a) -> Tuple[Optional[LazyForms], Optional[BlockELL]]:
    """Returns (operand, raw_ell).  operand is None for traced input."""
    from repro.sparse.matrix import SparseMatrix

    if isinstance(a, SparseMatrix):
        if "ell" in a.formats:
            return LazyForms.from_blockell(a.form("ell")), None
        return LazyForms.from_dense(a.to_dense()), None
    if isinstance(a, LazyForms):
        return a, None
    if isinstance(a, BlockELL):
        if _is_traced(a.blocks, a.indices):
            return None, a
        return LazyForms.from_blockell(a), None
    arr = np.asarray(a) if not _is_traced(a) else None
    if arr is None:
        raise TypeError(
            "dispatch_spmm: traced dense operand; pass a BlockELL (blocked "
            "fallback) or plan outside jit with plan_spmm + static stats")
    return LazyForms.from_dense(arr), None


def _run_spmm_path(path: str, op: LazyForms, h, *, use_kernel: bool,
                   interpret: bool, bd=None, out_dtype=None):
    from repro.kernels.spmm.ops import spmm_blockell
    from repro.sparse.paths import spmm_dense
    from repro.sparse.paths import spmm_elements as spmm_csr

    m = op.shape[0]
    if h.shape[0] != op.shape[1]:
        raise ValueError(
            f"spmm: H has {h.shape[0]} rows but A has {op.shape[1]} "
            f"columns (A shape {op.shape})")
    if path == PATH_ELL:
        ell = op.ell()
        n_pad = ell.shape[1]
        hh = h
        if h.shape[0] != n_pad:  # operand narrower than its block padding
            hh = jnp.zeros((n_pad,) + h.shape[1:], h.dtype) \
                .at[: h.shape[0]].set(h)
        y = spmm_blockell(ell, hh, bd=bd, out_dtype=out_dtype,
                          use_kernel=use_kernel or interpret,
                          interpret=interpret)
        return y[:m]
    if path == PATH_CSR:
        row_ids, col_ids, values = op.csr_arrays()
        y = spmm_csr(row_ids, col_ids, values, h[: op.shape[1]], m)
        return y.astype(out_dtype) if out_dtype else y
    if path == PATH_DENSE:
        y = spmm_dense(op.dense_jnp(), h[: op.shape[1]])
        return y.astype(out_dtype) if out_dtype else y
    raise ValueError(f"unknown spmm path {path!r}")


def dispatch_spmm(
    a,
    h,
    *,
    policy: str = POLICY_AUTO,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    bd: Optional[int] = None,
    out_dtype=None,
    cost_model: Optional[CostModel] = None,
    config: DispatchConfig = DEFAULT_CONFIG,
    cache: Optional[AutotuneCache] = None,
):
    """Y = A @ H through the sparsity-adaptive dispatch layer.

    ``a``: BlockELL, SparseMatrix, SparseOperand, or a concrete dense
    matrix.  Explicit ``use_kernel``/``interpret`` force the blocked
    path (the legacy kwarg rule, consolidated in
    ``repro.sparse.legacy.coerce_kernel_kwargs``).
    """
    from repro.sparse.legacy import coerce_kernel_kwargs

    policy, use_kernel, interpret, _ = coerce_kernel_kwargs(
        policy, use_kernel, interpret)
    h_was_1d = h.ndim == 1
    if h_was_1d:
        h = h[:, None]
    operand, raw_ell = _as_spmm_operand(a)

    if operand is None:  # traced BlockELL: blocked path is the only option
        from repro.kernels.spmm.ops import spmm_blockell

        if policy in (PATH_SELL, PATH_CSR, PATH_DENSE):
            raise TypeError(
                f"dispatch_spmm: policy {policy!r} needs host-visible "
                "operand data, but the BlockELL is traced (inside jit); "
                "dispatch outside jit or use the ell path")
        uk = use_kernel if use_kernel is not None \
            else default_use_kernel(config)
        _record(Plan(op="spmm", path=PATH_ELL, policy=policy,
                     reason="traced operand: blocked path only",
                     use_kernel=uk, interpret=interpret))
        return spmm_blockell(raw_ell, h, bd=bd, out_dtype=out_dtype,
                             use_kernel=uk or interpret,
                             interpret=interpret)

    d = h.shape[1]
    if policy in (PATH_ELL, PATH_CSR, PATH_DENSE):
        # forced path: no stats needed (skips the host nonzero count)
        uk = use_kernel if use_kernel is not None \
            else default_use_kernel(config)
        plan = Plan(op="spmm", path=policy, policy=policy, reason="forced",
                    use_kernel=uk, interpret=interpret)
        _record(plan)
        y = _audit_run(plan, lambda: _run_spmm_path(
            policy, operand, h, use_kernel=uk, interpret=interpret,
            bd=bd, out_dtype=out_dtype))
        return y[:, 0] if h_was_1d else y

    stats = operand.stats()

    if policy == POLICY_AUTOTUNE:
        cache = cache if cache is not None else autotune_mod.GLOBAL_CACHE
        key = make_key("spmm", stats.shape, d, h.dtype, stats.density,
                       buckets_per_decade=config.buckets_per_decade)
        uk = use_kernel if use_kernel is not None \
            else default_use_kernel(config)
        hit = cache.get(key)
        if hit is None:
            candidates = {
                p: (lambda p=p: _run_spmm_path(
                    p, operand, h, use_kernel=uk, interpret=interpret,
                    bd=bd, out_dtype=out_dtype))
                for p in (PATH_ELL, PATH_CSR, PATH_DENSE)
            }
            hit = measure(candidates, warmup=config.autotune_warmup,
                          iters=config.autotune_iters)
            cache.put(key, hit)
            reason = "autotune: measured " + ", ".join(
                f"{p}={t:.0f}us" for p, t in sorted(hit.timings_us.items()))
        else:
            reason = "autotune: cached winner"
        plan = Plan(op="spmm", path=hit.path, policy=POLICY_AUTOTUNE,
                    reason=reason, use_kernel=uk,
                    interpret=interpret, timings_us=hit.timings_us,
                    stats=stats)
    else:
        # the legacy LazyForms operand carries no sell packing, so the
        # SELL-C-σ path is not a candidate here (SparseMatrix is)
        plan = plan_spmm(stats, d, policy=policy, cost_model=cost_model,
                         config=config, use_kernel=use_kernel,
                         interpret=interpret,
                         candidates=(PATH_ELL, PATH_CSR, PATH_DENSE))
    _record(plan)
    y = _audit_run(plan, lambda: _run_spmm_path(
        plan.path, operand, h, use_kernel=plan.use_kernel,
        interpret=plan.interpret, bd=bd, out_dtype=out_dtype))
    return y[:, 0] if h_was_1d else y


# ---------------------------------------------------------------------------
# SDDMM dispatch
# ---------------------------------------------------------------------------


def _coo_element_coords(coo: BlockCOO):
    """Host-side element coordinates of a concrete BlockCOO's nonzeros."""
    blocks = np.asarray(coo.blocks)
    rows = np.asarray(coo.rows)
    cols = np.asarray(coo.cols)
    e, i, j = np.nonzero(blocks)
    gr = rows[e] * coo.bm + i
    gc = cols[e] * coo.bn + j
    return e, i, j, gr.astype(np.int32), gc.astype(np.int32)


def _run_sddmm_path(path: str, coo: BlockCOO, b, c, *, use_kernel: bool,
                    interpret: bool, bk=None, out_dtype=None) -> BlockCOO:
    from repro.kernels.sddmm.ops import sddmm_blockcoo
    from repro.sparse.paths import sddmm_element_dots as sddmm_coo

    if path == PATH_ELL:
        return sddmm_blockcoo(coo, b, c, bk=bk, out_dtype=out_dtype,
                              use_kernel=use_kernel or interpret,
                              interpret=interpret)
    out_dtype = out_dtype or jnp.result_type(coo.blocks.dtype, b.dtype)
    if path == PATH_CSR:
        e, i, j, gr, gc = _coo_element_coords(coo)
        dots = sddmm_coo(jnp.asarray(gr), jnp.asarray(gc), b, c)
        vals = (jnp.asarray(np.asarray(coo.blocks)[e, i, j])
                .astype(jnp.float32) * dots.astype(jnp.float32))
        out_blocks = jnp.zeros(coo.blocks.shape, jnp.float32) \
            .at[e, i, j].set(vals).astype(out_dtype)
        return BlockCOO(rows=coo.rows, cols=coo.cols, blocks=out_blocks,
                        shape=coo.shape)
    if path == PATH_DENSE:
        m, n = coo.shape
        bm, bn = coo.bm, coo.bn
        full = b.astype(jnp.float32) @ c.astype(jnp.float32)  # [M, N]
        tiles = full.reshape(m // bm, bm, n // bn, bn).transpose(0, 2, 1, 3)
        gathered = tiles[coo.rows, coo.cols]  # [nnzb, bm, bn]
        out_blocks = (coo.blocks.astype(jnp.float32)
                      * gathered).astype(out_dtype)
        return BlockCOO(rows=coo.rows, cols=coo.cols, blocks=out_blocks,
                        shape=coo.shape)
    raise ValueError(f"unknown sddmm path {path!r}")


def dispatch_sddmm(
    a,
    b,
    c,
    *,
    policy: str = POLICY_AUTO,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    bk: Optional[int] = None,
    out_dtype=None,
    cost_model: Optional[CostModel] = None,
    config: DispatchConfig = DEFAULT_CONFIG,
    cache: Optional[AutotuneCache] = None,
) -> BlockCOO:
    """Y = A (.) (B @ C) through the dispatch layer; returns BlockCOO.

    ``a``: BlockCOO (mask/values of A) or a concrete dense matrix, which
    is tiled with 64x64 blocks.  Path vocabulary matches SpMM: "ell" is
    the blocked (Block-COO) path, "csr" the element-COO path, "dense"
    the full-product-then-sample fallback.
    """
    from repro.sparse.legacy import coerce_kernel_kwargs

    policy, use_kernel, interpret, _ = coerce_kernel_kwargs(
        policy, use_kernel, interpret)
    if not isinstance(a, BlockCOO):
        from repro.sparse.matrix import SparseMatrix

        if isinstance(a, SparseMatrix):
            a = a.form("coo") if "coo" in a.formats \
                else BlockCOO.from_dense(a.to_dense(), 64, 64)
        elif _is_traced(a):
            raise TypeError("dispatch_sddmm: traced dense operand")
        else:
            a = BlockCOO.from_dense(np.asarray(a), 64, 64)

    # A's BlockCOO shape is block-padded; pad B/C to match so every path
    # (block reshape, element gather, dense product) sees aligned shapes.
    # The padded regions of A are zero, so they contribute nothing.
    mp, np_pad = a.shape
    if b.shape[0] != mp:
        if b.shape[0] > mp:
            raise ValueError(
                f"sddmm: B has {b.shape[0]} rows but A has {mp}")
        b = jnp.zeros((mp, b.shape[1]), b.dtype).at[: b.shape[0]].set(b)
    if c.shape[1] != np_pad:
        if c.shape[1] > np_pad:
            raise ValueError(
                f"sddmm: C has {c.shape[1]} columns but A has {np_pad}")
        c = jnp.zeros((c.shape[0], np_pad), c.dtype) \
            .at[:, : c.shape[1]].set(c)

    traced = _is_traced(a.blocks, a.rows, a.cols)
    uk = use_kernel if use_kernel is not None else default_use_kernel(config)
    if traced:  # blocked path is the only tracer-safe one
        if policy in (PATH_SELL, PATH_CSR, PATH_DENSE):
            raise TypeError(
                f"dispatch_sddmm: policy {policy!r} needs host-visible "
                "operand data, but the BlockCOO is traced (inside jit); "
                "dispatch outside jit or use the ell path")
        _record(Plan(op="sddmm", path=PATH_ELL, policy=policy,
                     reason="traced operand: blocked path only",
                     use_kernel=uk, interpret=interpret))
        return _run_sddmm_path(PATH_ELL, a, b, c, use_kernel=uk,
                               interpret=interpret, bk=bk,
                               out_dtype=out_dtype)

    k = b.shape[1]
    if policy in (PATH_ELL, PATH_CSR, PATH_DENSE):
        # forced path: no stats needed (skips the host nonzero count)
        plan = Plan(op="sddmm", path=policy, policy=policy, reason="forced",
                    use_kernel=uk, interpret=interpret)
        _record(plan)
        return _audit_run(plan, lambda: _run_sddmm_path(
            policy, a, b, c, use_kernel=uk, interpret=interpret, bk=bk,
            out_dtype=out_dtype))

    stats = MatrixStats.from_blockcoo(a)

    if policy == POLICY_AUTOTUNE:
        cache = cache if cache is not None else autotune_mod.GLOBAL_CACHE
        key = make_key("sddmm", stats.shape, k, b.dtype, stats.density,
                       buckets_per_decade=config.buckets_per_decade)
        hit = cache.get(key)
        if hit is None:
            candidates = {
                p: (lambda p=p: _run_sddmm_path(
                    p, a, b, c, use_kernel=uk, interpret=interpret,
                    bk=bk, out_dtype=out_dtype).blocks)
                for p in (PATH_ELL, PATH_CSR, PATH_DENSE)
            }
            hit = measure(candidates, warmup=config.autotune_warmup,
                          iters=config.autotune_iters)
            cache.put(key, hit)
            reason = "autotune: measured " + ", ".join(
                f"{p}={t:.0f}us" for p, t in sorted(hit.timings_us.items()))
        else:
            reason = "autotune: cached winner"
        plan = Plan(op="sddmm", path=hit.path, policy=POLICY_AUTOTUNE,
                    reason=reason, use_kernel=uk, interpret=interpret,
                    timings_us=hit.timings_us, stats=stats)
    else:
        plan = plan_sddmm(stats, k, policy=policy, cost_model=cost_model,
                          config=config, use_kernel=use_kernel,
                          interpret=interpret,
                          candidates=(PATH_ELL, PATH_CSR, PATH_DENSE))
    _record(plan)
    return _audit_run(plan, lambda: _run_sddmm_path(
        plan.path, a, b, c, use_kernel=plan.use_kernel,
        interpret=plan.interpret, bk=bk, out_dtype=out_dtype))
