"""Planned SpMM / SDDMM front-ends for ``SparseMatrix``.

``matmul`` (what ``A @ H`` calls) and ``sddmm`` (what ``A.sddmm(b, c)``
/ ``repro.sparse.sample`` call) resolve an execution path through the
sparsity-adaptive machinery in ``repro.dispatch`` — the analytic cost
model for ``policy="auto"``, the timed autotune cache for
``policy="autotune"``, or a forced path — then run the differentiable
``custom_vjp`` primitives in ``repro.sparse.autodiff``.

Plans are memoized per matrix instance (see ``repro.sparse.plan``):
the first call for a given (op, width, policy, dtype) plans, every
later call hits the memo and goes straight to execution.  Planning is
host logic over static ``MatrixStats`` aux metadata, so it happens at
``jax.jit`` trace time and is baked into the traced program.

Candidate paths follow the forms a matrix carries: ``ell`` (blocked)
needs an ``"ell"``/``"coo"`` form, ``sell`` (SELL-C-σ, the
hyper-sparsity path) a ``"sell"`` form, ``csr`` (element) a ``"csr"``
form; ``dense`` densifies on device and is always available.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

import dataclasses

from repro.dispatch import autotune as autotune_mod
from repro.dispatch.autotune import AutotuneCache, make_key, measure
from repro.dispatch.cost_model import CostModel
from repro.dispatch.dispatcher import (Plan, default_use_kernel,
                                       plan_fused_attention, plan_sddmm,
                                       plan_spmm, plan_spmv, record_plan)
from repro.dispatch.policy import (DEFAULT_CONFIG, DispatchConfig, PATHS,
                                   PATH_CSR, PATH_DENSE, PATH_ELL,
                                   PATH_FUSED_ATTN, PATH_SELL, POLICY_AUTO,
                                   POLICY_AUTOTUNE, normalize_policy)
from repro.kernels.fused.epilogue import normalize_epilogue
from repro.sparse import autodiff
from repro.sparse.matrix import SparseMatrix, with_values


def _is_traced(*operands) -> bool:
    return any(isinstance(x, jax.core.Tracer)
               for x in jax.tree_util.tree_leaves(list(operands)))


def available_paths(a: SparseMatrix) -> Tuple[str, ...]:
    """Execution paths the matrix's carried forms can run."""
    cand = []
    if "ell" in a._forms or "coo" in a._forms:
        cand.append(PATH_ELL)
    if "sell" in a._forms:
        cand.append(PATH_SELL)
    if "csr" in a._forms:
        cand.append(PATH_CSR)
    cand.append(PATH_DENSE)  # device densify works for every form
    return tuple(cand)


def _resolve_plan(op: str, a: SparseMatrix, inner_dim, ref_dtype,
                  policy: str, cand: Tuple[str, ...], uk: bool,
                  interpret: bool, cost_model: Optional[CostModel],
                  config: DispatchConfig,
                  autotune_cache: Optional[AutotuneCache],
                  exec_thunk, concrete: bool,
                  key_extra: Tuple = (),
                  fused: Optional[str] = None) -> Plan:
    """Resolve (and memoize) one dispatch plan.

    ``inner_dim`` is the operand feature width — an int for spmm/sddmm,
    a ``(k, d)`` pair for the fused attention op.  ``key_extra`` folds
    op-specific static config (e.g. the epilogue spec) into the memo
    key; ``fused`` tags the resulting plan for the dispatch log.
    """
    inner_key = tuple(int(x) for x in inner_dim) \
        if isinstance(inner_dim, tuple) else int(inner_dim)
    key = (op, inner_key, policy, str(ref_dtype), cand, uk, interpret,
           cost_model) + tuple(key_extra)
    if policy == POLICY_AUTOTUNE:
        # a trace-time autotune downgrades to the cost model; keep its
        # memo separate so it never masks a real (concrete) timing pass
        key += (concrete,)
    plan = a._cache.get(key)
    if plan is not None:
        return plan
    if policy in PATHS:
        if policy not in cand:
            raise ValueError(
                f"policy {policy!r} not among available paths {cand}")
        plan = Plan(op=op, path=policy, policy=policy, reason="forced",
                    use_kernel=uk, interpret=interpret, stats=a.stats)
    else:
        if a.stats is None:
            raise ValueError(
                f"{op}: matrix has no sparsity stats; construct it with "
                "SparseMatrix.from_dense/from_* (concrete) or force a "
                "path policy")
        # autotune must never time tracer thunks (it would cache trace-
        # construction time); any traced operand downgrades to the cost
        # model, exactly like plan_* does for pure planning
        if policy == POLICY_AUTOTUNE and concrete:
            cache = autotune_cache if autotune_cache is not None \
                else autotune_mod.GLOBAL_CACHE
            # the timing key must see the same static config as the plan
            # memo (a fused-epilogue thunk is a different computation),
            # stringified so the cache stays JSON-serializable
            akey = make_key(op, a.stats.shape, sum(inner_key)
                            if isinstance(inner_key, tuple) else inner_key,
                            ref_dtype, a.stats.density,
                            buckets_per_decade=config.buckets_per_decade) \
                + tuple(str(x) for x in key_extra)
            hit = cache.get(akey)
            if hit is None:
                hit = measure({p: exec_thunk(p) for p in cand},
                              warmup=config.autotune_warmup,
                              iters=config.autotune_iters)
                cache.put(akey, hit)
                reason = "autotune: measured " + ", ".join(
                    f"{p}={t:.0f}us"
                    for p, t in sorted(hit.timings_us.items()))
            else:
                reason = "autotune: cached winner"
            path = hit.path
            if path not in cand:  # cache shared across operands with
                finite = {p: t for p, t in hit.timings_us.items()
                          if p in cand}  # different carried forms
                path = min(finite, key=finite.get) if finite else cand[0]
            plan = Plan(op=op, path=path, policy=POLICY_AUTOTUNE,
                        reason=reason, use_kernel=uk, interpret=interpret,
                        timings_us=hit.timings_us, stats=a.stats)
        elif op == PATH_FUSED_ATTN:
            plan = plan_fused_attention(
                a.stats, inner_dim[0], inner_dim[1], policy=policy,
                cost_model=cost_model, config=config, use_kernel=uk,
                interpret=interpret, candidates=cand)
        elif op == "spmv":
            plan = plan_spmv(a.stats, policy=policy,
                             cost_model=cost_model, config=config,
                             use_kernel=uk, interpret=interpret,
                             candidates=cand)
        else:
            planner = plan_spmm if op == "spmm" else plan_sddmm
            plan = planner(a.stats, inner_dim, policy=policy,
                           cost_model=cost_model, config=config,
                           use_kernel=uk, interpret=interpret,
                           candidates=cand)
    if fused is not None and plan.fused != fused:
        plan = dataclasses.replace(plan, fused=fused)
    a._cache.put(key, plan)
    return plan


# ---------------------------------------------------------------------------
# SpMM
# ---------------------------------------------------------------------------


def matmul(
    a: SparseMatrix,
    h,
    *,
    policy: str = POLICY_AUTO,
    candidates: Optional[Tuple[str, ...]] = None,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
    bd: Optional[int] = None,
    out_dtype=None,
    epilogue=None,
    bias=None,
    residual=None,
    cost_model: Optional[CostModel] = None,
    config: DispatchConfig = DEFAULT_CONFIG,
    autotune_cache: Optional[AutotuneCache] = None,
):
    """Y = A @ H through the unified sparse front-end (differentiable).

    ``epilogue`` fuses an elementwise tail into the product:
    ``Y = act(A @ H + bias + residual)`` with ``act`` one of
    ``"identity" | "relu" | "leaky_relu"`` (or a full
    :class:`repro.kernels.fused.Epilogue` spec).  The kernel execution
    paths apply it to the VMEM accumulator before the single output
    flush; reference paths compose it elementwise — either way the raw
    product never makes a dedicated round-trip through memory, and the
    whole pipeline stays differentiable (bias/residual get cotangents).
    """
    if not isinstance(a, SparseMatrix):
        raise TypeError(f"matmul expects a SparseMatrix, got {type(a)}")
    h = jnp.asarray(h)
    h_was_1d = h.ndim == 1
    if h_was_1d and epilogue is None and bias is None and residual is None:
        # vector operand with no fused tail: take the SpMV fast lane
        # (direct per-layout reductions, no [N, 1] tile machinery)
        return spmv(a, h, policy=policy, candidates=candidates,
                    use_kernel=use_kernel, interpret=interpret,
                    out_dtype=out_dtype, cost_model=cost_model,
                    config=config, autotune_cache=autotune_cache)
    if h_was_1d:
        h = h[:, None]
        if residual is not None and jnp.ndim(residual) == 1:
            residual = residual[:, None]
    if h.ndim != 2:
        raise ValueError(f"spmm: H must be 1-D or 2-D, got shape {h.shape}")
    if h.shape[0] != a.shape[1]:
        raise ValueError(
            f"spmm: H has {h.shape[0]} rows but A has {a.shape[1]} "
            f"columns (A shape {a.shape})")
    if bias is not None:
        # canonicalize to a [D] vector (scalars broadcast) so every
        # execution path — and the bwd cotangent — sees one shape
        bias = jnp.asarray(bias)
        if bias.ndim == 0:
            bias = jnp.broadcast_to(bias, (h.shape[1],))
        if bias.shape != (h.shape[1],):
            raise ValueError(
                f"spmm epilogue: bias must be a scalar or a [{h.shape[1]}]"
                f" vector, got shape {bias.shape}")
    if residual is not None:
        residual = jnp.asarray(residual)
        if residual.shape != (a.shape[0], h.shape[1]):
            raise ValueError(
                f"spmm epilogue: residual must be output-shaped "
                f"[{a.shape[0]}, {h.shape[1]}], got {residual.shape}")
    epi = normalize_epilogue(epilogue, bias, residual)
    policy = normalize_policy(policy)
    cand = tuple(candidates) if candidates else available_paths(a)
    uk = use_kernel if use_kernel is not None else default_use_kernel(config)
    interpret = bool(interpret)
    odt = None if out_dtype is None else str(jnp.dtype(out_dtype))

    def exec_thunk(p):
        if epi is None:
            return lambda: autodiff.spmm_exec((p, uk, interpret, bd, odt),
                                              a, h)
        return lambda: autodiff.spmm_epilogue_exec(
            (p, uk, interpret, bd, odt, epi), a, h, bias, residual)

    plan = _resolve_plan("spmm", a, h.shape[1], h.dtype, policy, cand, uk,
                         interpret, cost_model, config, autotune_cache,
                         exec_thunk,
                         concrete=not _is_traced(a, h, bias, residual),
                         key_extra=() if epi is None else (epi,),
                         fused=None if epi is None else epi.describe())
    record_plan(plan)
    if epi is None:
        y = autodiff.spmm(
            (plan.path, plan.use_kernel, plan.interpret, bd, odt), a, h)
    else:
        y = autodiff.spmm_epilogue(
            (plan.path, plan.use_kernel, plan.interpret, bd, odt, epi),
            a, h, bias, residual)
    return y[:, 0] if h_was_1d else y


# ---------------------------------------------------------------------------
# SpMV
# ---------------------------------------------------------------------------


def spmv(
    a: SparseMatrix,
    x,
    *,
    policy: str = POLICY_AUTO,
    candidates: Optional[Tuple[str, ...]] = None,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
    out_dtype=None,
    cost_model: Optional[CostModel] = None,
    config: DispatchConfig = DEFAULT_CONFIG,
    autotune_cache: Optional[AutotuneCache] = None,
):
    """y = A @ x for a [N] vector, through the unified front-end.

    The dedicated d = 1 entry: plans on the SpMM cost surface at unit
    feature width (op tag ``"spmv"`` in the dispatch log) and executes
    direct per-layout reductions — no kernel grids, no D-padding, no
    epilogue plumbing.  ``matmul`` delegates its 1-D branch here, so
    ``A @ v`` gets this lane automatically.  Differentiable: the
    backward is the same SpMM duality at d = 1 (dx = Aᵀ ḡ, dA a rank-1
    SDDMM).
    """
    if not isinstance(a, SparseMatrix):
        raise TypeError(f"spmv expects a SparseMatrix, got {type(a)}")
    x = jnp.asarray(x)
    if x.ndim != 1:
        raise ValueError(f"spmv: x must be 1-D, got shape {x.shape}")
    if x.shape[0] != a.shape[1]:
        raise ValueError(
            f"spmv: x has {x.shape[0]} rows but A has {a.shape[1]} "
            f"columns (A shape {a.shape})")
    policy = normalize_policy(policy)
    cand = tuple(candidates) if candidates else available_paths(a)
    uk = use_kernel if use_kernel is not None else default_use_kernel(config)
    interpret = bool(interpret)
    odt = None if out_dtype is None else str(jnp.dtype(out_dtype))

    def exec_thunk(p):
        return lambda: autodiff.spmv_exec((p, uk, interpret, None, odt),
                                          a, x)

    plan = _resolve_plan("spmv", a, 1, x.dtype, policy, cand, uk,
                         interpret, cost_model, config, autotune_cache,
                         exec_thunk, concrete=not _is_traced(a, x))
    record_plan(plan)
    return autodiff.spmv(
        (plan.path, plan.use_kernel, plan.interpret, None, odt), a, x)


# ---------------------------------------------------------------------------
# SDDMM
# ---------------------------------------------------------------------------


def sddmm(
    a: SparseMatrix,
    b,
    c,
    *,
    policy: str = POLICY_AUTO,
    candidates: Optional[Tuple[str, ...]] = None,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
    bk: Optional[int] = None,
    out_dtype=None,
    cost_model: Optional[CostModel] = None,
    config: DispatchConfig = DEFAULT_CONFIG,
    autotune_cache: Optional[AutotuneCache] = None,
) -> SparseMatrix:
    """S = A ⊙ (B @ C) at A's stored entries (differentiable).

    Returns a single-form ``SparseMatrix`` sharing A's topology, in the
    layout of the form the planned path read; ``S.data`` holds the
    sampled values (element order for the csr path — what GAT's
    segment-softmax consumes).
    """
    if not isinstance(a, SparseMatrix):
        raise TypeError(f"sddmm expects a SparseMatrix, got {type(a)}")
    b = jnp.asarray(b)
    c = jnp.asarray(c)
    if b.shape[0] != a.shape[0]:
        raise ValueError(
            f"sddmm: B has {b.shape[0]} rows but A has {a.shape[0]}")
    if c.shape[1] != a.shape[1]:
        raise ValueError(
            f"sddmm: C has {c.shape[1]} columns but A has {a.shape[1]}")
    if b.shape[1] != c.shape[0]:
        raise ValueError(
            f"sddmm: inner dims disagree: B {b.shape} vs C {c.shape}")
    policy = normalize_policy(policy)
    cand = tuple(candidates) if candidates else available_paths(a)
    uk = use_kernel if use_kernel is not None else default_use_kernel(config)
    interpret = bool(interpret)
    odt = None if out_dtype is None else str(jnp.dtype(out_dtype))

    def exec_thunk(p):
        return lambda: autodiff.sddmm_values(
            (p, uk, interpret, bk, odt), a, b, c)

    plan = _resolve_plan("sddmm", a, b.shape[1], b.dtype, policy, cand, uk,
                         interpret, cost_model, config, autotune_cache,
                         exec_thunk, concrete=not _is_traced(a, b, c))
    record_plan(plan)
    vals = autodiff.sddmm_values(
        (plan.path, plan.use_kernel, plan.interpret, bk, odt), a, b, c)
    form_name = autodiff.form_read_by(a, plan.path)
    return SparseMatrix(
        {form_name: with_values(form_name, a._forms[form_name], vals)},
        a.shape, a.stats, cache=a._cache)


# the paper's naming for the masked product
sample = sddmm


# ---------------------------------------------------------------------------
# Fused graph attention (one-pass SDDMM → edge act → softmax → SpMM)
# ---------------------------------------------------------------------------


def fused_graph_attention(
    a: SparseMatrix,
    q,
    k,
    v,
    *,
    edge_act: str = "leaky_relu",
    negative_slope: float = 0.2,
    policy: str = POLICY_AUTO,
    candidates: Optional[Tuple[str, ...]] = None,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
    out_dtype=None,
    cost_model: Optional[CostModel] = None,
    config: DispatchConfig = DEFAULT_CONFIG,
    autotune_cache: Optional[AutotuneCache] = None,
):
    """Y = softmax_row(act(q kᵀ ⊙ pattern(A))) @ V, in one dispatch.

    The whole GAT aggregation — score the edges (SDDMM at A's nonzero
    pattern), activate, segment-softmax each row, aggregate V (SpMM) —
    runs as ONE planned pipeline: a single plan in ``dispatch_log()``,
    and on the blocked kernel paths a single pass over the topology's
    live tiles with the softmax statistics resident in VMEM (the
    E-length edge-score vector never exists in HBM).

    ``q``: [M, dk] / ``k``: [N, dk] score factors (1-D inputs are
    treated as single-column), ``v``: [N, D] values.  A contributes its
    structural nonzeros only (values are not read).  Differentiable in
    q, k, v via a ``custom_vjp`` that reassembles the backward from the
    SpMM/SDDMM duality plus the softmax Jacobian-vector trick.
    """
    if not isinstance(a, SparseMatrix):
        raise TypeError(
            f"fused_graph_attention expects a SparseMatrix, got {type(a)}")
    q = jnp.asarray(q)
    k = jnp.asarray(k)
    v = jnp.asarray(v)
    if q.ndim == 1:
        q = q[:, None]
    if k.ndim == 1:
        k = k[:, None]
    v_was_1d = v.ndim == 1
    if v_was_1d:
        v = v[:, None]
    if q.shape[0] != a.shape[0]:
        raise ValueError(
            f"fused_graph_attention: q has {q.shape[0]} rows but A has "
            f"{a.shape[0]}")
    if k.shape[0] != a.shape[1]:
        raise ValueError(
            f"fused_graph_attention: k has {k.shape[0]} rows but A has "
            f"{a.shape[1]} columns")
    if v.shape[0] != a.shape[1]:
        raise ValueError(
            f"fused_graph_attention: v has {v.shape[0]} rows but A has "
            f"{a.shape[1]} columns")
    if q.shape[1] != k.shape[1]:
        raise ValueError(
            f"fused_graph_attention: score widths disagree: q {q.shape} "
            f"vs k {k.shape}")
    policy = normalize_policy(policy)
    cand = tuple(candidates) if candidates else available_paths(a)
    uk = use_kernel if use_kernel is not None else default_use_kernel(config)
    interpret = bool(interpret)
    slope = float(negative_slope)
    odt = None if out_dtype is None else str(jnp.dtype(out_dtype))

    def exec_thunk(p):
        return lambda: autodiff.fused_attention_exec(
            (p, uk, interpret, edge_act, slope, odt), a, q, k, v)

    plan = _resolve_plan(PATH_FUSED_ATTN, a, (q.shape[1], v.shape[1]),
                         q.dtype, policy, cand, uk, interpret, cost_model,
                         config, autotune_cache, exec_thunk,
                         concrete=not _is_traced(a, q, k, v),
                         key_extra=(edge_act, slope), fused="attn")
    record_plan(plan)
    y = autodiff.fused_attention(
        (plan.path, plan.use_kernel, plan.interpret, edge_act, slope, odt),
        a, q, k, v)
    return y[:, 0] if v_was_1d else y
