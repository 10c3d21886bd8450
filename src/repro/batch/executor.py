"""Bucketed batch executor: O(#buckets) compiles for arbitrary traffic.

``BucketedExecutor`` is the layer between "a kernel that wins on one
matrix" and "an engine that sustains traffic": it takes a micro-batch of
(graph, features) requests with arbitrary shapes, groups them by
:func:`bucket_for`, pads every graph of a group into its bucket, fills
the group to a quantized batch size with all-zero dummies, composes the
group block-diagonally, and runs **one** jitted executor per
(bucket, batch-size) key.  Executors live in an LRU cache; a trace
counter distinguishes compiles from cache hits, and a
:class:`PaddingWaste` ledger accounts the streamed-but-dead volume.

The execution path is planned once per bucket from the bucket's
canonical stats through the regular cost model (or forced by policy),
so the batched engine inherits the paper's sparsity-adaptive routing.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.dispatch.cost_model import CostModel
from repro.dispatch.dispatcher import plan_spmm
from repro.dispatch.policy import PATH_CSR, PATH_ELL
from repro.resilience import chaos
from repro.resilience.errors import TRANSIENT, CompileError, classify
from repro.sparse import paths
from repro.sparse.matrix import SparseMatrix
from repro.batch.block_diag import BatchedSparseMatrix
from repro.batch.bucketing import (Bucket, BucketingConfig,
                                   DEFAULT_BUCKETING, PaddingWaste,
                                   bucket_for, canonical_stats,
                                   empty_in_bucket, pad_to_bucket)

Array = Any

# fn(batched_matrix, stacked_features) -> stacked outputs [rows, d_out];
# with a `context` configured, fn(context, batched_matrix, features)
ExecutorFn = Callable[..., Array]


def _quantize_batch(n: int, max_batch: int) -> int:
    """Next power of two >= n, capped at max_batch."""
    b = 1
    while b < n and b < max_batch:
        b *= 2
    return min(b, max_batch)


@dataclasses.dataclass(frozen=True)
class ExecutorKey:
    bucket: Bucket
    batch: int
    d: int
    form: str

    @property
    def label(self) -> str:
        """Stable per-cell name; ``BucketedExecutor.lane_label`` prefixes
        it with the owning executor's id to form the sentry lane."""
        return f"{self.bucket.label}/b{self.batch}/d{self.d}/{self.form}"


_EXECUTOR_IDS = itertools.count()


class BucketedExecutor:
    """Shape-bucketed compilation cache over block-diagonal batches.

    ``fn(matrix, h)`` is the traced program (default: the planned SpMM
    ``matrix @ h`` forced to the bucket's cost-model path).  One jitted
    instance is kept per (bucket, quantized batch, d, form) key in an
    LRU of ``max_executors``.

    ``context`` (a pytree, e.g. model weights) is passed to ``fn`` as a
    leading argument *through* jit — as a traced input, not a closure
    constant — so many cached executors share one copy of the weights
    instead of each baking them in as XLA constants.
    """

    def __init__(self, fn: Optional[ExecutorFn] = None, *,
                 context: Any = None,
                 form: str = "auto",
                 policy: str = "auto",
                 max_batch: int = 32,
                 max_executors: int = 64,
                 bucketing: BucketingConfig = DEFAULT_BUCKETING,
                 cost_model: Optional[CostModel] = None,
                 ladder: Any = None,
                 jit: bool = True,
                 degrade_after: int = 3):
        if form not in ("auto", "csr", "ell"):
            raise ValueError(
                f"form must be 'auto', 'csr' or 'ell'; got {form!r}")
        if fn is None and context is not None:
            raise ValueError("context without fn has nothing to consume it")
        self._fn = fn
        self.context = context
        self.form = form
        self.policy = policy
        self.max_batch = int(max_batch)
        self.max_executors = int(max_executors)
        self.bucketing = bucketing
        self.cost_model = cost_model
        # opt-in traffic-fitted bucket grid (an AdaptiveBucketLadder,
        # see repro.serve.runtime.ladder); None = the fixed geometric
        # grid, which needs no warm-up and stays the default
        self.ladder = ladder
        self.jit = jit
        self._executors: "collections.OrderedDict[ExecutorKey, Callable]" \
            = collections.OrderedDict()
        # sentry lanes are namespaced per executor instance: each
        # instance holds its own jit cache, so two engines compiling the
        # same (bucket, batch, d, form) cell are two first compiles, not
        # a retrace
        self.uid = next(_EXECUTOR_IDS)
        self.compiles = 0       # executor traces (LRU misses + retraces)
        self.calls = 0          # batched dispatches
        self.requests = 0       # individual graphs served
        self.evictions = 0
        self.waste = PaddingWaste()
        # bucket plans made by choose_form, kept for the cost audit (the
        # serving-side predicted-vs-measured rows need the cost vector)
        self._bucket_plans: Dict[Tuple[Bucket, int], Any] = {}
        # degraded mode: a (bucket, d, form) cell that fails
        # `degrade_after` consecutive transient executions is excluded
        # from auto form selection until the process restarts — the
        # caller replans onto the surviving form (see note_failure)
        self.degrade_after = int(degrade_after)
        self._form_failures: Dict[Tuple[Bucket, int, str], int] = {}
        self._degraded: set = set()

    # -- planning -----------------------------------------------------------

    def bucket_of(self, stats) -> Bucket:
        """The compile-grid cell a request with these stats pads into
        (the learned ladder when one is configured, else the fixed
        geometric grid)."""
        if self.ladder is not None:
            self.ladder.observe(stats)
            return self.ladder.bucket_for(stats)
        return bucket_for(stats, self.bucketing)

    def choose_form(self, bucket: Bucket, d: int,
                    carried: Sequence[str]) -> Tuple[str, str]:
        """(form to pad, path to run) for one bucket."""
        if self.policy in ("csr", "ell"):
            if self.policy not in carried:
                raise ValueError(
                    f"policy {self.policy!r} forced but the group carries "
                    f"only {tuple(carried)}")
            return self.policy, self.policy
        if self.form in ("csr", "ell"):
            if self.form not in carried:
                raise ValueError(
                    f"form {self.form!r} requested but the group carries "
                    f"only {tuple(carried)}")
            form = self.form
        else:
            cand = tuple(p for p in (PATH_ELL, PATH_CSR) if p in carried)
            if not cand:
                raise ValueError(
                    f"group carries no bucketable form: {tuple(carried)}")
            # degraded mode: skip forms that kept failing in this cell,
            # unless that would leave no candidate at all
            healthy = tuple(p for p in cand
                            if (bucket, d, p) not in self._degraded)
            plan = plan_spmm(canonical_stats(bucket), d, policy=self.policy,
                             cost_model=self.cost_model,
                             candidates=healthy or cand)
            self._bucket_plans[(bucket, d)] = plan
            form = plan.path
        return form, form

    def note_failure(self, bucket: Bucket, d: int, form: str) -> bool:
        """Record one transient execution failure for a cell.  Returns
        True exactly when the cell's form newly crosses
        ``degrade_after`` consecutive failures and enters degraded mode
        (the caller should replan the traffic onto a surviving form)."""
        key = (bucket, d, form)
        if key in self._degraded:
            return False
        n = self._form_failures.get(key, 0) + 1
        self._form_failures[key] = n
        if n < self.degrade_after:
            return False
        self._degraded.add(key)
        obs.counter("resilience_degraded_total", form=form).inc()
        obs.counter("resilience_recoveries_total", site="degrade").inc()
        return True

    def note_success(self, bucket: Bucket, d: int, form: str) -> None:
        """A successful execution resets the consecutive-failure count
        (a degraded form stays degraded — re-probation would flap)."""
        self._form_failures.pop((bucket, d, form), None)

    def bucket_plan(self, bucket: Bucket, d: int):
        """The cost-model plan made for this (bucket, d) cell, when one
        was (forced forms/policies plan nothing)."""
        return self._bucket_plans.get((bucket, d))

    def lane_label(self, key: ExecutorKey) -> str:
        """The retrace-sentry lane for this cell in this executor's
        compile cache (see ``uid``)."""
        return f"x{self.uid}/{key.label}"

    def executor_for(self, key: ExecutorKey) -> Callable:
        """The jitted program serving one (bucket, batch, d, form) cell
        (LRU-cached; tracing bumps ``compiles``).  Public so runtimes
        that manage their own batch composition (the continuous engine)
        can share this compile cache."""
        return self._executor_for(key)

    def _executor_for(self, key: ExecutorKey) -> Callable:
        cached = self._executors.get(key)
        if cached is not None:
            self._executors.move_to_end(key)
            return cached

        path = key.form
        inner = self._fn

        def body(*args):
            if inner is not None:
                return inner(*args)
            mat, h = args
            from repro.sparse import ops

            return ops.matmul(mat, h, policy=path, candidates=(path,))

        lane = self.lane_label(key)
        if self.jit:
            traced = threading.local()

            def run(*args):
                # trace-time chaos first, so an injected compile failure
                # does not pollute the compile counters or the sentry
                chaos.hook("executor.compile", lane=lane)
                self.compiles += 1  # runs at trace time only
                obs.SENTRY.record_compile(lane)
                out = body(*args)
                traced.done = True
                return out

            jitted = jax.jit(run)

            def exe(*args):
                # an error after this call's trace finished came from
                # lowering or compiling (a refused kernel): surface it as
                # a CompileError, never as a request's or a form's fault
                traced.done = False
                try:
                    return jitted(*args)
                except Exception as exc:
                    if getattr(traced, "done", False):
                        raise CompileError(
                            f"executor {lane}: the traced program failed "
                            f"to compile: {exc}") from exc
                    raise
        else:
            self.compiles += 1  # eager mode: one "trace" per key
            obs.SENTRY.record_compile(lane)
            exe = body
        self._executors[key] = exe
        while len(self._executors) > self.max_executors:
            evicted, _ = self._executors.popitem(last=False)
            self.evictions += 1
            obs.counter("executor_evictions_total").inc()
            # an evicted lane legitimately recompiles on its next use
            obs.SENTRY.forget(self.lane_label(evicted))
        return exe

    # -- execution ----------------------------------------------------------

    def run(self, mats: Sequence[SparseMatrix], hs: Sequence[Array]
            ) -> List[np.ndarray]:
        """Serve one micro-batch of (graph, features) requests.

        Groups by bucket, pads, composes block-diagonally, executes one
        jitted program per group, and returns per-request outputs (rows
        trimmed back to each graph's logical node count) in input order.
        """
        if len(mats) != len(hs):
            raise ValueError(f"{len(mats)} graphs but {len(hs)} features")
        groups: Dict[Tuple[Bucket, int], List[int]] = {}
        hs = [jnp.asarray(h) for h in hs]
        with obs.span("serve.bucket", requests=len(mats),
                      grid="ladder" if self.ladder is not None else "fixed"):
            for i, (m, h) in enumerate(zip(mats, hs)):
                if m.stats is None:
                    raise ValueError(
                        "bucketed execution needs matrices with stats "
                        "(construct with SparseMatrix.from_dense/from_*)")
                if h.ndim != 2 or h.shape[0] != m.shape[1]:
                    raise ValueError(
                        f"request {i}: features {h.shape} do not match "
                        f"matrix {m.shape}")
                bucket = self.bucket_of(m.stats)
                groups.setdefault((bucket, int(h.shape[1])), []).append(i)
        out: List[Optional[np.ndarray]] = [None] * len(mats)
        for (bucket, d), idxs in groups.items():
            for chunk_start in range(0, len(idxs), self.max_batch):
                chunk = idxs[chunk_start:chunk_start + self.max_batch]
                self._run_group(bucket, d, chunk, mats, hs, out)
        return out  # type: ignore[return-value]

    def _run_group(self, bucket: Bucket, d: int, idxs: List[int],
                   mats, hs, out) -> None:
        carried = [f for f in ("ell", "csr")
                   if all(mats[i].has_form(f) for i in idxs)]
        form, path = self.choose_form(bucket, d, carried)
        bs = _quantize_batch(len(idxs), self.max_batch)
        dtype = hs[idxs[0]].dtype
        key = ExecutorKey(bucket=bucket, batch=bs, d=d, form=path)
        lane = self.lane_label(key)
        with obs.span("serve.compose", lane=lane, n=len(idxs)):
            padded = [pad_to_bucket(mats[i], bucket, form=form)
                      for i in idxs]
            feats = [paths.pad_rows(hs[i], bucket.cols) for i in idxs]
            while len(padded) < bs:
                padded.append(empty_in_bucket(bucket, form=form,
                                              dtype=dtype))
                feats.append(jnp.zeros((bucket.cols, d), dtype))
            B = BatchedSparseMatrix.from_matrices(padded, formats=(form,))
            h = jnp.concatenate(feats, axis=0)
        args = (B.matrix, h) if self.context is None \
            else (self.context, B.matrix, h)
        with obs.span("serve.execute", lane=lane):
            t0 = time.perf_counter()
            try:
                chaos.hook("executor.execute", lane=lane, form=path)
                y = self._executor_for(key)(*args)
                jax.block_until_ready(y)
            except Exception as exc:
                if classify(exc) == TRANSIENT:
                    self.note_failure(bucket, d, path)
                raise
            exec_ms = (time.perf_counter() - t0) * 1e3
        y = chaos.corrupt("executor.output", y, lane=lane)
        self.note_success(bucket, d, path)
        obs.SENTRY.record_call(lane)
        plan = self.bucket_plan(bucket, d)
        obs.AUDIT.record_raw(
            op="spmm", path=path, measured_ms=exec_ms, bucket=bucket.label,
            costs=plan.costs if plan is not None else None,
            policy=plan.policy if plan is not None else self.policy)
        self.calls += 1
        self.requests += len(idxs)
        real_nnz = sum(mats[i].stats.nnz for i in idxs)
        real_rows = sum(mats[i].shape[0] for i in idxs)
        self.waste.add(real_rows=real_rows, padded_rows=bs * bucket.rows,
                       real_nnz=real_nnz, padded_nnz=bs * bucket.nnz,
                       bucket=bucket)
        with obs.span("serve.complete", lane=lane, n=len(idxs)):
            for slot, i in enumerate(idxs):
                lo = slot * bucket.rows
                out[i] = np.asarray(y[lo:lo + mats[i].shape[0]])

    # -- reporting ----------------------------------------------------------

    def report(self) -> Dict[str, Any]:
        """Canonical keys (see DESIGN.md "Observability"); the old
        ``padding`` spelling resolves via a deprecation alias."""
        out = {
            "requests": self.requests,
            "calls": self.calls,
            "compiles": self.compiles,
            "executors_cached": len(self._executors),
            "evictions": self.evictions,
            "buckets": len({k.bucket for k in self._executors}),
            "waste": self.waste.as_dict(),
        }
        if self.ladder is not None:
            out["ladder"] = self.ladder.report()
        if self._degraded:
            out["degraded"] = sorted(
                f"{b.label}/d{d}/{f}" for b, d, f in self._degraded)
        return obs.renamed_keys(out, {"padding": "waste"})
