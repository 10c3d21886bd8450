"""Batched serving engines.

``ServingEngine`` drives the transformer's prefill/decode entry points
for a batch of requests with continuous greedy/temperature decoding; the
same ``decode_step``/``prefill`` functions are what the dry-run lowers
for the ``decode_*``/``prefill_*`` shape cells.

``GNNServingEngine`` serves node-classification queries over a fixed
graph (the paper's driving app): the SpMM aggregation path is chosen
once per graph by the sparsity-adaptive dispatch layer and baked into
the jitted forward, and the engine reports which path serves traffic.

``BatchServingEngine`` serves a *stream* of variably-shaped graphs: a
bounded request queue feeds a micro-batching worker (flush on max-batch
or deadline) that groups requests by shape bucket and executes each
group as one block-diagonal batch through the bucketed compilation
cache (``repro.batch``) — compiles stay O(#buckets) while the report
tracks req/s, p50/p99 latency, retraces, and padding waste.

Long-context (500k) decode shards the KV cache over mesh axes via the
logical-axis rules ("kv_seq"); see launch/dryrun.py shape policies.
"""
from __future__ import annotations

import dataclasses
import queue as queue_mod
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutTimeout
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.models.transformer import decode_step, init_cache, prefill
from repro.resilience import chaos
from repro.resilience.errors import (FATAL, POISON, TRANSIENT,
                                     DeadlineExceededError,
                                     EngineClosedError, NaNOutputError,
                                     TransientExecutorError, classify)
from repro.resilience.retry import RetryBudget, RetryPolicy
from repro.resilience.supervisor import WorkerSupervisor


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 2048
    temperature: float = 0.0  # 0 => greedy
    seed: int = 0


class ServingEngine:
    def __init__(self, params, cfg: ModelConfig, scfg: ServeConfig):
        self.params = params
        self.cfg = cfg
        self.scfg = scfg
        self._prefill = jax.jit(
            lambda p, toks, **kw: prefill(p, cfg, toks, scfg.max_len, **kw))
        self._decode = jax.jit(
            lambda p, tok, cache: decode_step(p, cfg, tok, cache))
        self._key = jax.random.PRNGKey(scfg.seed)

    def _sample(self, logits):
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self._key, sub = jax.random.split(self._key)
        return jax.random.categorical(
            sub, logits / self.scfg.temperature, axis=-1).astype(jnp.int32)

    def generate(self, prompts: np.ndarray, n_new: int, *,
                 vision_embeds=None, enc_embeds=None) -> np.ndarray:
        """prompts: [B, S_prompt] int32 -> [B, n_new] generated tokens."""
        kw = {}
        if vision_embeds is not None:
            kw["vision_embeds"] = vision_embeds
        if enc_embeds is not None:
            kw["enc_embeds"] = enc_embeds
        logits, cache = self._prefill(self.params, jnp.asarray(prompts), **kw)
        toks = []
        tok = self._sample(logits)[:, None]
        toks.append(tok)
        for _ in range(n_new - 1):
            logits, cache = self._decode(self.params, tok, cache)
            tok = self._sample(logits)[:, None]
            toks.append(tok)
        return np.asarray(jnp.concatenate(toks, axis=1))


@dataclasses.dataclass
class GNNServeConfig:
    policy: str = "auto"   # dispatch policy for the aggregation SpMM
    jit: bool = True
    d: Optional[int] = None  # planning feature width (inferred if None)
    model: str = "gcn"     # "gcn" | "gat"
    fuse: bool = True      # fused epilogue (gcn) / one-pass attn (gat)


def _infer_planning_width(params) -> int:
    """Feature width the SpMM plan prices, from any GNN param layout.

    Prefers the first layer's output projection when the params follow
    the ``{"w": [...]}`` convention; otherwise falls back to the first
    2-D leaf in pytree order (the widths only scale every path's cost
    equally, so any layer's width ranks the paths identically).
    """
    ws = params.get("w") if isinstance(params, dict) else None
    if isinstance(ws, (list, tuple)):
        ws = ws[0] if ws else None
    if ws is not None and getattr(ws, "ndim", 0) == 2:
        return int(np.shape(ws)[1])
    for leaf in jax.tree_util.tree_leaves(params):
        if getattr(leaf, "ndim", 0) == 2:
            return int(np.shape(leaf)[1])
    raise ValueError(
        "could not infer a planning feature width from the params "
        "(no 2-D weight leaf); pass GNNServeConfig(d=...) explicitly")


class GNNServingEngine:
    """Serves GCN node-classification over a fixed graph.

    The dispatch plan is made once at construction (host side, from the
    graph's static sparsity stats) and the jitted forward executes the
    chosen path for every query batch — the serving analog of the
    paper's per-workload kernel selection.
    """

    def __init__(self, params, graph, scfg: Optional[GNNServeConfig] = None):
        from repro.dispatch.dispatcher import plan_fused_attention, plan_spmm
        from repro.models.gnn import (GRAPH_PATHS, gat_forward, gcn_forward,
                                      graph_candidates)

        self.params = params
        self.graph = graph
        self.scfg = scfg or GNNServeConfig()
        if graph.adj is None or graph.adj.stats is None:
            raise ValueError(
                "GNNServingEngine: Graph adjacency has no sparsity stats; "
                "construct it with build_graph()")
        if self.scfg.model not in ("gcn", "gat"):
            raise ValueError(
                f"GNNServeConfig.model must be 'gcn' or 'gat', got "
                f"{self.scfg.model!r}")
        d = self.scfg.d if self.scfg.d is not None \
            else _infer_planning_width(params)
        # candidates: the paths this graph's carried forms can execute
        # (a hyper-sparse adjacency also packs SELL-C-σ — see build_graph)
        cand = graph_candidates(graph.adj)
        fuse = self.scfg.fuse
        if self.scfg.model == "gat" and fuse:
            # one-pass attention: priced as a single stream of the
            # topology at the combined (score + value) width
            self.plan = plan_fused_attention(
                graph.adj.stats, 2, d, policy=self.scfg.policy,
                candidates=cand or GRAPH_PATHS)
        else:
            self.plan = plan_spmm(graph.adj.stats, d,
                                  policy=self.scfg.policy,
                                  candidates=cand or GRAPH_PATHS)

        if self.scfg.model == "gat":
            # unfused GAT samples on the element pattern, so the baked
            # layout plan only applies to the fused one-pass pipeline
            gat_policy = self.plan.path if fuse else self.scfg.policy

            def fwd(p, g, x):
                return gat_forward(p, g, x, policy=gat_policy, fuse=fuse)
        else:
            def fwd(p, g, x):
                return gcn_forward(p, g, x, policy=self.plan.path,
                                   fuse=fuse)

        self._fwd = jax.jit(fwd) if self.scfg.jit else fwd
        self.n_requests = 0

    def infer(self, x) -> np.ndarray:
        """x: [n_nodes, in_features] -> logits [n_nodes, n_classes]."""
        self.n_requests += 1
        return np.asarray(self._fwd(self.params, self.graph, jnp.asarray(x)))

    def classify(self, x) -> np.ndarray:
        return self.infer(x).argmax(axis=-1)

    def dispatch_report(self) -> Dict:
        """Which path serves this graph's traffic, and why."""
        from repro.sparse import plan_cache_stats

        stats = self.graph.adj.stats
        return {
            "model": self.scfg.model,
            "fused": self.scfg.fuse,
            "plan_op": self.plan.op,
            "path": self.plan.path,
            "policy": self.plan.policy,
            "reason": self.plan.reason,
            "density": stats.density,
            "occupancy": stats.occupancy,
            "padded_stream_blowup": stats.padded_stream_blowup,
            "n_requests": self.n_requests,
            # the served graph's own plan memo (per-matrix counters):
            # engines on distinct graphs no longer alias each other's
            # hit rates; engines sharing one Graph share its memo
            "plan_cache": self.graph.adj.plan_cache.stats(),
            "plan_cache_global": plan_cache_stats(),
        }


# ---------------------------------------------------------------------------
# Batched multi-graph serving (micro-batching over the bucketed executor)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BatchServeConfig:
    """Micro-batching window and bucketed-executor knobs."""

    max_batch: int = 32        # flush when this many requests are queued
    max_delay_ms: float = 5.0  # ... or when the oldest waits this long
    queue_depth: int = 1024    # bounded admission queue
    policy: str = "auto"       # dispatch policy inside the executor
    form: str = "auto"         # bucket form: auto | csr | ell
    max_executors: int = 64    # LRU cap on cached jitted executors
    growth: float = 2.0        # bucket grid growth factor
    fuse: bool = True          # fused epilogue inside the GCN executor
    # opt into the traffic-fitted bucket grid (an AdaptiveBucketLadder
    # replaces the fixed geometric grid once it has observed enough
    # traffic; see repro.serve.runtime).  ``ladder`` overrides its
    # LadderConfig.
    adaptive: bool = False
    ladder: Any = None
    # -- resilience (see DESIGN.md "Resilience") ----------------------------
    retry: RetryPolicy = RetryPolicy()  # per-request backoff + allowance
    retry_budget: int = 64              # engine-wide retry tokens
    retry_refill_per_s: float = 8.0
    guard_nonfinite: bool = True        # quarantine NaN/Inf outputs
    default_timeout_s: Optional[float] = 60.0  # infer() deadline
    max_worker_restarts: int = 3
    seed: int = 0                       # backoff-jitter rng


@dataclasses.dataclass
class _Request:
    matrix: Any                # SparseMatrix adjacency
    features: Any              # [n_nodes, d]
    future: Future
    t_submit: float
    attempts: int = 0          # transient retries consumed
    tag: Any = None            # chaos/match + caller bookkeeping label


class BatchServingEngine:
    """Serves a stream of (graph, features) requests with micro-batching.

    Requests enter a bounded queue; a worker thread drains it into
    micro-batches (flushing on ``max_batch`` or the ``max_delay_ms``
    deadline), groups each flush by shape bucket, and executes every
    group as one block-diagonal batch through a
    :class:`repro.batch.BucketedExecutor` — so arbitrary traffic
    compiles O(#buckets) programs and the whole batch rides one planned
    SpMM per model layer.

    ``fn(matrix, h)`` is the per-batch program (default: the planned
    ``matrix @ h``); with ``context`` set (e.g. model weights) it is
    called ``fn(context, matrix, h)`` and the context rides through jit
    as a traced argument shared by every cached executor.  Use
    :meth:`for_gcn` to serve GCN node classification with shared
    weights.
    """

    def __init__(self, fn: Optional[Callable] = None, *,
                 context: Any = None,
                 scfg: Optional[BatchServeConfig] = None):
        from repro.batch import BucketedExecutor
        from repro.batch.bucketing import BucketingConfig

        self.scfg = scfg or BatchServeConfig()
        ladder = None
        if self.scfg.adaptive:
            from repro.serve.runtime.ladder import (AdaptiveBucketLadder,
                                                    LadderConfig)

            lcfg = self.scfg.ladder
            if lcfg is None:
                lcfg = LadderConfig()
            ladder = (lcfg if isinstance(lcfg, AdaptiveBucketLadder)
                      else AdaptiveBucketLadder(lcfg))
        self.executor = BucketedExecutor(
            fn,
            context=context,
            form=self.scfg.form,
            policy=self.scfg.policy,
            max_batch=self.scfg.max_batch,
            max_executors=self.scfg.max_executors,
            bucketing=BucketingConfig(growth=self.scfg.growth),
            ladder=ladder,
        )
        self._queue: "queue_mod.Queue[_Request]" = queue_mod.Queue(
            maxsize=self.scfg.queue_depth)
        self._latencies_ms: List[float] = []
        self._flushes = {"full": 0, "deadline": 0}
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None
        self._submitted = 0
        self._completed = 0
        self._failed = 0
        self._close_lock = threading.Lock()
        self._close_once = threading.Lock()
        self._stop = threading.Event()
        self._rng = np.random.default_rng(self.scfg.seed)
        self._budget = RetryBudget(self.scfg.retry_budget,
                                   self.scfg.retry_refill_per_s)
        self._quarantined = 0
        self._sup = WorkerSupervisor(
            "batch-serve", self._serve_loop,
            max_restarts=self.scfg.max_worker_restarts)
        self._sup.start()

    @property
    def _worker(self) -> threading.Thread:
        """The current serving thread (restarts under the supervisor)."""
        return self._sup._thread

    @classmethod
    def for_gcn(cls, params, *, scfg: Optional[BatchServeConfig] = None,
                ) -> "BatchServingEngine":
        """Engine running a shared-weight GCN over each batch.

        The block-diagonal composition makes the batched forward exact:
        weights are node-independent, so ``diag(A_1..A_N) @ (H W)``
        aggregates every graph at once.
        """
        from repro.models.gnn import Graph, gcn_forward

        cfg = scfg or BatchServeConfig()
        policy, fuse = cfg.policy, cfg.fuse

        def fwd(p, mat, h):
            g = Graph(adj=mat, n_nodes=mat.shape[0])
            return gcn_forward(p, g, h, policy=policy, fuse=fuse)

        # weights enter as the executor context (a jit argument), so the
        # cached per-bucket executables share one copy instead of each
        # baking the params in as XLA constants
        return cls(fwd, context=params, scfg=scfg)

    # -- submission ---------------------------------------------------------

    def submit(self, matrix, features, *, tag: Any = None) -> Future:
        """Enqueue one request; resolves to [n_nodes, d_out] (numpy).

        ``matrix`` is the graph's (normalized) adjacency as a
        ``SparseMatrix`` — or a ``Graph``, whose adjacency is taken.
        Blocks while the admission queue is full (bounded backpressure).
        A dead serving worker is restarted here (bounded by
        ``max_worker_restarts``).
        """
        if self._stop.is_set():
            raise EngineClosedError("engine is closed")
        self._sup.ensure()
        adj = getattr(matrix, "adj", matrix)
        with obs.span("serve.admit", engine="batch"):
            req = _Request(matrix=adj, features=features, future=Future(),
                           t_submit=time.perf_counter(), tag=tag)
            if self._t_first is None:
                self._t_first = req.t_submit
            self._submitted += 1
            self._queue.put(req)
        if self._stop.is_set():
            # close() may have drained between our check and the put;
            # sweep again so no request can strand in a dead queue
            self._fail_queued()
        return req.future

    def infer(self, matrix, features, *,
              timeout: Optional[float] = None) -> np.ndarray:
        """Synchronous convenience wrapper around :meth:`submit`.

        ``timeout`` (default ``scfg.default_timeout_s``) bounds the
        wait; expiry raises :class:`DeadlineExceededError` (a
        :class:`TimeoutError`) instead of blocking forever on a stuck
        future.
        """
        t = self.scfg.default_timeout_s if timeout is None else timeout
        try:
            return self.submit(matrix, features).result(t)
        except DeadlineExceededError:
            raise
        except (TimeoutError, _FutTimeout):
            raise DeadlineExceededError(
                f"infer: no result within {t}s") from None

    # -- worker -------------------------------------------------------------

    def _serve_loop(self) -> None:
        while not self._stop.is_set():
            # chaos fires before any request is picked up, so an
            # injected worker death strands nothing — the supervisor
            # restarts the loop on the next submit()/drain()
            try:
                chaos.hook("serve.worker")
            except chaos.WorkerKilled:
                return  # injected death: the supervisor restarts us
            except Exception:
                continue  # any other injected fault: keep serving
            try:
                first = self._queue.get(timeout=0.05)
            except queue_mod.Empty:
                continue
            batch = [first]
            try:
                self._collect_and_flush(batch)
            except BaseException as exc:  # noqa: BLE001 — worker dying
                # (KeyboardInterrupt, MemoryError, ...) must not strand
                # the futures it already picked up: resolve them with
                # the error before the thread unwinds
                for r in batch:
                    with self._close_lock:
                        self._completed += 1
                        self._failed += 1
                    if not r.future.done() and not r.future.cancelled():
                        r.future.set_exception(
                            RuntimeError(f"serving worker died: {exc!r}"))
                raise

    def _collect_and_flush(self, batch: List[_Request]) -> None:
        # a negative max_delay_ms must degrade to greedy (immediate)
        # flushing, never reach Queue.get as a negative timeout — that
        # raises ValueError, kills the worker thread, and strands every
        # queued future with no error
        window_s = max(self.scfg.max_delay_ms, 0.0) / 1e3
        first = batch[0]
        # the window anchors at the oldest request's *submit* time
        # (queue wait already spent counts against the deadline);
        # requests already queued are always taken — the deadline
        # only bounds how long we *wait* for more
        deadline = first.t_submit + window_s
        while len(batch) < self.scfg.max_batch:
            try:
                batch.append(self._queue.get_nowait())
                continue
            except queue_mod.Empty:
                pass
            # clamped to [0, window]: a slow request — one that sat
            # queued past its whole window while the worker flushed
            # an earlier batch — yields a *negative* remainder and
            # must flush now, not wait; the upper clamp bounds any
            # single wait to one window regardless of timestamp skew
            remaining = min(deadline - time.perf_counter(), window_s)
            if remaining <= 0:
                break
            try:
                batch.append(self._queue.get(timeout=remaining))
            except queue_mod.Empty:
                break
        self._flushes["full" if len(batch) >= self.scfg.max_batch
                      else "deadline"] += 1
        self._flush(batch)

    def _flush(self, batch: List[_Request]) -> None:
        outs, exc = self._try_run(batch)
        if exc is None:
            self._complete(batch, outs)
        else:
            self._recover(batch, exc)

    def _try_run(self, batch: List[_Request]):
        """Execute the batch; returns (outs, None) or (None, exc)."""
        tags = [r.tag for r in batch if r.tag is not None]
        try:
            with obs.span("serve.flush", engine="batch", n=len(batch)):
                chaos.hook("serve.flush", tags=tags, n=len(batch))
                outs = self.executor.run([r.matrix for r in batch],
                                         [r.features for r in batch])
            return outs, None
        except Exception as exc:  # noqa: BLE001 — classified by _recover
            return None, exc

    def _complete(self, batch: List[_Request], outs) -> None:
        t_done = time.perf_counter()
        self._t_last = t_done
        lat_hist = obs.histogram("serve_latency_ms", engine="batch")
        for r, y in zip(batch, outs):
            if self.scfg.guard_nonfinite and not np.isfinite(y).all():
                self._fail_requests([r], NaNOutputError(
                    "non-finite output quarantined "
                    f"(request rows={np.shape(y)[0]})"), quarantine="nan")
                continue
            lat_ms = (t_done - r.t_submit) * 1e3
            self._latencies_ms.append(lat_ms)
            lat_hist.observe(lat_ms)
            with self._close_lock:
                self._completed += 1
            if not r.future.cancelled():
                r.future.set_result(y)

    def _recover(self, batch: List[_Request], exc, *,
                 retried: bool = False) -> None:
        """A flush failed: retry, bisect, quarantine (see DESIGN.md
        "Resilience").  Innocent co-batched requests complete from the
        bisection probes; only the pinned culprit fails."""
        kind = classify(exc)
        if kind == FATAL:
            self._fail_requests(batch, exc)
            return
        if len(batch) == 1:
            r = batch[0]
            if kind == POISON:
                self._fail_requests(batch, exc, quarantine="poison")
                return
            r.attempts += 1
            if self.scfg.retry.allows(r.attempts + 1) \
                    and self._budget.spend():
                obs.counter("resilience_retries_total",
                            site="serve.flush", kind=kind).inc()
                time.sleep(self.scfg.retry.backoff_s(
                    r.attempts + 1, self._rng))
                outs, exc2 = self._try_run(batch)
                if exc2 is None:
                    self._complete(batch, outs)
                else:
                    self._recover(batch, exc2, retried=True)
                return
            self._fail_requests(batch, TransientExecutorError(
                f"retries exhausted after {r.attempts} attempts "
                f"(last error: {exc!r})"))
            return
        if kind == TRANSIENT and not retried and self._budget.spend():
            obs.counter("resilience_retries_total",
                        site="serve.flush", kind=kind).inc()
            time.sleep(self.scfg.retry.backoff_s(2, self._rng))
            outs, exc2 = self._try_run(batch)
            if exc2 is None:
                self._complete(batch, outs)
                return
            exc, kind = exc2, classify(exc2)
            if kind == FATAL:
                self._fail_requests(batch, exc)
                return
        mid = len(batch) // 2
        for half in (batch[:mid], batch[mid:]):
            outs, exc_h = self._try_run(half)
            if exc_h is None:
                self._complete(half, outs)
            else:
                self._recover(half, exc_h, retried=True)

    def _fail_requests(self, batch: List[_Request], exc, *,
                       quarantine: Optional[str] = None) -> None:
        self._t_last = time.perf_counter()
        for r in batch:
            if quarantine is not None:
                self._quarantined += 1
                obs.counter("resilience_quarantined_total",
                            kind=quarantine).inc()
            with self._close_lock:
                self._completed += 1  # resolved (with an error):
                self._failed += 1     # drain must not wait on these
            if not r.future.done() and not r.future.cancelled():
                r.future.set_exception(exc)

    # -- lifecycle ----------------------------------------------------------

    def drain(self, timeout: float = 60.0) -> None:
        """Block until everything submitted so far has completed."""
        t0 = time.perf_counter()
        while self._completed < self._submitted:
            if not self._stop.is_set() and not self._sup.ensure():
                # the worker is dead beyond its restart budget and can
                # never complete the backlog: fail the queued futures
                # now instead of spinning to the timeout
                self._fail_queued()
                if self._completed < self._submitted:
                    raise RuntimeError(
                        "drain: serving worker died with "
                        f"{self._submitted - self._completed} requests "
                        "in flight")
                return
            if time.perf_counter() - t0 > timeout:
                raise TimeoutError(
                    f"drain: {self._submitted - self._completed} requests "
                    f"still pending after {timeout}s")
            time.sleep(0.002)

    def reset_metrics(self) -> None:
        """Zero the traffic counters (e.g. after a warm-up pass).

        Executor state (compiled programs, compile counters) is kept —
        only latency/throughput accounting restarts.  Call with no work
        in flight (after :meth:`drain`).
        """
        if self._completed < self._submitted:
            raise RuntimeError("reset_metrics with requests in flight; "
                               "drain() first")
        self._latencies_ms.clear()
        self._flushes = {"full": 0, "deadline": 0}
        self._t_first = self._t_last = None
        self._submitted = self._completed = self._failed = 0
        self._quarantined = 0

    def _fail_queued(self) -> None:
        """Fail everything still queued so no future blocks forever."""
        while True:
            try:
                req = self._queue.get_nowait()
            except queue_mod.Empty:
                return
            with self._close_lock:
                self._completed += 1
                self._failed += 1
            if not req.future.cancelled():
                req.future.set_exception(EngineClosedError("engine closed"))

    def close(self) -> None:
        """Shut down, leaving no future unresolved.

        Everything admitted before close is *drained* — the worker keeps
        flushing until the queue is empty, so already-submitted requests
        get their results, not an error.  Only if the drain cannot
        finish (dead worker, timeout) are the leftovers failed; either
        way every future resolves and no caller blocks forever.

        Idempotent and safe under concurrent callers: one closer does
        the drain/stop/sweep, later (or racing) closers serialize on
        its lock and find the work done.
        """
        with self._close_once:
            if not self._stop.is_set():
                try:
                    self.drain()
                except Exception:  # noqa: BLE001 — still sweep below
                    pass
            self._stop.set()
            self._sup.join(timeout=5.0)
            self._fail_queued()

    def __enter__(self) -> "BatchServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- reporting ----------------------------------------------------------

    def report(self) -> Dict[str, Any]:
        """Throughput, latency percentiles (``p50_ms``/``p99_ms``),
        compile + padding counters."""
        lat = np.asarray(self._latencies_ms, np.float64)
        elapsed = ((self._t_last - self._t_first)
                   if (self._t_first is not None
                       and self._t_last is not None) else 0.0)
        return {
            "submitted": self._submitted,
            "completed": self._completed,
            "failed": self._failed,
            "req_per_s": (self._completed / elapsed) if elapsed > 0 else 0.0,
            "p50_ms": float(np.percentile(lat, 50)) if len(lat) else 0.0,
            "p99_ms": float(np.percentile(lat, 99)) if len(lat) else 0.0,
            "flushes": dict(self._flushes),
            "executor": self.executor.report(),
            "resilience": {
                "quarantined": self._quarantined,
                "retry_tokens": self._budget.remaining(),
                "worker_restarts": self._sup.restarts,
            },
        }


def make_prefill_step(cfg: ModelConfig, max_len: int):
    """Lowerable prefill entry (the prefill_* dry-run cells).

    Takes the batch as a dict so modality side-inputs can never be
    positionally confused (a vision_embeds/enc_embeds swap silently drops
    the whisper encoder — caught by the multi-pod dry-run's out_shardings
    structure check).
    """

    def prefill_step(params, batch):
        kw = {}
        if cfg.vision_tokens and "vision_embeds" in batch:
            kw["vision_embeds"] = batch["vision_embeds"]
        if cfg.encoder_layers and "enc_embeds" in batch:
            kw["enc_embeds"] = batch["enc_embeds"]
        logits, cache = prefill(params, cfg, batch["tokens"], max_len, **kw)
        return logits, cache

    return prefill_step


def make_serve_step(cfg: ModelConfig):
    """Lowerable single-token decode entry (the decode_* dry-run cells)."""

    def serve_step(params, token, cache):
        return decode_step(params, cfg, token, cache)

    return serve_step
