"""Empirical autotune pass: time candidate paths, cache the winner.

The cache key deliberately buckets sparsity (log-density buckets) so one
measurement serves a whole sparsity regime: dispatching a 90%-sparse and
a 91%-sparse operand of the same shape/dtype should not trigger two
timing passes.  Keys are plain tuples so the cache can be serialized to
JSON for reuse across processes (the CS-3 analog: the host compiles one
routing table per workload family, not per matrix).
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time
from typing import Callable, Dict, Optional, Tuple

import jax

from repro.dispatch.stats import sparsity_bucket

AutotuneKey = Tuple  # (op, m, n, inner_dim, dtype_str, sparsity_bucket)


def make_key(op: str, shape: Tuple[int, int], inner_dim: int, dtype,
             density: float, *, buckets_per_decade: int = 2) -> AutotuneKey:
    return (
        str(op),
        int(shape[0]),
        int(shape[1]),
        int(inner_dim),
        str(dtype),
        sparsity_bucket(density, buckets_per_decade),
    )


@dataclasses.dataclass
class Measurement:
    path: str
    timings_us: Dict[str, float]


class AutotuneCache:
    """Thread-safe (key -> winning path) cache with JSON persistence.

    Besides the per-key timing entries, the cache can carry one
    calibrated :class:`~repro.dispatch.cost_model.CostModel` (see
    :func:`calibrate`) — ``save``/``load`` round-trip it, so a backend's
    measured cost constants persist across processes alongside the
    timing winners.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[AutotuneKey, Measurement] = {}
        self.cost_model = None  # Optional[CostModel], set by calibrate()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, key: AutotuneKey) -> Optional[Measurement]:
        with self._lock:
            m = self._entries.get(key)
            if m is None:
                self.misses += 1
            else:
                self.hits += 1
            return m

    def put(self, key: AutotuneKey, m: Measurement) -> None:
        with self._lock:
            self._entries[key] = m

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.cost_model = None
            self.hits = 0
            self.misses = 0

    # -- persistence --------------------------------------------------------

    def to_json(self) -> str:
        import dataclasses as _dc

        with self._lock:
            entries = [
                {"key": list(k), "path": m.path, "timings_us": m.timings_us}
                for k, m in self._entries.items()
            ]
            cm = (_dc.asdict(self.cost_model)
                  if self.cost_model is not None else None)
        return json.dumps({"entries": entries, "cost_model": cm},
                          indent=2, sort_keys=True)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())

    def load(self, path: str) -> None:
        from repro.dispatch.cost_model import CostModel

        with open(path) as f:
            payload = json.load(f)
        # legacy payloads were a bare entry list (no calibration)
        entries = payload if isinstance(payload, list) \
            else payload.get("entries", [])
        cm = None if isinstance(payload, list) \
            else payload.get("cost_model")
        with self._lock:
            for row in entries:
                self._entries[tuple(row["key"])] = Measurement(
                    path=row["path"], timings_us=row["timings_us"])
            if cm is not None:
                self.cost_model = CostModel(**cm)


def _time_us(fn: Callable[[], object], warmup: int, iters: int) -> float:
    out = None
    for _ in range(max(warmup, 0)):
        out = fn()
    if out is not None:
        jax.block_until_ready(out)
    best = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def measure(candidates: Dict[str, Callable[[], object]], *,
            warmup: int = 1, iters: int = 3) -> Measurement:
    """Time each candidate thunk; return the winner + all timings.

    A candidate that raises propagates: a kernel the compiler refuses is
    a fault to fix, not a path that quietly loses the race.
    """
    timings = {name: _time_us(thunk, warmup, iters)
               for name, thunk in candidates.items()}
    best = min(timings, key=timings.get)
    return Measurement(path=best, timings_us=timings)


def calibrate(
    *,
    n: int = 512,
    d: int = 64,
    densities: Tuple[float, ...] = (0.5, 0.05, 0.005),
    seed: int = 0,
    warmup: int = 1,
    iters: int = 3,
    cache: Optional[AutotuneCache] = None,
):
    """Microbenchmark the per-element path costs on the running backend.

    The analytic cost model prices each path as (elements streamed) x
    (a per-element constant); the shipped constants encode the *paper's*
    hardware asymmetry, which a CPU container or a different TPU
    generation will not match exactly.  This pass times every execution
    path on synthetic operands across a few sparsity regimes, normalizes
    each timing by the volume that path streams, and expresses it
    relative to the dense path's per-element time — exactly the
    ``c_ell`` / ``c_sell`` / ``c_csr`` constants, but measured.  Each
    path runs jitted, as it does inside a model's step.

    The sell path's volume is that of the executor timed (see
    ``cost_model``): on the kernels, the live tiles' cells, the same
    MXU tile product as the Block-ELL walk, so its ratios join
    ``c_ell``'s and ``c_sell`` (the slot executor's) keeps its shipped
    constant; on the reference, its slots, fitting ``c_sell``.

    Returns the tuned :class:`~repro.dispatch.cost_model.CostModel`
    (median across densities; a path with no valid measurement keeps its
    shipped constant).  When ``cache`` is given the model is attached to
    it, so ``AutotuneCache.save``/``load`` persist the calibration.
    """
    import functools

    import numpy as np

    import jax.numpy as jnp

    from repro.dispatch.cost_model import DEFAULT_COST_MODEL, CostModel
    from repro.dispatch.dispatcher import default_use_kernel
    from repro.sparse import SparseMatrix, autodiff

    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    # time what the dispatcher would actually run on this backend: the
    # Pallas kernels on TPU, the jnp references elsewhere
    use_kernel = default_use_kernel()
    ratios: Dict[str, list] = {"ell": [], "sell": [], "csr": []}
    for density in densities:
        dense = np.where(rng.random((n, n)) < density,
                         rng.normal(size=(n, n)), 0.0).astype(np.float32)
        a = SparseMatrix.from_dense(dense, formats=("ell", "sell", "csr"))
        stats = a.stats
        runs = {p: jax.jit(functools.partial(
                    autodiff.spmm_exec, (p, use_kernel, False, None, None)))
                for p in ("ell", "sell", "csr", "dense")}
        thunks = {p: (lambda f=f: f(a, h)) for p, f in runs.items()}
        t = measure(thunks, warmup=warmup, iters=iters).timings_us
        per_dense = t["dense"] / max(stats.dense_elements * d, 1)
        streamed = [("ell", "ell", stats.stored_elements),
                    ("csr", "csr", stats.nnz)]
        streamed.append(("sell", "ell", stats.sell_tile_elements)
                        if use_kernel else
                        ("sell", "sell", stats.sell_stored_elements))
        for p, const, vol in streamed:
            if vol > 0 and per_dense > 0:
                ratios[const].append((t[p] / (vol * d)) / per_dense)

    def _tuned(path: str, shipped: float) -> float:
        if not ratios[path]:
            return shipped
        # floor at a small positive constant so a noisy fast run can
        # never make a sparse path look cheaper than free
        return max(float(np.median(ratios[path])), 1e-3)

    cm = CostModel(
        c_ell=_tuned("ell", DEFAULT_COST_MODEL.c_ell),
        c_sell=_tuned("sell", DEFAULT_COST_MODEL.c_sell),
        c_csr=_tuned("csr", DEFAULT_COST_MODEL.c_csr),
    )
    if cache is not None:
        cache.cost_model = cm
    return cm


# Process-global cache used by the dispatcher's `autotune` policy.
GLOBAL_CACHE = AutotuneCache()
