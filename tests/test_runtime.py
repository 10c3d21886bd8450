"""Adaptive serving runtime: the online bucket ladder and the
continuous batching engine.

Covers the acceptance contract of the runtime subsystem:
  * the ladder serves the fixed geometric grid until it has enough
    observations, then parks rungs on the observed shapes (no geometric
    inflation for hot sizes), refits on drift, and snaps stable rungs
    so warm executors carry over;
  * the continuous engine returns per-request results identical to the
    dense forward, compiles exactly one executor per lane (occupancy is
    data, never shape), recycles freed slots, runs multi-step requests,
    and resolves every future on close();
  * the per-bucket waste ledger sums back to the aggregate;
  * ``BatchServeConfig(adaptive=True)`` routes the micro-batching
    engine through the ladder, and ``close()`` drains in-flight work
    instead of stranding futures.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.batch.bucketing import DEFAULT_BUCKETING, bucket_for
from repro.dispatch.stats import MatrixStats
from repro.serve.runtime import (AdaptiveBucketLadder, ContinuousBatchEngine,
                                 ContinuousConfig, LadderConfig)
from repro.sparse import SparseMatrix

BLOCK = (16, 16)
D = 8


def _stats(n: int, nnz: int, width: int = 4) -> MatrixStats:
    rng = np.random.default_rng(nnz)
    r = rng.integers(0, n, size=nnz)
    c = rng.integers(0, n, size=nnz)
    s = MatrixStats.from_coords((n, n), r, c, *BLOCK)
    return s


def _graph(rng, n: int, sparsity: float = 0.9):
    dense = np.where(rng.random((n, n)) < (1.0 - sparsity),
                     rng.normal(size=(n, n)), 0.0).astype(np.float32)
    if not dense.any():
        dense[0, 0] = 1.0
    return dense, SparseMatrix.from_dense(dense, formats=("ell", "csr"),
                                          block=BLOCK)


# ---------------------------------------------------------------------------
# AdaptiveBucketLadder
# ---------------------------------------------------------------------------


def test_ladder_prefit_serves_geometric_fallback():
    lad = AdaptiveBucketLadder(LadderConfig(min_fit=16))
    s = _stats(100, 400)
    assert not lad.fitted
    assert lad.bucket_for(s) == bucket_for(s, DEFAULT_BUCKETING)
    assert lad.report()["fallbacks"] == 1


def test_ladder_parks_rungs_on_hot_shapes():
    lad = AdaptiveBucketLadder(LadderConfig(min_fit=8, n_rungs=4))
    hot = _stats(100, 400)
    for _ in range(12):
        lad.observe(hot)
    assert lad.fitted
    b = lad.bucket_for(hot)
    # the learned rung sits on the observed size (block-rounded), not a
    # geometric growth step above it
    assert b.rows == 112  # 100 rounded up to the 16-block
    assert b.rows <= bucket_for(hot, DEFAULT_BUCKETING).rows
    assert b.nnz >= hot.nnz
    rungs = lad.rungs()
    assert all(len(rungs[d]) >= 1 for d in ("rows", "nnz", "width"))


def test_ladder_never_truncates_above_top_rung():
    lad = AdaptiveBucketLadder(LadderConfig(min_fit=8))
    for _ in range(10):
        lad.observe(_stats(64, 200))
    big = _stats(500, 3000)
    b = lad.bucket_for(big)
    assert b.rows >= 500 and b.nnz >= big.nnz
    assert b.rows % BLOCK[0] == 0


def test_ladder_refits_on_drift_and_snaps_stable_rungs():
    cfg = LadderConfig(min_fit=8, refit_interval=8, window=64,
                       drift_threshold=0.1)
    lad = AdaptiveBucketLadder(cfg)
    for _ in range(16):
        lad.observe(_stats(64, 200))
    fits0 = lad.refits
    assert fits0 >= 1
    # same distribution: drift stays under threshold, no refit
    for _ in range(16):
        lad.observe(_stats(64, 200))
    assert lad.refits == fits0
    # drifted distribution: the ladder must refit within a window
    for _ in range(64):
        lad.observe(_stats(512, 4000))
    rep = lad.report()
    assert rep["refits"] > fits0
    assert rep["drift_checks"] >= 1
    b = lad.bucket_for(_stats(512, 4000))
    assert b.rows == 512
    # a refit over an unchanged window lands on the same quantiles, and
    # every rung snaps back — warm executors survive the refit
    before = rep["snapped_rungs"]
    lad.refit()
    assert lad.report()["snapped_rungs"] > before


def test_ladder_forced_refit():
    lad = AdaptiveBucketLadder(LadderConfig(min_fit=1024))
    lad.observe(_stats(96, 300))
    assert not lad.fitted
    lad.refit()
    assert lad.fitted


# ---------------------------------------------------------------------------
# ContinuousBatchEngine
# ---------------------------------------------------------------------------


def _cfg(**kw) -> ContinuousConfig:
    kw.setdefault("slots", 4)
    kw.setdefault("adaptive", False)
    kw.setdefault("max_wait_ms", 0.0)  # tests step deterministically
    return ContinuousConfig(**kw)


def test_continuous_parity_and_trace_pin(rng):
    with ContinuousBatchEngine(cfg=_cfg()) as eng:
        futs, refs = [], []
        for n in (48, 48, 80, 48, 80, 48, 80, 48):
            dense, mat = _graph(rng, n)
            h = jnp.asarray(rng.normal(size=(n, D)).astype(np.float32))
            futs.append(eng.submit(mat, h))
            refs.append(dense @ np.asarray(h))
        eng.drain()
        for f, ref in zip(futs, refs):
            np.testing.assert_allclose(f.result(), ref,
                                       rtol=2e-4, atol=2e-4)
        rep = eng.report()
        # occupancy is data, not shape: exactly one compile per lane
        assert rep["executor"]["compiles"] == len(rep["lanes"])
        assert rep["completed"] == 8 and rep["failed"] == 0


def test_continuous_slot_recycling_and_occupancy(rng):
    with ContinuousBatchEngine(cfg=_cfg(slots=2)) as eng:
        dense, mat = _graph(rng, 48)
        h = jnp.asarray(rng.normal(size=(48, D)).astype(np.float32))
        futs = [eng.submit(mat, h) for _ in range(7)]
        lane = next(iter(eng._lanes.values()))
        assert lane.occupancy == 2 and len(lane.queue) == 5
        # each step completes the seated pair and recycles queued work
        assert eng.step(force=True) == 2
        assert lane.occupancy == 2 and len(lane.queue) == 3
        eng.drain()
        assert all(f.done() for f in futs)
        rep = eng.report()
        (lane_rep,) = rep["lanes"].values()
        assert lane_rep["steps"] == 4            # ceil(7 / 2)
        assert lane_rep["occupancy"] == pytest.approx(7 / 8)


def test_continuous_multistep_propagation(rng):
    with ContinuousBatchEngine(cfg=_cfg()) as eng:
        dense, mat = _graph(rng, 48)
        h = rng.normal(size=(48, D)).astype(np.float32)
        y = eng.infer(mat, jnp.asarray(h), steps=3)
        ref = dense @ (dense @ (dense @ h))
        np.testing.assert_allclose(y, ref, rtol=5e-4, atol=5e-4)


def test_continuous_batching_window_holds_partial_lanes(rng):
    # under max_wait_ms a partially-filled lane is not ready; force runs it
    with ContinuousBatchEngine(cfg=_cfg(max_wait_ms=60_000.0)) as eng:
        _, mat = _graph(rng, 48)
        h = jnp.asarray(rng.normal(size=(48, D)).astype(np.float32))
        fut = eng.submit(mat, h)
        assert eng.step() == 0
        assert eng.step(force=True) == 1
        assert fut.done()


def test_continuous_close_resolves_everything(rng):
    eng = ContinuousBatchEngine(cfg=_cfg())
    dense, mat = _graph(rng, 48)
    h = jnp.asarray(rng.normal(size=(48, D)).astype(np.float32))
    futs = [eng.submit(mat, h) for _ in range(6)]
    eng.close()
    # close drains: every admitted future resolves with its result
    for f in futs:
        np.testing.assert_allclose(f.result(timeout=1.0),
                                   dense @ np.asarray(h),
                                   rtol=2e-4, atol=2e-4)
    with pytest.raises(RuntimeError):
        eng.submit(mat, h)


def test_continuous_rejects_stat_less_and_mismatched(rng):
    with ContinuousBatchEngine(cfg=_cfg()) as eng:
        _, mat = _graph(rng, 48)
        with pytest.raises(ValueError):
            eng.submit(mat, jnp.zeros((40, D), jnp.float32))
        with pytest.raises(ValueError):
            eng.submit(mat, jnp.zeros((48, D), jnp.float32), steps=0)


def test_continuous_adaptive_ladder_feeds_executor(rng):
    cfg = _cfg(adaptive=True,
               ladder=LadderConfig(min_fit=4, n_rungs=4))
    with ContinuousBatchEngine(cfg=cfg) as eng:
        _, mat = _graph(rng, 100)
        h = jnp.asarray(rng.normal(size=(100, D)).astype(np.float32))
        for _ in range(6):
            eng.infer(mat, h)
        rep = eng.report()["executor"]
        assert rep["ladder"]["fitted"]
        # post-fit traffic lands on a learned rung, not a geometric step
        assert any(k.startswith("r112x") for k in rep["waste"]
                   .get("per_bucket", {}))


def test_per_bucket_waste_sums_to_aggregate(rng):
    with ContinuousBatchEngine(cfg=_cfg()) as eng:
        for n in (48, 80, 48, 130):
            _, mat = _graph(rng, n)
            h = jnp.asarray(rng.normal(size=(n, D)).astype(np.float32))
            eng.submit(mat, h)
        eng.drain()
        padding = eng.report()["executor"]["waste"]
        per = padding["per_bucket"]
        assert len(per) >= 2
        for field in ("real_rows", "padded_rows", "real_nnz", "padded_nnz"):
            assert sum(v[field] for v in per.values()) == padding[field]


# ---------------------------------------------------------------------------
# BatchServingEngine integration (adaptive opt-in + close regression)
# ---------------------------------------------------------------------------


def test_micro_engine_adaptive_opt_in(rng):
    from repro.serve.engine import BatchServeConfig, BatchServingEngine

    scfg = BatchServeConfig(max_batch=8, max_delay_ms=2.0, adaptive=True,
                            ladder=LadderConfig(min_fit=4, n_rungs=4))
    with BatchServingEngine(scfg=scfg) as eng:
        dense, mat = _graph(rng, 100)
        h = jnp.asarray(rng.normal(size=(100, D)).astype(np.float32))
        futs = [eng.submit(mat, h) for _ in range(12)]
        eng.drain()
        for f in futs:
            np.testing.assert_allclose(f.result(), dense @ np.asarray(h),
                                       rtol=2e-4, atol=2e-4)
        assert eng.report()["executor"]["ladder"]["fitted"]


def test_micro_engine_close_drains_inflight(rng):
    from repro.serve.engine import BatchServeConfig, BatchServingEngine

    scfg = BatchServeConfig(max_batch=4, max_delay_ms=1.0)
    eng = BatchServingEngine(scfg=scfg)
    dense, mat = _graph(rng, 48)
    h = jnp.asarray(rng.normal(size=(48, D)).astype(np.float32))
    futs = [eng.submit(mat, h) for _ in range(10)]
    eng.close()  # must drain, not strand
    for f in futs:
        assert f.done()
        np.testing.assert_allclose(f.result(timeout=1.0),
                                   dense @ np.asarray(h),
                                   rtol=2e-4, atol=2e-4)
    with pytest.raises(RuntimeError):
        eng.submit(mat, h)
