"""Batched multi-graph execution: block-diagonal composition, shape
bucketing, the bucketed compilation cache, and the micro-batching
serving engine.

Covers the acceptance contract of the batching subsystem:
  * block-diagonal ``B @ H`` equals per-graph ``A_i @ H_i`` stacking at
    0.5/0.9/0.99 sparsity for both the element (csr) and blocked (ell)
    forms;
  * ``unbatch`` round-trips; batched SDDMM equals per-graph SDDMM;
  * gradients through the batched product match per-graph gradients;
  * >= 100 mixed-shape requests compile at most O(#buckets) executors
    (trace-count pin);
  * the serving engine returns per-request results identical to the
    unbatched forward, and reports latency/throughput/padding counters.
"""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.batch import (BatchedSparseMatrix, Bucket, BucketingConfig,
                         BucketedExecutor, batch_matmul, batch_sddmm,
                         bucket_for, canonical_stats, empty_in_bucket,
                         pad_to_bucket, quantize_up)
from repro.sparse import SparseMatrix

SWEEP = [0.5, 0.9, 0.99]
BLOCK = (16, 16)
SIZES = [48, 80, 33]  # deliberately not block-aligned (33)
D = 8


def _uniform_sparse(rng, n, sparsity):
    mask = rng.random((n, n)) < (1.0 - sparsity)
    dense = np.where(mask, rng.normal(size=(n, n)), 0.0).astype(np.float32)
    if not dense.any():  # keep at least one nonzero at 0.99 sparsity
        dense[0, 0] = 1.0
    return dense


def _family(rng, sparsity, formats=("ell", "csr")):
    denses = [_uniform_sparse(rng, n, sparsity) for n in SIZES]
    mats = [SparseMatrix.from_dense(a, formats=formats, block=BLOCK)
            for a in denses]
    hs = [jnp.asarray(rng.normal(size=(a.shape[1], D)).astype(np.float32))
          for a in denses]
    return denses, mats, hs


# ---------------------------------------------------------------------------
# block-diagonal composition
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sparsity", SWEEP)
@pytest.mark.parametrize("fmt", ["csr", "ell"])
def test_blockdiag_matmul_matches_pergraph(rng, sparsity, fmt):
    denses, mats, hs = _family(rng, sparsity)
    ys = batch_matmul(mats, hs, formats=(fmt,), policy=fmt)
    for y, a, h in zip(ys, denses, hs):
        np.testing.assert_allclose(np.asarray(y), a @ np.asarray(h),
                                   rtol=2e-4, atol=2e-4)


def test_blockdiag_multiform_auto_policy(rng):
    denses, mats, hs = _family(rng, 0.9)
    B = BatchedSparseMatrix.from_matrices(mats)
    assert B.formats == ("ell", "csr") and B.n_graphs == 3
    # offsets are padded (block-aligned), so both forms agree on them
    assert all(seg.rows % BLOCK[0] == 0 for seg in B.segments)
    ys = B.unbatch(B @ B.batch_features(hs))
    for y, a, h in zip(ys, denses, hs):
        np.testing.assert_allclose(np.asarray(y), a @ np.asarray(h),
                                   rtol=2e-4, atol=2e-4)


def test_unbatch_roundtrip(rng):
    _, mats, hs = _family(rng, 0.9)
    B = BatchedSparseMatrix.from_matrices(mats)
    got = B.unbatch(B.batch_features(hs), space="cols")
    for h, back in zip(hs, got):
        np.testing.assert_array_equal(np.asarray(h), np.asarray(back))
    # values split recovers each graph's stored values (both forms)
    for fmt in ("csr", "ell"):
        Bf = BatchedSparseMatrix.from_matrices(mats, formats=(fmt,))
        parts = Bf.unbatch_values(Bf.matrix.data, form=fmt)
        for m, part in zip(mats, parts):
            vals = m.form(fmt)[2] if fmt == "csr" else m.form(fmt).blocks
            np.testing.assert_array_equal(np.asarray(vals),
                                          np.asarray(part))


@pytest.mark.parametrize("fmt", ["csr", "ell"])
def test_batch_sddmm_matches_pergraph(rng, fmt):
    denses, mats, hs = _family(rng, 0.9)
    bs = [jnp.asarray(rng.normal(size=(a.shape[0], 4)).astype(np.float32))
          for a in denses]
    cs = [jnp.asarray(rng.normal(size=(4, a.shape[1])).astype(np.float32))
          for a in denses]
    B = BatchedSparseMatrix.from_matrices(mats, formats=(fmt,))
    got = batch_sddmm(B, bs, cs, policy=fmt)
    for v, m, b, c in zip(got, mats, bs, cs):
        ref = m.to(fmt).sddmm(b, c, policy=fmt).data
        np.testing.assert_allclose(np.asarray(v), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


def test_blockdiag_gradients_match_pergraph(rng):
    denses, mats, hs = _family(rng, 0.9, formats=("csr",))
    B = BatchedSparseMatrix.from_matrices(mats)

    def batched_loss(vals, flat_h):
        y = B.matrix.with_data(vals) @ flat_h
        return jnp.sum(jnp.tanh(y))

    H = B.batch_features(hs)
    gv, gh = jax.grad(batched_loss, argnums=(0, 1))(B.matrix.data, H)
    gv_parts = B.unbatch_values(gv)
    gh_parts = B.unbatch(gh, space="cols")
    for m, h, gvp, ghp in zip(mats, hs, gv_parts, gh_parts):
        def loss(vals, hh, m=m):
            return jnp.sum(jnp.tanh(m.with_data(vals) @ hh))

        rv, rh = jax.grad(loss, argnums=(0, 1))(m.data, h)
        np.testing.assert_allclose(np.asarray(gvp), np.asarray(rv),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(ghp), np.asarray(rh),
                                   rtol=2e-4, atol=2e-4)


def test_from_matrices_rejects_mismatches(rng):
    _, mats, _ = _family(rng, 0.9)
    with pytest.raises(ValueError, match="at least one matrix"):
        BatchedSparseMatrix.from_matrices([])
    with pytest.raises(ValueError, match="carry no 'ell'"):
        BatchedSparseMatrix.from_matrices(
            [mats[0], mats[1].to("csr")], formats=("ell",))
    B = BatchedSparseMatrix.from_matrices(mats)
    with pytest.raises(ValueError, match="feature blocks"):
        B.batch_features([jnp.zeros((SIZES[0], D))])


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------


def test_quantize_up_grid():
    assert quantize_up(1, 32, 2.0) == 32
    assert quantize_up(32, 32, 2.0) == 32
    assert quantize_up(33, 32, 2.0) == 64
    assert quantize_up(129, 32, 2.0) == 256
    # monotone and always covering
    prev = 0
    for x in range(1, 2000, 7):
        q = quantize_up(x, 32, 2.0)
        assert q >= x and q >= prev
        prev = q


def test_bucket_padding_preserves_product_and_canonical_stats(rng):
    a = _uniform_sparse(rng, 70, 0.9)
    A = SparseMatrix.from_dense(a, formats=("ell", "csr"), block=BLOCK)
    h = jnp.asarray(rng.normal(size=(70, D)).astype(np.float32))
    bucket = bucket_for(A.stats)
    assert bucket.rows >= A.stats.shape[0]
    assert bucket.rows % BLOCK[0] == 0
    for form in ("csr", "ell"):
        P = pad_to_bucket(A, bucket, form=form)
        assert P.shape == (bucket.rows, bucket.cols)
        assert P.stats == canonical_stats(bucket)
        hp = jnp.zeros((bucket.cols, D), h.dtype).at[:70].set(h)
        y = np.asarray(P @ hp)[:70]
        np.testing.assert_allclose(y, a @ np.asarray(h),
                                   rtol=2e-4, atol=2e-4)
        # the all-zero batch filler is harmless under the product
        E = empty_in_bucket(bucket, form=form)
        assert np.asarray(E @ hp).max() == 0.0


def test_executor_trace_count_pin_100_mixed_requests(rng):
    """>= 100 mixed-shape requests compile O(#buckets) executors."""
    ex = BucketedExecutor(max_batch=16,
                          bucketing=BucketingConfig(growth=2.0))
    mats, hs, refs = [], [], []
    for i in range(104):
        n = int(rng.integers(20, 150))
        a = _uniform_sparse(rng, n, 0.92)
        mats.append(SparseMatrix.from_dense(a, formats=("ell", "csr"),
                                            block=BLOCK))
        h = jnp.asarray(rng.normal(size=(n, D)).astype(np.float32))
        hs.append(h)
        refs.append(a @ np.asarray(h))
    for lo in range(0, len(mats), 16):  # serve in micro-batches of 16
        outs = ex.run(mats[lo:lo + 16], hs[lo:lo + 16])
        for o, r in zip(outs, refs[lo:lo + 16]):
            np.testing.assert_allclose(o, r, rtol=2e-4, atol=2e-4)
    rep = ex.report()
    assert rep["requests"] == 104
    # the pin: compiles bounded by the bucket grid (7 buckets x a few
    # quantized batch sizes for this seed), not by the traffic
    assert rep["compiles"] == rep["executors_cached"] <= 22
    assert rep["compiles"] < rep["requests"] // 4
    assert rep["buckets"] <= 8
    # identical traffic replay: zero new compiles
    before = ex.compiles
    ex.run(mats[:16], hs[:16])
    assert ex.compiles == before
    waste = rep["waste"]
    assert waste["padded_nnz"] >= waste["real_nnz"] > 0
    assert 0.0 <= waste["waste_fraction"] < 1.0


def test_executor_lru_eviction(rng):
    ex = BucketedExecutor(max_batch=1, max_executors=2)
    for i, n in enumerate([30, 60, 120, 240]):
        a = _uniform_sparse(rng, n, 0.9)
        m = SparseMatrix.from_dense(a, formats=("ell", "csr"), block=BLOCK)
        ex.run([m], [jnp.zeros((n, D), jnp.float32)])
    rep = ex.report()
    assert rep["executors_cached"] <= 2
    assert rep["evictions"] >= 2


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gcn_setup():
    from repro.configs.paper_gnn import SMOKE_CONFIG as GCFG
    from repro.data.pipeline import random_graph
    from repro.models.gnn import build_graph, init_gcn

    params = init_gcn(jax.random.PRNGKey(0), GCFG)
    graphs = [build_graph(random_graph(n, avg_degree=4, seed=n), GCFG)
              for n in (48, 80, 33)]
    return GCFG, params, graphs


def test_gcn_forward_batched_matches_pergraph(rng, gcn_setup):
    from repro.models.gnn import batch_graphs, gcn_forward, \
        gcn_forward_batched

    cfg, params, graphs = gcn_setup
    xs = [jnp.asarray(rng.normal(size=(g.n_nodes, cfg.in_features))
                      .astype(np.float32)) for g in graphs]
    B = batch_graphs(graphs)
    outs = gcn_forward_batched(params, B, xs)
    for o, g, x in zip(outs, graphs, xs):
        ref = gcn_forward(params, g, x, policy="csr")
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)


def test_batch_serving_engine_end_to_end(rng, gcn_setup):
    from repro.models.gnn import gcn_forward
    from repro.serve.engine import BatchServeConfig, BatchServingEngine

    cfg, params, graphs = gcn_setup
    with BatchServingEngine.for_gcn(
            params, scfg=BatchServeConfig(max_batch=8,
                                          max_delay_ms=2.0)) as eng:
        futs, reqs = [], []
        for i in range(24):
            g = graphs[i % len(graphs)]
            x = jnp.asarray(rng.normal(size=(g.n_nodes, cfg.in_features))
                            .astype(np.float32))
            reqs.append((g, x))
            futs.append(eng.submit(g, x))
        for f, (g, x) in zip(futs, reqs):
            y = f.result(timeout=300)
            assert y.shape == (g.n_nodes, cfg.n_classes)
            ref = gcn_forward(params, g, x, policy="csr")
            np.testing.assert_allclose(y, np.asarray(ref),
                                       rtol=2e-3, atol=2e-3)
        eng.drain()
        rep = eng.report()
    assert rep["completed"] == rep["submitted"] == 24
    assert rep["req_per_s"] > 0
    assert rep["p99_ms"] >= rep["p50_ms"] > 0
    assert sum(rep["flushes"].values()) >= 1
    ex = rep["executor"]
    assert ex["compiles"] <= ex["calls"] <= rep["completed"]
    assert 0.0 <= ex["waste"]["waste_fraction"] < 1.0


def test_batch_serving_engine_error_propagates(gcn_setup):
    from repro.serve.engine import BatchServeConfig, BatchServingEngine

    cfg, params, graphs = gcn_setup
    with BatchServingEngine.for_gcn(
            params, scfg=BatchServeConfig(max_batch=4,
                                          max_delay_ms=1.0)) as eng:
        bad = jnp.zeros((graphs[0].n_nodes + 1, cfg.in_features),
                        jnp.float32)  # wrong node count
        with pytest.raises(ValueError, match="do not match"):
            eng.submit(graphs[0], bad).result(timeout=60)
        # failed requests still count as resolved: drain must not hang
        eng.drain(timeout=60)
        assert eng.report()["failed"] == 1
        # the engine keeps serving after a failed flush
        good = jnp.zeros((graphs[0].n_nodes, cfg.in_features), jnp.float32)
        y = eng.infer(graphs[0], good)
        assert y.shape == (graphs[0].n_nodes, cfg.n_classes)
        eng.drain(timeout=60)
        eng.reset_metrics()
        rep = eng.report()
        assert rep["submitted"] == rep["completed"] == rep["failed"] == 0


# ---------------------------------------------------------------------------
# per-engine plan-cache reporting (no cross-engine aliasing)
# ---------------------------------------------------------------------------


def test_batch_serving_engine_close_fails_queued_futures(gcn_setup):
    from repro.serve.engine import BatchServeConfig, BatchServingEngine

    cfg, params, graphs = gcn_setup
    eng = BatchServingEngine.for_gcn(
        params, scfg=BatchServeConfig(max_batch=4, max_delay_ms=1.0))
    x = jnp.zeros((graphs[0].n_nodes, cfg.in_features), jnp.float32)
    futs = [eng.submit(graphs[0], x) for _ in range(6)]
    eng.close()
    # every future resolves: with a result (flushed before close) or
    # with the engine-closed error — never left hanging
    for f in futs:
        try:
            y = f.result(timeout=60)
            assert y.shape == (graphs[0].n_nodes, cfg.n_classes)
        except RuntimeError as exc:
            assert "engine closed" in str(exc)
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(graphs[0], x)


def test_per_engine_plan_cache_not_aliased(rng, gcn_setup):
    from repro.data.pipeline import random_graph
    from repro.models.gnn import build_graph, init_gcn
    from repro.serve.engine import GNNServingEngine

    cfg, params, _ = gcn_setup
    g1 = build_graph(random_graph(48, avg_degree=4, seed=91), cfg)
    g2 = build_graph(random_graph(64, avg_degree=4, seed=92), cfg)
    e1 = GNNServingEngine(params, g1)
    e2 = GNNServingEngine(params, g2)
    x1 = rng.normal(size=(48, cfg.in_features)).astype(np.float32)
    e1.infer(x1)
    s1 = e1.dispatch_report()["plan_cache"]
    assert s1["misses"] > 0  # the jitted forward planned on this graph
    # traffic on engine 2 must not move engine 1's counters
    for _ in range(3):
        e2.infer(rng.normal(size=(64, cfg.in_features)).astype(np.float32))
    assert e1.dispatch_report()["plan_cache"] == s1
    s2 = e2.dispatch_report()["plan_cache"]
    assert s2["misses"] > 0
    # the global aggregate still counts both engines
    g = e1.dispatch_report()["plan_cache_global"]
    assert g["misses"] >= s1["misses"] + s2["misses"]


def test_gnn_serving_engine_width_inference(gcn_setup):
    from repro.models.gnn import init_gat
    from repro.serve.engine import (GNNServeConfig, GNNServingEngine,
                                    _infer_planning_width)

    cfg, params, graphs = gcn_setup
    # GAT-style params (extra per-layer attention leaves) infer cleanly
    gat_params = init_gat(jax.random.PRNGKey(1), cfg)
    assert _infer_planning_width(gat_params) == cfg.hidden
    eng = GNNServingEngine(gat_params, graphs[0])
    assert eng.plan.path in ("ell", "csr")
    # a single weight array under "w" (no list wrapper) works too
    single = {"w": np.ones((cfg.in_features, 7), np.float32)}
    assert _infer_planning_width(single) == 7
    # layouts without the {"w": ...} convention fall back to leaf scan
    odd = {"weights": [np.ones((cfg.in_features, 5), np.float32)]}
    assert _infer_planning_width(odd) == 5
    assert GNNServingEngine(odd, graphs[0]).plan.path in ("ell", "csr")
    # no 2-D leaf at all: explicit override required and honored
    with pytest.raises(ValueError, match="planning feature width"):
        _infer_planning_width({"bias": np.ones((3,), np.float32)})
    eng3 = GNNServingEngine({"bias": np.ones((3,), np.float32)}, graphs[0],
                            GNNServeConfig(d=64))
    assert eng3.plan.path in ("ell", "csr")


def test_block_diag_sell_composition(rng):
    """Sell forms compose block-diagonally: one planned SpMM over the
    batch equals per-graph products, on both execution routes."""
    from repro.sparse import matmul

    mats, denses, hs = [], [], []
    for n, s in ((40, 0.97), (64, 0.99), (24, 0.9)):
        dense = np.where(rng.random((n, n)) < (1 - s),
                         rng.normal(size=(n, n)), 0).astype(np.float32)
        denses.append(dense)
        mats.append(SparseMatrix.from_dense(
            dense, formats=("sell", "csr"), block=(8, 8)))
        hs.append(rng.normal(size=(n, 6)).astype(np.float32))
    B = BatchedSparseMatrix.from_matrices(mats)
    assert "sell" in B.formats
    H = B.batch_features(hs)
    for kwargs in ({"policy": "sell"},
                   {"policy": "sell", "use_kernel": True,
                    "interpret": True}):
        outs = B.unbatch(matmul(B.matrix, H, **kwargs))
        for o, d, h in zip(outs, denses, hs):
            np.testing.assert_allclose(np.asarray(o), d @ h,
                                       rtol=5e-4, atol=5e-4)
    # sell values split back per graph by slot count
    splits = B.unbatch_values(B.matrix.form("sell").slot_vals,
                              form="sell")
    assert [int(v.shape[0]) for v in splits] == \
        [m.form("sell").n_slots for m in mats]
    # composed stats price the sell path (sum of per-graph slot volumes)
    assert B.stats.sell_stored_elements == \
        sum(m.stats.sell_stored_elements for m in mats)


# ---------------------------------------------------------------------------
# serving-engine worker-loop hardening (deadline clamp regressions)
# ---------------------------------------------------------------------------


def _inject(eng, graph, x, t_submit):
    """Enqueue a request with a forged submit timestamp, bypassing
    ``submit`` — the only way to exercise the worker loop's handling of
    requests whose window math is already skewed when they arrive."""
    from concurrent.futures import Future

    from repro.serve.engine import _Request

    req = _Request(matrix=graph.adj, features=x, future=Future(),
                   t_submit=t_submit)
    if eng._t_first is None:
        eng._t_first = req.t_submit
    eng._submitted += 1
    eng._queue.put(req)
    return req.future


def test_slow_request_flushes_on_deadline_immediately(gcn_setup):
    """A request that sat queued past its whole window (stale t_submit)
    must flush *now* via the deadline path — the worker must not wait
    another window for company — and the engine keeps serving after."""
    import time

    from repro.serve.engine import BatchServeConfig, BatchServingEngine

    cfg, params, graphs = gcn_setup
    g = graphs[0]
    x = jnp.zeros((g.n_nodes, cfg.in_features), jnp.float32)
    with BatchServingEngine.for_gcn(
            params, scfg=BatchServeConfig(max_batch=8,
                                          max_delay_ms=50.0)) as eng:
        eng.infer(g, x)  # warm the executor so compile time is gone
        eng.drain(timeout=60)
        before = eng.report()["flushes"]
        fut = _inject(eng, g, x, time.perf_counter() - 1.0)  # long stale
        y = fut.result(timeout=60)
        assert y.shape == (g.n_nodes, cfg.n_classes)
        eng.drain(timeout=60)
        after = eng.report()["flushes"]
        # exactly one new flush, on the deadline path (1 req < max_batch)
        assert after["deadline"] == before["deadline"] + 1
        assert after["full"] == before["full"]
        # worker alive and serving
        assert eng._worker.is_alive()
        eng.infer(g, x)


def test_skewed_future_timestamp_wait_is_bounded(gcn_setup):
    """A forged *future* t_submit (clock skew, replayed request) must
    not stall the worker for the skew: any single wait is clamped to
    one delay window.  Pre-clamp the worker slept ~30 s here."""
    import time

    from repro.serve.engine import BatchServeConfig, BatchServingEngine

    cfg, params, graphs = gcn_setup
    g = graphs[0]
    x = jnp.zeros((g.n_nodes, cfg.in_features), jnp.float32)
    with BatchServingEngine.for_gcn(
            params, scfg=BatchServeConfig(max_batch=8,
                                          max_delay_ms=5.0)) as eng:
        eng.infer(g, x)  # warm executor
        fut = _inject(eng, g, x, time.perf_counter() + 30.0)
        y = fut.result(timeout=10)  # pre-fix: stuck ~30 s, times out
        assert y.shape == (g.n_nodes, cfg.n_classes)
        assert eng._worker.is_alive()


@pytest.mark.parametrize("delay_ms", [0.0, -3.0])
def test_non_positive_delay_degrades_to_greedy_flushing(gcn_setup,
                                                        delay_ms):
    """max_delay_ms <= 0 means greedy flushing: every request resolves,
    the worker thread survives (a negative Queue.get timeout would
    raise ValueError and strand every queued future)."""
    from repro.serve.engine import BatchServeConfig, BatchServingEngine

    cfg, params, graphs = gcn_setup
    g = graphs[0]
    x = jnp.zeros((g.n_nodes, cfg.in_features), jnp.float32)
    with BatchServingEngine.for_gcn(
            params, scfg=BatchServeConfig(max_batch=4,
                                          max_delay_ms=delay_ms)) as eng:
        futs = [eng.submit(g, x) for _ in range(6)]
        for f in futs:
            y = f.result(timeout=60)
            assert y.shape == (g.n_nodes, cfg.n_classes)
        eng.drain(timeout=60)
        rep = eng.report()
        assert rep["completed"] == rep["submitted"] == 6
        assert rep["failed"] == 0
        assert eng._worker.is_alive()
