"""Unified observability layer: ``repro.obs`` and its integrations.

Pins the contracts the obs layer exports to the rest of the repo:

  * the metrics registry (counters/gauges/histograms with label sets,
    snapshot schema, Prometheus/JSON-lines exporters, thread safety);
  * span tracing (parent propagation, summary, bounded ring);
  * the retrace sentry (warmup budget, unexpected-retrace flagging,
    eviction forgiveness) — including an **injected shape-drift
    retrace** through a real jitted executor, and zero unexpected
    retraces across a steady-state continuous-batching run;
  * the cost-model audit (stats buckets, predicted-vs-measured rows,
    misprediction detection);
  * the thread-safe bounded dispatch ring log;
  * the **schema pins** for every ``report()`` and for
    ``obs.snapshot()`` — these are the keys dashboards consume;
    renaming one is a breaking change that must show up here;
  * the one-snapshot acceptance contract: a single adaptive serving
    run surfaces dispatcher plan counts, per-lane compiles/calls,
    padding waste, latency histograms, and audit rows.
"""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.batch.executor import BucketedExecutor, ExecutorKey
from repro.obs.audit import CostAudit, stats_bucket
from repro.obs.registry import MetricsRegistry
from repro.obs.sentry import RetraceSentry, instrumented_jit
from repro.obs.tracing import Tracer
from repro.sparse import SparseMatrix

BLOCK = (16, 16)
D = 8


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Every test sees empty process-wide instruments."""
    obs.reset()
    yield
    obs.reset()


def _graph(rng, n: int, sparsity: float = 0.9):
    dense = np.where(rng.random((n, n)) < (1.0 - sparsity),
                     rng.normal(size=(n, n)), 0.0).astype(np.float32)
    if not dense.any():
        dense[0, 0] = 1.0
    return dense, SparseMatrix.from_dense(dense, formats=("ell", "csr"),
                                          block=BLOCK)


def _requests(rng, sizes):
    mats, hs = [], []
    for n in sizes:
        _, m = _graph(rng, n)
        mats.append(m)
        hs.append(jnp.asarray(rng.normal(size=(n, D)).astype(np.float32)))
    return mats, hs


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("reqs", engine="a").inc()
    reg.counter("reqs", engine="a").inc(4)
    reg.counter("reqs", engine="b").inc()
    reg.gauge("depth").set(3.5)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.histogram("lat_ms").observe(v)
    assert reg.value("reqs", engine="a") == 5
    assert reg.value("reqs", engine="b") == 1
    assert reg.total("reqs") == 6
    assert reg.value("depth") == 3.5
    snap = reg.snapshot()
    assert snap["counters"]["reqs"] == {"engine=a": 5, "engine=b": 1}
    h = snap["histograms"]["lat_ms"][""]
    assert h["count"] == 4 and h["min"] == 1.0 and h["max"] == 4.0
    assert h["p50"] <= h["p90"] <= h["p99"]


def test_registry_counter_rejects_negative_and_kind_conflict():
    reg = MetricsRegistry()
    reg.counter("x").inc()
    with pytest.raises(ValueError):
        reg.counter("x").inc(-1)
    with pytest.raises(ValueError):
        reg.gauge("x")  # same name, different kind


def test_registry_exporters_and_reset():
    reg = MetricsRegistry()
    reg.counter("hits", route="spmm").inc(2)
    reg.histogram("ms").observe(1.5)
    prom = reg.to_prometheus()
    assert "# TYPE hits counter" in prom
    assert 'hits{route="spmm"} 2' in prom
    assert "ms_count 1" in prom
    lines = [json.loads(ln) for ln in reg.to_jsonl().splitlines()]
    assert any(ln["name"] == "hits" and ln["value"] == 2 for ln in lines)
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_registry_thread_safety():
    reg = MetricsRegistry()

    def work():
        for _ in range(1000):
            reg.counter("n").inc()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.value("n") == 8000


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_span_nesting_and_parent_propagation():
    tr = Tracer()
    with tr.span("outer", job="x"):
        with tr.span("inner"):
            pass
    outer = tr.spans("outer")[0]
    inner = tr.spans("inner")[0]
    assert inner.parent_id == outer.span_id
    assert inner.trace_id == outer.trace_id
    assert outer.parent_id is None
    assert outer.dur_ms >= inner.dur_ms >= 0.0
    summ = tr.summary()
    assert set(summ) == {"outer", "inner"}
    assert set(summ["outer"]) == {"count", "total_ms", "p50_ms", "max_ms"}


def test_span_ring_is_bounded():
    tr = Tracer(capacity=8)
    for i in range(32):
        with tr.span("s", i=i):
            pass
    assert len(tr.spans()) == 8


def test_span_feeds_registry_histogram():
    with obs.span("unit.test"):
        pass
    hists = obs.REGISTRY.snapshot()["histograms"]
    assert hists["span_ms"]["span=unit.test"]["count"] == 1


# ---------------------------------------------------------------------------
# RetraceSentry
# ---------------------------------------------------------------------------


def test_sentry_warmup_then_flags():
    reg = MetricsRegistry()
    sen = RetraceSentry(registry=reg, warmup=1)
    assert sen.record_compile("lane-a") is False      # warmup
    sen.record_call("lane-a")
    assert sen.record_compile("lane-a") is True       # past budget
    rep = sen.report()
    assert rep["compiles"] == 2 and rep["calls"] == 1
    assert rep["unexpected_retraces"] == 1
    assert rep["events"][0]["lane"] == "lane-a"
    assert reg.value("unexpected_retrace_total", lane="lane-a") == 1


def test_sentry_forget_forgives_post_eviction_recompile():
    sen = RetraceSentry(registry=MetricsRegistry(), warmup=1)
    sen.record_compile("lane-a")
    sen.forget("lane-a")              # evicted from the LRU
    assert sen.record_compile("lane-a") is False   # legitimate recompile
    assert sen.record_compile("lane-a") is True    # but only one


def test_instrumented_jit_counts_compiles_and_calls():
    sen = RetraceSentry(registry=MetricsRegistry(), warmup=1)
    fn = instrumented_jit(lambda x: x * 2, "lane-j", sentry=sen)
    np.testing.assert_allclose(fn(jnp.ones((4,))), 2 * np.ones(4))
    fn(jnp.ones((4,)))                     # same shape: no retrace
    assert sen.report()["unexpected_retraces"] == 0
    fn(jnp.ones((8,)))                     # shape drift: retrace
    rep = sen.report()
    assert rep["compiles"] == 2
    assert rep["unexpected_retraces"] == 1


# ---------------------------------------------------------------------------
# CostAudit
# ---------------------------------------------------------------------------


def test_stats_bucket_is_coarse_and_stable():
    from repro.dispatch.stats import MatrixStats

    rng = np.random.default_rng(3)
    r = rng.integers(0, 100, 300)
    c = rng.integers(0, 100, 300)
    s1 = MatrixStats.from_coords((100, 100), r, c)
    s2 = MatrixStats.from_coords((120, 120), r, c)
    assert stats_bucket(s1) == stats_bucket(s2)  # same pow2 / decade
    assert stats_bucket(s1).startswith("n128/")


def test_audit_rows_summary_and_mispredictions():
    aud = CostAudit(registry=MetricsRegistry())
    # model says csr is cheaper, but measured says ell won: that is a
    # misprediction once both paths have run in the same bucket
    for _ in range(3):
        aud.record_raw(op="spmm", path="csr", measured_ms=5.0, bucket="b0",
                       costs={"csr": 1.0, "ell": 2.0}, policy="auto")
        aud.record_raw(op="spmm", path="ell", measured_ms=1.0, bucket="b0",
                       costs={"csr": 1.0, "ell": 2.0}, policy="auto")
    assert len(aud.rows()) == 6
    summ = aud.summary()
    assert summ["spmm/csr/b0"]["n"] == 3
    assert summ["spmm/csr/b0"]["measured_ms_mean"] == pytest.approx(5.0)
    assert summ["spmm/csr/b0"]["predicted_mean"] == pytest.approx(1.0)
    mis = aud.mispredictions()
    assert len(mis) == 1
    assert mis[0]["predicted_best"] == "csr"
    assert mis[0]["measured_best"] == "ell"


def test_audit_filters_non_finite_and_is_bounded():
    aud = CostAudit(registry=MetricsRegistry(), capacity=4)
    for i in range(10):
        aud.record_raw(op="spmm", path="csr", measured_ms=1.0, bucket="b",
                       costs={"csr": float("inf"), "ell": 1.0},
                       policy="auto")
    rows = aud.rows()
    assert len(rows) == 4                       # ring capacity
    assert "csr" not in dict(rows[0].costs)     # inf filtered
    assert rows[0].predicted is None            # chosen path's cost was inf


# ---------------------------------------------------------------------------
# Dispatch ring log (satellite 1)
# ---------------------------------------------------------------------------


def test_dispatch_log_ring_capacity_and_clear(rng):
    from repro import dispatch
    from repro.sparse import ops

    dispatch.clear_log()
    old = dispatch.log_capacity()
    try:
        dispatch.set_log_capacity(3)
        _, m = _graph(rng, 32)
        h = jnp.asarray(rng.normal(size=(32, D)).astype(np.float32))
        for _ in range(5):
            ops.matmul(m, h, policy="csr", candidates=("csr",))
        log = dispatch.dispatch_log()
        assert len(log) == 3                    # bounded, newest kept
        assert dispatch.last_plan() is log[-1]
        dispatch.clear_log()
        assert not dispatch.dispatch_log()
        with pytest.raises(ValueError):
            dispatch.set_log_capacity(0)
    finally:
        dispatch.set_log_capacity(old)


def test_dispatch_log_concurrent_records():
    from repro import dispatch
    from repro.dispatch.dispatcher import Plan, record_plan

    dispatch.clear_log()
    old = dispatch.log_capacity()
    try:
        dispatch.set_log_capacity(64)

        def record():
            for _ in range(100):
                record_plan(Plan(op="spmm", path="csr", policy="auto",
                                 reason="test", use_kernel=False,
                                 interpret=False))

        threads = [threading.Thread(target=record) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(dispatch.dispatch_log()) == 64   # capacity, no tears
        assert obs.REGISTRY.total("dispatch_plans_total") == 600
    finally:
        dispatch.set_log_capacity(old)
        dispatch.clear_log()


# ---------------------------------------------------------------------------
# Report schema pins (satellite 3)
# ---------------------------------------------------------------------------


def test_snapshot_schema():
    snap = obs.snapshot()
    assert set(snap) == {"metrics", "spans", "sentry", "audit"}
    assert set(snap["metrics"]) == {"counters", "gauges", "histograms"}
    assert set(snap["sentry"]) == {"lanes", "compiles", "calls",
                                   "unexpected_retraces", "events"}
    assert set(snap["audit"]) == {"rows", "summary", "mispredictions"}
    json.dumps(snap)                            # always serializable


def test_executor_report_schema(rng):
    ex = BucketedExecutor(policy="csr")
    mats, hs = _requests(rng, (32, 48))
    ex.run(mats, hs)
    rep = ex.report()
    assert {"requests", "calls", "compiles", "executors_cached",
            "evictions", "buckets", "waste"} <= set(rep)
    assert {"real_rows", "padded_rows", "real_nnz", "padded_nnz",
            "row_blowup", "nnz_blowup",
            "waste_fraction"} <= set(rep["waste"])


def test_engine_reports_use_canonical_latency_keys(rng):
    from repro.serve.engine import BatchServeConfig, BatchServingEngine
    from repro.serve.runtime import ContinuousBatchEngine, ContinuousConfig

    mats, hs = _requests(rng, (32, 48, 32))
    with BatchServingEngine(
            scfg=BatchServeConfig(max_batch=4, adaptive=True)) as eng:
        futs = [eng.submit(m, h) for m, h in zip(mats, hs)]
        eng.drain()
        [f.result(timeout=60) for f in futs]
        rep = eng.report()
    assert {"completed", "p50_ms", "p99_ms", "executor"} <= set(rep)

    with ContinuousBatchEngine(cfg=ContinuousConfig(
            slots=2, adaptive=False, max_wait_ms=0.0)) as ceng:
        futs = [ceng.submit(m, h) for m, h in zip(mats, hs)]
        ceng.drain()
        [f.result(timeout=60) for f in futs]
        rep = ceng.report()
    assert {"submitted", "completed", "p50_ms", "p99_ms", "lanes",
            "executor"} <= set(rep)


def _bucketed_report(mats, hs):
    ex = BucketedExecutor(policy="csr")
    ex.run(mats, hs)
    return ex.report()


def _batch_engine_report(mats, hs):
    from repro.serve.engine import BatchServeConfig, BatchServingEngine

    with BatchServingEngine(
            scfg=BatchServeConfig(max_batch=4, adaptive=True)) as eng:
        futs = [eng.submit(m, h) for m, h in zip(mats, hs)]
        eng.drain()
        [f.result(timeout=60) for f in futs]
        return eng.report()


def _continuous_engine_report(mats, hs):
    from repro.serve.runtime import ContinuousBatchEngine, ContinuousConfig

    with ContinuousBatchEngine(cfg=ContinuousConfig(
            slots=2, adaptive=False, max_wait_ms=0.0)) as eng:
        futs = [eng.submit(m, h) for m, h in zip(mats, hs)]
        eng.drain()
        [f.result(timeout=60) for f in futs]
        return eng.report()


@pytest.mark.parametrize("make_report", [
    _bucketed_report, _batch_engine_report, _continuous_engine_report,
], ids=["BucketedExecutor", "BatchServingEngine", "ContinuousBatchEngine"])
def test_report_is_plain_json_dict(rng, make_report):
    """Reports are plain dicts of canonical keys: what a dashboard reads
    is what json.dumps writes."""
    mats, hs = _requests(rng, (32, 48, 32))
    rep = make_report(mats, hs)
    assert type(rep) is dict
    assert json.loads(json.dumps(rep)) == rep
    assert not {"padding", "latency_ms_p50", "latency_ms_p99"} & set(rep)


def test_ladder_and_delta_report_schemas(rng):
    from repro.serve.runtime import AdaptiveBucketLadder, DeltaGraph

    lad = AdaptiveBucketLadder()
    mats, _ = _requests(rng, (32,))
    lad.observe(mats[0].stats)
    assert {"fitted", "observed", "refits", "drift_checks", "last_drift",
            "fallbacks", "snapped_rungs", "rungs"} <= set(lad.report())
    assert obs.REGISTRY.total("ladder_observed_total") == 1

    dense, _ = _graph(rng, 32)
    dg = DeltaGraph(dense, form="csr")
    r, c = np.nonzero(dense)
    dg.delete(int(r[0]), int(c[0]))
    assert {"form", "live_nnz", "capacity", "free_slots", "deltas_applied",
            "repacks", "stats_invalidations",
            "background_repack_running"} <= set(dg.report())
    assert obs.REGISTRY.value("graph_deltas_total", op="delete") == 1


# ---------------------------------------------------------------------------
# Acceptance: one snapshot from one adaptive serving run
# ---------------------------------------------------------------------------


def test_single_adaptive_run_populates_snapshot(rng):
    from repro.serve.engine import BatchServeConfig, BatchServingEngine

    with BatchServingEngine(
            scfg=BatchServeConfig(max_batch=4, adaptive=True)) as eng:
        mats, hs = _requests(rng, (32, 48, 64, 32, 48, 32, 96, 64))
        futs = [eng.submit(m, h) for m, h in zip(mats, hs)]
        eng.drain(timeout=120.0)
        [f.result(timeout=60) for f in futs]

    snap = obs.snapshot()
    counters = snap["metrics"]["counters"]
    # dispatcher plan counts, per-lane compiles/calls, padding waste
    assert sum(counters["dispatch_plans_total"].values()) > 0
    assert sum(counters["executor_compiles_total"].values()) > 0
    assert sum(counters["executor_calls_total"].values()) > 0
    assert counters["padding_rows_padded_total"][""] \
        >= counters["padding_rows_real_total"][""] > 0
    assert sum(counters["ladder_observed_total"].values()) == 8
    # serve latency histogram
    lat = snap["metrics"]["histograms"]["serve_latency_ms"]["engine=batch"]
    assert lat["count"] == 8 and lat["p50"] > 0
    # the serve path traced end to end
    assert {"serve.admit", "serve.bucket", "serve.flush", "serve.compose",
            "serve.execute", "serve.complete"} <= set(snap["spans"])
    # predicted-vs-measured audit rows from the serving executors
    rows = snap["audit"]["rows"]
    assert rows and all(r["op"] == "spmm" and r["measured_ms"] > 0
                        for r in rows)
    assert any(r["predicted"] is not None for r in rows)
    # a clean run never flags a retrace
    assert snap["sentry"]["unexpected_retraces"] == 0
    # sentry lanes agree with the executor's own counter
    assert snap["sentry"]["compiles"] > 0


# ---------------------------------------------------------------------------
# Retrace sentry through the real serve path
# ---------------------------------------------------------------------------


def test_injected_shape_drift_flags_unexpected_retrace(rng):
    ex = BucketedExecutor(policy="csr")
    mats, hs = _requests(rng, (32, 32))
    ex.run(mats, hs)
    assert obs.SENTRY.report()["unexpected_retraces"] == 0
    key = next(iter(ex._executors))
    exe = ex.executor_for(key)
    # drive the cached lane executor with a drifted shape: jit retraces,
    # and the sentry must flag it because the lane is past warmup
    _, m = _graph(rng, 2 * key.bucket.rows)
    h = jnp.asarray(rng.normal(size=(m.shape[1], D)).astype(np.float32))
    exe(m, h)
    rep = obs.SENTRY.report()
    assert rep["unexpected_retraces"] == 1
    assert rep["events"][0]["lane"] == ex.lane_label(key)
    assert obs.REGISTRY.value("unexpected_retrace_total",
                              lane=ex.lane_label(key)) == 1


def test_steady_state_continuous_run_is_retrace_free(rng):
    from repro.serve.runtime import ContinuousBatchEngine, ContinuousConfig

    with ContinuousBatchEngine(cfg=ContinuousConfig(
            slots=2, adaptive=False, max_wait_ms=0.0)) as eng:
        for wave in range(4):       # same shapes, wave after wave
            mats, hs = _requests(rng, (48, 48, 80, 80))
            futs = [eng.submit(m, h) for m, h in zip(mats, hs)]
            eng.drain(timeout=120.0)
            [f.result(timeout=60) for f in futs]
    rep = obs.SENTRY.report()
    assert rep["calls"] > rep["compiles"] > 0
    assert rep["unexpected_retraces"] == 0


# ---------------------------------------------------------------------------
# Bench regression gate (satellite 5)
# ---------------------------------------------------------------------------


def test_regression_check_kernels():
    from benchmarks.regression_check import check_kernels

    base = {"rows": [{"name": "spmm_a", "us_per_call": 10.0},
                     {"name": "spmm_gone", "us_per_call": 1.0}]}
    cur = {"rows": [{"name": "spmm_a", "us_per_call": 25.0},
                    {"name": "spmm_new", "us_per_call": 5.0}]}
    failures, notes = check_kernels(cur, base, tolerance=2.0)
    assert len(failures) == 1 and "spmm_a" in failures[0]
    assert any("spmm_gone" in n for n in notes)
    assert any("spmm_new" in n for n in notes)
    failures, _ = check_kernels(cur, base, tolerance=3.0)
    assert failures == []


def test_regression_check_serve_flags_retrace_increase():
    from benchmarks.regression_check import check_serve

    base = {"micro_adaptive": {"req_per_s_wall": 100.0,
                               "steady_compiles": 0}}
    ok = {"micro_adaptive": {"req_per_s_wall": 60.0,
                             "steady_compiles": 0}}
    failures, _ = check_serve(ok, base)
    assert failures == []           # 1.7x slower: inside tolerance
    slow = {"micro_adaptive": {"req_per_s_wall": 40.0,
                               "steady_compiles": 0}}
    failures, _ = check_serve(slow, base)
    assert len(failures) == 1 and "req/s" in failures[0]
    retrace = {"micro_adaptive": {"req_per_s_wall": 100.0,
                                  "steady_compiles": 2}}
    failures, _ = check_serve(retrace, base)
    assert len(failures) == 1 and "steady_compiles" in failures[0]


def test_regression_check_tolerates_old_key_spellings():
    from benchmarks.regression_check import get_key

    assert get_key({"latency_ms_p50": 3.0}, "p50_ms") == 3.0
    assert get_key({"p50_ms": 4.0}, "p50_ms") == 4.0
    assert get_key({}, "p50_ms") is None
