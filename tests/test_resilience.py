"""Chaos suite: fault injection, retry/bisection, shedding, recovery.

Covers the resilience layer's acceptance contract:
  * a poison request co-batched with innocents is quarantined alone —
    bisection completes the innocents from its probe executions (the
    ``_fail_lane`` collateral-damage regression);
  * transient executor faults retry with backoff and succeed; persistent
    ones fail with a structured retries-exhausted error;
  * NaN/Inf output blocks quarantine instead of returning garbage;
  * dead worker threads restart under a bounded supervisor, and
    ``infer(timeout=)`` bounds the wait on a stuck future;
  * queue overflow sheds the lowest-priority request; expired deadlines
    fail queued requests with ``DeadlineExceededError``;
  * a form that keeps failing degrades and the lane rebuilds on the
    surviving form;
  * a chaos-killed training step restores from the newest checkpoint
    and reconverges to the same final loss;
  * a crashed background repack leaves the old overlay serving;
  * a deterministic fault storm strands nothing: every future resolves,
    non-poison requests complete exactly once with correct results, and
    every recovery action is visible in ``obs.snapshot()``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.resilience import (CompileError, DeadlineExceededError,
                              FaultPlan, FaultSpec,
                              PoisonRequestError, RequestShedError,
                              RetryPolicy, TransientExecutorError, chaos)
from repro.serve.runtime import ContinuousBatchEngine, ContinuousConfig
from repro.sparse import SparseMatrix

BLOCK = (16, 16)
D = 8
FAST_RETRY = RetryPolicy(max_attempts=3, base_ms=0.1, max_ms=1.0)


@pytest.fixture(autouse=True)
def _clean_obs_and_chaos():
    obs.reset()
    chaos.uninstall()
    yield
    chaos.uninstall()


def _graph(rng, n: int, sparsity: float = 0.9):
    dense = np.where(rng.random((n, n)) < (1.0 - sparsity),
                     rng.normal(size=(n, n)), 0.0).astype(np.float32)
    if not dense.any():
        dense[0, 0] = 1.0
    return dense, SparseMatrix.from_dense(dense, formats=("ell", "csr"),
                                          block=BLOCK)


def _cfg(**kw) -> ContinuousConfig:
    kw.setdefault("slots", 4)
    kw.setdefault("adaptive", False)
    kw.setdefault("max_wait_ms", 0.0)
    kw.setdefault("retry", FAST_RETRY)
    return ContinuousConfig(**kw)


def _counter_total(snap, name: str) -> float:
    return sum(snap["metrics"]["counters"].get(name, {}).values())


# ---------------------------------------------------------------------------
# poison bisection (the _fail_lane collateral-damage regression)
# ---------------------------------------------------------------------------


def test_poison_bisection_quarantines_only_culprit(rng):
    """One poison request + three innocents in a full lane: only the
    tagged request fails; the innocents complete with correct results
    from the bisection probes."""
    plan = FaultPlan([FaultSpec(site="continuous.execute", kind="poison",
                                times=None, match={"tags": "bad"})])
    with chaos.active(plan), ContinuousBatchEngine(cfg=_cfg()) as eng:
        futs, refs = [], []
        for i in range(4):
            dense, mat = _graph(rng, 48)
            h = jnp.asarray(rng.normal(size=(48, D)).astype(np.float32))
            futs.append(eng.submit(mat, h, tag="bad" if i == 2 else None))
            refs.append(dense @ np.asarray(h))
        eng.drain()
        for i, (f, ref) in enumerate(zip(futs, refs)):
            if i == 2:
                with pytest.raises(PoisonRequestError):
                    f.result()
            else:
                np.testing.assert_allclose(f.result(), ref,
                                           rtol=2e-4, atol=2e-4)
        rep = eng.report()
        assert rep["resilience"]["quarantined"] == 1
        assert rep["failed"] == 1 and rep["completed"] == 4
    snap = obs.snapshot()
    assert _counter_total(snap, "resilience_quarantined_total") == 1
    assert _counter_total(snap, "chaos_faults_total") >= 1


def _refused_by_the_compiler(mat, h):
    """Traces, then fails to lower: a Pallas kernel without interpret
    mode has no CPU lowering — the CPU stand-in for a kernel the TPU
    compiler refuses."""
    from jax.experimental import pallas as pl

    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    return pl.pallas_call(copy, out_shape=jax.ShapeDtypeStruct(
        h.shape, h.dtype))(h)


def test_compile_failure_surfaces_not_quarantined_or_degraded(rng):
    """A program that traces but does not compile fails its requests
    with CompileError: no request is quarantined as poison, no retry
    runs, and the form is not degraded onto another path."""
    with ContinuousBatchEngine(_refused_by_the_compiler,
                               cfg=_cfg()) as eng:
        futs = []
        for _ in range(3):
            _, mat = _graph(rng, 48)
            h = jnp.asarray(rng.normal(size=(48, D)).astype(np.float32))
            futs.append(eng.submit(mat, h))
        eng.drain()
        for f in futs:
            with pytest.raises(CompileError, match="failed to compile"):
                f.result()
        rep = eng.report()
        assert rep["resilience"]["quarantined"] == 0
        assert "degraded" not in rep["executor"]
    snap = obs.snapshot()
    assert _counter_total(snap, "resilience_quarantined_total") == 0
    assert _counter_total(snap, "resilience_degraded_total") == 0
    assert _counter_total(snap, "resilience_retries_total") == 0


def test_transient_fault_retries_and_succeeds(rng):
    plan = FaultPlan([FaultSpec(site="continuous.execute", kind="raise",
                                at=1, times=1)])
    with chaos.active(plan), ContinuousBatchEngine(cfg=_cfg()) as eng:
        dense, mat = _graph(rng, 48)
        h = jnp.asarray(rng.normal(size=(48, D)).astype(np.float32))
        y = eng.infer(mat, h)
        np.testing.assert_allclose(y, dense @ np.asarray(h),
                                   rtol=2e-4, atol=2e-4)
        assert eng.report()["failed"] == 0
    assert _counter_total(obs.snapshot(), "resilience_retries_total") >= 1


def test_retries_exhausted_fails_structured(rng):
    """A request whose every execution fails transiently gets a
    structured retries-exhausted error, not a hang or a raw traceback
    from deep inside the executor."""
    plan = FaultPlan([FaultSpec(site="continuous.execute", kind="raise",
                                times=None, match={"tags": "cursed"})])
    # form is pinned so the persistent failure cannot trigger a lane
    # rebuild onto the other form (that path has its own test below)
    with chaos.active(plan), \
            ContinuousBatchEngine(cfg=_cfg(form="csr")) as eng:
        _, mat = _graph(rng, 48)
        h = jnp.asarray(rng.normal(size=(48, D)).astype(np.float32))
        fut = eng.submit(mat, h, tag="cursed")
        while not fut.done():
            eng.step(force=True)
        with pytest.raises(TransientExecutorError, match="retries exhausted"):
            fut.result()


def test_nan_output_quarantined(rng):
    from repro.resilience import NaNOutputError

    plan = FaultPlan([FaultSpec(site="continuous.output", kind="nan",
                                payload=(0, 0))])
    with chaos.active(plan), ContinuousBatchEngine(cfg=_cfg()) as eng:
        dense, mat = _graph(rng, 48)
        h = jnp.asarray(rng.normal(size=(48, D)).astype(np.float32))
        fut = eng.submit(mat, h)
        while not fut.done():
            eng.step(force=True)
        with pytest.raises(NaNOutputError):
            fut.result()
        # the engine keeps serving clean traffic afterwards
        y = eng.infer(mat, h)
        np.testing.assert_allclose(y, dense @ np.asarray(h),
                                   rtol=2e-4, atol=2e-4)
    snap = obs.snapshot()
    assert snap["metrics"]["counters"][
        "resilience_quarantined_total"].get("kind=nan") == 1


def test_latency_spike_is_survived(rng):
    plan = FaultPlan([FaultSpec(site="continuous.execute", kind="delay",
                                payload=0.02, times=2)])
    with chaos.active(plan), ContinuousBatchEngine(cfg=_cfg()) as eng:
        dense, mat = _graph(rng, 48)
        h = jnp.asarray(rng.normal(size=(48, D)).astype(np.float32))
        y = eng.infer(mat, h)
        np.testing.assert_allclose(y, dense @ np.asarray(h),
                                   rtol=2e-4, atol=2e-4)
    assert ("continuous.execute", "delay", 1) in plan.events


# ---------------------------------------------------------------------------
# worker supervision, deadlines, shedding
# ---------------------------------------------------------------------------


def test_continuous_worker_death_restarts(rng):
    plan = FaultPlan([FaultSpec(site="continuous.worker", kind="die",
                                at=1, times=1)])
    with chaos.active(plan), \
            ContinuousBatchEngine(cfg=_cfg(background=True,
                                           max_wait_ms=0.5)) as eng:
        import time
        time.sleep(0.05)  # let the first loop iteration die
        dense, mat = _graph(rng, 48)
        h = jnp.asarray(rng.normal(size=(48, D)).astype(np.float32))
        y = eng.infer(mat, h, timeout=30.0)
        np.testing.assert_allclose(y, dense @ np.asarray(h),
                                   rtol=2e-4, atol=2e-4)
        assert eng.report()["resilience"]["worker_restarts"] == 1
    assert _counter_total(obs.snapshot(),
                          "resilience_worker_restarts_total") == 1


def test_queued_deadline_expires(rng):
    with ContinuousBatchEngine(cfg=_cfg(slots=1)) as eng:
        dense, mat = _graph(rng, 48)
        h = jnp.asarray(rng.normal(size=(48, D)).astype(np.float32))
        seated = eng.submit(mat, h)                      # takes the slot
        doomed = eng.submit(mat, h, deadline_ms=0.0)     # queued, expired
        while not seated.done():
            eng.step(force=True)
        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=10)
        assert eng.report()["resilience"]["shed"] == 1
    assert obs.snapshot()["metrics"]["counters"][
        "resilience_shed_total"].get("reason=deadline") == 1


def test_queue_overflow_sheds_lowest_priority(rng):
    with ContinuousBatchEngine(cfg=_cfg(slots=1, queue_depth=1)) as eng:
        dense, mat = _graph(rng, 48)
        h = jnp.asarray(rng.normal(size=(48, D)).astype(np.float32))
        seated = eng.submit(mat, h, priority=1)
        queued = eng.submit(mat, h, priority=1)
        low = eng.submit(mat, h, priority=0)  # over capacity: shed (lowest)
        with pytest.raises(RequestShedError):
            low.result(timeout=10)
        eng.drain()
        for f in (seated, queued):
            np.testing.assert_allclose(f.result(), dense @ np.asarray(h),
                                       rtol=2e-4, atol=2e-4)
        assert eng.report()["resilience"]["shed"] == 1
    assert obs.snapshot()["metrics"]["counters"][
        "resilience_shed_total"].get("reason=queue_full") == 1


def test_degraded_form_rebuilds_lane_on_survivor(rng):
    """A form that keeps failing transiently is degraded; the lane
    rebuilds on the surviving form and the request still completes."""
    dense, mat = _graph(rng, 48)
    h = jnp.asarray(rng.normal(size=(48, D)).astype(np.float32))
    # learn which form the planner picks for this lane
    with ContinuousBatchEngine(cfg=_cfg()) as probe:
        probe.infer(mat, h)
        (lane_info,) = probe.report()["lanes"].values()
    doomed_form = lane_info["form"]
    other = {"ell": "csr", "csr": "ell"}[doomed_form]
    plan = FaultPlan([FaultSpec(site="continuous.execute", kind="raise",
                                times=None, match={"form": doomed_form})])
    with chaos.active(plan), ContinuousBatchEngine(cfg=_cfg()) as eng:
        y = eng.infer(mat, h)
        np.testing.assert_allclose(y, dense @ np.asarray(h),
                                   rtol=2e-4, atol=2e-4)
        rep = eng.report()
        (lane_info,) = rep["lanes"].values()
        assert lane_info["form"] == other
        assert any(d.endswith(doomed_form)
                   for d in rep["executor"]["degraded"])
    snap = obs.snapshot()
    assert _counter_total(snap, "resilience_degraded_total") == 1
    assert snap["metrics"]["counters"]["resilience_recoveries_total"].get(
        "site=lane_rebuild") == 1


# ---------------------------------------------------------------------------
# micro-batching engine (BatchServingEngine)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gcn_setup():
    from repro.configs.paper_gnn import SMOKE_CONFIG as GCFG
    from repro.data.pipeline import random_graph
    from repro.models.gnn import build_graph, init_gcn

    params = init_gcn(jax.random.PRNGKey(0), GCFG)
    graphs = [build_graph(random_graph(n, avg_degree=4, seed=n), GCFG)
              for n in (48, 80)]
    return GCFG, params, graphs


def test_batch_engine_worker_death_restarts(gcn_setup):
    from repro.serve.engine import BatchServeConfig, BatchServingEngine

    cfg, params, graphs = gcn_setup
    plan = FaultPlan([FaultSpec(site="serve.worker", kind="die",
                                at=1, times=1)])
    with chaos.active(plan), BatchServingEngine.for_gcn(
            params, scfg=BatchServeConfig(max_batch=4,
                                          max_delay_ms=1.0)) as eng:
        import time
        time.sleep(0.1)  # let the first loop iteration die
        x = jnp.zeros((graphs[0].n_nodes, cfg.in_features), jnp.float32)
        y = eng.infer(graphs[0], x)
        assert y.shape == (graphs[0].n_nodes, cfg.n_classes)
        assert eng.report()["resilience"]["worker_restarts"] == 1


def test_batch_engine_poison_bisection(gcn_setup):
    from repro.serve.engine import BatchServeConfig, BatchServingEngine

    cfg, params, graphs = gcn_setup
    plan = FaultPlan([FaultSpec(site="serve.flush", kind="poison",
                                times=None, match={"tags": "bad"})])
    scfg = BatchServeConfig(max_batch=4, max_delay_ms=200.0,
                            retry=FAST_RETRY)
    with chaos.active(plan), BatchServingEngine.for_gcn(
            params, scfg=scfg) as eng:
        g = graphs[0]
        x = jnp.zeros((g.n_nodes, cfg.in_features), jnp.float32)
        futs = [eng.submit(g, x, tag="bad" if i == 1 else None)
                for i in range(4)]
        eng.drain(timeout=60)
        for i, f in enumerate(futs):
            if i == 1:
                with pytest.raises(PoisonRequestError):
                    f.result()
            else:
                assert f.result().shape == (g.n_nodes, cfg.n_classes)
        assert eng.report()["resilience"]["quarantined"] == 1


def test_batch_engine_infer_timeout(gcn_setup):
    from repro.serve.engine import BatchServeConfig, BatchServingEngine

    cfg, params, graphs = gcn_setup
    # the worker dies immediately and the restart budget is zero: the
    # future can never resolve, so infer() must time out, not hang
    plan = FaultPlan([FaultSpec(site="serve.worker", kind="die",
                                times=None)])
    with chaos.active(plan), BatchServingEngine.for_gcn(
            params, scfg=BatchServeConfig(max_batch=2, max_delay_ms=1.0,
                                          max_worker_restarts=0)) as eng:
        import time
        time.sleep(0.05)
        x = jnp.zeros((graphs[0].n_nodes, cfg.in_features), jnp.float32)
        with pytest.raises(DeadlineExceededError):
            eng.infer(graphs[0], x, timeout=0.3)


# ---------------------------------------------------------------------------
# train-loop crash recovery
# ---------------------------------------------------------------------------


def _train_setup():
    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import DataConfig, lm_data_iter
    from repro.models.transformer import init_lm
    from repro.train.loop import (TrainConfig, init_train_state,
                                  make_train_step)
    from repro.train.optimizer import OptConfig

    cfg = dataclasses.replace(get_smoke_config("nemotron-4-15b"),
                              dtype="float32")
    tcfg = TrainConfig(opt=OptConfig(lr=5e-3, warmup_steps=0,
                                     total_steps=100))
    params = init_lm(jax.random.PRNGKey(0), cfg)
    state = init_train_state(params, tcfg)
    step = make_train_step(cfg, tcfg)
    it = lambda start: lm_data_iter(  # noqa: E731
        cfg, ShapeConfig("t", 32, 4, "train"), DataConfig(seed=9),
        start_step=start)
    return params, state, step, it


def test_train_crash_recovery_reconverges(tmp_path):
    """A chaos-killed step mid-epoch restores from the newest atomic
    checkpoint, replays the data stream, and lands on the same final
    params as the undisturbed run."""
    from repro.ft.checkpoint import Checkpointer
    from repro.train.loop import train_loop

    n_steps = 6
    params, state, step, it = _train_setup()
    base = train_loop(params, state, step, it(0), n_steps, log_every=1)
    assert base["recoveries"] == 0

    params, state, step2, it = _train_setup()
    ck = Checkpointer(str(tmp_path), keep=2, async_save=False)
    plan = FaultPlan([FaultSpec(site="train.step", kind="die", at=5)])
    with chaos.active(plan):
        out = train_loop(params, state, step2, it(0), n_steps, log_every=1,
                         checkpointer=ck, ckpt_every=2, data_factory=it,
                         max_recoveries=2)
    assert out["recoveries"] == 1
    assert ("train.step", "die", 5) in plan.events
    for a, b in zip(jax.tree_util.tree_leaves(base["params"]),
                    jax.tree_util.tree_leaves(out["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    assert base["history"][-1]["loss"] == pytest.approx(
        out["history"][-1]["loss"], rel=1e-6)
    assert _counter_total(obs.snapshot(),
                          "resilience_recoveries_total") >= 1


def test_train_crash_before_first_checkpoint_restarts_from_init():
    from repro.train.loop import train_loop
    from repro.ft.checkpoint import Checkpointer
    import tempfile

    n_steps = 3
    params, state, step, it = _train_setup()
    base = train_loop(params, state, step, it(0), n_steps, log_every=1)

    params, state, step2, it = _train_setup()
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, async_save=False)
        plan = FaultPlan([FaultSpec(site="train.step", kind="raise", at=2)])
        with chaos.active(plan):
            out = train_loop(params, state, step2, it(0), n_steps,
                             log_every=1, checkpointer=ck, ckpt_every=0,
                             data_factory=it, max_recoveries=1)
    assert out["recoveries"] == 1
    assert base["history"][-1]["loss"] == pytest.approx(
        out["history"][-1]["loss"], rel=1e-6)


# ---------------------------------------------------------------------------
# DeltaGraph background-repack crash safety
# ---------------------------------------------------------------------------


def test_repack_crash_leaves_old_overlay_serving(rng):
    from repro.serve.runtime import DeltaGraph

    dense = np.zeros((32, 32), np.float32)
    dense[rng.random((32, 32)) < 0.2] = 1.0
    g = DeltaGraph(dense, form="csr", slack=0.05)
    plan = FaultPlan([FaultSpec(site="delta.repack", kind="raise",
                                at=1, times=1)])
    with chaos.active(plan):
        # force the build to start (low free slots not required with a
        # high low_water) and crash inside it
        started = g.maybe_repack_async(low_water=1.0)
        assert started
        assert not g.poll_repack(timeout=10.0)  # crashed: nothing swapped
    assert g.report()["repack_failures"] == 1
    # the overlay never stopped serving, and a retry succeeds
    before = g.matrix.to_dense()
    assert g.maybe_repack_async(low_water=1.0)
    assert g.poll_repack(timeout=10.0)
    np.testing.assert_array_equal(np.asarray(before),
                                  np.asarray(g.matrix.to_dense()))
    assert obs.snapshot()["metrics"]["counters"][
        "resilience_recoveries_total"].get("site=delta.repack") == 1


# ---------------------------------------------------------------------------
# fault-storm soak
# ---------------------------------------------------------------------------


def test_fault_storm_strands_nothing(rng):
    """Deterministic storm: poison matched on two tags, a transient
    burst, and latency spikes.  Every future resolves; non-poison
    requests complete exactly once with correct results; the whole
    story is visible in obs.snapshot()."""
    plan = FaultPlan([
        FaultSpec(site="continuous.execute", kind="poison", times=None,
                  match={"tags": "p0"}),
        FaultSpec(site="continuous.execute", kind="poison", times=None,
                  match={"tags": "p1"}),
        FaultSpec(site="continuous.execute", kind="raise", at=4, times=2),
        FaultSpec(site="continuous.execute", kind="delay", payload=0.005,
                  at=8, times=3),
    ], seed=7)
    n_req, poison_at = 20, (3, 11)
    with chaos.active(plan), ContinuousBatchEngine(cfg=_cfg()) as eng:
        futs, refs, tags = [], [], []
        for i in range(n_req):
            n = 48 if i % 3 else 80
            dense, mat = _graph(rng, n)
            h = jnp.asarray(rng.normal(size=(n, D)).astype(np.float32))
            tag = f"p{poison_at.index(i)}" if i in poison_at else None
            futs.append(eng.submit(mat, h, tag=tag))
            refs.append(dense @ np.asarray(h))
            tags.append(tag)
        eng.drain(timeout=120)
        # zero stranded futures
        assert all(f.done() for f in futs)
        for f, ref, tag in zip(futs, refs, tags):
            if tag is None:
                np.testing.assert_allclose(f.result(), ref,
                                           rtol=2e-4, atol=2e-4)
            else:
                with pytest.raises(PoisonRequestError):
                    f.result()
        rep = eng.report()
        assert rep["completed"] == rep["submitted"] == n_req
        assert rep["pending"] == 0
        assert rep["failed"] == len(poison_at)
        assert rep["resilience"]["quarantined"] == len(poison_at)
    snap = obs.snapshot()
    assert set(snap) == {"metrics", "spans", "sentry", "audit"}
    counters = snap["metrics"]["counters"]
    assert _counter_total(snap, "chaos_faults_total") >= 4
    assert "resilience_quarantined_total" in counters
    # the storm's injected-fault ledger is replayable evidence
    assert len(plan.events) >= 4
    assert all(site.startswith("continuous.") for site, _, _ in plan.events)


# ---------------------------------------------------------------------------
# Supervisor restart race (concurrent ensure must charge one restart)
# ---------------------------------------------------------------------------


class TestSupervisorEnsureRace:
    def _dies_once_then_blocks(self):
        """A worker target that exits instantly on its first life and
        blocks forever afterwards (so the post-restart thread cannot
        die again and muddy the restart count)."""
        import threading as _t
        lives = {"n": 0}
        release = _t.Event()

        def target():
            lives["n"] += 1
            if lives["n"] > 1:
                release.wait()

        return target, release

    def test_concurrent_ensure_restarts_exactly_once(self):
        import threading as _t

        from repro.resilience.supervisor import WorkerSupervisor
        target, release = self._dies_once_then_blocks()
        sup = WorkerSupervisor("race", target, max_restarts=8)
        sup.start()
        sup.join(timeout=5.0)  # first life exits immediately
        assert not sup.alive()
        barrier = _t.Barrier(8)
        results = []

        def racer():
            barrier.wait()
            results.append(sup.ensure())

        threads = [_t.Thread(target=racer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10.0)
        try:
            assert all(results)
            # one death, eight observers, exactly one restart charged
            assert sup.restarts == 1
            assert sup.generation == 2
        finally:
            release.set()

    def test_ensure_with_stale_generation_is_noop(self):
        import threading as _t

        from repro.resilience.supervisor import WorkerSupervisor
        release = _t.Event()
        first = {"done": False}

        def target():
            if not first["done"]:
                first["done"] = True
                return
            release.wait()

        sup = WorkerSupervisor("stale", target, max_restarts=8)
        sup.start()
        sup.join(timeout=5.0)
        assert sup.ensure()  # handles the death: generation 1 -> 2
        assert sup.restarts == 1
        try:
            # an observer that saw generation 1 die reports late: the
            # death was already handled, so nothing is charged
            assert sup.ensure(observed_generation=1)
            assert sup.restarts == 1
            assert sup.generation == 2
        finally:
            release.set()


# ---------------------------------------------------------------------------
# Close/drain under failure (both engines; the fleet's variant lives in
# tests/test_fleet.py)
# ---------------------------------------------------------------------------


class TestCloseDrainUnderFailure:
    def test_continuous_double_close_and_submit_after_close(self, rng):
        from repro.resilience import EngineClosedError
        dense, mat = _graph(rng, 24)
        h = rng.standard_normal((24, D)).astype(np.float32)
        eng = ContinuousBatchEngine(cfg=_cfg())
        fut = eng.submit(mat, h)
        eng.close()
        eng.close()  # idempotent
        assert fut.done() and fut.exception() is None
        with pytest.raises(EngineClosedError):
            eng.submit(mat, h)

    def test_continuous_concurrent_close_resolves_everything(self, rng):
        import threading as _t

        from repro.resilience import EngineClosedError
        _, mat = _graph(rng, 24)
        h = rng.standard_normal((24, D)).astype(np.float32)
        eng = ContinuousBatchEngine(cfg=_cfg(background=True))
        futs = [eng.submit(mat, h) for _ in range(4)]
        closers = [_t.Thread(target=eng.close) for _ in range(3)]
        for t in closers:
            t.start()
        # keep submitting while close races; rejected submissions raise
        for _ in range(8):
            try:
                futs.append(eng.submit(mat, h))
            except EngineClosedError:
                break
        for t in closers:
            t.join(timeout=30.0)
        for f in futs:
            assert f.done()  # a result or EngineClosedError, never a hang

    def test_continuous_close_while_worker_dying(self, rng):
        _, mat = _graph(rng, 24)
        h = rng.standard_normal((24, D)).astype(np.float32)
        eng = ContinuousBatchEngine(cfg=_cfg(background=True))
        with chaos.active(FaultPlan([
                FaultSpec(site="continuous.worker", kind="die",
                          at=1, times=None)], seed=0)):
            futs = [eng.submit(mat, h) for _ in range(4)]
            eng.close()
        for f in futs:
            assert f.done()

    def test_batch_double_close_and_submit_after_close(self, gcn_setup):
        from repro.resilience import EngineClosedError
        from repro.serve.engine import BatchServeConfig, BatchServingEngine
        cfg, params, graphs = gcn_setup
        eng = BatchServingEngine.for_gcn(
            params, scfg=BatchServeConfig(max_batch=4, max_delay_ms=1.0))
        x = jnp.zeros((graphs[0].n_nodes, cfg.in_features), jnp.float32)
        fut = eng.submit(graphs[0], x)
        eng.close()
        eng.close()  # idempotent
        assert fut.done() and fut.exception() is None
        with pytest.raises(EngineClosedError):
            eng.submit(graphs[0], x)

    def test_batch_concurrent_close_resolves_everything(self, gcn_setup):
        import threading as _t

        from repro.serve.engine import BatchServeConfig, BatchServingEngine
        cfg, params, graphs = gcn_setup
        eng = BatchServingEngine.for_gcn(
            params, scfg=BatchServeConfig(max_batch=4, max_delay_ms=1.0))
        futs = [eng.submit(g, jnp.zeros((g.n_nodes, cfg.in_features),
                                        jnp.float32))
                for g in graphs for _ in range(2)]
        closers = [_t.Thread(target=eng.close) for _ in range(3)]
        for t in closers:
            t.start()
        for t in closers:
            t.join(timeout=30.0)
        for f in futs:
            assert f.done()
