"""Fleet serving: routing, journal at-most-once, supervision, chaos.

The deterministic acceptance storm lives here:
``test_fault_storm_kill_and_heartbeat_delay`` kills 1 of 3 workers
mid-batch while delaying heartbeats and requires zero stranded
requests, bit-identical outputs vs the fault-free run, exactly-once
completion of the dead worker's in-flight, and a clean RetraceSentry.
"""
import dataclasses
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.resilience import chaos
from repro.resilience.chaos import FaultPlan, FaultSpec
from repro.resilience.errors import EngineClosedError, WorkerLostError
from repro.serve.fleet import (AutoscaleConfig, Autoscaler, FleetConfig,
                               ServingFleet)
from repro.serve.fleet.router import Router
from repro.serve.fleet.rpc import encode_request, lane_key


@pytest.fixture(autouse=True)
def _clean_obs_and_chaos():
    obs.reset()
    chaos.uninstall()
    yield
    chaos.uninstall()
    obs.reset()


def _graph(rng, n, d=4):
    dense = (rng.random((n, n)) < 0.15).astype(np.float32)
    h = rng.standard_normal((n, d)).astype(np.float32)
    return dense, h


def _counter_total(snap, name):
    return sum(snap["metrics"]["counters"].get(name, {}).values())


# ---------------------------------------------------------------------------
# Router unit tests (no engines, no workers)
# ---------------------------------------------------------------------------


class _FakeCarrier:
    def __init__(self, workers=("a", "b", "c")):
        self.live_workers = list(workers)
        self.sent = []
        self.fail_sends_to = set()

    def send(self, worker, msg):
        if worker in self.fail_sends_to:
            return False
        self.sent.append((worker, msg))
        return True

    def live(self):
        return list(self.live_workers)


def _router(carrier, **kw):
    return Router(send=carrier.send, live=carrier.live,
                  lock=threading.RLock(), **kw)


class TestRouter:
    def _payload(self, rng, n=16, d=4):
        dense, h = _graph(rng, n, d)
        return encode_request(dense, h)

    def test_lane_sticky_round_robin(self, rng):
        carrier = _FakeCarrier()
        router = _router(carrier)
        p16 = self._payload(rng, 16)
        p32 = self._payload(rng, 32)
        e1 = router.admit(p16)
        router.dispatch(e1)
        e2 = router.admit(p32)
        router.dispatch(e2)
        assert e1.worker == "a" and e2.worker == "b"  # round-robin
        e3 = router.admit(self._payload(rng, 16))
        router.dispatch(e3)
        assert e3.worker == "a"  # sticky: same lane, same owner

    def test_journal_completes_exactly_once(self, rng):
        carrier = _FakeCarrier()
        router = _router(carrier)
        entry = router.admit(self._payload(rng))
        router.dispatch(entry)
        out = np.ones((16, 4), np.float32)
        first = router.complete(entry.rid, True, out, src=entry.worker)
        assert first is not None
        dup = router.complete(entry.rid, True, out * 2, src="b")
        assert dup is None
        assert np.array_equal(entry.future.result(0), out)
        snap = obs.snapshot()
        assert _counter_total(snap, "fleet_duplicate_results_total") == 1

    def test_failover_reroutes_orphans(self, rng):
        carrier = _FakeCarrier()
        router = _router(carrier)
        entries = [router.admit(self._payload(rng, 16)) for _ in range(3)]
        for e in entries:
            router.dispatch(e)
        owner = entries[0].worker
        assert all(e.worker == owner for e in entries)
        carrier.live_workers.remove(owner)
        orphans = router.orphans_of(owner)
        assert {o.rid for o in orphans} == {e.rid for e in entries}
        for o in orphans:
            assert router.dispatch(o, exclude=(owner,))
        assert all(e.worker != owner for e in entries)

    def test_unrouted_parks_without_workers(self, rng):
        carrier = _FakeCarrier(workers=())
        router = _router(carrier)
        entry = router.admit(self._payload(rng))
        assert not router.dispatch(entry)
        assert len(router.unrouted) == 1
        carrier.live_workers = ["a"]
        parked = router.take_unrouted()
        assert [e.rid for e in parked] == [entry.rid]
        assert router.dispatch(parked[0])
        assert entry.worker == "a"

    def test_hedge_first_wins_cancels_loser(self, rng):
        carrier = _FakeCarrier(workers=("a", "b"))
        router = _router(carrier)
        entry = router.admit(self._payload(rng))
        router.dispatch(entry)
        assert router.hedge(entry)
        assert entry.hedge_worker == "b"
        assert not router.hedge(entry)  # at most one hedge
        out = np.zeros((16, 4), np.float32)
        got = router.complete(entry.rid, True, out, src="b")
        assert got is not None
        _, loser = got
        assert loser == "a"  # the fleet sends ("cancel", rid) there

    def test_dead_send_falls_through_to_next_worker(self, rng):
        carrier = _FakeCarrier(workers=("a", "b"))
        carrier.fail_sends_to.add("a")
        router = _router(carrier)
        entry = router.admit(self._payload(rng))
        assert router.dispatch(entry)
        assert entry.worker == "b"

    def test_journal_gc_bounds_done_entries(self, rng):
        carrier = _FakeCarrier(workers=("a",))
        router = _router(carrier, keep_done=4)
        p = self._payload(rng)
        for _ in range(10):
            e = router.admit(p)
            router.dispatch(e)
            router.complete(e.rid, True, np.zeros(1), src="a")
        done = [e for e in router.journal.values() if e.done]
        assert len(done) <= 4


# ---------------------------------------------------------------------------
# Autoscaler decision logic (injected clock)
# ---------------------------------------------------------------------------


class TestAutoscaler:
    def _scaler(self, **kw):
        base = dict(enabled=True, min_workers=1, max_workers=3,
                    up_pending_per_worker=4.0,
                    down_pending_per_worker=0.5,
                    idle_grace_s=1.0, cooldown_s=2.0)
        base.update(kw)
        return Autoscaler(AutoscaleConfig(**base))

    def test_scale_up_on_backlog(self):
        s = self._scaler()
        assert s.decide(0.0, pending=20, live_workers=2) == "up"

    def test_cooldown_blocks_consecutive_actions(self):
        s = self._scaler()
        assert s.decide(0.0, pending=20, live_workers=1) == "up"
        assert s.decide(1.0, pending=20, live_workers=2) is None
        assert s.decide(2.5, pending=20, live_workers=2) == "up"

    def test_max_workers_caps_up(self):
        s = self._scaler()
        assert s.decide(0.0, pending=100, live_workers=3) is None

    def test_scale_down_needs_idle_grace(self):
        s = self._scaler()
        assert s.decide(0.0, pending=0, live_workers=2) is None
        assert s.decide(0.5, pending=0, live_workers=2) is None
        assert s.decide(1.5, pending=0, live_workers=2) == "down"

    def test_burst_resets_idle_grace(self):
        s = self._scaler()
        assert s.decide(0.0, pending=0, live_workers=2) is None
        assert s.decide(0.6, pending=3, live_workers=2) is None  # busy again
        assert s.decide(1.4, pending=0, live_workers=2) is None  # regrace
        assert s.decide(2.6, pending=0, live_workers=2) == "down"

    def test_min_workers_floors_down(self):
        s = self._scaler()
        assert s.decide(0.0, pending=0, live_workers=1) is None
        assert s.decide(5.0, pending=0, live_workers=1) is None

    def test_p99_trigger(self):
        s = self._scaler(up_p99_ms=100.0)
        assert s.decide(0.0, pending=1, live_workers=2,
                        p99_ms=250.0) == "up"


# ---------------------------------------------------------------------------
# Config-default hygiene (satellite: mutable dataclass defaults)
# ---------------------------------------------------------------------------


class TestConfigDefaults:
    def test_health_detectors_get_private_configs(self):
        from repro.ft.health import Heartbeat, StragglerDetector
        d1, d2 = StragglerDetector(), StragglerDetector()
        assert d1.cfg is not d2.cfg
        d1.cfg.straggler_ratio = 99.0
        assert d2.cfg.straggler_ratio != 99.0
        h1, h2 = Heartbeat(), Heartbeat()
        assert h1.cfg is not h2.cfg

    def test_no_shared_mutable_dataclass_defaults(self):
        """Audit: a dataclass field whose default is a dataclass
        *instance* shares that instance across every config built with
        the default — only safe when the instance is frozen."""
        import repro.ft.health
        import repro.resilience.chaos
        import repro.resilience.retry
        import repro.serve.engine
        import repro.serve.fleet.autoscale
        import repro.serve.fleet.fleet
        import repro.serve.fleet.worker
        import repro.serve.runtime.continuous
        import repro.serve.runtime.ladder
        mods = [repro.serve.engine, repro.serve.runtime.continuous,
                repro.serve.runtime.ladder, repro.resilience.retry,
                repro.resilience.chaos, repro.ft.health,
                repro.serve.fleet.fleet, repro.serve.fleet.worker,
                repro.serve.fleet.autoscale]
        offenders = []
        for mod in mods:
            for obj in vars(mod).values():
                if not (isinstance(obj, type)
                        and dataclasses.is_dataclass(obj)
                        and obj.__module__ == mod.__name__):
                    continue
                for f in dataclasses.fields(obj):
                    default = f.default
                    if default is dataclasses.MISSING or default is None:
                        continue
                    if dataclasses.is_dataclass(default) \
                            and not isinstance(default, type) \
                            and not type(default).__dataclass_params__.frozen:
                        offenders.append(
                            f"{obj.__qualname__}.{f.name} shares a "
                            f"mutable {type(default).__name__} instance")
        assert not offenders, offenders


# ---------------------------------------------------------------------------
# Fleet integration (thread backend — deterministic, tier-1)
# ---------------------------------------------------------------------------


def _fleet(**kw):
    base = dict(backend="thread", workers=2, hedge_after_ms=10_000.0)
    base.update(kw)
    return ServingFleet(FleetConfig(**base))


class TestFleetServing:
    def test_serves_correct_results_and_reports(self, rng):
        fleet = _fleet(workers=2)
        try:
            assert fleet.wait_live(2, timeout=60)
            reqs = [_graph(rng, 16 + 8 * (i % 2)) for i in range(8)]
            futs = [fleet.submit(d, h) for d, h in reqs]
            outs = [f.result(timeout=60) for f in futs]
            for (dense, h), out in zip(reqs, outs):
                np.testing.assert_allclose(out, dense @ h,
                                           rtol=1e-4, atol=1e-4)
            rep = fleet.report()
            assert rep["completed"] == 8 and rep["failed"] == 0
            for key in ("p50_ms", "p99_ms", "waste", "workers", "fleet"):
                assert key in rep
            assert rep["fleet"]["requests_lost"] == 0
            served = sum(w["served"] for w in rep["workers"].values())
            assert served == 8
        finally:
            fleet.close()

    def test_fault_storm_kill_and_heartbeat_delay(self, rng):
        """Acceptance: kill 1 of 3 workers mid-batch + delay heartbeats
        → zero strands, outputs bit-identical to the fault-free run,
        the dead worker's in-flight completes on survivors exactly
        once, and no unexpected retraces."""
        reqs = [_graph(np.random.default_rng(100 + i), 16 + 8 * (i % 2))
                for i in range(24)]

        def run(plan):
            obs.reset()
            fleet = _fleet(workers=3, max_restarts_per_worker=2)
            try:
                assert fleet.wait_live(3, timeout=60)
                if plan is not None:
                    chaos.install(plan)
                futs = [fleet.submit(d, h) for d, h in reqs]
                outs = [f.result(timeout=120) for f in futs]
                rep = fleet.report()
            finally:
                chaos.uninstall()
                fleet.close()
            return outs, rep, obs.snapshot()

        base_outs, base_rep, _ = run(None)
        assert base_rep["completed"] == len(reqs)

        plan = FaultPlan([
            FaultSpec(site="fleet.worker", kind="kill_proc", at=3,
                      match={"worker": "w2", "phase": "dispatch"}),
            FaultSpec(site="fleet.heartbeat", kind="delay",
                      payload=0.04, at=4, times=3),
        ], seed=7)
        outs, rep, snap = run(plan)

        assert any(k == "kill_proc" for _, k, _ in plan.events)
        # zero strands: every future resolved with a result
        assert rep["completed"] == len(reqs)
        assert rep["failed"] == 0
        assert rep["fleet"]["requests_lost"] == 0
        # innocents AND the victim's re-routed in-flight: bit-identical
        for a, b in zip(base_outs, outs):
            assert np.array_equal(a, b)
        # the dead worker's in-flight moved to survivors (exactly once
        # is the journal's invariant — completed == submitted above)
        assert _counter_total(snap, "fleet_failovers_total") >= 1
        assert _counter_total(snap, "fleet_worker_deaths_total") >= 1
        # post-failover the executor cache is coherent: no unexpected
        # retraces anywhere in the fleet
        assert snap["sentry"]["unexpected_retraces"] == 0

    def test_hang_triggers_missed_heartbeat_restart(self, rng):
        from repro.ft.health import HealthConfig
        fleet = _fleet(workers=2,
                       health=HealthConfig(heartbeat_timeout_s=0.2),
                       max_restarts_per_worker=2)
        try:
            assert fleet.wait_live(2, timeout=60)
            # one request to warm a lane (owned by w1)
            dense, h = _graph(rng, 16)
            fleet.infer(dense, h, timeout=60)
            chaos.install(FaultPlan([
                FaultSpec(site="fleet.worker", kind="hang", payload=30.0,
                          at=1, match={"worker": "w1",
                                       "phase": "monitor"}),
            ], seed=3))
            futs = [fleet.submit(*_graph(rng, 16)) for _ in range(6)]
            outs = [f.result(timeout=120) for f in futs]
            assert len(outs) == 6
            # the hang command is queued behind the requests, so w1 may
            # serve all six before it stops beating — the death is
            # guaranteed (the hang outlives the heartbeat timeout) but
            # asynchronous; poll for it
            deaths = {}
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                deaths = obs.snapshot()["metrics"]["counters"].get(
                    "fleet_worker_deaths_total", {})
                if deaths:
                    break
                time.sleep(0.02)
            assert any("heartbeat" in k or "killed" in k for k in deaths)
            assert fleet.report()["fleet"]["requests_lost"] == 0
        finally:
            chaos.uninstall()
            fleet.close()

    def test_blackholed_request_is_hedged(self, rng):
        fleet = _fleet(workers=2, hedge_after_ms=50.0)
        try:
            assert fleet.wait_live(2, timeout=60)
            dense, h = _graph(rng, 16)
            fleet.infer(dense, h, timeout=60)  # lane now owned by w1
            # blackhole the next request send to w1: claimed delivered,
            # never arrives — only hedging can complete it
            chaos.install(FaultPlan([
                FaultSpec(site="fleet.rpc", kind="hang", at=1,
                          match={"worker": "w1", "phase": "send"}),
            ], seed=5))
            out = fleet.infer(dense, h, timeout=60)
            np.testing.assert_allclose(out, dense @ h,
                                       rtol=1e-4, atol=1e-4)
            snap = obs.snapshot()
            assert _counter_total(snap, "fleet_hedges_total") >= 1
        finally:
            chaos.uninstall()
            fleet.close()

    def test_autoscale_up_then_down(self, rng):
        fleet = _fleet(
            workers=1,
            autoscale=AutoscaleConfig(
                enabled=True, min_workers=1, max_workers=2,
                up_pending_per_worker=2.0, down_pending_per_worker=0.5,
                idle_grace_s=0.1, cooldown_s=0.2))
        try:
            assert fleet.wait_live(1, timeout=60)
            futs = [fleet.submit(*_graph(rng, 16)) for _ in range(12)]
            deadline = time.monotonic() + 60
            while len(fleet.sup.live()) < 2:
                assert time.monotonic() < deadline, "no scale-up"
                time.sleep(0.01)
            for f in futs:
                f.result(timeout=120)
            deadline = time.monotonic() + 60
            while len(fleet.sup.live()) > 1:
                assert time.monotonic() < deadline, "no scale-down"
                time.sleep(0.01)
            snap = obs.snapshot()
            assert _counter_total(snap, "fleet_scale_ups_total") >= 1
            assert _counter_total(snap, "fleet_scale_downs_total") >= 1
            assert fleet.report()["fleet"]["requests_lost"] == 0
        finally:
            fleet.close()

    def test_rolling_restart_keeps_serving(self, rng):
        fleet = _fleet(workers=2)
        try:
            assert fleet.wait_live(2, timeout=60)
            reqs = [_graph(rng, 16) for _ in range(4)]
            for d, h in reqs:
                fleet.infer(d, h, timeout=60)
            old = {ws.name for ws in fleet.sup.states()}
            fleet.rolling_restart()
            assert fleet.wait_live(2, timeout=60)
            live = set(fleet.sup.live())
            assert live and live.isdisjoint(old)
            out = fleet.infer(*reqs[0], timeout=60)
            np.testing.assert_allclose(out, reqs[0][0] @ reqs[0][1],
                                       rtol=1e-4, atol=1e-4)
            assert fleet.report()["fleet"]["requests_lost"] == 0
        finally:
            fleet.close()

    def test_restart_budget_exhausted_fails_with_worker_lost(self, rng):
        fleet = _fleet(workers=1, max_restarts_per_worker=0)
        try:
            assert fleet.wait_live(1, timeout=60)
            chaos.install(FaultPlan([
                FaultSpec(site="fleet.worker", kind="kill_proc", at=1,
                          match={"worker": "w1", "phase": "dispatch"}),
            ], seed=1))
            fut = fleet.submit(*_graph(rng, 16))
            with pytest.raises(WorkerLostError):
                fut.result(timeout=30)
            snap = obs.snapshot()
            assert _counter_total(snap, "fleet_requests_lost_total") == 1
        finally:
            chaos.uninstall()
            fleet.close()


class TestFleetCloseDrain:
    def test_double_close_and_submit_after_close(self, rng):
        fleet = _fleet(workers=1)
        assert fleet.wait_live(1, timeout=60)
        dense, h = _graph(rng, 16)
        fut = fleet.submit(dense, h)
        fleet.close()
        fleet.close()  # idempotent
        assert fut.done() and fut.exception() is None
        with pytest.raises(EngineClosedError):
            fleet.submit(dense, h)

    def test_close_while_worker_mid_kill(self, rng):
        """close() racing a chaos kill: every future still resolves —
        with a result (failover) or a taxonomy error, never a hang."""
        fleet = _fleet(workers=2, max_restarts_per_worker=1)
        try:
            assert fleet.wait_live(2, timeout=60)
            chaos.install(FaultPlan([
                FaultSpec(site="fleet.worker", kind="kill_proc", at=2,
                          match={"phase": "dispatch"}),
            ], seed=11))
            futs = [fleet.submit(*_graph(rng, 16)) for _ in range(6)]
        finally:
            fleet.close(timeout=60)
            chaos.uninstall()
        for f in futs:
            assert f.done()
            exc = f.exception()
            assert exc is None or isinstance(
                exc, (EngineClosedError, WorkerLostError))

    def test_concurrent_close_races(self, rng):
        fleet = _fleet(workers=1)
        assert fleet.wait_live(1, timeout=60)
        fut = fleet.submit(*_graph(rng, 16))
        threads = [threading.Thread(target=fleet.close) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert fut.done()


# ---------------------------------------------------------------------------
# Process backend: real SIGKILL surface
# ---------------------------------------------------------------------------


class TestProcessBackend:
    def test_process_worker_serves(self, rng):
        fleet = _fleet(backend="process", workers=1)
        try:
            assert fleet.wait_live(1, timeout=120)
            dense, h = _graph(rng, 16)
            out = fleet.infer(dense, h, timeout=120)
            np.testing.assert_allclose(out, dense @ h,
                                       rtol=1e-4, atol=1e-4)
            assert fleet.report()["fleet"]["requests_lost"] == 0
        finally:
            fleet.close()

    @pytest.mark.slow
    def test_process_worker_sigkill_failover(self, rng):
        fleet = _fleet(backend="process", workers=2,
                       max_restarts_per_worker=1)
        try:
            assert fleet.wait_live(2, timeout=180)
            reqs = [_graph(rng, 16) for _ in range(6)]
            # warm both lanes, then SIGKILL whichever worker owns the
            # next dispatch and require completion on the survivor
            fleet.infer(*reqs[0], timeout=120)
            chaos.install(FaultPlan([
                FaultSpec(site="fleet.worker", kind="kill_proc", at=2,
                          match={"phase": "dispatch"}),
            ], seed=2))
            futs = [fleet.submit(d, h) for d, h in reqs]
            outs = [f.result(timeout=180) for f in futs]
            assert len(outs) == len(reqs)
            snap = obs.snapshot()
            assert _counter_total(snap, "fleet_kills_total") >= 1
            assert fleet.report()["fleet"]["requests_lost"] == 0
        finally:
            chaos.uninstall()
            fleet.close()


def test_process_backend_refuses_to_start_on_tpu(monkeypatch):
    """Only one process may hold a TPU, and the parent already does: the
    process backend refuses with a clear error instead of spawning a
    worker that would fail or hang reaching the chip."""
    import jax

    from repro.serve.fleet.rpc import ProcessHandle

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="backend='thread'"):
        ProcessHandle("w0", object())
