"""Engine supervision (engine/supervisor.py): quarantine-and-rebuild
for wedged engines, restart budgets, degraded-mode serving — plus the
robustness satellites that ride with it (wedge fault injection,
EVAM_FAULT_SEED reproducibility, capped/jittered stream reconnect
backoff, shutdown-drain leak accounting)."""

from __future__ import annotations

import asyncio
import json
import random
import time
from pathlib import Path

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from evam_tpu.config import Settings
from evam_tpu.engine import EngineHub, SupervisedEngine
from evam_tpu.engine.batcher import BatchEngine
from evam_tpu.models import ModelRegistry, ZOO_SPECS
from evam_tpu.obs import faults
from evam_tpu.obs.metrics import metrics
from evam_tpu.parallel import build_mesh
from evam_tpu.server.app import build_app
from evam_tpu.server.instance import _retry_delay
from evam_tpu.server.registry import PipelineRegistry

REPO = Path(__file__).resolve().parent.parent
SMALL = {k: (64, 64) for k in ZOO_SPECS}
SMALL["audio_detection/environment"] = (1, 1600)
NARROW = {k: 8 for k in ZOO_SPECS}


def _wedge_env(monkeypatch, spec: str, seed: int = 0) -> None:
    monkeypatch.setenv("EVAM_FAULT_INJECT", spec)
    monkeypatch.setenv("EVAM_FAULT_SEED", str(seed))
    faults.reset_cache()


def _toy_factory(name: str, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("deadline_ms", 1.0)
    kw.setdefault("stall_timeout_s", 0.5)

    def factory() -> BatchEngine:
        return BatchEngine(
            name, lambda p, x: x.astype(np.float32), params=None,
            input_names=("x",), **kw)

    return factory


def _wait_for(cond, timeout=20.0, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


class TestSupervisedEngine:
    def test_wedge_quarantine_rebuild_readmission(self, monkeypatch):
        """Acceptance path 1 at the engine level: an injected wedge
        strands the in-flight future (TimeoutError from the watchdog),
        the supervisor quarantines + rebuilds within budget, and a
        subsequent submit on the SAME handle succeeds."""
        sup = SupervisedEngine(
            "sup-rebuild", _toy_factory("sup-rebuild"),
            max_restarts=3, restart_window_s=60.0, backoff_s=0.05)
        try:
            first = sup._engine
            # warm the bucket first: the wedge must hit the PLAIN
            # watchdog budget, not the first-batch compile grace
            sup.submit(x=np.zeros((3,), np.float32)).result(timeout=30)
            _wedge_env(monkeypatch, "wedge=1,wedge_n=1,wedge_s=4")
            fut = sup.submit(x=np.full((3,), 7.0, np.float32))
            with pytest.raises(TimeoutError):
                fut.result(timeout=15)
            _wait_for(lambda: sup.state == "running" and sup.restarts == 1,
                      msg="rebuild + re-admission")
            assert sup._engine is not first  # fresh engine, same handle
            assert sup.last_stall_ts is not None
            out = sup.submit(
                x=np.full((3,), 5.0, np.float32)).result(timeout=30)
            np.testing.assert_allclose(out, 5.0)
            assert metrics.get_counter(
                "evam_engine_restarts",
                labels={"engine": "sup-rebuild"}) == 1
        finally:
            sup.stop()

    def test_budget_exhaustion_is_terminal_degraded(self, monkeypatch):
        """Acceptance path 2: every generation wedges; after
        max_restarts rebuilds inside the window the supervisor stops
        flapping — terminal degraded, submit fails loudly, and
        evam_engine_restarts reflects exactly the budget."""
        sup = SupervisedEngine(
            "sup-budget", _toy_factory("sup-budget"),
            max_restarts=2, restart_window_s=60.0, backoff_s=0.05)
        try:
            sup.submit(x=np.zeros((3,), np.float32)).result(timeout=30)
            _wedge_env(monkeypatch, "wedge=1,wedge_s=2")
            # one item at a time (see the hub-level twin below)
            deadline = time.time() + 60
            while sup.state != "degraded" and time.time() < deadline:
                try:
                    sup.submit(
                        x=np.zeros((3,), np.float32)).result(timeout=15)
                except (TimeoutError, RuntimeError):
                    time.sleep(0.05)
            assert sup.state == "degraded"
            assert sup.restarts == 2
            assert metrics.get_counter(
                "evam_engine_restarts",
                labels={"engine": "sup-budget"}) == 2
            assert metrics.get_gauge(
                "evam_engine_state", labels={"engine": "sup-budget"}) == 2.0
            with pytest.raises(RuntimeError, match="degraded"):
                sup.submit(x=np.zeros((3,), np.float32))
        finally:
            sup.stop()

    def test_cumulative_counters_survive_rebuild(self, monkeypatch):
        """hub.py shed_totals note: a rebuild swaps in a fresh engine
        with zeroed local counters — the supervised handle must fold
        the quarantined engine's cumulative counts into a carry so
        /healthz, /engines and the bench line stay MONOTONIC."""
        sup = SupervisedEngine(
            "sup-carry", _toy_factory("sup-carry"),
            max_restarts=3, restart_window_s=60.0, backoff_s=0.05)
        try:
            for v in range(3):
                sup.submit(
                    x=np.full((2,), float(v), np.float32)).result(timeout=30)
            pre = sup.stats
            assert pre.batches >= 1 and pre.items == 3
            pre_batches, pre_items = pre.batches, pre.items
            pre_launch = pre.stage_seconds.get("launch", 0.0)
            # simulate sheds on the live engine, then wedge it
            sup._engine.shed_counts = lambda: {"batch": 5}
            _wedge_env(monkeypatch, "wedge=1,wedge_n=1,wedge_s=4")
            fut = sup.submit(x=np.zeros((2,), np.float32))
            with pytest.raises(TimeoutError):
                fut.result(timeout=15)
            _wait_for(lambda: sup.state == "running" and sup.restarts == 1,
                      msg="rebuild + re-admission")
            # fresh engine: local counters are zeroed...
            assert sup._engine.stats.items == 0
            # ...but the handle's view carried everything across
            assert sup.shed_counts() == {
                "realtime": 0, "standard": 0, "batch": 5}
            assert sup.stats.batches >= pre_batches
            assert sup.stats.items >= pre_items
            assert sup.stats.stage_seconds.get("launch", 0.0) >= pre_launch
            # and keeps counting monotonically on the new engine (the
            # wedged item was failed by the watchdog, never recorded)
            sup.submit(x=np.zeros((2,), np.float32)).result(timeout=30)
            assert sup.stats.items == pre_items + 1
            assert sup.stats.mean_occupancy > 0
        finally:
            sup.stop()

    def test_dispatcher_death_triggers_rebuild(self):
        """The second wedge signal: a dispatcher thread that DIES
        (not blocks) is detected by liveness, not the stalled flag.
        The death is deterministic wherever the dispatcher is parked:
        the loop runs the shedder's sweep at the top of every
        iteration (at the latest 50 ms after the last, the pick's
        time-out), and the sweep is what raises."""
        sup = SupervisedEngine(
            "sup-dispdeath", _toy_factory("sup-dispdeath"),
            max_restarts=3, restart_window_s=60.0, backoff_s=0.05)
        try:
            eng = sup._engine
            out = sup.submit(
                x=np.full((2,), 3.0, np.float32)).result(timeout=30)
            np.testing.assert_allclose(out, 3.0)

            def boom(*a, **k):
                raise RuntimeError("injected dispatcher death")

            eng._shedder.sweep = boom
            _wait_for(lambda: not eng._dispatcher.is_alive(),
                      msg="dispatcher death")
            assert not eng.stalled.is_set()
            _wait_for(lambda: sup.state == "running" and sup.restarts == 1,
                      msg="rebuild after dispatcher death")
            assert sup._engine is not eng
            out = sup.submit(
                x=np.full((2,), 9.0, np.float32)).result(timeout=30)
            np.testing.assert_allclose(out, 9.0)
        finally:
            sup.stop()

    def test_launcher_death_triggers_rebuild(self):
        """Every engine has a launcher thread, and its death is a
        wedge signal of its own: the batch it died on never reaches
        the device, so no stall deadline exists for the watchdog to
        miss. The supervisor's teardown fails what sat in the upload
        queue behind it."""
        sup = SupervisedEngine(
            "sup-launchdeath", _toy_factory("sup-launchdeath"),
            max_restarts=3, restart_window_s=60.0, backoff_s=0.05)
        try:
            eng = sup._engine
            sup.submit(
                x=np.full((2,), 3.0, np.float32)).result(timeout=30)

            def boom(*a, **k):
                raise RuntimeError("injected launcher death")

            # what the launcher's own except-clause does not guard
            eng._track_dispatch = boom
            sup.submit(x=np.full((2,), 4.0, np.float32))
            _wait_for(lambda: not eng._launcher.is_alive(),
                      msg="launcher death")
            assert eng._dispatcher.is_alive()
            _wait_for(lambda: sup.state == "running" and sup.restarts == 1,
                      msg="rebuild after launcher death")
            assert sup._engine is not eng
            out = sup.submit(
                x=np.full((2,), 9.0, np.float32)).result(timeout=30)
            np.testing.assert_allclose(out, 9.0)
        finally:
            sup.stop()


@pytest.fixture(scope="module")
def sup_registry(eight_devices):
    settings = Settings(pipelines_dir=str(REPO / "pipelines"))
    model_registry = ModelRegistry(dtype="float32", input_overrides=SMALL,
                                   width_overrides=NARROW)
    # stall 1.0s: tight enough that an injected wedge trips fast, and
    # the first-batch grace (10×) still covers the CPU jit compile a
    # cold engine (or a rebuilt one) pays on its first batch
    # first_batch_grace 5×: generous enough for the CPU jit compile a
    # cold (or rebuilt) engine pays on its first batch, small enough
    # that the budget-exhaustion test's queued-wedge detection stays
    # inside its deadline
    hub = EngineHub(
        model_registry, plan=build_mesh(), max_batch=16, deadline_ms=4.0,
        wire_format="bgr", stall_timeout_s=1.0,
        supervise=True, max_restarts=2, restart_window_s=60.0,
        restart_backoff_s=0.6, first_batch_grace=5.0,
    )
    reg = PipelineRegistry(settings, hub=hub)
    yield reg
    reg.stop_all()


def _request(registry, method, path, body=None):
    async def go():
        app = build_app(registry)
        async with TestClient(TestServer(app)) as client:
            resp = await client.request(method, path, json=body)
            return resp.status, await resp.json()

    return asyncio.run(go())


class TestHubSupervision:
    """The acceptance flow end to end through the hub + REST layer."""

    def test_wedge_rebuild_and_healthz_transition(
            self, sup_registry, monkeypatch):
        hub = sup_registry.hub
        eng = hub.engine("detect", "object_detection/person_vehicle_bike",
                         instance_id="sup-hub-a")
        frame = np.zeros((64, 64, 3), np.uint8)
        # healthy first: the engine serves before the fault arms
        eng.submit(frames=frame).result(timeout=60)
        _wedge_env(monkeypatch, "wedge=1,wedge_n=1,wedge_s=6")
        fut = eng.submit(frames=frame)
        with pytest.raises(TimeoutError):
            fut.result(timeout=15)
        # /healthz: 503 "restarting" while the supervisor rebuilds,
        # then back to 200 once the replacement engine is re-admitted
        seen: list[tuple[int, str]] = []
        deadline = time.time() + 25
        while time.time() < deadline:
            status, data = _request(sup_registry, "GET", "/healthz")
            seen.append((status, data["status"]))
            if any(s == "restarting" for _, s in seen) and status == 200:
                break
            time.sleep(0.03)
        assert (503, "restarting") in seen, seen[-5:]
        assert seen[-1][0] == 200, seen[-5:]
        # re-admission: a subsequent submit on the SAME hub entry
        # (same cached handle) succeeds on the rebuilt engine
        out = eng.submit(frames=frame).result(timeout=60)
        assert out.shape[-1] == 7
        row = hub.stats()["detect:sup-hub-a"]
        assert row["state"] == "running"
        assert row["restarts"] == 1
        assert row["last_stall_ts"] is not None

    def test_budget_exhaustion_reports_degraded_healthz(
            self, sup_registry, monkeypatch):
        hub = sup_registry.hub
        eng = hub.engine("detect", "object_detection/person_vehicle_bike",
                         instance_id="sup-hub-b")
        frame = np.zeros((64, 64, 3), np.uint8)
        eng.submit(frames=frame).result(timeout=60)
        _wedge_env(monkeypatch, "wedge=1,wedge_s=2", seed=1)
        # one frame at a time, as a stream's window would hold it: a
        # submit never blocks, so a loop that does not wait for its
        # results queues batches behind the wedge, and every queued
        # batch buys the watchdog's detection one more stall budget
        # (BatchEngine._track_dispatch)
        deadline = time.time() + 40
        while eng.state != "degraded" and time.time() < deadline:
            try:
                eng.submit(frames=frame).result(timeout=15)
            except (TimeoutError, RuntimeError):
                time.sleep(0.05)
        assert eng.state == "degraded"
        status, data = _request(sup_registry, "GET", "/healthz")
        assert status == 503
        assert data["status"] == "degraded"
        assert data["degraded"] == 1
        assert data["restarts"] >= hub.max_restarts
        row = hub.stats()["detect:sup-hub-b"]
        assert row["restarts"] == hub.max_restarts
        assert metrics.get_counter(
            "evam_engine_restarts",
            labels={"engine": "detect:sup-hub-b"}) == hub.max_restarts
        with pytest.raises(RuntimeError, match="degraded"):
            eng.submit(frames=frame)


class TestFaultSeed:
    def test_seed_makes_runs_reproducible(self, monkeypatch):
        monkeypatch.setenv("EVAM_FAULT_INJECT", "drop=0.5")
        monkeypatch.setenv("EVAM_FAULT_SEED", "123")
        frame = np.zeros((4, 4, 3), np.uint8)

        def run():
            faults.reset_cache()
            inj = faults.from_env()
            assert inj is not None
            return [inj.apply(frame) is None for _ in range(64)]

        a, b = run(), run()
        assert a == b
        assert any(a) and not all(a)  # the faults actually fire

    def test_bad_seed_ignored(self, monkeypatch):
        monkeypatch.setenv("EVAM_FAULT_INJECT", "drop=0.5")
        monkeypatch.setenv("EVAM_FAULT_SEED", "not-an-int")
        faults.reset_cache()
        assert faults.from_env() is not None


class TestRetryBackoff:
    def test_delay_is_capped(self):
        rng = random.Random(0)
        for attempts in range(1, 20):
            d = _retry_delay(attempts, 1.0, 30.0, rng)
            assert d <= 30.0 * 1.25 + 1e-9
            assert d >= 0.05

    def test_jitter_decorrelates_streams(self):
        # same attempt number, different streams → different delays
        delays = {
            round(_retry_delay(4, 1.0, 30.0, random.Random(s)), 6)
            for s in range(16)
        }
        assert len(delays) > 8
        # and all within ±25% of the deterministic 8 s backoff
        assert all(6.0 - 1e-9 <= d <= 10.0 + 1e-9 for d in delays)

    def test_early_attempts_still_exponential(self):
        rng = random.Random(1)
        d1 = _retry_delay(1, 1.0, 30.0, rng)
        assert 0.75 <= d1 <= 1.25


class _StubbornSource:
    """Injected source whose reader ignores close() and keeps the
    worker thread alive well past the drain budget."""

    def __init__(self, hold_s: float = 3.0):
        self.hold_s = hold_s

    def frames(self):
        from evam_tpu.media.source import FrameEvent

        yield FrameEvent(frame=np.zeros((32, 32, 3), np.uint8),
                         pts_ns=0, seq=0)
        time.sleep(self.hold_s)  # wedged read: close() can't unblock it

    def close(self) -> None:
        pass


class TestShutdownDrain:
    def test_leaked_stragglers_are_counted(self, eight_devices):
        settings = Settings(pipelines_dir=str(REPO / "pipelines"),
                            drain_timeout_s=0.2)
        model_registry = ModelRegistry(
            dtype="float32", input_overrides=SMALL, width_overrides=NARROW)
        hub = EngineHub(model_registry, plan=build_mesh(), max_batch=16,
                        deadline_ms=4.0, wire_format="bgr")
        reg = PipelineRegistry(settings, hub=hub)
        inst = reg.start_instance(
            "video_decode", "app_dst",
            {"source": {"type": "application"},
             "destination": {"metadata": {"type": "null"}}},
            source=_StubbornSource(hold_s=3.0),
        )
        # let the worker enter the stubborn read
        time.sleep(0.3)
        t0 = time.time()
        leaked = reg.stop_all()
        assert time.time() - t0 < 2.5  # budget honored, not 3 s hold
        assert leaked == 1
        assert metrics.get_gauge("evam_shutdown_leaked_streams") == 1
        inst.wait(timeout=10)  # reap the daemon before the next test

    def test_straggler_checkpointed_not_leaked(self, eight_devices,
                                               monkeypatch, tmp_path):
        """EVAM_CKPT=on branch of the drain contract: a straggler that
        outlives the drain budget is captured at the ``drain`` barrier
        and persisted for resume instead of counted leaked."""
        from evam_tpu import state as stream_state
        from evam_tpu.config import reset_settings
        from evam_tpu.state import is_checkpoint_blob

        monkeypatch.setenv("EVAM_CKPT", "on")
        reset_settings()
        stream_state.reset_cache()
        try:
            settings = Settings(pipelines_dir=str(REPO / "pipelines"),
                                state_dir=str(tmp_path),
                                drain_timeout_s=0.2)
            model_registry = ModelRegistry(
                dtype="float32", input_overrides=SMALL,
                width_overrides=NARROW)
            hub = EngineHub(model_registry, plan=build_mesh(),
                            max_batch=16, deadline_ms=4.0,
                            wire_format="bgr")
            reg = PipelineRegistry(settings, hub=hub)
            assert reg._ckpt is not None
            drain_moves0 = metrics.get_counter(
                "evam_stream_migrations", labels={"reason": "drain"})
            inst = reg.start_instance(
                "video_decode", "app_dst",
                {"source": {"type": "application"},
                 "destination": {"metadata": {"type": "null"}}},
                source=_StubbornSource(hold_s=3.0),
            )
            time.sleep(0.3)  # let the worker enter the stubborn read
            t0 = time.time()
            leaked = reg.stop_all()
            assert time.time() - t0 < 2.5  # budget still honored
            # checkpointed instead of leaked
            assert leaked == 0
            assert metrics.get_gauge("evam_shutdown_leaked_streams") == 0
            assert metrics.get_counter(
                "evam_stream_migrations",
                labels={"reason": "drain"}) == drain_moves0 + 1
            # and the persisted entry is a resumable checkpoint blob
            entries = json.loads(
                (tmp_path / "streams.json").read_text())
            assert len(entries) == 1
            assert is_checkpoint_blob(entries[0]["state"])
            inst.wait(timeout=10)  # reap the daemon
        finally:
            monkeypatch.delenv("EVAM_CKPT", raising=False)
            reset_settings()
            stream_state.reset_cache()

    def test_clean_drain_counts_zero(self, eight_devices):
        settings = Settings(pipelines_dir=str(REPO / "pipelines"))
        model_registry = ModelRegistry(
            dtype="float32", input_overrides=SMALL, width_overrides=NARROW)
        hub = EngineHub(model_registry, plan=build_mesh(), max_batch=16,
                        deadline_ms=4.0, wire_format="bgr")
        reg = PipelineRegistry(settings, hub=hub)
        inst = reg.start_instance(
            "object_detection", "person_vehicle_bike",
            {"source": {"uri": "synthetic://96x96@30?count=3",
                        "type": "uri"},
             "destination": {"metadata": {"type": "null"}}})
        inst.wait(timeout=60)
        assert reg.stop_all() == 0
        assert metrics.get_gauge("evam_shutdown_leaked_streams") == 0
