"""The device path of the engine (engine/batcher.py): the dispatcher
issues the upload, the launcher thread launches and puts the readback
in flight, the completer resolves — stage-clock attribution
(h2d_issue / h2d_wait / readback residual), what happens to a batch
whose upload or launch raises, stop/abandon with batches parked
between the threads, supervisor rebuilds inheriting the upload-queue
depth, and the queue-gauge refresh satellite."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from evam_tpu.engine import batcher
from evam_tpu.engine.batcher import BatchEngine
from evam_tpu.engine.ringbuf import STAGES
from evam_tpu.obs import faults
from evam_tpu.obs.metrics import metrics
from evam_tpu.sched.classes import SchedConfig


def _engine(name: str, **kw) -> BatchEngine:
    kwargs = dict(
        # uint8 wrap math: elementwise and bitwise deterministic, so
        # per-item outputs cannot depend on batch composition/bucket
        step_fn=lambda params, x: x * 3 + 1,
        params=None,
        max_batch=8,
        deadline_ms=2.0,
        input_names=("x",),
        stall_timeout_s=0,
    )
    kwargs.update(kw)
    return BatchEngine(name, **kwargs)


def _rows(n: int, seed: int = 0) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 255, (6, 4), np.uint8) for _ in range(n)]


def _x(v: int) -> np.ndarray:
    return np.full((4,), v, np.uint8)


def _gate_launcher(eng: BatchEngine):
    """Park the launcher inside ``_launch`` (where a hung backend RPC
    would hold it) until ``gate`` is set; the dispatcher keeps staging
    and uploading behind it."""
    gate = threading.Event()
    entered = threading.Event()
    orig = eng._launch

    def gated(dev, clock, b):
        entered.set()
        gate.wait(timeout=60)
        return orig(dev, clock, b)

    eng._launch = gated
    return gate, entered


def _free_blocks(eng: BatchEngine) -> int:
    with eng._ring._cv:
        return len(eng._ring._free)


def _await(cond, timeout: float = 10.0) -> bool:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


class TestOnePath:
    @pytest.mark.parametrize("kw", [
        {},
        {"sched": SchedConfig()},
        {"sched": SchedConfig.disabled()},
        {"staging_depth": 2, "transfer_depth": 1, "max_in_flight": 1},
    ], ids=["plain", "sched", "sched-disabled", "shallow"])
    def test_one_dispatch_loop_whatever_the_arguments(self, kw):
        """Every engine starts the same three workers on the same
        three loops — the ones ``serve`` runs — and serves through
        them."""
        eng = _engine("xfer-one", **kw)
        try:
            for thread, loop in ((eng._dispatcher, eng._dispatch_loop),
                                 (eng._launcher, eng._launch_loop),
                                 (eng._completer, eng._completion_loop)):
                assert thread.is_alive()
                assert thread._args == (loop,)
            outs = [eng.submit(x=_x(i)).result(timeout=30)
                    for i in range(5)]
            for i, out in enumerate(outs):
                np.testing.assert_array_equal(out, _x(i * 3 + 1))
        finally:
            eng.stop()

    def test_stage_clock_reports_transfer_split(self):
        """The full STAGES clock lands in stats: h2d_issue from the
        dispatcher, h2d_wait and launch from the launcher."""
        eng = _engine("xfer-clock")
        try:
            # the clock leaves cold buckets out: compile them first
            eng.set_example(x=_rows(1)[0])
            eng.warmup()
            futs = [eng.submit(x=r) for r in _rows(20, seed=4)]
            for f in futs:
                f.result(timeout=30)
            st = eng.stats
            assert set(st.stage_seconds) == set(STAGES)
            assert st.stage_seconds["h2d_issue"] >= 0.0
            assert st.stage_seconds["h2d_wait"] >= 0.0
            assert set(st.stage_ms_per_batch()) == set(STAGES)
        finally:
            eng.stop()

    def test_classes_ride_the_launcher(self):
        eng = _engine("xfer-sched", sched=SchedConfig())
        try:
            futs = [eng.submit(priority=p, x=_x(i))
                    for i, p in enumerate(
                        ["realtime", "batch", "standard"])]
            for i, f in enumerate(futs):
                np.testing.assert_array_equal(
                    f.result(timeout=30), _x(i * 3 + 1))
        finally:
            eng.stop()


class TestBatchFailures:
    """A batch that cannot reach the device, or fails on it, fails
    ITS futures, gives its staging block back, and the engine goes on
    serving."""

    def test_upload_that_raises_fails_that_batch_only(self, monkeypatch):
        eng = _engine("xfer-h2d-raise", staging_depth=2)
        try:
            eng.submit(x=_x(1)).result(timeout=30)
            # take the explicit device_put arm of the dispatcher (a
            # TPU's; works on the CPU backend too) and make the next
            # upload raise
            eng._device_streams = True
            real = batcher.jax.device_put
            calls = []

            def flaky(*a, **k):
                calls.append(1)
                if len(calls) == 1:
                    raise RuntimeError("injected upload failure")
                return real(*a, **k)

            monkeypatch.setattr(batcher.jax, "device_put", flaky)
            with pytest.raises(RuntimeError, match="injected upload"):
                eng.submit(x=_x(2)).result(timeout=30)
            np.testing.assert_array_equal(
                eng.submit(x=_x(3)).result(timeout=30), _x(10))
            assert len(calls) >= 2
            assert eng._dispatcher.is_alive()
            assert _await(lambda: _free_blocks(eng) == 2)
        finally:
            eng.stop()

    def test_launch_that_raises_fails_that_batch_only(self):
        eng = _engine("xfer-launch-raise", staging_depth=2)
        try:
            eng.submit(x=_x(1)).result(timeout=30)
            orig = eng._launch
            fails = [True]

            def flaky(dev, clock, b):
                if fails.pop() if fails else False:
                    raise RuntimeError("injected launch failure")
                return orig(dev, clock, b)

            eng._launch = flaky
            with pytest.raises(RuntimeError, match="injected launch"):
                eng.submit(x=_x(2)).result(timeout=30)
            np.testing.assert_array_equal(
                eng.submit(x=_x(3)).result(timeout=30), _x(10))
            assert eng._launcher.is_alive()
            # the failed batch holds no in-flight slot, no watchdog
            # entry and no staging block
            assert not eng._outstanding
            assert _await(lambda: _free_blocks(eng) == 2)
        finally:
            eng.stop()

    @pytest.mark.parametrize("how", ["stop", "abandon"])
    def test_teardown_fails_uploaded_unlaunched_batches(self, how):
        """With the launcher parked in a launch, the dispatcher keeps
        staging and uploading: one batch is in flight, the next sits
        in the upload queue. ``stop()``/``abandon()`` must fail the
        queued batch's futures and return its block."""
        eng = _engine(f"xfer-{how}", max_batch=1, staging_depth=4,
                      transfer_depth=2)
        gate, entered = _gate_launcher(eng)
        try:
            f1 = eng.submit(x=_x(1))
            assert entered.wait(timeout=30)  # launcher holds batch 1
            f2 = eng.submit(x=_x(2))
            f3 = eng.submit(x=_x(3))
            assert _await(lambda: eng._upload_q.qsize() == 2)
            assert _free_blocks(eng) == 1  # 4 blocks, 3 batches staged
            if how == "abandon":
                eng.abandon()
                err = TimeoutError
            else:
                threading.Timer(0.3, gate.set).start()
                eng.stop()
                err = RuntimeError
            for f in (f2, f3):
                with pytest.raises(err):
                    f.result(timeout=10)
            if how == "abandon":
                # in flight behind the wedge: failed with the rest
                with pytest.raises(TimeoutError):
                    f1.result(timeout=10)
                assert _free_blocks(eng) == 3
            else:
                # the launch ran to its end once the gate opened
                np.testing.assert_array_equal(
                    f1.result(timeout=10), _x(4))
                assert _free_blocks(eng) == 4
            assert eng._upload_q.qsize() == 0
        finally:
            gate.set()
            eng.abandon()


class TestSupervisorInheritsTransferDepth:
    def test_rebuild_resumes_at_the_factory_transfer_depth(
            self, monkeypatch):
        """The factory closure carries the depth: an engine rebuilt
        after a wedge comes back at it, with a live launcher
        thread."""
        from evam_tpu.engine.supervisor import SupervisedEngine

        def factory() -> BatchEngine:
            return _engine("xfer-sup", transfer_depth=3,
                           max_batch=4, deadline_ms=1.0,
                           stall_timeout_s=0.5)

        sup = SupervisedEngine(
            "xfer-sup", factory,
            max_restarts=3, restart_window_s=60.0, backoff_s=0.05)
        try:
            first = sup._engine
            assert first.transfer_depth == 3
            sup.submit(x=np.zeros((4,), np.uint8)).result(timeout=30)
            monkeypatch.setenv("EVAM_FAULT_INJECT",
                               "wedge=1,wedge_n=1,wedge_s=4")
            faults.reset_cache()
            fut = sup.submit(x=np.full((4,), 2, np.uint8))
            with pytest.raises(TimeoutError):
                fut.result(timeout=15)
            deadline = time.time() + 20
            while time.time() < deadline:
                if sup.state == "running" and sup.restarts == 1:
                    break
                time.sleep(0.05)
            assert sup.state == "running" and sup.restarts == 1
            assert sup._engine is not first
            assert sup._engine.transfer_depth == 3
            assert sup._engine._upload_q.maxsize == 3
            assert sup._engine._launcher.is_alive()
            monkeypatch.setenv("EVAM_FAULT_INJECT", "")
            faults.reset_cache()
            out = sup.submit(x=np.full((4,), 5, np.uint8)).result(
                timeout=30)
            np.testing.assert_array_equal(out, np.full((4,), 16))
        finally:
            sup.stop()

    def test_hub_factory_carries_transfer_depth(self):
        from evam_tpu.engine.hub import EngineHub

        hub = EngineHub(registry=None, plan=None, max_batch=4,
                        supervise=True, stall_timeout_s=0,
                        transfer_depth=3)
        eng = hub._build("xfer-hub", lambda params, x: x + 1.0,
                         None, ("x",))
        try:
            assert eng.transfer_depth == 3  # delegated to live engine
            rebuilt = eng._factory()
            try:
                assert rebuilt.transfer_depth == 3
                assert rebuilt._upload_q.maxsize == 3
            finally:
                rebuilt.stop()
        finally:
            eng.stop()


class TestQueueGaugeRefresh:
    """Obs satellite: evam_engine_queue_depth/age_s used to refresh
    only on dispatch (_record_batch) — an idle or wedged engine showed
    stale gauges while its backlog grew. The watchdog tick and the
    supervisor monitor now refresh them too."""

    @staticmethod
    def _await_gauge(name: str, engine: str, want: float,
                     timeout: float = 5.0) -> float:
        deadline = time.time() + timeout
        while time.time() < deadline:
            v = metrics.get_gauge(name, labels={"engine": engine})
            if v >= want:
                return v
            time.sleep(0.05)
        return metrics.get_gauge(name, labels={"engine": engine})

    def test_watchdog_tick_refreshes_without_dispatch(self):
        # huge deadline: the two staged rows sit undispatched; only
        # the watchdog tick (stall 1.0s → 0.25s tick) can publish them
        eng = _engine("gauge-wd", deadline_ms=30_000.0,
                      stall_timeout_s=1.0)
        try:
            for i in range(2):
                eng.submit(x=np.full((4,), i, np.uint8))
            depth = self._await_gauge(
                "evam_engine_queue_depth", "gauge-wd", 2.0)
            assert depth == 2.0
            assert eng.stats.batches == 0  # really no dispatch yet
            assert metrics.get_gauge(
                "evam_engine_queue_age_s",
                labels={"engine": "gauge-wd"}) > 0.0
        finally:
            eng.stop()

    def test_supervisor_tick_refreshes_without_dispatch(self):
        from evam_tpu.engine.supervisor import SupervisedEngine

        # stall watchdog OFF: the supervisor monitor is the only
        # refresher left — the satellite's second path
        sup = SupervisedEngine(
            "gauge-sup",
            lambda: _engine("gauge-sup", deadline_ms=30_000.0,
                            stall_timeout_s=0),
            max_restarts=3, restart_window_s=60.0, backoff_s=0.05)
        try:
            for i in range(3):
                sup.submit(x=np.full((4,), i, np.uint8))
            depth = self._await_gauge(
                "evam_engine_queue_depth", "gauge-sup", 3.0)
            assert depth == 3.0
            assert sup.stats.batches == 0
        finally:
            sup.stop()
