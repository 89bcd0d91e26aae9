"""Transfer pipeline A/B (EVAM_TRANSFER, engine/batcher.py): pipelined
H2D-prefetch / launcher / async-D2H vs the inline serial path —
bit-identical results, stage-clock attribution (h2d_issue / h2d_wait /
readback residual), devlock degradation to inline, supervisor rebuilds
inheriting the mode, and the queue-gauge refresh satellite."""

from __future__ import annotations

import time

import numpy as np
import pytest

from evam_tpu.engine import devlock
from evam_tpu.engine.batcher import BatchEngine
from evam_tpu.engine.ringbuf import STAGES
from evam_tpu.obs import faults
from evam_tpu.obs.metrics import metrics


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


class TestTransferModes:
    def test_pipelined_is_default_with_launcher_thread(self):
        eng = _engine("xfer-default")
        try:
            assert eng.transfer == "pipelined"
            assert eng._pipelined
            assert eng._launcher is not None and eng._launcher.is_alive()
            out = eng.submit(x=np.full((4,), 7, np.uint8)).result(
                timeout=30)
            np.testing.assert_array_equal(out, np.full((4,), 22))
        finally:
            eng.stop()

    def test_inline_env_var_selects_serial_path(self, monkeypatch):
        monkeypatch.setenv("EVAM_TRANSFER", "inline")
        eng = _engine("xfer-inline-env")
        try:
            assert eng.transfer == "inline"
            assert not eng._pipelined and eng._launcher is None
            out = eng.submit(x=np.full((4,), 1, np.uint8)).result(
                timeout=30)
            np.testing.assert_array_equal(out, np.full((4,), 4))
        finally:
            eng.stop()

    def test_explicit_arg_beats_env(self, monkeypatch):
        monkeypatch.setenv("EVAM_TRANSFER", "inline")
        eng = _engine("xfer-arg", transfer="pipelined")
        try:
            assert eng.transfer == "pipelined" and eng._pipelined
        finally:
            eng.stop()

    def test_invalid_transfer_rejected(self):
        with pytest.raises(ValueError, match="EVAM_TRANSFER"):
            _engine("xfer-bad", transfer="sideways")

    def test_pipelined_and_inline_outputs_bit_identical(self):
        rows = _rows(40, seed=3)
        results = {}
        for mode in ("pipelined", "inline"):
            eng = _engine(f"xfer-ab-{mode}", transfer=mode)
            try:
                futs = [eng.submit(x=r) for r in rows]
                results[mode] = [f.result(timeout=30) for f in futs]
            finally:
                eng.stop()
        for a, b in zip(results["pipelined"], results["inline"]):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.tobytes() == b.tobytes()

    def test_stage_clock_reports_transfer_split(self):
        """Both modes must keep the full STAGES clock: h2d_issue and
        h2d_wait land in stats (inline pins h2d_wait at exactly 0 —
        the launch call absorbs any wait there by definition)."""
        for mode in ("pipelined", "inline"):
            eng = _engine(f"xfer-clock-{mode}", transfer=mode)
            try:
                # the clock leaves cold buckets out: compile them first
                eng.set_example(x=_rows(1)[0])
                eng.warmup()
                futs = [eng.submit(x=r) for r in _rows(20, seed=4)]
                for f in futs:
                    f.result(timeout=30)
                st = eng.stats
                assert set(st.stage_seconds) == set(STAGES), mode
                assert st.stage_seconds["h2d_issue"] >= 0.0
                assert st.stage_seconds["h2d_wait"] >= 0.0
                if mode == "inline":
                    assert st.stage_seconds["h2d_wait"] == 0.0
                assert set(st.stage_ms_per_batch()) == set(STAGES)
            finally:
                eng.stop()

    def test_sched_class_queues_compose_with_pipelined(self):
        from evam_tpu.sched.classes import SchedConfig

        eng = _engine("xfer-sched", sched=SchedConfig())
        try:
            assert eng._pipelined and eng._classq is not None
            futs = [eng.submit(priority=p, x=np.full((4,), i, np.uint8))
                    for i, p in enumerate(
                        ["realtime", "batch", "standard"])]
            for i, f in enumerate(futs):
                np.testing.assert_array_equal(
                    f.result(timeout=30), np.full((4,), i * 3 + 1))
        finally:
            eng.stop()

    def test_legacy_assembly_composes_with_pipelined(self):
        eng = _engine("xfer-legacy", assembly="legacy")
        try:
            assert eng._pipelined and eng._ring is None
            outs = [eng.submit(x=np.full((4,), i, np.uint8))
                    .result(timeout=30) for i in range(10)]
            assert [int(o[0]) for o in outs] == [i * 3 + 1
                                                for i in range(10)]
        finally:
            eng.stop()


class TestSerializeCompileForcesInline:
    def test_devlock_degrades_pipelined_to_inline(self, monkeypatch):
        """EVAM_SERIALIZE_COMPILE=1: device RPCs must never overlap,
        so a pipelined request degrades to the inline serial path at
        construction and the devlock gauge pins overlap_max at 1 (the
        TestSerializeCompile harness contract)."""
        monkeypatch.setenv("EVAM_SERIALIZE_COMPILE", "1")
        devlock.reset_stats()
        eng = _engine("xfer-devlock", transfer="pipelined")
        try:
            assert eng.transfer == "pipelined"  # the request...
            assert not eng._pipelined           # ...forced inline
            assert eng._launcher is None
            outs = [eng.submit(x=np.full((4,), i, np.uint8))
                    .result(timeout=30) for i in range(20)]
            assert [int(o[0]) for o in outs] == [(i * 3 + 1) % 256
                                                for i in range(20)]
        finally:
            eng.stop()
        assert devlock.max_concurrent() == 1


class TestSupervisorInheritsTransfer:
    def test_rebuild_keeps_transfer_mode(self, monkeypatch):
        """The factory closure is the rebuild recipe: a wedge-triggered
        rebuild must come back with the same transfer mode (and a live
        launcher thread) — EVAM_TRANSFER survives quarantine."""
        from evam_tpu.engine.supervisor import SupervisedEngine

        def factory() -> BatchEngine:
            return _engine("xfer-sup", transfer="pipelined",
                           max_batch=4, deadline_ms=1.0,
                           stall_timeout_s=0.5)

        sup = SupervisedEngine(
            "xfer-sup", factory,
            max_restarts=3, restart_window_s=60.0, backoff_s=0.05)
        try:
            first = sup._engine
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
            assert sup._engine.transfer == "pipelined"
            assert sup._engine._pipelined
            assert sup._engine._launcher.is_alive()
            monkeypatch.setenv("EVAM_FAULT_INJECT", "")
            faults.reset_cache()
            out = sup.submit(x=np.full((4,), 5, np.uint8)).result(
                timeout=30)
            np.testing.assert_array_equal(out, np.full((4,), 16))
        finally:
            sup.stop()

    def test_hub_factory_carries_transfer(self):
        from evam_tpu.engine.hub import EngineHub

        hub = EngineHub(registry=None, plan=None, max_batch=4,
                        supervise=True, stall_timeout_s=0,
                        transfer="inline")
        eng = hub._build("xfer-hub", lambda params, x: x + 1.0,
                         None, ("x",))
        try:
            assert eng.transfer == "inline"  # delegated to live engine
            rebuilt = eng._factory()
            try:
                assert rebuilt.transfer == "inline"
                assert not rebuilt._pipelined
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
