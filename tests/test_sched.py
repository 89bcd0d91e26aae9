"""Tier-1 tests for the QoS layer (evam_tpu/sched/): admission
control, priority-class scheduling, and load shedding.

Deterministic by construction — the flood tests gate the engine's
dispatcher on a threading.Event instead of hoping a race lands, so
the overload ladder (admit → queue → shed) is asserted exactly:

* an over-capacity start is rejected (503 path = AdmissionError),
  with ``standard``/``batch`` turned away before ``realtime``;
* under a synthetic flood, realtime-class frames are never shed while
  batch-class sheds are nonzero and counted in
  ``evam_sched_shed_total{class}``;
* with scheduling disabled (EVAM_SCHED=off / sched=None) the same
  dispatch loop runs as one FIFO: one class, the engine's deadline,
  nothing shed.

Marker-gated (``-m "not sched"`` skips) but NOT slow — this is the
tier-1 contract suite for the subsystem, like ``chaos``.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from pathlib import Path

import numpy as np
import pytest

from evam_tpu.engine.batcher import BatchEngine
from evam_tpu.obs.metrics import metrics
from evam_tpu.sched import (
    AdmissionController,
    AdmissionError,
    ClassQueues,
    SchedConfig,
    Shedder,
    ShedError,
    validate_priority,
)

REPO = Path(__file__).resolve().parent.parent

pytestmark = pytest.mark.sched


class _Item:
    """Minimal _WorkItem stand-in (t_submit + future)."""

    def __init__(self, t: float | None = None):
        self.t_submit = time.perf_counter() if t is None else t
        self.future: Future = Future()


def _toy_engine(name: str, **kw) -> BatchEngine:
    kwargs = dict(
        step_fn=lambda params, x: x * 2.0,
        params=None,
        plan=None,
        max_batch=4,
        deadline_ms=4.0,
        input_names=("x",),
        stall_timeout_s=0,
    )
    kwargs.update(kw)
    return BatchEngine(name, **kwargs)


def _x(v: float = 0.0) -> np.ndarray:
    return np.full((2,), v, np.float32)


# --------------------------------------------------------------- classes


class TestPriorityValidation:
    def test_valid_values_normalize(self):
        assert validate_priority("realtime") == "realtime"
        assert validate_priority(" Batch ") == "batch"

    def test_unknown_value_raises(self):
        with pytest.raises(ValueError, match="realtime|standard|batch"):
            validate_priority("turbo")
        with pytest.raises(ValueError):
            validate_priority(3)


class TestClassQueues:
    def test_realtime_first(self):
        q = ClassQueues()
        q.put("batch", _Item())
        q.put("standard", _Item())
        q.put("realtime", _Item())
        assert q.pick(timeout=0.1) == "realtime"

    def test_pick_timeout_on_empty(self):
        q = ClassQueues()
        assert q.pick(timeout=0.01) is None

    def test_starvation_guard_serves_lower_classes(self):
        """A saturated realtime lane must not starve batch/standard
        forever: within the starvation limits every class is served."""
        q = ClassQueues()
        q.put("standard", _Item())
        q.put("batch", _Item())
        picked = []
        for _ in range(40):
            q.put("realtime", _Item())  # lane never drains
            cls = q.pick(timeout=0.1)
            picked.append(cls)
            q.collect(cls, 64, 0.0)  # pop what was picked
            if "standard" in picked and "batch" in picked:
                break
        assert "standard" in picked, picked
        assert "batch" in picked, picked
        # realtime still dominates the schedule
        assert picked.count("realtime") > picked.count("batch")

    def test_collect_immediate_when_backlogged(self):
        q = ClassQueues()
        old = time.perf_counter() - 10.0
        for _ in range(6):
            q.put("batch", _Item(t=old))
        t0 = time.perf_counter()
        items = q.collect("batch", 4, deadline_s=5.0)
        assert len(items) == 4  # capped at max_n
        assert time.perf_counter() - t0 < 1.0  # head deadline long past
        assert q.depth() == 2

    def test_collect_honors_deadline_for_trickle(self):
        q = ClassQueues()
        q.put("realtime", _Item())
        t0 = time.perf_counter()
        items = q.collect("realtime", 4, deadline_s=0.05)
        assert len(items) == 1
        assert time.perf_counter() - t0 >= 0.04

    def test_pop_expired_oldest_first(self):
        q = ClassQueues()
        now = time.perf_counter()
        stale = [_Item(t=now - 1.0), _Item(t=now - 0.5)]
        fresh = _Item(t=now)
        for it in stale + [fresh]:
            q.put("batch", it)
        expired = q.pop_expired("batch", now - 0.1)
        assert expired == stale
        assert q.depth_by_class()["batch"] == 1

    def test_depth_and_age(self):
        q = ClassQueues()
        assert q.depth() == 0 and q.oldest_age_s() == 0.0
        q.put("standard", _Item(t=time.perf_counter() - 2.0))
        q.put("realtime", _Item())
        assert q.depth() == 2
        assert q.oldest_age_s() >= 2.0

    def test_close_drains_and_rejects(self):
        q = ClassQueues()
        q.put("standard", _Item())
        q.close()
        with pytest.raises(RuntimeError):
            q.put("standard", _Item())
        assert len(q.drain()) == 1
        assert q.pick(timeout=0.01) is None


# --------------------------------------------------------------- shedder


class TestShedder:
    def test_shed_drops_only_stale_items(self):
        sh = Shedder("eng", {"batch": 0.1})
        now = time.perf_counter()
        stale = [_Item(t=now - 1.0), _Item(t=now - 0.2)]
        fresh = [_Item(t=now)]
        survivors = sh.shed("batch", stale + fresh, now=now)
        assert survivors == fresh
        assert sh.counts["batch"] == 2
        for it in stale:
            with pytest.raises(ShedError) as ei:
                it.future.result(timeout=0)
            assert ei.value.priority == "batch"
            assert ei.value.age_s > ei.value.budget_s

    def test_zero_budget_never_sheds(self):
        sh = Shedder("eng", {"batch": 0.0})
        items = [_Item(t=time.perf_counter() - 100.0)]
        assert sh.shed("batch", items) == items
        assert sh.counts["batch"] == 0

    def test_sweep_shes_waiting_backlog_per_class(self):
        sh = Shedder("eng", {"batch": 0.05, "realtime": 10.0})
        q = ClassQueues()
        now = time.perf_counter()
        q.put("batch", _Item(t=now - 1.0))
        q.put("batch", _Item(t=now))
        q.put("realtime", _Item(t=now - 1.0))  # within its 10s budget
        before = metrics.get_counter("evam_sched_shed",
                                     labels={"class": "batch"})
        assert sh.sweep(q, now=now) == 1
        assert q.depth_by_class() == {"realtime": 1, "standard": 0,
                                      "batch": 1}
        assert metrics.get_counter(
            "evam_sched_shed", labels={"class": "batch"}) == before + 1


    @pytest.mark.parametrize("cls", [
        c for c, ms in SchedConfig().staleness_ms.items() if ms > 0])
    def test_a_class_sheds_at_its_own_setting(self, cls):
        """A class's staleness budget is its EVAM_SCHED_STALENESS_MS_*
        setting and nothing scales it: what is a tenth younger than it
        rides, what is a tenth older is shed and says so, in a formed
        batch and in the waiting queue alike."""
        cfg = SchedConfig()
        budget = cfg.staleness_ms[cls] / 1e3
        eng = _toy_engine(f"sched-budget-{cls}", sched=cfg)
        try:
            assert eng._shedder.staleness_s[cls] == budget
        finally:
            eng.stop()
        sh = Shedder("eng", cfg.staleness_s())
        now = time.perf_counter()
        old, young = _Item(t=now - 1.1 * budget), _Item(t=now - 0.9 * budget)
        assert sh.shed(cls, [old, young], now=now) == [young]
        with pytest.raises(ShedError) as ei:
            old.future.result(timeout=0)
        assert ei.value.budget_s == budget
        q = ClassQueues()
        q.put(cls, _Item(t=now - 1.1 * budget))
        q.put(cls, _Item(t=now - 0.9 * budget))
        assert sh.sweep(q, now=now) == 1
        assert q.depth_by_class()[cls] == 1


# ------------------------------------------------------------- admission


class _FakeHub:
    max_batch = 16

    def __init__(self, stats: dict | None = None):
        self._stats = stats or {}

    def stats(self) -> dict:
        return self._stats


class TestAdmission:
    def test_disabled_admits_everything_but_counts(self):
        ctrl = AdmissionController(_FakeHub(), SchedConfig.disabled())
        for _ in range(50):
            ctrl.admit("batch", 1000.0)
        assert ctrl.counts()["admitted"]["batch"] == 50
        assert ctrl.counts()["rejected"]["batch"] == 0

    def test_cold_hub_admits(self):
        cfg = SchedConfig(admit_util=0.5)  # derived capacity, no stats
        ctrl = AdmissionController(_FakeHub(), cfg)
        ctrl.admit("standard", 10_000.0)  # unknown capacity: admit

    def test_over_capacity_rejected_with_retry_after(self):
        cfg = SchedConfig(capacity_fps=10.0, admit_util=0.85)
        ctrl = AdmissionController(_FakeHub(), cfg)
        with pytest.raises(AdmissionError) as ei:
            ctrl.admit("realtime", 30.0)
        assert 1.0 <= ei.value.retry_after_s <= 30.0
        assert ctrl.counts()["rejected"]["realtime"] == 1

    def test_batch_and_standard_rejected_before_realtime(self):
        """Class headroom ladder: at the same projected load, batch is
        turned away first, then standard, realtime last."""
        cfg = SchedConfig(capacity_fps=100.0, admit_util=0.85)
        ctrl = AdmissionController(_FakeHub(), cfg)
        ctrl.admit("realtime", 30.0)  # util 0.3: everyone fits
        # next 30 fps stream projects util 0.6: above batch's ceiling
        # (0.85*0.6=0.51), below standard's (0.7225) and realtime's
        with pytest.raises(AdmissionError):
            ctrl.admit("batch", 30.0)
        ctrl.admit("standard", 30.0)
        # util now 0.6; another 30 fps projects 0.9 > realtime's 0.85
        with pytest.raises(AdmissionError):
            ctrl.admit("realtime", 30.0)

    def test_release_frees_capacity(self):
        cfg = SchedConfig(capacity_fps=100.0, admit_util=0.85)
        ctrl = AdmissionController(_FakeHub(), cfg)
        t1 = ctrl.admit("realtime", 60.0)
        with pytest.raises(AdmissionError):
            ctrl.admit("realtime", 60.0)
        t1.release()
        t1.release()  # idempotent
        ctrl.admit("realtime", 60.0)

    def test_capacity_derived_from_engine_stats(self):
        """capacity = batches/s x mean occupancy x top bucket of the
        BOTTLENECK engine (per-batch device path from the PR-1 stage
        clock: h2d issue/wait + launch + readback residual)."""
        stats = {
            "detect:m": {  # 10ms/batch, occ 0.5 -> 100*0.5*16 = 800
                "batches": 100, "mean_occupancy": 0.5,
                "stage_ms": {"h2d_issue": 1.0, "h2d_wait": 1.0,
                             "launch": 6.0, "readback": 2.0},
            },
            "classify:m": {  # 40ms/batch, occ 1.0 -> 25*1.0*16 = 400
                "batches": 50, "mean_occupancy": 1.0,
                "stage_ms": {"h2d_issue": 8.0, "h2d_wait": 2.0,
                             "launch": 20.0, "readback": 10.0},
            },
            "cold:m": {"batches": 0, "mean_occupancy": 0.0,
                       "stage_ms": {}},
        }
        ctrl = AdmissionController(_FakeHub(stats), SchedConfig())
        assert ctrl.capacity_fps() == pytest.approx(400.0, rel=0.01)

    def test_a_row_the_clock_has_not_sampled_says_nothing(self):
        """The stage clock covers steady-state batches only (the
        engine leaves out cold buckets and everything dispatched
        while a warmup compiles), so a row can have served batches
        and still carry no ``stage_ms``. Such a row is no
        measurement: with no other row the hub is cold and admits —
        on the v5e a cold start read as 99-321 fps and refused
        streams the chip carries."""
        row = {"batches": 500, "items": 4000, "stage_batches": 0,
               "stage_ms": {}}
        ctrl = AdmissionController(_FakeHub({"detect:m": row}),
                                   SchedConfig())
        assert ctrl.capacity_fps() == 0.0
        ctrl.admit("standard", 30.0)  # unknown capacity admits
        row.update(stage_batches=1, stage_ms={
            "h2d_issue": 1.0, "launch": 3.0, "readback": 1.0})
        assert ctrl.capacity_fps() == pytest.approx(1600.0)

    def test_ceiling_is_the_configured_one(self):
        """The ceiling a start is held to is EVAM_SCHED_ADMIT_UTIL
        times its class's headroom, before and after a rejection, and
        /scheduler reports the setting."""
        cfg = SchedConfig(capacity_fps=100.0, admit_util=0.7)
        ctrl = AdmissionController(_FakeHub(), cfg)
        for _ in range(2):
            with pytest.raises(AdmissionError) as ei:
                ctrl.admit("standard", 90.0)
            assert ei.value.ceiling == pytest.approx(0.7 * 0.85)
        assert ctrl.snapshot()["admit_util"] == 0.7
        ctrl.admit("standard", 59.0)  # 0.59 <= 0.595

    def test_capacity_is_the_engines_own_projection(self):
        """With no EVAM_SCHED_CAPACITY_FPS, capacity is read from the
        engines' stats at each call, unsmoothed: a row that models its
        own (engine/generate.py) is taken at its word, the slowest
        engine kind bounds the pipeline, and a new reading is the
        answer at once. A declared capacity beats every reading."""
        stats = {
            "detect:m": {"batches": 10, "items": 40, "stage_batches": 10,
                         "stage_ms": {"launch": 10.0}},  # 400 fps
            "generate:m": {"capacity_fps": 40.0},
        }
        hub = _FakeHub(stats)
        ctrl = AdmissionController(hub, SchedConfig())
        assert ctrl.capacity_fps() == pytest.approx(40.0)
        assert ctrl.snapshot()["capacity_fps"] == 40.0
        stats["generate:m"]["capacity_fps"] = 1000.0
        assert ctrl.capacity_fps() == pytest.approx(400.0)
        declared = AdmissionController(hub, SchedConfig(capacity_fps=55.0))
        assert declared.capacity_fps() == 55.0

    def test_snapshot_shape(self):
        ctrl = AdmissionController(_FakeHub(), SchedConfig())
        snap = ctrl.snapshot()
        for key in ("enabled", "admit_util", "capacity_fps",
                    "demand_fps", "utilization", "streams", "admitted",
                    "rejected", "deadline_ms", "staleness_ms"):
            assert key in snap, key


# ---------------------------------------------------------------- engine


def _gate_dispatcher(eng: BatchEngine):
    """Park the dispatcher at its hand-over to the device path
    (``_dispatch_batch``: the batch is staged, nothing is uploaded)
    until ``gate`` is set, so everything submitted meanwhile stays in
    the class queues. ``batches`` records, in dispatch order, the
    futures each batch carried."""
    gate = threading.Event()
    entered = threading.Event()
    batches: list[list[Future]] = []
    orig = eng._dispatch_batch

    def gated(sealed):
        batches.append([it.future for it in sealed.items])
        entered.set()
        gate.wait(timeout=60)
        return orig(sealed)

    eng._dispatch_batch = gated
    return gate, entered, batches


class TestEngineSched:
    def test_classes_all_resolve(self):
        eng = _toy_engine("sched-ok", sched=SchedConfig())
        try:
            futs = [eng.submit(priority=p, x=_x(i)) for i, p in enumerate(
                ["realtime", "standard", "batch", "realtime", "batch"])]
            outs = [f.result(timeout=60) for f in futs]
            for i, out in enumerate(outs):
                np.testing.assert_allclose(out, np.full((2,), 2.0 * i))
        finally:
            eng.stop()

    def test_unknown_priority_rejected_at_submit(self):
        eng = _toy_engine("sched-bad-prio", sched=SchedConfig())
        try:
            with pytest.raises(ValueError, match="priority"):
                eng.submit(priority="turbo", x=_x())
        finally:
            eng.stop()

    def test_flood_sheds_batch_never_realtime(self):
        """The acceptance gate: gate the dispatcher on an Event so a
        backlog builds deterministically; realtime (10s budget) rides
        it out, batch (40ms budget) is shed oldest-first and counted
        in evam_sched_shed_total{class}."""
        cfg = SchedConfig(staleness_ms={
            "realtime": 10_000.0, "standard": 10_000.0, "batch": 40.0})
        # the DISPATCHER must be the thread that blocks: gated at the
        # launcher it would keep draining the class queues into the
        # upload queue and the backlog this test asserts on would
        # live there instead
        eng = _toy_engine("sched-flood", sched=cfg)
        gate, entered, _ = _gate_dispatcher(eng)
        shed0 = {
            c: metrics.get_counter("evam_sched_shed", labels={"class": c})
            for c in ("realtime", "batch")
        }
        try:
            first_rt = eng.submit(priority="realtime", x=_x(1.0))
            assert entered.wait(timeout=30)  # dispatcher is now gated
            rt = [eng.submit(priority="realtime", x=_x(2.0))
                  for _ in range(3)]
            bt = [eng.submit(priority="batch", x=_x(3.0))
                  for _ in range(8)]
            # queued work is visible while the engine is busy — the
            # gauge satellite's raison d'etre
            assert eng.queue_depth() >= 11
            assert eng.class_depths()["batch"] == 8
            time.sleep(0.1)  # age the batch items past their 40ms
            assert eng.queue_age_s() >= 0.1
            gate.set()
            # realtime NEVER shed: every future resolves to its value
            np.testing.assert_allclose(
                first_rt.result(timeout=60), np.full((2,), 2.0))
            for f in rt:
                np.testing.assert_allclose(
                    f.result(timeout=60), np.full((2,), 4.0))
            shed = 0
            for f in bt:
                try:
                    f.result(timeout=60)
                except ShedError:
                    shed += 1
            assert shed > 0
            assert eng.shed_counts()["batch"] == shed
            assert eng.shed_counts()["realtime"] == 0
            assert metrics.get_counter(
                "evam_sched_shed", labels={"class": "batch"}
            ) == shed0["batch"] + shed
            assert metrics.get_counter(
                "evam_sched_shed", labels={"class": "realtime"}
            ) == shed0["realtime"]
        finally:
            gate.set()
            eng.stop()

    def test_sched_off_is_one_fifo(self):
        """EVAM_SCHED=off: sched=None is the same loop read as one
        FIFO — every submit joins ``standard`` whatever priority it
        names (and none is validated), dispatch order is submit order
        across the named priorities, and nothing is shed however old
        (10 s here: past every class's default budget)."""
        eng = _toy_engine("sched-off")
        gate, entered, batches = _gate_dispatcher(eng)
        try:
            assert eng.sched is None
            first = eng.submit(priority="batch", x=_x(0))
            assert entered.wait(timeout=30)  # dispatcher is now gated
            names = ["batch", "realtime", "standard", "turbo",
                     "realtime", "batch", "realtime", "batch"]
            futs = [eng.submit(priority=p, x=_x(i + 1))
                    for i, p in enumerate(names)]
            assert eng.class_depths() == {
                "realtime": 0, "standard": len(names), "batch": 0}
            with eng._classq._cv:
                for it in eng._classq._q["standard"]:
                    it.t_submit -= 10.0
            assert eng.queue_age_s() >= 10.0
            gate.set()
            for i, f in enumerate([first] + futs):
                np.testing.assert_allclose(
                    f.result(timeout=60), np.full((2,), 2.0 * i))
            assert sum(batches, []) == [first] + futs
            assert eng.shed_counts() == {
                "realtime": 0, "standard": 0, "batch": 0}
        finally:
            gate.set()
            eng.stop()

    def test_sched_off_forms_under_engine_deadline(self):
        """sched=None: whatever priority a submit names, its batch
        forms under the engine's ``deadline_ms`` (300 ms here), not
        under that class's scheduler default (realtime: 4 ms) — two
        submits 50 ms apart ride ONE batch, dispatched no earlier
        than the deadline past the first."""
        eng = _toy_engine("sched-off-deadline", deadline_ms=300.0)
        try:
            t0 = time.perf_counter()
            f1 = eng.submit(priority="realtime", x=_x(1.0))
            time.sleep(0.05)
            f2 = eng.submit(priority="realtime", x=_x(2.0))
            f1.result(timeout=60)
            f2.result(timeout=60)
            assert time.perf_counter() - t0 >= 0.3
            assert eng.stats.batches == 1 and eng.stats.items == 2
        finally:
            eng.stop()

    @pytest.mark.parametrize("cls", ["realtime", "standard", "batch"])
    def test_a_batch_forms_under_max_batch_and_its_class_deadline(
            self, cls):
        """The dispatcher collects a class's batch under the engine's
        ``max_batch`` and that class's EVAM_SCHED_DEADLINE_MS_*, and
        under nothing else."""
        cfg = SchedConfig(deadline_ms={
            "realtime": 3.0, "standard": 7.0, "batch": 11.0})
        eng = _toy_engine(f"sched-collect-{cls}", sched=cfg, max_batch=4)
        calls: list[tuple] = []
        orig = eng._classq.collect

        def spy(priority, max_n, deadline_s):
            calls.append((priority, max_n, deadline_s))
            return orig(priority, max_n, deadline_s)

        eng._classq.collect = spy
        try:
            eng.submit(priority=cls, x=_x(1.0)).result(timeout=60)
            assert calls == [(cls, 4, cfg.deadline_ms[cls] / 1e3)]
        finally:
            eng.stop()

    def test_stop_fails_queued_items(self):
        cfg = SchedConfig()
        # the gate blocks the dispatcher (see the flood test) so the
        # stuck submits stay queued until stop()
        eng = _toy_engine("sched-stop", sched=cfg)
        gate, entered, _ = _gate_dispatcher(eng)
        eng.submit(priority="realtime", x=_x())
        assert entered.wait(timeout=30)
        stuck = [eng.submit(priority="batch", x=_x()) for _ in range(3)]
        gate.set()
        eng.stop()
        for f in stuck:
            with pytest.raises((RuntimeError, ShedError)):
                f.result(timeout=10)


# ------------------------------------------------------------------ rest


class TestRestRejection:
    """Acceptance gate (a): an over-capacity start is rejected with
    503 + Retry-After at the REST surface. A rejected start never
    builds stages or engines, so this runs against a cold hub."""

    def test_over_capacity_post_is_503_with_retry_after(
            self, eight_devices):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from evam_tpu.config.settings import Settings
        from evam_tpu.engine import EngineHub
        from evam_tpu.models import ModelRegistry
        from evam_tpu.parallel import build_mesh
        from evam_tpu.server.app import build_app
        from evam_tpu.server.registry import PipelineRegistry

        hub = EngineHub(ModelRegistry(dtype="float32"), plan=build_mesh(),
                        max_batch=16,
                        sched=SchedConfig(capacity_fps=10.0))
        reg = PipelineRegistry(
            Settings(pipelines_dir=str(REPO / "pipelines")), hub=hub)

        async def go():
            app = build_app(reg)
            async with TestClient(TestServer(app)) as client:
                resp = await client.post(
                    "/pipelines/object_detection/person_vehicle_bike",
                    json={
                        "source": {"uri": "synthetic://96x96@30?count=6",
                                   "type": "uri"},
                        "destination": {"metadata": {"type": "null"}},
                        "priority": "batch",
                    })
                return resp.status, dict(resp.headers), await resp.json()

        try:
            status, headers, body = asyncio.run(go())
            assert status == 503
            assert int(headers["Retry-After"]) >= 1
            assert body["retry_after_s"] >= 1
            assert "admission rejected" in body["error"]
            assert reg.admission.counts()["rejected"]["batch"] == 1
        finally:
            reg.stop_all()


# ------------------------------------------------------------- plumbing


class TestSettingsPlumbing:
    def test_env_keys_reach_hub_and_engine(self, eight_devices,
                                           monkeypatch):
        """The satellite audit: EVAM_BATCH_DEADLINE_MS really reaches
        EngineHub/BatchEngine, and the EVAM_SCHED_* keys land in the
        hub's SchedConfig."""
        from evam_tpu.config.settings import Settings
        from evam_tpu.server.registry import PipelineRegistry

        monkeypatch.setenv("EVAM_BATCH_DEADLINE_MS", "11.5")
        monkeypatch.setenv("EVAM_SCHED", "on")
        monkeypatch.setenv("EVAM_SCHED_ADMIT_UTIL", "0.7")
        monkeypatch.setenv("EVAM_SCHED_DEADLINE_MS_BATCH", "40")
        monkeypatch.setenv("EVAM_SCHED_STALENESS_MS_REALTIME", "77")
        settings = Settings.from_env()
        settings = settings.model_copy(
            update={"pipelines_dir": str(REPO / "pipelines")})
        assert settings.tpu.batch_deadline_ms == 11.5
        reg = PipelineRegistry(settings)
        try:
            assert reg.hub.deadline_ms == 11.5
            assert reg.hub.sched is not None
            assert reg.hub.sched.admit_util == 0.7
            assert reg.hub.sched.deadline_ms["batch"] == 40.0
            assert reg.hub.sched.staleness_ms["realtime"] == 77.0
            # the audited knob stays live with sched on: the standard
            # class inherits EVAM_BATCH_DEADLINE_MS unless
            # EVAM_SCHED_DEADLINE_MS_STANDARD overrides it
            assert reg.hub.sched.deadline_ms["standard"] == 11.5
            assert reg.sched_cfg is reg.hub.sched
        finally:
            reg.stop_all()
        # and the engine honors the hub's deadline verbatim
        eng = _toy_engine("deadline-pin", deadline_ms=11.5)
        try:
            assert eng.deadline_s == pytest.approx(0.0115)
        finally:
            eng.stop()

    def test_evam_sched_off_disables_layer(self, eight_devices,
                                           monkeypatch):
        from evam_tpu.config.settings import Settings
        from evam_tpu.server.registry import PipelineRegistry

        monkeypatch.setenv("EVAM_SCHED", "off")
        settings = Settings.from_env().model_copy(
            update={"pipelines_dir": str(REPO / "pipelines")})
        assert settings.sched.enabled is False
        reg = PipelineRegistry(settings)
        try:
            assert reg.hub.sched is None
            assert reg.sched_cfg.enabled is False
            # admission in disabled mode admits anything
            reg.admission.admit("batch", 1e9)
        finally:
            reg.stop_all()

    def test_supervised_rebuild_inherits_sched_config(self):
        """The factory closure carries the sched config, so a
        supervisor-rebuilt engine keeps its classes (and goes on
        validating the priority a submit names)."""
        from evam_tpu.engine.hub import EngineHub

        hub = EngineHub(registry=None, plan=None, max_batch=4,
                        sched=SchedConfig(), supervise=True,
                        stall_timeout_s=0)
        eng = hub._build("toy", lambda params, x: x + 1.0, None, ("x",))
        try:
            assert eng.sched is hub.sched  # delegated to live engine
            out = eng.submit(priority="realtime", x=_x(1.0)).result(
                timeout=60)
            np.testing.assert_allclose(out, np.full((2,), 2.0))
            rebuilt = eng._factory()
            try:
                assert rebuilt.sched is eng.sched
                with pytest.raises(ValueError, match="priority"):
                    rebuilt.submit(priority="turbo", x=_x())
            finally:
                rebuilt.stop()
        finally:
            eng.stop()
