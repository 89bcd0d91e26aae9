"""Fleet-scale serving (evam_tpu/fleet/, EVAM_FLEET=sharded).

Tier-1 coverage for the fleet tentpole: consistent-hash placement is
deterministic (same stream id → same shard across process restarts),
a degraded shard drains and rebalances with counters carried (the
PR-5 rebuild discipline one level up), a shard with no streams idles
cleanly, admission sums capacity across shards instead of treating
each chip as an independent bottleneck, and EVAM_FLEET=off stays
byte-identical at the STAGE level. The chip-loss path against real
supervised engines is tools/fleet_soak.py's job (slow battery)."""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np
import pytest

from evam_tpu.engine.batcher import BatchEngine, EngineStats
from evam_tpu.engine.ragged import consolidate_buckets
from evam_tpu.fleet import ConsistentHashPlacer, FleetEngine, fleet_mode
from evam_tpu.parallel.mesh import build_mesh
from evam_tpu.sched.admission import AdmissionController

MODEL = "object_detection/person_vehicle_bike"


# ---------------------------------------------------------- placer


class TestPlacer:
    def test_deterministic_across_instances(self):
        labels = [f"s{i}" for i in range(8)]
        a = ConsistentHashPlacer(labels)
        b = ConsistentHashPlacer(labels)
        keys = [f"cam{i}" for i in range(200)]
        assert [a.place(k) for k in keys] == [b.place(k) for k in keys]

    def test_spreads_streams(self):
        p = ConsistentHashPlacer([f"s{i}" for i in range(8)])
        hit = {p.place(f"cam{i}") for i in range(200)}
        assert len(hit) >= 6  # 200 keys must land on nearly every shard

    def test_down_shard_moves_only_its_streams(self):
        p = ConsistentHashPlacer([f"s{i}" for i in range(8)])
        keys = [f"cam{i}" for i in range(200)]
        before = {k: p.place(k) for k in keys}
        victim = before[keys[0]]
        p.mark_down(victim)
        after = {k: p.place(k) for k in keys}
        for k in keys:
            if before[k] == victim:
                assert after[k] != victim  # migrated off the dead chip
            else:
                assert after[k] == before[k]  # survivors undisturbed

    def test_no_live_shards_raises(self):
        p = ConsistentHashPlacer(["s0"])
        p.mark_down("s0")
        with pytest.raises(RuntimeError):
            p.place("cam")

    def test_add_moves_only_the_new_shards_streams(self):
        """Ring growth (scale_up satellite): adding a shard must equal
        a fresh ring built with it — and therefore move ONLY the
        streams whose arcs the new vnodes own."""
        grown = ConsistentHashPlacer([f"s{i}" for i in range(4)])
        keys = [f"cam{i}" for i in range(200)]
        before = {k: grown.place(k) for k in keys}
        grown.add("s4")
        fresh = ConsistentHashPlacer([f"s{i}" for i in range(5)])
        moved = 0
        for k in keys:
            assert grown.place(k) == fresh.place(k)
            if grown.place(k) != before[k]:
                assert grown.place(k) == "s4"  # moves only TO the new
                moved += 1
        assert 0 < moved < len(keys)

    def test_down_then_add_brings_streams_home(self):
        """A scale-down + later scale-up of the same label restores
        the original placement exactly — vnodes never left the ring,
        so returning streams land where they were."""
        p = ConsistentHashPlacer([f"s{i}" for i in range(4)])
        keys = [f"cam{i}" for i in range(100)]
        before = {k: p.place(k) for k in keys}
        p.mark_down("s2")
        p.add("s2")
        assert {k: p.place(k) for k in keys} == before

    def test_fleet_mode_validation(self, monkeypatch):
        assert fleet_mode("sharded") == "sharded"
        monkeypatch.setenv("EVAM_FLEET", "sharded")
        assert fleet_mode() == "sharded"
        monkeypatch.delenv("EVAM_FLEET")
        assert fleet_mode() == "off"
        with pytest.raises(ValueError):
            fleet_mode("cluster")


# ------------------------------------------------------ fleet engine


class _FakeShard:
    """Duck-typed shard: the engine surface FleetEngine aggregates."""

    def __init__(self, label):
        self.name = label
        self.state = "running"
        self.stats = EngineStats()
        self.warmed = threading.Event()
        self.warmed.set()
        self.stalled = threading.Event()
        self.restarts = 0
        self.streams_seen: list[str | None] = []
        self.stopped = False
        self._shed: dict[str, int] = {}

    def submit(self, priority="standard", units=None, stream=None,
               **inputs):
        if self.state == "degraded":
            raise RuntimeError(f"{self.name} degraded")
        self.streams_seen.append(stream)
        self.stats.batches += 1
        self.stats.items += 1
        fut: Future = Future()
        fut.set_result(np.zeros(1, np.float32))
        return fut

    def shed_counts(self):
        return dict(self._shed)

    def queue_depth(self):
        return 0

    def queue_age_s(self):
        return 0.0

    def class_depths(self):
        return {}

    def set_example(self, **example):
        pass

    def warm_async(self, **example):
        pass

    def abandon(self):
        pass

    def stop(self):
        self.stopped = True


def _fake_fleet(n=4, initial=0):
    plans = build_mesh().per_device_plans()[:n]
    shards: dict[str, _FakeShard] = {}

    def factory(plan, label):
        s = _FakeShard(label)
        shards[label.split("@")[-1]] = s
        return s

    eng = FleetEngine("detect:m", factory, plans, initial=initial)
    return eng, shards


class TestFleetEngine:
    def test_stream_pinned_to_one_shard(self):
        eng, shards = _fake_fleet()
        for _ in range(10):
            eng.submit(stream="camA", frames=np.zeros(1)).result()
        hit = [s for s in shards.values() if s.streams_seen]
        assert len(hit) == 1 and len(hit[0].streams_seen) == 10

    def test_placement_deterministic_across_restart(self):
        keys = [f"cam{i}" for i in range(50)]
        maps = []
        for _ in range(2):  # two "process lifetimes"
            eng, shards = _fake_fleet()
            for k in keys:
                eng.submit(stream=k, frames=np.zeros(1))
            maps.append({
                k: label for label, s in shards.items()
                for k in s.streams_seen})
        assert maps[0] == maps[1]

    def test_degraded_drain_rebalances_and_carries(self):
        """Satellite: the supervisor carry discipline across a
        PLACEMENT move — counters from the retired shard stay in the
        fleet aggregate, streams migrate, moves are counted."""
        eng, shards = _fake_fleet()
        eng.submit(stream="camA", frames=np.zeros(1))
        victim = next(s for s in shards.values() if s.streams_seen)
        victim.stats.batches = 7
        victim.stats.items = 7
        victim._shed["realtime"] = 3
        before = eng.stats.batches
        victim.state = "degraded"
        eng.submit(stream="camA", frames=np.zeros(1))  # sweeps + re-places
        survivor = next(
            s for s in shards.values()
            if s is not victim and s.streams_seen)
        assert survivor.streams_seen == ["camA"]
        assert eng.rebalances >= 1
        eng.drain_wait()
        assert victim.stopped  # drained: in-flight work resolved via stop
        # monotonic fleet-wide: retired shard's counters absorbed
        assert eng.stats.batches >= before
        assert eng.shed_counts().get("realtime", 0) == 3
        summary = eng.fleet_summary()
        assert summary["degraded_shards"] == 1
        assert summary["shards"] == len(shards) - 1
        assert summary["rebalances"] == eng.rebalances

    def test_state_ladder_and_all_degraded(self):
        eng, shards = _fake_fleet(n=2)
        assert eng.state == "running"
        for s in shards.values():
            s.state = "degraded"
        eng._sweep_degraded()
        assert eng.state == "degraded"
        with pytest.raises(RuntimeError):
            eng.submit(stream="camA", frames=np.zeros(1))

    def test_one_dead_chip_keeps_fleet_running(self):
        eng, shards = _fake_fleet(n=4)
        next(iter(shards.values())).state = "degraded"
        eng._sweep_degraded()
        assert eng.state == "running"  # /healthz must not 503 the pod


# ------------------------------------------------ elastic fleet (PR 18)


def _wait(predicate, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not predicate():
        if time.monotonic() >= deadline:
            return False
        time.sleep(0.01)
    return True


class TestScaleUp:
    def test_initial_builds_a_subset_of_the_plans(self):
        eng, shards = _fake_fleet(n=4, initial=2)
        assert sorted(shards) == ["s0", "s1"]
        summary = eng.fleet_summary()
        assert summary["shards"] == 2
        assert summary["max_shards"] == 4  # the structural ceiling

    def test_scale_up_joins_warm_and_rebalances_deterministically(self):
        eng, shards = _fake_fleet(n=4, initial=2)
        eng.set_example(frames=np.zeros(1))
        keys = [f"cam{i}" for i in range(40)]
        for k in keys:
            eng.submit(stream=k, frames=np.zeros(1))
        label = eng.scale_up()
        assert label == "s2"
        assert label in eng.shards  # joined the shard map AND ring
        # every pin matches the grown ring — the moved streams are
        # exactly the ones the new vnodes own, each move counted
        moved = 0
        for k in keys:
            assert eng._pins[k] == eng._placer.place(k)
            if eng._pins[k] == label:
                moved += 1
        assert moved > 0 and eng.rebalances >= moved
        summary = eng.fleet_summary()
        assert summary["shards"] == 3
        assert summary["scale_ups"] == 1
        assert eng._last_spinup_s >= 0.0

    def test_scale_up_refuses_at_plan_capacity(self):
        eng, shards = _fake_fleet(n=2, initial=2)
        assert eng.scale_up() is None
        assert eng.fleet_summary()["scale_ups"] == 0

    def test_scale_up_reuses_a_planned_retirement_slot(self):
        """scale_down retires a healthy chip — its label (and plan
        slot) must come back on the next grow, so the ring's vnodes
        bring its streams home (the placer determinism above)."""
        eng, shards = _fake_fleet(n=3, initial=3)
        retired = eng.scale_down()
        assert retired == "s2"
        assert eng.fleet_summary()["scale_downs"] == 1
        assert eng.scale_up() == "s2"
        assert eng.fleet_summary()["shards"] == 3

    def test_scale_up_never_reuses_a_dead_chip(self):
        eng, shards = _fake_fleet(n=3, initial=3)
        shards["s1"].state = "degraded"
        eng._sweep_degraded()  # chip loss: s1's plan index is dead
        assert eng.scale_up() is None  # s0/s2 live, s1 unusable
        summary = eng.fleet_summary()
        assert summary["degraded_shards"] == 1
        assert summary["max_shards"] == 2  # ceiling shrank with the chip

    def test_scale_up_warm_timeout_never_joins_cold(self):
        plans = build_mesh().per_device_plans()[:2]
        built: list[_FakeShard] = []

        def factory(plan, label):
            s = _FakeShard(label)
            if built:  # the grown shard never warms
                s.warmed.clear()
            built.append(s)
            return s

        eng = FleetEngine("detect:m", factory, plans, initial=1)
        eng.set_example(frames=np.zeros(1))
        assert eng.scale_up(warm_timeout_s=0.05) is None
        assert "s1" not in eng.shards
        assert eng.fleet_summary()["scale_ups"] == 0
        assert _wait(lambda: built[1].stopped)  # abandoned, not leaked

    def test_concurrent_scale_up_is_single_flight(self):
        eng, shards = _fake_fleet(n=4, initial=2)
        with eng._lock:
            eng._scaling = True
        assert eng.scale_up() is None
        with eng._lock:
            eng._scaling = False
        assert eng.scale_up() == "s2"

    def test_scale_up_checkpoints_moving_streams(self, monkeypatch):
        """The warm shard's first frame must see each migrated
        stream's gate/coaster/tracker state: the pre_rebalance barrier
        fires for every moving pin, tagged reason=scale_up."""
        from evam_tpu import state as ckpt
        from evam_tpu.config.settings import reset_settings

        monkeypatch.setenv("EVAM_CKPT", "1")
        reset_settings()
        ckpt.reset_cache()
        try:
            eng, shards = _fake_fleet(n=4, initial=2)
            eng.set_example(frames=np.zeros(1))
            keys = [f"cam{i}" for i in range(40)]
            for k in keys:
                eng.submit(stream=k, frames=np.zeros(1))
            captured: list[tuple[str, str]] = []
            store = ckpt.active()
            monkeypatch.setattr(
                store, "capture",
                lambda s, barrier="", reason="": captured.append(
                    (s, barrier, reason)))
            label = eng.scale_up()
            moved = [k for k in keys if eng._pins[k] == label]
            assert moved
            assert sorted(captured) == sorted(
                (k, "pre_rebalance", "scale_up") for k in moved)
        finally:
            ckpt.reset_cache()
            reset_settings()


# ------------------------------------------------- fleet admission


class TestFleetAdmission:
    def _ctrl(self, rows):
        hub = SimpleNamespace(stats=lambda: rows, max_batch=32,
                              sched=None)
        cfg = SimpleNamespace(enabled=True, admit_util=0.85,
                              capacity_fps=0)
        return AdmissionController(hub, cfg)

    def _row(self, group, fps_per_shard):
        # service 10 ms/batch, 10 items/batch → 1000 fps × scale
        return {
            "batches": 100, "items": fps_per_shard,
            "stage_ms": {"launch": 10.0}, "group": group,
        }

    def test_capacity_sums_shards_mins_groups(self):
        rows = {
            "detect:m@s0": self._row("detect:m", 1000),
            "detect:m@s1": self._row("detect:m", 1000),
            "classify:m": self._row("classify:m", 1500),
        }
        ctrl = self._ctrl(rows)
        # detect group: Σ shards = 2000 fps; classify: 1500 → min
        assert ctrl.capacity_fps() == pytest.approx(1500.0)

    def test_unsampled_live_shard_counts_as_its_siblings_mean(self):
        """A live shard no stream has hashed to since warmup has no
        clock sample; it runs the same program on the same kind of
        chip as its siblings, so it is their mean — not zero. On the
        four-chip v5e one sampled shard stood for its whole group
        (467 fps read for four chips)."""
        idle = dict(self._row("detect:m", 0), batches=0, stage_ms={},
                    shard="s2", state="running")
        rows = {
            "detect:m@s0": dict(self._row("detect:m", 1000), shard="s0"),
            "detect:m@s1": dict(self._row("detect:m", 600), shard="s1"),
            "detect:m@s2": idle,
        }
        assert self._ctrl(rows).capacity_fps() == pytest.approx(2400.0)
        # a shard that is not serving, the mesh twin and a plain
        # engine with no sample add nothing
        idle["state"] = "restarting"
        assert self._ctrl(rows).capacity_fps() == pytest.approx(1600.0)
        idle.update(state="running", shard="mesh")
        assert self._ctrl(rows).capacity_fps() == pytest.approx(1600.0)
        idle["shard"] = None
        assert self._ctrl(rows).capacity_fps() == pytest.approx(1600.0)

    def test_single_chip_rows_unchanged(self):
        rows = {
            "detect:m": self._row("detect:m", 1000),
            "classify:m": self._row("classify:m", 1500),
        }
        assert self._ctrl(rows).capacity_fps() == pytest.approx(1000.0)

    def test_rows_without_group_fall_back_to_key(self):
        rows = {
            "a": {"batches": 100, "items": 1000,
                  "stage_ms": {"launch": 10.0}},
        }
        assert self._ctrl(rows).capacity_fps() == pytest.approx(1000.0)


# ------------------------------------------- bucket-ladder alignment


class TestLadderAlignment:
    def test_align_rounds_kept_rungs_to_data_size(self):
        out = consolidate_buckets([8, 16, 32, 64, 100], align=8)
        assert 104 in out and 100 not in out
        assert all(b % 8 == 0 for b in out if b >= 8)

    def test_sub_align_rungs_left_alone(self):
        # fleet_local sub-data rungs dispatch single-device — rounding
        # them up to the data size would destroy the local buckets
        out = consolidate_buckets([1, 2, 4, 8, 16], align=8)
        assert out[0] == 1 and set(out) & {2, 4} == set(out) - {1, 8, 16}

    def test_align_one_is_legacy_behavior(self):
        ladder = [8, 16, 32, 64, 128]
        assert (consolidate_buckets(ladder)
                == consolidate_buckets(ladder, align=8))

    def test_engine_ladder_never_repads_sealed_block(self, eight_devices):
        """Regression (data=8, 100-row bucket): every rung the engine
        builds under a sharded plan must satisfy pad_batch(b) == b —
        otherwise every dispatch through that bucket re-pads the
        sealed staging block on the host."""
        plan = build_mesh()
        assert plan.data_size == 8
        eng = BatchEngine(
            "align-test", lambda params, frames: frames, params=None,
            plan=plan, max_batch=100, deadline_ms=1.0, ragged="packed")
        try:
            assert all(plan.pad_batch(b) == b for b in eng.buckets)
            assert eng.buckets[-1] == plan.pad_batch(100) == 104
        finally:
            eng.stop()


# -------------------------------------- real engines: off-path A/B


@pytest.fixture(scope="module")
def tiny_hubs(eight_devices):
    from evam_tpu.engine.hub import EngineHub
    from evam_tpu.models import ModelRegistry, ZOO_SPECS

    def make(fleet, plan):
        overrides = {k: (64, 64) for k in ZOO_SPECS}
        overrides["audio_detection/environment"] = (1, 1600)
        registry = ModelRegistry(
            dtype="float32", input_overrides=overrides,
            width_overrides={k: 8 for k in ZOO_SPECS})
        return EngineHub(registry, plan=plan, max_batch=8,
                         deadline_ms=2.0, supervise=False,
                         stall_timeout_s=0, fleet=fleet)

    fleet_hub = make("sharded", build_mesh(devices=eight_devices[:2]))
    off_hub = make("off", None)
    yield fleet_hub, off_hub
    fleet_hub.stop()
    off_hub.stop()


class TestRealEngines:
    def test_stage_level_byte_identity_off_vs_sharded(self, tiny_hubs,
                                                      monkeypatch):
        """EVAM_FLEET=off A/B at the stage level: the same frames
        through a real DetectStage produce identical regions whether
        the hub serves single-chip or fleet-sharded — placement must
        never change a number, only where it runs."""
        monkeypatch.setenv("EVAM_ALLOW_RANDOM_WEIGHTS", "1")
        from evam_tpu.stages.context import FrameContext
        from evam_tpu.stages.infer import DetectStage

        fleet_hub, off_hub = tiny_hubs
        rng = np.random.default_rng(3)
        frames = [rng.integers(0, 255, (96, 96, 3), np.uint8)
                  for _ in range(6)]

        def run(hub):
            stage = DetectStage("det", MODEL, {"threshold": 0.0}, hub)
            out = []
            for i, f in enumerate(frames):
                ctx = FrameContext(frame=f, pts_ns=i, seq=i,
                                   stream_id="cam0")
                fut = stage.submit(ctx)
                stage.complete(
                    ctx, fut.result(timeout=60) if fut is not None
                    else None)
                out.append([
                    (r.x0, r.y0, r.x1, r.y1, r.confidence, r.label_id)
                    for r in ctx.regions])
            return out

        assert run(fleet_hub) == run(off_hub)

    def test_zero_stream_shard_idles_cleanly(self, tiny_hubs,
                                             monkeypatch):
        monkeypatch.setenv("EVAM_ALLOW_RANDOM_WEIGHTS", "1")
        fleet_hub, _ = tiny_hubs
        eng = fleet_hub.engine("detect", MODEL)
        rows = fleet_hub.stats()
        shard_rows = {k: v for k, v in rows.items() if "@s" in k}
        assert len(shard_rows) == 2
        # one pinned stream -> exactly one shard carries the traffic,
        # the other idles at zero batches (and stop() in the fixture
        # teardown must join its threads cleanly)
        batches = {k: v["batches"] for k, v in shard_rows.items()}
        busy = [k for k, b in batches.items() if b > 0]
        idle = [k for k, b in batches.items() if b == 0]
        if not busy:  # stage test may have run first on this shard
            from evam_tpu.ops.color import wire_shape

            ws = tuple(wire_shape("i420", 64, 64))
            f = np.zeros(ws, np.uint8)
            for _ in range(3):
                eng.submit(stream="solo", frames=f).result(timeout=60)
            batches = {k: v["batches"]
                       for k, v in fleet_hub.stats().items()
                       if "@s" in k}
            busy = [k for k, b in batches.items() if b > 0]
            idle = [k for k, b in batches.items() if b == 0]
        assert len(busy) == 1
        assert len(idle) == 1
        # per-chip columns ride the rows (the /engines contract)
        for k, v in fleet_hub.stats().items():
            if "@s" in k:
                assert v["shard"] in ("s0", "s1")
                assert v["group"].startswith("detect:")
                assert v["device"]
