import threading
import time

import numpy as np
import pytest

from evam_tpu.engine import BatchEngine, EngineHub, DETECT_FIELDS
from evam_tpu.models import ModelRegistry, ZOO_SPECS
from evam_tpu.parallel import build_mesh

SMALL = {k: (64, 64) for k in ZOO_SPECS}
SMALL["audio_detection/environment"] = (1, 1600)
NARROW = {k: 8 for k in ZOO_SPECS}


@pytest.fixture(scope="module")
def hub(eight_devices):
    plan = build_mesh()  # 8 virtual CPU devices, data axis
    registry = ModelRegistry(dtype="float32", input_overrides=SMALL,
                             width_overrides=NARROW)
    # raw-BGR wire: these tests drive engines directly with [H,W,3] arrays
    hub = EngineHub(registry, plan=plan, max_batch=16, deadline_ms=5.0,
                    wire_format="bgr")
    yield hub
    hub.stop()


def test_mesh_has_8_devices(hub):
    assert hub.plan.data_size == 8
    assert hub.plan.pad_batch(3) == 8
    assert hub.plan.pad_batch(9) == 16


def test_detect_engine_single_item(hub):
    eng = hub.engine("detect", "object_detection/person_vehicle_bike")
    frame = np.random.default_rng(0).integers(0, 255, (64, 64, 3), np.uint8)
    out = eng.submit(frames=frame).result(timeout=60)
    assert out.shape == (32, DETECT_FIELDS)


def test_detect_engine_batches_across_streams(hub):
    eng = hub.engine("detect", "object_detection/person_vehicle_bike")
    rng = np.random.default_rng(1)
    futs = [
        eng.submit(frames=rng.integers(0, 255, (64, 64, 3), np.uint8))
        for _ in range(24)
    ]
    outs = [f.result(timeout=60) for f in futs]
    assert all(o.shape == (32, DETECT_FIELDS) for o in outs)
    # the engine should have formed multi-item batches, not 24 singles
    assert eng.stats.batches < 24


def test_engine_bucket_padding(hub):
    eng = hub.engine("detect", "object_detection/person_vehicle_bike")
    # buckets are multiples of the 8-device data axis
    assert eng.buckets[0] == 8
    assert eng._bucket(1) == 8
    assert eng._bucket(9) == 16
    assert eng._bucket(100) == 16  # capped at max_batch


def test_engine_sharing_by_instance_id(hub):
    a = hub.engine("detect", "object_detection/person_vehicle_bike", "shared-1")
    b = hub.engine("detect", "object_detection/person_vehicle_bike", "shared-1")
    c = hub.engine("detect", "object_detection/person_vehicle_bike", "other")
    assert a is b
    assert a is not c


def test_classify_engine_rois(hub):
    eng = hub.engine("classify", "object_classification/vehicle_attributes")
    frame = np.random.default_rng(2).integers(0, 255, (64, 64, 3), np.uint8)
    boxes = np.zeros((4, 4), np.float32)
    boxes[0] = [0.1, 0.1, 0.5, 0.5]
    out = eng.submit(frames=frame, boxes=boxes).result(timeout=60)
    assert out.shape == (4, 11)  # 7 colors + 4 types
    np.testing.assert_allclose(out[0, :7].sum(), 1.0, atol=1e-4)


def test_audio_engine(hub):
    eng = hub.engine("audio", "audio_detection/environment")
    window = (np.random.default_rng(3).normal(0, 8000, 1600)).astype(np.int16)
    out = eng.submit(windows=window).result(timeout=60)
    assert out.shape == (53,)
    np.testing.assert_allclose(out.sum(), 1.0, atol=1e-4)


def test_action_engines(hub):
    enc = hub.engine("action_encode", "action_recognition/encoder")
    dec = hub.engine("action_decode", "action_recognition/decoder")
    frame = np.random.default_rng(4).integers(0, 255, (64, 64, 3), np.uint8)
    emb = enc.submit(frames=frame).result(timeout=60)
    assert emb.shape == (512,)
    clip = np.stack([emb] * 16)
    probs = dec.submit(clips=clip).result(timeout=60)
    assert probs.shape == (400,)


def test_engine_concurrent_submitters(hub):
    eng = hub.engine("detect", "object_detection/person_vehicle_bike")
    errors = []
    results = []
    lock = threading.Lock()

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(5):
                out = eng.submit(
                    frames=rng.integers(0, 255, (64, 64, 3), np.uint8)
                ).result(timeout=60)
                with lock:
                    results.append(out)
        except Exception as exc:  # noqa: BLE001
            with lock:
                errors.append(exc)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(results) == 40


def test_engine_rejects_wrong_inputs(hub):
    eng = hub.engine("detect", "object_detection/person_vehicle_bike")
    with pytest.raises(ValueError):
        eng.submit(bogus=np.zeros((4, 4, 3), np.uint8))


def test_engine_stop_rejects_new_work():
    registry = ModelRegistry(dtype="float32", input_overrides=SMALL,
                             width_overrides=NARROW)
    eng = BatchEngine(
        "t", lambda p, x: x.sum(axis=(1, 2, 3)).astype(np.float32),
        params={}, max_batch=4, input_names=("x",),
    )
    out = eng.submit(x=np.ones((2, 2, 3), np.uint8)).result(timeout=30)
    assert float(out) == 12.0
    eng.stop()
    with pytest.raises(RuntimeError):
        eng.submit(x=np.ones((2, 2, 3), np.uint8))


@pytest.mark.parametrize("env, bound", [({}, 2),
                                        ({"EVAM_TRANSFER_DEPTH": "4"}, 4)])
def test_upload_queue_bound_is_the_setting(monkeypatch, env, bound):
    """The upload queue holds EVAM_TRANSFER_DEPTH staged batches, 2
    where it is not set: the setting reaches the engine through the
    hub, and nothing else decides it."""
    from evam_tpu.config.settings import Settings

    monkeypatch.delenv("EVAM_TRANSFER_DEPTH", raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    depth = Settings.from_env().tpu.transfer_depth
    h = EngineHub(registry=None, plan=None, max_batch=4, supervise=False,
                  stall_timeout_s=0, transfer_depth=depth)
    eng = h._build("depth", lambda params, x: x + 1.0, None, ("x",))
    try:
        assert eng.transfer_depth == bound
        assert eng._upload_q.maxsize == bound
    finally:
        eng.stop()


def test_hub_stats(hub):
    stats = hub.stats()
    det = stats["detect:object_detection/person_vehicle_bike"]
    assert det["items"] >= 25
    assert 0 < det["mean_occupancy"] <= 1.0
    # the host stage clock rides every engine's stats
    assert {"slot_write", "launch", "readback"} <= set(det["stage_ms"])
    # the /healthz aggregate: fixed keys, real time where work ran
    summary = hub.stage_summary()
    from evam_tpu.engine.ringbuf import STAGES
    assert set(summary) == set(STAGES)
    assert summary["launch"] > 0.0


def test_warm_async_precompiles_buckets(hub):
    import time

    model = hub.model("object_detection/person")
    engine = hub.engine("detect", "object_detection/person",
                        instance_id="warm-test")
    # hub fixture uses the raw-BGR wire
    h, w = model.preprocess.height, model.preprocess.width
    frame = np.zeros((h, w, 3), np.uint8)
    engine.warm_async(frames=frame)
    engine.warm_async(frames=frame)  # idempotent: second call no-ops
    assert engine.warmed.wait(timeout=180), "warmup did not finish"
    # warmed engine serves traffic normally
    out = engine.submit(frames=frame).result(timeout=60)
    assert out.shape[-1] == 7


class TestStallWatchdog:
    def test_wedged_step_fails_futures_and_flags_engine(self, monkeypatch):
        """A device call that never returns must not strand callers: the watchdog fails in-flight
        and queued futures with TimeoutError, flags the engine, and
        submit() starts rejecting. The wedge is injected with the
        `wedge` fault (obs/faults.py) — it blocks the launcher
        inside _launch exactly like a hung backend RPC — and hits a WARM
        bucket; a cold bucket's first batch gets the compile grace
        (test_first_batch_compile_grace below)."""
        from evam_tpu.engine.batcher import BatchEngine
        from evam_tpu.obs import faults

        eng = BatchEngine(
            "wedged", lambda p, frames: frames, params=None, max_batch=2,
            deadline_ms=1.0, stall_timeout_s=1.0,
        )
        try:
            # warm the bucket: compile + one healthy round-trip
            eng.submit(frames=np.zeros((2, 2), np.float32)).result(
                timeout=30)
            monkeypatch.setenv("EVAM_FAULT_INJECT",
                               "wedge=1,wedge_n=1,wedge_s=6")
            faults.reset_cache()
            f1 = eng.submit(frames=np.zeros((2, 2), np.float32))
            time.sleep(0.2)
            f2 = eng.submit(frames=np.zeros((2, 2), np.float32))
            with pytest.raises(TimeoutError):
                f1.result(timeout=10)
            with pytest.raises(TimeoutError):
                f2.result(timeout=10)
            assert eng.stalled.is_set()
            with pytest.raises(RuntimeError, match="stalled"):
                eng.submit(frames=np.zeros((2, 2), np.float32))
        finally:
            monkeypatch.setenv("EVAM_FAULT_INJECT", "")
            faults.reset_cache()
            # the launcher is mid-wedge: abandon (non-blocking)
            # instead of stop()'s joins
            eng.abandon()

    def test_wedge_fails_in_flight_uploaded_and_queued(self, monkeypatch):
        """The wedge holds the LAUNCHER; the dispatcher keeps staging
        and uploading behind it until the upload queue is full. When
        the watchdog fires, the stranded work sits in three places and
        all of it must fail with TimeoutError: the batch in flight
        (``_outstanding``), the batches uploaded but not launched
        (the upload queue, and the one the dispatcher holds while it
        waits to put it), and the items still in the class queues.
        Their staging blocks come back."""
        from evam_tpu.engine.batcher import BatchEngine
        from evam_tpu.obs import faults

        eng = BatchEngine(
            "wedged-3", lambda p, frames: frames, params=None,
            max_batch=1, deadline_ms=1.0, stall_timeout_s=1.0,
            staging_depth=4, transfer_depth=1,
        )
        one = np.zeros((2, 2), np.float32)
        try:
            eng.submit(frames=one).result(timeout=30)  # warm bucket 1
            monkeypatch.setenv("EVAM_FAULT_INJECT",
                               "wedge=1,wedge_n=1,wedge_s=3")
            faults.reset_cache()
            futs = [eng.submit(frames=one)]
            deadline = time.time() + 10
            while not eng._outstanding and time.time() < deadline:
                time.sleep(0.01)
            assert eng._outstanding  # batch 1 is in flight, wedged
            futs += [eng.submit(frames=one) for _ in range(5)]
            # batch 2 fills the upload queue, the dispatcher holds
            # batch 3 waiting to put it, the rest stay queued
            deadline = time.time() + 10
            while time.time() < deadline and not (
                    eng._upload_q.qsize() == 1
                    and eng.queue_depth() == 3):
                time.sleep(0.01)
            assert eng._upload_q.qsize() == 1
            assert eng.queue_depth() == 3
            for f in futs:
                with pytest.raises(TimeoutError):
                    f.result(timeout=10)
            assert eng.stalled.is_set()
            assert eng.queue_depth() == 0
            # every block but the in-flight batch's is free again
            # (the wedged launch still holds that one)
            deadline = time.time() + 5
            while (len(eng._ring._free) < 3
                   and time.time() < deadline):
                time.sleep(0.05)
            assert len(eng._ring._free) == 3
        finally:
            monkeypatch.setenv("EVAM_FAULT_INJECT", "")
            faults.reset_cache()
            # joins the launcher once its 3 s wedge has run out: no
            # thread of this engine outlives the test
            eng.stop()

    def test_first_batch_compile_grace(self, monkeypatch):
        """A cold bucket's first round-trip legitimately contains
        trace + compile: the watchdog must budget it at
        stall_timeout_s × first_batch_grace, or every supervisor
        rebuild (fresh jit by design) would flap back into quarantine
        on its first batch. Same slowness, two outcomes: absorbed on
        the cold bucket, a stall once the bucket is warm."""
        from evam_tpu.engine.batcher import BatchEngine
        from evam_tpu.obs import faults

        monkeypatch.setenv("EVAM_FAULT_INJECT",
                           "wedge=1,wedge_n=2,wedge_s=0.9")
        faults.reset_cache()
        eng = BatchEngine(
            "coldstart", lambda p, frames: frames, params=None,
            max_batch=2, deadline_ms=1.0, stall_timeout_s=0.3,
            first_batch_grace=10.0,
        )
        try:
            # wedge #1 rides the cold first batch: 0.9 s > the plain
            # 0.3 s budget but inside the 3 s grace — absorbed
            out = eng.submit(
                frames=np.zeros((2, 2), np.float32)).result(timeout=30)
            assert out.shape == (2, 2)
            assert not eng.stalled.is_set()
            # wedge #2 hits the now-warm bucket: plain budget → stall
            f = eng.submit(frames=np.zeros((2, 2), np.float32))
            with pytest.raises(TimeoutError):
                f.result(timeout=10)
            assert eng.stalled.is_set()
        finally:
            monkeypatch.setenv("EVAM_FAULT_INJECT", "")
            faults.reset_cache()
            eng.stop()

    def test_healthy_engine_never_trips_watchdog(self):
        from evam_tpu.engine.batcher import BatchEngine

        eng = BatchEngine(
            "healthy", lambda p, frames: frames * 2, params=None,
            max_batch=4, deadline_ms=1.0, stall_timeout_s=2.0,
        )
        try:
            futs = [eng.submit(frames=np.full((2,), float(i)))
                    for i in range(8)]
            for i, f in enumerate(futs):
                np.testing.assert_allclose(f.result(timeout=30), i * 2.0)
            assert not eng.stalled.is_set()
        finally:
            eng.stop()


class _Item:
    """What the ring needs of a work item: a failable future."""

    def __init__(self):
        from concurrent.futures import Future

        self.future = Future()


def _stage(ring, rows, bucket, name="x"):
    """Stage ``rows`` as one pick; returns (sealed, leftovers, items)."""
    from evam_tpu.obs.trace import StageClock

    staged = [({name: r}, _Item()) for r in rows]
    sealed, rest = ring.stage(staged, lambda n: bucket, StageClock())
    return sealed, rest, [it for _, it in staged]


class TestSlotAssembly:
    """Zero-copy staging path (engine/ringbuf.py): pre-allocated
    blocks reused across batches, zeroed pad tails, per-row shape
    checks, backpressure, and the per-batch stage clock."""

    @staticmethod
    def _echo_engine(**kw):
        from evam_tpu.engine.batcher import BatchEngine

        kw.setdefault("deadline_ms", 2.0)
        return BatchEngine(
            "slot-echo", lambda p, x: x.astype(np.float32), params=None,
            max_batch=8, input_names=("x",), **kw)

    def test_ring_seals_zeroed_tail_and_reuses_blocks(self):
        from evam_tpu.engine.ringbuf import SlotRing

        ring = SlotRing(capacity=8, depth=2)
        sealed, rest, items = _stage(
            ring, [np.full((4,), 1.0, np.float32)] * 6, bucket=8)
        assert rest == []
        assert sealed.n == 6 and sealed.bucket == 8
        assert sealed.items == items
        assert set(sealed.clock) == {"slot_write", "seal"}
        arr = sealed.arrays["x"]
        assert arr.shape == (8, 4)
        # the sealed batch is a VIEW of the staging block, not a copy
        assert arr.base is sealed.slot.arrays["x"]
        np.testing.assert_array_equal(arr[:6], 1.0)
        np.testing.assert_array_equal(arr[6:], 0.0)  # pad pre-zeroed
        allocs = ring.blocks_allocated
        ring.release(sealed)
        # exhaust every slot several times over: tails stay zero and
        # no block is EVER allocated again (buffer identity)
        for _ in range(6):
            s, _, _ = _stage(
                ring, [np.full((4,), 9.0, np.float32)] * 3, bucket=4)
            assert s.n == 3 and s.arrays["x"].shape == (4, 4)
            np.testing.assert_array_equal(s.arrays["x"][3:], 0.0)
            np.testing.assert_array_equal(s.arrays["x"][:3], 9.0)
            ring.release(s)
        assert ring.blocks_allocated == allocs

    def test_ring_bad_row_fails_only_its_own_future(self):
        """A row whose shape or dtype mismatches the ring fails ITS
        item's future; the survivors compact into contiguous rows, in
        order, and no future of theirs is touched."""
        from evam_tpu.engine.ringbuf import SlotRing

        ring = SlotRing(capacity=8, depth=2)
        rows = [np.full((4,), 1.0, np.float32),
                np.full((5,), 2.0, np.float32),   # wrong shape
                np.full((4,), 3.0, np.float32),
                np.full((4,), 4, np.int32),       # wrong dtype
                np.full((4,), 5.0, np.float32)]
        sealed, rest, items = _stage(ring, rows, bucket=4)
        assert rest == []
        assert sealed.n == 3
        assert sealed.items == [items[0], items[2], items[4]]
        np.testing.assert_array_equal(
            sealed.arrays["x"][:3, 0], [1.0, 3.0, 5.0])
        np.testing.assert_array_equal(sealed.arrays["x"][3:], 0.0)
        for i in (1, 3):
            with pytest.raises(ValueError, match="staging ring"):
                items[i].future.result(timeout=0)
        assert not any(items[i].future.done() for i in (0, 2, 4))
        ring.release(sealed)
        # no row survives: no batch, and the block goes straight back
        sealed, rest, items = _stage(
            ring, [np.zeros((5,), np.float32)] * 2, bucket=4)
        assert sealed is None and rest == []
        assert all(it.future.done() for it in items)
        assert len(ring._free) == 2

    def test_ring_hands_back_what_a_block_cannot_hold(self):
        """Rows past the block's capacity are not clamped: they come
        back, in order, for the caller to stage as the next batch."""
        from evam_tpu.engine.ringbuf import SlotRing

        ring = SlotRing(capacity=4, depth=2)
        rows = [np.full((2,), float(i), np.float32) for i in range(10)]
        sealed, rest, items = _stage(ring, rows, bucket=4)
        assert sealed.n == 4 and sealed.items == items[:4]
        assert [it for _, it in rest] == items[4:]
        np.testing.assert_array_equal(
            sealed.arrays["x"][:, 0], [0.0, 1.0, 2.0, 3.0])

    def test_ring_blocks_while_every_block_is_in_flight(self):
        """Host-side backpressure: with every block sealed and
        unreleased, ``stage()`` waits; a ``release()`` lets it
        through."""
        from evam_tpu.engine.ringbuf import SlotRing

        ring = SlotRing(capacity=2, depth=2)
        row = [np.ones((3,), np.float32)]
        a, _, _ = _stage(ring, row, bucket=1)
        b, _, _ = _stage(ring, row, bucket=1)
        got: list = []
        t = threading.Thread(
            target=lambda: got.append(_stage(ring, row, bucket=1)[0]))
        t.start()
        t.join(timeout=0.4)
        assert t.is_alive() and not got  # no free block: still waiting
        ring.release(a)
        t.join(timeout=10)
        assert not t.is_alive()
        assert got[0].slot is a.slot  # the released block, reused
        ring.release(b)

    def test_ring_raises_once_closed(self):
        """``close()`` wakes a dispatcher waiting for a block and
        every later ``stage()`` raises; the staged items' futures are
        the caller's to fail (none is touched here)."""
        from evam_tpu.engine.ringbuf import SlotRing

        ring = SlotRing(capacity=2, depth=2)
        row = [np.ones((3,), np.float32)]
        _stage(ring, row, bucket=1)
        _stage(ring, row, bucket=1)
        raised: list = []

        def waiter():
            try:
                _stage(ring, row, bucket=1)
            except RuntimeError as exc:
                raised.append(exc)

        t = threading.Thread(target=waiter)
        t.start()
        t.join(timeout=0.3)
        assert t.is_alive()
        ring.close()
        t.join(timeout=10)
        assert not t.is_alive() and len(raised) == 1
        with pytest.raises(RuntimeError, match="closed"):
            _stage(ring, row, bucket=1)

    def test_no_per_batch_allocation_at_steady_state(self):
        eng = self._echo_engine()
        try:
            futs = [eng.submit(x=np.full((3, 3), float(i), np.float32))
                    for i in range(20)]
            for f in futs:
                f.result(timeout=30)
            ring = eng._ring
            allocs = ring.blocks_allocated
            ids0 = {id(s.arrays["x"]) for s in list(ring._free)}
            futs = [eng.submit(x=np.full((3, 3), float(i), np.float32))
                    for i in range(40)]
            for f in futs:
                f.result(timeout=30)
            # block count AND identities are steady — the engine
            # never allocates a staging buffer after the first batch
            assert ring.blocks_allocated == allocs
            import time as _time
            deadline = _time.time() + 10
            while _time.time() < deadline:
                free_ids = {id(s.arrays["x"]) for s in list(ring._free)}
                if free_ids >= ids0:
                    break
                _time.sleep(0.05)
            assert free_ids >= ids0
        finally:
            eng.stop()

    def test_concurrent_submitters_never_interleave_rows(self):
        eng = self._echo_engine(deadline_ms=3.0)
        errors: list = []

        def worker(v: int):
            try:
                for k in range(10):
                    val = float(v * 100 + k)
                    out = eng.submit(
                        x=np.full((6,), val, np.float32)).result(timeout=30)
                    # every element of the returned row must be THIS
                    # submitter's value — an interleaved slot write
                    # would mix another thread's row in
                    assert out.shape == (6,)
                    assert np.all(out == val), (val, out)
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        eng.stop()
        assert not errors, errors

    def test_stage_clock_reconciles_with_wall_time(self):
        from evam_tpu.engine.ringbuf import STAGES

        eng = self._echo_engine()
        try:
            t0 = time.perf_counter()
            futs = [eng.submit(x=np.full((4,), float(i), np.float32))
                    for i in range(30)]
            for f in futs:
                f.result(timeout=30)
            elapsed = time.perf_counter() - t0
            st = eng.stats
            assert st.batches > 0
            # every pipeline stage was clocked
            assert set(st.stage_seconds) == set(STAGES)
            assert all(v >= 0.0 for v in st.stage_seconds.values())
            # work stages reconcile with wall time: the engine runs 3
            # threads (submitter copies ride the callers), so summed
            # per-stage work can't exceed elapsed × thread count;
            # submit_wait additionally contains the deadline waits
            work = sum(v for k, v in st.stage_seconds.items()
                       if k != "submit_wait")
            assert 0.0 < work <= elapsed * 4.0, (work, elapsed)
            ms = st.stage_ms_per_batch()
            assert set(ms) == set(STAGES)
        finally:
            eng.stop()

    def test_stage_clock_is_steady_state_only(self, monkeypatch):
        """The clock is the service-time signal admission derives
        capacity from, so ONE rule leaves out what only a start-up
        pays: a batch is ``unclocked`` when its bucket is cold (its
        launch is the compile, banked as compile_seconds) or while
        ANY engine's background warmup runs in the process (it shares
        the host and the chip with the compiler). On the v5e the
        unfiltered clock read a cold start as 99-321 fps of capacity,
        and a per-engine flag let one warm shard clock 24 batches
        while seven others compiled beside it (467 fps for four
        chips)."""
        from evam_tpu.engine import batcher
        from evam_tpu.engine.ringbuf import STAGES

        def one(eng):
            return eng.submit(
                x=np.ones((4,), np.float32)).result(timeout=30)

        eng = self._echo_engine()
        try:
            one(eng)  # bucket 1's first batch: cold
            st = eng.stats
            assert st.batches == 1 and st.unclocked == 1
            assert st.stage_seconds == {} and st.stage_ms_per_batch() == {}
            assert st.compile_seconds > 0.0
            # some OTHER engine's background warmup is compiling
            monkeypatch.setattr(batcher, "_warmups_running", 1)
            one(eng)
            assert eng.stats.unclocked == 2 and eng.stats.clocked == 0
            monkeypatch.setattr(batcher, "_warmups_running", 0)
            one(eng)  # warm bucket, nothing compiling: a sample
            assert eng.stats.clocked == 1
            assert set(eng.stats.stage_seconds) == set(STAGES)
            assert set(eng.stats.stage_ms_per_batch()) == set(STAGES)
        finally:
            eng.stop()

    def test_background_warmup_is_counted_process_wide(self):
        """warm_async holds the process-wide warmup count for as long
        as its thread compiles, and gives it back on failure too."""
        from evam_tpu.engine import batcher

        eng = self._echo_engine()
        try:
            before = batcher._warmups_running
            eng.warm_async(x=np.ones((4,), np.float32))
            assert eng.warmed.wait(60)
            assert batcher._warmups_running == before
            assert eng.warm_error is None
        finally:
            eng.stop()

    def test_mismatched_shape_fails_its_future_not_its_batch(self):
        """Through the engine: the ring's shapes are pinned by the
        first batch; a later submit of another shape fails ITS future
        (at the dispatcher, not at submit) while the rows that share
        its batch resolve to their own values."""
        eng = self._echo_engine(deadline_ms=200.0)
        try:
            eng.submit(x=np.zeros((4,), np.float32)).result(timeout=30)
            good = [eng.submit(x=np.full((4,), float(i), np.float32))
                    for i in range(3)]
            bad = eng.submit(x=np.zeros((5,), np.float32))
            good += [eng.submit(x=np.full((4,), float(i), np.float32))
                     for i in range(3, 7)]  # the 8th fills the batch
            with pytest.raises(ValueError, match="staging ring"):
                bad.result(timeout=30)
            for i, f in enumerate(good):
                np.testing.assert_array_equal(
                    f.result(timeout=30), np.full((4,), float(i)))
            # one pick of 8, one batch of its 7 survivors
            assert eng.stats.batches == 2 and eng.stats.items == 8
        finally:
            eng.stop()

    def test_dense_pick_past_the_top_bucket_splits_in_order(self):
        """A pick larger than the top bucket's rows is split across
        batches in dispatch order and counted, never clamped. (The
        dense path cannot form one by itself — ``max_batch`` never
        exceeds the top bucket — so the cap is lifted under a built
        engine; the packed path reaches the same split through its
        unit rows, tests/test_ragged.py.)"""
        from evam_tpu.engine.batcher import BatchEngine
        from evam_tpu.obs.metrics import metrics

        eng = BatchEngine(
            "slot-split", lambda p, x: x.astype(np.float32),
            params=None, max_batch=4, deadline_ms=2.0,
            input_names=("x",))
        assert eng.buckets[-1] == 4 and eng._ring.capacity == 4
        eng.max_batch = 16
        gate, entered = threading.Event(), threading.Event()
        batches: list[list] = []
        orig = eng._dispatch_batch

        def gated(sealed):
            batches.append([it.future for it in sealed.items])
            entered.set()
            gate.wait(timeout=60)
            return orig(sealed)

        eng._dispatch_batch = gated
        split0 = metrics.get_counter(
            "evam_engine_oversize_splits", labels={"engine": "slot-split"})
        try:
            first = eng.submit(x=np.full((3,), -1.0, np.float32))
            assert entered.wait(timeout=30)  # dispatcher parked
            futs = [eng.submit(x=np.full((3,), float(i), np.float32))
                    for i in range(10)]
            gate.set()
            for i, f in enumerate(futs):
                np.testing.assert_array_equal(
                    f.result(timeout=30), np.full((3,), float(i)))
            # ONE pick of ten against a four-row block: 4 + 4 + 2
            assert batches == [[first], futs[:4], futs[4:8], futs[8:]]
            assert eng.stats.oversize_splits == 2
            assert metrics.get_counter(
                "evam_engine_oversize_splits",
                labels={"engine": "slot-split"}) == split0 + 2
        finally:
            gate.set()
            eng.stop()
