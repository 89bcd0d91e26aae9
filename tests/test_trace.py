"""Tier-1 contract tests for the per-frame tracing layer
(evam_tpu/obs/trace.py): stage vocabulary pinned to the engine's
ring-buffer clock, span-tree completeness through the real serving
path, batch↔frame linkage, tail-based retention, the EVAM_TRACE=off
no-op guarantee, the bounded ring, the quarantine flight recorder's
JSONL shape, the Chrome trace-event renderer (tools/trace_dump.py),
the OpenMetrics exemplar on the latency p99 line, batch records as
timelines, the waits between the layers and the freeze recorder."""

from __future__ import annotations

import dataclasses
import functools
import gc
import json
import signal
import sys
import threading
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

from evam_tpu.config.settings import Settings, reset_settings
from evam_tpu.engine import ringbuf
from evam_tpu.models import ZOO_SPECS
from evam_tpu.obs import trace
from evam_tpu.obs.metrics import metrics

_KNOBS = ("EVAM_TRACE", "EVAM_TRACE_SAMPLE_N", "EVAM_TRACE_RING",
          "EVAM_TRACE_SLOW_MS", "EVAM_TRACE_FLIGHT_DIR",
          "EVAM_TRACE_FLIGHT_N", "EVAM_TRACE_FLIGHT_MAX_FILES",
          "EVAM_TRACE_FLIGHT_MAX_BYTES")


def _fresh(monkeypatch, **env: str) -> None:
    """Reset the memoized ring under a controlled EVAM_TRACE* env.
    The autouse conftest fixture restores the memo on teardown."""
    for k in _KNOBS:
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    reset_settings()
    trace.reset_cache()


def _time_limit(seconds: float):
    """A time limit of the test's own (no pytest-timeout here): the
    alarm raises in the main thread, where pytest runs the test."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*a, **k):
            def on_alarm(signum, frame):
                raise TimeoutError(f"{fn.__name__} passed {seconds} s")
            old = signal.signal(signal.SIGALRM, on_alarm)
            signal.setitimer(signal.ITIMER_REAL, seconds)
            try:
                return fn(*a, **k)
            finally:
                signal.setitimer(signal.ITIMER_REAL, 0)
                signal.signal(signal.SIGALRM, old)
        return run
    return wrap


def _timeline(stages=trace.STAGE_ORDER[1:], t0=100.0, dur=0.001):
    """A clock whose stages follow each other from ``t0``."""
    clock = trace.StageClock()
    clock["submit_wait"] = dur
    for s in stages:
        clock.mark(s, t0, dur)
        t0 += dur
    return clock


def test_stage_order_pins_engine_clock():
    # the span vocabulary IS the engine's per-batch stage clock — a
    # stage added/renamed in ringbuf.STAGES must update the tracer's
    # rendering + last_stage attribution in the same change
    assert trace.STAGE_ORDER == ringbuf.STAGES


def test_tail_sampling_retention():
    ring = trace.TraceRing(sample_n=10_000, slow_ms=1e9)
    ok = ring.mint("s", 0, "standard")
    ring.finish(ok, "ok")                 # healthy, not the 1-in-N tick
    shed = ring.mint("s", 1, "realtime")
    ring.finish(shed, "shed")             # always retained
    err = ring.mint("s", 2, "standard")
    ring.finish(err, "error")             # always retained
    frames, _, _ = ring.snapshot()
    assert [f.status for f in frames] == ["shed", "error"]
    assert ring.retained_count == 2 and ring.dropped_count == 1

    slow = trace.TraceRing(sample_n=10_000, slow_ms=0.0)
    ft = slow.mint("s", 0, "standard")
    slow.finish(ft, "ok")                 # dur >= slow_ms → slow tail
    frames, _, _ = slow.snapshot()
    assert [f.status for f in frames] == ["ok"]


def test_fanout_children_share_one_retention_decision():
    ring = trace.TraceRing(sample_n=1)
    ft = ring.mint("s", 0, "standard")
    ring.finish(ft, "ok")
    ring.finish(ft, "error")  # late fan-out sibling: no double count
    assert ring.retained_count == 1
    assert ft.status == "ok"


def test_ring_stays_bounded():
    ring = trace.TraceRing(sample_n=1, ring=8)
    for i in range(100):
        ring.finish(ring.mint("s", i, "standard"), "ok")
    frames, _, _ = ring.snapshot()
    assert len(frames) == 8
    assert ring.retained_count == 100
    assert [f.seq for f in frames] == list(range(92, 100))


def test_trace_off_is_noop(monkeypatch):
    _fresh(monkeypatch, EVAM_TRACE="off")
    assert trace.active() is None
    assert trace.start_frame("s", 0) is None
    trace.finish_frame(None)              # must not raise
    trace.batch_begin("e", 0, (), 4, 1, {})
    trace.batch_complete("e", 0)
    assert trace.traces_payload() == {
        "enabled": False, "retained": 0, "dropped": 0,
        "frames": 0, "batches": 0, "pending": 0, "traceEvents": [],
    }
    assert trace.flight_dump("e", "test") is None


def test_batch_links_frames_and_chrome_rendering(monkeypatch):
    _fresh(monkeypatch, EVAM_TRACE_SAMPLE_N="1")
    ring = trace.active()

    class _Item:
        def __init__(self, ft):
            self.trace = ft
            self.t_submit = ft.t0
            self.priority = ft.priority

    fts = [trace.start_frame("cam0", i, "realtime") for i in range(3)]
    items = [_Item(ft) for ft in fts]
    clock = _timeline(trace.STAGE_ORDER[1:-2])
    trace.batch_begin("det", 7, items, bucket=4, n=3, clock=clock,
                      device="cpu:0")
    # the completer appends its spans, with a named wait before them
    clock.wait("wait_completer", 100.0065)
    clock.span("readback", 100.0065, 0.002)
    clock.span("resolve", 100.0085, 0.001)
    trace.batch_complete("det", 7, items)
    for ft in fts:
        trace.finish_frame(ft, "ok")
        assert ft.bids == ["det#7"]
        names = [s[0] for s in ft.spans]
        assert "sched.queue_wait" in names
        assert "engine.dispatch" in names
        # the batch's spans at their real starts, not laid end to end
        assert ("engine.readback", 100.0065, 0.002, None) in ft.spans

    payload = trace.traces_payload()
    assert payload["enabled"] and payload["frames"] == 3
    assert payload["batches"] == 1 and payload["pending"] == 0
    batch_ev = [e for e in payload["traceEvents"] if e["cat"] == "batch"]
    assert len(batch_ev) == 1
    args = batch_ev[0]["args"]
    # the batch↔frame link: one batch span naming >= 2 member frame
    # trace ids, with the full stage clock attributed
    assert args["frames"] == [ft.trace_id for ft in fts]
    assert args["stages"] == [*trace.STAGE_ORDER[1:-2], "wait_completer",
                              "readback", "resolve"]
    assert args["last_stage"] == "resolve"
    assert batch_ev[0]["ts"] == 100.0 * 1e6  # where its first span starts
    stage_ev = [e for e in payload["traceEvents"]
                if e["cat"] == "batch-stage"]
    assert [e["name"] for e in stage_ev] == list(trace.STAGE_ORDER[1:])
    assert stage_ev[-2]["ts"] == round(100.0065 * 1e6, 1)
    wait_ev = [e for e in payload["traceEvents"] if e["cat"] == "batch-wait"]
    assert [e["name"] for e in wait_ev] == ["wait_completer"]

    import trace_dump
    doc = trace_dump.convert(payload)
    assert doc["displayTimeUnit"] == "ms"
    assert trace_dump.linked_batches(doc["traceEvents"]) == 1


def test_wedged_batch_last_stage(monkeypatch):
    _fresh(monkeypatch)
    clock = _timeline(("slot_write", "seal"))
    trace.batch_begin("det", 3, (), bucket=8, n=2, clock=clock)
    clock.mark("h2d_issue", 100.002, 0.004)  # AFTER begin: still visible
    clock.wait("wait_launcher", 100.01)      # a wait is not a stage
    _, _, pending = trace.active().snapshot()
    assert trace.last_stage(trace._clock_spans(pending[0]["clock"])) \
        == "h2d_issue"


def test_flight_dump_shape(monkeypatch, tmp_path):
    _fresh(monkeypatch, EVAM_TRACE_FLIGHT_DIR=str(tmp_path),
           EVAM_TRACE_SAMPLE_N="1")
    ft = trace.start_frame("cam0", 0, "standard")
    trace.finish_frame(ft, "error")
    clock = _timeline(("slot_write", "seal", "h2d_issue"))
    trace.batch_begin("det", 11, (), bucket=8, n=2, clock=clock)
    path = trace.flight_dump("det", "stall watchdog",
                             state={"queue_depth": 5})
    assert path is not None and Path(path).parent == tmp_path
    rows = [json.loads(l) for l in
            Path(path).read_text().splitlines() if l.strip()]
    header = rows[0]
    assert header["type"] == "flight" and header["engine"] == "det"
    assert isinstance(header["profiler_running"], bool)
    assert header["state"] == {"queue_depth": 5}
    batch = [r for r in rows if r["type"] == "batch"]
    assert len(batch) == 1 and batch[0]["pending"] is True
    assert batch[0]["last_stage"] == "h2d_issue"
    assert [sp[0] for sp in batch[0]["spans"]] == [
        "slot_write", "seal", "h2d_issue"]
    assert "clock" not in batch[0]
    frame = [r for r in rows if r["type"] == "frame"]
    assert len(frame) == 1 and frame[0]["status"] == "error"

    # the flight artifact renders to Chrome events too
    import trace_dump
    events = trace_dump.events_from_flight(rows)
    assert any(e["cat"] == "batch" for e in events)


def test_flight_dir_rotation_pins_file_cap(monkeypatch, tmp_path):
    """A flapping engine must not grow the flight dir without bound:
    after every dump the oldest flight-*.jsonl rotate out past
    EVAM_TRACE_FLIGHT_MAX_FILES, and the just-written dump always
    survives."""
    _fresh(monkeypatch, EVAM_TRACE_FLIGHT_DIR=str(tmp_path),
           EVAM_TRACE_FLIGHT_MAX_FILES="3",
           EVAM_TRACE_FLIGHT_MAX_BYTES="0")
    paths = [trace.flight_dump("det", f"flap {i}") for i in range(6)]
    assert all(p is not None for p in paths)
    kept = sorted(tmp_path.glob("flight-*.jsonl"))
    assert len(kept) == 3
    assert Path(paths[-1]) in kept          # freshest dump survives
    assert Path(paths[0]) not in kept       # oldest rotated out
    # an unrelated artifact in the dir is never touched
    stray = tmp_path / "notes.txt"
    stray.write_text("keep me")
    trace.flight_dump("det", "flap 6")
    assert stray.exists()
    assert len(list(tmp_path.glob("flight-*.jsonl"))) == 3


def test_flight_dir_rotation_pins_byte_cap(monkeypatch, tmp_path):
    _fresh(monkeypatch, EVAM_TRACE_FLIGHT_DIR=str(tmp_path),
           EVAM_TRACE_FLIGHT_MAX_FILES="0",
           EVAM_TRACE_FLIGHT_MAX_BYTES="1")
    # every dump is bigger than 1 byte, so each write prunes all
    # older dumps — but never the file it just wrote
    paths = [trace.flight_dump("det", f"flap {i}") for i in range(4)]
    kept = list(tmp_path.glob("flight-*.jsonl"))
    assert [str(p) for p in kept] == [paths[-1]]


def test_flight_dir_rotation_zero_is_unbounded(monkeypatch, tmp_path):
    _fresh(monkeypatch, EVAM_TRACE_FLIGHT_DIR=str(tmp_path),
           EVAM_TRACE_FLIGHT_MAX_FILES="0",
           EVAM_TRACE_FLIGHT_MAX_BYTES="0")
    for i in range(8):
        trace.flight_dump("det", f"flap {i}")
    assert len(list(tmp_path.glob("flight-*.jsonl"))) == 8


def test_runner_backdates_decode_span(monkeypatch):
    """StreamRunner.feed mints the trace and backdates a ``decode``
    span from the event's host decode cost, then wraps every sync
    stage in a ``stage.<name>`` span and finishes ``ok``."""
    import numpy as np

    from evam_tpu.media.source import FrameEvent
    from evam_tpu.stages.runner import StreamRunner

    class _Passthrough:
        name = "resize"
        is_async = False

        def process(self, ctx):
            return [ctx]

    _fresh(monkeypatch, EVAM_TRACE_SAMPLE_N="1")
    runner = StreamRunner("cam0", [_Passthrough()])
    ev = FrameEvent(frame=np.zeros((8, 8, 3), np.uint8), pts_ns=0,
                    seq=0, decode_s=0.005)
    runner.feed(ev)
    runner.drain()
    frames, _, _ = trace.active().snapshot()
    assert len(frames) == 1 and frames[0].status == "ok"
    spans = frames[0].spans
    assert [s[0] for s in spans] == ["decode", "stage.resize"]
    dec_t0, dec_dur = spans[0][1], spans[0][2]
    assert dec_dur == 0.005
    assert dec_t0 <= spans[1][1]  # decode precedes the chain


def test_exemplar_on_p99_line(monkeypatch):
    _fresh(monkeypatch)
    # the latency histogram is process-global and other tests land
    # their own exemplars; the renderer surfaces the SLOWEST recorded
    # pair, so observe one slower than any plausible real latency
    trace.observe_frame_latency("cam0", 86400.0, priority="realtime",
                                trace_id="evam-test-42")
    out = metrics.render()
    p99 = [l for l in out.splitlines()
           if l.startswith("evam_frame_latency_seconds{")
           and 'quantile="0.99"' in l and "class" not in l]
    assert p99 and '# {trace_id="evam-test-42"} 86400.0' in p99[0]


def test_span_tree_through_serving_path(monkeypatch, eight_devices):
    """End-to-end: a synthetic stream through PipelineRegistry →
    StreamRunner → shared BatchEngine leaves complete span trees —
    decode, per-stage, queue-wait and dispatch — all linked to the
    batch records that served them."""
    from evam_tpu.engine import EngineHub
    from evam_tpu.models import ModelRegistry
    from evam_tpu.parallel import build_mesh
    from evam_tpu.server.registry import PipelineRegistry

    _fresh(monkeypatch, EVAM_TRACE_SAMPLE_N="1")
    small = {k: (64, 64) for k in ZOO_SPECS}
    small["audio_detection/environment"] = (1, 1600)
    narrow = {k: 8 for k in ZOO_SPECS}
    settings = Settings(pipelines_dir=str(REPO / "pipelines"))
    hub = EngineHub(
        ModelRegistry(dtype="float32", input_overrides=small,
                      width_overrides=narrow),
        plan=build_mesh(), max_batch=8, deadline_ms=4.0)
    registry = PipelineRegistry(settings, hub=hub)
    try:
        inst = registry.start_instance(
            "object_detection", "person_vehicle_bike",
            {"source": {"uri": "synthetic://96x96@30?count=12&seed=1",
                        "type": "uri"},
             "destination": {"metadata": {"type": "null"}}})
        inst.wait(timeout=180)
        assert inst.state.value == "COMPLETED", inst.error
    finally:
        registry.stop_all()

    frames, batches, pending = trace.active().snapshot()
    done = [f for f in frames if f.status == "ok" and f.bids]
    assert done, [f.to_dict() for f in frames]
    ft = done[-1]
    names = [s[0] for s in ft.spans]
    assert any(n.startswith("stage.") for n in names)
    assert "sched.queue_wait" in names and "engine.dispatch" in names
    # every bid a frame carries resolves to a recorded batch that
    # names the frame back — the link is bidirectional
    by_bid = {f"{r['engine']}#{r['bid']}": r for r in batches + pending}
    for bid in ft.bids:
        assert ft.trace_id in by_bid[bid]["frames"]
    served = by_bid[ft.bids[0]]
    assert served["spans"], served
    assert trace.last_stage(served["spans"]) == "resolve"
    # spans nest inside the frame's lifetime, orderable for rendering
    t_end = time.perf_counter()
    for (_, t0, dur, _) in ft.spans:
        assert ft.t0 - 1.0 <= t0 <= t_end and 0.0 <= dur < 300.0


# -- the freeze recorder --------------------------------------------------

class _StubEngine:
    """What the recorder reads of an engine."""
    def __init__(self, age: float, name: str = "det") -> None:
        self.age = age
        self.name = name

    def queue_age_s(self) -> float:
        return self.age

    def thread_states(self) -> dict:
        return {"dispatch": ("h2d_issue", self.age)}


def _hold_the_gil_for_a_while(then_wait: threading.Event):
    """The function a freeze dump has to name: a C call that keeps the
    GIL (``PyDLL`` does not release it) for 1.5 s. The thread lives on
    afterwards, as a server's do, so the dump can name it."""
    import ctypes

    ctypes.PyDLL(None).usleep(1_500_000)
    then_wait.wait(30)


@_time_limit(60)
def test_freeze_recorder_names_a_held_gil(monkeypatch, tmp_path):
    _fresh(monkeypatch, EVAM_TRACE_FLIGHT_DIR=str(tmp_path))
    before = metrics.render()
    rec = trace.start_freeze_recorder()
    try:
        assert rec is not None and trace.start_freeze_recorder() is rec
        assert any(t.name == "evam-heartbeat" for t in threading.enumerate())

        eng = _StubEngine(0.5)
        trace.watch_engine(eng)
        time.sleep(0.6)  # a few quiet beats first
        done = threading.Event()
        holder = threading.Thread(target=_hold_the_gil_for_a_while,
                                  args=(done,), name="gil-holder")
        holder.start()
        deadline = time.time() + 10
        while not list(tmp_path.glob("flight-process-*.jsonl")) \
                and time.time() < deadline:
            time.sleep(0.05)
        done.set()
        holder.join()
    finally:
        trace.stop_freeze_recorder()
    assert not any(t.name == "evam-heartbeat" for t in threading.enumerate())
    assert rec._on_gc not in gc.callbacks

    def count(text):
        return float(next(l for l in text.splitlines() if l.startswith(
            "evam_freeze_seconds_count")).split()[-1]) \
            if "evam_freeze_seconds_count" in text else 0.0
    assert count(metrics.render()) >= count(before) + 1

    dumps = list(tmp_path.glob("flight-process-*.jsonl"))
    assert len(dumps) == 1, dumps
    header = json.loads(dumps[0].read_text().splitlines()[0])
    assert header["reason"] == "freeze" and header["engine"] == "process"
    state = header["state"]
    assert 1.0 <= state["late_s"] < 3.0
    # the heartbeat spun on the GIL while it was late: the interpreter
    # was held, the process was not stopped; the stacks name the holder
    assert state["verdict"] == "gil_held", state
    assert state["gil_wait_cpu_s"] >= 0.001 * state["late_s"]
    # (taken right after the wake: the holder has moved on inside it)
    assert "_hold_the_gil_for_a_while" in state["stacks"]
    assert "gil-holder" in state["top_frames"]
    assert state["queue_age_s"]["det"] == 0.5
    assert state["threads"]["det"]["dispatch"] == ["h2d_issue", 0.5]
    assert isinstance(state["gc"], list)
    assert "_hold_the_gil_for_a_while" in (
        tmp_path / "freeze-stacks.log").read_text()


@pytest.mark.parametrize("spin_s,gc_s,verdict", [
    (0.0, 0.0, "process_stopped"),   # late, and never asked for the GIL
    (0.008, 1.6, "gil_held_by_gc"),  # a collection covers the lateness
    (0.008, 0.0, "gil_held"),
])
def test_freeze_verdicts(monkeypatch, tmp_path, spin_s, gc_s, verdict):
    _fresh(monkeypatch, EVAM_TRACE_FLIGHT_DIR=str(tmp_path))
    rec = trace.FreezeRecorder(str(tmp_path))
    rec._fh = open(rec.path, "a", encoding="utf-8")
    t0 = time.perf_counter()
    if gc_s:
        rec._gc_slow.append((t0 + 0.1, gc_s, 2))
    rec._on_late(2.0, t0, spin_s)
    rec._fh.close()
    (dump,) = tmp_path.glob("flight-process-*.jsonl")
    state = json.loads(dump.read_text().splitlines()[0])["state"]
    assert state["verdict"] == verdict
    assert [g["gen"] for g in state["gc"]] == ([2] if gc_s else [])
    assert "test_freeze_verdicts" in state["stacks"]


def test_no_annotation_object_outside_a_capture(monkeypatch):
    _fresh(monkeypatch)
    spans = trace.thread_spans("det", "dispatch", cpu=True)
    spans.to("slot_write")
    assert spans._ann is None and spans.where()[0] == "slot_write"
    assert trace.annotate("evam.runner.wire") is trace._NO_ANNOTATION
    spans.to(None)


def _blocked_in_a_call_that_released_the_gil(until: threading.Event):
    until.wait(30)


@_time_limit(30)
def test_stall_with_a_live_interpreter_leaves_a_dump(monkeypatch, tmp_path):
    """An engine's queue ages past a second while the heartbeat wakes
    on time: no freeze, one ``stall`` dump for the whole stretch, with
    the engine threads' stretches and every thread's stack."""
    _fresh(monkeypatch, EVAM_TRACE_FLIGHT_DIR=str(tmp_path))
    eng = _StubEngine(0.2, "det-stall")  # _watched is the process's
    done = threading.Event()
    blocked = threading.Thread(
        target=_blocked_in_a_call_that_released_the_gil, args=(done,),
        name="engine-det-dispatch")
    blocked.start()
    rec = trace.start_freeze_recorder()
    try:
        trace.watch_engine(eng)
        time.sleep(0.6)
        assert not list(tmp_path.glob("flight-process-*.jsonl"))
        eng.age = 1.3
        time.sleep(1.2)  # several beats inside the one stall
        eng.age = 0.0
        time.sleep(0.6)
    finally:
        trace.stop_freeze_recorder()
        done.set()
        blocked.join()
    dumps = list(tmp_path.glob("flight-process-*.jsonl"))
    assert len(dumps) == 1, dumps
    header = json.loads(dumps[0].read_text().splitlines()[0])
    assert header["reason"] == "stall"
    state = header["state"]
    assert state["verdict"] == "interpreter_alive"
    assert state["queue_age_s"]["det-stall"] == 1.3
    assert state["threads"]["det-stall"]["dispatch"] == ["h2d_issue", 1.3]
    assert "_blocked_in_a_call_that_released_the_gil" in state["stacks"]
    assert "wait" in state["top_frames"]["engine-det-dispatch"]
    assert rec._stall_dumped is False  # re-armed once the queue was young


@_time_limit(30)
def test_gc_pauses_are_recorded_by_generation(monkeypatch, tmp_path):
    _fresh(monkeypatch, EVAM_TRACE_FLIGHT_DIR=str(tmp_path))

    def gen2_count():
        key = 'evam_gc_pause_seconds_count{gen="2"}'
        return next((float(l.split()[-1]) for l in
                     metrics.render().splitlines() if l.startswith(key)),
                    None)
    rec = trace.start_freeze_recorder()
    try:
        n0 = gen2_count()
        assert n0 is not None  # declared at start: a reader sees zero
        gc.collect()
        deadline = time.time() + 5
        while gen2_count() < n0 + 1 and time.time() < deadline:
            time.sleep(0.05)
        assert gen2_count() >= n0 + 1
    finally:
        trace.stop_freeze_recorder()


@_time_limit(30)
def test_trace_off_starts_nothing_and_stamps_nothing(monkeypatch):
    import numpy as np

    from evam_tpu.stages import infer
    from evam_tpu.stages.context import FrameContext

    _fresh(monkeypatch, EVAM_TRACE="off")
    callbacks = list(gc.callbacks)
    assert trace.start_freeze_recorder() is None
    assert not any(t.name == "evam-heartbeat" for t in threading.enumerate())
    assert gc.callbacks == callbacks

    class _NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"time.{name} read with EVAM_TRACE=off")

    monkeypatch.setattr(trace, "time", _NoClock())
    monkeypatch.setattr(infer, "time", _NoClock())
    spans = trace.thread_spans("det", "dispatch", cpu=True)
    assert spans.to("wait_items") is None and spans.to(None) is None
    trace.watch_engine(object())
    assert len(trace._watched) == 0
    assert trace.start_frame("s", 0, "standard", None, 1.0) is None
    ctx = FrameContext(frame=np.zeros((4, 4, 3), np.uint8), pts_ns=0,
                      seq=0, stream_id="s")
    assert ctx.trace is None
    infer._timed_wire(ctx, (4, 4), "seed", "detect")


# -- batch timelines and the waits between the layers ---------------------

@pytest.fixture(scope="module")
def paced_run(eight_devices):
    """One paced synthetic camera through the real serving path, with
    every frame retained; what the cases below read."""
    import os

    from evam_tpu.engine import EngineHub
    from evam_tpu.models import ModelRegistry
    from evam_tpu.parallel import build_mesh
    from evam_tpu.sched import SchedConfig
    from evam_tpu.server.registry import PipelineRegistry

    saved = {k: os.environ.pop(k, None) for k in _KNOBS}
    os.environ["EVAM_TRACE_SAMPLE_N"] = "1"
    reset_settings()
    trace.reset_cache()
    small = {k: (64, 64) for k in ZOO_SPECS}
    small["audio_detection/environment"] = (1, 1600)
    settings = Settings(pipelines_dir=str(REPO / "pipelines"))
    # the scheduler on, as the server runs it: the dispatcher copies
    # the rows itself, so slot_write is a stretch of the timeline
    hub = EngineHub(
        ModelRegistry(dtype="float32", input_overrides=small,
                      width_overrides={k: 8 for k in ZOO_SPECS}),
        plan=build_mesh(), max_batch=8, deadline_ms=4.0,
        # capacity declared, as the CPU rehearsal declares it: the stage
        # clock of a CPU under a whole test run refused the paced camera
        # (utilization 1.32 for 0.72) and the cases below read timelines
        sched=dataclasses.replace(
            SchedConfig.from_settings(settings.sched,
                                      standard_deadline_ms=4.0),
            capacity_fps=1000.0))
    # every earlier engine of this process has stopped and flushed: the
    # idle seconds from here on are this hub's engines'
    start, t_start = metrics.render(), time.perf_counter()
    registry = PipelineRegistry(settings, hub=hub)
    n = 12
    try:
        # once unpaced, so the paced camera meets a compiled bucket
        warm = registry.start_instance(
            "object_detection", "person_vehicle_bike",
            {"source": {"uri": "synthetic://96x96@30?count=4&seed=2",
                        "type": "uri"},
             "destination": {"metadata": {"type": "null"}}})
        warm.wait(timeout=180)
        before = metrics.render()
        inst = registry.start_instance(
            "object_detection", "person_vehicle_bike",
            {"source": {"uri": f"synthetic://96x96@30?count={n}&seed=1",
                        "type": "uri", "realtime": True},
             "destination": {"metadata": {"type": "null"}}})
        inst.wait(timeout=180)
        assert inst.state.value == "COMPLETED", inst.error
        # the launcher flushes its sums, the ledger's with them, on its
        # next turn after FLUSH_S (it turns every 0.1 s while it waits)
        time.sleep(trace.ThreadSpans.FLUSH_S + 0.2)
        after, t_after = metrics.render(), time.perf_counter()
        frames, batches, _ = trace.active().snapshot()
        payload = trace.traces_payload()
    finally:
        registry.stop_all()
        for k, v in saved.items():
            if v is not None:
                os.environ[k] = v
        os.environ.pop("EVAM_TRACE_SAMPLE_N", None)
        reset_settings()
        trace.reset_cache()
    frames = [f for f in frames if f.stream_id == inst.id]
    assert len(frames) == n
    return {"n": n, "before": before, "after": after, "frames": frames,
            "batches": batches, "payload": payload, "stream": inst.id,
            "start": start, "wall_s": t_after - t_start}


def _series(text: str, key: str) -> float:
    return next((float(l.split()[-1]) for l in text.splitlines()
                 if l.startswith(key + " ")), 0.0)


@_time_limit(240)
def test_batch_record_is_a_timeline(paced_run):
    """Stage spans are ordered, start inside the batch, leave no hole
    over 0.5 ms that is not a named wait, and sum to the dispatch."""
    served = {b for f in paced_run["frames"] for b in f.bids}
    recs = [r for r in paced_run["batches"]
            if f"{r['engine']}#{r['bid']}" in served]
    assert recs
    for rec in recs:
        spans = rec["spans"]
        names = [sp[0] for sp in spans]
        stages = [n for n in names if n in trace.STAGE_ORDER]
        assert stages == [s for s in trace.STAGE_ORDER if s in stages]
        assert {"slot_write", "seal", "h2d_issue", "h2d_wait", "launch",
                "readback", "resolve"} <= set(stages), names
        assert {n for n in names if n not in trace.STAGE_ORDER} <= {
            "wait_launcher", "wait_slot", "wait_completer"}
        end = rec["t0"]
        assert spans[0][1] == rec["t0"]
        for name, t0, dur in spans:
            assert dur >= 0.0 and t0 >= rec["t0"], (name, spans)
            assert -1e-6 <= t0 - end < 0.0005, (name, t0 - end, spans)
            end = t0 + dur
        assert end <= rec["t0"] + rec["dur_s"] + 1e-6
        assert abs(sum(sp[2] for sp in spans) - rec["dur_s"]) < 0.001
    # a member frame carries the same spans, and its dispatch is the batch
    ft = paced_run["frames"][-1]
    rec = next(r for r in recs if f"{r['engine']}#{r['bid']}" == ft.bids[0])
    mine = {s[0]: s for s in ft.spans}
    for name, t0, dur in rec["spans"]:
        assert mine[f"engine.{name}"][1:3] == (t0, dur)
    assert abs(mine["engine.dispatch"][2] - rec["dur_s"]) < 0.001
    assert mine["sched.queue_wait"][1] + mine["sched.queue_wait"][2] \
        == pytest.approx(rec["t0"])


@_time_limit(240)
def test_waits_between_layers_observe_once_per_frame(paced_run):
    n, before, after = (paced_run[k] for k in ("n", "before", "after"))
    for key in ("evam_collect_wait_seconds_count",
                "evam_source_lag_seconds_count",
                'evam_stage_seconds_count{stage="detect.wire"}'):
        assert _series(after, key) - _series(before, key) == n, key
    for ft in paced_run["frames"]:
        names = [s[0] for s in ft.spans]
        for want in ("wire", "runner.collect_wait"):
            assert names.count(want) == 1, (want, names)
        assert sum(n.startswith("stage.") and n.endswith(".submit")
                   for n in names) == 1
        spans = {s[0]: s for s in ft.spans}
        submit = next(s for s in ft.spans if s[0].endswith(".submit"))
        # feed -> submit -> wire inside it -> collected after the resolve
        assert ft.due_t <= ft.t0 <= submit[1] <= spans["wire"][1]
        assert spans["wire"][1] + spans["wire"][2] <= submit[1] + submit[2]
        resolve = spans["engine.resolve"]
        assert spans["runner.collect_wait"][1] == resolve[1]
        assert spans["runner.collect_wait"][2] >= 0.0
    # the engine's threads account for their seconds by state
    for state in ("wait_items", "work"):
        assert any(l.startswith("evam_engine_thread_seconds_total{")
                   and 'thread="dispatch"' in l and f'state="{state}"' in l
                   for l in after.splitlines()), state
    assert any(l.startswith("evam_engine_thread_cpu_seconds_total{")
               and 'thread="dispatch"' in l for l in after.splitlines())


def _summed(text: str, series: str, **labels: str) -> float:
    """Every line of ``series`` whose labels hold ``labels``, summed."""
    return sum(float(l.split()[-1]) for l in text.splitlines()
               if l.startswith(series + "{")
               and all(f'{k}="{v}"' in l for k, v in labels.items()))


@_time_limit(240)
def test_a_served_runs_idle_is_divided_and_no_more_than_its_wall(paced_run):
    """The engines' idle ledger over a served run: seconds under the five
    ``where`` alone, more than none (a paced camera leaves the engine dry
    between frames) and no more than the wall time the hub has lived."""
    start, after = paced_run["start"], paced_run["after"]
    series = "evam_engine_idle_seconds_total"
    idle = _summed(after, series) - _summed(start, series)
    assert 0.0 < idle <= paced_run["wall_s"], (idle, paced_run["wall_s"])
    parts = {w: _summed(after, series, where=w) - _summed(start, series,
                                                          where=w)
             for w in ("upstream", "queued", "stage", "upload", "launch")}
    assert sum(parts.values()) == pytest.approx(idle)
    # one camera at 30 frames/s: the engine is dry for want of a frame
    # most of the time, and every frame waits out the class deadline
    assert parts["upstream"] > parts["stage"] + parts["upload"]
    assert parts["queued"] > 0.0
    assert not [l for l in after.splitlines() if l.startswith(series)
                and 'stage="' not in l]


@_time_limit(240)
def test_the_chain_threads_account_for_their_seconds(paced_run):
    """A stream's chain thread is a fourth ``ThreadSpans``: wall seconds
    asleep and at work and its CPU, summed over the streams."""
    start, after = paced_run["start"], paced_run["after"]
    labels = {"engine": "streams", "thread": "chain"}
    grew = {state: _summed(after, "evam_engine_thread_seconds_total",
                           state=state, **labels)
            - _summed(start, "evam_engine_thread_seconds_total",
                      state=state, **labels)
            for state in ("wait_result", "work")}
    assert grew["wait_result"] > grew["work"] > 0.0, grew
    assert sum(grew.values()) <= 2 * paced_run["wall_s"]  # two streams
    cpu = (_summed(after, "evam_engine_thread_cpu_seconds_total", **labels)
           - _summed(start, "evam_engine_thread_cpu_seconds_total", **labels))
    assert 0.0 < cpu <= paced_run["wall_s"]


@_time_limit(240)
def test_traces_route_carries_clock_due_and_ingest(paced_run):
    payload = paced_run["payload"]
    clock = payload["clock"]
    assert abs(clock["time_ns"] / 1e9 - clock["perf_counter"]
               - (time.time() - time.perf_counter())) < 0.05
    mine = [e for e in payload["traceEvents"]
            if e["cat"] == "frame" and e["tid"] == paced_run["stream"]]
    assert mine
    by_seq = {f.seq: f for f in paced_run["frames"]}
    for ev in mine:
        ft = by_seq[ev["args"]["seq"]]
        assert ev["args"]["ingest_t"] == ft.t0
        assert ev["args"]["due_t"] == ft.due_t
        assert ev["ts"] >= round(ft.t0 * 1e6, 1) - 0.2
    # a paced camera's frames are due one period apart, on its own clock
    dues = [by_seq[k].due_t for k in sorted(by_seq)]
    gaps = [b - a for a, b in zip(dues, dues[1:])]
    assert all(abs(g - 1 / 30) < 1e-6 for g in gaps), gaps
