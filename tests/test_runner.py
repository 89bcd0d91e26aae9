"""StreamRunner as a pipeline: one in-order queue per async stage, a
head resumed when its future resolves, all stage code on the stream's
one chain thread. Fake stages and fake engines only: no model, no JAX."""

from __future__ import annotations

import queue
import random
import threading
import time
from concurrent.futures import Future

import pytest

from evam_tpu.media.source import FrameEvent
from evam_tpu.obs import metrics
from evam_tpu.sched.shedder import ShedError
from evam_tpu.stages.base import AsyncStage, Stage
from evam_tpu.stages.context import FrameContext
from evam_tpu.stages.runner import StreamRunner


def _event(seq: int) -> FrameEvent:
    return FrameEvent(frame=None, pts_ns=seq * 1000, seq=seq)


def _wait(cond, timeout: float = 5.0) -> bool:
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(0.001)
    return cond()


def _resumes() -> dict[str, float]:
    return {by: metrics.get_counter("evam_runner_resumes", {"by": by})
            for by in ("resolve", "feed", "drain")}


def _resumes_since(before: dict[str, float]) -> dict[str, float]:
    return {by: n - before[by] for by, n in _resumes().items()}


class ManualEngine:
    """Futures the test resolves itself, from a thread of its own."""

    def __init__(self):
        self.futures: dict[int, Future] = {}

    def submit(self, ctx: FrameContext) -> Future:
        fut = self.futures[ctx.seq] = Future()
        return fut

    def resolve(self, seq: int, result=None, exc: Exception | None = None):
        assert _wait(lambda: seq in self.futures), f"{seq} never submitted"

        def go():
            if exc is not None:
                self.futures[seq].set_exception(exc)
            else:
                self.futures[seq].set_result(result)
        t = threading.Thread(target=go)
        t.start()
        t.join()

    def cancel_all(self):
        for fut in list(self.futures.values()):
            if not fut.done():
                fut.set_result(None)


class ThreadedEngine:
    """Resolves each future from one of its own threads after a random
    delay, so results land out of order; a share of them as sheds."""

    def __init__(self, rng: random.Random, workers: int = 2,
                 shed_share: float = 0.0):
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._rng = rng
        self._shed_share = shed_share
        self.resolver_idents: set[int] = set()
        self._threads = [threading.Thread(target=self._loop, daemon=True)
                         for _ in range(workers)]
        for t in self._threads:
            t.start()

    def submit(self, ctx: FrameContext) -> Future:
        fut: Future = Future()
        r = self._rng.random()
        delay = 0.0 if r < 0.5 else self._rng.random() * 4e-4
        shed = self._rng.random() < self._shed_share
        self._q.put((fut, delay, shed, ctx.seq))
        return fut

    def _loop(self):
        self.resolver_idents.add(threading.get_ident())
        while True:
            item = self._q.get()
            if item is None:
                return
            fut, delay, shed, seq = item
            if delay:
                time.sleep(delay)
            if shed:
                fut.set_exception(ShedError("standard", 1.2, 1.0, "fake"))
            else:
                fut.set_result(seq)

    def close(self):
        for _ in self._threads:
            self._q.put(None)
        for t in self._threads:
            t.join()


class FakeAsync(AsyncStage):
    def __init__(self, name: str, engine, *, raise_on=(), fan_out: int = 1,
                 skip_every: int = 0):
        self.name = name
        self.engine = engine
        self.raise_on = set(raise_on)
        self.fan_out = fan_out
        self.skip_every = skip_every
        self.submitted: list[int] = []
        self.completed: list[int] = []
        self.idents: set[int] = set()
        self.cancelled = False

    def submit(self, ctx):
        self.idents.add(threading.get_ident())
        self.submitted.append(ctx.seq)
        if self.skip_every and ctx.seq % self.skip_every:
            return None  # as inference-interval does
        return self.engine.submit(ctx)

    def complete(self, ctx, result):
        self.idents.add(threading.get_ident())
        self.completed.append(ctx.seq)
        if ctx.seq in self.raise_on:
            raise RuntimeError(f"complete of {ctx.seq} fails")
        if self.cancelled and result is None:
            return []
        if self.fan_out == 1:
            return [ctx]
        return [FrameContext(frame=None, pts_ns=ctx.pts_ns, seq=ctx.seq,
                             stream_id=ctx.stream_id,
                             scratch={"part": part})
                for part in range(self.fan_out)]

    def cancel(self):
        self.cancelled = True
        self.engine.cancel_all()


class Sink(Stage):
    name = "sink"

    def __init__(self):
        self.out: list[tuple[int, int]] = []
        self.idents: set[int] = set()

    def process(self, ctx):
        self.idents.add(threading.get_ident())
        self.out.append((ctx.seq, ctx.scratch.get("part", 0)))
        return [ctx]

    @property
    def seqs(self) -> list[int]:
        return [seq for seq, _ in self.out]


def _two_stage(window: int = 6, **second):
    e1, e2 = ManualEngine(), ManualEngine()
    s1, s2, sink = FakeAsync("one", e1), FakeAsync("two", e2, **second), Sink()
    runner = StreamRunner("cam", [s1, s2, sink], window=window)
    return runner, e1, e2, s1, s2, sink


# the chain thread's own account -------------------------------------

@pytest.mark.parametrize("tracing", ["on", "off"])
def test_the_chain_threads_wait_is_counted_and_never_annotated(
        monkeypatch, tracing):
    """The chain thread keeps its seconds asleep (``wait_result``) and at
    work, and its CPU, under ``thread="chain"``; even while a profiler
    capture runs it annotates neither (a consumer's wait gates nothing).
    With EVAM_TRACE=off it keeps nothing and reads no clock for it."""
    from evam_tpu.config.settings import reset_settings
    from evam_tpu.obs import trace

    monkeypatch.setenv("EVAM_TRACE", tracing)
    reset_settings()
    trace.reset_cache()
    names: list[str] = []

    class _Ann:
        def __init__(self, name: str) -> None:
            names.append(name)

        def __exit__(self, *exc) -> None:
            pass

    monkeypatch.setattr(trace, "_annotation", _Ann)
    monkeypatch.setattr(trace, "_profiling", lambda: True)

    def seconds() -> dict[str, float]:
        labels = {"engine": "streams", "thread": "chain"}
        out = {state: metrics.get_counter(
            "evam_engine_thread_seconds", {**labels, "state": state})
            for state in ("wait_result", "work")}
        out["cpu"] = metrics.get_counter("evam_engine_thread_cpu_seconds",
                                         labels)
        return out

    before = seconds()
    if tracing == "off":
        class _NoClock:
            def __getattr__(self, name):
                raise AssertionError(f"time.{name} read with EVAM_TRACE=off")

        monkeypatch.setattr(trace, "time", _NoClock())
    eng, sink = ManualEngine(), Sink()
    runner = StreamRunner("cam", [FakeAsync("one", eng), sink])
    t0 = time.perf_counter()
    for seq in range(3):
        runner.feed(_event(seq))
        assert _wait(lambda: seq in eng.futures)
        time.sleep(0.02)  # the frame is parked in the engine
        eng.resolve(seq)
    runner.drain()  # the thread ends, and flushes
    wall = time.perf_counter() - t0
    assert sink.seqs == [0, 1, 2]
    grew = {k: v - before[k] for k, v in seconds().items()}
    assert names == []
    if tracing == "off":
        assert grew == {"wait_result": 0.0, "work": 0.0, "cpu": 0.0}
    else:
        assert 0.06 <= grew["wait_result"] <= wall
        assert 0.0 < grew["work"] < wall - grew["wait_result"] + 1e-6
        assert 0.0 <= grew["cpu"] <= grew["work"] + 0.01


# (a) ---------------------------------------------------------------

def test_a_message_leaves_before_the_next_feed():
    """A paced source with a long period: the result is published when
    it resolves, with no second frame fed at all."""
    eng, sink = ManualEngine(), Sink()
    runner = StreamRunner("cam", [FakeAsync("one", eng), sink])
    before = _resumes()
    runner.feed(_event(0))
    assert _wait(lambda: 0 in eng.futures)
    assert sink.out == []
    eng.resolve(0)
    assert _wait(lambda: sink.seqs == [0]), "not published until the next feed"
    assert runner.frames_out == 1
    assert _resumes_since(before) == {"resolve": 1, "feed": 0, "drain": 0}
    runner.feed(_event(1))
    eng.resolve(1)
    runner.drain()
    assert sink.seqs == [0, 1]


# (b) ---------------------------------------------------------------

def test_b_a_frame_passes_on_to_stage_two_while_its_elder_is_parked_there():
    runner, e1, e2, s1, s2, sink = _two_stage()
    for seq in range(3):
        runner.feed(_event(seq))
    assert _wait(lambda: len(e1.futures) == 3)
    e1.resolve(0)
    assert _wait(lambda: s2.submitted == [0])
    # frame 0 is parked at stage two for good; frame 1's detection lands
    e1.resolve(1)
    assert _wait(lambda: s2.submitted == [0, 1]), (
        "frame 1 stands behind frame 0's second park")
    assert not e2.futures[0].done()
    # out of order at both stages: each waits for its own head
    e1.resolve(2)
    assert _wait(lambda: s2.submitted == [0, 1, 2])
    e2.resolve(2)
    e2.resolve(1)
    time.sleep(0.05)
    assert sink.out == []
    e2.resolve(0)
    runner.drain()
    assert sink.seqs == [0, 1, 2]
    assert s1.completed == [0, 1, 2] and s2.completed == [0, 1, 2]
    assert s1.submitted == [0, 1, 2]


def test_b_an_unresolved_head_holds_its_stage_back():
    """Order at a stage: a later frame's result never overtakes."""
    runner, e1, e2, s1, s2, sink = _two_stage()
    for seq in range(3):
        runner.feed(_event(seq))
    assert _wait(lambda: len(e1.futures) == 3)
    e1.resolve(2)
    e1.resolve(1)
    time.sleep(0.05)
    assert s1.completed == [] and s2.submitted == []
    e1.resolve(0)
    assert _wait(lambda: s2.submitted == [0, 1, 2])
    for seq in (1, 0, 2):
        e2.resolve(seq)
    runner.drain()
    assert sink.seqs == [0, 1, 2]


def test_b_a_later_stage_holds_its_share_of_the_window():
    """Two async stages share a window of 4 evenly: the third frame
    waits, resolved, at stage one's head until stage two has room, and
    goes on the moment a frame leaves there."""
    runner, e1, e2, s1, s2, sink = _two_stage(window=4)
    for seq in range(4):
        runner.feed(_event(seq))
    for seq in range(4):
        e1.resolve(seq)
    assert _wait(lambda: s2.submitted == [0, 1])
    time.sleep(0.05)
    assert s2.submitted == [0, 1] and s1.completed == [0, 1]
    e2.resolve(1)  # not the head: nothing leaves, nothing moves on
    time.sleep(0.05)
    assert s2.submitted == [0, 1]
    e2.resolve(0)  # 0 and 1 leave; 2 and 3 take their places
    assert _wait(lambda: s2.submitted == [0, 1, 2, 3])
    assert sink.seqs == [0, 1]
    e2.resolve(2)
    e2.resolve(3)
    runner.drain()
    assert sink.seqs == [0, 1, 2, 3]


# (c) ---------------------------------------------------------------

@pytest.mark.parametrize("window", [1, 2, 4])
def test_c_window_bounds_the_frames_in_the_chain(window):
    runner, e1, e2, s1, s2, sink = _two_stage(window=window)
    for seq in range(window):
        runner.feed(_event(seq))
    fed = threading.Event()

    def one_more():
        runner.feed(_event(window))
        fed.set()

    feeder = threading.Thread(target=one_more, daemon=True)
    feeder.start()
    assert not fed.wait(0.15), "feed() went past a full window"
    assert s1.submitted == list(range(window))
    # moving on to stage two frees nothing: the frame is still inside
    e1.resolve(0)
    assert _wait(lambda: s2.submitted == [0])
    assert not fed.wait(0.1)
    before = _resumes()
    e2.resolve(0)
    assert fed.wait(5.0), "feed() stayed blocked after a frame left"
    feeder.join()
    # found while a feed stood blocked: the old window collected it too
    assert _resumes_since(before)["feed"] == 1
    assert _wait(lambda: s1.submitted == list(range(window + 1)))
    assert sink.seqs == [0]
    for seq in range(1, window + 1):
        e1.resolve(seq)
        assert _wait(lambda: seq in e2.futures)
        e2.resolve(seq)
    runner.drain()
    assert sink.seqs == list(range(window + 1))
    assert runner.frames_in == runner.frames_out == window + 1


# (d) ---------------------------------------------------------------

@pytest.mark.parametrize("stage_at", [1, 2])
@pytest.mark.parametrize("kind", ["shed", "complete_raises"])
def test_d_a_failed_middle_frame_is_counted_once_and_stalls_nothing(
        kind, stage_at):
    errors: list[Exception] = []
    e1, e2 = ManualEngine(), ManualEngine()
    raise_on = {1} if kind == "complete_raises" else ()
    s1 = FakeAsync("one", e1, raise_on=raise_on if stage_at == 1 else ())
    s2 = FakeAsync("two", e2, raise_on=raise_on if stage_at == 2 else ())
    sink = Sink()
    runner = StreamRunner("cam", [s1, s2, sink], window=6,
                          on_error=errors.append)
    for seq in range(3):
        runner.feed(_event(seq))
    assert _wait(lambda: len(e1.futures) == 3)
    shed = ShedError("standard", 1.2, 1.0, "fake") if kind == "shed" else None
    # the failing frame's result lands FIRST, ahead of its elder's
    bad, other = (e1, e2) if stage_at == 1 else (e2, e1)
    if stage_at == 2:
        for seq in range(3):
            e1.resolve(seq)
        assert _wait(lambda: len(e2.futures) == 3)
    bad.resolve(1, exc=shed)
    time.sleep(0.02)
    assert runner.errors == 0, "failed out of its turn"
    bad.resolve(0)
    bad.resolve(2)
    if stage_at == 1:
        assert _wait(lambda: set(e2.futures) == {0, 2})
        other.resolve(0)
        other.resolve(2)
    runner.drain()
    assert sink.seqs == [0, 2]
    assert runner.errors == 1 and len(errors) == 1
    assert isinstance(errors[0], ShedError if kind == "shed" else RuntimeError)
    assert runner.frames_in == runner.frames_out + runner.errors == 3


def test_d_a_raising_submit_and_an_injected_fault_are_frame_errors(
        monkeypatch):
    class BadSubmit(FakeAsync):
        def submit(self, ctx):
            if ctx.seq == 1:
                raise RuntimeError("no room")
            return super().submit(ctx)

    eng, sink = ManualEngine(), Sink()
    runner = StreamRunner("cam", [BadSubmit("one", eng), sink])
    for seq in range(3):
        runner.feed(_event(seq))
    assert _wait(lambda: set(eng.futures) == {0, 2})
    eng.resolve(2)
    eng.resolve(0)
    runner.drain()
    assert sink.seqs == [0, 2] and runner.errors == 1

    # an injected error is handed to the chain thread and counted there
    monkeypatch.setenv("EVAM_FAULT_INJECT", "error=1")
    errors: list[Exception] = []
    runner = StreamRunner("cam", [Sink()], on_error=errors.append)
    feeder = threading.get_ident()
    idents: list[int] = []
    runner.on_error = lambda exc: (errors.append(exc),
                                   idents.append(threading.get_ident()))
    runner.feed(_event(0))
    runner.drain()
    assert runner.errors == 1 and len(errors) == 1
    assert idents and idents[0] != feeder


# (e) ---------------------------------------------------------------

@pytest.mark.parametrize("how", ["stop", "drain"])
def test_e_stop_and_drain_with_frames_parked_at_two_stages(how):
    runner, e1, e2, s1, s2, sink = _two_stage()
    for seq in range(4):
        runner.feed(_event(seq))
    assert _wait(lambda: len(e1.futures) == 4)
    e1.resolve(0)
    e1.resolve(1)
    assert _wait(lambda: s2.submitted == [0, 1])
    chain = runner._thread
    assert chain is not None and chain.is_alive()
    before = _resumes()
    if how == "stop":
        runner.stop()  # cancel(): every parked future resolves with None
        runner.drain()
        assert sink.out == [] and runner.errors == 0
        assert s1.completed == [0, 1, 2, 3]
    else:
        def engine():
            time.sleep(0.05)
            for seq in (3, 2):
                e1.resolve(seq)
            assert _wait(lambda: len(e2.futures) == 3)
            for seq in (1, 0):  # frame 3 waits for stage two's share
                e2.resolve(seq)
            assert _wait(lambda: len(e2.futures) == 4)
            for seq in (3, 2):
                e2.resolve(seq)

        t = threading.Thread(target=engine)
        t.start()
        runner.drain()
        t.join()
        assert sink.seqs == [0, 1, 2, 3]
        got = _resumes_since(before)
        assert got["drain"] == 6 and got["resolve"] == got["feed"] == 0
    assert not chain.is_alive() and runner._thread is None
    # the runner serves again after a drain: a new chain thread
    if how == "drain":
        runner.feed(_event(4))
        assert _wait(lambda: 4 in e1.futures)
        e1.resolve(4)
        assert _wait(lambda: 4 in e2.futures)
        e2.resolve(4)
        runner.drain()
        assert sink.seqs == [0, 1, 2, 3, 4]


def test_e_run_drains_and_ends_the_chain_thread_when_the_source_fails():
    eng, sink = ThreadedEngine(random.Random(5)), Sink()
    runner = StreamRunner("cam", [FakeAsync("one", eng), sink])

    def source():
        for seq in range(6):
            yield _event(seq)
        raise IOError("camera gone")

    with pytest.raises(IOError):
        runner.run(source())
    eng.close()
    assert sink.seqs == list(range(6)), "fed frames were abandoned"
    assert runner._thread is None
    assert not [t for t in threading.enumerate()
                if t.name == "chain-cam" and t.is_alive()]


def test_e_a_fault_of_the_chain_thread_reaches_the_feeder():
    def boom(exc):
        raise KeyError("on_error itself fails")

    eng = ManualEngine()
    runner = StreamRunner("cam", [FakeAsync("one", eng, raise_on={0}), Sink()],
                          window=1, on_error=boom)
    runner.feed(_event(0))
    eng.resolve(0)
    with pytest.raises(KeyError):
        runner.feed(_event(1))  # would wait for room for ever
    with pytest.raises(KeyError):
        runner.drain()


# (f) ---------------------------------------------------------------

@pytest.mark.parametrize("where", ["async_first", "async_second", "sync"])
def test_f_fan_out_keeps_order(where):
    class Split(Stage):
        name = "split"

        def process(self, ctx):
            return [FrameContext(frame=None, pts_ns=0, seq=ctx.seq,
                                 stream_id=ctx.stream_id,
                                 scratch={"part": p}) for p in range(2)]

    rng = random.Random(11)
    e1, e2 = ThreadedEngine(rng), ThreadedEngine(rng)
    s1 = FakeAsync("one", e1, fan_out=2 if where == "async_first" else 1)
    s2 = FakeAsync("two", e2, fan_out=2 if where == "async_second" else 1)
    sink = Sink()
    stages = [s1, Split(), s2, sink] if where == "sync" else [s1, s2, sink]
    runner = StreamRunner("cam", stages)
    n = 40
    runner.run(_event(seq) for seq in range(n))
    e1.close()
    e2.close()
    want = [(seq, part) for seq in range(n) for part in range(2)]
    assert sink.out == want
    assert runner.frames_in == n and runner.frames_out == 2 * n
    assert runner._in_chain == 0


def test_f_skipped_inference_keeps_its_place():
    """submit() returning None (inference-interval) parks with nothing
    to wait for, and still leaves in its turn."""
    eng, sink = ManualEngine(), Sink()
    stage = FakeAsync("one", eng, skip_every=2)
    runner = StreamRunner("cam", [stage, sink])
    for seq in range(4):
        runner.feed(_event(seq))
    assert _wait(lambda: set(eng.futures) == {0, 2})
    eng.resolve(2)
    time.sleep(0.02)
    assert sink.out == []
    eng.resolve(0)
    runner.drain()
    assert sink.seqs == [0, 1, 2, 3] and stage.completed == [0, 1, 2, 3]


# (g), (h) ----------------------------------------------------------

def _stress(streams: int, frames: int, shed_share: float):
    rng = random.Random(29)
    engines = [ThreadedEngine(random.Random(rng.random()), workers=3,
                              shed_share=shed_share) for _ in range(2)]
    runs = []
    for i in range(streams):
        s1, s2, sink = (FakeAsync("one", engines[0]),
                        FakeAsync("two", engines[1]), Sink())
        runner = StreamRunner(f"cam{i}", [s1, s2, sink])
        feeder = threading.Thread(
            target=runner.run, args=((_event(seq) for seq in range(frames)),))
        runs.append((runner, s1, s2, sink, feeder))
    for run in runs:
        run[4].start()
    for run in runs:
        run[4].join(120)
        assert not run[4].is_alive(), "a stream stalled"
    for eng in engines:
        eng.close()
    return engines, runs


def test_g_stress_every_seq_published_once_and_in_order():
    engines, runs = _stress(streams=8, frames=1250, shed_share=0.01)
    lost = 0
    for runner, s1, s2, sink, _ in runs:
        seqs = sink.seqs
        assert seqs == sorted(set(seqs)), "reordered or duplicated"
        assert s1.submitted == list(range(1250))
        # a shed frame's complete() is never called; the rest in order
        assert s1.completed == sorted(set(s1.completed)) == s2.submitted
        assert s2.completed == sorted(set(s2.completed)) == seqs
        assert runner.frames_in == 1250
        assert runner.frames_in == runner.frames_out + runner.errors
        assert len(seqs) == runner.frames_out
        assert runner._in_chain == 0 and not any(runner._parked.values())
        lost += runner.errors
    assert 0 < lost < 10000 * 0.05  # the sheds, each counted once


def test_h_no_stage_code_runs_on_a_resolving_thread():
    engines, runs = _stress(streams=4, frames=200, shed_share=0.0)
    resolvers = set().union(*(e.resolver_idents for e in engines))
    assert len(resolvers) == 6
    feeders = {run[4].ident for run in runs}
    for runner, s1, s2, sink, _ in runs:
        idents = s1.idents | s2.idents | sink.idents
        assert len(idents) == 1, "stage state has more than one owner"
        assert not idents & resolvers, "stage code on an engine's thread"
        assert not idents & feeders, "stage code on the source's thread"
