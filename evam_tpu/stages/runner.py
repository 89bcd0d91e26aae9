"""StreamRunner: drives one stream's frames through its stage chain
as a pipeline, with multiple frames in flight.

The reference overlaps decode and inference through GStreamer's
per-element threads and queues (SURVEY.md §2d-5). Here a stream's
chain is a pipeline with one in-order queue per async (engine-backed)
stage: a frame walks sync stages inline, parks at the tail of the next
async stage's queue, and leaves that queue only from its head, once
its result has landed. So every stage, and the publisher at the end,
sees frames strictly in seq order, while frame k+1 goes on to the
second async stage of a chain although frame k is still parked there.
Up to ``window`` frames of the stream are inside the chain at once;
``feed`` blocks while it is full, and the async stages share the
window evenly (see ``_share``). This is what lets one stream sustain
full rate even when each engine round-trip costs more than a frame
interval (deep pipelining over the device queue).

All chain work of a stream (``submit``, ``complete``, the sync stages,
the publisher) runs on ONE thread, the stream's chain thread, which
owns the queues and the counters and sleeps until ``feed`` hands it a
frame or a parked future's done-callback signals that a result is
there: a queue's head is resumed when it resolves, not at the
stream's next frame. The callback runs on whatever thread resolved the
future (an engine's completion thread, the shedder, a watchdog) and
only signals; the source's thread only builds frames and feeds."""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Callable, Iterator

from evam_tpu.media.source import FrameEvent
from evam_tpu.obs import get_logger, metrics
from evam_tpu.obs import trace
from evam_tpu.obs.faults import from_env as faults_from_env
from evam_tpu.obs.trace import observe_frame_latency, stage_timer
from evam_tpu.sched.shedder import ShedError
from evam_tpu.stages.base import Stage
from evam_tpu.stages.context import FrameContext
from evam_tpu.state import active as ckpt_active

log = get_logger("stages.runner")


@dataclass
class _Parked:
    ctx: FrameContext
    future: Future | None

    def ready(self) -> bool:
        return self.future is None or self.future.done()


class StreamRunner:
    def __init__(
        self,
        stream_id: str,
        stages: list[Stage],
        source_uri: str = "",
        window: int = 4,
        on_error: Callable[[Exception], None] | None = None,
        priority: str = "standard",
    ):
        self.stream_id = stream_id
        self.stages = stages
        self.source_uri = source_uri
        #: QoS class stamped on every FrameContext (evam_tpu/sched/)
        self.priority = priority
        self.window = max(1, window)
        self.on_error = on_error
        #: written by the feeding thread alone
        self.frames_in = 0
        #: written by the chain thread alone
        self.frames_out = 0
        self.errors = 0
        self._stopped = False
        self._faults = faults_from_env()
        #: crash-consistent checkpoints (evam_tpu/state/): resolved
        #: once at construction like the fault injector — None when
        #: EVAM_CKPT=off, so the post-resolve hook is one None-check
        self._ckpt = ckpt_active()
        #: trace-id of the last resolved frame — the checkpoint's
        #: trace-continuity marker (only maintained when ckpt is on)
        self.last_trace_id = ""
        #: the chain thread's own, under no lock: one FIFO of parked
        #: frames per async stage (by stage index), scanned from the
        #: chain's end back so a frame nearer the publisher goes first
        self._parked: dict[int, deque[_Parked]] = {
            i: deque() for i in reversed(range(len(stages)))
            if stages[i].is_async}
        #: the window is shared out evenly among the async stages: a
        #: frame moves on to a LATER async stage only while that one
        #: holds fewer than its share, and waits, resolved, at the head
        #: of its own queue otherwise (the first async stage takes
        #: whatever the window lets in). One slow last stage then never
        #: holds a stream's whole window: a frame that leaves it is
        #: replaced at once by one that is already through the stages
        #: before, and a result's latency there stays that of ``share``
        #: frames a stream in the engine, not of ``window``
        self._share = max(1, self.window // max(1, len(self._parked)))
        later = [None, *self._parked]  # index of the next async stage
        self._next_async = dict(zip(self._parked, later))
        #: under ``_lock``: frames fed and not yet taken in by the
        #: chain thread (with the injected fault that ends one, if
        #: any), the contexts inside the chain (the parked and the one
        #: being walked; a frame is taken in only while they are fewer
        #: than ``window``), who waits, and what ended the chain thread
        self._lock = threading.Lock()
        self._room = threading.Condition(self._lock)
        #: what wakes the chain thread: one token a resolved future, a
        #: frame fed that can enter, a drain. A put takes no lock of
        #: this runner's, so an engine's thread never waits for one
        self._wake: queue.SimpleQueue = queue.SimpleQueue()
        self._inbox: deque[tuple[FrameContext, Exception | None]] = deque()
        self._in_chain = 0
        self._feed_blocked = False
        self._draining = False
        self._fatal: BaseException | None = None
        self._thread: threading.Thread | None = None
        # all three series exist from the first stream on, so a reader
        # of deltas tells "none of that kind" from "no such counter"
        for by in ("resolve", "feed", "drain"):
            metrics.inc("evam_runner_resumes", 0.0, {"by": by})

    # ----------------------------------------------------------- API

    def run(self, events: Iterator[FrameEvent]) -> None:
        """Consume the event iterator to completion (blocking). The
        chain is drained and its thread ended however the source ends:
        a retry builds a new runner over the same stages."""
        try:
            for ev in events:
                if self._stopped:
                    break
                self.feed(ev)
        finally:
            self.drain()

    def stop(self) -> None:
        self._stopped = True
        for stage in self.stages:
            stage.cancel()

    def feed(self, ev: FrameEvent) -> None:
        """Hand one frame to the chain; blocks while ``window`` frames
        are inside it."""
        self.frames_in += 1
        ingest_t = time.perf_counter()
        ctx = FrameContext(
            frame=ev.frame,
            audio=ev.audio,
            pts_ns=ev.pts_ns,
            seq=ev.seq,
            stream_id=self.stream_id,
            source_uri=self.source_uri,
            ingest_t=ingest_t,
            priority=self.priority,
            trace=trace.start_frame(self.stream_id, ev.seq, self.priority,
                                    ingest_t, ev.due_t),
        )
        if ctx.trace is not None:
            if ev.due_t is not None:
                # how long after the paced source's own due time the
                # frame is fed: the source's frame build and the wait
                # for room in the chain
                metrics.observe("evam_source_lag_seconds",
                                ingest_t - ev.due_t)
            if ev.decode_s is not None:
                # decode happened before ingest; backdate the span so
                # the tree starts where the frame's wall time started
                ctx.trace.add_span("decode", ingest_t - ev.decode_s,
                                   ev.decode_s)
        fault = None
        if self._faults is not None:
            try:
                frame = self._faults.apply(ctx.frame)
            except Exception as exc:  # noqa: BLE001 — injected error
                fault = exc  # counted by the chain thread, in order
            else:
                if frame is None and ctx.frame is not None:
                    return  # injected drop
                ctx.frame = frame
        with self._lock:
            if self._fatal is not None:
                raise self._fatal
            self._inbox.append((ctx, fault))
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._chain, daemon=True,
                    name=f"chain-{self.stream_id[:8]}")
                self._thread.start()
            if self._in_chain < self.window:
                self._wake.put(None)
            # else the chain is full: the frame waits at its door, and
            # the feeder with it. The chain thread takes it in the
            # moment a frame leaves; neither thread is woken for it
            while (self._in_chain + len(self._inbox) > self.window
                   and self._fatal is None):
                self._feed_blocked = True
                self._room.wait()
            self._feed_blocked = False
            if self._fatal is not None:
                raise self._fatal

    def drain(self) -> None:
        """Wait until every frame fed has left the chain, then end the
        chain thread (the next ``feed`` starts another)."""
        with self._lock:
            self._draining = True
            self._wake.put(None)
            while ((self._in_chain or self._inbox)
                   and self._fatal is None):
                self._room.wait()
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join()  # it ends once it finds the chain drained
        self._draining = False
        if self._fatal is not None:
            raise self._fatal

    # ------------------------------------------------------ internals

    def _signal(self, _future: Future) -> None:
        """A parked future's done-callback, on the resolving thread:
        wake the chain thread and nothing else."""
        self._wake.put(None)

    def _chain(self) -> None:
        """The chain thread: resume whichever stage's head has its
        result, else take the next fed frame in, else sleep."""
        # this thread's seconds asleep and at work, and its CPU: summed
        # over the streams, work far above CPU is the streams' side of
        # the GIL. No annotation: a consumer's wait gates nothing
        sp = trace.thread_spans("streams", "chain", cpu=True,
                                annotates=False)
        try:
            fed = False  # took a fed frame in since it last slept
            sp.to("work")
            while True:
                parked = self._ready_head()
                if parked is not None:
                    # what the one in-order window would have done with
                    # this result now: left it lying until the stream's
                    # next feed ("resolve": only the signal brought the
                    # chain here), or found it
                    by = ("drain" if self._draining else
                          "feed" if fed or self._inbox or self._feed_blocked
                          else "resolve")
                    metrics.inc("evam_runner_resumes", labels={"by": by})
                    self._resume(parked)
                    continue
                with self._lock:
                    if self._inbox and self._in_chain < self.window:
                        ctx, fault = self._inbox.popleft()
                        self._in_chain += 1
                    elif self._draining and not (self._in_chain
                                                 or self._inbox):
                        return
                    else:
                        ctx = None
                if ctx is not None:
                    fed = True
                    if fault is not None:
                        self._handle_error(fault, ctx)
                    else:
                        self._advance(ctx)
                    continue
                fed = False
                if self._feed_blocked:
                    # only now, with everything that could go on its
                    # way gone: the feeder's next frame does not vie
                    # with a frame's way back into the engine
                    with self._lock:
                        self._room.notify_all()
                sp.to("wait_result")
                self._wake.get()
                sp.to("work")
        except BaseException as exc:  # noqa: BLE001 — raised by feed/drain
            with self._lock:
                self._fatal = exc
                self._room.notify_all()
        finally:
            sp.to(None)

    def _ready_head(self) -> _Parked | None:
        for i, fifo in self._parked.items():
            nxt = self._next_async[i]
            if fifo and fifo[0].ready() and (
                    nxt is None or len(self._parked[nxt]) < self._share):
                return fifo.popleft()
        return None

    def _left(self, n: int = 1) -> None:
        """``n`` contexts left the chain (negative: a fan-out added)."""
        with self._lock:
            self._in_chain -= n
            if self._draining:
                self._room.notify_all()

    def _resume(self, head: _Parked) -> None:
        """Fold a resolved head's result in and send it on down the
        chain (in order: it was its stage's oldest)."""
        ctx, fut, ft = head.ctx, head.future, head.ctx.trace
        stage = self.stages[ctx.stage_index]
        try:
            result = None if fut is None else fut.result()
            t_c = time.perf_counter()
            t_r = getattr(fut, "t_resolved", None)
            if t_r is not None and ft is not None:
                # the engine resolved the future at t_r (its completion
                # loop stamps it); it lay there until the chain thread
                # woke and every frame ahead of it in this stage's
                # queue had gone
                metrics.observe("evam_collect_wait_seconds", t_c - t_r)
                ft.add_span("runner.collect_wait", t_r, t_c - t_r)
            with stage_timer(f"{stage.name}.complete"):
                outs = stage.complete(ctx, result)
            if ft is not None:
                ft.add_span(
                    f"stage.{stage.name}.complete", t_c,
                    time.perf_counter() - t_c)
        except Exception as exc:  # noqa: BLE001 — frame-level fault isolation
            self._handle_error(exc, ctx)
            return
        self._fan_out(ctx, outs)

    def _fan_out(self, ctx: FrameContext, outs: list[FrameContext]) -> None:
        """Send what a stage made of ``ctx`` on from the next stage.
        Each emitted context inherits the parent's ingest time, so the
        latency histogram covers it, and its trace."""
        if len(outs) != 1:
            self._left(1 - len(outs))
        for out in outs:
            out.stage_index = ctx.stage_index + 1
            if out.ingest_t is None:
                out.ingest_t = ctx.ingest_t
            if out.trace is None:
                out.trace = ctx.trace
            self._advance(out)

    def _advance(self, ctx: FrameContext) -> None:
        """Walk sync stages until the chain ends or an async stage parks."""
        i = ctx.stage_index
        while i < len(self.stages):
            stage = self.stages[i]
            ctx.stage_index = i
            if stage.is_async:
                try:
                    if ctx.trace is None:
                        fut = stage.submit(ctx)
                    else:
                        t_s = time.perf_counter()
                        fut = stage.submit(ctx)
                        ctx.trace.add_span(f"stage.{stage.name}.submit",
                                           t_s, time.perf_counter() - t_s)
                except Exception as exc:  # noqa: BLE001
                    self._handle_error(exc, ctx)
                    return
                self._parked[i].append(_Parked(ctx, fut))
                if fut is not None:
                    fut.add_done_callback(self._signal)
                return
            try:
                t_s = time.perf_counter()
                with stage_timer(stage.name):
                    outs = stage.process(ctx)
                if ctx.trace is not None:
                    ctx.trace.add_span(f"stage.{stage.name}", t_s,
                                       time.perf_counter() - t_s)
            except Exception as exc:  # noqa: BLE001
                self._handle_error(exc, ctx)
                return
            if len(outs) == 1 and outs[0] is ctx:
                i += 1
                continue
            # consumed/dropped, or a fan-out (e.g. audio re-chunking)
            self._fan_out(ctx, outs)
            return
        self.frames_out += 1
        metrics.inc("evam_frames_processed", labels={"stream": self.stream_id})
        if ctx.ingest_t is not None:
            observe_frame_latency(
                self.stream_id, time.perf_counter() - ctx.ingest_t,
                priority=ctx.priority,
                trace_id=ctx.trace.trace_id if ctx.trace is not None else None)
        trace.finish_frame(ctx.trace, "ok")
        if self._ckpt is not None:
            # post-resolve barrier: the frame fully left the chain, so
            # every stage's cross-frame state is consistent — refresh
            # this stream's checkpoint on the capture cadence
            if ctx.trace is not None:
                self.last_trace_id = ctx.trace.trace_id
            if self.frames_out % self._ckpt.interval == 0:
                self._ckpt.capture(self.stream_id,
                                   barrier="post_resolve")
        self._left()

    def _handle_error(self, exc: Exception, ctx: FrameContext) -> None:
        self.errors += 1
        metrics.inc("evam_frame_errors", labels={"stream": self.stream_id})
        log.warning("stream %s frame %d error: %s", self.stream_id, ctx.seq, exc)
        # tail sampling always retains shed/error frames (a shed IS a
        # deadline miss — the staleness budget expired in queue)
        trace.finish_frame(ctx.trace,
                           "shed" if isinstance(exc, ShedError) else "error")
        if self.on_error is not None:
            self.on_error(exc)
        self._left()
