"""StreamRunner: drives one stream's frames through its stage chain
with multiple frames in flight.

The reference overlaps decode and inference through GStreamer's
per-element threads and queues (SURVEY.md §2d-5). Here a single
runner keeps up to ``window`` frames in flight: a frame walks sync
stages inline, parks at an async (engine-backed) stage, and resumes
— strictly in seq order — once its batch result lands. This is what
lets one stream sustain full rate even when each engine round-trip
costs more than a frame interval (deep pipelining over the device
queue)."""

from __future__ import annotations

from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Callable, Iterator

import time

from evam_tpu.media.source import FrameEvent
from evam_tpu.obs import get_logger, metrics
from evam_tpu.obs import trace
from evam_tpu.obs.faults import from_env as faults_from_env
from evam_tpu.obs.trace import observe_frame_latency, stage_timer
from evam_tpu.sched.shedder import ShedError
from evam_tpu.stages.base import AsyncStage, Stage
from evam_tpu.stages.context import FrameContext
from evam_tpu.state import active as ckpt_active

log = get_logger("stages.runner")


@dataclass
class _Parked:
    ctx: FrameContext
    stage: AsyncStage
    future: Future | None


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
        self.frames_in = 0
        self.frames_out = 0
        self.errors = 0
        self._parked: deque[_Parked] = deque()
        self._stopped = False
        self._faults = faults_from_env()
        #: crash-consistent checkpoints (evam_tpu/state/): resolved
        #: once at construction like the fault injector — None when
        #: EVAM_CKPT=off, so the post-resolve hook is one None-check
        self._ckpt = ckpt_active()
        #: trace-id of the last resolved frame — the checkpoint's
        #: trace-continuity marker (only maintained when ckpt is on)
        self.last_trace_id = ""

    # ----------------------------------------------------------- API

    def run(self, events: Iterator[FrameEvent]) -> None:
        """Consume the event iterator to completion (blocking)."""
        for ev in events:
            if self._stopped:
                break
            self.feed(ev)
        self.drain()

    def stop(self) -> None:
        self._stopped = True
        for stage in self.stages:
            stage.cancel()

    def feed(self, ev: FrameEvent) -> None:
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
                # frame is fed: the source's frame build and the
                # runner's previous frame
                metrics.observe("evam_source_lag_seconds",
                                ingest_t - ev.due_t)
            if ev.decode_s is not None:
                # decode happened before ingest; backdate the span so
                # the tree starts where the frame's wall time started
                ctx.trace.add_span("decode", ingest_t - ev.decode_s,
                                   ev.decode_s)
        if self._faults is not None:
            try:
                frame = self._faults.apply(ctx.frame)
            except Exception as exc:  # noqa: BLE001 — injected error
                self._handle_error(exc, ctx)
                return
            if frame is None and ctx.frame is not None:
                return  # injected drop
            ctx.frame = frame
        # Free a slot first (blocking only when the window is full),
        # then start this frame down the chain.
        self.pump(block=len(self._parked) >= self.window)
        self._advance(ctx)
        self.pump(block=False)

    def drain(self) -> None:
        while self._parked:
            self.pump(block=True)

    # ------------------------------------------------------ internals

    def pump(self, block: bool) -> None:
        """Resume parked frames whose results are ready (in order)."""
        while self._parked:
            head = self._parked[0]
            fut, ft = head.future, head.ctx.trace
            waits = fut is not None and not fut.done()
            if waits and not block:
                return
            self._parked.popleft()
            try:
                if fut is None:
                    result = None
                elif waits and ft is not None:
                    with trace.annotate("evam.runner.wait_result"):
                        result = fut.result()
                else:
                    result = fut.result()
                t_c = time.perf_counter()
                t_r = getattr(fut, "t_resolved", None)
                if t_r is not None and ft is not None:
                    # the engine resolved the future at t_r (its
                    # completion loop stamps it); it lay there until
                    # this pump, which runs inside the NEXT feed unless
                    # the window was full
                    metrics.observe("evam_collect_wait_seconds", t_c - t_r)
                    ft.add_span("runner.collect_wait", t_r, t_c - t_r)
                with stage_timer(f"{head.stage.name}.complete"):
                    outs = head.stage.complete(head.ctx, result)
                if ft is not None:
                    ft.add_span(
                        f"stage.{head.stage.name}.complete", t_c,
                        time.perf_counter() - t_c)
            except Exception as exc:  # noqa: BLE001 — frame-level fault isolation
                self._handle_error(exc, head.ctx)
                continue
            for ctx in outs:
                ctx.stage_index = head.ctx.stage_index + 1
                if ctx.ingest_t is None:
                    ctx.ingest_t = head.ctx.ingest_t
                if ctx.trace is None:
                    ctx.trace = head.ctx.trace
                self._advance(ctx)
            block = False  # only the head wait is blocking

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
                self._parked.append(_Parked(ctx, stage, fut))
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
            if not outs:
                return  # frame consumed/dropped
            if len(outs) == 1 and outs[0] is ctx:
                i += 1
                continue
            # fan-out (e.g. audio re-chunking): each emitted ctx
            # continues from the next stage, inheriting the parent's
            # ingest time so the latency histogram covers them.
            for out in outs:
                out.stage_index = i + 1
                if out.ingest_t is None:
                    out.ingest_t = ctx.ingest_t
                if out.trace is None:
                    out.trace = ctx.trace
                self._advance(out)
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
