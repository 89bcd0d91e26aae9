"""What the serving path records about itself, and who reads it.

Everything here is on when ``EVAM_TRACE`` is on (the default) and a
no-op when it is ``off``: ``active()`` memoizes to None, FrameContext
.trace stays None, no thread is started and no hook takes a stamp.

* **Stage histograms** (always on): ``stage_timer`` lands a stage
  execution in ``evam_stage_seconds{stage}``; ``observe_frame_latency``
  is feed to chain complete, with the slowest frame's trace id as an
  OpenMetrics exemplar on the p99 line.
* **Frame span trees**: ``start_frame`` mints a ``FrameTrace`` at feed
  (stages/runner.py) that rides FrameContext into every engine submit.
  Spans are ``(name, t0, dur_s, attrs)`` with ``t0`` a ``perf_counter``
  stamp: decode, ``stage.<name>[.submit|.complete]``, ``wire``,
  ``sched.queue_wait``, the member batch's ``engine.<stage>`` spans at
  their real starts, ``engine.dispatch`` and ``runner.collect_wait``.
  ``TraceRing`` keeps error, shed and slow frames always and healthy
  ones 1 in N.
* **Batch timelines**: the engine's per-batch ``StageClock`` holds the
  stage durations (what ``EngineStats`` and the capacity model sum) and,
  as ``spans``, every stage and every named wait between two stages
  with its start, in the order they happened. A pending batch's record
  holds the SAME clock object the dispatch path fills in, so a flight
  dump of a wedged batch shows the last stage it completed.
* **Thread stretches**: ``ThreadSpans`` is one engine thread's state
  machine (dispatch, launch, complete; a stream's chain thread, seconds
  only). Each stretch is a ``jax.profiler.TraceAnnotation`` named
  ``evam.<thread>.<what>`` (a wait is named by what it waits for, and
  behind a dot by which of its cases it is), so a profiler capture
  shows the host on the device's clock, and its seconds land in
  ``evam_engine_thread_seconds{engine,thread,state}``.
* **The idle ledger**: ``IdleLedger`` is one batch engine's stretches
  with no program on the device, each divided by the timeline of the
  batch that ended it (``divide_idle``) into where that batch was:
  ``evam_engine_idle_seconds{engine,where,stage}``.
* **The freeze recorder**: ``FreezeRecorder`` is one daemon heartbeat.
  Every 250 ms it measures how late it woke; ``gc.callbacks`` stamp
  every collection. A wake >= 100 ms late lands in
  ``evam_freeze_seconds``; >= 1 s writes a ``freeze`` flight dump with
  a verdict (process stopped, GIL held by the collector, GIL held in a
  call), the collections, the engines' queue ages and every thread's
  stack. A beat that woke on time while an engine's oldest item is a
  second old writes a ``stall`` dump instead: the interpreter runs, an
  engine thread sits in a call.
* **Readout**: ``GET /traces`` (``traces_payload``: ring counters, one
  ``clock`` pair mapping ``perf_counter`` onto the wall clock, Chrome
  trace events; tools/trace_dump.py renders a capture) and
  ``flight_dump`` (JSONL of the last N records plus the caller's state;
  the engine supervisor writes one per quarantine, the freeze recorder
  one per freeze), rotated oldest-first.

Sampling and flight settings are memoized through config/settings.py:
no environment read on any hot path.
"""

from __future__ import annotations

import contextlib
import faulthandler
import gc
import itertools
import json
import os
import re
import tempfile
import threading
import time
import uuid
import weakref
from collections import deque
from pathlib import Path

from evam_tpu.obs import get_logger
from evam_tpu.obs.metrics import metrics

log = get_logger("obs.trace")

_PROFILER_PORT = 9999
_profiler_started = False

#: Engine stage order for "last completed stage" attribution — must
#: mirror engine/ringbuf.py STAGES (pinned by tests/test_trace.py;
#: duplicated here so obs never imports engine).
STAGE_ORDER = ("submit_wait", "slot_write", "seal", "h2d_issue",
               "h2d_wait", "launch", "readback", "resolve")

#: Trace ids: short per-process prefix + monotonic counter — unique
#: across a fleet of processes without coordination, cheap to mint.
_TRACE_PREFIX = uuid.uuid4().hex[:8]
_trace_seq = itertools.count(1)
_flight_seq = itertools.count(1)


def stage_timer(stage_name: str):
    """Record one stage execution into evam_stage_seconds{stage=...}
    (thin alias over the registry's timing context manager)."""
    return metrics.time("evam_stage_seconds", labels={"stage": stage_name})


def observe_frame_latency(stream_id: str, seconds: float,
                          priority: str | None = None,
                          trace_id: str | None = None) -> None:
    """End-to-end per-frame latency (feed → chain complete) — the
    BASELINE.md p99 target is measured from this histogram. ONE
    aggregate histogram, not per-stream: stream ids are per-instance
    UUIDs and a labeled histogram per dead stream would grow the
    process-global registry forever. A ``priority`` additionally
    lands a {class=...} series — BOUNDED (three QoS classes,
    evam_tpu/sched/) and the evidence the overload contract is
    judged on: realtime p99 vs budget while batch absorbs the shed.
    A ``trace_id`` rides along as an OpenMetrics exemplar, so the
    rendered p99 quantile line names a concrete frame to pull from
    /traces."""
    metrics.observe("evam_frame_latency_seconds", seconds,
                    exemplar=trace_id)
    if priority:
        metrics.observe("evam_frame_latency_seconds", seconds,
                        {"class": priority}, exemplar=trace_id)


class StageClock(dict):
    """One batch's host clock: ``stage -> seconds`` (the dict itself:
    what ``EngineStats`` sums and ``evam_engine_stage_seconds``
    observes) plus ``spans``, the batch's timeline: every stage and
    every named wait between two stages as ``(name, t0, dur_s)`` with
    ``t0`` a ``perf_counter`` stamp, appended in the order they
    happened. ``submit_wait`` is a duration only: it lies before the
    batch, and each member frame's ``sched.queue_wait`` span shows it.
    The completer appends to ``spans`` alone, never to the dict, which
    the launcher may be iterating."""

    __slots__ = ("spans",)

    def __init__(self) -> None:
        super().__init__()
        self.spans: list[tuple[str, float, float]] = []

    def mark(self, stage: str, t0: float, dur: float) -> None:
        self[stage] = dur
        self.spans.append((stage, t0, dur))

    def span(self, name: str, t0: float, dur: float) -> None:
        self.spans.append((name, t0, dur))

    def wait(self, name: str, until: float) -> None:
        """A named wait from the end of the last span to ``until``."""
        if self.spans:
            _, t0, dur = self.spans[-1]
            self.spans.append((name, t0 + dur, until - t0 - dur))


_annotation = None
_profiling = None


def _profiler_hooks():
    """``jax.profiler.TraceAnnotation`` and its ``is_enabled``, imported
    on first use (obs never imports jax at module load)."""
    global _annotation, _profiling
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation, _profiling = TraceAnnotation, TraceAnnotation.is_enabled
    return _annotation, _profiling


_NO_ANNOTATION = contextlib.nullcontext()


class ThreadSpans:
    """One engine thread's stretches. ``to(state)`` ends the stretch
    the thread was in and begins ``state``: while a profiler capture
    runs, an annotation ``evam.<thread>.<state>`` on the calling thread
    (no capture, no object); always, the ended stretch's wall seconds
    under ``work`` or, for a state named ``wait_*``, under that name up
    to its first dot (``wait_items.fill`` is a stretch of its own in a
    capture and ``wait_items`` in the sums).
    With ``cpu`` the thread's CPU seconds (``time.thread_time``) are
    kept too: a wait burns none, so they are the work stretches', and
    wall far above CPU inside work is time spent waiting for the GIL.
    The sums reach the registry about every ``FLUSH_S``, on the owning
    thread, and a ``ledger`` kept on that thread goes with them: a
    stretch costs one stamp and one dict update, no lock. A thread
    whose waits gate nothing (``annotates=False``: a consumer's, as a
    stream's chain thread) keeps its seconds and annotates none."""

    __slots__ = ("_prefix", "_labels", "_ann", "_key", "_state", "_t",
                 "_acc", "_flushed", "_cpu0", "_ledger", "_annotates")

    FLUSH_S = 0.25

    def __init__(self, engine: str, thread: str, cpu: bool = False,
                 ledger: "IdleLedger | None" = None,
                 annotates: bool = True) -> None:
        if annotates:
            _profiler_hooks()
        self._annotates = annotates
        self._prefix = f"evam.{thread}."
        self._labels = {"engine": engine, "thread": thread}
        self._ann = None
        #: the stretch the thread is in (None: in none) and the key its
        #: seconds go under
        self._state: str | None = None
        self._key: str | None = None
        self._t = self._flushed = time.perf_counter()
        self._acc: dict[str, float] = {}
        #: thread CPU seconds at the last flush (None: not kept); the
        #: first flush, on the owning thread, only sets it
        self._cpu0: float | None = -1.0 if cpu else None
        self._ledger = ledger

    def to(self, state: str | None, now: float | None = None,
           annotate: bool = True) -> float:
        """``now`` is the caller's own stamp of this moment where it
        has one already (the stamp used is returned); ``annotate=False``
        keeps the seconds and leaves the profiler's timeline without
        the stretch."""
        if now is None:
            now = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        key = self._key
        if key is not None:
            self._acc[key] = self._acc.get(key, 0.0) + now - self._t
        self._key = (state if state is None
                     else state.partition(".")[0] if state[:5] == "wait_"
                     else "work")
        self._state = state
        self._t = now
        if (annotate and self._annotates and state is not None
                and _profiling()):
            self._ann = _annotation(self._prefix + state)
        if now - self._flushed >= self.FLUSH_S or state is None:
            self._flush(now)
        return now

    def where(self) -> tuple[str | None, float]:
        """The stretch the thread is in and for how long (read from
        another thread: a moment's view, good enough for a dump)."""
        return self._state, round(time.perf_counter() - self._t, 4)

    def _flush(self, now: float) -> None:
        self._flushed = now
        for state, sec in self._acc.items():
            metrics.inc("evam_engine_thread_seconds", sec,
                        {**self._labels, "state": state})
        self._acc.clear()
        if self._cpu0 is not None:
            cpu = time.thread_time()
            if self._cpu0 >= 0.0:
                metrics.inc("evam_engine_thread_cpu_seconds",
                            cpu - self._cpu0, self._labels)
            self._cpu0 = cpu
        if self._ledger is not None:
            self._ledger.flush()


class _NoSpans:
    """``ThreadSpans`` with tracing off: nothing stamped or kept."""

    __slots__ = ()

    def to(self, state, now=None, annotate=True) -> None:
        pass

    def where(self) -> tuple[None, float]:
        return None, 0.0


_NO_SPANS = _NoSpans()


def thread_spans(engine: str, thread: str, cpu: bool = False,
                 ledger: "IdleLedger | None" = None,
                 annotates: bool = True) -> "ThreadSpans | _NoSpans":
    return (ThreadSpans(engine, thread, cpu, ledger, annotates)
            if active() is not None else _NO_SPANS)


#: where a batch is during each span of its clock up to its launch: in
#: the dispatcher's staging, on its way to the device, or in the launch
_IDLE_WHERE = {"slot_write": "stage", "seal": "stage",
               "h2d_issue": "upload", "wait_launcher": "upload",
               "wait_slot": "upload", "h2d_wait": "upload",
               "launch": "launch"}


def divide_idle(since: float, until: float, items,
                clock: StageClock) -> dict[tuple[str, str], float]:
    """``[since, until)``, in which an engine had no program on the
    device, by where the batch launched at ``until`` was, as ``(where,
    stage) -> seconds``. Up to the earliest ``t_submit`` of ``items`` no
    frame of the batch had reached the engine (``upstream``); up to the
    moment the dispatcher took them (the head's submit plus the clock's
    ``submit_wait``) they lay in the class queue, for the deadline's
    fill or for the dispatcher (``queued``); then the clock's spans as
    they lie, each clipped to the stretch: ``stage`` (the wait for a
    free staging block before the first span, ``slot_write``, ``seal``),
    ``upload`` (``h2d_issue`` to ``h2d_wait``), ``launch`` (the call,
    and the launcher's own steps between the spans: ``bookkeep``). The
    parts add up to the stretch."""
    out: dict[tuple[str, str], float] = {}
    at = since

    def upto(t: float, where: str, stage: str) -> None:
        nonlocal at
        t = min(t, until)
        if t > at:
            out[where, stage] = out.get((where, stage), 0.0) + t - at
            at = t

    upto(min(it.t_submit for it in items), "upstream", "upstream")
    upto(items[0].t_submit + clock["submit_wait"], "queued", "queued")
    before = ("stage", "wait_staging")
    for name, t0, dur in clock.spans:
        upto(t0, *before)
        upto(t0 + dur, _IDLE_WHERE.get(name, "launch"), name)
        before = ("launch", "bookkeep")
    upto(until, *before)
    return out


class IdleLedger:
    """One batch engine's seconds with no program on the device by where
    the batch that ended each stretch was (``divide_idle``), summed on
    the launcher's thread and flushed with its ``ThreadSpans``. It is
    the ENGINE's idle: two engines that share a chip each keep their
    own, and the chip idles only where both do."""

    __slots__ = ("_engine", "_acc")

    def __init__(self, engine: str) -> None:
        self._engine = engine
        # each ``where`` is there from the start, at 0: an engine that
        # never ran dry reads 0, a build without the ledger nothing
        self._acc: dict[tuple[str, str], float] = {
            ("upstream", "upstream"): 0.0, ("queued", "queued"): 0.0,
            ("stage", "slot_write"): 0.0, ("upload", "h2d_issue"): 0.0,
            ("launch", "launch"): 0.0}
        self.flush()

    def add(self, since: float, until: float, items,
            clock: StageClock) -> None:
        acc = self._acc
        for key, sec in divide_idle(since, until, items, clock).items():
            acc[key] = acc.get(key, 0.0) + sec

    def flush(self) -> None:
        for (where, stage), sec in self._acc.items():
            metrics.inc("evam_engine_idle_seconds", sec,
                        {"engine": self._engine, "where": where,
                         "stage": stage})
        self._acc.clear()


def idle_ledger(engine: str) -> IdleLedger | None:
    return IdleLedger(engine) if active() is not None else None


def annotate(name: str):
    """A profiler annotation for one stretch on the calling thread
    (``with annotate("evam.runner.wire"): ...``) while a capture runs,
    else nothing; callers hold a frame trace, so tracing is on."""
    annotation, profiling = _profiler_hooks()
    return annotation(name) if profiling() else _NO_ANNOTATION


class FrameTrace:
    """One frame's span tree, mutated lock-free by its owning threads.

    Spans are ``(name, t0, dur_s, attrs|None)`` tuples appended with
    list.append (atomic under the GIL); the ring only ever reads a
    trace after ``finish`` or via snapshot copies, so no lock is
    needed on the hot path."""

    __slots__ = ("trace_id", "stream_id", "seq", "priority", "t0",
                 "due_t", "status", "spans", "bids")

    def __init__(self, trace_id: str, stream_id: str, seq: int,
                 priority: str, t0: float,
                 due_t: float | None = None) -> None:
        self.trace_id = trace_id
        self.stream_id = stream_id
        self.seq = seq
        self.priority = priority
        #: the feed (ingest) stamp, and the paced source's own due time
        #: for the frame where it has one; both ``perf_counter``
        self.t0 = t0
        self.due_t = due_t
        self.status = "open"
        self.spans: list[tuple] = []
        self.bids: list[str] = []

    def add_span(self, name: str, t0: float, dur: float,
                 attrs: dict | None = None) -> None:
        self.spans.append((name, t0, dur, attrs))

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "stream": self.stream_id,
            "seq": self.seq,
            "class": self.priority,
            "t0": self.t0,
            "due_t": self.due_t,
            "status": self.status,
            "bids": list(self.bids),
            "spans": [
                {"name": name, "t0": t0, "dur_s": dur,
                 **({"attrs": attrs} if attrs else {})}
                for (name, t0, dur, attrs) in self.spans
            ],
        }


class TraceRing:
    """Bounded ring of retained frame traces + batch records with
    tail-based sampling. One per process, memoized like the fault
    injector (``active()``)."""

    SHARED_UNDER = {
        "_frames": "_lock",
        "_batches": "_lock",
        "_pending": "_lock",
        "_tick": "_lock",
        "retained_count": "_lock",
        "dropped_count": "_lock",
    }

    #: in-flight batch records awaiting completion; bounded so an
    #: abandoned (wedged) engine's orphans can't grow the map forever
    PENDING_MAX = 256

    def __init__(self, enabled: bool = True, sample_n: int = 16,
                 ring: int = 1024, slow_ms: float = 250.0,
                 flight_dir: str = "", flight_n: int = 256,
                 flight_max_files: int = 64,
                 flight_max_bytes: int = 64 * 1024 * 1024) -> None:
        self.enabled = enabled
        self.sample_n = max(1, int(sample_n))
        self.ring = max(1, int(ring))
        self.slow_ms = float(slow_ms)
        self.flight_dir = flight_dir
        self.flight_n = max(1, int(flight_n))
        #: flight-recorder disk bound: a flapping engine quarantining
        #: in a loop must not fill the artifact volume. Oldest-first
        #: rotation after every dump; 0 = unbounded (either axis).
        self.flight_max_files = int(flight_max_files)
        self.flight_max_bytes = int(flight_max_bytes)
        self._lock = threading.Lock()
        self._frames: deque = deque(maxlen=self.ring)
        self._batches: deque = deque(maxlen=self.ring)
        self._pending: dict[tuple[str, int], dict] = {}
        self._tick = 0
        self.retained_count = 0
        self.dropped_count = 0

    # -- frame lifecycle ------------------------------------------------

    def mint(self, stream_id: str, seq: int, priority: str,
             t0: float | None = None,
             due_t: float | None = None) -> FrameTrace:
        trace_id = f"{_TRACE_PREFIX}-{next(_trace_seq)}"
        return FrameTrace(trace_id, stream_id, seq, priority,
                          time.perf_counter() if t0 is None else t0, due_t)

    def finish(self, ft: FrameTrace, status: str) -> None:
        """Tail-based retention decision: error/shed/deadline-miss
        frames and the slowest tail always land in the ring; healthy
        frames are kept 1-in-sample_n."""
        if ft.status != "open":  # fan-out children share one trace
            return
        ft.status = status
        dur_ms = (time.perf_counter() - ft.t0) * 1e3
        if status in ("error", "shed", "deadline_miss"):
            reason = status
        elif dur_ms >= self.slow_ms:
            reason = "slow"
        else:
            reason = None
        with self._lock:
            if reason is None:
                self._tick += 1
                if self._tick % self.sample_n == 0:
                    reason = "sampled"
            if reason is None:
                self.dropped_count += 1
            else:
                self.retained_count += 1
                self._frames.append(ft)
        if reason is None:
            metrics.inc("evam_trace_dropped")
        else:
            metrics.inc("evam_trace_retained", labels={"reason": reason})

    # -- batch lifecycle ------------------------------------------------

    def batch_begin(self, engine: str, bid: int, items, bucket: int,
                    n: int, clock: StageClock, device: str = "") -> None:
        """Register an in-flight batch. ``items`` are duck-typed work
        items carrying an optional ``.trace`` attribute; ``clock`` is
        stored BY REFERENCE — the dispatch path keeps appending to its
        ``spans``, so a flight dump of a still-pending batch reads the
        stages completed so far. The batch begins where its first span
        does: when the dispatcher took its items."""
        frames = []
        for it in items:
            ft = getattr(it, "trace", None)
            if ft is not None:
                frames.append(ft.trace_id)
                ft.bids.append(f"{engine}#{bid}")
        spans = getattr(clock, "spans", None)
        rec = {
            "engine": engine, "bid": bid, "bucket": bucket, "n": n,
            "device": device,
            "t0": spans[0][1] if spans else time.perf_counter(),
            "wall_t": time.time(), "frames": frames, "clock": clock,
            "status": "in_flight", "dur_s": None,
        }
        with self._lock:
            self._pending[(engine, bid)] = rec
            while len(self._pending) > self.PENDING_MAX:
                self._pending.pop(next(iter(self._pending)))

    def batch_complete(self, engine: str, bid: int, items=(),
                       status: str = "ok") -> None:
        """Retire an in-flight batch record and give every member
        frame its queue wait, the batch's spans at their real starts
        and the dispatch as a whole."""
        now = time.perf_counter()
        with self._lock:
            rec = self._pending.pop((engine, bid), None)
        t0 = None
        member: list[tuple] = []
        if rec is not None:
            t0 = rec["t0"]
            # the clock is quiescent once the batch reaches completion
            rec["spans"] = _clock_spans(rec["clock"])
            rec["clock"] = None
            rec["status"] = status
            rec["dur_s"] = now - t0
            member = [(f"engine.{name}", s0, dur, None)
                      for (name, s0, dur) in rec["spans"]]
            with self._lock:
                self._batches.append(rec)
        for it in items:
            ft = getattr(it, "trace", None)
            if ft is None:
                continue
            t_sub = getattr(it, "t_submit", None)
            if t0 is not None and t_sub is not None:
                ft.add_span("sched.queue_wait", t_sub, t0 - t_sub,
                            {"class": getattr(it, "priority", "")})
            ft.spans.extend(member)
            start = t0 if t0 is not None else now
            ft.add_span("engine.dispatch", start, now - start,
                        {"engine": engine, "bid": bid, "status": status})

    # -- readout --------------------------------------------------------

    def snapshot(self) -> tuple[list, list, list]:
        """(retained frames, completed batches, pending batches) —
        shallow copies safe to iterate outside the lock."""
        with self._lock:
            return (list(self._frames), list(self._batches),
                    [dict(rec) for rec in self._pending.values()])


def _clock_spans(clock) -> tuple:
    """A (possibly still growing) clock's timeline as it stands."""
    return tuple(getattr(clock, "spans", ()))


def last_stage(spans) -> str | None:
    """The last engine stage a batch completed — a wedged batch's
    record stops exactly where the device stopped answering. ``spans``
    is a timeline (``[name, t0, dur]`` rows) or any iterable of stage
    names; named waits between stages do not count."""
    found = None
    for sp in spans or ():
        name = sp if isinstance(sp, str) else sp[0]
        if name in STAGE_ORDER:
            found = name
    return found


# -- memoized process-global ring (same shape as obs/faults.py) ---------

_resolved: tuple[TraceRing | None] | None = None


def active() -> TraceRing | None:
    """The process TraceRing, or None when EVAM_TRACE=off. Resolved
    once from settings and memoized — the per-frame/per-batch hooks
    below cost one None-check when tracing is disabled."""
    global _resolved
    if _resolved is None:
        from evam_tpu.config.settings import get_settings

        cfg = get_settings().trace
        ring = TraceRing(
            enabled=cfg.enabled, sample_n=cfg.sample_n, ring=cfg.ring,
            slow_ms=cfg.slow_ms, flight_dir=cfg.flight_dir,
            flight_n=cfg.flight_n,
            flight_max_files=cfg.flight_max_files,
            flight_max_bytes=cfg.flight_max_bytes,
        ) if cfg.enabled else None
        _resolved = (ring,)
    return _resolved[0]


def reset_cache() -> None:
    """Drop the memoized ring (tests / settings reload)."""
    global _resolved
    _resolved = None


# -- hot-path hooks (all no-ops when tracing is off) --------------------

def start_frame(stream_id: str, seq: int, priority: str = "standard",
                t0: float | None = None,
                due_t: float | None = None) -> FrameTrace | None:
    ring = active()
    if ring is None:
        return None
    return ring.mint(stream_id, seq, priority, t0, due_t)


def finish_frame(ft: FrameTrace | None, status: str = "ok") -> None:
    if ft is None:
        return
    ring = active()
    if ring is None:
        return
    ring.finish(ft, status)


def batch_begin(engine: str, bid: int, items, bucket: int, n: int,
                clock: StageClock, device: str = "") -> None:
    ring = active()
    if ring is None:
        return
    ring.batch_begin(engine, bid, items, bucket, n, clock, device)


def batch_complete(engine: str, bid: int, items=(),
                   status: str = "ok") -> None:
    ring = active()
    if ring is None:
        return
    ring.batch_complete(engine, bid, items, status=status)


# -- Chrome trace-event rendering (GET /traces, tools/trace_dump.py) ----

def chrome_trace_events(frames: list | None = None,
                        batches: list | None = None) -> list[dict]:
    """Chrome trace-event ("X" complete events, microsecond ts/dur)
    view of the ring. Frame spans land one track per stream, each with
    the frame's ``ingest_t`` and ``due_t`` in its args; each batch
    emits one span carrying ``args.frames`` — the trace ids of its
    member frames (the batch↔frame link) — plus one child slice per
    stage (``batch-stage``) and per named wait (``batch-wait``) at its
    real start."""
    if frames is None and batches is None:
        ring = active()
        if ring is None:
            return []
        frames, done, pending = ring.snapshot()
        batches = done + pending
    events: list[dict] = []
    for ft in frames or ():
        for (name, t0, dur, attrs) in ft.spans:
            args = {"trace_id": ft.trace_id, "seq": ft.seq,
                    "class": ft.priority, "status": ft.status,
                    "ingest_t": ft.t0, "due_t": ft.due_t}
            if attrs:
                args.update(attrs)
            events.append({
                "name": name, "ph": "X", "cat": "frame",
                "ts": round(t0 * 1e6, 1), "dur": round(dur * 1e6, 1),
                "pid": "frames", "tid": ft.stream_id, "args": args,
            })
    for rec in batches or ():
        events.extend(batch_events(rec))
    return events


def batch_events(rec: dict) -> list[dict]:
    """One batch record (of the ring or of a flight dump) as its span
    and its child slices."""
    spans = rec.get("spans")
    if spans is None:
        spans = _clock_spans(rec.get("clock"))
    total = rec.get("dur_s")
    if total is None:  # pending: up to the end of its last span
        total = (spans[-1][1] + spans[-1][2] - rec["t0"]) if spans else 0.0
    pid = f"engine {rec['engine']}"
    events = [{
        "name": f"batch {rec['engine']}#{rec['bid']}", "ph": "X",
        "cat": "batch", "ts": round(rec["t0"] * 1e6, 1),
        "dur": round(total * 1e6, 1),
        "pid": pid, "tid": rec.get("device", ""),
        "args": {
            "bid": rec["bid"], "frames": list(rec.get("frames", ())),
            "bucket": rec.get("bucket"), "n": rec.get("n"),
            "device": rec.get("device", ""),
            "status": rec.get("status", ""),
            "stages": [sp[0] for sp in spans],
            "last_stage": last_stage(spans),
        },
    }]
    for (name, t0, dur) in spans:
        events.append({
            "name": name, "ph": "X",
            "cat": "batch-stage" if name in STAGE_ORDER else "batch-wait",
            "ts": round(t0 * 1e6, 1), "dur": round(dur * 1e6, 1),
            "pid": pid, "tid": f"{rec.get('device', '')}/stages",
            "args": {"bid": rec["bid"]},
        })
    return events


def traces_payload() -> dict:
    """The GET /traces response body: ring counters, one ``clock``
    pair taken together (every ``ts`` is ``perf_counter`` microseconds;
    the pair maps them onto the wall clock a client or a profiler
    capture uses) and the Chrome trace events (fixed key set so the
    route goldens stay canonical)."""
    ring = active()
    if ring is None:
        return {"enabled": False, "retained": 0, "dropped": 0,
                "frames": 0, "batches": 0, "pending": 0,
                "traceEvents": []}
    frames, done, pending = ring.snapshot()
    return {
        "enabled": True,
        "retained": ring.retained_count,
        "dropped": ring.dropped_count,
        "frames": len(frames),
        "batches": len(done),
        "pending": len(pending),
        "clock": {"perf_counter": time.perf_counter(),
                  "time_ns": time.time_ns()},
        "traceEvents": chrome_trace_events(frames, done + pending),
    }


# -- flight recorder ----------------------------------------------------

def _default_flight_dir() -> str:
    return os.path.join(tempfile.gettempdir(), "evam_flight")


def flight_dump(engine: str, reason: str,
                state: dict | None = None) -> str | None:
    """Dump the ring's last-N frame/batch records plus caller-supplied
    engine/queue state to a JSONL artifact (the supervisor calls this
    on quarantine and on the degraded transition, the freeze recorder
    after a freeze). Pending batch records read their live clock, so a
    wedged batch's row carries ``last_stage`` — where the device
    stopped answering.
    Returns the artifact path, or None when tracing is off or the
    write fails (a chaos drill must never take the supervisor down)."""
    ring = active()
    if ring is None:
        return None
    out_dir = ring.flight_dir or _default_flight_dir()
    name = re.sub(r"[^A-Za-z0-9._-]+", "_", engine) or "engine"
    frames, done, pending = ring.snapshot()
    try:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(
            out_dir,
            f"flight-{name}-{int(time.time() * 1e3)}-{next(_flight_seq)}.jsonl")
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(json.dumps({
                "type": "flight", "engine": engine, "reason": reason,
                "ts": time.time(),
                "profiler_running": profiler_running(),
                "state": state or {},
            }) + "\n")
            for rec in (done + pending)[-ring.flight_n:]:
                row = {k: v for k, v in rec.items() if k != "clock"}
                row["type"] = "batch"
                row["pending"] = rec.get("status") == "in_flight"
                if row.get("spans") is None:
                    row["spans"] = _clock_spans(rec.get("clock"))
                row["last_stage"] = last_stage(row["spans"])
                fh.write(json.dumps(row) + "\n")
            for ft in frames[-ring.flight_n:]:
                row = ft.to_dict()
                row["type"] = "frame"
                fh.write(json.dumps(row) + "\n")
    except OSError as exc:
        log.warning("flight recorder dump failed: %s", exc)
        return None
    _prune_flight_dir(out_dir, path, ring.flight_max_files,
                      ring.flight_max_bytes)
    metrics.inc("evam_flight_dumps", labels={"engine": engine})
    log.error("flight recorder: engine %s (%s) -> %s", engine, reason, path)
    return path


def _prune_flight_dir(out_dir: str, keep_path: str,
                      max_files: int, max_bytes: int) -> None:
    """Oldest-first rotation of flight-*.jsonl artifacts: an engine
    flapping through quarantines (or a chaos soak) must not grow the
    artifact volume without bound. The just-written dump is never
    pruned — the freshest post-mortem always survives. 0 disables the
    corresponding axis (EVAM_TRACE_FLIGHT_MAX_FILES / _MAX_BYTES)."""
    if max_files <= 0 and max_bytes <= 0:
        return
    try:
        entries = []
        for fn in os.listdir(out_dir):
            if not (fn.startswith("flight-") and fn.endswith(".jsonl")):
                continue
            p = os.path.join(out_dir, fn)
            try:
                st = os.stat(p)
            except OSError:
                continue  # concurrent prune/collection
            entries.append((st.st_mtime, st.st_size, p))
    except OSError as exc:
        log.warning("flight recorder rotation scan failed: %s", exc)
        return
    entries.sort()
    count = len(entries)
    total = sum(size for _, size, _ in entries)
    removed = 0
    for _, size, p in entries:
        if not ((max_files > 0 and count > max_files)
                or (max_bytes > 0 and total > max_bytes)):
            break
        if os.path.abspath(p) == os.path.abspath(keep_path):
            continue
        try:
            os.remove(p)
        except OSError:
            continue
        count -= 1
        total -= size
        removed += 1
    if removed:
        log.info("flight recorder rotated out %d artifact(s) from %s",
                 removed, out_dir)


# -- the freeze recorder ------------------------------------------------

#: live engines (anything with ``name``, ``queue_age_s()`` and
#: ``thread_states()``) that the recorder's dumps read
_watched: "weakref.WeakSet" = weakref.WeakSet()


def watch_engine(engine) -> None:
    if active() is not None:
        _watched.add(engine)


_THREAD_HEAD = re.compile(r"^(?:Current thread|Thread) (0x[0-9a-f]+)")


def _top_frames(stacks: str) -> dict[str, str]:
    """``faulthandler``'s dump -> thread name (or id) -> its innermost
    frame, ``file:line in function``."""
    names = {f"0x{t.ident:016x}": t.name for t in threading.enumerate()
             if t.ident is not None}
    out: dict[str, str] = {}
    head = None
    for line in stacks.splitlines():
        m = _THREAD_HEAD.match(line)
        if m:
            head = names.get(m.group(1), m.group(1))
        elif head is not None and line.startswith("  File "):
            out[head] = line.strip()
            head = None
    return out


class FreezeRecorder:
    """The heartbeat that names a freeze (module docstring). The thread
    needs the GIL to wake, which is the measurement. Its stack dumps
    are taken WITH the GIL, right after the wake: a watchdog that needs
    none (``faulthandler.dump_traceback_later``) walks the frames of
    threads that run, and killed the server with SIGSEGV twice on the
    chip (PERF.md section 6, PR 25)."""

    PERIOD_S = 0.25
    #: a wake this late is recorded in ``evam_freeze_seconds``
    LATE_S = 0.1
    #: the lateness, and the queue age, that writes a dump
    FREEZE_S = 1.0
    STACKS_MAX_BYTES = 1 << 20

    def __init__(self, flight_dir: str) -> None:
        self.path = os.path.join(flight_dir, "freeze-stacks.log")
        self._fh = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._gc_t0 = 0.0
        #: (t0, dur_s, generation) of finished collections, appended by
        #: the gc callback and drained by the heartbeat
        self._gc_log: deque = deque(maxlen=4096)
        #: collections of >= 10 ms, for the dumps
        self._gc_slow: deque = deque(maxlen=32)
        #: a stall has one dump: set while an engine's queue stays old
        self._stall_dumped = False

    def start(self) -> None:
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        self._fh = open(self.path, "a", encoding="utf-8")
        metrics.declare("evam_freeze_seconds")
        for gen in range(3):
            metrics.declare("evam_gc_pause_seconds", {"gen": str(gen)})
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(
            target=self._run, name="evam-heartbeat", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if self._fh is not None:
            self._fh.close()

    def _on_gc(self, phase: str, info: dict) -> None:
        # Runs inside the collector, on whichever thread triggered it
        # and possibly inside the registry's lock: two stamps and a
        # deque append, nothing that could take a lock.
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self._gc_log.append((self._gc_t0,
                                 time.perf_counter() - self._gc_t0,
                                 info["generation"]))

    def _run(self) -> None:
        while True:
            cpu0 = time.thread_time()
            t0 = time.perf_counter()
            stopping = self._stop.wait(self.PERIOD_S)
            late = time.perf_counter() - t0 - self.PERIOD_S
            while self._gc_log:
                rec = self._gc_log.popleft()
                metrics.observe("evam_gc_pause_seconds", rec[1],
                                {"gen": str(rec[2])})
                if rec[1] >= 0.01:
                    self._gc_slow.append(rec)
            if stopping:
                break
            if late >= self.LATE_S:
                self._on_late(late, t0, time.thread_time() - cpu0)
            else:
                self._check_queues(t0)

    def _gc_since(self, t0: float) -> list[dict]:
        return [{"gen": gen, "at_s": round(g0 - t0, 4),
                 "dur_s": round(dur, 4)}
                for (g0, dur, gen) in self._gc_slow if g0 + dur >= t0]

    def _on_late(self, late: float, t0: float, spin_s: float) -> None:
        """``spin_s``: this thread's own CPU seconds over the beat. A
        thread that waits for the GIL wakes every 5 ms to ask for it
        (4-6 ms of CPU per second, measured); one that had no CPU, or
        whose process stood still, used none."""
        metrics.observe("evam_freeze_seconds", late)
        gc_in = self._gc_since(t0)
        log.warning("heartbeat woke %.0f ms late (%.1f ms of its own CPU "
                    "in that beat); collections of >= 10 ms: %s",
                    late * 1e3, spin_s * 1e3, gc_in or "none")
        if late < self.FREEZE_S:
            return
        # Three cases. ``process_stopped``: this thread did not even
        # contend for the GIL while it was late, so it was not running:
        # the kernel, the hypervisor, a stop signal stood the process
        # still. ``gil_held_by_gc``: the collections listed cover half
        # the lateness. ``gil_held``: one thread kept the GIL inside a
        # call; the stacks are of the moment after, so look in them
        # for the thread whose stack holds a call that can keep it.
        if spin_s < 0.001 * late:
            verdict = "process_stopped"
        elif sum(g["dur_s"] for g in gc_in) >= 0.5 * late:
            verdict = "gil_held_by_gc"
        else:
            verdict = "gil_held"
        self._dump("freeze", {"late_s": round(late, 4), "verdict": verdict,
                              "gil_wait_cpu_s": round(spin_s, 5),
                              "gc": gc_in})

    def _check_queues(self, t0: float) -> None:
        """The other way a server stands still: this thread woke on
        time, so Python runs, yet an engine's oldest item has waited a
        second — an engine thread sits in a call that released the GIL
        (a transfer, a launch, a readback that the runtime does not
        return from). One ``stall`` dump per such stretch."""
        if max((eng.queue_age_s() for eng in list(_watched)),
               default=0.0) < self.FREEZE_S:
            self._stall_dumped = False
        elif not self._stall_dumped:
            self._stall_dumped = True
            self._dump("stall", {"verdict": "interpreter_alive",
                                 "gc": self._gc_since(t0 - self.FREEZE_S)})

    def _dump(self, reason: str, state: dict) -> None:
        """One flight dump: ``state`` plus every watched engine's queue
        age and its threads' stretches (which stage, for how long) and
        every thread's stack, taken here with the GIL held."""
        fd = self._fh.fileno()
        size0 = os.fstat(fd).st_size
        faulthandler.dump_traceback(file=self._fh, all_threads=True)
        with open(self.path, "rb") as fh:
            fh.seek(size0)
            stacks = fh.read(65536).decode("utf-8", "replace")
        ages, threads = {}, {}
        for eng in list(_watched):
            ages[eng.name] = round(eng.queue_age_s(), 4)
            threads[eng.name] = eng.thread_states()
        flight_dump("process", reason, state={
            **state, "queue_age_s": ages, "threads": threads,
            "top_frames": _top_frames(stacks), "stacks": stacks})
        if os.fstat(fd).st_size > self.STACKS_MAX_BYTES:
            os.ftruncate(fd, 0)


_recorder: FreezeRecorder | None = None


def start_freeze_recorder() -> FreezeRecorder | None:
    """Start the process's one recorder (None with tracing off)."""
    global _recorder
    ring = active()
    if ring is None or _recorder is not None:
        return _recorder
    _recorder = FreezeRecorder(ring.flight_dir or _default_flight_dir())
    _recorder.start()
    return _recorder


def stop_freeze_recorder() -> None:
    global _recorder
    if _recorder is not None:
        _recorder.stop()
        _recorder = None


# -- profiler glue ------------------------------------------------------

def maybe_start_profiler(enabled: bool, port: int = _PROFILER_PORT) -> bool:
    """Start the jax.profiler server once when PROFILING_MODE is on."""
    global _profiler_started
    if not enabled or _profiler_started:
        return _profiler_started
    import jax

    jax.profiler.start_server(port)
    _profiler_started = True
    log.info("jax profiler server on :%d (PROFILING_MODE)", port)
    return True


def profiler_running() -> bool:
    """Whether the jax.profiler server was started this process —
    recorded in every flight-recorder header so a post-mortem knows
    whether a device timeline capture was possible."""
    return _profiler_started


def init_observability(settings) -> None:
    """One-call runtime bootstrap for both serve entrypoints:
    compilation cache, optional profiler server, freeze recorder
    (``stop_freeze_recorder`` when the server stops)."""
    configure_compilation_cache()
    maybe_start_profiler(settings.profiling_mode)
    start_freeze_recorder()


#: the in-checkout default. The directory is part of every cache
#: entry's key, so it is one fixed path — never derived from a
#: tempdir, uid, pid or the clock, or a restart would never hit.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compilation_cache() -> str:
    """Persist XLA executables across restarts (SURVEY.md §5.4 — the
    reference's analogue is the OpenCL cl_cache, Dockerfile:77-78).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    this sets no directory; otherwise the cache lives at
    ``COMPILE_CACHE_DIR``. Returns the directory in use."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    log.info("XLA compilation cache at %s", cache_dir)
    return cache_dir
