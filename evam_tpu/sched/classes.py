"""Priority classes and per-class work queues for the QoS layer.

OCTOPINF (PAPERS.md) argues that an edge video-analytics server must
schedule by workload class: a realtime camera and a bulk file re-run
have opposite latency/throughput needs, and one global FIFO + one
global batch deadline serves both badly. This module defines the
three classes the scheduler speaks —

* ``realtime`` — live cameras; small batch-formation deadline, tight
  staleness budget, drained first;
* ``standard`` — the default, and the one class every submit joins
  with the scheduler off;
* ``batch``    — bulk/offline re-runs; big batch-formation deadline
  (fill large buckets), generous staleness budget, first to shed.

— plus the two data structures the rest of ``evam_tpu.sched`` builds
on: ``SchedConfig`` (the resolved knob set, kept OUT of the hot loop
— tf.data's lesson from PAPERS.md: policy is data, the loop only
reads it) and ``ClassQueues`` (per-class FIFOs with a
starvation-proof realtime-first pick — the queues every
``BatchEngine.submit`` joins).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any

#: scheduling classes, highest priority first (drain order)
PRIORITIES = ("realtime", "standard", "batch")

DEFAULT_PRIORITY = "standard"

#: consecutive times a non-empty class may be passed over before it
#: MUST be picked (the starvation guard of the weighted pick). The
#: ratios are the effective drain weights under contention:
#: realtime gets ~4x standard and ~12x batch.
STARVATION_LIMITS = {"standard": 4, "batch": 12}


def coerce_priority(value: Any, default: str = DEFAULT_PRIORITY) -> str:
    """Best-effort priority normalization for restored state
    (evam_tpu/state checkpoints): a sched class decoded from a
    possibly stale or corrupted checkpoint must never raise — an
    unknown value falls back to ``default`` instead."""
    if isinstance(value, str) and value.strip().lower() in PRIORITIES:
        return value.strip().lower()
    return default


def validate_priority(value: Any) -> str:
    """Normalize + validate a request/spec ``priority`` value."""
    if not isinstance(value, str):
        raise ValueError(
            f"priority must be one of {'|'.join(PRIORITIES)}, "
            f"got {value!r}")
    prio = value.strip().lower()
    if prio not in PRIORITIES:
        raise ValueError(
            f"unknown priority {value!r}; valid values: "
            f"{'|'.join(PRIORITIES)}")
    return prio


@dataclasses.dataclass(frozen=True)
class SchedConfig:
    """Resolved scheduler knobs (config/settings.py ``SchedSettings``
    → this runtime view; see that class for the EVAM_SCHED_* env
    surface). Frozen: the dispatcher and admission controller read it
    lock-free."""

    enabled: bool = True
    #: projected-utilization ceiling for admission (0 disables
    #: admission control; classes get headroom-scaled ceilings —
    #: sched/admission.py CLASS_HEADROOM)
    admit_util: float = 0.85
    #: operator-declared serving capacity in frames/s (0 = derive it
    #: from live EngineStats; see AdmissionController.capacity_fps)
    capacity_fps: float = 0.0
    #: assumed per-stream demand when a start request declares no fps
    default_fps: float = 30.0
    #: per-class batch-formation deadline (ms) — replaces the single
    #: EVAM_BATCH_DEADLINE_MS when scheduling is on
    deadline_ms: dict[str, float] = dataclasses.field(
        default_factory=lambda: {
            "realtime": 4.0, "standard": 8.0, "batch": 25.0})
    #: per-class staleness budget (ms): frames older than this at
    #: dispatch are shed (0 = never shed that class)
    staleness_ms: dict[str, float] = dataclasses.field(
        default_factory=lambda: {
            "realtime": 200.0, "standard": 1000.0, "batch": 5000.0})

    def deadline_s(self, priority: str) -> float:
        return self.deadline_ms.get(priority, 8.0) / 1e3

    def staleness_s(self) -> dict[str, float]:
        return {c: ms / 1e3 for c, ms in self.staleness_ms.items()}

    @classmethod
    def from_settings(cls, s,
                      standard_deadline_ms: float | None = None
                      ) -> "SchedConfig":
        """Build from config.settings.SchedSettings.

        ``standard_deadline_ms``: the engine-level
        EVAM_BATCH_DEADLINE_MS. Unless the operator explicitly set
        EVAM_SCHED_DEADLINE_MS_STANDARD, the ``standard`` class
        follows it — turning the scheduler on must not silently
        repeal a tuned global batch deadline (the satellite audit's
        point: that knob must keep reaching the dispatcher)."""
        std = s.deadline_ms_standard
        if (standard_deadline_ms is not None
                and "deadline_ms_standard" not in s.model_fields_set):
            std = standard_deadline_ms
        return cls(
            enabled=s.enabled,
            admit_util=s.admit_util,
            capacity_fps=s.capacity_fps,
            default_fps=s.default_fps,
            deadline_ms={
                "realtime": s.deadline_ms_realtime,
                "standard": std,
                "batch": s.deadline_ms_batch,
            },
            staleness_ms={
                "realtime": s.staleness_ms_realtime,
                "standard": s.staleness_ms_standard,
                "batch": s.staleness_ms_batch,
            },
        )

    @classmethod
    def disabled(cls) -> "SchedConfig":
        return cls(enabled=False, admit_util=0.0)


class ClassQueues:
    """Per-class FIFO queues with a starvation-proof realtime-first
    pick — what ``BatchEngine.submit`` puts into and its dispatcher
    drains.

    Items must expose ``t_submit`` (perf_counter at enqueue) and
    ``future`` (failable on drain) — the engine's ``_WorkItem``
    contract. All state is guarded by one condition variable; the
    enqueue path does a deque append + notify, so submit-side cost
    stays O(1).

    Pick policy: the highest-priority non-empty class wins, EXCEPT
    that a class passed over ``STARVATION_LIMITS[cls]`` consecutive
    times is served first (lowest class checked first so ``batch``
    cannot starve behind a starving ``standard``). Under saturation
    this degenerates to weighted round-robin with weights ~12/3/1;
    with an idle realtime lane it is exactly realtime-first.
    """

    #: every queue mutation happens inside ``with self._cv:`` — the
    #: condition variable doubles as the state lock (lock-discipline
    #: pass enforces it).
    SHARED_UNDER = {
        "_q": "_cv",
        "_starve": "_cv",
        "_closed": "_cv",
    }

    def __init__(self, starvation_limits: dict[str, int] | None = None):
        self._limits = dict(starvation_limits or STARVATION_LIMITS)
        self._cv = threading.Condition()
        self._q: dict[str, deque] = {c: deque() for c in PRIORITIES}
        self._starve = {c: 0 for c in PRIORITIES}
        self._closed = False

    # ------------------------------------------------------ submit side

    def put(self, priority: str, item) -> None:
        with self._cv:
            if self._closed:
                raise RuntimeError("scheduler queues are closed")
            self._q[priority].append(item)
            self._cv.notify_all()

    # -------------------------------------------------- dispatcher side

    def pick(self, timeout: float) -> str | None:
        """Block until any class has work (or ``timeout``); return the
        chosen class per the starvation-aware priority policy, or
        None on timeout / closed-and-empty."""
        deadline = time.perf_counter() + timeout
        with self._cv:
            while True:
                nonempty = [c for c in PRIORITIES if self._q[c]]
                if nonempty:
                    break
                if self._closed:
                    return None
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    return None
                self._cv.wait(remaining)
            chosen = None
            # most-starved lowest class first: batch must not starve
            # behind a starving standard
            for c in reversed(PRIORITIES):
                limit = self._limits.get(c)
                if limit and c in nonempty and self._starve[c] >= limit:
                    chosen = c
                    break
            if chosen is None:
                chosen = nonempty[0]
            for c in nonempty:
                if c != chosen:
                    self._starve[c] += 1
            self._starve[chosen] = 0
            return chosen

    def collect(self, priority: str, max_n: int,
                deadline_s: float) -> list:
        """Form one batch from ``priority``'s queue: wait until it
        holds ``max_n`` items or until ``deadline_s`` past the HEAD
        item's submit time — a backlogged queue dispatches a full
        bucket immediately, a trickle dispatches at the deadline."""
        with self._cv:
            dq = self._q[priority]
            if not dq:
                return []
            deadline = dq[0].t_submit + deadline_s
            while len(dq) < max_n and not self._closed:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                self._cv.wait(remaining)
            return [dq.popleft() for _ in range(min(max_n, len(dq)))]

    def pop_expired(self, priority: str, min_t_submit: float) -> list:
        """Remove and return the head items submitted before
        ``min_t_submit`` — the oldest-first shed primitive (FIFO order
        means every expired item sits at the head; the fresh tail
        survives — freshest-frame-wins)."""
        out = []
        with self._cv:
            dq = self._q[priority]
            while dq and dq[0].t_submit < min_t_submit:
                out.append(dq.popleft())
        return out

    # ------------------------------------------------------- lifecycle

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def drain(self) -> list:
        """Remove and return every queued item (stop/stall/abandon:
        the engine fails their futures)."""
        out = []
        with self._cv:
            for dq in self._q.values():
                out.extend(dq)
                dq.clear()
        return out

    # -------------------------------------------------- introspection

    def empty(self) -> bool:
        with self._cv:
            return not any(self._q.values())

    def depth(self) -> int:
        with self._cv:
            return sum(len(dq) for dq in self._q.values())

    def depth_by_class(self) -> dict[str, int]:
        with self._cv:
            return {c: len(dq) for c, dq in self._q.items()}

    def oldest_age_s(self, now: float | None = None) -> float:
        now = time.perf_counter() if now is None else now
        with self._cv:
            heads = [dq[0].t_submit for dq in self._q.values() if dq]
        return max(0.0, now - min(heads)) if heads else 0.0
