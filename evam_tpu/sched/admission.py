"""Admission control: reject over-capacity starts at the REST edge.

Before this module ``POST /pipelines/{name}/{version}`` admitted
every start unconditionally; overload showed up only later and only
indirectly, as queue growth, watchdog stalls and uniformly blown
latency for EVERY stream. OCTOPINF (PAPERS.md) makes the standard
serving argument: an edge box has a knowable frame budget, and the
honest answer to a start request beyond it is an immediate 503 with
``Retry-After`` — not a silent oversubscription that degrades the
streams already admitted.

The capacity model stays out of the hot loop (tf.data's policy/
mechanism split, PAPERS.md) and is driven by observed engine timings:

    capacity_fps = min over engines of
        batches/s (1 / per-batch device-path seconds, from the PR-1
        stage clock: h2d issue + wait + launch + readback residual)
        x mean occupancy x top bucket

i.e. "what the slowest shared engine delivers if every batch were as
full as the measured mix". Operators can pin it instead with
``EVAM_SCHED_CAPACITY_FPS``. Demand is the sum of admitted streams'
DECLARED fps (request ``fps`` field, default
``EVAM_SCHED_DEFAULT_FPS``). A start is rejected when projected
utilization exceeds the class ceiling — ``EVAM_SCHED_ADMIT_UTIL``
scaled by CLASS_HEADROOM, so ``batch`` is turned away first and
``realtime`` last. A cold hub (no declared capacity, no steady-state
batch on the stage clock yet) admits everything: you cannot model
what you have not run.
"""

from __future__ import annotations

import math
import threading
import uuid

from evam_tpu.obs import get_logger, metrics
from evam_tpu.sched.classes import PRIORITIES, SchedConfig

log = get_logger("sched.admission")

#: fraction of admit_util each class may fill: under pressure the
#: ceiling is hit by batch first, then standard, then realtime — the
#: admission-side expression of the class ladder.
CLASS_HEADROOM = {"realtime": 1.0, "standard": 0.85, "batch": 0.6}

#: device-path stages of the per-batch clock (engine/ringbuf.STAGES)
#: that bound the serial service time of one batch. With the
#: pipelined transfer h2d_wait and readback are residuals — honest
#: inputs here: overlapped copy time must not be double-counted
#: against capacity.
_SERVICE_STAGES = ("h2d_issue", "h2d_wait", "launch", "readback")


class AdmissionError(RuntimeError):
    """Start rejected for capacity: HTTP 503 + Retry-After."""

    def __init__(self, priority: str, util: float, ceiling: float,
                 retry_after_s: float):
        self.priority = priority
        self.util = util
        self.ceiling = ceiling
        self.retry_after_s = retry_after_s
        super().__init__(
            f"admission rejected: projected utilization {util:.2f} "
            f"exceeds the {priority}-class ceiling {ceiling:.2f}; "
            f"retry after {retry_after_s:.0f}s"
        )


class _Ticket:
    """One admitted stream's capacity reservation. ``release`` is
    idempotent — it runs from both the instance-finish cleanup chain
    and the start-failure unwind."""

    __slots__ = ("_ctrl", "key", "priority", "fps", "_released")

    def __init__(self, ctrl: "AdmissionController", key: str,
                 priority: str, fps: float):
        self._ctrl = ctrl
        self.key = key
        self.priority = priority
        self.fps = fps
        self._released = False

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        self._ctrl._release(self.key)


class AdmissionController:
    """Tracks admitted demand vs modeled capacity for one hub.

    Duck-types the hub: needs only ``hub.stats()`` (per-engine
    batches / mean_occupancy / stage_ms from EngineStats) and
    ``hub.max_batch``. Disabled (``cfg.enabled`` False or
    ``admit_util`` <= 0) it admits everything but still counts
    per-class admissions so the bench contract line and /scheduler
    stay populated.
    """

    #: admit/release run on stream threads, snapshots on the server
    #: thread — mutations must hold ``_lock`` (lock-discipline pass).
    SHARED_UNDER = {
        "_streams": "_lock",
        "_admitted": "_lock",
        "_rejected": "_lock",
    }

    def __init__(self, hub, cfg: SchedConfig):
        self.hub = hub
        self.cfg = cfg
        self._lock = threading.Lock()
        #: ticket key -> (priority, fps)
        self._streams: dict[str, tuple[str, float]] = {}
        #: reset-proof counters (metrics.reset() in bench windows must
        #: not erase admission history)
        self._admitted = {c: 0 for c in PRIORITIES}
        self._rejected = {c: 0 for c in PRIORITIES}

    # ------------------------------------------------------------- API

    def admit(self, priority: str, fps: float) -> _Ticket:
        """Reserve capacity for one stream or raise AdmissionError."""
        enforcing = self.cfg.enabled and self.cfg.admit_util > 0
        if enforcing:
            cap = self.capacity_fps()
            if cap > 0:
                util = (self.effective_demand_fps() + fps) / cap
                ceiling = self.cfg.admit_util * CLASS_HEADROOM.get(
                    priority, 1.0)
                if util > ceiling:
                    retry_after = self._retry_after_s(util, ceiling)
                    with self._lock:
                        self._rejected[priority] += 1
                    metrics.inc("evam_sched_rejected",
                                labels={"class": priority})
                    log.warning(
                        "rejected %s-class start (%.0f fps): projected "
                        "util %.2f > ceiling %.2f (capacity %.0f fps, "
                        "post-gate demand %.0f fps)", priority, fps, util,
                        ceiling, cap, self.effective_demand_fps(),
                    )
                    raise AdmissionError(priority, util, ceiling,
                                         retry_after)
        key = uuid.uuid4().hex
        with self._lock:
            self._streams[key] = (priority, fps)
            self._admitted[priority] += 1
        metrics.inc("evam_sched_admitted", labels={"class": priority})
        return _Ticket(self, key, priority, fps)

    def _release(self, key: str) -> None:
        with self._lock:
            self._streams.pop(key, None)

    # -------------------------------------------------- capacity model

    def demand_fps(self) -> float:
        with self._lock:
            return sum(fps for _, fps in self._streams.values())

    def effective_demand_fps(self) -> float:
        """Declared demand minus the motion gate's recent
        skipped-frames/s (stages/gate.py registry): frames the gate is
        provably not submitting don't consume engine capacity, so
        admission headroom grows while scenes are static. The credit
        is a live windowed rate — when a static scene starts moving,
        it decays within the rate window and utilization climbs back
        toward the declared projection."""
        from evam_tpu.stages.gate import registry as gate_registry

        return max(0.0, self.demand_fps() - gate_registry.skipped_fps())

    def capacity_fps(self) -> float:
        """Declared capacity, or the bottleneck projection from live
        stats; 0 = unknown (cold hub — admit).

        Fleet-aware aggregation (evam_tpu/fleet/): each stats row
        derives ITS OWN capacity from its own EngineStats (per-chip
        service time × per-chip batch fill), rows are summed within
        their ``group`` (the shards of one engine key are parallel
        capacity, Σ shards — not independent bottlenecks), and the
        fleet capacity is the min ACROSS groups (a pipeline is still
        bounded by its slowest engine kind). A live shard without a
        sample counts as the mean of its group's measured shards (on
        the four-chip v5e one sampled shard stood for its whole
        group). Single-chip rows are their own group, so
        EVAM_FLEET=off reproduces the old bottleneck-engine number
        exactly."""
        if self.cfg.capacity_fps > 0:
            return self.cfg.capacity_fps
        measured: dict[str, list[float]] = {}
        unmeasured: dict[str, int] = {}
        for key, stats in self.hub.stats().items():
            group = stats.get("group") or key
            cap = self._row_capacity_fps(stats)
            if cap > 0:
                measured.setdefault(group, []).append(cap)
            elif (stats.get("shard") not in (None, "mesh")
                  and stats.get("state", "running") == "running"):
                # a live fleet shard the clock has not sampled yet (no
                # stream hashed to it since warmup): the same program
                # on the same kind of chip as its measured siblings,
                # not zero capacity
                unmeasured[group] = unmeasured.get(group, 0) + 1
        group_caps = [
            sum(caps) + unmeasured.get(group, 0) * sum(caps) / len(caps)
            for group, caps in measured.items()
        ]
        return min(group_caps) if group_caps else 0.0

    def _row_capacity_fps(self, stats: dict) -> float:
        """One stats row's items per device-path second; 0 = the
        steady-state stage clock has no sample of it."""
        own = stats.get("capacity_fps")
        if own is not None:
            # an engine whose requests take many steps (engine/generate.py)
            # models its own: items per second with every slot busy
            return float(own)
        batches = stats.get("batches")
        if not batches:
            return 0.0
        stage_ms = stats.get("stage_ms") or {}
        service_ms = sum(stage_ms.get(s, 0.0) for s in _SERVICE_STAGES)
        if service_ms <= 0:
            return 0.0
        # honest occupancy (the ragged-batching satellite): real
        # items per dispatched batch, straight from the engine
        # counters. The old mean_occupancy × top-bucket projection
        # overstated capacity whenever traffic landed in small
        # buckets (a FULL bucket-4 batch read as occupancy 1.0 of
        # the 128-slot shape). Stats rows without an item count
        # (declared/faked hubs) keep the legacy projection.
        items = stats.get("items")
        if items:
            per_batch = items / batches
        else:
            occ = max(float(stats.get("mean_occupancy", 0.0)), 1e-3)
            per_batch = occ * self.hub.max_batch
        return (1e3 / service_ms) * per_batch

    def utilization(self) -> float:
        cap = self.capacity_fps()
        return self.effective_demand_fps() / cap if cap > 0 else 0.0

    @staticmethod
    def _retry_after_s(util: float, ceiling: float) -> float:
        """Back off proportionally to how far past the ceiling the
        projection landed — a mild hint, bounded [1, 30]s."""
        over = util / max(ceiling, 1e-6)
        return float(min(30, max(1, math.ceil(2.0 * over))))

    # ------------------------------------------------- introspection

    def counts(self) -> dict[str, dict[str, int]]:
        """Reset-proof per-class admitted/rejected (bench contract)."""
        with self._lock:
            return {
                "admitted": dict(self._admitted),
                "rejected": dict(self._rejected),
            }

    def streams_by_class(self) -> dict[str, int]:
        out = {c: 0 for c in PRIORITIES}
        with self._lock:
            for prio, _ in self._streams.values():
                out[prio] = out.get(prio, 0) + 1
        return out

    def snapshot(self) -> dict:
        """The /scheduler payload core (fixed keys — route golden)."""
        counts = self.counts()
        return {
            "enabled": bool(self.cfg.enabled),
            "admit_util": self.cfg.admit_util,
            "capacity_fps": round(self.capacity_fps(), 1),
            "demand_fps": round(self.demand_fps(), 1),
            # post-gate view (stages/gate.py): what the engines
            # actually see after motion-gated skips
            "effective_demand_fps": round(self.effective_demand_fps(), 1),
            "utilization": round(self.utilization(), 3),
            "streams": self.streams_by_class(),
            "admitted": counts["admitted"],
            "rejected": counts["rejected"],
            "deadline_ms": dict(self.cfg.deadline_ms),
            "staleness_ms": dict(self.cfg.staleness_ms),
        }
