"""The shared batch engine — the heart of the framework.

Where the reference runs one inference engine per GStreamer pipeline
(optionally shared via ``model-instance-id``,
reference pipelines/object_detection/person_vehicle_bike/
pipeline.json:26-32), evam_tpu runs ONE BatchEngine per model
instance and multiplexes every active stream into it (BASELINE.json
north_star). One path, four cooperating threads per engine:

  submit() ──class queue──► dispatcher ──upload──► launcher ──► completer

* **submit()** (stream threads) appends the item to its scheduling
  class's FIFO (sched/classes.py ``ClassQueues``) and returns a
  future — O(1), no copy: class-ordered dispatch needs the item
  mobile until it is picked;
* the **dispatcher** sheds what outlived its class's staleness
  budget, picks a class (realtime first, starvation-proof), forms a
  batch under that class's deadline (latency/occupancy tension,
  SURVEY.md §7 "hard parts"), copies its rows into a free
  pre-allocated staging block (engine/ringbuf.py — the one host copy;
  no stack, no concat, no allocation), picks the bucket (bounded
  compile count), zeroes only the dirty pad tail, and ``device_put``s
  the block view on the mesh (data-axis sharded) — WITHOUT waiting
  for the copy;
* the **launcher** waits out the residual of the head batch's H2D
  copy, issues the jitted step, and puts the device→host copy in
  flight immediately (``copy_to_host_async``) — so the dispatcher is
  already staging and uploading batch N+1 while batch N's launch is
  being issued, and up to ``max_in_flight`` D2H copies ride the
  device at once;
* the **completer** blocks on the single per-batch readback
  residual, resolves per-item futures, and returns the block to the
  ring. Keeping launch and readback on separate threads
  double-buffers the device: batch N+1 is enqueued while batch N
  computes (the decode-ahead/infer overlap the reference gets from
  GStreamer element threads, SURVEY.md §2d-5);
* an in-flight semaphore bounds device queueing (backpressure, the
  analogue of the reference msgbus ``zmq_recv_hwm``,
  eii/config.json:37); the staging ring adds a second, host-side
  bound — a block is reusable only after its batch's readback (it
  may back an in-flight H2D transfer until the step consumes the
  device buffer).

Without a scheduler config (``sched=None``, ``EVAM_SCHED=off``) the
same loop runs as one FIFO: every submit joins the ``standard`` class,
batches form under the engine's ``deadline_ms`` and nothing is shed.

Every batch carries a **stage clock** (ringbuf.STAGES: submit_wait →
slot_write → seal → h2d_issue → h2d_wait → launch → readback →
resolve) into ``EngineStats`` and the ``evam_engine_stage_seconds``
histogram, so the serve bench and /healthz can attribute host
overhead instead of hiding it inside a throughput number (VERDICT r5
weak #5) — and attribute transfer cost vs the dispatch floor honestly
(h2d_wait and readback are residuals).
The clock (obs/trace.py ``StageClock``) also keeps each stage's start
and the batch's waits BETWEEN the stages, named by what the batch
waits for (``wait_launcher``: uploaded, until the launcher takes it;
``wait_slot``: an in-flight slot; ``wait_completer``: launched, until
the completer takes it): a batch record on ``/traces`` is a timeline.
Each of the three threads marks its own stretches on a
``ThreadSpans`` (``evam.dispatch.*``, ``evam.launch.*``,
``evam.complete.*`` in a profiler capture; a wait is named by what
the THREAD waits for) and their seconds land in
``evam_engine_thread_seconds``.
"""

from __future__ import annotations

import dataclasses
import os
import queue
import threading
import time
from concurrent.futures import Future
from typing import Callable

import jax
import numpy as np

from evam_tpu.aot import active as aot_active
from evam_tpu.aot import cache_key as aot_cache_key
from evam_tpu.engine.ragged import (
    RaggedSpec,
    consolidate_buckets,
    ragged_mode,
)
from evam_tpu.engine.ringbuf import STAGES, SealedBatch, SlotRing
from evam_tpu.obs import get_logger, metrics
from evam_tpu.obs import trace
from evam_tpu.obs.faults import current as active_faults
from evam_tpu.parallel.mesh import MeshPlan
from evam_tpu.sched.classes import (
    DEFAULT_PRIORITY,
    PRIORITIES,
    ClassQueues,
    SchedConfig,
)
from evam_tpu.sched.shedder import Shedder

log = get_logger("engine.batcher")

#: background bucket warmups running in this process, over ALL
#: engines. A batch dispatched while any of them compiles shares the
#: host — and, for engines of one chip, the device — with the
#: compiler, so the steady-state stage clock leaves it out
#: (EngineStats.stage_seconds).
_warmups_running = 0
_warmups_lock = threading.Lock()


@dataclasses.dataclass
class _WorkItem:
    inputs: dict[str, np.ndarray]
    future: Future
    t_submit: float
    priority: str = DEFAULT_PRIORITY
    #: real unit rows this item carries (a frame's region count for
    #: classify engines) — honest-occupancy metadata. None = unknown;
    #: accounting then assumes the pessimistic dense budget.
    units: int | None = None
    #: per-frame trace handle (obs/trace.py FrameTrace) — links this
    #: item's frame span tree to the batch it rides in; None when
    #: tracing is off or the caller has no frame context
    trace: object | None = None


def _safe_set_result(fut: Future, value) -> None:
    """The watchdog may have already failed this future; a late
    success from an unwedged backend must not crash the completer."""
    try:
        fut.set_result(value)
    except Exception:  # noqa: BLE001 — InvalidStateError
        pass


def _safe_set_exception(fut: Future, exc: Exception) -> None:
    try:
        fut.set_exception(exc)
    except Exception:  # noqa: BLE001 — InvalidStateError
        pass


@dataclasses.dataclass
class EngineStats:
    batches: int = 0
    items: int = 0
    occupancy_sum: float = 0.0
    #: real vs computed unit rows (ragged accounting, engine/ragged.py):
    #: a classify batch COMPUTES bucket × roi_budget unit rows on the
    #: dense path (unit_slots) however few regions the frames really
    #: carried (units). units/unit_slots is the honest occupancy the
    #: per-item n/bucket number silently overstates. Frame-per-row
    #: engines count 1 unit per item, so the two occupancies agree.
    units: int = 0
    unit_slots: int = 0
    #: per-bucket dispatched-batch counts (pad-tax attribution:
    #: which program shapes the traffic actually lands in)
    bucket_batches: dict[int, int] = dataclasses.field(default_factory=dict)
    #: compile-cache accounting: distinct bucket programs this engine
    #: has executed (each cost a jit trace + XLA compile) and the
    #: cumulative wall seconds their first batches took — warmup or
    #: mid-traffic. Bucket consolidation's "compile-cache entries
    #: drop" claim is measured against these, not asserted.
    compiled_programs: int = 0
    compile_seconds: float = 0.0
    #: AOT-cache attribution (evam_tpu/aot/): buckets warmed from a
    #: deserialized executable instead of a jit trace + XLA compile,
    #: and the wall seconds those loads+validations took — the warm
    #: counterpart of compile_seconds, so /engines shows cold vs warm
    #: spin-up honestly (a cache-hit shard: aot_hits == buckets,
    #: compile_seconds ≈ 0)
    aot_hits: int = 0
    aot_load_seconds: float = 0.0
    #: submits past the top bucket that had to be split across batches
    #: instead of silently clamped (oversize-split contract)
    oversize_splits: int = 0
    #: cumulative per-stage host clock (seconds), keyed by
    #: ringbuf.STAGES — submit_wait/slot_write/seal/h2d_issue come
    #: from the dispatcher, h2d_wait/launch from the launcher,
    #: readback/resolve from the completion thread. Single writer per
    #: key, so plain dict updates are safe. The clock is the
    #: STEADY-STATE service signal (admission derives capacity from
    #: it), so one rule leaves out what only a start-up pays: a batch
    #: is ``unclocked`` when its bucket is cold (its launch is trace +
    #: XLA compile, banked as compile_seconds) or when any engine's
    #: background warmup is running in this process. The
    #: evam_engine_stage_seconds histogram and the trace clock keep
    #: recording EVERY batch — a wedged cold batch must stay visible.
    stage_seconds: dict[str, float] = dataclasses.field(default_factory=dict)
    #: batches the clock left out
    unclocked: int = 0

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / self.batches if self.batches else 0.0

    @property
    def unit_occupancy(self) -> float:
        """Real units / computed unit rows — the honest pad-tax view."""
        return self.units / self.unit_slots if self.unit_slots else 0.0

    @property
    def clocked(self) -> int:
        """Batches the stage clock's sums cover."""
        return self.batches - self.unclocked

    def absorb(self, other: "EngineStats") -> None:
        """Fold another engine's cumulative counters into this one
        (supervisor rebuild carry — /healthz, /engines and the bench
        line must stay monotonic across quarantine swaps)."""
        self.batches += other.batches
        self.unclocked += other.unclocked
        self.items += other.items
        self.occupancy_sum += other.occupancy_sum
        self.units += other.units
        self.unit_slots += other.unit_slots
        self.compiled_programs += other.compiled_programs
        self.compile_seconds += other.compile_seconds
        self.aot_hits += other.aot_hits
        self.aot_load_seconds += other.aot_load_seconds
        self.oversize_splits += other.oversize_splits
        for b, c in other.bucket_batches.items():
            self.bucket_batches[b] = self.bucket_batches.get(b, 0) + c
        for k, v in other.stage_seconds.items():
            self.stage_seconds[k] = self.stage_seconds.get(k, 0.0) + v

    def add_stage(self, stage: str, dt: float) -> None:
        self.stage_seconds[stage] = self.stage_seconds.get(stage, 0.0) + dt

    def stage_ms_per_batch(self) -> dict[str, float]:
        """Mean per-batch host cost of each pipeline stage (ms) over
        the ``clocked`` batches."""
        if self.clocked <= 0:
            return {}
        return {
            s: round(1e3 * self.stage_seconds.get(s, 0.0) / self.clocked, 3)
            for s in STAGES if s in self.stage_seconds
        }


class BatchEngine:
    """Deadline-batching dispatcher around one jitted step function.

    ``step_fn(params, **batch) -> packed`` must accept stacked inputs
    (leading batch axis) and return one array whose leading axis
    matches. Bucketed batch sizes keep the number of distinct
    compiled programs small (recompilation-storm guard)."""

    #: Cross-thread mutable state and the lock that guards it — the
    #: dispatcher, launcher, completer, watchdog, and warmup threads
    #: all touch these.  ``evam_tpu.analysis`` (lock-discipline pass)
    #: enforces that every mutation happens under ``_exec_lock``.
    SHARED_UNDER = {
        "stats": "_exec_lock",
        "_buckets_done": "_exec_lock",
        "_outstanding": "_exec_lock",
        "_next_batch_id": "_exec_lock",
        "_on_device": "_exec_lock",
        "_idle_since": "_exec_lock",
        "_aot_exec": "_exec_lock",
    }

    def __init__(
        self,
        name: str,
        step_fn: Callable,
        params,
        plan: MeshPlan | None = None,
        max_batch: int = 32,
        deadline_ms: float = 8.0,
        max_in_flight: int = 3,
        input_names: tuple[str, ...] = ("frames",),
        stall_timeout_s: float = 120.0,
        staging_depth: int | None = None,
        first_batch_grace: float = 10.0,
        sched: SchedConfig | None = None,
        ragged: str | None = None,
        ragged_spec: RaggedSpec | None = None,
        fleet_local: bool = False,
        transfer_depth: int | None = None,
        aot_key: str | None = None,
    ):
        self.name = name
        self.plan = plan
        self.max_batch = max_batch
        self.deadline_s = deadline_ms / 1000.0
        self.input_names = input_names
        self.stats = EngineStats()
        #: ragged batching (engine/ragged.py, EVAM_RAGGED): "packed"
        #: packs variable-size items into one fixed device shape with
        #: a row_len/row_offset descriptor + masked compute, and thins
        #: the bucket ladder so adjacent shapes share a program; "off"
        #: (default) is the dense bucketed path.
        self.ragged = ragged_mode(ragged)
        #: unit-level shape of the one ragged input (classify-family
        #: engines). Attached even in "off" mode so the occupancy
        #: accounting stays honest about per-item ROI padding; packing
        #: itself is mode-gated.
        self.ragged_spec = ragged_spec
        self._packed = self.ragged == "packed" and ragged_spec is not None
        #: whether the backend keeps transfer streams separate from
        #: compute (TPU: PJRT tracks per-buffer readiness and DMAs
        #: ride their own stream). Gates the device-specific halves of
        #: the pipeline — the explicit plan-less device_put, the
        #: h2d_wait reading (blocking on the CPU "device" would wait
        #: behind the PREVIOUS batch's compute on the shared stream
        #: and re-serialize exactly what the launcher overlaps), and
        #: the async D2H issue (an extra host-side copy when the
        #: "device" is host memory). The pipeline STRUCTURE
        #: (dispatcher/launcher split, upload queue, watchdog
        #: semantics) is the same on CPU, so tests exercise it end to
        #: end.
        self._device_streams = jax.default_backend() == "tpu"
        #: device identity recorded on batch trace records — a fleet
        #: shard's spans name the chip it serves (obs/trace.py)
        self._trace_device = (str(plan.mesh.devices.flat[0])
                              if plan is not None
                              else jax.default_backend())
        #: QoS scheduling (evam_tpu/sched/): per-class batch deadlines
        #: and staleness budgets for the class queues every submit
        #: joins. None (or disabled, EVAM_SCHED=off) is the same loop
        #: read as one FIFO: every submit joins ``standard``, every
        #: class forms under the engine's ``deadline_ms`` and no
        #: class has a staleness budget (the shedder's "never shed").
        self.sched = sched if (sched is not None and sched.enabled) else None
        self._class_deadline_s = {
            c: (self.sched.deadline_s(c) if self.sched is not None
                else self.deadline_s)
            for c in PRIORITIES}
        self._classq = ClassQueues()
        self._shedder = Shedder(
            name, self.sched.staleness_s() if self.sched is not None else {})
        #: watchdog bound on one batch's device round-trip; a wedged
        #: backend blocks the launcher in C++ forever — the watchdog
        #: can't unblock it, but it CAN fail the stranded futures and
        #: flag the engine so /healthz degrades and callers stop
        #: queueing into a black hole
        #: (SURVEY §5.3 failure detection; 0 disables).
        self.stall_timeout_s = stall_timeout_s
        #: a bucket's FIRST batch pays jit trace + XLA compile inside
        #: its device round-trip; counting that against stall_timeout_s
        #: makes every cold engine — including every supervisor rebuild
        #: (fresh jit by design) — look wedged and flap until the
        #: restart budget degrades it. Buckets that have completed a
        #: batch get the plain budget; unseen buckets get
        #: stall_timeout_s × first_batch_grace.
        self.first_batch_grace = first_batch_grace
        self._buckets_done: set[int] = set()
        #: set when a batch exceeded stall_timeout_s (engine is
        #: considered wedged; submit() fails fast). Cleared if the
        #: wedged call later completes (slow compile, transient hang).
        self.stalled = threading.Event()
        #: every dispatched-but-not-completed batch: id → (t_dispatch,
        #: items, bucket, stall_deadline, unclocked). Covers the
        #: device launch, the _done queue wait, AND the readback — a
        #: wedge anywhere strands nothing. The deadline is FIXED at
        #: dispatch time (_track_dispatch): a concurrent warmup
        #: finishing mid-flight must not retroactively shrink an
        #: in-flight cold batch's compile allowance.
        self._outstanding: dict[
            int, tuple[float, list[_WorkItem], int, float, bool]] = {}
        self._next_batch_id = 0
        self._exec_lock = threading.Lock()
        #: persistent AOT executable cache (evam_tpu/aot/): the hub's
        #: program fingerprint for this engine — part of the cache key
        #: together with shapes and devices. None (the EVAM_AOT
        #: default, or a caller that never passes it) leaves warmup and
        #: dispatch on the plain jit path.
        self._aot_key = aot_key
        #: bucket → validated AOT executable, installed by warmup;
        #: dispatch (``_exec_for``) prefers it over the jitted step —
        #: both share the ``fn(params, *arrays)`` call signature.
        self._aot_exec: dict[int, object] = {}

        d = plan.data_size if plan else 1
        top = plan.pad_batch(max_batch) if plan else max_batch
        self.buckets = []
        b = d
        while b < top:
            self.buckets.append(b)
            b *= 2
        self.buckets.append(top)
        if self.ragged == "packed":
            # bucket consolidation (engine/ragged.py): adjacent shape
            # buckets share a program instead of each paying compile +
            # program memory + a cold first-batch stall. Rungs are
            # aligned to the data-axis size at BUILD time so sharded
            # dispatch never re-pads a sealed block per batch.
            self.buckets = consolidate_buckets(self.buckets, align=d)
        #: fleet mode's per-batch collective bypass (evam_tpu/fleet/):
        #: sub-data-size rungs are added to the ladder and dispatched
        #: through a second, single-device jit of the SAME step — a
        #: lightly-filled bucket on the mesh engine runs on one chip
        #: instead of paying an 8-way collective for 2 real rows. The
        #: existing bucket fn does the selection (_exec_for); off
        #: (default) leaves ladder and dispatch as they are.
        self._fleet_local = bool(fleet_local and plan is not None
                                 and plan.data_size > 1)
        if self._fleet_local:
            sub, s = [], 1
            while s < d:
                sub.append(s)
                s *= 2
            self.buckets = sub + self.buckets

        #: staging ring: blocks sized to the LARGEST bucket so a
        #: sealed batch is always a contiguous [:bucket] prefix view;
        #: max_in_flight + 1 deep (one slot assembling while
        #: max_in_flight batches ride the device) so the ring never
        #: shrinks the device pipeline, while bounding host memory at
        #: depth × top-bucket batches. EVAM_STAGING_DEPTH overrides.
        depth = staging_depth or int(
            os.environ.get("EVAM_STAGING_DEPTH", "0")) or (max_in_flight + 1)
        self._ring = SlotRing(capacity=self.buckets[-1], depth=depth,
                              ragged=(ragged_spec if self._packed
                                      else None))
        #: jit-call input order: the packed-ragged step takes the
        #: segment-id vector after the submit inputs (the stage never
        #: submits it — the ring seals it per batch)
        self._step_inputs = (input_names + ("seg",) if self._packed
                             else input_names)
        if self._packed and "seg" in input_names:
            raise ValueError(
                f"engine {name}: input name 'seg' is reserved by the "
                "packed-ragged path")

        # No donate_argnums: the inputs are uint8 wire blocks and the
        # output a few KB of f32 per frame, so XLA finds nothing to
        # alias them with — on the v5e the donating program was the
        # same executable plus a "donated buffers were not usable"
        # warning per compile.
        if plan is not None:
            self._params = jax.device_put(params, plan.replicated())
            self._jit_step = jax.jit(
                step_fn,
                in_shardings=(
                    plan.replicated(),
                    # every step input is batch-sharded — including
                    # the packed-ragged seg vector, whose unit rows
                    # scale with the (data-divisible) bucket
                    *([plan.batch_sharding()] * len(self._step_inputs)),
                ),
            )
        else:
            self._params = params
            self._jit_step = jax.jit(step_fn)
        if self._fleet_local:
            # single-device twin of the sharded step for the sub-data
            # rungs: params replicated onto (i.e. copied to) the first
            # mesh device, batch axis "sharded" over a 1-device mesh —
            # XLA emits no collectives for it
            self._local_plan = plan.per_device_plans()[0]
            self._params_local = jax.device_put(
                params, self._local_plan.replicated())
            self._jit_step_local = jax.jit(
                step_fn,
                in_shardings=(
                    self._local_plan.replicated(),
                    *([self._local_plan.batch_sharding()]
                      * len(self._step_inputs)),
                ),
            )
        else:
            self._local_plan = None

        self._done: queue.Queue[tuple | None] = queue.Queue()
        #: sealed batches whose H2D copy has been issued, awaiting
        #: launch. Default depth 2 — device-side
        #: double buffering (one batch uploading while one launches);
        #: EVAM_TRANSFER_DEPTH sets it.
        self.transfer_depth = max(1, int(transfer_depth or 2))
        self._upload_q: queue.Queue = queue.Queue(
            maxsize=self.transfer_depth)
        self._warm_lock = threading.Lock()
        self._warming = False
        #: set when background warmup finishes (or fails)
        self.warmed = threading.Event()
        #: why background warmup failed, else None — traffic still
        #: compiles on demand, but a preload must not call this ready
        self.warm_error: str | None = None
        self._in_flight = threading.Semaphore(max_in_flight)
        self._stop = threading.Event()
        #: each worker thread's own stretches (obs/trace.py); inert
        #: with EVAM_TRACE=off
        self._sp_dispatch = trace.thread_spans(name, "dispatch", cpu=True)
        #: the engine's idle ledger (None with EVAM_TRACE=off): batches
        #: launched and not yet read back, and the moment that count
        #: reached zero, stamped by the completer and taken by the
        #: launcher, which puts the stretch up to its launch down to
        #: where the launched batch was meanwhile
        self._idle = trace.idle_ledger(name)
        self._on_device = 0
        self._idle_since: float | None = None
        self._sp_launch = trace.thread_spans(name, "launch",
                                             ledger=self._idle)
        self._sp_complete = trace.thread_spans(name, "complete", cpu=True)
        trace.watch_engine(self)
        self._dispatcher = threading.Thread(
            target=self._thread_guard, args=(self._dispatch_loop,),
            name=f"engine-{name}-dispatch", daemon=True,
        )
        self._completer = threading.Thread(
            target=self._thread_guard, args=(self._completion_loop,),
            name=f"engine-{name}-complete", daemon=True,
        )
        self._launcher = threading.Thread(
            target=self._thread_guard, args=(self._launch_loop,),
            name=f"engine-{name}-launch", daemon=True,
        )
        self._launcher.start()
        self._dispatcher.start()
        self._completer.start()
        if self.stall_timeout_s > 0:
            threading.Thread(
                target=self._watchdog_loop,
                name=f"engine-{name}-watchdog", daemon=True,
            ).start()

    def _thread_guard(self, loop_fn: Callable) -> None:
        """Engine worker loops must never escape their thread with a
        raw traceback: a crashed dispatcher, launcher or completer is
        an ENGINE failure — logged here, detected by the
        EngineSupervisor via thread liveness, and answered with a
        quarantine + rebuild."""
        try:
            loop_fn()
        except Exception:  # noqa: BLE001 — terminal thread failure
            log.exception(
                "engine %s worker thread %s died; the engine is wedged "
                "until the supervisor rebuilds it",
                self.name, threading.current_thread().name,
            )

    # ------------------------------------------------------------- API

    def submit(self, priority: str = DEFAULT_PRIORITY,
               units: int | None = None,
               stream: str | None = None,
               trace: "object | None" = None,
               **inputs: np.ndarray) -> Future:
        """Enqueue one item (no batch dim); resolves to its packed row(s).

        ``priority`` selects the scheduling class (realtime|standard|
        batch) when the engine has a scheduler config
        (evam_tpu/sched/); without one the argument is accepted and
        ignored and the item joins ``standard`` — one FIFO.

        ``stream`` is the submitting stream's identity. A single-chip
        engine accepts and ignores it —
        it exists so the fleet mode (evam_tpu/fleet/) can pin a
        stream's traffic to a per-chip shard; stages pass it
        unconditionally and the engine kind behind the hub decides
        whether placement applies.

        ``units`` is honest-occupancy metadata: the item's REAL unit
        rows (a frame's region count on classify engines, where the
        dense path pads every item to the ROI budget). On the
        packed-ragged path it is derived from the ragged input's
        leading dim instead; the item then resolves to exactly its
        own rows of the packed output.

        ``trace`` is the submitting frame's FrameTrace handle
        (obs/trace.py) or None: the batch this item lands in records
        the trace id (batch↔frame linkage) and the completion path
        appends queue-wait + dispatch spans to the frame's tree.
        Accepted and ignored — zero-cost — when tracing is off.

        The call never blocks and never copies: the item waits in its
        class queue, and the dispatcher copies its arrays into a
        staging block once it has picked it (class-ordered dispatch
        needs the item mobile until then). An array that does not
        match the ring's shapes fails this item's future, there."""
        if self._stop.is_set():
            raise RuntimeError(f"engine {self.name} is stopped")
        if self.stalled.is_set():
            # the launcher is wedged inside a device call — queueing
            # more work would strand more futures
            raise RuntimeError(
                f"engine {self.name} is stalled (device call exceeded "
                f"{self.stall_timeout_s:.0f}s — backend wedged?)"
            )
        if set(inputs) != set(self.input_names):
            raise ValueError(
                f"engine {self.name} expects inputs {self.input_names}, got {tuple(inputs)}"
            )
        if self._packed:
            units = int(np.asarray(
                inputs[self.ragged_spec.input]).shape[0])
        fut: Future = Future()
        if self.sched is None:
            priority = DEFAULT_PRIORITY
        elif priority not in PRIORITIES:
            raise ValueError(
                f"unknown priority {priority!r}; valid: "
                f"{'|'.join(PRIORITIES)}")
        item = _WorkItem(inputs, fut, time.perf_counter(), priority,
                         units, trace)
        try:
            self._classq.put(priority, item)
        except RuntimeError:
            raise RuntimeError(f"engine {self.name} is stopped") from None
        return fut

    def queue_depth(self) -> int:
        """Items submitted but not yet dispatched — the previously
        invisible backlog (satellite: queue gauges)."""
        return self._classq.depth()

    def queue_age_s(self) -> float:
        """Age (s) of the oldest undispatched item; 0 when idle."""
        return self._classq.oldest_age_s(time.perf_counter())

    def thread_states(self) -> dict[str, tuple]:
        """Each worker thread's current stretch and its age in seconds
        (the freeze recorder's dumps, obs/trace.py)."""
        return {"dispatch": self._sp_dispatch.where(),
                "launch": self._sp_launch.where(),
                "complete": self._sp_complete.where()}

    def class_depths(self) -> dict[str, int]:
        """Per-class queued depth (without a scheduler config every
        item queues as ``standard``)."""
        return self._classq.depth_by_class()

    def shed_counts(self) -> dict[str, int]:
        """Per-class shed totals (zeros without a scheduler config:
        no class has a staleness budget)."""
        return dict(self._shedder.counts)

    def warmup(self) -> None:
        """Compile every bucket size ahead of traffic.

        With the AOT cache active (EVAM_AOT=on and an ``aot_key``),
        each rung first tries a deserialized executable from the
        persistent store (validated by actually running the warm
        batch through it); a hit skips trace+compile entirely, a miss
        compiles ahead-of-time once and populates the store. Any
        failure on that path falls through to the plain jit warmup
        below — the cache can degrade serving to cold, never to
        broken."""
        example = self._example_item()
        cache = aot_active() if self._aot_key else None
        for b in self.buckets:
            batch = self._warm_batch(example, b)
            t0 = time.perf_counter()
            if cache is not None and self._warm_bucket_aot(
                    cache, b, batch, t0):
                continue
            np.asarray(self._run(b, batch))
            with self._exec_lock:
                if b not in self._buckets_done:
                    # compile-cache accounting: a bucket's first run
                    # pays jit trace + XLA compile — bank it so
                    # consolidation's "fewer programs" claim is
                    # measurable
                    self.stats.compiled_programs += 1
                    self.stats.compile_seconds += (
                        time.perf_counter() - t0)
                # warmed bucket = compiled: its batches get the plain
                # (not first-batch-grace) watchdog budget from here on
                self._buckets_done.add(b)
        log.info("engine %s warmed %d buckets %s", self.name, len(self.buckets), self.buckets)

    # ------------------------------------------- AOT cache (evam_tpu/aot/)

    def _bucket_devices(self, b: int) -> list:
        """The devices bucket ``b``'s executable runs on, in mesh
        order: the engine's plan, the single-device twin for a
        fleet-local sub rung, or the default device without a plan."""
        plan = (self._local_plan
                if (self._fleet_local and 0 < b < self.plan.data_size)
                else self.plan)
        if plan is not None:
            return list(plan.mesh.devices.flat)
        return [jax.devices()[0]]

    def _aot_bucket_key(self, b: int,
                        batch: dict[str, np.ndarray]) -> str:
        """Cache key for bucket ``b``'s executable: the hub program
        fingerprint + the exact step-input shapes/dtypes + the params
        aval signature + the device set the executable binds to +
        backend. Fleet-local sub rungs address different
        entries than the mesh rungs by their single-device list."""
        devices = [str(d) for d in self._bucket_devices(b)]
        inputs = [(name, tuple(batch[name].shape),
                   str(batch[name].dtype))
                  for name in self._step_inputs]
        params_sig = [
            (tuple(getattr(leaf, "shape", ())),
             str(getattr(leaf, "dtype", "")))
            for leaf in jax.tree_util.tree_leaves(self._params)]
        return aot_cache_key(self._aot_key, b, inputs, params_sig,
                             devices, jax.default_backend())

    def _warm_arrays(self, b: int, batch: dict[str, np.ndarray]):
        """(params, placed input arrays) for bucket ``b``'s warm
        batch — shared by the plain warm-up and the AOT validate and
        populate paths."""
        _, prm, sharding = self._exec_plain(b)
        arrays = []
        for name in self._step_inputs:
            a = batch[name]
            if sharding is not None:
                a = jax.device_put(a, sharding)
            arrays.append(a)
        return prm, arrays

    def _warm_bucket_aot(self, cache, b: int,
                         batch: dict[str, np.ndarray],
                         t0: float) -> bool:
        """Warm bucket ``b`` through the AOT cache. True = the rung is
        warmed (hit, or compiled+stored); False = fall back to the
        plain jit warmup. Hits bank into aot_hits/aot_load_seconds,
        misses into compile_seconds — /engines attributes cold vs
        warm spin-up from exactly these."""
        key = self._aot_bucket_key(b, batch)
        prm, arrays = self._warm_arrays(b, batch)
        loaded = cache.load(key, self._bucket_devices(b),
                            engine=self.name)
        if loaded is not None:
            try:
                # the only honest validation of a deserialized,
                # device-bound executable is running it — this IS
                # the warm run on success
                np.asarray(loaded(prm, *arrays))
            except Exception as exc:  # noqa: BLE001 — device/placement drift
                log.warning(
                    "engine %s: cached AOT executable for bucket "
                    "%d would not execute (%s) — recompiling",
                    self.name, b, exc)
                cache.execute_miss(key, engine=self.name)
                loaded = None
        if loaded is not None:
            with self._exec_lock:
                if b not in self._buckets_done:
                    self.stats.compiled_programs += 1
                    self.stats.aot_hits += 1
                    self.stats.aot_load_seconds += (
                        time.perf_counter() - t0)
                self._aot_exec[b] = loaded
                self._buckets_done.add(b)
            cache.hit(engine=self.name)
            return True
        try:
            # miss: compile ahead-of-time ONCE (lower().compile()
            # and jit don't share a cache — running both would
            # double the cold-start bill) and use the compiled
            # executable for the warm run and for dispatch
            jit_fn, _, _ = self._exec_plain(b)
            compiled = jit_fn.lower(prm, *arrays).compile()
            np.asarray(compiled(prm, *arrays))
        except Exception as exc:  # noqa: BLE001 — AOT unsupported here
            log.warning(
                "engine %s: AOT compile path failed for bucket %d "
                "(%s) — plain jit warmup", self.name, b, exc)
            return False
        with self._exec_lock:
            if b not in self._buckets_done:
                self.stats.compiled_programs += 1
                self.stats.compile_seconds += (
                    time.perf_counter() - t0)
            self._aot_exec[b] = compiled
            self._buckets_done.add(b)
        cache.store(key, compiled, engine=self.name)
        return True

    def _warm_batch(self, example: dict[str, np.ndarray],
                    b: int) -> dict[str, np.ndarray]:
        """Bucket-``b`` warmup batch from a per-item example. Packed
        engines compile the PACKED shapes — the unit block + seg
        vector at ``unit_rows(b)``, all-pad (seg −1) so the masked
        step compiles without touching real data."""
        spec = self.ragged_spec
        batch: dict[str, np.ndarray] = {}
        for k, v in example.items():
            if self._packed and k == spec.input:
                batch[k] = np.zeros(
                    (spec.unit_rows(b),) + tuple(spec.unit_shape),
                    spec.dtype)
            else:
                batch[k] = np.broadcast_to(v, (b,) + v.shape).copy()
        if self._packed:
            batch["seg"] = np.full((spec.unit_rows(b),), -1, np.int32)
        return batch

    def warm_async(self, **example: np.ndarray) -> None:
        """Fire-and-forget bucket precompilation (serving path: kills
        the mid-traffic compile spike when a batch first crosses a
        bucket boundary). Idempotent."""
        global _warmups_running
        with self._warm_lock:
            if self._warming:
                return
            self._warming = True
        with _warmups_lock:
            _warmups_running += 1
        self.set_example(**example)
        threading.Thread(
            target=self._warm_guarded,
            name=f"engine-{self.name}-warmup",
            daemon=True,
        ).start()

    def _warm_guarded(self) -> None:
        global _warmups_running
        try:
            self.warmup()
        except Exception as exc:  # noqa: BLE001 — warmup must never kill serving
            self.warm_error = f"{type(exc).__name__}: {exc}"
            log.warning("engine %s warmup failed: %s", self.name, exc)
        finally:
            with _warmups_lock:
                _warmups_running -= 1
            self.warmed.set()

    def stop(self) -> None:
        self._stop.set()
        self._classq.close()
        self._ring.close()
        self._dispatcher.join(timeout=10)
        try:
            self._upload_q.put_nowait(None)
        except queue.Full:
            pass  # launcher drains the backlog, then exits on _stop
        self._launcher.join(timeout=10)
        self._done.put(None)
        self._completer.join(timeout=10)
        exc = RuntimeError("engine stopped")
        self._drain_upload_q(exc)
        self._fail_queued(exc)

    def _track_dispatch(self, t0: float, items: list[_WorkItem],
                        bucket: int) -> tuple[int, bool]:
        """Register a dispatched batch with the watchdog; returns its
        id and whether the stage clock leaves it out (the one rule on
        ``EngineStats.stage_seconds``). The stall deadline is locked
        in here. A bucket that has never
        completed a batch gets stall_timeout_s × first_batch_grace (its
        round-trip legitimately contains trace + compile). Device
        execution is ordered, so a batch enqueued behind others can't
        finish before them: its deadline is additionally floored at
        the latest outstanding deadline + one plain budget — the tail
        of a cold engine's first wave inherits the compile wait, but
        each queued batch extends detection by only stall_timeout_s,
        so a genuinely wedged engine with a standing backlog is still
        caught in bounded time."""
        with self._exec_lock:
            cold = bucket not in self._buckets_done
            if cold:
                deadline = t0 + self.stall_timeout_s * self.first_batch_grace
            else:
                deadline = t0 + self.stall_timeout_s
            if self._outstanding:
                queue_ahead = max(
                    e[3] for e in self._outstanding.values())
                deadline = max(deadline,
                               queue_ahead + self.stall_timeout_s)
            bid = self._next_batch_id
            self._next_batch_id += 1
            unclocked = cold or _warmups_running > 0
            self._outstanding[bid] = (t0, items, bucket, deadline,
                                      unclocked)
        return bid, unclocked

    def abandon(self) -> None:
        """Quarantine teardown (EngineSupervisor): release every
        failable caller WITHOUT joining the worker threads — a wedged
        engine's launcher/completer may be blocked in C++ (or an
        injected wedge's sleep) indefinitely, and the supervisor must
        not inherit that wait. The threads are daemons; they observe
        ``_stop``/the closed ring when (if) they ever wake and exit on
        their own. Idempotent."""
        self._stop.set()
        exc = TimeoutError(
            f"engine {self.name} quarantined: wedged device call; "
            "the supervisor is rebuilding the engine"
        )
        self._classq.close()
        self._fail_queued(exc)
        self._ring.close()
        self._done.put(None)
        self._drain_upload_q(exc)
        with self._exec_lock:
            stranded = [it for entry in self._outstanding.values()
                        for it in entry[1]]
            self._outstanding.clear()
        for it in stranded:
            _safe_set_exception(it.future, exc)

    # -------------------------------------------------------- internals

    def _example_item(self) -> dict[str, np.ndarray]:
        item = self._peek_shapes
        if item is None:
            raise RuntimeError("warmup requires example_shapes")
        return item

    #: optional dict name -> example array (no batch dim) for warmup
    _peek_shapes: dict[str, np.ndarray] | None = None

    def set_example(self, **example: np.ndarray) -> None:
        self._peek_shapes = example

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        # n past the top bucket would silently truncate: the ring
        # hands back what a block cannot hold and the dispatcher
        # stages it as another batch BEFORE bucketing, so landing
        # here is an accounting bug — be loud, never lossy
        log.warning(
            "engine %s: %d items exceed top bucket %d (oversize split "
            "missed a path); clamping the SHAPE, items are preserved "
            "by the caller's split", self.name, n, self.buckets[-1])
        return self.buckets[-1]

    def _bucket_ragged(self, n: int, units: int) -> int:
        """Packed-ragged bucket pick: the smallest rung that fits both
        the item rows AND the packed unit rows (a few region-heavy
        frames can need a bigger unit block than their item count
        alone suggests)."""
        spec = self.ragged_spec
        for b in self.buckets:
            if n <= b and units <= spec.unit_rows(b):
                return b
        return self.buckets[-1]

    def _count_oversize_split(self, extra: int) -> None:
        with self._exec_lock:
            self.stats.oversize_splits += extra
        metrics.inc("evam_engine_oversize_splits", float(extra),
                    labels={"engine": self.name})

    def _exec_plain(self, b: int):
        """(jit, params, sharding) for one sealed bucket. With the
        fleet mode's local bypass, sub-data-size buckets select the
        single-device twin — the existing bucket fn already routed the
        batch to rung ``b``, so this is the per-batch choice the fleet
        contract names: small batches never pay a collective."""
        if self._fleet_local and 0 < b < self.plan.data_size:
            return (self._jit_step_local, self._params_local,
                    self._local_plan.batch_sharding())
        if self.plan is not None:
            return self._jit_step, self._params, self.plan.batch_sharding()
        return self._jit_step, self._params, None

    def _exec_for(self, b: int):
        """(callable, params, sharding) for one sealed bucket — the
        warmed AOT executable when the cache installed one for this
        rung, the jitted step otherwise. Both share the
        ``fn(params, *arrays)`` call signature, so the launcher is
        agnostic to which it got. (Lock-free read: dict get
        is atomic and a rung's entry, once installed by warmup, is
        never replaced.)"""
        jit_fn, prm, sharding = self._exec_plain(b)
        exe = self._aot_exec.get(b)
        return (exe if exe is not None else jit_fn), prm, sharding

    def _run(self, b: int, batch: dict[str, np.ndarray]):
        """Warm-up's device calls, back to back on the calling thread:
        place bucket ``b``'s warm batch and run the jitted step on it
        (the first run of a shape is its trace + XLA compile).
        Nothing that serves comes through here."""
        jit_fn, _, _ = self._exec_for(b)
        prm, arrays = self._warm_arrays(b, batch)
        return jit_fn(prm, *arrays)

    def refresh_queue_gauges(self) -> None:
        """Push the submit-backlog gauges. Called on every dispatch
        (_record_batch) AND from the watchdog/supervisor ticks — a
        wedged or idle engine must not freeze its queue gauges at the
        last dispatch's values while the backlog grows underneath."""
        metrics.set("evam_engine_queue_depth", self.queue_depth(),
                    {"engine": self.name})
        metrics.set("evam_engine_queue_age_s", self.queue_age_s(),
                    {"engine": self.name})

    def _record_batch(self, sealed: SealedBatch,
                      unclocked: bool) -> None:
        n, b, clock = sealed.n, sealed.bucket, sealed.clock
        spec = self.ragged_spec
        with self._exec_lock:
            self.stats.batches += 1
            self.stats.items += n
            self.stats.occupancy_sum += n / b
            # honest unit accounting (engine/ragged.py): what the
            # program COMPUTED (unit_slots) vs the real work inside it
            # (units). Packed batches know both exactly from the
            # sealed descriptor; dense batches compute bucket ×
            # max_units unit rows and fall back to the pessimistic
            # budget for items that didn't declare their real count.
            # Frame-per-row engines: 1 unit per item.
            if sealed.row_len is not None:
                self.stats.units += sealed.units
                self.stats.unit_slots += sealed.unit_rows
            elif spec is not None:
                self.stats.unit_slots += b * spec.max_units
                self.stats.units += sum(
                    (it.units if it.units is not None else spec.max_units)
                    for it in sealed.items)
            else:
                self.stats.unit_slots += b
                self.stats.units += n
            self.stats.bucket_batches[b] = (
                self.stats.bucket_batches.get(b, 0) + 1)
            if unclocked:
                self.stats.unclocked += 1
            else:
                for stage, dt in clock.items():
                    self.stats.add_stage(stage, dt)
            mean_occ = self.stats.mean_occupancy
            unit_occ = self.stats.unit_occupancy
        metrics.observe("evam_batch_occupancy", n / b, {"engine": self.name})
        # live occupancy for operators (satellite: occupancy export) —
        # both the item-fill mean and the pad-tax-honest unit view
        metrics.set("evam_engine_occupancy", mean_occ,
                    {"engine": self.name})
        metrics.set("evam_engine_unit_occupancy",
                    unit_occ, {"engine": self.name})
        self.refresh_queue_gauges()
        for stage, dt in clock.items():
            metrics.observe(
                "evam_engine_stage_seconds", dt,
                {"engine": self.name, "stage": stage})

    # --------------------------------------------- transfer pipeline

    def _fail_queued(self, exc: Exception) -> None:
        """Fail every item still waiting in the class queues."""
        for it in self._classq.drain():
            _safe_set_exception(it.future, exc)

    def _fail_batch(self, sealed: SealedBatch, exc: Exception) -> None:
        """Fail every future of a batch that will not reach the device
        and give its block back. The block releases without waiting
        on a possibly in-flight H2D copy: the futures are failed, so
        nothing ever observes those rows again."""
        for it in sealed.items:
            _safe_set_exception(it.future, exc)
        self._ring.release(sealed)

    def _dispatch_batch(self, sealed: SealedBatch) -> None:
        """Hand one sealed batch to the device: enqueue the H2D copy
        here (h2d_issue — device_put returns once the transfer is in
        flight) and queue the batch for the launcher thread, so the
        dispatcher is staging and uploading batch N+1 while batch N's
        launch is being issued."""
        sp = self._sp_dispatch
        batch = sealed.arrays
        try:
            t0 = time.perf_counter()
            sp.to("h2d_issue", t0)
            _, _, sharding = self._exec_for(sealed.bucket)
            if sharding is not None:
                # sharded placement is semantics, not an
                # optimization — always explicit
                dev = [jax.device_put(batch[name], sharding)
                       for name in self._step_inputs]
            elif self._device_streams:
                dev = [jax.device_put(batch[name])
                       for name in self._step_inputs]
            else:
                # CPU: let the launcher's jit call do the one
                # host-side conversion — an explicit device_put here
                # would be a second copy with no DMA to overlap
                dev = [batch[name] for name in self._step_inputs]
            t_up = time.perf_counter()
            sealed.clock.mark("h2d_issue", t0, t_up - t0)
        except Exception as exc:  # noqa: BLE001 — surface to every caller
            self._fail_batch(sealed, exc)
            log.exception("engine %s H2D upload failed", self.name)
            return
        entry = (dev, sealed)
        if self._upload_q.full():
            sp.to("wait_launcher", t_up)
        while True:
            try:
                self._upload_q.put(entry, timeout=0.1)
                return
            except queue.Full:
                if self._stop.is_set():
                    # launcher is exiting — don't strand the batch
                    self._fail_batch(sealed, RuntimeError(
                        f"engine {self.name} is stopped"))
                    return

    def _launch(self, dev: list, clock: trace.StageClock, b: int):
        """Wait out the head batch's H2D residual where that is
        measurable without re-serializing (h2d_wait is ≈0 when the
        upload overlapped the previous launch, the full copy time
        when it did not), issue the jitted step, and put the D2H copy
        in flight immediately so the completer blocks only on the
        readback residual."""
        # chaos hook: an injected `wedge` blocks right here — on the
        # thread that issues the device RPC, exactly where a hung
        # backend would — so the watchdog/supervisor path is testable
        # without wedging real hardware (obs/faults.py)
        inj = active_faults()
        if inj is not None:
            inj.maybe_wedge(self.name)
        sp = self._sp_launch
        t0 = time.perf_counter()
        sp.to("h2d_wait", t0)
        if self._device_streams:
            jax.block_until_ready(dev)
        t1 = time.perf_counter()
        sp.to("launch", t1)
        jit_fn, prm, _ = self._exec_for(b)
        out = jit_fn(prm, *dev)
        t2 = time.perf_counter()
        sp.to("bookkeep", t2)
        clock.mark("h2d_wait", t0, t1 - t0)
        clock.mark("launch", t1, t2 - t1)
        if self._device_streams:
            # async D2H: the device→host copy rides along while
            # later batches launch; np.asarray in the completer
            # then pays only the residual (the `readback` stage)
            out.copy_to_host_async()
        return out

    def _launch_loop(self) -> None:
        """Pop uploaded batches and launch them — while this thread is
        inside a launch (or blocked on a wedged backend RPC), the
        dispatcher keeps staging and uploading."""
        sp = self._sp_launch
        idle = self._idle
        while True:
            # seconds kept, no annotation: while the launcher idles for
            # want of an upload, what gates the device is whatever the
            # dispatcher is doing or waiting for, and that is annotated
            sp.to("wait_upload", annotate=False)
            try:
                entry = self._upload_q.get(timeout=0.1)
            except queue.Empty:
                if self._stop.is_set():
                    break
                continue
            if entry is None:
                break
            dev, sealed = entry
            if self._stop.is_set():
                self._fail_batch(sealed, RuntimeError(
                    f"engine {self.name} is stopped"))
                continue
            items, clock = sealed.items, sealed.clock
            if self._in_flight.acquire(blocking=False):
                t0 = time.perf_counter()
                clock.wait("wait_launcher", t0)
            else:
                # every in-flight slot is taken: from here the batch
                # waits for a readback, not for this thread
                t_w = sp.to("wait_slot")
                if t_w is not None:
                    clock.wait("wait_launcher", t_w)
                self._in_flight.acquire()
                t0 = time.perf_counter()
                clock.wait("wait_slot", t0)
            sp.to("bookkeep", t0)
            bid, unclocked = self._track_dispatch(t0, items, sealed.bucket)
            # the pending trace record holds the SAME clock dict
            # _launch fills in — a flight dump of a wedged batch reads
            # the stages completed so far (obs/trace.py)
            trace.batch_begin(self.name, bid, items, sealed.bucket,
                              sealed.n, clock, self._trace_device)
            try:
                out = self._launch(dev, clock, sealed.bucket)
            except Exception as exc:  # noqa: BLE001 — surface to every caller
                self._in_flight.release()
                with self._exec_lock:
                    self._outstanding.pop(bid, None)
                self._fail_batch(sealed, exc)
                trace.batch_complete(self.name, bid, items,
                                     status="error")
                log.exception("engine %s step failed", self.name)
                continue
            since = None
            if idle is not None:
                with self._exec_lock:
                    since, self._idle_since = self._idle_since, None
                    self._on_device += 1
                # the launch span's end, before the completer appends
                _, t1, dur = clock.spans[-1]
            self._done.put((out, t0, bid, sealed))
            self._record_batch(sealed, unclocked)
            if since is not None:
                # launched onto a dry engine: the stretch up to the
                # launch's end, by where this batch was meanwhile
                # (spans the completer has appended since lie behind it)
                idle.add(since, t1 + dur, items, clock)
        sp.to(None)

    def _drain_upload_q(self, exc: Exception) -> None:
        """Fail every uploaded-but-unlaunched batch (stop/abandon/
        stall) and return its block."""
        while True:
            try:
                entry = self._upload_q.get_nowait()
            except queue.Empty:
                return
            if entry is not None:
                self._fail_batch(entry[1], exc)

    # -------------------------------------------------------- dispatch

    def _dispatch_loop(self) -> None:
        """The one dispatch loop (evam_tpu/sched/): drain the class
        queues realtime-first (starvation-proof weighted pick), form
        batches under the CLASS deadline — cameras keep a small
        latency floor while bulk traffic fills big buckets — and shed
        frames that outlived their class staleness budget
        (oldest-first) before they waste a device slot."""
        cq = self._classq
        shedder = self._shedder
        sp = self._sp_dispatch
        while True:
            # from here until a batch is formed the dispatcher waits
            # for items: the class queues are empty (the pick), then it
            # HOLDS items for the class deadline's fill; one state in
            # the sums, two names in a capture
            sp.to("wait_items")
            if self._stop.is_set():
                self._fail_queued(RuntimeError("engine stopped"))
                break
            # shed expired waiters across ALL classes first: the
            # backlog a busy realtime lane starves must fail loudly
            # instead of rotting in queue
            shedder.sweep(cq)
            cls = cq.pick(timeout=0.05)
            if cls is None:
                continue
            sp.to("wait_items.fill")
            items = cq.collect(cls, self.max_batch,
                               self._class_deadline_s[cls])
            # the batch-formation wait itself can age items past
            # budget (and a realtime burst can delay a picked batch
            # class) — filter the formed batch too
            items = shedder.shed(cls, items)
            if not items:
                continue
            self._dispatch_items(items)
        sp.to(None)

    def _dispatch_items(self, items: list[_WorkItem]) -> None:
        """Stage + dispatch one class-ordered pick through the staging
        ring (zero per-batch allocation, copies on this thread). A
        pick that exceeds the top bucket's rows — or, packed, the
        unit block — is split across batches in dispatch order
        instead of silently clamped (oversize-split contract)."""
        bucket_fn = self._bucket_ragged if self._packed else self._bucket
        staged = [(it.inputs, it) for it in items]
        dispatched = 0
        while staged:
            clock = trace.StageClock()
            clock["submit_wait"] = (
                time.perf_counter() - staged[0][1].t_submit)
            try:
                sealed, staged = self._ring.stage(
                    staged, bucket_fn, clock, self._sp_dispatch)
            except RuntimeError:
                exc = RuntimeError(f"engine {self.name} is stopped")
                for _, it in staged:
                    _safe_set_exception(it.future, exc)
                return
            if sealed is None:
                continue  # every staged row failed its shape check
            dispatched += 1
            self._dispatch_batch(sealed)
        if dispatched > 1:
            self._count_oversize_split(dispatched - 1)

    # ------------------------------------------------------ completion

    def _completion_loop(self) -> None:
        sp = self._sp_complete
        idle = self._idle
        while True:
            sp.to("wait_launch", annotate=False)  # as the launcher's
            entry = self._done.get()
            if entry is None:
                sp.to(None)
                break
            out, t0, bid, sealed = entry
            items, clock = sealed.items, sealed.clock
            t_rb = time.perf_counter()
            # np.asarray returns when the device has finished the step
            # and the copy to the host has landed
            sp.to("wait_device", t_rb)
            clock.wait("wait_completer", t_rb)
            try:
                # single readback per batch; the D2H copy is already
                # in flight (copy_to_host_async at launch), so this
                # blocks only on the residual
                host = np.asarray(out)
            except Exception as exc:  # noqa: BLE001
                self._fail_batch(sealed, exc)
                trace.batch_complete(self.name, bid, items,
                                     status="error")
                self._in_flight.release()
                continue
            finally:
                t_end = sp.to("resolve")
                with self._exec_lock:
                    done = self._outstanding.pop(bid, None)
                    if idle is not None:
                        self._on_device -= 1
                        if not self._on_device:
                            self._idle_since = t_end
            self._in_flight.release()
            if done is not None:
                # bucket compiled + round-tripped: plain watchdog
                # budget (no first-batch grace) from here on — and a
                # mid-traffic cold bucket's round-trip IS its compile
                # (compile-cache accounting; warmup banks warmed
                # buckets before traffic instead)
                with self._exec_lock:
                    if done[2] not in self._buckets_done:
                        self.stats.compiled_programs += 1
                        self.stats.compile_seconds += (
                            time.perf_counter() - done[0])
                    self._buckets_done.add(done[2])
            # the staging block is free the moment the readback
            # materialized the output on host
            self._ring.release(sealed)
            if self.stalled.is_set():
                # the "wedged" call was merely slow (e.g. a mid-traffic
                # multichip compile) and has now completed — recover
                # instead of staying bricked until restart
                self.stalled.clear()
                log.warning(
                    "engine %s recovered: a previously-stalled device "
                    "call completed; accepting work again", self.name,
                )
            now = time.perf_counter()
            metrics.observe("evam_step_seconds", now - t0, {"engine": self.name})
            readback_s = now - t_rb
            t_res = time.perf_counter()
            clock.span("readback", t_rb, t_res - t_rb)
            # ragged scatter-back: a packed batch's output rows are
            # unit rows — item i owns host[offset[i] : offset[i] +
            # row_len[i]] (exactly its real region rows, zero-region
            # items resolve to an empty slice). Dense batches keep the
            # one-row-per-item contract.
            ragged = sealed.row_len is not None
            for i, it in enumerate(items):
                metrics.observe(
                    "evam_item_latency_seconds", now - it.t_submit, {"engine": self.name}
                )
                if it.trace is not None:
                    # the runner's collect wait starts here
                    # (stages/runner.py pump)
                    it.future.t_resolved = t_res
                if ragged:
                    off = int(sealed.row_offset[i])
                    _safe_set_result(
                        it.future,
                        host[off:off + int(sealed.row_len[i])])
                else:
                    _safe_set_result(it.future, host[i])
            resolve_s = time.perf_counter() - t_res
            clock.span("resolve", t_res, resolve_s)
            # retire the batch trace record: every member frame's
            # tree gets its queue wait and the batch's timeline
            trace.batch_complete(self.name, bid, items)
            if done is not None and not done[4]:
                with self._exec_lock:
                    self.stats.add_stage("readback", readback_s)
                    self.stats.add_stage("resolve", resolve_s)
            metrics.observe("evam_engine_stage_seconds", readback_s,
                            {"engine": self.name, "stage": "readback"})
            metrics.observe("evam_engine_stage_seconds", resolve_s,
                            {"engine": self.name, "stage": "resolve"})

    def _watchdog_loop(self) -> None:
        """Fail futures stranded behind a wedged device call and flag
        the engine (the launcher/completer threads stay blocked in
        C++ — only the service-level contract can be saved). A
        bucket's first batch gets stall_timeout_s × first_batch_grace:
        its round-trip legitimately contains trace + XLA compile, and
        without the grace every cold start — especially a supervisor
        rebuild's fresh jit — reads as a wedge."""
        # floor 0.2 s: supervised tests run sub-second stall budgets;
        # production timeouts (120 s) still poll every 30 s
        interval = max(self.stall_timeout_s / 4.0, 0.2)
        while not self._stop.wait(interval):
            # keep the backlog gauges live even when nothing
            # dispatches — a wedged or idle engine must not show the
            # last batch's queue depth while work piles up
            self.refresh_queue_gauges()
            now = time.perf_counter()
            with self._exec_lock:
                slots = list(self._outstanding.values())
            stuck: list[_WorkItem] = []
            for _t0, items, _b, deadline, _unclocked in slots:
                if now > deadline:
                    stuck.extend(items)
            if not stuck:
                continue
            self.stalled.set()
            log.error(
                "engine %s stalled: device call exceeded %.0fs; failing "
                "%d stranded item(s) and rejecting new work",
                self.name, self.stall_timeout_s, len(stuck),
            )
            metrics.inc("evam_engine_stalls", labels={"engine": self.name})
            exc = TimeoutError(
                f"engine {self.name} device call exceeded "
                f"{self.stall_timeout_s:.0f}s (backend wedged)"
            )
            for it in stuck:
                _safe_set_exception(it.future, exc)
            # strand nothing in the upload queue or the class queues
            # either
            self._drain_upload_q(exc)
            self._fail_queued(exc)
