"""EngineSupervisor: quarantine-and-rebuild for wedged BatchEngines.

The stall watchdog (engine/batcher.py) detects a wedged device call,
fails the stranded futures and flags the engine — but until this
module the engine then stayed dead: /healthz sat at 503 "stalled" and
every stream sharing the engine failed until someone restarted the
process (the exact outage documented across BENCH_r03–r05; the
reference's only recovery story is container restart policy,
SURVEY.md §5.3). ``SupervisedEngine`` closes that gap with in-process
recovery, the continuous-operation discipline OCTOPINF (PAPERS.md)
treats as table stakes for edge video serving:

* a **stable handle**: the hub caches ONE SupervisedEngine per key and
  stages capture it once (`stages/infer.py`); the live BatchEngine
  underneath is swappable, so a rebuild is invisible to every holder —
  no re-resolution, no stage rebuild, no stream restart;
* a **monitor thread** watches the live engine (stalled flag set by
  the watchdog, or a dead dispatcher/completer thread) and, on a trip:
  **quarantines** the old engine (``BatchEngine.abandon()`` — fail
  everything failable, never join the wedged-in-C++ threads), waits an
  exponential backoff, **rebuilds** via the factory (fresh jitted
  step, fresh SlotRing, fresh warmup from the captured example) and
  atomically swaps the replacement in;
* a **restart budget**: at most ``max_restarts`` rebuilds within a
  sliding ``restart_window_s``. Exhausting it is a terminal
  ``degraded`` state — the engine stops flapping, /healthz reports
  503 "degraded" (vs the transient 503 "restarting"), and the
  operator's restart policy takes over with full information.

In-flight streams see exactly one transient ``TimeoutError`` per
wedge (stranded futures from the watchdog; submits during the rebuild
window) — absorbed by the per-frame error isolation in
``stages/runner.py`` and the per-stream retry loop in
``server/instance.py`` — instead of permanent failure.

States ride ``evam_engine_state`` (gauge: 0=running, 1=restarting,
2=degraded) and rebuilds ride ``evam_engine_restarts`` (counter), both
surfaced on /healthz, /engines and the serve bench contract line.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Callable

from evam_tpu.analysis.annotations import locked_by
from evam_tpu.engine.batcher import BatchEngine, EngineStats
from evam_tpu.obs import get_logger, metrics
from evam_tpu.obs import trace

log = get_logger("engine.supervisor")

#: gauge encoding for evam_engine_state, index = value
ENGINE_STATES = ("running", "restarting", "degraded")


def _engine_snapshot(eng) -> dict:
    """Best-effort queue/in-flight snapshot of a wedged engine for the
    flight-recorder header — taken before abandon() fails the stranded
    futures and zeroes the evidence."""
    try:
        return {
            "queue_depth": eng.queue_depth(),
            "class_depths": eng.class_depths(),
            "shed_counts": eng.shed_counts(),
            "outstanding": len(eng._outstanding),
            "stalled": eng.stalled.is_set(),
            "batches": eng.stats.batches,
        }
    except Exception:  # noqa: BLE001 — engine mid-teardown
        return {}


class SupervisedEngine:
    """Stable, restartable handle around a replaceable BatchEngine.

    Duck-types the BatchEngine surface the stages and hub use
    (``submit``/``warm_async``/``set_example``/``stats``/``warmed``/
    ``stalled``/...): unknown attributes delegate to the live engine,
    so existing callers — including tests poking ``buckets`` or
    ``_bucket`` — keep working unchanged.
    """

    #: Shared between the monitor thread and every caller thread
    #: (submit/stop/healthz snapshots); guarded by ``_lock``
    #: (enforced by the ``evam_tpu.analysis`` lock-discipline pass).
    SHARED_UNDER = {
        "state": "_lock",
        "restarts": "_lock",
        "last_stall_ts": "_lock",
        "_shed_carry": "_lock",
        "_stats_carry": "_lock",
        "_example": "_lock",
        "_warm_requested": "_lock",
        "_engine": "_lock",
    }

    def __init__(
        self,
        name: str,
        factory: Callable[[], BatchEngine],
        max_restarts: int = 3,
        restart_window_s: float = 300.0,
        backoff_s: float = 0.5,
        max_backoff_s: float = 30.0,
        poll_interval_s: float = 0.1,
    ):
        self.name = name
        self._factory = factory
        self.max_restarts = max_restarts
        self.restart_window_s = restart_window_s
        self.backoff_s = backoff_s
        self.max_backoff_s = max_backoff_s
        self.poll_interval_s = poll_interval_s
        #: lifecycle stats surfaced on /engines and /healthz
        self.restarts = 0
        self.last_stall_ts: float | None = None
        self._restart_times: deque[float] = deque()
        #: cumulative counters folded in from quarantined engines
        #: (_absorb_counters): a rebuild swaps in a fresh BatchEngine
        #: with zeroed local counts, and /healthz, /engines and the
        #: bench contract line must stay MONOTONIC across it
        self._shed_carry: dict[str, int] = {}
        self._stats_carry: EngineStats | None = None
        self._example: dict | None = None
        self._warm_requested = False
        self._lock = threading.RLock()
        self.state = "running"
        self._engine = factory()
        metrics.set("evam_engine_state", 0.0, {"engine": name})
        self._stop_evt = threading.Event()
        self._monitor = threading.Thread(
            target=self._monitor_loop,
            name=f"engine-{name}-supervisor", daemon=True,
        )
        self._monitor.start()

    # ------------------------------------------------------------- API

    def submit(self, priority: str = "standard",
               units: int | None = None,
               stream: str | None = None,
               trace: "object | None" = None, **inputs) -> Future:
        with self._lock:
            state = self.state
            eng = self._engine
        if state == "degraded":
            raise RuntimeError(
                f"engine {self.name} is degraded: restart budget "
                f"({self.max_restarts} rebuilds in "
                f"{self.restart_window_s:.0f}s) exhausted; serving this "
                "engine requires a process restart"
            )
        if state == "restarting":
            # same transient contract as a stranded future: the stream
            # retry/error-isolation layer absorbs it and the next
            # submit after the swap succeeds
            raise TimeoutError(
                f"engine {self.name} is restarting after a wedge; "
                "retry shortly"
            )
        return eng.submit(priority=priority, units=units, stream=stream,
                          trace=trace, **inputs)

    def warm_async(self, **example) -> None:
        with self._lock:
            self._example = dict(example)
            self._warm_requested = True
            eng = self._engine
        eng.warm_async(**example)

    def set_example(self, **example) -> None:
        with self._lock:
            self._example = dict(example)
            eng = self._engine
        eng.set_example(**example)

    def stop(self) -> None:
        self._stop_evt.set()
        with self._lock:
            eng = self._engine
            state = self.state
        if state == "running":
            eng.stop()
        else:
            # a quarantined/degraded engine may hold threads wedged in
            # C++ — abandon (non-blocking) instead of joining them
            eng.abandon()
        self._monitor.join(timeout=5)

    # --------------------------------------- cumulative counter carry

    @property
    def stats(self) -> EngineStats:
        """Cumulative EngineStats: the live engine's counts plus
        everything absorbed from quarantined predecessors. With no
        restarts this is the live object itself (zero overhead); after
        a rebuild it is a merged read-only snapshot."""
        live = object.__getattribute__(self, "_engine").stats
        with self._lock:
            carry = self._stats_carry
            if carry is None:
                return live
            merged = EngineStats()
            merged.absorb(carry)
        merged.absorb(live)
        return merged

    def shed_counts(self) -> dict[str, int]:
        """Per-class shed totals including quarantined predecessors —
        keeps hub.shed_totals() (and with it /healthz and the bench
        line) monotonic across supervisor rebuilds."""
        live = object.__getattribute__(self, "_engine").shed_counts()
        with self._lock:
            if not self._shed_carry:
                return live
            out = dict(self._shed_carry)
        for c, n in live.items():
            out[c] = out.get(c, 0) + n
        return out

    def _absorb_counters(self, eng: BatchEngine) -> None:
        """Fold a quarantined engine's cumulative counters into the
        carry BEFORE it is abandoned and replaced.

        The fleet layer (evam_tpu/fleet/engine.py) applies this same
        carry discipline one level up when a PLACEMENT MOVE retires a
        degraded shard: the shard's merged counters (which already
        include this carry) are absorbed into the fleet-level carry,
        so /healthz and the bench line stay monotonic fleet-wide."""
        try:
            shed = eng.shed_counts()
            live = eng.stats
        except Exception:  # noqa: BLE001 — engine mid-teardown
            return
        with self._lock:
            for c, n in shed.items():
                self._shed_carry[c] = self._shed_carry.get(c, 0) + n
            if self._stats_carry is None:
                self._stats_carry = EngineStats()
            # absorb() covers the full counter surface (items, unit
            # occupancy, bucket counts, compile-cache bill, oversize
            # splits) so /engines and the bench line stay monotonic
            # across rebuilds for the new fields too
            self._stats_carry.absorb(live)

    # ------------------------------------------------------ delegation

    def __getattr__(self, item):
        # only called for attributes NOT found on the proxy: stats,
        # warmed, stalled, buckets, _ring, _bucket, ...
        return getattr(object.__getattribute__(self, "_engine"), item)

    # ------------------------------------------------------- internals

    @locked_by("_lock")
    def _set_state(self, state: str) -> None:
        self.state = state
        metrics.set("evam_engine_state", float(ENGINE_STATES.index(state)),
                    {"engine": self.name})

    def _wedged(self, eng: BatchEngine) -> str | None:
        """Reason string when the live engine needs a rebuild."""
        if eng.stalled.is_set():
            return "stall watchdog fired"
        if eng._stop.is_set():
            return None  # deliberate stop, not a wedge
        if not eng._dispatcher.is_alive():
            return "dispatcher thread died"
        if not eng._completer.is_alive():
            return "completion thread died"
        if not eng._launcher.is_alive():
            return "transfer launcher thread died"
        return None

    def _monitor_loop(self) -> None:
        while not self._stop_evt.wait(self.poll_interval_s):
            with self._lock:
                if self.state == "degraded":
                    return
                eng = self._engine
            # keep the backlog gauges live even while the engine is
            # wedged/idle (they otherwise refresh only on dispatch)
            try:
                eng.refresh_queue_gauges()
            except Exception:  # noqa: BLE001 — engine mid-teardown
                pass
            reason = self._wedged(eng)
            if reason is not None:
                self._quarantine_and_rebuild(eng, reason)

    def _quarantine_and_rebuild(self, eng: BatchEngine, reason: str) -> None:
        with self._lock:
            self.last_stall_ts = time.time()
        log.error("engine %s wedged (%s); quarantining", self.name, reason)
        # flight recorder: dump the last-N spans + the wedged engine's
        # queue/in-flight state to a JSONL artifact BEFORE abandon()
        # fails the stranded futures and mutates the evidence
        trace.flight_dump(self.name, reason, state=_engine_snapshot(eng))
        # crash-consistency barrier (evam_tpu/state/): snapshot every
        # registered stream's cross-frame state before the swap — if
        # this rebuild cascades into a process restart, the resumed
        # streams restore from a checkpoint no older than the wedge
        from evam_tpu.state import active as ckpt_active

        ckpt = ckpt_active()
        if ckpt is not None:
            ckpt.capture_all(barrier="pre_rebuild")
        self._absorb_counters(eng)
        eng.abandon()
        while not self._stop_evt.is_set():
            now = time.time()
            while (self._restart_times
                   and now - self._restart_times[0] > self.restart_window_s):
                self._restart_times.popleft()
            if len(self._restart_times) >= self.max_restarts:
                with self._lock:
                    self._set_state("degraded")
                trace.flight_dump(
                    self.name, "restart budget exhausted; degraded",
                    state=_engine_snapshot(eng))
                log.error(
                    "engine %s restart budget exhausted (%d rebuilds in "
                    "%.0fs); entering terminal degraded state — process "
                    "restart required",
                    self.name, self.max_restarts, self.restart_window_s,
                )
                return
            self._restart_times.append(now)
            with self._lock:
                self.restarts += 1
                self._set_state("restarting")
            metrics.inc("evam_engine_restarts", labels={"engine": self.name})
            attempt = len(self._restart_times)
            delay = min(self.backoff_s * (2 ** (attempt - 1)),
                        self.max_backoff_s)
            log.warning(
                "engine %s rebuild %d/%d in %.2fs (window %.0fs)",
                self.name, attempt, self.max_restarts, delay,
                self.restart_window_s,
            )
            if self._stop_evt.wait(delay):
                return
            try:
                new = self._factory()
            except Exception:  # noqa: BLE001 — a failed build consumes budget
                log.exception("engine %s rebuild failed", self.name)
                continue
            with self._lock:
                warm = self._warm_requested and self._example is not None
                example = self._example
            if warm:
                # re-admit WARM: swapping in a cold engine makes every
                # stream pay (and contend with) the fresh jit's
                # compile inside a dispatched batch — on a loaded host
                # that reads as another stall and the engine flaps.
                # While warming, the handle stays `restarting`
                # (healthz 503) and submits fail fast and cheap. A
                # warmup that never finishes means the backend is
                # still broken: abandon, consume budget, retry.
                new.warm_async(**example)
                warm_timeout = max(
                    new.stall_timeout_s * new.first_batch_grace
                    * max(len(new.buckets), 1), 10.0)
                warm_deadline = time.time() + warm_timeout
                warm_ok = True
                while not new.warmed.wait(timeout=0.2):
                    if self._stop_evt.is_set():
                        new.abandon()
                        return
                    if time.time() > warm_deadline:
                        warm_ok = False
                        break
                if not warm_ok:
                    log.error(
                        "engine %s rebuild warmup did not finish in "
                        "%.0fs; treating as a failed rebuild",
                        self.name, warm_timeout,
                    )
                    new.abandon()
                    continue
            else:
                if example is not None:
                    new.set_example(**example)
                # no warmup was requested: the fresh engine is as
                # ready as the original ever was
                new.warmed.set()
            with self._lock:
                self._engine = new
                self._set_state("running")
            log.warning(
                "engine %s rebuilt and re-admitted (restart %d, fresh "
                "jitted step + staging ring)", self.name, self.restarts,
            )
            return
