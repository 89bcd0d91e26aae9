"""Load shedding: per-class staleness budgets enforced at dispatch.

A video frame is perishable — detections on a frame the camera
captured two seconds ago are not "late results", they are wrong
results (OCTOPINF's stale-frame argument, PAPERS.md). So under
overload the right policy is freshest-frame-wins: drop the OLDEST
queued frames first and fail their futures loudly, instead of letting
the queue rot and every frame arrive uniformly late.

The ``Shedder`` owns the per-class staleness budgets
(``EVAM_SCHED_STALENESS_MS_*`` → SchedConfig.staleness_ms) and the
accounting: every shed rides ``evam_sched_shed_total{class}`` plus a
reset-proof local counter (the bench contract line and /healthz read
the local counts so a window-scoped ``metrics.reset()`` can't hide
sheds). A shed future fails with ``ShedError`` — a loud, typed error
the per-frame isolation in stages/runner.py absorbs as one counted
frame error, never a stream kill.
"""

from __future__ import annotations

import threading
import time

from evam_tpu.obs import get_logger, metrics
from evam_tpu.sched.classes import PRIORITIES

log = get_logger("sched.shedder")


class ShedError(RuntimeError):
    """A queued frame exceeded its class staleness budget and was
    dropped at dispatch (oldest-first). Deliberate overload behavior,
    not an engine fault."""

    def __init__(self, priority: str, age_s: float, budget_s: float,
                 engine: str = ""):
        self.priority = priority
        self.age_s = age_s
        self.budget_s = budget_s
        self.engine = engine
        super().__init__(
            f"frame shed: {priority}-class item aged {age_s * 1e3:.0f}ms "
            f"> staleness budget {budget_s * 1e3:.0f}ms"
            f"{f' (engine {engine})' if engine else ''}"
        )


class Shedder:
    """Per-engine staleness enforcement over ClassQueues.

    ``sweep`` runs every dispatcher cycle and sheds expired items
    still WAITING in any class queue (this is what bounds the backlog
    a busy realtime lane starves out of service); ``shed`` filters a
    just-formed batch (items can expire during batch-formation wait).
    Both drop oldest-first by construction: FIFO queues age
    monotonically from head to tail.
    """

    #: dispatcher thread sheds, server/bench threads read the counts —
    #: mutations must hold ``_lock`` (lock-discipline pass).
    SHARED_UNDER = {"counts": "_lock"}

    def __init__(self, engine_name: str, staleness_s: dict[str, float]):
        self.engine_name = engine_name
        self.staleness_s = dict(staleness_s)
        self._lock = threading.Lock()
        #: reset-proof per-class shed counts (bench/healthz source)
        self.counts = {c: 0 for c in PRIORITIES}

    def sweep(self, queues, now: float | None = None) -> int:
        """Shed every expired item waiting in ``queues``; returns the
        number shed."""
        now = time.perf_counter() if now is None else now
        total = 0
        for cls, budget in self.staleness_s.items():
            if budget <= 0:
                continue
            expired = queues.pop_expired(cls, now - budget)
            if expired:
                self._fail(cls, expired, now, budget)
                total += len(expired)
        return total

    def shed(self, priority: str, items: list,
             now: float | None = None) -> list:
        """Filter a formed batch: fail items over budget, return the
        fresh survivors (order preserved)."""
        budget = self.staleness_s.get(priority, 0.0)
        if budget <= 0 or not items:
            return items
        now = time.perf_counter() if now is None else now
        cutoff = now - budget
        survivors = [it for it in items if it.t_submit >= cutoff]
        dropped = [it for it in items if it.t_submit < cutoff]
        if dropped:
            self._fail(priority, dropped, now, budget)
        return survivors

    def _fail(self, priority: str, items: list, now: float,
              budget: float) -> None:
        with self._lock:
            self.counts[priority] = self.counts.get(priority, 0) + len(items)
        metrics.inc("evam_sched_shed", value=float(len(items)),
                    labels={"class": priority})
        log.warning(
            "engine %s shed %d stale %s-class frame(s) "
            "(oldest %.0fms > budget %.0fms)",
            self.engine_name, len(items), priority,
            (now - items[0].t_submit) * 1e3, budget * 1e3,
        )
        for it in items:
            exc = ShedError(priority, now - it.t_submit, budget,
                            self.engine_name)
            try:
                it.future.set_exception(exc)
            except Exception:  # noqa: BLE001 — already resolved/cancelled
                pass
