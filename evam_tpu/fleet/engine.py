"""FleetEngine: one model served by N per-chip shards + a mesh twin.

The stable fleet-mode handle the hub caches per engine key
(EVAM_FLEET=sharded). It owns:

- ``shards``: one engine per mesh device (each usually a
  SupervisedEngine around a single-device BatchEngine), serving the
  small buckets. A stream's traffic is pinned to one shard by the
  consistent-hash placer, so per-stream outputs are bit-identical to
  a single-chip engine — same jit, same device count, no collective.
- one lazily-built MESH engine (full data mesh, ``fleet_local``
  bucket bypass) for ``batch``-class traffic: bulk frames tolerate
  the collective and want the big data-parallel buckets; its sub-data
  rungs run single-device, so a trickle of batch traffic doesn't pay
  an 8-way all-gather for 2 real rows.

Drain-and-rebalance: when a shard's supervisor marks it terminally
``degraded`` (restart budget exhausted — transient wedges are the
supervisor's own job), the shard is retired: its counters are
absorbed into a fleet-level carry (the supervisor's rebuild-carry
discipline, one level up — /healthz and the bench line stay monotonic
fleet-wide), its streams re-place onto the survivors
(``evam_fleet_rebalance_total`` counts every move), and its in-flight
futures resolve with the stop error so the per-class stream policy
decides: realtime/standard retry onto the new shard, batch sheds.

Everything the hub's aggregate views touch (stats, warmed, stalled,
state, queue depths, shed counts) is implemented as a fleet-wide
aggregate, so /healthz, /engines and admission read through a
FleetEngine exactly like a single engine — with Σ-shard capacity
instead of one chip's.
"""

from __future__ import annotations

import os
import threading
import time

from evam_tpu.engine.batcher import EngineStats
from evam_tpu.fleet.placer import ConsistentHashPlacer
from evam_tpu.obs import faults, get_logger, metrics

log = get_logger("fleet.engine")

FLEET_MODES = ("sharded", "off")


def fleet_mode(value: str | None = None) -> str:
    """Resolve the fleet mode: explicit arg > EVAM_FLEET > off."""
    mode = value or os.environ.get("EVAM_FLEET", "off") or "off"
    if mode not in FLEET_MODES:
        raise ValueError(
            f"EVAM_FLEET must be one of {FLEET_MODES}, got {mode!r}")
    return mode


class _AllWarmed:
    """Event-shaped view: set when every member event is set."""

    def __init__(self, events):
        self._events = events

    def is_set(self) -> bool:
        return bool(self._events) and all(
            e.is_set() for e in self._events)


class _AnySet:
    """Event-shaped view: set when any member event is set."""

    def __init__(self, events):
        self._events = events

    def is_set(self) -> bool:
        return any(e.is_set() for e in self._events)


class FleetEngine:
    """Consistent-hash front over per-chip shard engines.

    ``shard_factory(plan, label)`` builds one shard engine on a
    single-device plan; ``mesh_factory(label)`` (optional) builds the
    data-parallel big-bucket engine on the full mesh. Both are hub
    closures so shards inherit the hub's supervision, sched,
    transfer-depth and ragged configuration.
    """

    #: Placement/carry state is hit from every submitting stream
    #: thread plus the degraded-sweep and drain threads; guarded by
    #: ``_lock`` (RLock).  The lazily-built mesh engine has its own
    #: creation lock.  Enforced by the ``evam_tpu.analysis`` lock-
    #: discipline pass.
    SHARED_UNDER = {
        "shards": "_lock",
        "_pins": "_lock",
        "_degraded": "_lock",
        "_retired_planned": "_lock",
        "_devices": "_lock",
        "rebalances": "_lock",
        "scale_ups": "_lock",
        "scale_downs": "_lock",
        "_scaling": "_lock",
        "_last_spinup_s": "_lock",
        "_stats_carry": "_lock",
        "_shed_carry": "_lock",
        "_restarts_carry": "_lock",
        "_drains": "_lock",
        "_example": "_lock",
        "_mesh_eng": "_mesh_lock",
    }

    def __init__(self, name: str, shard_factory, plans,
                 mesh_factory=None, vnodes: int = 512,
                 initial: int = 0):
        if not plans:
            raise ValueError(f"fleet engine {name}: no shard plans")
        self.name = name
        self._mesh_factory = mesh_factory
        self._mesh_eng = None
        self._mesh_lock = threading.Lock()
        self._lock = threading.RLock()
        #: full per-device plan list — the structural scale ceiling;
        #: ``initial`` (autoscaling boot size, EVAM_FLEET_SHARDS when
        #: EVAM_FLEET_MAX_SHARDS is set) builds only the first n and
        #: leaves the rest for scale_up()
        self._plans = list(plans)
        self._shard_factory = shard_factory
        self._vnodes = vnodes
        n = len(self._plans)
        if initial > 0:
            n = max(1, min(initial, n))
        self.shards: dict[str, object] = {}
        self._devices: dict[str, str] = {}
        for i, plan in enumerate(self._plans[:n]):
            label = f"s{i}"
            self.shards[label] = shard_factory(plan, f"{name}@{label}")
            self._devices[label] = str(plan.mesh.devices.flat[0])
        self._placer = ConsistentHashPlacer(list(self.shards), vnodes)
        #: stream key -> shard label (the pin that makes placement
        #: sticky; the placer alone would already be deterministic,
        #: the pin makes MOVES observable so they can be counted)
        self._pins: dict[str, str] = {}
        #: chip-loss retirements: the plan index is DEAD — scale_up
        #: never reuses these labels. Planned scale-downs land in
        #: _retired_planned instead (healthy chip, reusable slot).
        self._degraded: set[str] = set()
        self._retired_planned: set[str] = set()
        self.rebalances = 0
        self.scale_ups = 0
        self.scale_downs = 0
        #: one spin-up at a time (warm-before-join can take seconds;
        #: a second concurrent grow returns None, it does not race
        #: the first)
        self._scaling = False
        #: last scale_up's build+warm wall seconds (soak/bench probe)
        self._last_spinup_s = 0.0
        #: retired-shard carry (supervisor discipline, fleet level)
        self._stats_carry: EngineStats | None = None
        self._shed_carry: dict[str, int] = {}
        self._restarts_carry = 0
        self._example: dict | None = None
        self._drains: list[threading.Thread] = []

    # ------------------------------------------------------------- API

    def submit(self, priority: str = "standard",
               units: int | None = None,
               stream: str | None = None,
               trace: "object | None" = None, **inputs):
        """Route one item: batch class → mesh engine (big data-parallel
        buckets), everything else → the stream's pinned shard."""
        self._sweep_degraded()
        if priority == "batch" and self._mesh_factory is not None:
            return self._mesh().submit(priority=priority, units=units,
                                       stream=stream, trace=trace,
                                       **inputs)
        label = self._place(stream or "")
        # fault drill: current() is memoized (None-check when clean)
        # and re-resolved per submit — soaks arm EVAM_FAULT_INJECT
        # after the fleet is built and warm
        inj = faults.current()
        if inj is not None:
            with self._lock:
                survivors = len(self.shards) > 1
            if survivors and inj.maybe_shard_loss(label):
                # injected chip loss mid-dispatch: the placed shard
                # dies between placement and submit — exactly the
                # window the checkpoint/migration path must cover
                # (never injected on the last live shard; a fleet of
                # zero can't serve)
                self._retire(label, reason="shard_loss")
                label = self._place(stream or "")
        with self._lock:
            eng = self.shards.get(label)
        if eng is None:  # retired between place and lookup
            label = self._place(stream or "")
            with self._lock:
                eng = self.shards[label]
        return eng.submit(priority=priority, units=units, stream=stream,
                          trace=trace, **inputs)

    def _place(self, stream: str) -> str:
        with self._lock:
            cur = self._pins.get(stream)
            if cur is not None and cur in self.shards:
                return cur
            label = self._placer.place(stream)
            if cur is not None and cur != label:
                self.rebalances += 1
                metrics.inc("evam_fleet_rebalance_total",
                            labels={"engine": self.name})
            self._pins[stream] = label
            return label

    def _sweep_degraded(self) -> None:
        """Retire every live shard whose supervisor went terminal."""
        with self._lock:
            dead = [l for l, e in self.shards.items()
                    if getattr(e, "state", "running") == "degraded"]
        for label in dead:
            self._retire(label)

    def _retire(self, label: str, reason: str = "shard_loss") -> None:
        """Drain-and-rebalance one degraded shard: absorb counters,
        migrate its streams, fail its in-flight work via stop()."""
        # checkpoint BEFORE the pins move: the pre-rebalance barrier
        # snapshots each migrating stream's cross-frame state so the
        # destination shard's first frame sees the same gate/coaster/
        # tracker state the lost chip had (evam_tpu/state/). Capture
        # takes the instance's own locks — must run outside _lock.
        from evam_tpu.state import active as ckpt_active

        store = ckpt_active()
        if store is not None:
            with self._lock:
                doomed = [s for s, l in self._pins.items() if l == label]
            for s in doomed:
                store.capture(s, barrier="pre_rebalance", reason=reason)
        with self._lock:
            eng = self.shards.pop(label, None)
            if eng is None:
                return
            if reason == "scale_down":
                # planned shrink: the chip is healthy, the label (and
                # its plan slot) is reusable by a later scale_up
                self._retired_planned.add(label)
            else:
                self._degraded.add(label)
            self._placer.mark_down(label)
            # carry BEFORE the engine goes away — the PR-5 rebuild
            # discipline applied to a placement move: the fleet view
            # must stay monotonic even though the shard's rows vanish
            try:
                carry = self._stats_carry or EngineStats()
                carry.absorb(eng.stats)
                self._stats_carry = carry
                for k, v in eng.shed_counts().items():
                    self._shed_carry[k] = self._shed_carry.get(k, 0) + v
                self._restarts_carry += getattr(eng, "restarts", 0)
            except Exception:  # noqa: BLE001 — shard mid-teardown
                pass
            moved = [s for s, l in self._pins.items() if l == label]
            for s in moved:
                new = self._placer.place(s)
                self._pins[s] = new
                self.rebalances += 1
                metrics.inc("evam_fleet_rebalance_total",
                            labels={"engine": self.name})
        log.warning(
            "fleet %s: shard %s degraded — retired, %d stream(s) "
            "migrated (%d moves total)", self.name, label, len(moved),
            self.rebalances)
        # stop() fails the shard's queued + in-flight futures with the
        # engine-stopped error; the per-class stream policy upstream
        # (retry/shed) takes it from there. Joined off-thread — a
        # placement move must not stall the submitting stream.
        t = threading.Thread(target=self._safe_stop, args=(eng,),
                             name=f"fleet-{self.name}-drain-{label}",
                             daemon=True)
        t.start()
        with self._lock:
            self._drains.append(t)

    def scale_down(self, label: str | None = None) -> str | None:
        """Deliberate fleet scale-down: retire one live shard (the
        highest-numbered by default), migrating its streams with a
        pre-rebalance checkpoint exactly like a chip loss — a planned
        shrink must not cost tracker identities. Refuses to retire the
        last shard. Returns the retired label (None = nothing done)."""
        with self._lock:
            live = sorted(self.shards)
            if len(live) <= 1:
                return None
            if label is None:
                label = live[-1]
            elif label not in self.shards:
                return None
        self._retire(label, reason="scale_down")
        with self._lock:
            self.scale_downs += 1
        return label

    def scale_up(self, warm_timeout_s: float = 120.0) -> str | None:
        """Grow the fleet by one shard (the counterpart to
        :meth:`scale_down`; nothing in the server calls it yet,
        ROADMAP.md Queue 3).

        The shard is built from the factory — whose warmup path goes
        through the persistent AOT cache (evam_tpu/aot/), so a
        cache-hit spin-up is deserialize-speed — and is **warmed
        before it joins placement**: no stream is ever pinned to a
        cold shard. Only once warm does the label enter the shard map
        and the consistent-hash ring; the streams whose arcs the new
        vnodes own are checkpointed (pre_rebalance barrier, reason
        ``scale_up``) and re-pinned, each move counted on
        ``evam_fleet_rebalance_total``.

        Returns the new label, or None (at capacity, already scaling,
        or the warm gate timed out — the half-built shard is stopped
        and nothing joined the ring)."""
        with self._lock:
            if self._scaling:
                return None
            free = [i for i in range(len(self._plans))
                    if f"s{i}" not in self.shards
                    and f"s{i}" not in self._degraded]
            if not free:
                return None
            idx = free[0]
            label = f"s{idx}"
            self._scaling = True
            example = self._example
        t0 = time.perf_counter()
        try:
            try:
                eng = self._shard_factory(self._plans[idx],
                                          f"{self.name}@{label}")
            except Exception:  # noqa: BLE001 — factory failure is a no-op grow
                log.exception("fleet %s: scale_up build of %s failed",
                              self.name, label)
                return None
            if example:
                # warm-before-join gate (skipped when the fleet has
                # never seen an example — matching boot, where shards
                # are built cold and warm when traffic shapes arrive)
                try:
                    eng.set_example(**example)
                    eng.warm_async(**example)
                except Exception:  # noqa: BLE001 — warm API optional on fakes
                    pass
                deadline = time.monotonic() + warm_timeout_s
                while not eng.warmed.wait(0.05):
                    if time.monotonic() >= deadline:
                        log.warning(
                            "fleet %s: scale_up of %s abandoned — "
                            "warmup exceeded %.0fs; the shard never "
                            "joined the ring", self.name, label,
                            warm_timeout_s)
                        threading.Thread(
                            target=self._safe_stop, args=(eng,),
                            name=f"fleet-{self.name}-abort-{label}",
                            daemon=True).start()
                        return None
            # join: shard map FIRST, ring second — a submit that races
            # the ring growth and places onto the new label must find
            # the engine in ``shards`` (placer.add before the map
            # insert would KeyError exactly that window)
            with self._lock:
                self.shards[label] = eng
                self._devices[label] = str(
                    self._plans[idx].mesh.devices.flat[0])
                self._retired_planned.discard(label)
                self._placer.add(label)
                self.scale_ups += 1
                self._last_spinup_s = time.perf_counter() - t0
                # which pinned streams the grown ring now owns —
                # their pins move only after the checkpoint below
                moving = [s for s, cur in self._pins.items()
                          if cur != label
                          and self._placer.place(s) == label]
            # pre-move checkpoint (outside _lock: capture takes the
            # store's own locks) so the new shard's first frame sees
            # the stream's gate/coaster/tracker state, same contract
            # as a chip-loss migration
            from evam_tpu.state import active as ckpt_active

            store = ckpt_active()
            if store is not None:
                for s in moving:
                    store.capture(s, barrier="pre_rebalance",
                                  reason="scale_up")
            with self._lock:
                moved = 0
                for s in moving:
                    if (self._pins.get(s) != label
                            and self._placer.place(s) == label):
                        self._pins[s] = label
                        self.rebalances += 1
                        moved += 1
                        metrics.inc("evam_fleet_rebalance_total",
                                    labels={"engine": self.name})
                spinup = self._last_spinup_s
            log.info(
                "fleet %s: scaled up — shard %s joined warm in %.2fs, "
                "%d stream(s) rebalanced onto it", self.name, label,
                spinup, moved)
            return label
        finally:
            with self._lock:
                self._scaling = False

    @staticmethod
    def _safe_stop(eng) -> None:
        try:
            eng.stop()
        except Exception:  # noqa: BLE001 — already torn down
            pass

    def drain_wait(self, timeout: float = 10.0) -> None:
        """Join outstanding retirement drains (tests / shutdown)."""
        for t in list(self._drains):
            t.join(timeout=timeout)

    def _mesh(self):
        with self._mesh_lock:
            if self._mesh_eng is None:
                self._mesh_eng = self._mesh_factory(f"{self.name}@mesh")
                if self._example:
                    try:
                        self._mesh_eng.set_example(**self._example)
                    except Exception:  # noqa: BLE001 — example optional
                        pass
            return self._mesh_eng

    # -------------------------------------------------- engine surface

    def _members(self) -> list:
        with self._lock:
            members = list(self.shards.values())
        if self._mesh_eng is not None:
            members.append(self._mesh_eng)
        return members

    @property
    def stats(self) -> EngineStats:
        merged = EngineStats()
        with self._lock:
            if self._stats_carry is not None:
                merged.absorb(self._stats_carry)
        for e in self._members():
            merged.absorb(e.stats)
        return merged

    @property
    def warmed(self) -> _AllWarmed:
        return _AllWarmed([e.warmed for e in self._members()])

    @property
    def stalled(self) -> _AnySet:
        return _AnySet([
            e.stalled for e in self._members()
            if getattr(e, "state", "running") == "running"])

    @property
    def state(self) -> str:
        states = [getattr(e, "state", "running")
                  for e in self._members()]
        if any(s == "running" for s in states):
            # one live chip keeps the pod serving — a single loss must
            # not flip /healthz to 503 while survivors carry the load
            return "running"
        if any(s == "restarting" for s in states):
            return "restarting"
        return "degraded"

    @property
    def restarts(self) -> int:
        with self._lock:
            carry = self._restarts_carry
        return carry + sum(getattr(e, "restarts", 0)
                           for e in self._members())

    @property
    def last_stall_ts(self):
        ts = [getattr(e, "last_stall_ts", None) for e in self._members()]
        ts = [t for t in ts if t]
        return max(ts) if ts else None

    def queue_depth(self) -> int:
        return sum(e.queue_depth() for e in self._members())

    def queue_age_s(self) -> float:
        ages = [e.queue_age_s() for e in self._members()]
        return max(ages) if ages else 0.0

    def class_depths(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for e in self._members():
            for k, v in e.class_depths().items():
                out[k] = out.get(k, 0) + v
        return out

    def shed_counts(self) -> dict[str, int]:
        with self._lock:
            out = dict(self._shed_carry)
        for e in self._members():
            for k, v in e.shed_counts().items():
                out[k] = out.get(k, 0) + v
        return out

    def set_example(self, **example) -> None:
        with self._lock:
            self._example = example
        for e in self._members():
            e.set_example(**example)

    def warm_async(self, **example) -> None:
        with self._lock:
            self._example = example
            shards = list(self.shards.values())
        for e in shards:
            e.warm_async(**example)

    def abandon(self) -> None:
        for e in self._members():
            try:
                e.abandon()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass

    def stop(self) -> None:
        for e in self._members():
            self._safe_stop(e)
        self.drain_wait()

    def __getattr__(self, item):
        # structural attributes (buckets, ragged flags, …)
        # are identical across shards by construction — answer from
        # the first one
        with self._lock:
            for e in self.shards.values():
                return getattr(e, item)
        raise AttributeError(item)

    # ------------------------------------------------- fleet introspection

    def shard_rows(self) -> list[tuple[str, str, object]]:
        """(label, device, engine) per live shard + the mesh twin —
        the /engines per-chip rows."""
        with self._lock:
            rows = [(label, self._devices[label], eng)
                    for label, eng in self.shards.items()]
        mesh = self._mesh_eng
        if mesh is not None:
            rows.append(("mesh", mesh.plan.device_names(), mesh))
        return rows

    def placement_counts(self) -> dict[str, int]:
        """Streams pinned per shard label (placement view)."""
        with self._lock:
            out = {label: 0 for label in self.shards}
            for label in self._pins.values():
                if label in out:
                    out[label] += 1
            return out

    def fleet_summary(self) -> dict:
        self._sweep_degraded()
        with self._lock:
            return {
                "shards": len(self.shards),
                "degraded_shards": len(self._degraded),
                "streams": self.placement_counts(),
                "rebalances": self.rebalances,
                # the structural ceiling (mesh size minus dead chips;
                # the hub clamps it to EVAM_FLEET_MAX_SHARDS) and the
                # grow/shrink totals /scheduler reports
                "max_shards": len(self._plans) - len(self._degraded),
                "scale_ups": self.scale_ups,
                "scale_downs": self.scale_downs,
            }
