"""EngineHub: model-instance-id → shared BatchEngine.

Implements the reference's engine-sharing contract: pipelines that
pass the same ``model-instance-id`` share one inference engine and
its batch queue (reference pipelines/object_detection/
person_vehicle_bike/pipeline.json:26-32, SURVEY.md §2d-2). Pipelines
that omit it share per-model-key engines — the cross-stream batching
default that the TPU design is built around.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from evam_tpu.engine import steps as step_builders
from evam_tpu.engine.batcher import BatchEngine
from evam_tpu.engine.ragged import RaggedSpec, ragged_mode
from evam_tpu.engine.supervisor import SupervisedEngine
from evam_tpu.models.registry import LoadedModel, ModelRegistry
from evam_tpu.obs import get_logger, metrics
from evam_tpu.parallel.mesh import MeshPlan
from evam_tpu.sched.classes import PRIORITIES, SchedConfig

log = get_logger("engine.hub")

_BUILDERS = {
    "detect": (step_builders.build_detect_step, ("frames",), True),
    "classify": (step_builders.build_classify_step, ("frames", "boxes"), True),
    "action_encode": (step_builders.build_action_encode_step, ("frames",), True),
    "action_decode": (step_builders.build_action_decode_step, ("clips",), False),
    "audio": (step_builders.build_audio_step, ("windows",), False),
}


class EngineHub:
    """Creates/caches engines; one per (kind, model key or instance id)."""

    def __init__(
        self,
        registry: ModelRegistry,
        plan: MeshPlan | None = None,
        max_batch: int = 128,  # serving default, see TPUSettings.max_batch
        deadline_ms: float = 8.0,
        wire_format: str = "i420",
        warmup: bool = False,
        stall_timeout_s: float = 120.0,
        device_synth: bool = False,
        supervise: bool = True,
        max_restarts: int = 3,
        restart_window_s: float = 300.0,
        restart_backoff_s: float = 0.5,
        first_batch_grace: float = 10.0,
        sched: SchedConfig | None = None,
        transfer_depth: int = 0,
        ragged: str | None = None,
        ragged_unit_budget: int = 0,
        fleet: str | None = None,
        fleet_shard_max_batch: int = 0,
        fleet_max_shards: int = 0,
        fleet_initial_shards: int = 0,
        lm=None,
    ):
        #: serving sets True: stages precompile every batch bucket in
        #: the background right after engine creation
        self.warmup = warmup
        self.registry = registry
        self.plan = plan
        self.max_batch = max_batch
        self.deadline_ms = deadline_ms
        self.stall_timeout_s = stall_timeout_s
        #: host→device frame encoding for video engines ("i420" halves
        #: ingest bandwidth; see evam_tpu.ops.color)
        self.wire_format = wire_format
        #: bench-only mode (bench.py --config serve --serve-ingest
        #: seed): video stages submit uint32 seeds and each engine's
        #: step synthesizes its wire batch on-chip
        #: (steps.wrap_device_synth) — the serving path minus only the
        #: host→device pixel copy
        self.device_synth = device_synth
        #: engine supervision (engine/supervisor.py): wedged engines
        #: are quarantined and rebuilt in place, with a restart budget
        #: (EVAM_ENGINE_MAX_RESTARTS within EVAM_ENGINE_RESTART_WINDOW_S)
        self.supervise = supervise
        self.max_restarts = max_restarts
        self.restart_window_s = restart_window_s
        self.restart_backoff_s = restart_backoff_s
        #: stall-watchdog multiplier for a bucket's first (compiling)
        #: batch — see BatchEngine._track_dispatch
        self.first_batch_grace = first_batch_grace
        #: QoS scheduling config (evam_tpu/sched/): engines get
        #: per-class deadlines and staleness shedding. Part of the
        #: rebuild recipe — a supervisor-rebuilt engine inherits it
        #: because the factory closure carries it. None = every
        #: engine's class queues run as one FIFO (EVAM_SCHED=off).
        self.sched = sched if (sched is not None and sched.enabled) else None
        #: upload-queue bound (EVAM_TRANSFER_DEPTH). Part of the
        #: rebuild recipe.
        self.transfer_depth = transfer_depth
        #: ragged batching (engine/ragged.py, EVAM_RAGGED): "packed"
        #: gives classify-family engines masked region packing (the
        #: ragged builder + a RaggedSpec'd staging ring) and every
        #: engine a consolidated bucket ladder; "off" (default) is the
        #: byte-identical dense path. Part of the rebuild recipe — the
        #: factory closure carries mode + spec, so supervisor rebuilds
        #: inherit EVAM_RAGGED.
        self.ragged = ragged_mode(ragged)
        #: packed unit rows budgeted per batch row (EVAM_RAGGED_UNIT_
        #: BUDGET): the knob that turns "roi_budget slots per frame,
        #: mostly empty" into a shared pool sized for the real mix
        self.ragged_unit_budget = ragged_unit_budget or int(
            os.environ.get("EVAM_RAGGED_UNIT_BUDGET", "4"))
        #: fleet serving mode (evam_tpu/fleet/, EVAM_FLEET): "sharded"
        #: fronts every engine key with a FleetEngine — one per-chip
        #: shard per mesh device behind a consistent-hash stream
        #: placer, plus a mesh-sharded twin for batch-class big
        #: buckets; "off" (default) is the byte-identical single-chip
        #: path. Needs a multi-device plan — on one device the modes
        #: are the same thing, so sharded quietly degrades to off.
        from evam_tpu.fleet.engine import fleet_mode
        self.fleet = fleet_mode(fleet)
        self.fleet_active = (
            self.fleet == "sharded" and plan is not None
            and plan.data_size > 1)
        if self.fleet == "sharded" and not self.fleet_active:
            log.warning(
                "EVAM_FLEET=sharded needs a multi-device mesh plan "
                "(have %s) — running single-chip",
                plan.data_size if plan else "none")
        #: per-shard ladder top: a chip serving 1/N of the streams
        #: does not need the fleet-wide max_batch — capping it keeps
        #: shard compile bills and staging memory proportional
        self.fleet_shard_max_batch = fleet_shard_max_batch or (
            max(1, max_batch // plan.data_size) if self.fleet_active
            else max_batch)
        #: growth ceiling (EVAM_FLEET_MAX_SHARDS): how many shards
        #: the fleet may be grown to, bounded by the mesh. 0
        #: (default): fleet_summary reports max_shards 0.
        self.fleet_max_shards = fleet_max_shards
        #: boot fleet size (EVAM_FLEET_SHARDS when autoscaling):
        #: FleetEngines start with this many shards and grow/shrink
        #: between 1 and the ceiling. 0 = all plan devices (the
        #: pre-autoscaling behavior).
        self.fleet_initial_shards = fleet_initial_shards
        #: the generate engine's shapes (config/settings.py LMSettings;
        #: None = its defaults). Part of the rebuild recipe.
        if lm is None:
            from evam_tpu.config.settings import LMSettings

            lm = LMSettings()
        self.lm = lm
        self._engines: dict[str, BatchEngine | SupervisedEngine] = {}
        #: device_synth only: engine key → the (H, W) its on-chip
        #: generator was compiled for (cache-hit mismatch guard)
        self._synth_hw: dict[str, tuple[int, int] | None] = {}
        self._models: dict[str, LoadedModel] = {}
        # RLock: engine() calls model() while holding the lock.
        self._lock = threading.RLock()

    def model(self, model_key: str) -> LoadedModel:
        with self._lock:
            if model_key not in self._models:
                self._models[model_key] = self.registry.get(model_key)
            return self._models[model_key]

    def engine(
        self,
        kind: str,
        model_key: str,
        instance_id: str | None = None,
        **builder_kwargs,
    ) -> BatchEngine:
        """Get or create the shared engine for (kind, model, instance).

        ``instance_id`` is the model-instance-id parameter; None
        defaults to sharing by model key (maximum batching).
        """
        if kind not in _BUILDERS:
            raise ValueError(f"no step builder for stage kind '{kind}'")
        synth_hw = builder_kwargs.pop("synth_wire_hw", None)
        key = f"{kind}:{instance_id or model_key}"
        with self._lock:
            if key not in self._engines:
                model = self.model(model_key)
                builder, input_names, wired = _BUILDERS[kind]
                if wired:
                    builder_kwargs.setdefault("wire_format", self.wire_format)
                spec = self._ragged_spec(kind, builder_kwargs)
                if spec is not None and self.ragged == "packed":
                    # masked region packing: one fixed-shape program
                    # over the packed unit block (engine/ragged.py)
                    builder = step_builders.build_classify_step_ragged
                step_fn = builder(model, **builder_kwargs)
                if self.device_synth and wired:
                    step_fn = self._synth_wrap(step_fn, synth_hw, key)
                    self._synth_hw[key] = tuple(synth_hw)
                self._engines[key] = self._build(
                    key, step_fn, model.params, input_names,
                    ragged_spec=spec)
                log.info("created engine %s (model %s)", key, model_key)
            elif self.device_synth and synth_hw is not None:
                self._check_synth_hw(key, synth_hw)
            return self._engines[key]

    def fused_engine(
        self,
        det_key: str,
        cls_key: str,
        instance_id: str | None = None,
        **builder_kwargs,
    ) -> BatchEngine:
        """Fused detect+classify engine: one upload, one readback per
        frame (see steps.build_detect_classify_step). Builder kwargs
        (e.g. the object-class filter) are part of the cache key —
        pipelines may only share a fused program when the compiled
        semantics match."""
        synth_hw = builder_kwargs.pop("synth_wire_hw", None)
        kw_sig = ",".join(f"{k}={v}" for k, v in sorted(builder_kwargs.items()))
        key = f"detect_classify:{instance_id or det_key + '+' + cls_key}:{kw_sig}"
        with self._lock:
            if key not in self._engines:
                det = self.model(det_key)
                cls = self.model(cls_key)
                builder_kwargs.setdefault("wire_format", self.wire_format)
                step_fn = step_builders.build_detect_classify_step(
                    det, cls, **builder_kwargs
                )
                if self.device_synth:
                    step_fn = self._synth_wrap(step_fn, synth_hw, key)
                    self._synth_hw[key] = tuple(synth_hw)
                self._engines[key] = self._build(
                    key, step_fn,
                    {"det": det.params, "cls": cls.params}, ("frames",))
                log.info("created fused engine %s", key)
            elif self.device_synth and synth_hw is not None:
                self._check_synth_hw(key, synth_hw)
            return self._engines[key]

    def generate_engine(self, model_key: str,
                        instance_id: str | None = None, *, prefix_ids):
        """Get or create the generate engine (engine/generate.py) of a
        language model: many device steps per request, its family's
        state on the device (cache rows in pages, and per-slot recurrent
        state where the family has such layers), ``prefix_ids`` prefilled
        once and shared by every sequence. Supervised and listed like any
        engine. It lives on the plan's first device: what one chip holds
        of the model is one chip's."""
        key = f"generate:{instance_id or model_key}"
        with self._lock:
            if key not in self._engines:
                from evam_tpu.engine.generate import (
                    GenerateEngine,
                    GenerateSizes,
                )

                cfg = self.registry.lm_config(model_key)
                sizes = GenerateSizes.from_settings(self.lm)

                def factory():
                    return GenerateEngine(
                        key, cfg, prefix_ids, sizes=sizes, plan=self.plan,
                        sched=self.sched,
                        stall_timeout_s=self.stall_timeout_s,
                        first_batch_grace=self.first_batch_grace)

                self._engines[key] = (
                    SupervisedEngine(
                        key, factory, max_restarts=self.max_restarts,
                        restart_window_s=self.restart_window_s,
                        backoff_s=self.restart_backoff_s)
                    if self.supervise else factory())
                log.info("created engine %s (model %s)", key, model_key)
            return self._engines[key]

    def _ragged_spec(self, kind: str, builder_kwargs: dict
                     ) -> RaggedSpec | None:
        """Unit-level shape declaration for classify-family engines
        (the per-item ROI budget the dense path pads to). Attached in
        BOTH ragged modes so occupancy accounting is honest about
        interior padding; packing itself is mode-gated."""
        if kind != "classify":
            return None
        budget = int(builder_kwargs.get("roi_budget", 8))
        return RaggedSpec(
            input="boxes", unit_shape=(4,), dtype=np.float32,
            max_units=budget,
            unit_budget=min(self.ragged_unit_budget, budget),
        )

    def _build(self, key: str, step_fn, params, input_names,
               ragged_spec: RaggedSpec | None = None):
        """Construct the engine for ``key`` — as a SupervisedEngine
        (the stable handle whose live BatchEngine a wedge-triggered
        rebuild swaps underneath) unless supervision is disabled. The
        factory closure is the rebuild recipe: a replacement engine
        gets a fresh ``jax.jit`` wrapper and a fresh SlotRing from the
        same step function and params (and the same EVAM_RAGGED mode +
        unit spec — a rebuild must not flip the batch layout).

        Fleet mode builds the same recipe once per mesh device
        (single-device plan, shard-capped ladder) behind a FleetEngine
        plus one full-mesh twin for the batch-class big buckets — each
        shard individually supervised, so a wedge on one chip is that
        shard's quarantine, not the fleet's."""

        # AOT cache program fingerprint (evam_tpu/aot/): everything at
        # the hub level that changes what the step COMPUTES. Shapes,
        # devices and params avals are appended per bucket
        # by the engine (BatchEngine._aot_bucket_key) — so supervisor
        # rebuilds and fleet shard spin-ups of the same program land
        # on the same entries, while a wire-format or ragged-mode flip
        # addresses different ones.
        aot_key = (f"{key}|wire={self.wire_format}"
                   f"|synth={int(self.device_synth)}"
                   f"|ragged={self.ragged}|ub={self.ragged_unit_budget}"
                   f"|sched={int(self.sched is not None)}")

        def make(plan, name, max_batch, fleet_local=False):
            def factory() -> BatchEngine:
                return BatchEngine(
                    name=name,
                    step_fn=step_fn,
                    params=params,
                    plan=plan,
                    max_batch=max_batch,
                    deadline_ms=self.deadline_ms,
                    input_names=input_names,
                    stall_timeout_s=self.stall_timeout_s,
                    first_batch_grace=self.first_batch_grace,
                    sched=self.sched,
                    transfer_depth=self.transfer_depth or None,
                    ragged=self.ragged,
                    ragged_spec=ragged_spec,
                    fleet_local=fleet_local,
                    aot_key=aot_key,
                )

            if not self.supervise:
                return factory()
            return SupervisedEngine(
                name, factory,
                max_restarts=self.max_restarts,
                restart_window_s=self.restart_window_s,
                backoff_s=self.restart_backoff_s,
            )

        if not self.fleet_active:
            return make(self.plan, key, self.max_batch)
        from evam_tpu.fleet.engine import FleetEngine
        return FleetEngine(
            key,
            shard_factory=lambda plan, label: make(
                plan, label, self.fleet_shard_max_batch),
            plans=self.plan.per_device_plans(),
            mesh_factory=lambda label: make(
                self.plan, label, self.max_batch, fleet_local=True),
            initial=self.fleet_initial_shards,
        )

    def _check_synth_hw(self, key: str, synth_hw) -> None:
        """Device-synth cache hits must agree on the wire resolution —
        seeds carry no shape, so unlike the host pixel path nothing
        downstream would catch a mismatch (it would silently measure
        the wrong wire size)."""
        have = self._synth_hw.get(key)
        if have is not None and tuple(synth_hw) != have:
            raise ValueError(
                f"engine {key}: device_synth compiled for wire {have} "
                f"but a stage requested {tuple(synth_hw)} — give the "
                "stages matching ingest sizes or distinct "
                "model-instance-ids"
            )

    def _synth_wrap(self, step_fn, synth_hw: tuple[int, int] | None, key: str):
        """Wrap a wire-frame step for device_synth mode (the stage must
        pass its ingest (H, W) as ``synth_wire_hw`` so the on-chip
        generator produces wire batches of the exact serving shape)."""
        if synth_hw is None:
            raise ValueError(
                f"engine {key}: EngineHub(device_synth=True) requires the "
                "stage to pass synth_wire_hw=(H, W)"
            )
        from evam_tpu.ops.color import wire_shape

        h, w = synth_hw
        return step_builders.wrap_device_synth(
            step_fn, wire_shape(self.wire_format, h, w))

    @staticmethod
    def _stat_row(e, shard: str | None, device: str | None,
                  group: str) -> dict:
        own_capacity = getattr(e, "capacity_fps", None)
        row = {
            "batches": e.stats.batches,
            "items": e.stats.items,
            "mean_occupancy": e.stats.mean_occupancy,
            "warmed": e.warmed.is_set(),
            # why the background warmup failed (traffic then compiles
            # on demand), else None
            "warm_error": getattr(e, "warm_error", None),
            # ragged batching (engine/ragged.py): effective
            # mode, the honest units/computed-unit-rows
            # occupancy (the pad tax n/bucket hides), where
            # traffic lands per program shape, and the
            # compile-cache bill bucket consolidation exists
            # to shrink
            "ragged": getattr(e, "ragged", "off"),
            "unit_occupancy": round(e.stats.unit_occupancy, 4),
            # the bucket ladder: a finished warmup has compiled (or
            # AOT-loaded) one program per rung
            "buckets": list(e.buckets),
            "bucket_batches": {
                str(b): c for b, c in sorted(
                    e.stats.bucket_batches.items())},
            "compiled_programs": e.stats.compiled_programs,
            "compile_s": round(e.stats.compile_seconds, 3),
            # cold-vs-warm spin-up attribution (evam_tpu/aot/): rungs
            # warmed from the persistent executable cache and what
            # those loads cost — a cache-hit shard shows hits ==
            # compiled_programs and compile_s ≈ 0
            "aot": {"hits": e.stats.aot_hits,
                    "load_s": round(e.stats.aot_load_seconds, 3)},
            "oversize_splits": e.stats.oversize_splits,
            # per-batch host clock means (ringbuf.STAGES order) and
            # how many batches they cover (EngineStats.stage_seconds
            # says which ones the clock leaves out)
            "stage_ms": e.stats.stage_ms_per_batch(),
            "stage_batches": e.stats.clocked,
            # supervision lifecycle (engine/supervisor.py);
            # unsupervised raw engines report a static running
            "state": getattr(e, "state", "running"),
            "restarts": getattr(e, "restarts", 0),
            "last_stall_ts": getattr(e, "last_stall_ts", None),
            # submit-queue visibility (sched satellite): the
            # backlog that used to be invisible until the
            # stall watchdog tripped
            "queue_depth": e.queue_depth(),
            "queue_age_s": round(e.queue_age_s(), 3),
            # per-class depths when the QoS layer is on
            "sched_queues": e.class_depths(),
            # fleet placement (evam_tpu/fleet/): which chip this row
            # is, and the engine key it aggregates under — admission
            # sums capacity per group (Σ shards) instead of treating
            # every shard as an independent bottleneck
            "shard": shard,
            "device": device,
            "group": group,
        }
        if own_capacity is not None:
            # a generate engine models its own capacity
            # (sched/admission.py reads it), and holds pages
            row["capacity_fps"] = round(own_capacity(), 2)
            row["pages_in_use"], row["pages"] = e.pages_in_use()
            (row["state_slots_in_use"], row["state_slots"],
             row["state_bytes"]) = e.state_slots()
            # the slots the engine took, and the ceiling it was handed
            row["slots"], row["slots_ceiling"] = e.slots()
            row["prefix_heads_bytes"] = e.prefix_heads_bytes()
        return row

    def _rows(self):
        """(row key, engine, shard label, device, group) per /engines
        row: one per engine, one per shard of a FleetEngine."""
        with self._lock:
            engines = dict(self._engines)
        # a mesh engine runs on EVERY device of its plan — name them
        # all, so a multi-chip placement is checkable from /engines
        default_dev = (self.plan.device_names()
                       if self.plan is not None else None)
        for k, e in engines.items():
            if hasattr(e, "shard_rows"):  # FleetEngine (duck-typed: no cycle)
                for label, dev, sub in e.shard_rows():
                    yield f"{k}@{label}", sub, label, dev, k
            else:
                yield k, e, None, default_dev, k

    def stats(self) -> dict[str, dict]:
        return {
            key: self._stat_row(e, shard=shard, device=dev, group=group)
            for key, e, shard, dev, group in self._rows()
        }

    def stage_summary(self) -> dict[str, float]:
        """Batch-weighted mean per-batch host-stage cost across ALL
        engines (ms) — the /healthz attribution block: where a
        batch's wall time goes (slot-write vs h2d issue/wait vs launch
        vs readback residual) without scraping /metrics quantiles.
        Keys are fixed
        (ringbuf.STAGES) from boot so the health payload keeps a
        stable shape; per-engine detail lives on /engines."""
        from evam_tpu.engine.ringbuf import STAGES

        with self._lock:
            engines = list(self._engines.values())
        batches = sum(e.stats.clocked for e in engines)
        return {
            s: (round(
                1e3 * sum(e.stats.stage_seconds.get(s, 0.0)
                          for e in engines) / batches, 3)
                if batches > 0 else 0.0)
            for s in STAGES
        }

    def queue_summary(self) -> dict[str, float]:
        """Aggregate submit-queue backlog for /healthz (fixed keys —
        golden contract): total undispatched items and the oldest
        item's age across every engine. Refreshes the per-engine
        gauges on the way so a scrape sees live values even between
        dispatches (the whole point: backlog must be visible BEFORE
        the stall watchdog fires)."""
        with self._lock:
            engines = dict(self._engines)
        depth = 0
        oldest = 0.0
        for k, e in engines.items():
            d = e.queue_depth()
            age = e.queue_age_s()
            depth += d
            oldest = max(oldest, age)
            metrics.set("evam_engine_queue_depth", d, {"engine": k})
            metrics.set("evam_engine_queue_age_s", age, {"engine": k})
        return {"depth": depth, "oldest_age_s": round(oldest, 3)}

    def class_queue_depths(self) -> dict[str, int]:
        """Summed per-class queued depth across engines (zeros when
        the QoS layer is off — the /scheduler payload keeps a stable
        shape either way)."""
        out = {c: 0 for c in PRIORITIES}
        with self._lock:
            engines = list(self._engines.values())
        for e in engines:
            for c, n in e.class_depths().items():
                out[c] = out.get(c, 0) + n
        return out

    def shed_totals(self) -> dict[str, int]:
        """Summed per-class shed counts across engines. Monotonic
        across supervisor rebuilds: SupervisedEngine.shed_counts folds
        in the counts absorbed from quarantined predecessors
        (supervisor._absorb_counters), so this matches the
        evam_sched_shed_total{class} series instead of silently
        resetting when an engine is rebuilt."""
        out = {c: 0 for c in PRIORITIES}
        with self._lock:
            engines = list(self._engines.values())
        for e in engines:
            for c, n in e.shed_counts().items():
                out[c] = out.get(c, 0) + n
        return out

    def readiness(self) -> dict[str, int]:
        """Engine warm state for /healthz (serve-time preload,
        round-1 VERDICT item 7): ``warming`` > 0 means a first POST
        would still hit a compile in the hot path."""
        with self._lock:
            engines = list(self._engines.values())
        # without background warmup the event never fires — engines
        # compile on first batch and are "as ready as they get"
        warmed = (
            sum(1 for e in engines if e.warmed.is_set())
            if self.warmup else len(engines)
        )
        states = [getattr(e, "state", "running") for e in engines]
        batches = sum(e.stats.batches for e in engines)
        return {
            "engines": len(engines),
            "warmed": warmed,
            "warming": len(engines) - warmed,
            # occupancy export (engine/ragged.py satellite): the
            # batch-weighted item fill and the pad-tax-honest unit
            # fill across every engine — the fleet-level "are we
            # paying for empty rows" number, scalar so the health
            # payload keeps a fixed shape (per-bucket batch counts
            # live on /engines, per-engine gauges on /metrics)
            "occupancy": round(
                sum(e.stats.occupancy_sum for e in engines) / batches
                if batches else 0.0, 4),
            "unit_occupancy": round(
                (sum(e.stats.units for e in engines)
                 / max(1, sum(e.stats.unit_slots for e in engines)))
                if batches else 0.0, 4),
            # compile-cache bill across engines (bucket consolidation
            # drops it; /engines itemizes per engine)
            "compiled_programs": sum(
                e.stats.compiled_programs for e in engines),
            # a wedged backend (stall watchdog fired) is a liveness
            # failure, not a warmup phase — monitoring must see it.
            # Supervised engines leave this bucket the moment the
            # supervisor quarantines them (state flips to restarting/
            # degraded), so the three counts are disjoint.
            "stalled": sum(
                1 for e, s in zip(engines, states)
                if s == "running" and e.stalled.is_set()
            ),
            # supervision (engine/supervisor.py): restarting is a
            # transient 503 (rebuild in progress), degraded a terminal
            # one (restart budget exhausted — process restart needed)
            "restarting": sum(1 for s in states if s == "restarting"),
            "degraded": sum(1 for s in states if s == "degraded"),
            "restarts": sum(getattr(e, "restarts", 0) for e in engines),
        }

    def wait_warm(self, timeout_s: float) -> None:
        """Block until every engine's background warmup has ended;
        raise if one failed, or if ``timeout_s`` (> 0) runs out — a
        compile that hangs is not under the stall watchdog, and a
        caller that waits for warm must not wait forever."""
        t_end = time.monotonic() + timeout_s
        while True:
            rows = self.stats()
            if not self.warmup or all(r["warmed"] for r in rows.values()):
                break
            if timeout_s > 0 and time.monotonic() > t_end:
                cold = sorted(k for k, r in rows.items() if not r["warmed"])
                raise TimeoutError(
                    f"engine warmup not finished after {timeout_s:.0f} s: "
                    f"{cold}")
            time.sleep(0.2)
        failed = {k: r["warm_error"] for k, r in rows.items()
                  if r["warm_error"]}
        if failed:
            raise RuntimeError(f"engine warmup failed: {failed}")

    def fleet_summary(self) -> dict:
        """The /scheduler fleet operating point (fixed keys — route
        golden): placement counts per chip, live/degraded shard
        counts, and the cumulative rebalance total. EVAM_FLEET=off
        reports the same shape with zeros so dashboards and the bench
        serve line don't branch on mode."""
        with self._lock:
            engines = list(self._engines.values())
        out = {
            "mode": "sharded" if self.fleet_active else "off",
            "shards": 0,
            "degraded_shards": 0,
            "rebalances": 0,
            "streams": {},
            "max_shards": 0,
            "scale_ups": 0,
            "scale_downs": 0,
        }
        for e in engines:
            if not hasattr(e, "shard_rows"):  # FleetEngine only
                continue
            s = e.fleet_summary()
            # every engine kind shards over the same chips: shard
            # counts report the widest view, placement counts sum
            # (a stream pins once per engine kind it traverses)
            out["shards"] = max(out["shards"], s["shards"])
            out["degraded_shards"] = max(
                out["degraded_shards"], s["degraded_shards"])
            out["rebalances"] += s["rebalances"]
            out["max_shards"] = max(out["max_shards"],
                                    s.get("max_shards", 0))
            out["scale_ups"] += s.get("scale_ups", 0)
            out["scale_downs"] += s.get("scale_downs", 0)
            for label, n in s["streams"].items():
                out["streams"][label] = out["streams"].get(label, 0) + n
        # autoscaling policy ceiling: the structural bound above is
        # the mesh (len(plans)); the operator's EVAM_FLEET_MAX_SHARDS
        # clamps it, and 0 — the default — reads "never grow"
        if self.fleet_active and self.fleet_max_shards > 0:
            cap = self.fleet_max_shards
            if out["max_shards"]:
                cap = min(cap, out["max_shards"])
            out["max_shards"] = cap
        else:
            out["max_shards"] = 0
        return out

    def stop(self) -> None:
        with self._lock:
            engines = list(self._engines.values())
            self._engines.clear()
        for e in engines:
            e.stop()
