"""PipelineRegistry: definitions + shared engines + instance table.

The reference's PipelineServer scans a pipelines dir and hands out
per-instance handles (`PipelineServer.pipeline(name, version)` then
`pipeline.start(...)`, evas/manager.py:134-141). Here the registry
also owns the one EngineHub — the central inversion: instances are
lightweight adapters around shared per-model batch engines
(SURVEY.md §7 architecture stance).
"""

from __future__ import annotations

import json
import os
import threading
from pathlib import Path
from typing import Any

from evam_tpu.config import Settings
from evam_tpu.engine.hub import EngineHub
from evam_tpu.graph import PipelineLoader, resolve_parameters
from evam_tpu.models.registry import ModelRegistry
from evam_tpu.obs import get_logger, metrics
from evam_tpu.parallel.mesh import build_mesh
from evam_tpu.publish.base import create_destination
from evam_tpu.sched import (
    AdmissionController,
    SchedConfig,
    validate_priority,
)
from evam_tpu.sched.classes import DEFAULT_PRIORITY
from evam_tpu.server.instance import InstanceState, StreamInstance
from evam_tpu.stages.build import build_stages
from evam_tpu.state import active as ckpt_active
from evam_tpu.state import is_checkpoint_blob

log = get_logger("server.registry")


class RequestError(ValueError):
    """400-class problem with a start request."""


class PipelineRegistry:
    def __init__(self, settings: Settings, hub: EngineHub | None = None):
        self.settings = settings
        self.loader = PipelineLoader(settings.pipelines_dir)
        if hub is None:
            plan = build_mesh(
                shape=list(settings.tpu.mesh_shape),
                axes=list(settings.tpu.mesh_axes),
            )
            n_devices = max(settings.tpu.fleet_shards,
                            settings.tpu.fleet_max_shards)
            if settings.tpu.fleet == "sharded" and n_devices > 0:
                # canary/bench knob: shard over the first N chips only
                # (scaling curves, partial-fleet rollout). With
                # autoscaling the MESH must span the ceiling — the
                # fleet boots at EVAM_FLEET_SHARDS shards and grows
                # into the remaining plan slots via scale_up().
                import jax

                devices = list(jax.devices())[:n_devices]
                plan = build_mesh(devices=devices)
            registry = ModelRegistry(
                models_dir=settings.models_dir,
                dtype=settings.tpu.precision,
            )
            sched_cfg = SchedConfig.from_settings(
                settings.sched,
                standard_deadline_ms=settings.tpu.batch_deadline_ms)
            hub = EngineHub(
                registry,
                plan=plan,
                max_batch=settings.tpu.max_batch,
                deadline_ms=settings.tpu.batch_deadline_ms,
                warmup=settings.tpu.warmup,
                stall_timeout_s=settings.tpu.stall_timeout_s,
                supervise=settings.tpu.supervise,
                max_restarts=settings.tpu.max_restarts,
                restart_window_s=settings.tpu.restart_window_s,
                restart_backoff_s=settings.tpu.restart_backoff_s,
                first_batch_grace=settings.tpu.first_batch_grace,
                sched=sched_cfg if sched_cfg.enabled else None,
                transfer_depth=settings.tpu.transfer_depth,
                ragged=settings.tpu.ragged,
                ragged_unit_budget=settings.tpu.ragged_unit_budget,
                fleet=settings.tpu.fleet,
                fleet_shard_max_batch=settings.tpu.fleet_shard_max_batch,
                fleet_max_shards=settings.tpu.fleet_max_shards,
                # boot size only meaningful under an autoscaling
                # ceiling — without one the fleet spans the plan, the
                # pre-autoscaling behavior (fleet_shards narrowed the
                # mesh itself above)
                fleet_initial_shards=(
                    settings.tpu.fleet_shards
                    if settings.tpu.fleet_max_shards > 0 else 0),
                lm=settings.lm,
            )
        self.hub = hub
        #: QoS layer (evam_tpu/sched/): the hub's sched config is the
        #: single source of truth — an embedder-supplied hub without
        #: one (tests, benches) gets a disabled admission controller,
        #: so the legacy unconditional-admit path stays byte-identical
        self.sched_cfg = (getattr(hub, "sched", None)
                          or SchedConfig.disabled())
        self.admission = AdmissionController(hub, self.sched_cfg)
        #: shared decode pool (opt-in, EVAM_DECODE_POOL_WORKERS>0):
        #: bounds total decode threads across all instances
        self.decode_pool = None
        if settings.decode_pool_workers > 0:
            from evam_tpu.media.pool import DecodePool

            self.decode_pool = DecodePool(
                workers=settings.decode_pool_workers)
        #: async live-RTSP demux (opt-in, EVAM_RTSP_DEMUX_WORKERS>0):
        #: one selector thread + N decode workers for ALL rtsp://
        #: sources — live streams stop pinning a reader thread each
        #: (media/demux.py; VERDICT r4 item 3)
        self.rtsp_demux = None
        if settings.rtsp_demux_workers > 0:
            from evam_tpu.media.demux import RtspDemux

            self.rtsp_demux = RtspDemux(
                decode_workers=settings.rtsp_demux_workers)
        self.instances: dict[str, StreamInstance] = {}
        self._lock = threading.Lock()
        self._draining = False
        #: crash-consistent checkpoint store (evam_tpu/state/,
        #: EVAM_CKPT): resolved once, None when off — every hook below
        #: is a single None-check on the legacy path
        self._ckpt = ckpt_active()
        #: Optional RtspServer for destination.frame re-streaming
        #: (set by run_server when ENABLE_RTSP, reference
        #: docker-compose.yml:49-50).
        self.rtsp = None
        self._state_file = (
            Path(settings.state_dir) / "streams.json"
            if settings.state_dir else None
        )
        self._persist_lock = threading.Lock()
        # Crash-resume freshness: _persist fires on lifecycle EVENTS
        # (start/stop/finish); long-quiet periods would leave stage
        # state (tracker id high-water) stale in streams.json if the
        # process dies non-gracefully (SIGKILL/OOM). A low-frequency
        # re-persist bounds that staleness window.
        self._persist_interval_s = 30.0
        self._persist_stop = threading.Event()
        self._persist_thread: threading.Thread | None = None
        if self._state_file is not None:
            self._persist_thread = threading.Thread(
                target=self._periodic_persist,
                name="registry-persist", daemon=True,
            )
            self._persist_thread.start()

    # ------------------------------------------------------- preload

    def preload(self, names: str) -> int:
        """Serve-time engine preload: build
        and warm the engines for the named pipelines BEFORE the REST
        port opens, so the first POST never pays model build + XLA
        compile in the hot path. ``names``: comma list of
        ``name/version`` (or bare ``name`` = all versions), or ``all``.

        Raises when a name matches no pipeline, a pipeline fails to
        build, or (with ``tpu.warmup``) a bucket fails to compile or
        the warmup outlasts ``hub.wait_warm``'s deadline — a
        deployment that asked for a pipeline to be ready must not open
        the port without it.

        Engines are cached in the hub by (kind, model-instance) —
        building a throwaway stage chain per pipeline is exactly the
        instance start path minus the stream, so later instances get
        cache hits."""
        wanted = [n.strip() for n in names.split(",") if n.strip()]
        known = self.loader.names()

        def names_it(w: str, name: str, version: str) -> bool:
            return w in ("all", name, f"{name}/{version}")

        for w in wanted:
            if not any(names_it(w, n, v) for n, v in known):
                raise KeyError(f"preload: pipeline {w!r} not found")
        count = 0
        for name, version in known:
            label = f"{name}/{version}"
            if not any(names_it(w, name, version) for w in wanted):
                continue
            spec = self.loader.get(name, version)
            stage_specs, _ = resolve_parameters(spec, {})
            build_stages(
                stage_specs, self.hub,
                publish_fn=lambda ctx: None, sink_fn=lambda ctx: None,
            )
            count += 1
            log.info("preloaded %s", label)
        # the whole ladder gets what the watchdog grants ONE cold
        # bucket; a compile that hangs must fail start-up, not hold
        # the port shut forever
        self.hub.wait_warm(
            self.hub.stall_timeout_s * self.hub.first_batch_grace)
        return count

    # ----------------------------------------------------- definitions

    def pipelines(self) -> list[dict[str, Any]]:
        out = []
        for name, version in self.loader.names():
            spec = self.loader.get(name, version)
            out.append({
                "name": name,
                "version": version,
                "type": spec.raw.get("type", "evam_tpu"),
                "description": spec.description,
                "parameters": spec.parameters,
            })
        return out

    def describe(self, name: str, version: str) -> dict[str, Any] | None:
        spec = self.loader.get(name, version)
        if spec is None:
            return None
        return {
            "name": name,
            "version": version,
            "type": spec.raw.get("type", "evam_tpu"),
            "description": spec.description,
            "parameters": spec.parameters,
        }

    # -------------------------------------------------------- instances

    def start_instance(
        self,
        name: str,
        version: str,
        request: dict[str, Any],
        publish_fn=None,
        source=None,
        sink_fn=None,
        saved_state: dict[str, dict] | None = None,
    ) -> StreamInstance:
        """``publish_fn``/``source`` are embedder overrides (the EII
        manager publishes (meta, frame) over the msgbus and injects an
        app source fed by a subscriber — reference evas/manager.py
        appsrc rewiring at :109-115)."""
        spec = self.loader.get(name, version)
        if spec is None:
            raise KeyError(f"pipeline {name}/{version} not found")
        src = request.get("source")
        if source is None:
            if not isinstance(src, dict):
                raise RequestError("request.source must be an object")
            if "uri" not in src and src.get("type", "uri") == "uri":
                raise RequestError("request.source.uri is required")
        # QoS class: request body beats the pipeline spec's default
        # beats `standard` — validated HERE so a bad value is a 400,
        # never a silently-standard stream (evam_tpu/sched/).
        priority = request.get("priority")
        if priority is None:
            priority = spec.raw.get("priority", DEFAULT_PRIORITY)
        try:
            priority = validate_priority(priority)
        except ValueError as exc:
            raise RequestError(str(exc)) from None
        try:
            fps = float(request.get("fps") or self.sched_cfg.default_fps)
        except (TypeError, ValueError):
            raise RequestError("request.fps must be a number") from None
        if fps <= 0:
            raise RequestError("request.fps must be > 0")
        # Admission BEFORE any resource work: an over-capacity start
        # must cost nothing and fail fast (503 + Retry-After raised as
        # AdmissionError to server/app.py). The ticket is the stream's
        # capacity reservation; release is idempotent and runs from
        # BOTH the failure unwind and the instance-finish cleanups.
        ticket = self.admission.admit(priority, fps)
        try:
            return self._start_admitted(
                name, version, spec, src, request, priority, ticket,
                publish_fn, source, sink_fn, saved_state)
        except BaseException:
            ticket.release()
            raise

    def _start_admitted(
        self,
        name: str,
        version: str,
        spec,
        src,
        request: dict[str, Any],
        priority: str,
        ticket,
        publish_fn,
        source,
        sink_fn,
        saved_state: dict[str, dict] | None,
    ) -> StreamInstance:
        params = request.get("parameters") or {}
        # Resolve stages BEFORE opening the destination: a bad
        # parameter must not truncate/leak the operator's output file.
        stage_specs, _ = resolve_parameters(spec, params)
        dest_cfg = (request.get("destination") or {}).get("metadata")
        destination = create_destination(dest_cfg)
        instance = StreamInstance(
            pipeline_name=name,
            version=version,
            stages=[],
            request=request,
            destination=destination,
            on_finish=lambda _inst: self._on_instance_finish(cleanup_fns),
            source=source,
            decode_pool=self.decode_pool,
            rtsp_demux=self.rtsp_demux,
            priority=priority,
        )
        meta_fn = publish_fn or (lambda ctx: destination.publish(ctx.metadata))
        frame_cfg = (request.get("destination") or {}).get("frame") or {}
        relay = None
        cleanup_fns: list = [ticket.release]
        if frame_cfg.get("type") == "rtsp" and self.rtsp is not None:
            # Annotated re-stream at rtsp://host:8554/<path> (reference
            # destination.frame contract + ENABLE_RTSP flow).
            relay = self.rtsp.mount(frame_cfg.get("path") or name)
            cleanup_fns.append(lambda: self.rtsp.unmount(relay.path))
        elif (frame_cfg.get("type") == "webrtc"
              and self.settings.enable_webrtc
              and self.settings.webrtc_signaling_server):
            # Announce to the external signaling server (reference
            # ENABLE_WEBRTC + WEBRTC_SIGNALING_SERVER flow,
            # docker-compose.yml:51-52).
            from evam_tpu.publish.rtsp import FrameRelay
            from evam_tpu.publish.webrtc import WebRtcSignaler

            relay = FrameRelay(frame_cfg.get("peer-id") or name)
            signaler = WebRtcSignaler(
                self.settings.webrtc_signaling_server,
                relay.path, relay,
                video_mode=self.settings.webrtc_video_mode,
            )
            signaler.start()
            cleanup_fns.append(signaler.stop)
        if relay is not None:
            from evam_tpu.publish.annotate import annotate_frame

            base_fn = meta_fn

            def meta_fn(ctx, _base=base_fn, _relay=relay):  # noqa: F811
                _base(ctx)
                # annotate+encode only when someone is actually
                # watching — it's full-frame host CPU per frame.
                if ctx.frame is not None and _relay.has_clients:
                    _relay.push_bgr(annotate_frame(ctx))

        try:
            stages = build_stages(
                stage_specs,
                self.hub,
                source_uri=(src or {}).get("uri", "") if isinstance(src, dict) else "",
                publish_fn=meta_fn,
                sink_fn=sink_fn,
            )
        except Exception:
            # Already-acquired resources must not leak on a failed
            # start: file/socket destination, RTSP mount, signaler.
            destination.close()
            for fn in cleanup_fns:
                try:
                    fn()
                except Exception:  # noqa: BLE001
                    pass
            raise
        instance.stages = stages
        if saved_state:
            # BEFORE start(): the first resumed frame must already see
            # the restored cross-frame state (tracker id high-water)
            if self._ckpt is not None and is_checkpoint_blob(saved_state):
                # versioned+CRC-guarded StreamCheckpoint from a prior
                # run's drain/migration barrier: full restore with the
                # degradation ladder (corrupt/stale/timeout → loud
                # cold start, never a failed start)
                self._ckpt.restore_into(saved_state, instance)
            else:
                instance.restore_stage_state(saved_state)
        if self._ckpt is not None:
            # register before start(): the runner's first post-resolve
            # capture must find the instance
            self._ckpt.register(instance.id, instance)
        with self._lock:
            self.instances[instance.id] = instance
        instance.start()
        log.info("started %s/%s instance %s", name, version, instance.id)
        self._persist()
        return instance

    def _on_instance_finish(self, cleanup_fns: list) -> None:
        for fn in cleanup_fns:
            try:
                fn()
            except Exception as exc:  # noqa: BLE001
                log.warning("frame-destination cleanup failed: %s", exc)
        self._persist()

    def get_instance(self, instance_id: str) -> StreamInstance | None:
        return self.instances.get(instance_id)

    def stop_instance(self, instance_id: str) -> StreamInstance | None:
        inst = self.instances.get(instance_id)
        if inst is not None:
            inst.deleted = True  # deliberate: survives the drain filter
            if self._ckpt is not None:
                # a deliberate DELETE must not leave a checkpoint that
                # could resurrect the stream on the next boot
                self._ckpt.unregister(instance_id)
            inst.stop()
            self._persist()
        return inst

    def statuses(self) -> list[dict[str, Any]]:
        with self._lock:
            instances = list(self.instances.values())
        return [i.status() for i in instances]

    def scheduler_status(self) -> dict[str, Any]:
        """GET /scheduler payload: the admission snapshot (capacity /
        demand / utilization / per-class counters) plus the live
        per-class queue depths and shed totals from the engines. Keys
        are fixed from boot regardless of EVAM_SCHED — the route is a
        golden contract."""
        out = self.admission.snapshot()
        out["shed"] = self.hub.shed_totals()
        out["queues"] = self.hub.class_queue_depths()
        out["queue"] = self.hub.queue_summary()
        # fleet operating point (evam_tpu/fleet/): per-chip placement
        # counts, shard health, rebalance total — zeros, same shape,
        # when EVAM_FLEET=off or the hub is embedder-supplied
        fleet_fn = getattr(self.hub, "fleet_summary", None)
        out["fleet"] = (fleet_fn() if fleet_fn is not None else {
            "mode": "off", "shards": 0, "degraded_shards": 0,
            "rebalances": 0, "streams": {},
            "max_shards": 0, "scale_ups": 0, "scale_downs": 0})
        return out

    def stop_all(self) -> int:
        """Drain every instance and shut the engines down. Returns the
        number of LEAKED instances — worker threads still alive after
        the per-instance drain budget (settings.drain_timeout_s). A
        wedged stream must not hold shutdown hostage, but it must not
        vanish silently either: stragglers are logged, counted in
        ``evam_shutdown_leaked_streams``, and their persisted state is
        flagged best-effort."""
        # Shutdown drain must keep streams.json intact: these streams
        # should re-attach on the next boot (unlike per-stream DELETE).
        with self._lock:
            instances = list(self.instances.values())
        # capture WHICH streams were live before stop() flips their
        # intent flags; their final stage state is read after the
        # drain so no ids assigned mid-drain are lost
        active = [i for i in instances if self._is_active(i)]
        self._draining = True
        self._persist_stop.set()
        for inst in instances:
            inst.stop()
        for inst in instances:
            inst.wait(timeout=self.settings.drain_timeout_s)
        if self.decode_pool is not None:
            self.decode_pool.stop()
        if self.rtsp_demux is not None:
            self.rtsp_demux.stop()
        leaked = 0
        for inst in instances:
            if inst._thread is not None and inst._thread.is_alive():
                # wait() timed out: this worker may still assign ids
                # after the snapshot below — warn, the persisted state
                # is best-effort for a wedged stream
                if (self._ckpt is not None
                        and self._ckpt.capture(
                            inst.id, barrier="drain",
                            reason="drain") is not None):
                    # checkpointed instead of leaked: the straggler's
                    # state is banked for the next boot's resume(), so
                    # it is a migration, not a loss
                    log.warning(
                        "stream %s still draining at shutdown; "
                        "checkpointed for resume", inst.id[:8],
                    )
                    continue
                leaked += 1
                log.warning(
                    "stream %s still draining at shutdown; persisted "
                    "state may lag", inst.id[:8],
                )
        if self._ckpt is not None:
            # drain barrier for the cleanly-stopped streams: their
            # workers are quiesced, so this capture is exactly the
            # post-resolve state of their last frame — fresher than
            # the periodic in-flight checkpoint
            for inst in active:
                if inst._thread is None or not inst._thread.is_alive():
                    self._ckpt.capture(inst.id, barrier="drain")
        metrics.set("evam_shutdown_leaked_streams", leaked)
        if leaked:
            log.error(
                "shutdown drain abandoned %d straggler stream(s) after "
                "%.1fs each (daemon threads; the process exit reaps "
                "them)", leaked, self.settings.drain_timeout_s,
            )
        # a DELETE racing shutdown must stay deleted (its persist
        # already excluded it), and a stream that finished NATURALLY
        # during the drain must not be replayed on the next boot —
        # only aborted/still-running streams re-attach
        self._write_state([
            self._entry(i) for i in active
            if not i.deleted
            and i.state not in (InstanceState.COMPLETED, InstanceState.ERROR)
        ])
        self.hub.stop()
        return leaked

    # ------------------------------------------------- restart/resume

    def _persist(self) -> None:
        """Persist active stream requests so a restarted server can
        re-attach them (SURVEY.md §5.4 — the reference is stateless
        and drops streams on restart; k8s Recreate just restarts the
        container)."""
        if self._state_file is None or self._draining:
            return
        with self._lock:
            instances = list(self.instances.values())
        active = [
            self._entry(i) for i in instances if self._is_active(i)
        ]
        self._write_state(active)

    def _entry(self, inst: StreamInstance) -> dict:
        """One streams.json record (single definition — the drain and
        event persists must stay schema-identical)."""
        state: dict = inst.stage_state()
        if self._ckpt is not None:
            # prefer the barrier-consistent StreamCheckpoint blob over
            # the live read: the blob was taken with no frame mid-
            # chain, carries the sched class / trace marker / staleness
            # bound, and is CRC-guarded against torn writes. resume()
            # feeds it back through restore_into's degradation ladder.
            blob = self._ckpt.export(inst.id)
            if blob is not None:
                state = blob
        return {
            "pipeline": inst.pipeline_name,
            "version": inst.version,
            "request": inst.request,
            # cross-frame stage state (tracker id high-water mark
            # etc.) so a resumed stream keeps its invariants
            "state": state,
        }

    @staticmethod
    def _is_active(inst: StreamInstance) -> bool:
        # _stop records intent immediately; the worker thread flips
        # state to ABORTED asynchronously, so state alone would
        # resurrect deliberately-stopped streams on restart.
        return (
            inst.state in (InstanceState.QUEUED, InstanceState.RUNNING)
            and not inst._stop.is_set()
        )

    def _periodic_persist(self) -> None:
        while not self._persist_stop.wait(self._persist_interval_s):
            if self._draining:
                return
            with self._lock:
                any_active = any(
                    self._is_active(i) for i in self.instances.values())
            if any_active:
                self._persist()

    def _write_state(self, entries: list[dict]) -> None:
        # Atomic replace under a lock: a finishing stream's on_finish
        # races a DELETE's persist; interleaved write_text calls would
        # corrupt the file and poison the next boot's resume().
        if self._state_file is None:
            return
        with self._persist_lock:
            self._state_file.parent.mkdir(parents=True, exist_ok=True)
            tmp = self._state_file.with_suffix(".tmp")
            tmp.write_text(json.dumps(entries, indent=2))
            os.replace(tmp, self._state_file)

    def resume(self) -> int:
        """Re-start streams recorded by a previous run. Returns count."""
        if self._state_file is None or not self._state_file.exists():
            return 0
        try:
            entries = json.loads(self._state_file.read_text())
        except (OSError, json.JSONDecodeError) as exc:
            log.warning("stream state file unreadable (%s); skipping resume",
                        exc)
            return 0
        n = 0
        for e in entries:
            try:
                self.start_instance(
                    e["pipeline"], e["version"], e["request"],
                    saved_state=e.get("state") or None,
                )
                n += 1
            except Exception as exc:  # noqa: BLE001
                log.warning("resume of %s/%s failed: %s",
                            e.get("pipeline"), e.get("version"), exc)
        if n:
            log.info("resumed %d stream(s) from %s", n, self._state_file)
        return n
