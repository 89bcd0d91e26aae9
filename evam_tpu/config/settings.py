"""Service settings: file < env < pipeline default < request override.

(Env beats the config file — operators override a deployed file with
container env vars, matching the reference's compose-driven env
surface.)

Covers the reference's three config tiers (SURVEY.md §5.6):
  (a) env vars — RUN_MODE (reference run.sh:26), DETECTION_DEVICE /
      CLASSIFICATION_DEVICE (docker-compose.yml:58-59), ENABLE_RTSP /
      RTSP_PORT / ENABLE_WEBRTC / WEBRTC_SIGNALING_SERVER
      (docker-compose.yml:49-52), MODELS_DIR / PIPELINES_DIR
      (eii/docker-compose.yml:50-51), PY_LOG_LEVEL / DEV_MODE
      (evas/__main__.py:36-46), PROFILING_MODE
      (eii/docker-compose.yml:43);
  (b) a config file (the reference uses etcd via EII ConfigManager,
      evas/__main__.py:34 — here a local JSON file with an optional
      watcher, see evam_tpu/eii/configmgr.py);
  (c) per-pipeline JSON parameter defaults with per-request overrides
      (resolved in evam_tpu/graph/params.py).
"""

from __future__ import annotations

import json
import os
from pathlib import Path

from typing import Literal

from pydantic import BaseModel, Field


class TPUSettings(BaseModel):
    """TPU engine knobs — new surface, no reference equivalent."""

    mesh_shape: list[int] = Field(default_factory=lambda: [-1])
    mesh_axes: list[str] = Field(default_factory=lambda: ["data"])
    #: top batch bucket: the latency-leaning default; throughput-bound
    #: deployments raise EVAM_MAX_BATCH (higher p99) — dispatch
    #: overhead amortizes with batch, so undersizing this is the first
    #: thing to check when a chip underdelivers.
    max_batch: int = 128
    batch_deadline_ms: float = 8.0
    precision: str = "bfloat16"
    #: precompile every batch bucket in the background when an engine
    #: is created (kills mid-traffic compile spikes; off in tests)
    warmup: bool = True
    #: engine stall watchdog: one batch's device round-trip bound in
    #: seconds (0 disables); raise for very large models/compiles
    stall_timeout_s: float = 120.0
    #: engine supervision (engine/supervisor.py): quarantine a wedged
    #: engine and rebuild it in place instead of serving 503 until a
    #: process restart
    supervise: bool = True
    #: restart budget: at most this many rebuilds per engine within
    #: restart_window_s; exhausting it is terminal `degraded`
    max_restarts: int = 3
    restart_window_s: float = 300.0
    #: base of the exponential backoff between quarantine and rebuild
    restart_backoff_s: float = 0.5
    #: stall-watchdog multiplier for a bucket's FIRST batch (its
    #: round-trip contains trace + XLA compile); without it every
    #: cold start — including a supervisor rebuild's fresh jit —
    #: reads as a wedge
    first_batch_grace: float = 10.0
    #: upload-queue depth (engine/batcher.py): how many staged batches
    #: may sit between the dispatcher's h2d_issue and the launcher.
    #: 2 = one batch uploading while one launches.
    transfer_depth: int = 2
    #: ragged batching (engine/ragged.py): "packed" packs classify
    #: region sets into one fixed masked-compute device shape (row
    #: length/offset vectors, Ragged Paged Attention style) and
    #: consolidates adjacent batch buckets onto shared programs;
    #: "off" (default until a TPU accuracy window) keeps the dense
    #: bucketed path byte-identical for A/B (tools/bench_ragged.py).
    ragged: Literal["packed", "off"] = "off"
    #: packed unit rows budgeted per batch row (how many region slots
    #: a packed classify batch carries per frame ON AVERAGE; floored
    #: at the stage ROI budget so a lone full frame always fits)
    ragged_unit_budget: int = 4
    #: fleet serving mode (evam_tpu/fleet/): "sharded" serves every
    #: engine key as one per-chip shard per mesh device behind a
    #: consistent-hash stream placer (small buckets, no collectives)
    #: plus one mesh-sharded twin for batch-class big buckets, with
    #: fleet-wide Σ-shard admission capacity and drain-and-rebalance
    #: on shard degradation; "off" (default) keeps the single-chip
    #: path byte-identical for A/B (tools/bench_fleet.py), the same
    #: discipline as EVAM_GATE / EVAM_RAGGED.
    fleet: Literal["sharded", "off"] = "off"
    #: fleet only: restrict sharding to the first N mesh devices
    #: (0 = all) — the bench/canary knob for scaling curves
    fleet_shards: int = 0
    #: fleet only: per-shard bucket-ladder top (0 = max_batch / shard
    #: count) — a chip serving 1/N of the streams doesn't need the
    #: fleet-wide max_batch worth of compile bill and staging memory
    fleet_shard_max_batch: int = 0
    #: fleet growth ceiling: FleetEngine.scale_up may grow the fleet
    #: up to this many shards (bounded by the mesh), and the fleet
    #: then boots at EVAM_FLEET_SHARDS. 0 (default): no growth, the
    #: fleet stays at EVAM_FLEET_SHARDS. Nothing in the server calls
    #: scale_up yet (ROADMAP.md Queue 3).
    fleet_max_shards: int = 0


class SchedSettings(BaseModel):
    """QoS scheduling knobs (evam_tpu/sched/): admission control,
    priority classes, load shedding. ``EVAM_SCHED=off`` disables the
    whole layer — admission admits everything and every engine's
    class queues run as one FIFO that sheds nothing."""

    enabled: bool = True
    #: projected-utilization ceiling for admission control; a start
    #: that would push demand/capacity past it is rejected 503 +
    #: Retry-After (classes get headroom-scaled ceilings — batch is
    #: turned away first, realtime last). 0 disables admission.
    admit_util: float = 0.85
    #: operator-declared serving capacity in frames/s; 0 = derive it
    #: from live EngineStats stage timings (a cold hub admits all)
    capacity_fps: float = 0.0
    #: assumed per-stream fps when a start request declares none
    default_fps: float = 30.0
    #: per-class batch-formation deadlines (ms): cameras keep a small
    #: latency floor, bulk traffic fills big buckets. Unless
    #: explicitly set, the standard class inherits the engine-level
    #: EVAM_BATCH_DEADLINE_MS (SchedConfig.from_settings) — turning
    #: the scheduler on must not repeal a tuned global deadline.
    deadline_ms_realtime: float = 4.0
    deadline_ms_standard: float = 8.0
    deadline_ms_batch: float = 25.0
    #: per-class staleness budgets (ms): frames older than this at
    #: dispatch are shed oldest-first (freshest-frame-wins) with
    #: their futures failed as ShedError. 0 = never shed that class.
    staleness_ms_realtime: float = 200.0
    staleness_ms_standard: float = 1000.0
    staleness_ms_batch: float = 5000.0


class TraceSettings(BaseModel):
    """Per-frame tracing knobs (obs/trace.py): trace ids minted at
    ingest, span trees through engine dispatch, a bounded in-process
    ring with tail-based sampling, and the quarantine flight
    recorder. ``EVAM_TRACE=off`` disables the whole layer —
    byte-identical A/B (tools/bench_trace.py), same discipline as
    EVAM_GATE."""

    enabled: bool = True
    #: healthy-frame retention: keep 1-in-N (error/shed/deadline-miss
    #: frames and the slow tail are ALWAYS retained regardless)
    sample_n: int = 16
    #: bounded ring capacity — retained frame traces and completed
    #: batch records each (the ring never grows past this)
    ring: int = 1024
    #: frames slower than this end-to-end are "the slow tail" and are
    #: always retained
    slow_ms: float = 250.0
    #: flight-recorder artifact directory; empty = <tmpdir>/evam_flight
    flight_dir: str = ""
    #: most-recent records of each kind written per flight dump
    flight_n: int = 256
    #: flight-recorder disk bound: keep at most this many
    #: flight-*.jsonl files in flight_dir (oldest rotated out after
    #: every dump; 0 = unbounded, the pre-cap behavior)
    flight_max_files: int = 64
    #: flight-recorder disk bound: total bytes across retained dumps
    #: (oldest rotated out first; 0 = unbounded)
    flight_max_bytes: int = 67108864


class CkptSettings(BaseModel):
    """Crash-consistent stream-state checkpoints (evam_tpu/state/):
    a versioned, CRC-guarded StreamCheckpoint of every stream's
    serving state (gate luma grid, coaster velocities, tracker
    identities, sched class, trace continuity) captured at the
    post-resolve and pre-rebalance barriers and restored before the
    first frame after a migration, rebuild, or restart.
    ``EVAM_CKPT=off`` (default until proven) disables the whole layer
    — byte-identical A/B, same discipline as EVAM_GATE /
    EVAM_TRACE."""

    enabled: bool = False
    #: post-resolve capture cadence: refresh a stream's checkpoint
    #: every N resolved frames (1 = every frame; the barrier capture
    #: is a dict build + CRC, no device work)
    interval: int = 30
    #: restore budget in seconds: a restore slower than this (stuck
    #: state volume, injected restore_ms fault) is abandoned for a
    #: loud cold start — a checkpoint must never wedge a stream
    restore_timeout_s: float = 2.0


class AotSettings(BaseModel):
    """Persistent AOT executable cache (evam_tpu/aot/): serialized
    compiled executables in a content-addressed, CRC-guarded,
    size-capped on-disk store shared by supervisor rebuilds, fleet
    shard spin-up and every warmup path. ``EVAM_AOT=off`` (default
    until proven) disables the whole layer — byte-identical A/B
    (tools/bench_aot.py), same discipline as EVAM_GATE /
    EVAM_TRACE / EVAM_CKPT."""

    enabled: bool = False
    #: cache directory; empty = <tmpdir>/evam_aot. Share it across
    #: processes/containers on one host — entries are atomic and
    #: content-addressed, concurrent writers converge.
    dir: str = ""
    #: size cap in bytes (LRU by mtime past it; the newest entry
    #: always survives). Default 1 GiB.
    max_bytes: int = 1073741824


class LMSettings(BaseModel):
    """Fixed shapes of the generate engine (engine/generate.py), which
    serves the ``describe`` stage's language model. Nothing reads them
    in a server without such a stage. The defaults are the deployment's;
    EVAM_LM_SHAPES exists for rehearsals and tests at a tiny size, and no
    deployment file sets it."""

    #: the CEILING of the sequence slots (generations in flight on the
    #: device; a family with recurrent layers keeps one row of state per
    #: slot): the engine takes as many of them as its family's state
    #: leaves room for on the device (engine/generate.py ``fit_slots``)
    slots: int = 128
    #: tokens per page of the family's cache (latent rows, or keys and
    #: values)
    page_tokens: int = 128
    #: prompt tokens one prefill step packs, of at most max_segments
    #: sequences
    chunk_tokens: int = 512
    max_segments: int = 8
    #: tokens a sequence may add to the shared prefix (prompt +
    #: generated)
    private_tokens: int = 384
    #: tokens of the operator instruction every sequence shares
    prefix_tokens: int = 2048


class Settings(BaseModel):
    """Flat service settings resolved from env + optional config file."""

    run_mode: str = "EVA"  # EVA (REST) vs EII (msgbus) — reference run.sh:26-30
    rest_port: int = 8080  # reference docker-compose.yml:44
    detection_device: str = "tpu"  # reference default CPU, docker-compose.yml:58
    classification_device: str = "tpu"  # reference docker-compose.yml:59
    models_dir: str = "models"  # reference eii/docker-compose.yml:50
    pipelines_dir: str = "pipelines"  # reference eii/docker-compose.yml:51
    enable_rtsp: bool = False  # reference docker-compose.yml:49
    rtsp_port: int = 8554  # reference docker-compose.yml:45,50
    enable_webrtc: bool = False  # reference docker-compose.yml:51
    webrtc_signaling_server: str = ""  # reference docker-compose.yml:52
    #: "key" = keyframe-only VP8 (shared encoder, lowest latency);
    #: "delta" = per-viewer GOP delta encoding (~40x lower bitrate,
    #: gop/fps extra latency) — see publish/rtc/vp8.py
    webrtc_video_mode: Literal["key", "delta"] = "key"
    log_level: str = "INFO"  # PY_LOG_LEVEL, reference evas/__main__.py:42
    dev_mode: bool = True  # DEV_MODE, reference evas/__main__.py:36
    profiling_mode: bool = False  # reference eii/docker-compose.yml:43
    state_dir: str = ""  # stream-registry persistence (hardening, SURVEY §5.4)
    #: comma list of pipelines (name or name/version) or "all" to
    #: build+warm engines before the REST port opens (EVAM_PRELOAD)
    preload: str = ""
    #: >0 routes file/RTSP decode through a shared DecodePool of this
    #: many worker threads instead of per-stream inline decode —
    #: bounds total decode threads at 64-stream scale
    #: (media/pool.py; VERDICT r3 item 10). 0 = per-stream (default).
    decode_pool_workers: int = 0
    #: >0 routes rtsp:// sources through the async RtspDemux (one
    #: selector thread + this many JPEG-decode workers for ALL live
    #: streams — media/demux.py; VERDICT r4 item 3). 0 = per-stream
    #: blocking reader via cv2/FFmpeg (default; required for
    #: non-RFC-2435 camera codecs until RFC 6184 lands).
    rtsp_demux_workers: int = 0
    #: shutdown drain: per-instance join budget in seconds; stragglers
    #: past it are logged and counted (evam_shutdown_leaked_streams),
    #: never waited on indefinitely
    drain_timeout_s: float = 5.0
    tpu: TPUSettings = Field(default_factory=TPUSettings)
    sched: SchedSettings = Field(default_factory=SchedSettings)
    trace: TraceSettings = Field(default_factory=TraceSettings)
    ckpt: CkptSettings = Field(default_factory=CkptSettings)
    aot: AotSettings = Field(default_factory=AotSettings)
    lm: LMSettings = Field(default_factory=LMSettings)

    @classmethod
    def from_env(cls, config_file: str | os.PathLike | None = None) -> "Settings":
        data: dict = {}
        if config_file and Path(config_file).exists():
            data.update(json.loads(Path(config_file).read_text()))

        env = os.environ
        mapping = {
            "RUN_MODE": ("run_mode", str),
            "REST_PORT": ("rest_port", int),
            "DETECTION_DEVICE": ("detection_device", str),
            "CLASSIFICATION_DEVICE": ("classification_device", str),
            "MODELS_DIR": ("models_dir", str),
            "PIPELINES_DIR": ("pipelines_dir", str),
            "ENABLE_RTSP": ("enable_rtsp", _parse_bool),
            "RTSP_PORT": ("rtsp_port", int),
            "ENABLE_WEBRTC": ("enable_webrtc", _parse_bool),
            "WEBRTC_SIGNALING_SERVER": ("webrtc_signaling_server", str),
            "EVAM_WEBRTC_VIDEO_MODE": ("webrtc_video_mode", str),
            "PY_LOG_LEVEL": ("log_level", str),
            "DEV_MODE": ("dev_mode", _parse_bool),
            "PROFILING_MODE": ("profiling_mode", _parse_bool),
            "EVAM_STATE_DIR": ("state_dir", str),
            "EVAM_PRELOAD": ("preload", str),
            "EVAM_DECODE_POOL_WORKERS": ("decode_pool_workers", int),
            "EVAM_RTSP_DEMUX_WORKERS": ("rtsp_demux_workers", int),
            "EVAM_DRAIN_TIMEOUT_S": ("drain_timeout_s", float),
        }
        for var, (key, conv) in mapping.items():
            if var in env:
                data[key] = conv(env[var])

        tpu = data.setdefault("tpu", {})
        tpu_mapping = {
            "EVAM_MAX_BATCH": ("max_batch", int),
            "EVAM_BATCH_DEADLINE_MS": ("batch_deadline_ms", float),
            "EVAM_PRECISION": ("precision", str),
            "EVAM_WARMUP": ("warmup", _parse_bool),
            "EVAM_STALL_TIMEOUT_S": ("stall_timeout_s", float),
            "EVAM_ENGINE_SUPERVISE": ("supervise", _parse_bool),
            "EVAM_ENGINE_MAX_RESTARTS": ("max_restarts", int),
            "EVAM_ENGINE_RESTART_WINDOW_S": ("restart_window_s", float),
            "EVAM_ENGINE_RESTART_BACKOFF_S": ("restart_backoff_s", float),
            "EVAM_FIRST_BATCH_GRACE": ("first_batch_grace", float),
            "EVAM_TRANSFER_DEPTH": ("transfer_depth", int),
            "EVAM_RAGGED": ("ragged", str),
            "EVAM_RAGGED_UNIT_BUDGET": ("ragged_unit_budget", int),
            "EVAM_FLEET": ("fleet", str),
            "EVAM_FLEET_SHARDS": ("fleet_shards", int),
            "EVAM_FLEET_SHARD_MAX_BATCH": ("fleet_shard_max_batch", int),
            "EVAM_FLEET_MAX_SHARDS": ("fleet_max_shards", int),
        }
        if isinstance(tpu, dict):
            for var, (key, conv) in tpu_mapping.items():
                if var in env:
                    tpu[key] = conv(env[var])

        sched = data.setdefault("sched", {})
        sched_mapping = {
            "EVAM_SCHED": ("enabled", _parse_bool),
            "EVAM_SCHED_ADMIT_UTIL": ("admit_util", float),
            "EVAM_SCHED_CAPACITY_FPS": ("capacity_fps", float),
            "EVAM_SCHED_DEFAULT_FPS": ("default_fps", float),
            "EVAM_SCHED_DEADLINE_MS_REALTIME": ("deadline_ms_realtime", float),
            "EVAM_SCHED_DEADLINE_MS_STANDARD": ("deadline_ms_standard", float),
            "EVAM_SCHED_DEADLINE_MS_BATCH": ("deadline_ms_batch", float),
            "EVAM_SCHED_STALENESS_MS_REALTIME": (
                "staleness_ms_realtime", float),
            "EVAM_SCHED_STALENESS_MS_STANDARD": (
                "staleness_ms_standard", float),
            "EVAM_SCHED_STALENESS_MS_BATCH": ("staleness_ms_batch", float),
        }
        if isinstance(sched, dict):
            for var, (key, conv) in sched_mapping.items():
                if var in env:
                    sched[key] = conv(env[var])

        trace = data.setdefault("trace", {})
        trace_mapping = {
            "EVAM_TRACE": ("enabled", _parse_bool),
            "EVAM_TRACE_SAMPLE_N": ("sample_n", int),
            "EVAM_TRACE_RING": ("ring", int),
            "EVAM_TRACE_SLOW_MS": ("slow_ms", float),
            "EVAM_TRACE_FLIGHT_DIR": ("flight_dir", str),
            "EVAM_TRACE_FLIGHT_N": ("flight_n", int),
            "EVAM_TRACE_FLIGHT_MAX_FILES": ("flight_max_files", int),
            "EVAM_TRACE_FLIGHT_MAX_BYTES": ("flight_max_bytes", int),
        }
        if isinstance(trace, dict):
            for var, (key, conv) in trace_mapping.items():
                if var in env:
                    trace[key] = conv(env[var])

        ckpt = data.setdefault("ckpt", {})
        ckpt_mapping = {
            "EVAM_CKPT": ("enabled", _parse_bool),
            "EVAM_CKPT_INTERVAL": ("interval", int),
            "EVAM_CKPT_RESTORE_TIMEOUT_S": ("restore_timeout_s", float),
        }
        if isinstance(ckpt, dict):
            for var, (key, conv) in ckpt_mapping.items():
                if var in env:
                    ckpt[key] = conv(env[var])

        aot = data.setdefault("aot", {})
        aot_mapping = {
            "EVAM_AOT": ("enabled", _parse_bool),
            "EVAM_AOT_DIR": ("dir", str),
            "EVAM_AOT_MAX_BYTES": ("max_bytes", int),
        }
        if isinstance(aot, dict):
            for var, (key, conv) in aot_mapping.items():
                if var in env:
                    aot[key] = conv(env[var])

        # EVAM_LM_SHAPES="slots=8,page_tokens=8,...": the generate
        # engine's shapes move together (a rehearsal sets them all), so
        # they share one variable
        lm = data.setdefault("lm", {})
        if isinstance(lm, dict) and env.get("EVAM_LM_SHAPES"):
            for item in env["EVAM_LM_SHAPES"].split(","):
                key, _, value = item.partition("=")
                key = key.strip()
                if key not in LMSettings.model_fields:
                    raise ValueError(
                        f"EVAM_LM_SHAPES: unknown shape {key!r} "
                        f"({', '.join(LMSettings.model_fields)})")
                lm[key] = int(value)
        return cls.model_validate(data)


def _parse_bool(value: str) -> bool:
    return value.strip().lower() in ("1", "true", "yes", "on")


_settings: Settings | None = None


def get_settings() -> Settings:
    global _settings
    if _settings is None:
        _settings = Settings.from_env(os.environ.get("EVAM_CONFIG_FILE"))
    return _settings


def reset_settings() -> None:
    """Drop the cached settings (tests / hot reload)."""
    global _settings
    _settings = None
