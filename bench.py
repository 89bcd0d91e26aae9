"""Headline benchmark: concurrent 1080p detect+classify streams per chip.

Measures sustained throughput of the flagship fused engine step
(wire-decode + preprocess + SSD detect + NMS + ROI classify in ONE XLA
program, evam_tpu.engine.steps) on real 1080p frames in I420 wire
format, with deep pipelining (multiple batches in flight over the
async dispatch queue) exactly like the serving BatchEngine.

Metric: `streams_1080p_30fps_per_chip` — aggregate FPS / 30.
vs_baseline: against the BASELINE.json north star of 64 streams on a
v5e-4, i.e. 16 streams per chip (the reference publishes no numbers —
BASELINE.md "Published FPS / latency: none").

Prints ONE JSON line on stdout; diagnostics go to stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# The bench is hermetic by design (BASELINE.md: no published weights to
# compare against) — explicitly opt in to deterministic random-init
# weights; production serving stays strict (registry.MissingWeightsError)
os.environ.setdefault("EVAM_ALLOW_RANDOM_WEIGHTS", "1")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _metric_for(cfg: str) -> str:
    """Metric naming:

    * detect / detect_classify → ``streams_1080p_30fps_per_chip``:
      sustained FPS of the fused XLA program on 1080p wire frames,
      divided by 30 (how many 30 fps cameras one chip's compute
      absorbs).
    * serve → ``serve_streams_30fps_per_chip``: same normalization but
      measured through the WHOLE serving path (REST-shaped pipeline
      instances: source → StreamRunner → shared BatchEngine → track →
      metaconvert → publish), counting only frames that completed the
      full chain.
    * action → ``action_streams_30fps_per_chip``: one "stream" is a
      30 fps camera. Every frame passes the encoder AND (after the
      16-frame warm-up) one sliding-window clip passes the decoder per
      frame (stages/infer.py ActionStage), so a stream costs 30
      encoder-frames/s + 30 decoder-clips/s. Both engines share the
      chip serially → streams = 1 / (30/enc_fps + 30/dec_cps). The
      JSON line carries both component rates.
    * audio → ``audio_streams_per_chip``: one stream is a live audio
      feed at the reference's sliding-window default (1 s window,
      0.2 s stride ⇒ 5 windows/s per stream,
      pipelines/audio_detection/environment/pipeline.json), so
      streams = window_rate / 5. NOT a 30 fps metric — the round-2
      numbers normalized by 30 and were meaningless (PROFILE.md
      reconciliation note).
    """
    if cfg in ("detect_classify", "detect"):
        return "streams_1080p_30fps_per_chip"
    if cfg == "audio":
        return "audio_streams_per_chip"
    return f"{cfg}_streams_30fps_per_chip"


def fail_line(metric: str, reason: str) -> int:
    """A bench that could not run: one parseable JSON line carrying
    the ``error``, and a NON-ZERO exit code — a failure must never
    read as a measured 0.0."""
    log(f"BENCH FAILURE: {reason}")
    print(json.dumps({
        "metric": metric,
        "value": 0.0,
        "unit": "streams",
        "vs_baseline": 0.0,
        "error": reason,
    }))
    return 1


def _measure_action_decoder(registry, args, batch: int, depth: int,
                            seconds: float = 4.0) -> float:
    """Decoder clips/s at the serving clip shape (sliding CLIP_LEN
    window of encoder embeddings, stages/infer.py ActionStage) — the
    second component of the action stream metric (_metric_for).
    Clips are synthesized on-device, same pipelined loop as measure()."""
    import jax
    import jax.numpy as jnp

    from evam_tpu.engine import steps as step_builders
    from evam_tpu.models.zoo.action import CLIP_LEN

    dec = registry.get("action_recognition/decoder")
    enc = registry.get("action_recognition/encoder")
    d_embed = int(getattr(enc.module, "embed_dim", 512) or 512)
    step = step_builders.build_action_decode_step(dec)
    params = jax.device_put(dec.params)
    n = batch * CLIP_LEN * d_embed

    def seeded(params, seed):
        bits = step_builders.weyl_bits(seed.astype(jnp.uint32), n)
        clips = (bits >> jnp.uint32(9)).astype(jnp.float32) / 8388608.0
        return step(params, clips.reshape(batch, CLIP_LEN, d_embed))

    fn = jax.jit(seeded)
    seeds = [np.uint32(0), np.uint32(1)]
    jax.block_until_ready(fn(params, seeds[0]))
    inflight: list = []
    batches = 0
    start = time.perf_counter()
    deadline = start + seconds
    while time.perf_counter() < deadline:
        inflight.append(fn(params, seeds[batches % 2]))
        batches += 1
        if len(inflight) >= depth:
            jax.block_until_ready(inflight.pop(0))
    for out in inflight:
        jax.block_until_ready(out)
    elapsed = time.perf_counter() - start
    return batches * batch / elapsed


def _label_values(series: dict, ndigits: int) -> dict:
    """{'{stage="tracking"}': 0.0012} → {'tracking': 1.2} (ms)."""
    import re

    out = {}
    for lbl, v in series.items():
        m = re.search(r'"([^"]+)"', lbl)
        key = m.group(1) if m else lbl
        out[key] = round(v * 1e3, ndigits)
    return out


def run_serve_bench(args) -> dict:
    """Benchmark the FRAMEWORK, not just the XLA program (round-2
    VERDICT item 1): boot a PipelineRegistry + shared EngineHub exactly
    as ``evam-tpu serve`` does, start N free-running synthetic pipeline
    instances through the full stage chain — source → StreamRunner →
    BatchEngine dispatcher/completer → track → metaconvert → publish —
    and report aggregate sustained throughput plus END-TO-END per-frame
    latency (feed → chain complete, the evam_frame_latency_seconds
    histogram that obs/trace.py keeps for /metrics).

    ``--serve-ingest seed`` (default here) synthesizes wire batches
    on-chip (steps.wrap_device_synth), so the number leaves out the
    host→device pixel copy; ``--serve-ingest host`` runs the real
    pixel path (host resize + wire encode + transfer) — the
    deployment shape.
    """
    import pathlib

    from evam_tpu.config import Settings
    from evam_tpu.engine import EngineHub
    from evam_tpu.models import ModelRegistry
    from evam_tpu.obs.metrics import metrics
    from evam_tpu.parallel import build_mesh
    from evam_tpu.server.registry import PipelineRegistry

    repo = pathlib.Path(__file__).resolve().parent
    settings = Settings(
        pipelines_dir=str(repo / "pipelines"),
        rtsp_demux_workers=(
            args.demux_workers if args.serve_ingest == "rtsp" else 0),
    )
    registry = ModelRegistry(
        models_dir=args.models_dir,
        dtype="int8" if args.precision == "int8" else "bfloat16")
    hub = EngineHub(
        registry, plan=build_mesh(), max_batch=args.batch,
        deadline_ms=args.deadline_ms, wire_format=args.wire,
        warmup=True, device_synth=args.serve_ingest == "seed",
        stall_timeout_s=args.stall_timeout,
    )
    reg = PipelineRegistry(settings, hub=hub)
    name, _, version = args.serve_pipeline.partition("/")
    if args.serve_ingest == "seed":
        # descriptor-only host frames: pixels are synthesized on-chip,
        # so source resolution only feeds metadata (and host costs)
        src_w, src_h = 128, 96
    else:
        src_w, src_h = args.width, args.height
    dest = {
        "null": {"type": "null"},
        "file": {"type": "file", "path": "/tmp/evam_serve_bench.jsonl",
                 "format": "json-lines"},
        "mqtt": {"type": "mqtt", "host": "127.0.0.1", "port": 1883,
                 "topic": "evam/serve_bench"},
    }[args.serve_publish]

    # live-RTSP loopback ingest: an in-process camera farm paced at
    # 30 fps feeding the async demux — the config-5 ingest shape
    cam_srv = None
    cam_stop = None
    if args.serve_ingest == "rtsp":
        import threading as _th

        import numpy as _np

        from evam_tpu.publish.rtsp import RtspServer

        cam_srv = RtspServer(port=0, host="127.0.0.1")
        cam_srv.start()
        cam_stop = _th.Event()

        def _feeder(relay, i):
            k = 0
            f = _np.zeros((src_h, src_w, 3), _np.uint8)
            f[:, :, 2] = (13 * i) % 256
            next_t = time.monotonic()
            while not cam_stop.is_set():
                # push_bgr owns the encode (MAX_DIM cap + 8-align);
                # has_clients skips N×30fps encodes while engines
                # warm and no demux stream has connected yet
                if relay.has_clients:
                    f[:, :, 1] = (k * 9) % 256
                    relay.push_bgr(f)
                k += 1
                next_t += 1 / 30.0
                time.sleep(max(0.0, next_t - time.monotonic()))

        for i in range(args.streams):
            _th.Thread(
                target=_feeder, args=(cam_srv.mount(f"cam{i}"), i),
                daemon=True).start()

    insts = []
    try:
        # Build + warm the pipeline's engines BEFORE any stream
        # exists, so the window measures steady state. Preload uses
        # the instance stage-build path (streams get cache hits) and
        # raises on an unknown name, a build failure or a failed
        # bucket compile.
        t_warm0 = time.perf_counter()
        reg.preload(args.serve_pipeline)
        log(f"[serve] {reg.hub.readiness()['engines']} engines warm "
            f"after {time.perf_counter() - t_warm0:.1f}s")

        for i in range(args.streams):
            if args.serve_ingest == "rtsp":
                uri = f"rtsp://127.0.0.1:{cam_srv.port}/cam{i}"
            else:
                uri = f"synthetic://{src_w}x{src_h}@30?seed={i}"
            insts.append(reg.start_instance(name, version, {
                "source": {"uri": uri, "type": "uri"},
                "destination": {"metadata": dest},
            }))
        time.sleep(3.0)  # reach steady state before the clock starts

        def frames_out():
            return [
                inst._runner.frames_out if inst._runner else 0
                for inst in insts
            ]

        metrics.reset()  # window-scoped latency histogram
        base = frames_out()
        t0 = time.perf_counter()
        time.sleep(max(args.seconds, 3.0))
        elapsed = time.perf_counter() - t0
        deltas = [n - b for n, b in zip(frames_out(), base)]
        fps = sum(deltas) / elapsed
        wnd = {
            "streams": fps / 30.0,
            "fps": fps,
            "p50": metrics.quantile(
                "evam_frame_latency_seconds", 0.5) * 1e3,
            "p99": metrics.quantile(
                "evam_frame_latency_seconds", 0.99) * 1e3,
            "min_stream_fps": min(deltas) / elapsed,
            "max_stream_fps": max(deltas) / elapsed,
            # where the end-to-end latency goes: engine round-trip
            # per item vs host stage costs (obs/trace histograms)
            "stage_p50_ms": _label_values(
                metrics.quantiles_by_label(
                    "evam_stage_seconds", 0.5), 2),
            "engine_item_p50_ms": _label_values(
                metrics.quantiles_by_label(
                    "evam_item_latency_seconds", 0.5), 1),
            # per-batch host clock through the BatchEngine
            # (ringbuf.STAGES): slot-write / seal / h2d issue+wait
            # / launch / readback attribution, max across engines
            "host_stage_p50_ms": {
                stage: round(v * 1e3, 3)
                for stage, v in metrics.quantiles_grouped(
                    "evam_engine_stage_seconds", 0.5,
                    "stage").items()
            },
        }
        log(f"[serve] window: {fps:.0f} FPS total "
            f"({wnd['streams']:.1f} streams), e2e "
            f"p50={wnd['p50']:.0f}ms p99={wnd['p99']:.0f}ms, "
            f"per-stream fps [{wnd['min_stream_fps']:.1f}, "
            f"{wnd['max_stream_fps']:.1f}]")
        errors = sum(
            inst._runner.errors if inst._runner else 0 for inst in insts
        )
        states = [inst.state.value for inst in insts]
        dead = sum(1 for s in states if s not in ("RUNNING", "QUEUED"))
        # snapshot before stop(): hub.stop() drops the engine registry
        eng_stats = reg.hub.stats()
        occupancy = {
            k: round(v["items"] / max(1, v["batches"]), 1)
            for k, v in eng_stats.items()
        }
        # compile-cache accounting (engine/ragged.py satellite):
        # distinct bucket programs the run compiled across engines —
        # the number bucket consolidation (EVAM_RAGGED=packed) exists
        # to shrink; measured here so the claim is checkable on every
        # serve line rather than asserted
        compiled_programs = sum(
            v.get("compiled_programs", 0) for v in eng_stats.values())
        # engine supervision outcome (engine/supervisor.py): a wedge
        # mid-window shows up as restarts>0 with state back to
        # running — or as a degraded engine, which the driver must
        # not mistake for a healthy low-throughput run
        engine_restarts = sum(
            v.get("restarts", 0) for v in eng_stats.values())
        engine_states = {
            k: v.get("state", "running") for k, v in eng_stats.items()}
        # QoS-layer outcome (evam_tpu/sched/): per-class admission and
        # shed counts on the contract line, from the reset-proof local
        # counters (the window-scoped metrics.reset() above must not
        # erase them). All-zero shed/rejected = the run never hit the
        # overload ladder.
        sched_counts = reg.admission.counts()
        sched_shed = reg.hub.shed_totals()
        # content-adaptive gating outcome (stages/gate.py): run/skip
        # totals across gated streams, reset-proof like the sched
        # counters. All-zero = the run never gated (EVAM_GATE off and
        # no adaptive inference-interval) — the ungated A/B baseline.
        from evam_tpu.stages.gate import registry as gate_registry

        gate_summary = gate_registry.summary()
        # fleet operating point (evam_tpu/fleet/): fixed shape whether
        # EVAM_FLEET is off (mode=off, zeros) or sharded — the
        # contract line pins the keys either way
        fleet_summary = reg.hub.fleet_summary()
        demux_stats = (reg.rtsp_demux.stats()
                       if reg.rtsp_demux is not None else None)
    finally:
        if cam_stop is not None:
            cam_stop.set()
        reg.stop_all()  # registry owns hub shutdown (stops engines too)
        if cam_srv is not None:
            cam_srv.stop()

    result_extra = {}
    if wnd["streams"] <= 0:
        # distinguish "the serving path is slow" from "nothing moved"
        # (wedged backend mid-window)
        result_extra["error"] = (
            f"no frames completed in the window (states: {states})")
    return {
        "metric": "serve_streams_30fps_per_chip",
        "value": round(wnd["streams"], 2),
        **result_extra,
        "unit": "streams",
        "vs_baseline": round(wnd["streams"] / 16.0, 3),
        "n_instances": args.streams,
        "pipeline": args.serve_pipeline,
        "serve_ingest": args.serve_ingest,
        "publish": args.serve_publish,
        "e2e_p50_ms": round(wnd["p50"], 1),
        "e2e_p99_ms": round(wnd["p99"], 1),
        "min_stream_fps": round(wnd["min_stream_fps"], 2),
        "max_stream_fps": round(wnd["max_stream_fps"], 2),
        "frames_per_batch": occupancy,
        "compiled_programs": compiled_programs,
        "stage_p50_ms": wnd["stage_p50_ms"],
        "engine_item_p50_ms": wnd["engine_item_p50_ms"],
        "host_stage_p50_ms": wnd["host_stage_p50_ms"],
        "errors": errors,
        "dead_streams": dead,
        "engine_restarts": engine_restarts,
        "engine_states": engine_states,
        "sched_admitted": sched_counts["admitted"],
        "sched_rejected": sched_counts["rejected"],
        "sched_shed": sched_shed,
        "gate": gate_summary,
        "fleet": fleet_summary,
        **({"demux": demux_stats} if demux_stats else {}),
    }


def main() -> int:
    p = argparse.ArgumentParser()
    # Default operating point: batch 256 x depth 3 — large batches
    # amortize the per-dispatch cost, and at the 64-stream north-star
    # fan-in (1920 frames/s) a 256-frame deadline batch fills in
    # ~130 ms. Latency-bound deployments run batch 128 x depth 1.
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--height", type=int, default=1080)
    p.add_argument("--width", type=int, default=1920)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--depth", type=int, default=3,
                   help="batches in flight (device queue depth)")
    p.add_argument("--wire", choices=["i420", "bgr"], default="i420")
    p.add_argument(
        "--config",
        choices=["detect_classify", "detect", "action", "audio", "serve"],
        default="detect_classify",
        help="which engine program to benchmark (BASELINE.md configs: "
        "detect=1/3, detect_classify=2/5, action=4, audio=extra; "
        "serve=the REAL serving path: pipeline instances through "
        "source/runner/BatchEngine/track/metaconvert/publish)",
    )
    p.add_argument("--streams", type=int, default=64,
                   help="[serve] concurrent pipeline instances")
    p.add_argument("--serve-pipeline",
                   default="object_tracking/person_vehicle_bike",
                   help="[serve] pipeline name/version to instantiate "
                        "(the reference's detect+track+classify hot "
                        "path by default)")
    p.add_argument(
        "--serve-ingest", choices=["seed", "host", "rtsp"], default="seed",
        help="[serve] seed: stages submit per-frame uint32 seeds and "
        "engines synthesize wire batches on-chip "
        "(steps.wrap_device_synth) — the full serving path minus only "
        "the host→device pixel copy; host: real pixels "
        "host-resized+wire-encoded and transferred per batch (the "
        "deployment shape); rtsp: every stream is a LIVE camera — an "
        "in-process RTSP loopback server paces 30 fps JPEG streams "
        "into the async demux (media/demux.py), the true north-star "
        "config-5 ingest shape",
    )
    p.add_argument("--demux-workers", type=int, default=2,
                   help="[serve --serve-ingest rtsp] shared demux "
                        "decode workers")
    p.add_argument("--serve-publish", choices=["null", "file", "mqtt"],
                   default="null",
                   help="[serve] metadata destination for every stream")
    p.add_argument(
        "--stall-timeout", type=float, default=600.0,
        help="[serve] engine stall watchdog (s): how long a hung "
             "device call may burn the window before the entry fails")
    p.add_argument("--deadline-ms", type=float, default=8.0,
                   help="[serve] engine batch-fill deadline")
    p.add_argument(
        "--ingest", choices=["device", "host"], default="device",
        help="device: frames synthesized on-chip (measures the XLA "
        "program alone); host: real host->device transfer per batch "
        "(the deployment shape)",
    )
    p.add_argument("--models-dir", default=None,
                   help="serving-layout model directory (e.g. installed "
                        "via fetch-models --from-ir / --synthesize-omz) — "
                        "bench real IR-backed models instead of the zoo")
    p.add_argument("--det-model", default="object_detection/person_vehicle_bike",
                   help="registry key for the detector under --config "
                        "detect/detect_classify")
    p.add_argument("--cls-model", default="object_classification/vehicle_attributes",
                   help="registry key for the classifier under --config "
                        "detect_classify")
    p.add_argument("--precision", choices=["bf16", "int8"], default="bf16",
                   help="int8: quantized module variants on the int8 MXU "
                   "path (weights stay float; ops/qlinear.py)")
    p.add_argument("--sweep", action="store_true",
                   help="measure several (batch, depth) operating points "
                   "and report the best meeting --p99-target (the "
                   "JSON line reports the winner)")
    p.add_argument("--p99-target-ms", type=float, default=100.0,
                   help="latency bound the sweep optimizes under")
    args = p.parse_args()

    metric_name = _metric_for(args.config)

    import jax

    from evam_tpu.obs.trace import configure_compilation_cache
    from evam_tpu.parallel.mesh import require_requested_backend

    # JAX_PLATFORMS selects the backend; a CPU fallback nobody asked
    # for is a failure, not a measurement
    require_requested_backend()
    configure_compilation_cache()

    from evam_tpu.engine import steps as step_builders
    from evam_tpu.models.registry import ModelRegistry

    dev = jax.devices()[0]
    log(f"device: {dev.platform} {getattr(dev, 'device_kind', '')}")

    if args.config == "serve":
        print(json.dumps(run_serve_bench(args)))
        return 0

    registry = ModelRegistry(
        models_dir=args.models_dir,
        dtype="int8" if args.precision == "int8" else "bfloat16")
    b, h, w = args.batch, args.height, args.width
    if args.config == "detect_classify":
        det = registry.get(args.det_model)
        cls = registry.get(args.cls_model)
        step = step_builders.build_detect_classify_step(
            det, cls, wire_format=args.wire
        )
        params = {"det": det.params, "cls": cls.params}
    elif args.config == "detect":
        det = registry.get(args.det_model)
        step = step_builders.build_detect_step(det, wire_format=args.wire)
        params = det.params
    elif args.config == "action":
        enc = registry.get("action_recognition/encoder")
        step = step_builders.build_action_encode_step(
            enc, wire_format=args.wire
        )
        params = enc.params
    else:  # audio
        aud = registry.get("audio_detection/environment")
        step = step_builders.build_audio_step(aud)
        params = aud.params
        args.wire = "none"
    params = jax.device_put(params)

    input_name = "windows" if args.config == "audio" else "frames"
    wire_dtype = np.int16 if args.config == "audio" else np.uint8
    #: depth doesn't change the XLA program — cache compiled fns per
    #: batch size so the sweep pays one compile per distinct batch
    _fn_cache: dict = {}

    def measure(b: int, depth: int, seconds: float):
        """One operating point: compile, warm, run, return
        (streams, p50_ms, p99_ms, host_stage_p50_ms). The stage dict
        attributes the host-side per-batch cost (h2d_issue = time for
        device_put to enqueue the copy, h2d_wait = the blocking
        residual of that copy before launch, launch dispatch,
        readback wait) the same way the serving BatchEngine's stage
        clock does (engine/ringbuf.STAGES)."""
        put_issue_s: list[float] = []
        put_wait_s: list[float] = []
        launch_s: list[float] = []
        rb_s: list[float] = []
        if args.config == "audio":
            wire_shape = (b, 16000)  # 1 s windows at 16 kHz
        elif args.wire == "i420":
            wire_shape = (b, h * 3 // 2, w)
        else:
            wire_shape = (b, h, w, 3)

        if args.ingest == "device":
            import jax.numpy as jnp

            n_elems = int(np.prod(wire_shape))

            def seeded_step(params, seed):
                # Frames synthesized on-chip (steps.weyl_bits — the
                # shared generator): the full wire-decode + preprocess
                # + infer + NMS + classify program still runs; only
                # the host->device copy is excluded.
                bits = step_builders.weyl_bits(
                    seed.astype(jnp.uint32), n_elems)
                data = (bits >> 13).astype(jnp.dtype(wire_dtype))
                return step(params, **{input_name: data.reshape(wire_shape)})

            if b not in _fn_cache:
                _fn_cache[b] = jax.jit(seeded_step)
            fn = _fn_cache[b]
            inputs = [np.int32(0), np.int32(1)]

            def submit(i):
                t0 = time.perf_counter()
                out = fn(params, inputs[i % 2])
                launch_s.append(time.perf_counter() - t0)
                return out
        else:
            if b not in _fn_cache:
                _fn_cache[b] = jax.jit(step)
            fn = _fn_cache[b]
            rng = np.random.default_rng(0)
            # Distinct host batches so transfers aren't cached.
            host_batches = [
                rng.integers(0, 255, wire_shape).astype(wire_dtype)
                for _ in range(2)
            ]

            def submit(i):
                t0 = time.perf_counter()
                dev = jax.device_put(host_batches[i % 2])
                t1 = time.perf_counter()
                # transfer-honest split (ringbuf.STAGES): issue vs the
                # blocking residual of the copy before the launch
                jax.block_until_ready(dev)
                t2 = time.perf_counter()
                out = fn(params, **{input_name: dev})
                put_issue_s.append(t1 - t0)
                put_wait_s.append(t2 - t1)
                launch_s.append(time.perf_counter() - t2)
                return out

        t0 = time.perf_counter()
        out = submit(0)
        jax.block_until_ready(out)
        log(f"[b={b} d={depth}] compile+first step: "
            f"{time.perf_counter() - t0:.1f}s; out {out.shape} {out.dtype}")
        for i in range(3):
            jax.block_until_ready(submit(i))
        # drop warmup/compile samples from the attribution
        put_issue_s.clear(); put_wait_s.clear()
        launch_s.clear(); rb_s.clear()

        # Timed: keep `depth` batches in flight; async dispatch
        # overlaps the host->device copy of batch k+1 with compute of
        # batch k.
        inflight = []
        batches = 0
        start = time.perf_counter()
        deadline = start + seconds
        lat_samples = []
        while time.perf_counter() < deadline:
            t_sub = time.perf_counter()
            out = submit(batches)
            inflight.append((out, t_sub))
            batches += 1
            if len(inflight) >= depth:
                done, t_sub0 = inflight.pop(0)
                t_rb = time.perf_counter()
                jax.block_until_ready(done)
                rb_s.append(time.perf_counter() - t_rb)
                lat_samples.append(time.perf_counter() - t_sub0)
        for done, t_sub in inflight:
            t_rb = time.perf_counter()
            jax.block_until_ready(done)
            rb_s.append(time.perf_counter() - t_rb)
            lat_samples.append(time.perf_counter() - t_sub)
        elapsed = time.perf_counter() - start

        frames = batches * b
        fps = frames / elapsed
        # audio: a stream produces 5 windows/s (1 s window, 0.2 s
        # stride — the reference's sliding-window default), not 30
        streams = fps / (5.0 if args.config == "audio" else 30.0)
        # Effective per-frame latency through a depth-`depth` pipeline.
        p50 = float(np.percentile(lat_samples, 50)) * 1e3
        p99 = float(np.percentile(lat_samples, 99)) * 1e3
        host_stages = {
            stage: round(float(np.percentile(samples, 50)) * 1e3, 3)
            for stage, samples in (
                ("h2d_issue", put_issue_s), ("h2d_wait", put_wait_s),
                ("launch", launch_s), ("readback", rb_s),
            ) if samples
        }
        log(f"[b={b} d={depth}] {frames} frames in {elapsed:.2f}s = "
            f"{fps:.1f} FPS ({streams:.1f} x 1080p30 streams); "
            f"batch-latency p50={p50:.1f}ms p99={p99:.1f}ms "
            f"host stages {host_stages}")
        return streams, p50, p99, host_stages

    extra: dict = {}
    if args.sweep:
        points = [(512, 2), (256, 3), (128, 4), (128, 1), (64, 1), (32, 2)]
        # never push a window under 3 s (p99 over a handful of
        # batches is noise and flips the SLA gate)
        per = max(args.seconds / len(points), 3.0)
        results = [(b, d, *measure(b, d, per)) for b, d in points]
        ok = [r for r in results if r[4] <= args.p99_target_ms]
        best = max(ok or results, key=lambda r: r[2])
        b_, d_, streams, p50, p99, host_stages = best
        extra["p99_target_ms"] = args.p99_target_ms
        extra["sla_met"] = bool(ok)
        log(f"sweep winner: batch={b_} depth={d_} ({streams:.1f} streams, "
            f"p99={p99:.0f}ms, target {args.p99_target_ms:.0f}ms, "
            f"sla_met={bool(ok)})")
    else:
        streams, p50, p99, host_stages = measure(
            args.batch, args.depth, args.seconds)
        b_, d_ = args.batch, args.depth

    if args.config == "action":
        # A 30 fps action stream costs 30 encoder-frames/s AND (after
        # clip warm-up) 30 decoder-clips/s; the engines share the chip
        # serially, so combine the component rates (see _metric_for).
        enc_fps = streams * 30.0
        dec_cps = _measure_action_decoder(registry, args, b_, d_)
        streams = 1.0 / (30.0 / enc_fps + 30.0 / dec_cps)
        extra["enc_fps"] = round(enc_fps, 1)
        extra["dec_clips_per_s"] = round(dec_cps, 1)
        log(f"action combined: enc {enc_fps:.0f} fps + dec {dec_cps:.0f} "
            f"clips/s -> {streams:.1f} streams")

    print(json.dumps({
        "metric": metric_name,
        "value": round(streams, 2),
        "unit": "streams",
        "vs_baseline": round(streams / 16.0, 3),
        "batch": b_,
        "depth": d_,
        "p50_ms": round(p50, 1),
        "p99_ms": round(p99, 1),
        "host_stage_p50_ms": host_stages,
        **extra,
    }))
    return 0


def _argv_metric() -> str:
    """Metric name for the crash handler, from --config in argv."""
    cfg = "detect_classify"
    for i, a in enumerate(sys.argv):
        if a == "--config" and i + 1 < len(sys.argv):
            cfg = sys.argv[i + 1]
        elif a.startswith("--config="):
            cfg = a.split("=", 1)[1]
    return _metric_for(cfg)


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as exc:  # noqa: BLE001 — the JSON error line, then a non-zero exit
        import traceback

        traceback.print_exc(file=sys.stderr)
        sys.exit(fail_line(_argv_metric(), f"{type(exc).__name__}: {exc}"))
