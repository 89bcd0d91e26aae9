#!/usr/bin/env python
"""Live-soak drop ATTRIBUTION: where does the <10% budget actually go?

VERDICT item 5: the 64-stream live soak asserted a blanket drop rate;
a framework regression could hide inside it. This tool runs the same
live-paced loopback shape (RTSP feeders → shared async demux → stage
chain → publish) and reports EVERY loss layer separately:

* ``demux.dropped_decode``      — shared decode workers behind
  (decode-bound; the ingest layer's own ceiling);
* ``demux.dropped_downstream``  — the per-stream emit queue was full
  (runner/engine behind — backpressure working as designed);
* ``engine shed``               — QoS staleness shedding
  (evam_sched_shed_total, only with EVAM_SCHED on);
* ``publish dropped``           — destination backpressure
  (evam_publish_dropped{dest});
* ``runner errors``             — per-frame faults (injected or real).

``--null-engine`` runs the identical ingest load through the
``video_decode/app_dst`` pipeline (decode → sink, NO inference), the
decode-bound control: any drops there are pure framework/ingest
overhead, so the engine's contribution in the full run is separable
by subtraction. INGEST.md records the attribution from both modes.

The accounting gate: total demux drops must equal the sum of the two
demux layers (no unattributed loss), and with instant-decode frames
on this box the control run is expected lossless.

Prints ONE JSON line on stdout; diagnostics go to stderr.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--streams", type=int, default=16)
    p.add_argument("--fps", type=float, default=4.0)
    p.add_argument("--seconds", type=float, default=10.0,
                   help="steady-state measurement window")
    p.add_argument("--null-engine", action="store_true",
                   help="decode-bound control: video_decode/app_dst "
                        "(no inference stage) under the same load")
    p.add_argument("--max-drop-frac", type=float, default=0.10,
                   help="steady-state demux drop budget (gate)")
    args = p.parse_args()

    import os

    os.environ.setdefault("EVAM_ALLOW_RANDOM_WEIGHTS", "1")
    os.environ["JAX_PLATFORMS"] = "cpu"

    import numpy as np

    from evam_tpu.config import Settings
    from evam_tpu.engine import EngineHub
    from evam_tpu.models import ModelRegistry, ZOO_SPECS
    from evam_tpu.obs.metrics import metrics
    from evam_tpu.parallel import build_mesh
    from evam_tpu.publish.rtsp import RtspServer
    from evam_tpu.server.registry import PipelineRegistry

    small = {k: (64, 64) for k in ZOO_SPECS}
    small["audio_detection/environment"] = (1, 1600)
    narrow = {k: 8 for k in ZOO_SPECS}
    hub = EngineHub(
        ModelRegistry(dtype="float32", input_overrides=small,
                      width_overrides=narrow),
        plan=build_mesh(), max_batch=16, deadline_ms=4.0,
    )
    settings = Settings(pipelines_dir=str(REPO / "pipelines"),
                        rtsp_demux_workers=2)
    reg = PipelineRegistry(settings, hub=hub)

    pipeline = (("video_decode", "app_dst") if args.null_engine
                else ("object_tracking", "person_vehicle_bike"))
    log(f"mode: {'null-engine control' if args.null_engine else 'full'} "
        f"({'/'.join(pipeline)}), {args.streams} streams @ {args.fps} f/s")

    srv = RtspServer(port=0, host="127.0.0.1")
    srv.start()
    stop_feed = threading.Event()

    def feeder(relay, i):
        k = 0
        f = np.zeros((96, 96, 3), np.uint8)
        f[:, :, 2] = (3 * i) % 256
        while not stop_feed.is_set():
            f[:, :, 1] = (k * 5) % 256
            relay.push_bgr(f)
            k += 1
            time.sleep(1 / args.fps)

    for i in range(args.streams):
        threading.Thread(target=feeder, args=(srv.mount(f"cam{i}"), i),
                         daemon=True).start()

    def publish_drops() -> float:
        return metrics.counter_total("evam_publish_dropped")

    try:
        if not args.null_engine:
            reg.preload("object_tracking")
            for _, e in reg.hub._engines.items():
                e.warmed.wait(timeout=120)
        insts = [
            reg.start_instance(*pipeline, {
                "source": {"uri": f"rtsp://127.0.0.1:{srv.port}/cam{i}",
                           "type": "uri"},
                "destination": {"metadata": {"type": "null"}},
            })
            for i in range(args.streams)
        ]
        time.sleep(4.0)  # past the handshake storm
        demux = reg.rtsp_demux
        base = demux.stats()
        base_shed = reg.hub.shed_totals()
        base_pub = publish_drops()
        base_err = sum(i._runner.errors for i in insts if i._runner)
        t0 = time.perf_counter()
        time.sleep(args.seconds)
        elapsed = time.perf_counter() - t0
        stats = demux.stats()
        shed = reg.hub.shed_totals()

        win = {
            "decoded": stats["decoded"] - base["decoded"],
            "demux_dropped_decode":
                stats["dropped_decode"] - base["dropped_decode"],
            "demux_dropped_downstream":
                stats["dropped_downstream"] - base["dropped_downstream"],
            "engine_shed": {
                c: shed.get(c, 0) - base_shed.get(c, 0) for c in shed},
            "publish_dropped": publish_drops() - base_pub,
            "runner_errors": sum(
                i._runner.errors for i in insts if i._runner) - base_err,
        }
        states = [i.state.value for i in insts]
    finally:
        stop_feed.set()
        reg.stop_all()
        srv.stop()

    win_dropped = (win["demux_dropped_decode"]
                   + win["demux_dropped_downstream"])
    total_demux = stats["dropped_decode"] + stats["dropped_downstream"]
    accounted = stats["dropped"] == total_demux
    drop_frac = win_dropped / max(1, win["decoded"])
    alive = all(s in ("RUNNING", "QUEUED") for s in states)
    ok = bool(accounted and alive
              and drop_frac <= args.max_drop_frac
              and win["decoded"] > 0)
    log(f"window {elapsed:.1f}s: {win}")
    print(json.dumps({
        "metric": "soak_drop_attribution",
        "mode": "null_engine" if args.null_engine else "full",
        "streams": args.streams,
        "fps": args.fps,
        "window_s": round(elapsed, 1),
        **win,
        "drop_frac": round(drop_frac, 4),
        "drops_accounted": accounted,
        "all_alive": alive,
        "ok": ok,
    }))
    if not accounted:
        log("FAIL: demux total != decode-side + downstream-side drops")
    if drop_frac > args.max_drop_frac:
        log(f"FAIL: drop fraction {drop_frac:.3f} > {args.max_drop_frac}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
