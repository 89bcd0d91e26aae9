#!/usr/bin/env python
"""Async-demux throughput/scaling bench (round 5).

Measures the live-RTSP demux (media/demux.py) at N paced streams ×
M decode workers on THIS host, for both payload formats:

  * jpeg — RFC 2435 (server packetizes cv2 JPEGs)
  * h264 — RFC 6184 intra-only (server packetizes media/h264.py AUs;
    decode pays the per-AU file-shim documented in INGEST.md)

Streams are camera-paced (the server pushes at --fps); consumers
drain instantly, so drops measure the demux+decode layer itself, not
a downstream consumer. Prints ONE JSON line.

Usage: python tools/bench_demux.py [--streams 16] [--workers 2]
[--fps 10] [--seconds 8] [--codec jpeg] [--width 640] [--height 480]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path

# host-side measurement: no evam_tpu import may claim the chip
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, default=16)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--fps", type=float, default=10.0)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--codec", choices=["jpeg", "h264"], default="jpeg")
    ap.add_argument("--width", type=int, default=640)
    ap.add_argument("--height", type=int, default=480)
    args = ap.parse_args()

    import numpy as np

    from evam_tpu.media import h264
    from evam_tpu.media.demux import RtspDemux
    from evam_tpu.publish.rtsp import RtspServer

    srv = RtspServer(port=0, host="127.0.0.1")
    srv.start()
    stop = threading.Event()

    # pre-encode the payloads once: the bench charges the DEMUX side,
    # not the camera simulator
    rng = np.random.default_rng(0)
    frames = []
    bh, bw = args.height // 3, args.width // 3     # busy block, fits
    for i in range(4):
        f = np.zeros((args.height, args.width, 3), np.uint8)
        f[:, :] = (40, 30 * i, 160)
        y0 = (args.height // 8) * (i % 4)
        f[y0:y0 + bh, bw:2 * bw] = rng.integers(
            0, 255, (bh, bw, 3), np.uint8)
        frames.append(f)
    if args.codec == "h264":
        payloads = [h264.encode_frames([f]) for f in frames]
    else:
        import cv2

        payloads = [
            cv2.imencode(".jpg", f, [cv2.IMWRITE_JPEG_QUALITY, 80])[1]
            .tobytes() for f in frames
        ]

    def feeder(relay):
        # deadline pacing: sleep(1/fps) per cycle would drift the
        # offered rate below nominal (push time + 64-thread
        # contention), flattering decoded/offered comparisons
        k = 0
        next_t = time.monotonic()
        while not stop.is_set():
            if args.codec == "h264":
                relay.push_annexb(payloads[k % len(payloads)])
            else:
                relay.push_jpeg(payloads[k % len(payloads)])
            k += 1
            next_t += 1 / args.fps
            time.sleep(max(0.0, next_t - time.monotonic()))

    for i in range(args.streams):
        relay = srv.mount(f"cam{i}", codec=args.codec)
        threading.Thread(target=feeder, args=(relay,),
                         daemon=True).start()

    dmx = RtspDemux(decode_workers=args.workers)
    streams = [
        dmx.add_stream(f"rtsp://127.0.0.1:{srv.port}/cam{i}",
                       stream_id=f"s{i}")
        for i in range(args.streams)
    ]
    for s in streams:
        threading.Thread(
            target=lambda s=s: [None for _ in s.frames()],
            daemon=True).start()

    time.sleep(2.0)                       # settle
    base = dmx.stats()
    t0 = time.perf_counter()
    time.sleep(args.seconds)
    dt = time.perf_counter() - t0
    st = dmx.stats()
    stop.set()
    dmx.stop()
    srv.stop()

    decoded = st["decoded"] - base["decoded"]
    dropped = st["dropped"] - base["dropped"]
    offered = args.streams * args.fps
    out = {
        "metric": "demux_decoded_fps",
        "value": round(decoded / dt, 1),
        "unit": "frames/s aggregate",
        "codec": args.codec,
        "streams": args.streams,
        "decode_workers": args.workers,
        "threads_total": st["threads"],
        "offered_fps": offered,
        "dropped": dropped,
        "drop_frac": round(dropped / max(1, decoded + dropped), 4),
        "resolution": [args.height, args.width],
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
