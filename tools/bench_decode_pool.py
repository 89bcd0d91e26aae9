#!/usr/bin/env python
"""Decode-pool consolidation experiment (VERDICT r3 item 10).

Measures aggregate decode throughput of K concurrent 1080p file
streams two ways on THIS host:

  A. per-stream — one ``DecodeWorker`` thread per stream (the serving
     default; mirrors the reference's decodebin thread-graph-per-
     pipeline model),
  B. pooled — one shared ``DecodePool`` with M worker threads
     (``--pool-workers``) multiplexing all K streams.

Prints ONE JSON line with both aggregate fps and the pool-efficiency
factor (pooled/per-stream). The factor feeds INGEST.md's H.264
core-count extrapolation: cores_needed(pooled) =
cores_needed(per-stream) / factor. On a 1-vCPU container the factor
mostly reads GIL/scheduler overhead (expect ≈1.0); the pool's
deployment value is the thread-count bound (K+K·ffmpeg → M threads).

Usage: python tools/bench_decode_pool.py [--streams 8]
[--pool-workers 1] [--frames 90]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

# pure host-side measurement: no evam_tpu import may claim the chip
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.measure_decode import busy_frames  # noqa: E402


def make_clip(n_frames: int, codec: str = "mp4v") -> str:
    import cv2

    if codec == "h264":
        # genuine H.264 via the from-scratch intra-only generator
        # (media/h264.py) — I_PCM, so a lower bound on camera-grade
        # H.264 decode cost, but through FFmpeg's real H.264 path
        from evam_tpu.media import h264

        path = str(Path(tempfile.gettempdir()) / "pool_bench.h264")
        h264.write_annexb(path, list(busy_frames(n_frames)))
        return path
    path = str(Path(tempfile.gettempdir()) / "pool_bench.mp4")
    wr = cv2.VideoWriter(
        path, cv2.VideoWriter_fourcc(*codec), 30, (1920, 1080))
    if not wr.isOpened():
        raise RuntimeError(f"{codec} encoder unavailable")
    for f in busy_frames(n_frames):
        wr.write(f)
    wr.release()
    return path


def run_per_stream(clip: str, k: int) -> tuple[float, int]:
    from evam_tpu.media import DecodeWorker, FileSource

    counts = [0] * k

    def sink(i):
        def on_frame(ev):
            counts[i] += 1
        return on_frame

    t0 = time.perf_counter()
    workers = [
        DecodeWorker(f"s{i}", lambda: FileSource(clip),
                     on_frame=sink(i)).start()
        for i in range(k)
    ]
    for w in workers:
        while not w.finished:
            time.sleep(0.05)
    dt = time.perf_counter() - t0
    return sum(counts) / dt, sum(counts)


def run_pooled(clip: str, k: int, m: int) -> tuple[float, int]:
    from evam_tpu.media import DecodePool, FileSource

    counts = [0] * k

    def sink(i):
        def on_frame(ev):
            counts[i] += 1
        return on_frame

    pool = DecodePool(workers=m)
    t0 = time.perf_counter()
    streams = [
        pool.add_stream(f"p{i}", lambda: FileSource(clip),
                        on_frame=sink(i))
        for i in range(k)
    ]
    while not all(s.finished for s in streams):
        time.sleep(0.05)
    dt = time.perf_counter() - t0
    pool.stop()
    errors = [s.error for s in streams if s.error]
    if errors:
        raise RuntimeError(f"pooled streams failed: {errors[:3]}")
    return sum(counts) / dt, sum(counts)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, default=8)
    ap.add_argument("--pool-workers", type=int, default=1)
    ap.add_argument("--frames", type=int, default=90)
    ap.add_argument("--codec", default="mp4v",
                    help="mp4v (default) or h264 (intra-only Annex-B "
                         "from media/h264.py — real FFmpeg H.264 path)")
    args = ap.parse_args()

    clip = make_clip(args.frames, args.codec)
    expected = args.frames * args.streams
    # warm the page cache so both runs read hot
    Path(clip).read_bytes()

    fps_a, n_a = run_per_stream(clip, args.streams)
    fps_b, n_b = run_pooled(clip, args.streams, args.pool_workers)
    assert n_a == expected, (n_a, expected)
    assert n_b == expected, (n_b, expected)

    out = {
        "metric": "decode_pool_efficiency",
        "codec": args.codec,
        "streams": args.streams,
        "pool_workers": args.pool_workers,
        "frames_per_stream": args.frames,
        "per_stream_fps": round(fps_a, 1),
        "pooled_fps": round(fps_b, 1),
        "value": round(fps_b / fps_a, 3),
        "unit": "pooled/per-stream aggregate fps",
        "decode_threads_per_stream_mode": args.streams,
        "decode_threads_pooled_mode": args.pool_workers,
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
