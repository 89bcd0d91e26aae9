#!/usr/bin/env python
"""Tracing-overhead gate: EVAM_TRACE on vs off through a real engine.

Two properties hold or the exit code says so:

1. **Off-identity** — with ``EVAM_TRACE=off`` every hook is a
   memoized-None check and the engine's outputs are BIT-IDENTICAL to
   the traced run (tracing observes, it never perturbs compute). Same
   discipline as EVAM_GATE A/B.
2. **Overhead** — with sampling on (default 1-in-16 retention), the
   sustained submit->result throughput delta stays within
   ``--max-overhead`` (3% by default) of the off path.

CPU-only (JAX_PLATFORMS=cpu works), runs in seconds; ``--smoke`` is
the CI shape. Prints ONE JSON line on stdout; diagnostics on stderr.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _set_mode(mode: str) -> None:
    """Flip EVAM_TRACE and drop every memo that captured it."""
    os.environ["EVAM_TRACE"] = mode
    from evam_tpu.config.settings import reset_settings
    from evam_tpu.obs import trace

    reset_settings()
    trace.reset_cache()


def run_mode(mode: str, frames: int, reps: int,
             batch: int) -> tuple[float, str]:
    """(median frames/s, output checksum) for one EVAM_TRACE mode.
    A fresh engine per call so neither mode inherits warm state."""
    _set_mode(mode)
    from evam_tpu.engine.batcher import BatchEngine
    from evam_tpu.obs import trace

    eng = BatchEngine(
        f"bench-trace-{mode}", lambda p, x: (x * 2.0 + 1.0),
        params={}, max_batch=batch, input_names=("x",), deadline_ms=2.0)
    rng = np.random.default_rng(0)
    rows = [rng.standard_normal((64,)).astype(np.float32)
            for _ in range(frames)]
    digest = hashlib.sha256()
    rates = []
    try:
        # warmup rep compiles the bucket ladder out of the timing
        for rep in range(reps + 1):
            t0 = time.perf_counter()
            futs = []
            for i, row in enumerate(rows):
                ft = trace.start_frame("bench", rep * frames + i,
                                       "standard")
                futs.append((ft, eng.submit(trace=ft, x=row)))
            for ft, fut in futs:
                out = np.asarray(fut.result(timeout=60))
                if rep == 1:
                    digest.update(out.tobytes())
                trace.finish_frame(ft, "ok")
            if rep > 0:
                rates.append(frames / (time.perf_counter() - t0))
    finally:
        eng.stop()
    return float(np.median(rates)), digest.hexdigest()


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="CI shape: fewer frames/reps, same gates")
    p.add_argument("--frames", type=int, default=400)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--max-overhead", type=float, default=0.03,
                   help="max throughput loss with tracing on (3%%)")
    args = p.parse_args()
    if args.smoke:
        args.frames, args.reps = min(args.frames, 200), min(args.reps, 3)

    log(f"{args.frames} frames x {args.reps} reps, bucket {args.batch}")
    off_fps, off_sum = run_mode("off", args.frames, args.reps, args.batch)
    on_fps, on_sum = run_mode("on", args.frames, args.reps, args.batch)
    # leave the process memo as the env found it before this tool ran
    overhead = (off_fps - on_fps) / off_fps if off_fps > 0 else 0.0
    identical = off_sum == on_sum
    log(f"off {off_fps:.0f} f/s, on {on_fps:.0f} f/s "
        f"-> overhead {overhead * 100:.2f}%  identity={identical}")

    ok = identical and overhead <= args.max_overhead
    print(json.dumps({
        "metric": "trace_overhead",
        "value": round(overhead, 4),
        "unit": "fraction",
        "off_fps": round(off_fps, 1),
        "on_fps": round(on_fps, 1),
        "identical_outputs": identical,
        "max_overhead": args.max_overhead,
        "ok": ok,
    }))
    if not identical:
        log("FAIL: EVAM_TRACE=on changed the engine outputs")
        return 1
    if overhead > args.max_overhead:
        log(f"FAIL: tracing overhead {overhead * 100:.2f}% > "
            f"{args.max_overhead * 100:.1f}%")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
