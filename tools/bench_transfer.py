#!/usr/bin/env python
"""Device-transfer pipeline microbench: EVAM_TRANSFER pipelined vs inline.

CPU-only A/B through the REAL BatchEngine (engine/batcher.py): the
same deterministic elementwise step, the same wire-shaped uint8 rows,
once with the pipelined transfer (H2D issued on the dispatcher,
launch on the launcher thread, D2H put in flight at launch) and once
with the inline serial path (H2D + launch back-to-back on the
dispatcher — the pre-pipeline behavior `EVAM_TRANSFER=inline`
preserves byte-identically).

Two assertions, both gating:

* **bit-identical outputs** — every item's result through the
  pipelined engine equals the inline engine's, byte for byte (the
  pipeline moves copies around; it must never change a number);
* **throughput parity ≥ --min-speedup** — sustained items/s,
  pipelined / inline, as the MEDIAN of per-pair ratios over
  --windows adjacent window pairs (paired + order-alternated because
  a shared-vCPU host swings single windows by ±30%; the ratio within
  a pair cancels most of that). On CPU the two modes do the same
  total host work — the pipeline overlaps DEVICE time, it does not
  remove host work — so the truthful CPU expectation is parity
  (measured 0.95-1.1x across runs on the 1-vCPU dev box, median ~1.0)
  and the gate asserts the pipeline never costs meaningful
  throughput. The overlap win itself is device-bound, which the
  per-stage attribution in the JSON line (h2d_issue / h2d_wait /
  launch / readback residual) exists to isolate on the chip.

Prints ONE JSON line on stdout; diagnostics go to stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from collections import deque
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _step(params, frames):
    # deterministic elementwise uint8 math: per-row results are
    # independent of batch composition/bucket, so the bit-identical
    # A/B holds regardless of how the two runs happened to batch
    return frames * 3 + 1


def _build_engine(mode: str, bucket: int, example: np.ndarray):
    from evam_tpu.engine.batcher import BatchEngine

    eng = BatchEngine(
        f"xfer-{mode}", _step, params=None, max_batch=bucket,
        deadline_ms=2.0, input_names=("frames",),
        stall_timeout_s=0, transfer=mode,
    )
    eng.set_example(frames=example)
    eng.warmup()  # compile every bucket before anything is timed
    return eng


def _identical(eng_a, eng_b, rows: list[np.ndarray]) -> bool:
    outs = []
    for eng in (eng_a, eng_b):
        futs = [eng.submit(frames=r) for r in rows]
        outs.append([f.result(timeout=120) for f in futs])
    return all(
        a.tobytes() == b.tobytes() for a, b in zip(outs[0], outs[1])
    )


def _drive(eng, rows: list[np.ndarray], items: int,
           feeders: int = 2) -> dict:
    """Fixed-work window: push exactly ``items`` rows through the
    engine from ``feeders`` threads (each pipelining up to 64
    in-flight futures) and clock the wall time to complete ALL of
    them; return items/s plus the per-batch stage means accumulated
    during the window (warmup batches subtracted out). Fixed work —
    rather than fixed time — keeps the two modes' windows exactly
    comparable on a noisy shared-vCPU host."""
    base_batches = eng.stats.batches
    base_clocked = eng.stats.clocked
    base_stages = dict(eng.stats.stage_seconds)
    quota = [items // feeders + (1 if k < items % feeders else 0)
             for k in range(feeders)]

    def feeder(k: int):
        inflight: deque = deque()
        for j in range(quota[k]):
            inflight.append(eng.submit(frames=rows[(k + j) % len(rows)]))
            if len(inflight) > 64:
                inflight.popleft().result(timeout=120)
        while inflight:
            inflight.popleft().result(timeout=120)

    threads = [threading.Thread(target=feeder, args=(k,), daemon=True)
               for k in range(feeders)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    elapsed = time.perf_counter() - t0

    batches = eng.stats.batches - base_batches
    clocked = eng.stats.clocked - base_clocked
    stage_ms = {
        s: round(1e3 * (eng.stats.stage_seconds.get(s, 0.0)
                        - base_stages.get(s, 0.0)) / max(clocked, 1), 3)
        for s in ("h2d_issue", "h2d_wait", "launch", "readback")
    }
    return {
        "items_per_s": round(items / elapsed, 1),
        "batches": batches,
        "occupancy": round(
            (eng.stats.items / eng.stats.batches) if eng.stats.batches
            else 0.0, 1),
        "stage_ms": stage_ms,
    }


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--bucket", type=int, default=128,
                   help="top batch bucket (the hub's serving default)")
    p.add_argument("--height", type=int, default=324,
                   help="wire row height (default: a quarter-area "
                        "432x768 I420 wire row — full serving rows "
                        "make the CPU A/B take minutes, same code "
                        "path)")
    p.add_argument("--width", type=int, default=384)
    p.add_argument("--items", type=int, default=4096,
                   help="rows pushed through each engine per window "
                        "(fixed work, default 32 full serving "
                        "buckets)")
    p.add_argument("--min-speedup", type=float, default=0.9,
                   help="fail when the median pipelined/inline "
                        "throughput ratio drops below this — the "
                        "shared-vCPU noise floor under parity (the "
                        "pipeline must never meaningfully cost "
                        "throughput; the overlap WIN is device-bound "
                        "and measured on hardware)")
    p.add_argument("--windows", type=int, default=4,
                   help="adjacent window pairs; the median per-pair "
                        "ratio gates")
    p.add_argument("--smoke", action="store_true",
                   help="CI shape: short windows, correctness gates "
                        "only (bit-identical outputs + both modes "
                        "serve); the speedup still prints but does "
                        "not gate")
    args = p.parse_args()
    if args.smoke:
        args.items = min(args.items, 1024)

    os.environ["JAX_PLATFORMS"] = "cpu"  # the CPU A/B by definition

    rng = np.random.default_rng(0)
    rows = [rng.integers(0, 255, (args.height, args.width), np.uint8)
            for _ in range(16)]
    row_mb = rows[0].nbytes / 1e6
    log(f"bucket {args.bucket}, rows {args.height}x{args.width} uint8 "
        f"({row_mb:.2f} MB each), {args.items} rows per window")

    t0 = time.perf_counter()
    eng_pipe = _build_engine("pipelined", args.bucket, rows[0])
    eng_inline = _build_engine("inline", args.bucket, rows[0])
    log(f"engines warmed in {time.perf_counter() - t0:.1f}s")

    ident_rows = [rng.integers(0, 255, (args.height, args.width),
                               np.uint8) for _ in range(48)]
    identical = _identical(eng_pipe, eng_inline, ident_rows)
    log(f"bit-identical outputs: {identical}")

    # paired windows, order alternating pair to pair, so machine
    # noise (CPU steal, GC) hits both modes of a pair alike and the
    # per-pair ratio stays comparable
    windows = max(1, args.windows) if not args.smoke else 1
    engines = {"inline": eng_inline, "pipelined": eng_pipe}
    results = {"inline": None, "pipelined": None}
    ratios = []
    for k in range(windows):
        order = (("inline", "pipelined") if k % 2 == 0
                 else ("pipelined", "inline"))
        pair = {}
        for mode in order:
            r = _drive(engines[mode], rows, args.items)
            pair[mode] = r
            prev = results[mode]
            if prev is None or r["items_per_s"] > prev["items_per_s"]:
                results[mode] = r
            log(f"[{mode}] {r['items_per_s']:.0f} items/s, "
                f"{r['batches']} batches, stages {r['stage_ms']}")
        ratios.append(pair["pipelined"]["items_per_s"]
                      / max(pair["inline"]["items_per_s"], 1e-9))
    eng_pipe.stop()
    eng_inline.stop()

    speedup = float(np.median(ratios))
    log(f"per-pair ratios {[round(r, 3) for r in ratios]} "
        f"→ median {speedup:.2f}x (best windows: inline "
        f"{results['inline']['items_per_s']:.0f}, pipelined "
        f"{results['pipelined']['items_per_s']:.0f} items/s)")

    gate = 0.0 if args.smoke else args.min_speedup
    ok = bool(identical and speedup >= gate)
    print(json.dumps({
        "metric": "transfer_pipeline_speedup",
        "value": round(speedup, 2),
        "unit": "x",
        "identical": identical,
        "ratios": [round(r, 3) for r in ratios],
        "inline": results["inline"],
        "pipelined": results["pipelined"],
        "bucket": args.bucket,
        "row_shape": [args.height, args.width],
        "smoke": bool(args.smoke),
        "ok": ok,
    }))
    if not identical:
        log("FAIL: pipelined and inline outputs differ")
    if speedup < gate:
        log(f"FAIL: pipelined throughput below inline "
            f"({speedup:.2f}x < {gate:.2f}x)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
