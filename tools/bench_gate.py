#!/usr/bin/env python
"""Motion-gate microbench: content-adaptive inference gating A/B.

CPU-only, through the REAL DetectStage + BatchEngine path
(stages/infer.py → stages/gate.py → engine/batcher.py): a
deterministic synthetic workload alternates MOVING segments (a square
relocating every frame) with STATIC segments (frozen frame), with a
majority-static mix — the temporal shape of surveillance video. The
same frames run twice: once with ``inference-interval=adaptive`` (the
motion gate) and once ungated.

Three assertions, all gating (full mode):

* **throughput uplift ≥ --min-uplift** — wall-clock frames/s through
  the stage chain, gated / ungated, as the MEDIAN of per-pair ratios
  over --windows order-alternated window pairs. The gate removes
  whole engine round-trips, so this win IS expected on CPU;
* **bounded detection staleness** — the gate never skipped more than
  ``gate-max-skip`` consecutive frames (every object re-validated
  within that bound), and every skipped frame still carried coasted
  detections;
* **EVAM_GATE=off identity** — with the kill switch set, a stage built
  WITH gate properties produces byte-identical per-frame regions to a
  stage built with none (the A/B the serving default relies on).

``--smoke`` (CI): short run, identity + staleness gate only; the
uplift still prints but does not gate.

Prints ONE JSON line on stdout; diagnostics go to stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def make_frames(n: int, static_frac: float, h: int = 96, w: int = 96,
                cycle: int = 50) -> list[np.ndarray]:
    """Deterministic majority-static workload: each ``cycle`` frames
    start with a moving burst (square relocating every frame) and then
    freeze. Returned frames are reused across runs so both A/B sides
    hash the exact same pixels."""
    moving_len = max(1, int(round(cycle * (1.0 - static_frac))))
    frames = []
    base = np.full((h, w, 3), 18, np.uint8)
    sq = 24
    x = y = 0
    for i in range(n):
        if i % cycle < moving_len:
            x = (x + 17) % (w - sq)
            y = (y + 11) % (h - sq)
        f = base.copy()
        f[y:y + sq, x:x + sq] = (64, 160, 240)
        frames.append(f)
    return frames


def build_hub():
    from evam_tpu.engine import EngineHub
    from evam_tpu.models import ModelRegistry, ZOO_SPECS
    from evam_tpu.parallel import build_mesh

    small = {k: (64, 64) for k in ZOO_SPECS}
    small["audio_detection/environment"] = (1, 1600)
    narrow = {k: 8 for k in ZOO_SPECS}
    registry = ModelRegistry(dtype="float32", input_overrides=small,
                             width_overrides=narrow)
    return EngineHub(registry, plan=build_mesh(), max_batch=16,
                     deadline_ms=2.0)


MODEL = "object_detection/person_vehicle_bike"


def run_stream(hub, frames, props, collect=False):
    """Drive the frames through a fresh DetectStage (shared warm
    engine) on a StreamRunner; return (elapsed_s, stage, outputs).
    ``outputs`` is the per-frame serialized region payload when
    ``collect`` (identity/staleness checks), else None."""
    from evam_tpu.media.source import FrameEvent
    from evam_tpu.stages.base import Stage
    from evam_tpu.stages.infer import DetectStage
    from evam_tpu.stages.runner import StreamRunner

    stage = DetectStage("detection", MODEL, dict(props), hub)
    outs: list[bytes] = []

    class Collect(Stage):
        name = "collect"

        def process(self, ctx):
            rows = np.asarray(
                [[r.x0, r.y0, r.x1, r.y1, r.confidence, r.label_id]
                 for r in ctx.regions], np.float32)
            outs.append(rows.tobytes())
            return [ctx]

    stages = [stage] + ([Collect()] if collect else [])
    runner = StreamRunner("bench-gate", stages)
    events = (FrameEvent(frame=f, pts_ns=i, seq=i)
              for i, f in enumerate(frames))
    t0 = time.perf_counter()
    runner.run(events)
    elapsed = time.perf_counter() - t0
    assert runner.frames_out == len(frames), (
        runner.frames_out, runner.errors)
    return elapsed, stage, outs if collect else None


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--frames", type=int, default=400,
                   help="frames per measured window")
    p.add_argument("--static-frac", type=float, default=0.8,
                   help="fraction of each segment cycle that is static "
                        "(the majority-static surveillance shape)")
    p.add_argument("--max-skip", type=int, default=8,
                   help="gate-max-skip: the detection staleness bound")
    p.add_argument("--min-uplift", type=float, default=1.5,
                   help="fail when the median gated/ungated throughput "
                        "ratio drops below this (full mode)")
    p.add_argument("--windows", type=int, default=3,
                   help="order-alternated A/B window pairs; median "
                        "per-pair ratio gates")
    p.add_argument("--smoke", action="store_true",
                   help="CI shape: short run, identity + staleness "
                        "gates only; uplift prints but does not gate")
    args = p.parse_args()
    if args.smoke:
        args.frames = min(args.frames, 150)
        args.windows = 1

    os.environ.setdefault("EVAM_ALLOW_RANDOM_WEIGHTS", "1")
    os.environ.pop("EVAM_GATE", None)  # props drive the A/B below
    os.environ["JAX_PLATFORMS"] = "cpu"  # the CPU A/B by definition

    frames = make_frames(args.frames, args.static_frac)
    log(f"{args.frames} frames, static fraction {args.static_frac}, "
        f"max_skip {args.max_skip}")

    gated_props = {"threshold": 0.2, "inference-interval": "adaptive",
                   "gate-max-skip": args.max_skip}
    plain_props = {"threshold": 0.2}

    hub = build_hub()
    try:
        t0 = time.perf_counter()
        _, warm_stage, _ = run_stream(hub, frames[:8], plain_props)
        warm_stage.engine.warmup()  # compile every bucket pre-timing
        log(f"engine warmed in {time.perf_counter() - t0:.1f}s")

        # ---- correctness: staleness bound + coasted detections on a
        # collected gated run
        _, gstage, gouts = run_stream(hub, frames, gated_props,
                                      collect=True)
        snap = gstage.gate.snapshot()
        log(f"gated run: {snap}")
        stale_ok = snap["max_consecutive_skips"] <= args.max_skip
        # every frame after the first inference must carry detections
        # (real or coasted) — a skip must never publish an empty frame
        # while an object is in scene
        coasted_ok = all(len(o) > 0 for o in gouts[1:])
        skip_rate = snap["skip_rate"]

        # ---- identity: EVAM_GATE=off + gate props == no gate props
        os.environ["EVAM_GATE"] = "off"
        try:
            _, _, off_outs = run_stream(hub, frames, gated_props,
                                        collect=True)
            _, _, plain_outs = run_stream(hub, frames, plain_props,
                                          collect=True)
        finally:
            os.environ.pop("EVAM_GATE", None)
        identical = off_outs == plain_outs
        log(f"EVAM_GATE=off identity: {identical}")

        # ---- throughput: paired, order-alternated windows
        ratios = []
        best = {"gated": 0.0, "ungated": 0.0}
        for k in range(max(1, args.windows)):
            order = (("ungated", "gated") if k % 2 == 0
                     else ("gated", "ungated"))
            pair = {}
            for mode in order:
                props = gated_props if mode == "gated" else plain_props
                dt, _, _ = run_stream(hub, frames, props)
                fps = len(frames) / dt
                pair[mode] = fps
                best[mode] = max(best[mode], fps)
                log(f"[{mode}] {fps:.0f} frames/s")
            ratios.append(pair["gated"] / max(pair["ungated"], 1e-9))
    finally:
        hub.stop()

    uplift = float(np.median(ratios))
    log(f"per-pair ratios {[round(r, 3) for r in ratios]} "
        f"→ median {uplift:.2f}x")

    perf_gate = 0.0 if args.smoke else args.min_uplift
    ok = bool(identical and stale_ok and coasted_ok
              and skip_rate > 0.3 and uplift >= perf_gate)
    print(json.dumps({
        "metric": "gate_engine_uplift",
        "value": round(uplift, 2),
        "unit": "x",
        "identical": identical,
        "skip_rate": skip_rate,
        "max_consecutive_skips": snap["max_consecutive_skips"],
        "max_skip": args.max_skip,
        "staleness_bounded": stale_ok,
        "coasted_frames_nonempty": coasted_ok,
        "ratios": [round(r, 3) for r in ratios],
        "gated_fps": round(best["gated"], 1),
        "ungated_fps": round(best["ungated"], 1),
        "frames": args.frames,
        "static_frac": args.static_frac,
        "smoke": bool(args.smoke),
        "ok": ok,
    }))
    if not identical:
        log("FAIL: EVAM_GATE=off does not reproduce the ungated outputs")
    if not stale_ok:
        log(f"FAIL: staleness bound violated "
            f"({snap['max_consecutive_skips']} > {args.max_skip})")
    if not coasted_ok:
        log("FAIL: a skipped frame published no detections")
    if skip_rate <= 0.3:
        log(f"FAIL: gate barely engaged (skip rate {skip_rate})")
    if uplift < perf_gate:
        log(f"FAIL: uplift {uplift:.2f}x < {perf_gate:.2f}x")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
