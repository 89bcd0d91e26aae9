#!/usr/bin/env python
"""Ragged-batching microbench: EVAM_RAGGED packed vs off (the pad tax).

CPU-only A/B through the REAL EngineHub + BatchEngine + classify
steps (engine/ragged.py, steps.build_classify_step[_ragged]): a
deliberately heterogeneous stream mix — two classify engines at
MIXED ingest resolutions (the bucket-fragmentation half of the pad
tax) fed items with RAGGED per-frame region counts drawn from a
skewed surveillance-like distribution, zero-region frames included
(the interior-padding half). The same frames and boxes run twice:
once packed (masked region packing + consolidated bucket ladder) and
once through today's dense bucketed path.

Four assertions, all gating (full mode):

* **bit-identical outputs** — every item's packed result rows equal
  the dense path's first ``k`` rows, byte for byte ("equal accuracy"
  is checked, not assumed: packing moves rows, it must never change
  a number);
* **occupancy-weighted throughput ≥ --min-ratio (1.0)** — real unit
  rows (regions) classified per second, packed / off, as the MEDIAN
  of per-pair ratios over --windows order-alternated window pairs.
  Pad rows are not useful
  work, so units/s is the honest rate; the CPU gate is parity-plus —
  the masking overhead (per-unit frame gather + seg mask) must not
  eat the computed-rows saving. The full win is device-bound (fewer
  unit rows = fewer FLOPs AND fewer programs);
* **mean unit occupancy strictly higher** — EngineStats'
  units/unit_slots (the honest fill the dense n/bucket number
  hides) must rise under packing;
* **compiled-program count strictly lower** — after warming every
  bucket on both engines, the consolidated ladder must have compiled
  fewer programs than the dense ladder (the "compile-cache entries
  drop" claim, measured via EngineStats.compiled_programs).

``--smoke`` (CI): short run; identity + occupancy + program-count
gate, the throughput ratio prints but does not gate.

Prints ONE JSON line on stdout; diagnostics go to stderr.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


#: skewed per-frame region counts: mostly 1-3 of the 8-slot budget,
#: the occasional empty and the occasional full frame — the already-
#: ragged shape the classifier sees behind a detector
REGION_MIX = (1, 2, 0, 3, 1, 2, 8, 1, 4, 2, 1, 0, 2, 5, 1, 3)

MODEL_A = "object_classification/vehicle_attributes"
MODEL_B = "emotion_recognition/1"


def _build_hub(ragged: str, sizes: dict[str, tuple[int, int]],
               max_batch: int):
    from evam_tpu.engine.hub import EngineHub
    from evam_tpu.models import ModelRegistry, ZOO_SPECS

    overrides = {k: (64, 64) for k in ZOO_SPECS}
    overrides["audio_detection/environment"] = (1, 1600)
    overrides.update(sizes)
    registry = ModelRegistry(
        dtype="float32", input_overrides=overrides,
        width_overrides={k: 8 for k in ZOO_SPECS})
    return EngineHub(
        registry, plan=None, max_batch=max_batch, deadline_ms=2.0,
        supervise=False, stall_timeout_s=0, ragged=ragged)


def _engines(hub):
    """The heterogeneous pair: two classify engines at different wire
    resolutions (mixed-resolution fleets fragment buckets — each
    engine pays its own ladder)."""
    a = hub.engine("classify", MODEL_A, roi_budget=8)
    b = hub.engine("classify", MODEL_B, roi_budget=8)
    return a, b


def make_items(n: int, hw: tuple[int, int], seed: int = 7):
    """Deterministic (wire_frame, boxes[k,4], k) items, reused across
    both sides so the A/B hashes the exact same pixels and boxes."""
    from evam_tpu.ops.color import wire_shape

    rng = np.random.default_rng(seed)
    ws = tuple(wire_shape("i420", *hw))
    items = []
    for i in range(n):
        k = REGION_MIX[i % len(REGION_MIX)]
        frame = rng.integers(0, 255, ws, np.uint8)
        boxes = np.sort(
            rng.random((k, 2, 2)).astype(np.float32), axis=1
        ).reshape(k, 4)
        items.append((frame, boxes, k))
    return items


def _submit_all(eng, items, packed: bool, budget: int = 8):
    futs = []
    for frame, boxes, k in items:
        if packed:
            bx = boxes
        else:
            bx = np.zeros((budget, 4), np.float32)
            bx[:k] = boxes
        futs.append(eng.submit(units=k, frames=frame, boxes=bx))
    return [f.result(timeout=120) for f in futs]


def _identical(out_off, out_pk, items) -> bool:
    for (_, _, k), od, op in zip(items, out_off, out_pk):
        if op.shape[0] != k:
            log(f"packed row count {op.shape[0]} != {k}")
            return False
        if not np.array_equal(od[:k], op):
            log(f"output mismatch at k={k}: "
                f"max|Δ|={np.max(np.abs(od[:k] - op))}")
            return False
    return True


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--items", type=int, default=192,
                   help="items per timed window")
    p.add_argument("--windows", type=int, default=5,
                   help="paired (off, packed) windows")
    p.add_argument("--max-batch", type=int, default=16)
    p.add_argument("--min-ratio", type=float, default=1.0,
                   help="gate: median packed/off units-per-second")
    p.add_argument("--smoke", action="store_true",
                   help="CI mode: short run, throughput ratio "
                        "informational only")
    args = p.parse_args()
    if args.smoke:
        args.items = min(args.items, 96)
        args.windows = 2

    import os

    os.environ.setdefault("EVAM_ALLOW_RANDOM_WEIGHTS", "1")

    sizes = {MODEL_A: (64, 64), MODEL_B: (96, 96)}
    log("building dense (off) and packed hubs ...")
    hub_off = _build_hub("off", sizes, args.max_batch)
    hub_pk = _build_hub("packed", sizes, args.max_batch)
    try:
        eng_off, eng_off_b = _engines(hub_off)
        eng_pk, eng_pk_b = _engines(hub_pk)
        items = make_items(args.items, sizes[MODEL_A])
        items_b = make_items(max(16, args.items // 4), sizes[MODEL_B],
                             seed=11)

        # warm every bucket on every engine: the compile bill is the
        # consolidation claim, and nothing below should time a compile
        for eng, ex in ((eng_off, items[0]), (eng_pk, items[0]),
                        (eng_off_b, items_b[0]), (eng_pk_b, items_b[0])):
            frame, boxes, _ = ex
            eng.set_example(frames=frame,
                            boxes=np.zeros((8, 4), np.float32))
            t0 = time.perf_counter()
            eng.warmup()
            log(f"warmed {eng.name} ragged={eng.ragged} "
                f"buckets={eng.buckets} in "
                f"{time.perf_counter() - t0:.1f}s")
        programs_off = (eng_off.stats.compiled_programs
                        + eng_off_b.stats.compiled_programs)
        programs_pk = (eng_pk.stats.compiled_programs
                       + eng_pk_b.stats.compiled_programs)

        log("identity pass (packed rows == dense rows, bit for bit)")
        out_off = _submit_all(eng_off, items, packed=False)
        out_pk = _submit_all(eng_pk, items, packed=True)
        identical = _identical(out_off, out_pk, items)
        # the mixed-resolution engine too (smaller set)
        out_off_b = _submit_all(eng_off_b, items_b, packed=False)
        out_pk_b = _submit_all(eng_pk_b, items_b, packed=True)
        identical = identical and _identical(out_off_b, out_pk_b,
                                             items_b)

        units = sum(k for _, _, k in items)
        ratios = []
        sides = {"off": 0.0, "packed": 0.0}
        for w in range(args.windows):
            order = (("off", "packed") if w % 2 == 0
                     else ("packed", "off"))
            pair = {}
            for side in order:
                eng = eng_off if side == "off" else eng_pk
                t0 = time.perf_counter()
                _submit_all(eng, items, packed=(side == "packed"))
                dt = time.perf_counter() - t0
                pair[side] = units / dt
                sides[side] += units / dt
            ratios.append(pair["packed"] / pair["off"])
            log(f"window {w}: off={pair['off']:.0f} u/s "
                f"packed={pair['packed']:.0f} u/s "
                f"ratio={ratios[-1]:.3f}")
        ratio = statistics.median(ratios)
        occ_off = eng_off.stats.unit_occupancy
        occ_pk = eng_pk.stats.unit_occupancy
    finally:
        hub_off.stop()
        hub_pk.stop()

    perf_gate = 0.0 if args.smoke else args.min_ratio
    ok_perf = ratio >= perf_gate
    ok_occ = occ_pk > occ_off
    ok_programs = programs_pk < programs_off
    ok = identical and ok_perf and ok_occ and ok_programs
    print(json.dumps({
        "metric": "ragged_units_per_s_ratio",
        "value": round(ratio, 3),
        "unit": "x (packed/off, median of paired windows)",
        "vs_baseline": round(ratio, 3),
        "identical_outputs": identical,
        "unit_occupancy_off": round(occ_off, 4),
        "unit_occupancy_packed": round(occ_pk, 4),
        "compiled_programs_off": programs_off,
        "compiled_programs_packed": programs_pk,
        "units_per_s_off": round(sides["off"] / args.windows, 1),
        "units_per_s_packed": round(sides["packed"] / args.windows, 1),
        "items_per_window": args.items,
        "windows": args.windows,
        "min_ratio": args.min_ratio,
        "smoke": bool(args.smoke),
        "ok": ok,
    }))
    if not identical:
        log("FAIL: packed outputs differ from the dense path")
    if not ok_occ:
        log(f"FAIL: packed unit occupancy {occ_pk:.3f} not above "
            f"dense {occ_off:.3f}")
    if not ok_programs:
        log(f"FAIL: packed compiled {programs_pk} programs, dense "
            f"{programs_off} — consolidation didn't shrink the cache")
    if not ok_perf:
        log(f"FAIL: packed/off units-per-second ratio {ratio:.3f} "
            f"below {perf_gate}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
