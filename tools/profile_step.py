"""Cumulative-program timing of the fused detect+classify step.

Measures P1..P6 where each program adds one pipeline phase, all
consuming a seed-synthesized on-device input (like bench.py
--ingest device) so host transfer is out of the measured path, and
all reducing to a small
output so readback cost is constant. The phase cost is the delta
between consecutive rows. Produces the PROFILE.md table.
"""

from __future__ import annotations

import os as _os
_os.environ.setdefault("EVAM_ALLOW_RANDOM_WEIGHTS", "1")  # hermetic profiling tool

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench_fn(fn, seeds, iters=20, warmup=3):
    import jax

    for i in range(warmup):
        jax.block_until_ready(fn(np.int32(i)))
    t0 = time.perf_counter()
    for i in range(iters):
        out = fn(np.int32(100 + i))
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def main() -> int:
    import jax
    import jax.numpy as jnp

    from evam_tpu.engine import steps as step_builders
    from evam_tpu.models.registry import ModelRegistry
    from evam_tpu.ops.boxes import decode_boxes
    from evam_tpu.ops.preprocess import decode_wire, preprocess_bgr

    b, h, w = 32, 1080, 1920
    dev = jax.devices()[0]
    print(f"device: {dev.platform} batch={b} {h}x{w} wire=i420", flush=True)

    registry = ModelRegistry()
    det = registry.get("object_detection/person_vehicle_bike")
    cls = registry.get("object_classification/vehicle_attributes")
    anchors = jnp.asarray(det.anchors)
    det_params = jax.device_put(det.params)
    cls_params = jax.device_put(cls.params)

    wire_shape = (b, h * 3 // 2, w)
    n_elems = int(np.prod(wire_shape))

    def synth(seed):
        i = jax.lax.iota(jnp.uint32, n_elems)
        bits = i * jnp.uint32(2654435761) + seed.astype(jnp.uint32)
        return (bits >> 13).astype(jnp.uint8).reshape(wire_shape)

    rows = []

    def add(name, ms):
        prev = rows[-1][1] if rows else 0.0
        rows.append((name, ms))
        print(f"{name:44s} {ms:8.2f} ms  (+{ms - prev:6.2f})", flush=True)

    # P1 synth + decode_wire
    @jax.jit
    def p1(seed):
        return decode_wire(synth(seed), "i420").sum()

    add("P1 synth+decode_wire", bench_fn(p1, None))

    # P2 + preprocess (resize to 512)
    @jax.jit
    def p2(seed):
        x = preprocess_bgr(decode_wire(synth(seed), "i420"), det.preprocess)
        return x.astype(jnp.float32).sum()

    add("P2 +preprocess(512)", bench_fn(p2, None))

    # P3 + SSD forward
    @jax.jit
    def p3(seed):
        x = preprocess_bgr(decode_wire(synth(seed), "i420"), det.preprocess)
        out = det.forward(det_params, x)
        return out["loc"].astype(jnp.float32).sum() + out["conf"].astype(jnp.float32).sum()

    add("P3 +SSD forward", bench_fn(p3, None))

    # P4 + box decode + softmax + top_k
    @jax.jit
    def p4(seed):
        x = preprocess_bgr(decode_wire(synth(seed), "i420"), det.preprocess)
        out = det.forward(det_params, x)
        boxes = decode_boxes(out["loc"].astype(jnp.float32), anchors)
        scores = jax.nn.softmax(out["conf"].astype(jnp.float32), axis=-1)
        fg = scores[..., 1:]
        best = jnp.max(fg, axis=-1)
        top, idx = jax.lax.top_k(best, 32)
        return top.sum() + boxes.sum()

    add("P4 +decode+softmax+topk", bench_fn(p4, None))

    # P5 + NMS (full detect)
    det_step = step_builders.build_detect_step(det, wire_format="i420")

    @jax.jit
    def p5(seed):
        return det_step(det_params, synth(seed)).sum()

    add("P5 +NMS = full detect", bench_fn(p5, None))

    # P6 full fused detect+classify
    full_step = step_builders.build_detect_classify_step(
        det, cls, wire_format="i420")
    params = {"det": det_params, "cls": cls_params}

    @jax.jit
    def p6(seed):
        return full_step(params, synth(seed)).sum()

    add("P6 +crop+classify = full fused", bench_fn(p6, None))

    full_ms = rows[-1][1]
    print(f"\nper-frame: {full_ms / b:.3f} ms -> "
          f"{b / (full_ms / 1e3):.0f} FPS = "
          f"{b / (full_ms / 1e3) / 30:.1f} streams", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
