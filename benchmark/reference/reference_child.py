"""Child process of the correctness check: runs the plain reference on the
CPU (``JAX_PLATFORMS=cpu``; the server holds the chip, and this is a check
of answers, not of speed) over a seeded sample of published frames.

  python benchmark/reference/reference_child.py job.json result.json

The job holds the configuration's ``shapes`` and ``reference`` blocks, the
paths of the installed IR (``model.xml``/``model.bin``: the weights are
data, read here by offset and name) and, per sampled frame, the stream's
synthetic-source parameters, the frame's sequence number and the published
message. Nothing of the program is imported.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
os.environ["JAX_PLATFORMS"] = "cpu"

#: frames per forward pass: a 1024x1024 float32 pass holds ~1 GB a frame
CHUNK = 2


def main() -> int:
    import numpy as np
    import jax.numpy as jnp

    from benchmark.reference import compare
    from benchmark.reference import ssd_plain as ref

    job = json.loads(Path(sys.argv[1]).read_text())
    cfg, shapes = job["reference"], job["shapes"]
    ih, iw = shapes["detector_input_hw"]
    weights = ref.read_ir_weights(job["model_xml"], job["model_bin"])
    anchors = ref.make_anchors(shapes)
    frames = job["frames"]

    out = []
    for lo in range(0, len(frames), CHUNK):
        part = frames[lo:lo + CHUNK]
        x = np.stack([
            ref.resize_bilinear(
                ref.synth_frame(f["width"], f["height"], f["seed"], f["seq"]),
                ih, iw) for f in part])
        loc, scores = ref.ssd_forward(weights, jnp.asarray(x), shapes)
        loc, scores = np.asarray(loc), np.asarray(scores)
        for i, f in enumerate(part):
            msg = f["message"]
            bad = compare.check_schema(msg)
            if bad:
                out.append({"stream": f["stream"], "seq": f["seq"],
                            "problems": [f"schema: {bad}"]})
                continue
            fg = scores[i][:, 1:]
            best = fg.max(axis=1)
            labels = fg.argmax(axis=1) + 1
            boxes = ref.decode(loc[i], anchors, shapes["variances"])
            problems = compare.compare_detections(
                msg["objects"], boxes, best, labels, cfg["threshold"],
                cfg["iou_threshold"], shapes["max_detections"])
            kept = [k for k in ref.greedy_nms(
                boxes, best, labels, shapes["max_detections"],
                cfg["iou_threshold"], cfg["score_floor"])
                if best[k] >= cfg["threshold"]]
            served = compare.boxes_of(msg["objects"])
            shared = sum(
                1 for k in kept if len(served) and
                np.abs(served - boxes[k]).max(axis=1).min() <= compare.BOX_TOL)
            out.append({"stream": f["stream"], "seq": f["seq"],
                        "problems": problems,
                        "objects": len(msg["objects"]),
                        "reference_objects": len(kept),
                        "shared_with_reference_nms": shared,
                        "reference_best_score": float(best.max())})
    Path(sys.argv[2]).write_text(json.dumps({
        "ok": all(not r["problems"] for r in out),
        "frames": out,
        "tolerances": {"box": compare.BOX_TOL, "score": compare.SCORE_TOL,
                       "score_median": compare.SCORE_MEDIAN_TOL},
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
