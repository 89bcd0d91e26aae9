"""The comparison that decides ``correct`` for the SSD configuration.

Tolerances, and why they are what they are. The served path computes in
bfloat16 from an I420 wire frame (8-bit fixed-point resize, chroma at half
resolution); the reference in float32 from the BGR frame. Under seeded
random weights the softmax scores of all 40 960 anchors lie within a few
thousandths of each other, so WHICH 32 anchors win the top-k, and which of
two overlapping ones survives suppression, turns on rounding: the set of
published boxes cannot be compared one to one. What can be: every published
box must BE one of the reference's candidates (an anchor decoded with the
reference's own arithmetic) with the same class and coordinates within
BOX_TOL; its score must agree within SCORE_TOL, and the MEDIAN disagreement
over a frame's objects within SCORE_MEDIAN_TOL; the published set must
itself obey the published semantics (best first, over the threshold, no
same-class pair over the IoU limit, at most K); and the best published
score must reach the reference's best.

Measured on the chip (PR 23, first cut: the registry's 512x512 net, 84
objects of 12 frames): boxes differ by at most 0.0014; 82 scores differ by
0.0003-0.0005 (bfloat16 against float32) and the two whose receptive field
holds the bright square's edge by 0.009 and 0.010 (0.014 once in 72
frames): that is the wire's chroma subsampling and fixed-point resize, not
the net. The IR-served 1024x1024 net passed the same limits in every run of
PR 23's review pass (8 frames of 32 objects a run). Hence a wide per-object limit and a
tight median: a step that dropped a layer, mis-decoded boxes, ran the wrong
class map or computed in 8 bits moves EVERY score, and the median catches
it at 0.003.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import ssd_plain as ref

BOX_TOL = 0.006          # normalised; one /8-level anchor step is 0.0078
SCORE_TOL = 0.03         # absolute, one object (pixel-path departures)
SCORE_MEDIAN_TOL = 0.003  # absolute, median over a frame's objects
IOU_SLACK = 0.03


def box_of(obj: dict) -> list[float]:
    bb = obj["detection"]["bounding_box"]
    return [bb["x_min"], bb["y_min"], bb["x_max"], bb["y_max"]]


def boxes_of(objects: list[dict]) -> np.ndarray:
    return np.asarray([box_of(o) for o in objects],
                      np.float64).reshape(-1, 4)


def check_schema(msg: dict) -> str | None:
    """The reference's metadata schema (charts/README.md sample)."""
    for key, kind in (("objects", list), ("resolution", dict),
                      ("source", str), ("timestamp", int)):
        if not isinstance(msg.get(key), kind):
            return f"key {key!r} missing or not {kind.__name__}"
    res = msg["resolution"]
    if not all(isinstance(res.get(k), int) for k in ("height", "width")):
        return "resolution lacks integer height/width"
    for obj in msg["objects"]:
        det = obj.get("detection")
        if not isinstance(det, dict):
            return "object lacks detection"
        bb = det.get("bounding_box")
        if not isinstance(bb, dict) or not all(
                isinstance(bb.get(k), (int, float))
                for k in ("x_min", "y_min", "x_max", "y_max")):
            return "detection lacks a numeric bounding_box"
        if not isinstance(det.get("confidence"), (int, float)):
            return "detection lacks confidence"
        if not isinstance(det.get("label_id"), int) or not isinstance(
                det.get("label"), str):
            return "detection lacks label/label_id"
        if not all(isinstance(obj.get(k), int) for k in "xywh"):
            return "object lacks integer x/y/w/h"
        if obj.get("roi_type") != det["label"]:
            return "roi_type differs from the detection's label"
    return None


def compare_detections(objects: list[dict], cand_boxes: np.ndarray,
                       cand_scores: np.ndarray, cand_labels: np.ndarray,
                       threshold: float, iou_thr: float, top_k: int
                       ) -> list[str]:
    problems = []
    served = boxes_of(objects)
    conf = np.asarray([o["detection"]["confidence"] for o in objects])
    lab = np.asarray([o["detection"]["label_id"] for o in objects])
    if len(objects) > top_k:
        problems.append(f"{len(objects)} objects, more than K={top_k}")
    if len(conf) and conf.min() < threshold:
        problems.append(f"confidence {conf.min():.4f} under the threshold")
    if np.any(np.diff(conf) > 1e-6):
        problems.append("objects are not ordered best first")
    diffs = []
    for i in range(len(objects)):
        dist = np.abs(cand_boxes - served[i]).max(axis=1)
        dist[cand_labels != lab[i]] = np.inf
        j = int(dist.argmin())
        if dist[j] > BOX_TOL:
            problems.append(
                f"object {i} (label {lab[i]}) is no reference candidate: "
                f"the nearest anchor of its class differs by {dist[j]:.4f} "
                "in box")
        else:
            diffs.append(abs(conf[i] - cand_scores[j]))
            if diffs[-1] > SCORE_TOL:
                problems.append(
                    f"object {i}: score {conf[i]:.4f} against the "
                    f"reference's {cand_scores[j]:.4f} for the same anchor")
        same = [j for j in range(i) if lab[j] == lab[i]]
        if same and ref.iou_one_to_many(
                served[i], served[same]).max() > iou_thr + IOU_SLACK:
            problems.append(f"object {i} overlaps a better one of its class "
                            "beyond the IoU limit")
    if diffs and float(np.median(diffs)) > SCORE_MEDIAN_TOL:
        problems.append(
            f"scores differ from the reference's by {np.median(diffs):.4f} "
            "in the median")
    best = float(cand_scores.max())
    if best >= threshold + SCORE_TOL and (
            not len(conf) or conf.max() < best - SCORE_TOL):
        problems.append(
            f"best published score {conf.max() if len(conf) else None} "
            f"does not reach the reference's best {best:.4f}")
    return problems
