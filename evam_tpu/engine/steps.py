"""Jitted step builders: one fused XLA program per model stage.

Each builder returns ``(step_fn, params)`` where ``step_fn(params,
**batch)`` maps a uint8 host batch to ONE packed float32 array.
Replaces the reference's per-frame OpenVINO infer requests inside
gvadetect/gvaclassify/gvaactionrecognitionbin/gvaaudiodetect
(SURVEY.md §2b) with cross-stream batched programs.

Design constraints:
* single packed output array — each extra output is one more
  device→host readback per batch, so steps never return tuples;
* everything fused — preprocess, net, decode, NMS in one jit, frames
  cross the host boundary exactly once as uint8;
* static shapes — batch size is bucketed by the caller, ROI budget
  and NMS K are fixed;
* batch inputs are positional after ``params`` and never returned.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from evam_tpu.models.registry import LoadedModel
from evam_tpu.ops.boxes import decode_boxes, yolo_gather
from evam_tpu.ops.color import crop_rois_i420
from evam_tpu.ops.nms import batched_nms
from evam_tpu.ops.preprocess import (
    crop_rois,
    decode_wire,
    preprocess_bgr,
    preprocess_wire,
)

#: Packed detection row layout: [x0, y0, x1, y1, score, label, valid]
DETECT_FIELDS = 7


def weyl_bits(seeds, n: int) -> jnp.ndarray:
    """[...]-shaped uint32 seeds → [..., n] uint32 Weyl-sequence bits.

    THE on-chip synthetic-data generator: bench.py --ingest device,
    the serve bench's device-synth mode (wrap_device_synth) and the
    action-decoder mini-measure all draw from this one recipe, so
    "same generator as the headline bench" stays true by
    construction. Plain iota arithmetic, not the PRNG.
    """
    i = jax.lax.iota(jnp.uint32, n)
    return i * jnp.uint32(2654435761) + jnp.asarray(
        seeds, jnp.uint32)[..., None]


def wrap_device_synth(step_fn, wire_shape: tuple[int, ...]) -> Callable:
    """Device-synth serving ingest: per-item uint32 seeds replace wire
    frames, and the uint8 wire batch is synthesized ON-CHIP (the same
    Weyl-sequence generator as ``bench.py --ingest device``) before the
    wrapped step runs.

    Used by ``EngineHub(device_synth=True)`` so ``bench.py --config
    serve`` can measure the REAL serving path — source →
    StreamRunner → BatchEngine dispatcher/completer → tracker →
    metaconvert → publish — without the per-frame host→device pixel
    copy. Every other byte of the serving path (threads, queues, deadline
    batching, bucket padding, readback, host postprocess) is exercised
    unchanged; only ``frames`` arrives as a [B] seed vector.
    """
    import numpy as np

    n = int(np.prod(wire_shape))

    def synth_step(params, seeds, *rest):
        b = seeds.shape[0]
        mix = seeds.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
        frames = (weyl_bits(mix, n) >> jnp.uint32(13)).astype(jnp.uint8)
        return step_fn(params, frames.reshape((b,) + tuple(wire_shape)),
                       *rest)

    return synth_step


def _head_probs(model, name: str, out) -> jnp.ndarray:
    """Per-head probabilities, honoring in-graph SoftMax of IR imports."""
    x = out[name].astype(jnp.float32)
    if model.head_is_prob.get(name, False):
        return x
    return jax.nn.softmax(x, axis=-1)


def _wire_spec(model: LoadedModel, wire_format: str):
    """Model preprocess spec bound to the step's wire format."""
    return dataclasses.replace(model.preprocess, wire_format=wire_format)


def _detect_packed(params, x, model, anchors, max_detections,
                   iou_threshold, score_threshold):
    """Preprocessed input → (packed [B,K,7], boxes). See DETECT_FIELDS."""
    out = model.forward(params, x)
    if model.detector_kind == "yolo":
        # RegionYolo-cut IR: raw grid maps, decoded here (fused) —
        # scores come out as probabilities with a background column.
        # Numeric sort: lexicographic would pair yolo_10 with head 2's
        # anchors on 11+-head models.
        keys = sorted(out, key=lambda k: int(k.rsplit("_", 1)[1]))
        if len(keys) != len(model.yolo_specs):
            raise ValueError(
                f"{len(keys)} yolo outputs vs {len(model.yolo_specs)} "
                "anchor specs — importer/model mismatch"
            )
        maps = [out[k].astype(jnp.float32) for k in keys]
        boxes, scores = yolo_gather(
            maps, model.yolo_specs,
            (model.preprocess.height, model.preprocess.width),
            model.spec.num_classes,
        )
    else:
        boxes = decode_boxes(
            out["loc"].astype(jnp.float32), anchors,
            variances=model.variances,
        )
        conf = out["conf"].astype(jnp.float32)
        # IR-imported graphs usually softmax in-graph (OMZ convention,
        # models/ir.py output_is_prob); re-softmaxing flattens scores.
        scores = conf if model.conf_is_prob else jax.nn.softmax(conf, axis=-1)
    bx, sc, lb, valid = batched_nms(
        boxes,
        scores,
        max_outputs=max_detections,
        iou_threshold=iou_threshold,
        score_threshold=score_threshold,
    )
    packed = jnp.concatenate(
        [
            bx,
            sc[..., None],
            lb[..., None].astype(jnp.float32),
            valid[..., None].astype(jnp.float32),
        ],
        axis=-1,
    )
    return packed, bx


def build_detect_step(
    model: LoadedModel,
    max_detections: int = 32,
    iou_threshold: float = 0.45,
    score_threshold: float = 0.3,
    wire_format: str = "bgr",
) -> Callable:
    """Wire-encoded uint8 frames → packed detections [B,K,7] float32."""
    anchors = jnp.asarray(model.anchors) if model.anchors is not None else None
    spec = _wire_spec(model, wire_format)

    def step(params, frames):
        x = preprocess_wire(frames, spec)
        packed, _ = _detect_packed(
            params, x, model, anchors, max_detections,
            iou_threshold, score_threshold,
        )
        return packed

    return step


def build_detect_classify_step(
    det_model: LoadedModel,
    cls_model: LoadedModel,
    max_detections: int = 32,
    roi_budget: int = 8,
    iou_threshold: float = 0.45,
    score_threshold: float = 0.3,
    wire_format: str = "bgr",
    allowed_label_ids: tuple[int, ...] | None = None,
) -> Callable:
    """Fused gvadetect+gvaclassify: ONE frame upload, ONE readback.

    The reference runs detection and classification as separate
    engines with the frame crossing the CPU pipeline between them
    (pipelines/object_classification/vehicle_attributes/
    pipeline.json:4-5); fusing them into one XLA program keeps the
    decoded frame in HBM: preprocess → SSD → NMS → on-device ROI crop
    of the top-R eligible boxes → classifier — one jit.
    ``allowed_label_ids`` is the object-class filter applied BEFORE
    ROI selection (gvaclassify filters by class first, then
    classifies — stages/infer.py _eligible), so budget slots are
    never wasted on filtered-out classes. Output
    [B, K, 7 + total_classes]: packed detections; a row's probability
    block is all-zero iff that detection was not classified
    (softmaxed blocks sum to #heads otherwise).
    """
    anchors = (jnp.asarray(det_model.anchors)
               if det_model.anchors is not None else None)
    head_total = sum(n for _, n in cls_model.spec.heads)
    cls_pre = cls_model.preprocess
    det_spec = _wire_spec(det_model, wire_format)

    def step(params, frames):
        x = preprocess_wire(frames, det_spec)
        packed, bx = _detect_packed(
            params["det"], x, det_model, anchors, max_detections,
            iou_threshold, score_threshold,
        )
        b = frames.shape[0]
        eligible = packed[..., 6] > 0.5
        if allowed_label_ids is not None:
            labels = packed[..., 5]
            ok = jnp.zeros_like(eligible)
            for lid in allowed_label_ids:
                ok = ok | (labels == float(lid))
            eligible = eligible & ok
        # Stable sort: eligible rows first, NMS score order preserved
        # within each group.
        order = jnp.argsort(
            (~eligible).astype(jnp.int32), axis=1, stable=True
        )
        roi_idx = order[:, :roi_budget]
        roi_boxes = jnp.take_along_axis(bx, roi_idx[..., None], axis=1)
        roi_ok = jnp.take_along_axis(eligible, roi_idx, axis=1)
        if wire_format == "i420":
            # Crop straight from the wire planes — the full-res float
            # BGR batch (800 MB at 1080p/32) never materializes.
            crops = crop_rois_i420(
                frames, roi_boxes, (cls_pre.height, cls_pre.width))
        else:
            crops = crop_rois(
                decode_wire(frames, wire_format), roi_boxes,
                (cls_pre.height, cls_pre.width))
        crops = crops.reshape((b * roi_budget,) + crops.shape[2:])
        cls_in = preprocess_bgr(crops, cls_pre)
        out = cls_model.forward(params["cls"], cls_in)
        probs = jnp.concatenate(
            [_head_probs(cls_model, name, out) for name, _ in cls_model.spec.heads],
            axis=-1,
        ).reshape(b, roi_budget, head_total)
        probs = probs * roi_ok[..., None]
        # Scatter each ROI's probs back onto its detection row.
        full = jnp.zeros((b, packed.shape[1], head_total), jnp.float32)
        full = full.at[jnp.arange(b)[:, None], roi_idx].set(probs)
        return jnp.concatenate([packed, full], axis=-1)

    return step


def build_classify_step(
    model: LoadedModel, roi_budget: int = 8, wire_format: str = "bgr"
) -> Callable:
    """Frames + ROI boxes → packed per-ROI head probabilities.

    ``frames`` uint8 [B,H,W,3]; ``boxes`` float32 [B,R,4] normalized
    corners (R = roi_budget, invalid rows zeroed). Output
    [B, R, total_classes] — concatenated per-head probability vectors
    (head order = model.spec.heads). ROI crop happens on-device so
    detection output never has to round-trip through the host between
    the detect and classify engines beyond the box coordinates.
    """
    preproc = model.preprocess
    forward = model.forward
    head_sizes = [n for _, n in model.spec.heads]

    def step(params, frames, boxes):
        b, r = boxes.shape[:2]
        if wire_format == "i420":
            crops = crop_rois_i420(
                frames, boxes, (preproc.height, preproc.width))
        else:
            crops = crop_rois(
                decode_wire(frames, wire_format), boxes,
                (preproc.height, preproc.width))
        crops = crops.reshape((b * r,) + crops.shape[2:])
        x = preprocess_bgr(crops, preproc)
        out = forward(params, x)  # dict head -> [B*R, n]
        probs = [_head_probs(model, name, out) for name, _ in model.spec.heads]
        packed = jnp.concatenate(probs, axis=-1)
        return packed.reshape(b, r, sum(head_sizes))

    return step


def build_classify_step_ragged(
    model: LoadedModel, roi_budget: int = 8, wire_format: str = "bgr"
) -> Callable:
    """Packed-ragged classify (EVAM_RAGGED=packed, engine/ragged.py):
    frames + a PACKED box block + segment ids → per-unit head probs.

    The dense step (`build_classify_step`) computes ``B × roi_budget``
    ROI crops whatever the frames' real region counts — on the
    serving mix most of those unit rows are per-item zero-pad (the
    invisible half of the pad tax). Here the staging ring packs every
    frame's REAL boxes end to end: ``boxes`` is ``[U, 4]``, ``seg[j]``
    names the batch row that owns packed unit j (−1 on the pad tail),
    and the step computes exactly the packed block — one fixed-shape
    program for every fill level, Ragged Paged Attention style
    (PAPERS.md).

    Masked compute: pad rows gather a clamped (valid) frame index so
    the program stays branch-free, and their outputs are zeroed by
    the validity mask. Real rows multiply by exactly 1.0, so a unit's
    output is bit-identical to the dense step's row for the same
    (frame, box) pair — the EVAM_RAGGED A/B contract. Output
    ``[U, total_classes]``; the completer scatters rows back per item
    via the sealed batch's row_len/row_offset.
    """
    preproc = model.preprocess
    forward = model.forward

    def step(params, frames, boxes, seg):
        u = boxes.shape[0]
        valid = seg >= 0
        src = jnp.clip(seg, 0, frames.shape[0] - 1)
        f = jnp.take(frames, src, axis=0)  # [U, wire...]
        if wire_format == "i420":
            crops = crop_rois_i420(
                f, boxes[:, None, :], (preproc.height, preproc.width))
        else:
            crops = crop_rois(
                decode_wire(f, wire_format), boxes[:, None, :],
                (preproc.height, preproc.width))
        crops = crops.reshape((u,) + crops.shape[2:])
        x = preprocess_bgr(crops, preproc)
        out = forward(params, x)  # dict head -> [U, n]
        probs = [_head_probs(model, name, out) for name, _ in model.spec.heads]
        packed = jnp.concatenate(probs, axis=-1)
        return packed * valid[:, None].astype(packed.dtype)

    return step


def build_action_encode_step(
    model: LoadedModel, wire_format: str = "bgr"
) -> Callable:
    """Wire-encoded uint8 frames → embeddings [B,D] float32."""
    spec = _wire_spec(model, wire_format)
    forward = model.forward

    def step(params, frames):
        x = preprocess_wire(frames, spec)
        return forward(params, x).astype(jnp.float32)

    return step


def build_action_decode_step(model: LoadedModel) -> Callable:
    """Embedding clips [B,T,D] float32 → class probabilities [B,C]."""
    forward = model.forward
    is_prob = model.out_is_prob  # IR graphs may softmax in-graph

    def step(params, clips):
        out = forward(params, clips).astype(jnp.float32)
        return out if is_prob else jax.nn.softmax(out, axis=-1)

    return step


def build_audio_step(model: LoadedModel) -> Callable:
    """int16 audio windows [B,S] → class probabilities [B,C].

    Normalization of S16LE to [-1, 1] happens on-device (the
    reference's gvaaudiodetect consumes S16LE directly,
    pipelines/audio_detection/environment/pipeline.json:5).
    """
    forward = model.forward
    is_prob = model.out_is_prob  # IR graphs may softmax in-graph

    def step(params, windows):
        x = windows.astype(jnp.float32) / 32768.0
        out = forward(params, x).astype(jnp.float32)
        return out if is_prob else jax.nn.softmax(out, axis=-1)

    return step
