"""Engine-backed inference stages: detect, classify, action, audio.

These are the TPU counterparts of the reference's gvadetect /
gvaclassify / gvaactionrecognitionbin / gvaaudiodetect elements
(SURVEY.md §2b), sharing per-model BatchEngines across streams
(model-instance-id semantics) instead of owning per-stream OpenVINO
requests.

Thresholds are applied host-side on the packed engine output so
engines stay shareable between pipelines with different ``threshold``
parameters (the engine's in-jit NMS uses a permissive floor).
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from concurrent.futures import Future

import numpy as np

from evam_tpu.engine.hub import EngineHub
from evam_tpu.models.zoo.action import CLIP_LEN
from evam_tpu.obs import get_logger, metrics, trace
from evam_tpu.stages.base import AsyncStage
from evam_tpu.stages.context import FrameContext, Region, Tensor
from evam_tpu.stages.gate import maybe_gate
from evam_tpu.stages.track import RegionCoaster

log = get_logger("stages.infer")

#: floor baked into the shared engine's NMS; per-stage thresholds
#: filter above this.
ENGINE_SCORE_FLOOR = 0.1


def _wire_safe_size(size: tuple[int, int]) -> tuple[int, int]:
    """Round an ingest (H, W) up to the I420 wire constraint
    (ops.color.i420_shape: height%4, width%2) so user-set sizes like
    430x768 can't break the planar encoding."""
    h, w = int(size[0]), int(size[1])
    return (-(-h // 4) * 4, -(-w // 2) * 2)


def _resize_for_engine(frame: np.ndarray, size: tuple[int, int]) -> np.ndarray:
    """Host-side resize to the engine's canonical ingest resolution so
    frames from heterogeneous streams stack into one batch."""
    h, w = size
    if frame.shape[0] == h and frame.shape[1] == w:
        return frame
    from evam_tpu import native

    return native.resize_bgr(frame, h, w)


def _encode_wire(frame_bgr: np.ndarray, wire_format: str) -> np.ndarray:
    """Host-side wire encoding (decode-thread side of ops.color)."""
    if wire_format == "i420":
        from evam_tpu import native

        return native.bgr_to_i420(frame_bgr)
    return np.ascontiguousarray(frame_bgr)


#: per-process frame-seed sequence for device_synth mode (the GIL makes
#: itertools.count().__next__ atomic enough for distinct seeds)
_SYNTH_SEQ = itertools.count()


def _timed_gate_decide(gate, ctx: FrameContext) -> bool:
    """Run the motion gate's decision with a "gate.decide" span on the
    frame trace (the gate verdict rides as an attr so a skipped
    frame's tree explains itself)."""
    if ctx.trace is None:
        return gate.decide(ctx.frame)
    t_g = time.perf_counter()
    go = gate.decide(ctx.frame)
    ctx.trace.add_span("gate.decide", t_g, time.perf_counter() - t_g,
                       {"go": bool(go)})
    return go


def _detect_state_snapshot(stage) -> dict | None:
    """Shared ckpt-gated ``Stage.snapshot()`` body for the two
    detect-class stages (DetectStage / FusedDetectClassifyStage):
    gate controller state + coaster regions/velocities + the interval
    counter. Returns None when EVAM_CKPT is off — base-class behavior,
    byte-identical serve path."""
    from evam_tpu import state as stream_state

    if stream_state.active() is None:
        return None
    out: dict = {
        "count": int(stage._count),
        "coaster": stage._coaster.state_dict(),
    }
    if stage.gate is not None:
        out["gate"] = stage.gate.state_dict()
    return out


def _detect_state_restore(stage, state: dict) -> None:
    """Re-apply a ``_detect_state_snapshot`` on a freshly built stage.
    A ``stale`` marker (checkpoint older than the gate's max-skip
    bound — StreamInstance.restore_checkpoint prunes it to this) drops
    the detections/gate anchor and forces a refresh — identities in
    the track stage survive regardless."""
    stage._count = int(state.get("count", 0))
    if state.get("stale"):
        if stage.gate is not None:
            stage.gate.force_refresh()
        return
    if state.get("coaster"):
        stage._coaster.load_state(state["coaster"])
    if stage.gate is not None and state.get("gate"):
        stage.gate.load_state(state["gate"])


def _parse_interval(properties: dict) -> int:
    """``inference-interval``: a positive int, or ``"adaptive"`` —
    the motion gate replaces the static schedule (stages/gate.py), so
    the static interval collapses to 1."""
    iv = properties.get("inference-interval", 1)
    if isinstance(iv, str) and iv.strip().lower() == "adaptive":
        return 1
    return max(1, int(iv))


def _wire_frame(
    frame: np.ndarray, size: tuple[int, int], wire_format: str
) -> np.ndarray:
    """Fused resize + wire encode — ONE pass over the pixels in the
    native kernel (native/evam_media.cpp) instead of a resize pass
    plus a convert pass; this is the per-frame host hot op at high
    stream counts. native.resize_bgr_to_i420 owns the
    native-vs-cv2 policy and fallback.

    The returned array is copied into the engine's staging slot by
    ``BatchEngine.submit`` ON THIS (the stream's) thread — together
    the wire encode and the slot write are the stream's entire
    per-frame host cost; the dispatcher never touches the pixels
    again (engine/ringbuf.py).

    ``wire_format="seed"`` (EngineHub.device_synth, bench.py --config
    serve): the engine synthesizes pixels on-chip, so the stage
    submits only a distinct uint32 per frame."""
    if wire_format == "seed":
        return np.uint32(next(_SYNTH_SEQ) & 0xFFFFFFFF)
    if wire_format == "i420":
        from evam_tpu import native

        return native.resize_bgr_to_i420(frame, size[0], size[1])
    return _encode_wire(_resize_for_engine(frame, size), wire_format)



def _timed_wire(ctx: FrameContext, size: tuple[int, int],
                wire_format: str, stage: str) -> np.ndarray:
    """``_wire_frame`` of the frame, timed where the frame is traced:
    ``evam_stage_seconds{stage="<stage>.wire"}``, a ``wire`` span and
    ``evam.runner.wire`` on the stream's thread in a profiler
    capture."""
    ft = ctx.trace
    if ft is None:
        return _wire_frame(ctx.frame, size, wire_format)
    t0 = time.perf_counter()
    with trace.annotate("evam.runner.wire"):
        out = _wire_frame(ctx.frame, size, wire_format)
    dt = time.perf_counter() - t0
    metrics.observe("evam_stage_seconds", dt, {"stage": f"{stage}.wire"})
    ft.add_span("wire", t0, dt)
    return out


def _warm_engine(hub: EngineHub, engine, ingest_size, wire_format,
                 **extra_example) -> None:
    """Precompile the engine's batch buckets in the background when the
    hub serves live traffic (hub.warmup). The example is recorded on
    the engine EITHER way (set_example): a supervised rebuild
    (engine/supervisor.py) re-warms the replacement engine from it, so
    recovery never pays the mid-traffic compile spike the original
    warmup was added to kill."""
    h, w = ingest_size
    if wire_format == "seed":
        frame = np.uint32(0)
    else:
        from evam_tpu.ops.color import wire_shape

        frame = np.zeros(wire_shape(wire_format, h, w), np.uint8)
    if hub.warmup:
        engine.warm_async(frames=frame, **extra_example)
    else:
        engine.set_example(frames=frame, **extra_example)


class DetectStage(AsyncStage):
    """gvadetect counterpart. Properties (reference
    pipelines/object_detection/person_vehicle_bike/pipeline.json:18-40):
    device, threshold, inference-interval, model-instance-id."""

    def __init__(self, name: str, model_key: str, properties: dict, hub: EngineHub):
        self.name = name
        self.model_key = model_key
        self.threshold = float(properties.get("threshold", 0.5))
        if self.threshold < ENGINE_SCORE_FLOOR:
            log.warning(
                "detect stage %s threshold %.3f below shared-engine floor %.2f; "
                "effective threshold is %.2f",
                name, self.threshold, ENGINE_SCORE_FLOOR, ENGINE_SCORE_FLOOR,
            )
        self.interval = _parse_interval(properties)
        self.model = hub.model(model_key)
        self.wire = "seed" if hub.device_synth else hub.wire_format
        self.ingest_size = _wire_safe_size(
            (self.model.preprocess.height, self.model.preprocess.width)
        )
        self.engine = hub.engine(
            "detect",
            model_key,
            properties.get("model-instance-id"),
            score_threshold=ENGINE_SCORE_FLOOR,
            synth_wire_hw=self.ingest_size,
        )
        _warm_engine(hub, self.engine, self.ingest_size, self.wire)
        #: content-adaptive motion gate (stages/gate.py): None unless
        #: inference-interval=adaptive or EVAM_GATE=on
        self.gate = maybe_gate(
            properties, engine_name=getattr(self.engine, "name", ""))
        #: CoW reuse + constant-velocity coasting of the last inferred
        #: detections (stages/track.py) — both skip paths share it
        self._coaster = RegionCoaster()
        self._count = 0
        self._last_regions: list[Region] = []

    def submit(self, ctx: FrameContext) -> Future | None:
        self._count += 1
        if self.gate is not None:
            if ctx.frame is not None and not _timed_gate_decide(
                    self.gate, ctx):
                # motion gate skip: coast the last detections forward
                ctx.scratch["gate_coast"] = self.gate.consecutive_skips
                return None
        elif (self._count - 1) % self.interval:
            return None  # inference-interval skip: reuse last regions
        return self.engine.submit(
            priority=ctx.priority,
            stream=ctx.stream_id,
            trace=ctx.trace,
            frames=_timed_wire(ctx, self.ingest_size, self.wire, "detect"))

    def complete(self, ctx: FrameContext, result: np.ndarray | None) -> list[FrameContext]:
        if result is None:
            # skipped frame: shallow-frozen clones of the last
            # detections (value-equal to the old per-frame deepcopy),
            # velocity-coasted when the motion gate did the skipping.
            steps = ctx.scratch.pop("gate_coast", 0)
            ctx.regions.extend(self._coaster.coast(steps))
            return [ctx]
        labels = self.model.labels
        regions = []
        for row in result:
            x0, y0, x1, y1, score, label_id, valid = row
            if valid < 0.5 or score < self.threshold:
                continue
            lid = int(label_id)
            label = labels[lid] if 0 <= lid < len(labels) else str(lid)
            region = Region(
                x0=float(x0), y0=float(y0), x1=float(x1), y1=float(y1),
                confidence=float(score), label_id=lid, label=label,
            )
            region.tensors.append(
                Tensor(
                    name="detection",
                    confidence=float(score),
                    label_id=lid,
                    label=label,
                    is_detection=True,
                )
            )
            regions.append(region)
        self._last_regions = regions
        self._coaster.observe(regions)
        ctx.regions.extend(regions)
        return [ctx]

    def snapshot(self) -> dict | None:
        return _detect_state_snapshot(self)

    def restore(self, state: dict) -> None:
        _detect_state_restore(self, state)


class ClassifyStage(AsyncStage):
    """gvaclassify counterpart. Properties (reference
    pipelines/object_classification/vehicle_attributes/pipeline.json:63-85):
    object-class, reclassify-interval, threshold, model-instance-id."""

    ROI_BUDGET = 8

    def __init__(self, name: str, model_key: str, properties: dict, hub: EngineHub):
        self.name = name
        self.model_key = model_key
        self.object_class = properties.get("object-class")
        self.interval = max(1, int(properties.get("reclassify-interval", 1)))
        self.threshold = float(properties.get("threshold", 0.0))
        self.wire = "seed" if hub.device_synth else hub.wire_format
        self.model = hub.model(model_key)
        # Crops are taken on-device from the submitted frame; a fixed
        # canonical ingest resolution keeps cross-stream batches
        # stackable while preserving enough pixels for small ROIs.
        self.ingest_size = _wire_safe_size(
            tuple(properties.get("ingest-size", (432, 768)))
        )
        self.engine = hub.engine(
            "classify",
            model_key,
            properties.get("model-instance-id"),
            roi_budget=self.ROI_BUDGET,
            synth_wire_hw=self.ingest_size,
        )
        #: packed-ragged engine (EVAM_RAGGED=packed, engine/ragged.py):
        #: submit the frame's REAL region boxes — shape (k, 4) — and
        #: let the staging ring pack them across the batch, instead of
        #: zero-padding every frame to the ROI budget
        self._packed = getattr(self.engine, "ragged", "off") == "packed"
        _warm_engine(
            hub, self.engine, self.ingest_size, self.wire,
            boxes=np.zeros((self.ROI_BUDGET, 4), np.float32),
        )
        self._count = 0

    def _eligible(self, ctx: FrameContext) -> list[Region]:
        return [
            r
            for r in ctx.regions
            if self.object_class in (None, "", r.label)
        ][: self.ROI_BUDGET]

    def submit(self, ctx: FrameContext) -> Future | None:
        self._count += 1
        if (self._count - 1) % self.interval:
            return None
        regions = self._eligible(ctx)
        if not regions:
            return None
        # packed: exactly the frame's region rows (the ring packs them
        # across the batch); dense: the fixed ROI-budget pad block.
        # ``units`` keeps the engine's occupancy accounting honest
        # about interior padding on BOTH paths.
        rows = len(regions) if self._packed else self.ROI_BUDGET
        boxes = np.zeros((rows, 4), np.float32)
        for i, r in enumerate(regions):
            boxes[i] = [r.x0, r.y0, r.x1, r.y1]
        return self.engine.submit(
            priority=ctx.priority,
            units=len(regions),
            stream=ctx.stream_id,
            trace=ctx.trace,
            frames=_timed_wire(ctx, self.ingest_size, self.wire, "classify"),
            boxes=boxes)

    def complete(self, ctx: FrameContext, result: np.ndarray | None) -> list[FrameContext]:
        if result is None:
            return [ctx]
        regions = self._eligible(ctx)
        offset = 0
        head_slices = []
        for head_name, n in self.model.spec.heads:
            head_slices.append((head_name, offset, offset + n))
            offset += n
        for i, region in enumerate(regions):
            for head_name, a, b in head_slices:
                probs = result[i, a:b]
                lid = int(np.argmax(probs))
                conf = float(probs[lid])
                if conf < self.threshold:
                    continue
                label_list = self.model.head_labels.get(head_name, [])
                region.tensors.append(
                    Tensor(
                        name=head_name,
                        confidence=conf,
                        label_id=lid,
                        label=label_list[lid] if lid < len(label_list) else str(lid),
                    )
                )
        return [ctx]


class ActionStage(AsyncStage):
    """gvaactionrecognitionbin counterpart: per-frame encoder + 16-frame
    sliding-clip decoder (reference pipelines/action_recognition/general/
    pipeline.json:4, composite model note in that README:13-19)."""

    def __init__(self, name: str, properties: dict, hub: EngineHub):
        self.name = name
        enc_key = properties.get("enc-model", "action_recognition/encoder")
        dec_key = properties.get("dec-model", "action_recognition/decoder")
        self.dec_model = hub.model(dec_key)
        self.enc_model = hub.model(enc_key)
        self.ingest_size = _wire_safe_size((
            self.enc_model.preprocess.height,
            self.enc_model.preprocess.width,
        ))
        self.enc_engine = hub.engine("action_encode", enc_key,
                                     properties.get("model-instance-id"),
                                     synth_wire_hw=self.ingest_size)
        self.dec_engine = hub.engine("action_decode", dec_key)
        self.clip: deque[np.ndarray] = deque(maxlen=CLIP_LEN)
        self.threshold = float(properties.get("threshold", 0.0))
        self.wire = "seed" if hub.device_synth else hub.wire_format
        _warm_engine(hub, self.enc_engine, self.ingest_size, self.wire)
        if hub.warmup:
            embed_dim = getattr(self.enc_model.module, "embed_dim", 512)
            self.dec_engine.warm_async(
                clips=np.zeros((CLIP_LEN, embed_dim), np.float32)
            )

    def submit(self, ctx: FrameContext) -> Future | None:
        """Chain encoder → decoder without ever blocking the runner.

        The returned future resolves to the decoder's class
        probabilities (or None during clip warm-up). The decoder
        submit happens inside the encoder future's callback — on the
        encoder engine's dispatcher thread — so the runner's pump
        never waits on a decoder round-trip inline (round-1 VERDICT
        "ActionStage.complete blocks the stream"): frames keep
        flowing while a decoder batch is pending, and the action
        pipeline runs at encoder throughput.
        """
        prio = ctx.priority
        stream_id = ctx.stream_id
        tr = ctx.trace
        enc_fut = self.enc_engine.submit(
            priority=prio,
            stream=ctx.stream_id,
            trace=tr,
            frames=_timed_wire(ctx, self.ingest_size, self.wire, "action"))
        outer: Future = Future()

        def _on_encoded(f: Future) -> None:
            # concurrent.futures swallows exceptions raised inside
            # done-callbacks — any failure here must land on `outer`
            # or the runner's pump would block on it forever.
            try:
                emb = f.result()
                # Encoder futures complete in submission order (FIFO
                # batcher), so appends preserve frame order even
                # though this runs on the dispatcher thread.
                self.clip.append(emb)
                if len(self.clip) < CLIP_LEN:
                    outer.set_result(None)  # warm-up: no action tensor yet
                    return
                clip = np.stack(self.clip)  # [T, D]
                # raises RuntimeError when the engine is stopping
                dec_fut = self.dec_engine.submit(priority=prio,
                                                 stream=stream_id,
                                                 trace=tr,
                                                 clips=clip)
            except Exception as exc:  # noqa: BLE001 — propagate to the runner
                outer.set_exception(exc)
                return

            def _on_decoded(g: Future) -> None:
                try:
                    outer.set_result(g.result())
                except Exception as exc:  # noqa: BLE001
                    outer.set_exception(exc)

            dec_fut.add_done_callback(_on_decoded)

        enc_fut.add_done_callback(_on_encoded)
        return outer

    def complete(self, ctx: FrameContext, result: np.ndarray | None) -> list[FrameContext]:
        if result is None:
            return [ctx]  # clip warm-up (or no inference this frame)
        probs = result
        lid = int(np.argmax(probs))
        conf = float(probs[lid])
        if conf >= self.threshold:
            labels = self.dec_model.labels
            ctx.tensors.append(
                Tensor(
                    name="action",
                    confidence=conf,
                    label_id=lid,
                    label=labels[lid] if lid < len(labels) else str(lid),
                    data=[float(x) for x in probs],
                )
            )
        return [ctx]


class AudioDetectStage(AsyncStage):
    """gvaaudiodetect counterpart: classify 1-second 16 kHz windows
    (reference pipelines/audio_detection/environment/pipeline.json:4-9,
    sliding-window parameter :34-38)."""

    WINDOW = 16000  # 1 s at 16 kHz

    def __init__(self, name: str, model_key: str, properties: dict, hub: EngineHub):
        self.name = name
        self.threshold = float(properties.get("threshold", 0.0))
        # sliding-window: stride as a fraction of the 1 s window
        # (reference default 0.2, pipeline.json:34-38)
        self.stride = max(1, int(self.WINDOW * float(properties.get("sliding-window", 0.2))))
        self.engine = hub.engine(
            "audio", model_key, properties.get("model-instance-id")
        )
        self.model = hub.model(model_key)
        if hub.warmup:
            self.engine.warm_async(
                windows=np.zeros(self.WINDOW, np.int16))
        self._buffer = np.zeros(0, np.int16)
        self._since_last = 0

    def submit(self, ctx: FrameContext) -> Future | None:
        if ctx.audio is None:
            return None
        self._buffer = np.concatenate([self._buffer, ctx.audio])[-self.WINDOW:]
        self._since_last += len(ctx.audio)
        if len(self._buffer) < self.WINDOW or self._since_last < self.stride:
            return None
        self._since_last = 0
        return self.engine.submit(priority=ctx.priority,
                                  stream=ctx.stream_id,
                                  trace=ctx.trace,
                                  windows=self._buffer.copy())

    def complete(self, ctx: FrameContext, result: np.ndarray | None) -> list[FrameContext]:
        if result is None:
            return [ctx]
        lid = int(np.argmax(result))
        conf = float(result[lid])
        if conf >= self.threshold:
            labels = self.model.labels
            ctx.tensors.append(
                Tensor(
                    name="detection",
                    confidence=conf,
                    label_id=lid,
                    label=labels[lid] if lid < len(labels) else str(lid),
                )
            )
        return [ctx]


class FusedDetectClassifyStage(AsyncStage):
    """Detect+classify fused into one engine round-trip.

    Produced by the stage builder's fusion pass when a classify stage
    follows detect in the chain (the standard object_classification /
    object_tracking templates): one frame upload and one packed
    readback replace two of each, doubling effective ingest bandwidth
    — the scarce resource on the host→TPU path. The ``object-class``
    filter runs inside the program (scores of non-matching classes are
    ineligible for the ROI budget); a row whose probability block is
    all-zero was not classified. Known trade-off vs the unfused pair:
    ROI crops come from the frame pre-resized to the detector's input
    (the 8x upload saving at 1080p), not a classification-sized
    ingest; reclassify-interval > 1 disables fusion entirely
    (stages/build.py _fusable)."""

    ROI_BUDGET = 8

    def __init__(
        self,
        name: str,
        det_key: str,
        cls_key: str,
        det_props: dict,
        cls_props: dict,
        hub: EngineHub,
    ):
        self.name = name
        self.det_threshold = float(det_props.get("threshold", 0.5))
        self.cls_threshold = float(cls_props.get("threshold", 0.0))
        self.object_class = cls_props.get("object-class")
        self.interval = _parse_interval(det_props)
        self.det_model = hub.model(det_key)
        allowed = None
        if self.object_class:
            allowed = tuple(
                i for i, lbl in enumerate(self.det_model.labels)
                if lbl == self.object_class
            )
        self.wire = "seed" if hub.device_synth else hub.wire_format
        self.ingest_size = _wire_safe_size(
            (self.det_model.preprocess.height, self.det_model.preprocess.width)
        )
        self.engine = hub.fused_engine(
            det_key,
            cls_key,
            det_props.get("model-instance-id"),
            roi_budget=self.ROI_BUDGET,
            score_threshold=ENGINE_SCORE_FLOOR,
            allowed_label_ids=allowed,
            synth_wire_hw=self.ingest_size,
        )
        self.cls_model = hub.model(cls_key)
        _warm_engine(hub, self.engine, self.ingest_size, self.wire)
        #: motion gate + coasting — same submit-side gating contract
        #: as DetectStage (detect properties drive it)
        self.gate = maybe_gate(
            det_props, engine_name=getattr(self.engine, "name", ""))
        self._coaster = RegionCoaster()
        self._count = 0
        self._last_regions: list[Region] = []

    def submit(self, ctx: FrameContext) -> Future | None:
        self._count += 1
        if self.gate is not None:
            if ctx.frame is not None and not _timed_gate_decide(
                    self.gate, ctx):
                ctx.scratch["gate_coast"] = self.gate.consecutive_skips
                return None
        elif (self._count - 1) % self.interval:
            return None
        return self.engine.submit(
            priority=ctx.priority,
            stream=ctx.stream_id,
            trace=ctx.trace,
            frames=_timed_wire(ctx, self.ingest_size, self.wire, "fused"))

    def complete(self, ctx: FrameContext, result: np.ndarray | None) -> list[FrameContext]:
        if result is None:
            steps = ctx.scratch.pop("gate_coast", 0)
            ctx.regions.extend(self._coaster.coast(steps))
            return [ctx]
        det_labels = self.det_model.labels
        head_slices = []
        offset = 7
        for head_name, n in self.cls_model.spec.heads:
            head_slices.append((head_name, offset, offset + n))
            offset += n
        regions = []
        for i, row in enumerate(result):
            x0, y0, x1, y1, score, label_id, valid = row[:7]
            if valid < 0.5 or score < self.det_threshold:
                continue
            lid = int(label_id)
            label = det_labels[lid] if 0 <= lid < len(det_labels) else str(lid)
            region = Region(
                x0=float(x0), y0=float(y0), x1=float(x1), y1=float(y1),
                confidence=float(score), label_id=lid, label=label,
            )
            region.tensors.append(
                Tensor(name="detection", confidence=float(score),
                       label_id=lid, label=label, is_detection=True)
            )
            # An all-zero probability block marks an unclassified row
            # (classified blocks are softmaxes summing to #heads).
            if row[7:].sum() > 0.5:
                for head_name, a, b in head_slices:
                    probs = row[a:b]
                    hid = int(np.argmax(probs))
                    conf = float(probs[hid])
                    if conf < self.cls_threshold:
                        continue
                    label_list = self.cls_model.head_labels.get(head_name, [])
                    region.tensors.append(
                        Tensor(
                            name=head_name,
                            confidence=conf,
                            label_id=hid,
                            label=label_list[hid] if hid < len(label_list) else str(hid),
                        )
                    )
            regions.append(region)
        self._last_regions = regions
        self._coaster.observe(regions)
        ctx.regions.extend(regions)
        return [ctx]

    def snapshot(self) -> dict | None:
        return _detect_state_snapshot(self)

    def restore(self, state: dict) -> None:
        _detect_state_restore(self, state)
