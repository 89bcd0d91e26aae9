"""Ground-truth accuracy on the REAL chip (battery entry `accuracy`).

Two phases:

1. **fit** (CPU subprocess, ~3 min, cached): trains the zoo SSD on
   synthetic ground-truth scenes via evam_tpu.models.accuracy and
   saves weights to a /tmp cache keyed on the fit config — rerun the
   battery and the fit is reused.
2. **eval** (this process, default backend = the TPU): loads the
   fitted weights, renders the same held-out 1080p scenes as
   ``tests/test_accuracy.py`` (seed 99), runs the fused i420 detect
   step on the device, and reports recall/precision plus the max
   divergence of the packed rows vs the CPU reference — device
   numerics AND geometry in one line.

Prints ONE JSON line (battery/fold contract).
"""

from __future__ import annotations

import os as _os
_os.environ.setdefault("EVAM_ALLOW_RANDOM_WEIGHTS", "1")  # hermetic tool

import json
import os
import subprocess
import sys
import time
from pathlib import Path

KEY = "object_detection/person_vehicle_bike"
INPUT = (96, 96)
WIDTH = 16
SEED = 99
CLS_KEY = "object_classification/vehicle_attributes"
CLS_INPUT = (48, 48)
CLS_WIDTH = 16
ENC_KEY = "action_recognition/encoder"
DEC_KEY = "action_recognition/decoder"
AUD_KEY = "audio_detection/environment"
ENC_INPUT = (48, 48)
TEMPORAL_WIDTH = 8
#: cache keyed on the fit config — stale weights from an older
#: KEY/INPUT/WIDTH can't poison a new run
FIT_PATH = Path(
    f"/tmp/evam_acc_fit_{KEY.replace('/', '_')}"
    f"_{INPUT[0]}x{INPUT[1]}_w{WIDTH}.msgpack")
#: color-attr variant (detector refit on attr scenes) + classifier —
#: the fused detect+classify / wire-plane-ROI-crop assertion
FIT_ATTR_PATH = FIT_PATH.with_suffix(".attr.msgpack")
CLS_FIT_PATH = Path(
    f"/tmp/evam_acc_fit_{CLS_KEY.replace('/', '_')}"
    f"_{CLS_INPUT[0]}x{CLS_INPUT[1]}_w{CLS_WIDTH}.msgpack")
#: temporal families (action enc+dec, aclnet) — one cache file each
ENC_FIT_PATH = Path(
    f"/tmp/evam_acc_fit_action_enc_{ENC_INPUT[0]}x{ENC_INPUT[1]}"
    f"_w{TEMPORAL_WIDTH}.msgpack")
DEC_FIT_PATH = ENC_FIT_PATH.with_suffix(".dec.msgpack")
AUD_FIT_PATH = Path(
    f"/tmp/evam_acc_fit_aclnet_w{TEMPORAL_WIDTH}.msgpack")


def _build():
    from evam_tpu.models.registry import ModelRegistry

    reg = ModelRegistry(dtype="float32", input_overrides={KEY: INPUT},
                        width_overrides={KEY: WIDTH},
                        allow_random_weights=True)
    return reg.get(KEY)


def _build_cls():
    from evam_tpu.models.registry import ModelRegistry

    reg = ModelRegistry(
        dtype="float32", input_overrides={CLS_KEY: CLS_INPUT},
        width_overrides={CLS_KEY: CLS_WIDTH},
        allow_random_weights=True)
    return reg.get(CLS_KEY)


def _build_temporal():
    from evam_tpu.models.registry import ModelRegistry

    reg = ModelRegistry(
        dtype="float32", input_overrides={ENC_KEY: ENC_INPUT},
        width_overrides={ENC_KEY: TEMPORAL_WIDTH,
                         DEC_KEY: TEMPORAL_WIDTH,
                         AUD_KEY: TEMPORAL_WIDTH},
        allow_random_weights=True)
    return reg.get(ENC_KEY), reg.get(DEC_KEY), reg.get(AUD_KEY)


def run_fit() -> int:
    """CPU-pinned subprocess body: fit + save."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    from flax import serialization

    from evam_tpu.models import accuracy as acc

    model = _build()
    params, history = acc.fit_detector(model, steps=1200, n_scenes=128)
    print(json.dumps({"fit_final_loss": history[-1]}), file=sys.stderr)
    if history[-1] >= 0.5:
        # never cache a diverged fit — the next run must retry
        print("fit did not converge; not caching", file=sys.stderr)
        return 3
    FIT_PATH.write_bytes(serialization.to_bytes(
        jax.tree.map(lambda a: __import__("numpy").asarray(a), params)))
    return 0


def run_fit_classify() -> int:
    """CPU-pinned subprocess: color-attr detector + classifier fits."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np
    from flax import serialization

    from evam_tpu.models import accuracy as acc

    model = _build()
    params, history = acc.fit_detector(
        model, steps=1200, n_scenes=128, color_attr=True)
    cls_model = _build_cls()
    cls_params, chist = acc.fit_classifier(
        cls_model, steps=900, n_crops=768)
    print(json.dumps({"det_attr_loss": history[-1],
                      "cls_loss": chist[-1]}), file=sys.stderr)
    if history[-1] >= 0.6 or chist[-1] >= 0.2:
        print("classify fits did not converge; not caching",
              file=sys.stderr)
        return 3
    FIT_ATTR_PATH.write_bytes(serialization.to_bytes(
        jax.tree.map(np.asarray, params)))
    CLS_FIT_PATH.write_bytes(serialization.to_bytes(
        jax.tree.map(np.asarray, cls_params)))
    return 0


def run_fit_temporal() -> int:
    """CPU-pinned subprocess: action enc+dec and aclnet fits."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np
    from flax import serialization

    from evam_tpu.models import accuracy as acc

    enc, dec, aud = _build_temporal()
    (ep, dp), hist = acc.fit_action(enc, dec)
    ap, ahist = acc.fit_audio(aud)
    print(json.dumps({"action_loss": hist[-1],
                      "audio_loss": ahist[-1]}), file=sys.stderr)
    if hist[-1] >= 0.6 or ahist[-1] >= 0.3:
        print("temporal fits did not converge; not caching",
              file=sys.stderr)
        return 3
    ENC_FIT_PATH.write_bytes(serialization.to_bytes(
        jax.tree.map(np.asarray, ep)))
    DEC_FIT_PATH.write_bytes(serialization.to_bytes(
        jax.tree.map(np.asarray, dp)))
    AUD_FIT_PATH.write_bytes(serialization.to_bytes(
        jax.tree.map(np.asarray, ap)))
    return 0


def main() -> int:
    if "--fit" in sys.argv:
        return run_fit()
    if "--fit-classify" in sys.argv:
        return run_fit_classify()
    if "--fit-temporal" in sys.argv:
        return run_fit_temporal()

    if not FIT_PATH.exists():
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            rc = subprocess.run(
                [sys.executable, __file__, "--fit"], env=env,
                timeout=900).returncode
        except subprocess.TimeoutExpired:
            rc = -9
        if rc != 0 or not FIT_PATH.exists():
            print(json.dumps({"metric": "accuracy_recall_1080p_i420",
                              "value": 0.0, "unit": "recall",
                              "error": f"fit failed rc={rc}"}))
            return 1
    attr_error = None
    if not (FIT_ATTR_PATH.exists() and CLS_FIT_PATH.exists()):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            crc = subprocess.run(
                [sys.executable, __file__, "--fit-classify"], env=env,
                timeout=900).returncode
        except subprocess.TimeoutExpired:
            crc = -9
        if crc != 0 or not (FIT_ATTR_PATH.exists()
                            and CLS_FIT_PATH.exists()):
            # classify phase is additive (detect still reports), but
            # an attempted-and-failed fit must be visible in the line
            attr_error = f"fit-classify failed rc={crc}"
    temporal_error = None
    if not (ENC_FIT_PATH.exists() and DEC_FIT_PATH.exists()
            and AUD_FIT_PATH.exists()):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            trc = subprocess.run(
                [sys.executable, __file__, "--fit-temporal"], env=env,
                timeout=900).returncode
        except subprocess.TimeoutExpired:
            trc = -9
        if trc != 0 or not (ENC_FIT_PATH.exists()
                            and DEC_FIT_PATH.exists()
                            and AUD_FIT_PATH.exists()):
            temporal_error = f"fit-temporal failed rc={trc}"

    import jax
    import numpy as np
    from flax import serialization

    from evam_tpu.engine.steps import build_detect_step
    from evam_tpu.models import accuracy as acc
    from evam_tpu.ops.color import bgr_to_i420_host

    model = _build()
    params = serialization.from_bytes(model.params, FIT_PATH.read_bytes())

    rng = np.random.default_rng(SEED)
    scenes = [acc.render_scene(rng, hw=(1080, 1920)) for _ in range(8)]
    wire = np.stack([bgr_to_i420_host(s.frame) for s in scenes])
    step = build_detect_step(model, max_detections=16,
                             score_threshold=0.3, wire_format="i420")

    dev = jax.devices()[0]
    fn = jax.jit(step)
    t0 = time.time()
    packed_dev = np.asarray(jax.block_until_ready(fn(
        jax.device_put(params, dev), jax.device_put(wire, dev))))
    dt = time.time() - t0
    report = acc.evaluate_packed(packed_dev, scenes)

    # CPU reference for numeric divergence (committed inputs pick the
    # backend; same jitted fn recompiles for the cpu placement)
    cpu = jax.devices("cpu")[0]
    packed_cpu = np.asarray(fn(
        jax.device_put(params, cpu), jax.device_put(wire, cpu)))
    raw_div = np.abs(packed_dev[..., :5] - packed_cpu[..., :5]).max()
    # non-finite divergence IS the finding — keep the line valid JSON
    max_div = float(raw_div) if np.isfinite(raw_div) else str(raw_div)

    line = {
        "metric": "accuracy_recall_1080p_i420",
        "value": round(report["recall"], 4),
        "unit": "recall@iou0.5",
        "precision": round(report["precision"], 4),
        "gt": report["gt"],
        "device": str(dev.platform),
        "first_call_s": round(dt, 2),
        "max_divergence_vs_cpu": max_div,
    }

    # fused detect+classify on device: exercises the wire-plane ROI
    # crop (crop_rois_i420) geometry + classifier numerics on chip
    if FIT_ATTR_PATH.exists() and CLS_FIT_PATH.exists():
        from evam_tpu.engine.steps import build_detect_classify_step

        det_attr = serialization.from_bytes(
            model.params, FIT_ATTR_PATH.read_bytes())
        cls_model = _build_cls()
        cls_params = serialization.from_bytes(
            cls_model.params, CLS_FIT_PATH.read_bytes())
        rng2 = np.random.default_rng(123)
        cscenes = [acc.render_scene(rng2, hw=(1080, 1920),
                                    color_attr=True)
                   for _ in range(12)]
        cwire = np.stack(
            [bgr_to_i420_host(s.frame) for s in cscenes])
        cstep = jax.jit(build_detect_classify_step(
            model, cls_model, max_detections=16, roi_budget=8,
            score_threshold=0.3, wire_format="i420",
            allowed_label_ids=(2,)))
        cparams = {"det": det_attr, "cls": cls_params}
        cp = np.asarray(jax.block_until_ready(cstep(
            jax.device_put(cparams, dev),
            jax.device_put(cwire, dev))))
        attr_report = acc.evaluate_attrs(cp, cscenes)
        line["attr_recall"] = round(attr_report["attr_recall"], 4)
        line["attr_gt"] = attr_report["gt"]
    elif attr_error is not None:
        line["attr_error"] = attr_error

    # temporal families on device: action clip classes + audio tones
    if (ENC_FIT_PATH.exists() and DEC_FIT_PATH.exists()
            and AUD_FIT_PATH.exists()):
        from evam_tpu.engine.steps import (
            build_action_decode_step,
            build_action_encode_step,
            build_audio_step,
        )

        enc, dec_m, aud = _build_temporal()
        ep = serialization.from_bytes(
            enc.params, ENC_FIT_PATH.read_bytes())
        dp = serialization.from_bytes(
            dec_m.params, DEC_FIT_PATH.read_bytes())
        ap = serialization.from_bytes(
            aud.params, AUD_FIT_PATH.read_bytes())
        enc_step = jax.jit(build_action_encode_step(
            enc, wire_format="bgr"))
        dec_step = jax.jit(build_action_decode_step(dec_m))
        rng3 = np.random.default_rng(21)
        classes = [i % 4 for i in range(8)]
        clips = np.stack([
            acc.render_temporal_clip(rng3, c, ENC_INPUT, 16)
            for c in classes])                    # [8, 16, H, W, 3]
        ep_d = jax.device_put(ep, dev)
        dp_d = jax.device_put(dp, dev)
        flat = clips.reshape((-1,) + clips.shape[2:])
        emb = enc_step(ep_d, jax.device_put(flat, dev))
        emb = np.asarray(emb).reshape(8, 16, -1)
        aprobs = np.asarray(dec_step(dp_d, jax.device_put(emb, dev)))
        line["action_acc"] = float(
            (aprobs.argmax(axis=1) == np.asarray(classes)).mean())

        audio_step = jax.jit(build_audio_step(aud))
        rng4 = np.random.default_rng(22)
        n_samples = aud.spec.input_size[1]  # aclnet window (matches
        # fit_audio's sizing — no duplicated constant)
        wins = []
        tones = []
        for i in range(8):
            t = i % 4
            tones.append(t)
            wins.append(acc.render_tone_window(rng4, t, n_samples))
        probs = np.asarray(audio_step(
            jax.device_put(ap, dev),
            jax.device_put(np.stack(wins), dev)))
        line["audio_acc"] = float(
            (probs.argmax(axis=1) == np.asarray(tones)).mean())
    elif temporal_error is not None:
        line["temporal_error"] = temporal_error

    print(json.dumps(line))
    return 0 if report["recall"] >= 0.75 else 1


if __name__ == "__main__":
    sys.exit(main())
