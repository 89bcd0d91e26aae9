"""INT8 quantized serving path (ops/qlinear.py + quant module
variants): numeric closeness to float, checkpoint-pytree parity, and
the full fused step running quantized."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from evam_tpu.ops.qlinear import quant_conv, quant_dense, quantize_weight


def test_quant_conv_close_to_float():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 8, 16)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.normal(size=(16,)) * 0.1, jnp.float32)

    ref = jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
    got = quant_conv(x, w, b)
    err = jnp.abs(got - ref).max() / (jnp.abs(ref).max() + 1e-9)
    assert float(err) < 0.02, f"relative error {float(err):.4f}"


def test_quant_dense_close_to_float():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 10)) * 0.2, jnp.float32)
    ref = x @ w
    got = quant_dense(x, w, None)
    err = jnp.abs(got - ref).max() / (jnp.abs(ref).max() + 1e-9)
    assert float(err) < 0.02


def test_quantize_weight_roundtrip_exact_for_grid():
    # values already on the int8 grid survive quantization exactly
    w = jnp.asarray([[-127.0], [64.0], [0.0], [127.0]]).reshape(1, 1, 4, 1)
    wq, scale = quantize_weight(w)
    np.testing.assert_allclose(
        np.asarray(wq, np.float32) * np.asarray(scale), np.asarray(w))


def test_quant_and_float_share_checkpoint_pytree():
    """The whole point of in-jit quantization: FP checkpoints serve
    under INT8 unchanged. Same param tree, same shapes."""
    from evam_tpu.models.zoo.classifier import MultiHeadClassifier
    from evam_tpu.models.zoo.ssd import SSDDetector

    x = jnp.zeros((1, 64, 64, 3), jnp.float32)
    for fp_mod, q_mod in [
        (SSDDetector(num_classes=3, width=8),
         SSDDetector(num_classes=3, width=8, quant=True)),
        (MultiHeadClassifier(heads=(("c", 4),), width=8),
         MultiHeadClassifier(heads=(("c", 4),), width=8, quant=True)),
    ]:
        fp = fp_mod.init(jax.random.PRNGKey(0), x)["params"]
        q = q_mod.init(jax.random.PRNGKey(0), x)["params"]
        fp_shapes = jax.tree.map(lambda a: a.shape, fp)
        q_shapes = jax.tree.map(lambda a: a.shape, q)
        assert fp_shapes == q_shapes
        # float weights apply directly under the quant module
        out = q_mod.apply({"params": fp}, x)
        assert jax.tree.all(
            jax.tree.map(lambda a: bool(jnp.isfinite(a).all()), out))


def test_int8_registry_serves_fused_step():
    from evam_tpu.engine import steps as step_builders
    from evam_tpu.models.registry import ModelRegistry, ZOO_SPECS

    reg = ModelRegistry(
        dtype="int8",
        input_overrides={k: (64, 64) for k in ZOO_SPECS},
        width_overrides={k: 8 for k in ZOO_SPECS},
    )
    assert reg.precision == "INT8" and reg.dtype == "bfloat16"
    det = reg.get("object_detection/person_vehicle_bike")
    cls = reg.get("object_classification/vehicle_attributes")
    assert det.module.quant and cls.module.quant

    step = jax.jit(step_builders.build_detect_classify_step(
        det, cls, max_detections=8, roi_budget=2, wire_format="bgr",
        score_threshold=0.0))
    frames = np.random.default_rng(0).integers(
        0, 255, (2, 64, 64, 3), np.uint8)
    out = np.asarray(step(
        {"det": det.params, "cls": cls.params}, frames))
    assert out.shape[0] == 2 and out.shape[2] == 7 + 11
    assert np.isfinite(out).all()


class TestPallasQGemm:
    """The fused pallas int8 GEMM (interpret mode on CPU) must agree
    with the XLA quantize→dot→dequant path bit-for-bit-ish."""

    def test_matches_xla_quant_dense(self):
        from evam_tpu.ops.pallas_qgemm import pallas_quant_dense

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(48, 64)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(64, 96)) * 0.2, jnp.float32)
        b = jnp.asarray(rng.normal(size=(96,)) * 0.1, jnp.float32)
        ref = quant_dense(x, w, b)
        got = pallas_quant_dense(x, w, b, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_ragged_shapes_pad_correctly(self):
        from evam_tpu.ops.pallas_qgemm import pallas_quant_dense

        rng = np.random.default_rng(1)
        # m and n deliberately not multiples of the tile sizes
        x = jnp.asarray(rng.normal(size=(130, 32)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(32, 130)) * 0.3, jnp.float32)
        ref = quant_dense(x, w, None)
        got = pallas_quant_dense(x, w, None, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_backend_switch_routes_1x1_conv(self, monkeypatch):
        """The pallas route quantizes per PIXEL (finer than the XLA
        path's per-example scale), so compare both against the float
        conv: pallas must be valid PTQ and no worse than XLA."""
        from evam_tpu.ops import qlinear

        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(2, 8, 8, 16)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(1, 1, 16, 32)) * 0.2, jnp.float32)
        b = jnp.asarray(rng.normal(size=(32,)) * 0.1, jnp.float32)
        fp = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
        xla_q = quant_conv(x, w, b)
        monkeypatch.setattr(qlinear, "QGEMM_BACKEND", "pallas")
        # Mosaic does not target the CPU: ask for the interpreter
        # explicitly (no code path picks it from the backend)
        from evam_tpu.ops import pallas_qgemm

        monkeypatch.setattr(
            pallas_qgemm, "pallas_quant_dense",
            functools.partial(pallas_qgemm.pallas_quant_dense,
                              interpret=True))
        pallas_q = qlinear.quant_conv(x, w, b)
        assert pallas_q.shape == fp.shape

        def max_rel(a):
            return float(jnp.abs(a - fp).max() / (jnp.abs(fp).max() + 1e-9))

        assert max_rel(pallas_q) < 0.02
        assert max_rel(pallas_q) <= max_rel(xla_q) * 1.5  # no worse


def test_int8_outputs_track_float_outputs():
    """Quantized detector scores stay close to the float ones on the
    same weights (dynamic PTQ error budget)."""
    from evam_tpu.models.registry import ModelRegistry, ZOO_SPECS

    kw = dict(
        input_overrides={k: (64, 64) for k in ZOO_SPECS},
        width_overrides={k: 8 for k in ZOO_SPECS},
    )
    fp = ModelRegistry(dtype="float32", **kw).get(
        "object_detection/person_vehicle_bike")
    q = ModelRegistry(dtype="int8", **kw).get(
        "object_detection/person_vehicle_bike")

    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, 64, 64, 3)) * 50 + 128,
        jnp.float32)
    fp_out = fp.forward(fp.params, x)
    q_params = jax.tree.map(lambda a: a.astype(jnp.float32), q.params)
    q_out = q.forward(q_params, x.astype(jnp.float32))
    # what serving consumes: class probabilities per anchor — the
    # PTQ error budget is on the softmax surface, not raw logits
    # (random-init width-8 nets are a worst case; trained nets do
    # better)
    fp_probs = jax.nn.softmax(fp_out["conf"].astype(jnp.float32), axis=-1)
    q_probs = jax.nn.softmax(q_out["conf"].astype(jnp.float32), axis=-1)
    mad = float(jnp.abs(fp_probs - q_probs).mean())
    assert mad < 0.05, f"mean abs prob difference {mad:.4f}"
    agree = float(
        (fp_probs.argmax(-1) == q_probs.argmax(-1)).mean())
    # random-init logits are near-uniform, so top-1 flips on hair-thin
    # margins; 0.85 still catches a broken quantization path (which
    # scores ~1/num_classes agreement)
    assert agree > 0.85, f"top-class agreement {agree:.3f}"


def test_pallas_qgemm_empty_batch():
    from evam_tpu.ops.pallas_qgemm import pallas_quant_dense

    x = jnp.zeros((0, 16), jnp.float32)
    w = jnp.ones((16, 8), jnp.float32)
    out = pallas_quant_dense(x, w, jnp.ones((8,)), interpret=True)
    assert out.shape == (0, 8)
