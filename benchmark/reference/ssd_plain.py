"""Plain reference of the served detector, float32 throughout.

Nothing here calls the program's kernels, steps, batching, wire format or
IR importer. It makes the synthetic frame from the URI's arithmetic,
resizes it with a plain bilinear filter, applies the net that the
configuration's ``shapes`` describe (a MobileNet-v1 ladder of depthwise
separable blocks with 1x1 SSD heads) layer by layer with
``jax.lax.conv_general_dilated`` at ``Precision.HIGHEST``, makes the
clustered prior boxes from the same ``shapes``, and decodes and suppresses
boxes in numpy. The only thing shared with the served path is data: the
weight tensors of the installed ``model.bin``, found through the names,
offsets and shapes of the ``Const`` layers in ``model.xml``.

Departures from the served path, which the tolerance has to absorb and
``compare.py`` writes down: the served frame crosses the wire as I420
(chroma at half resolution, 8-bit fixed-point resize) and the net runs in
bfloat16.
"""

from __future__ import annotations

import itertools
from pathlib import Path

import numpy as np

# ----------------------------------------------------------------- pixels


def synth_frame(width: int, height: int, seed: int, seq: int) -> np.ndarray:
    """Frame ``seq`` of ``synthetic://WxH@fps?seed=``: a bright square
    moving over a dark ground (BGR uint8)."""
    frame = np.full((height, width, 3), 16, np.uint8)
    sq = max(8, min(height, width) // 8)
    x = (seed * 37 + seq * 7) % max(1, width - sq)
    y = (seed * 53 + seq * 5) % max(1, height - sq)
    frame[y:y + sq, x:x + sq] = (64, 160, 240)
    return frame


def resize_bilinear(img: np.ndarray, dh: int, dw: int) -> np.ndarray:
    """Bilinear, half-pixel centres, edges clamped; float32 out."""
    sh, sw = img.shape[:2]
    src = img.astype(np.float32)

    def taps(dn, sn):
        f = np.maximum((np.arange(dn) + 0.5) * sn / dn - 0.5, 0.0)
        i0 = np.floor(f).astype(np.int64)
        i1 = np.minimum(i0 + 1, sn - 1)
        return i0, i1, (f - i0).astype(np.float32)

    y0, y1, wy = taps(dh, sh)
    x0, x1, wx = taps(dw, sw)
    top = src[y0][:, x0] * (1 - wx)[None, :, None] + src[y0][:, x1] * wx[None, :, None]
    bot = src[y1][:, x0] * (1 - wx)[None, :, None] + src[y1][:, x1] * wx[None, :, None]
    return top * (1 - wy)[:, None, None] + bot * wy[:, None, None]


# ---------------------------------------------------------------- weights


def read_ir_weights(xml_path, bin_path) -> dict[str, np.ndarray]:
    """name -> float32 array, for every float ``Const`` of the IR."""
    import xml.etree.ElementTree as ET

    blob = Path(bin_path).read_bytes()
    out = {}
    for layer in ET.parse(xml_path).getroot().find("layers"):
        if layer.get("type") != "Const":
            continue
        d = layer.find("data").attrib
        if d["element_type"] != "f32":
            continue
        shape = [int(x) for x in d["shape"].split(",") if x]
        off, size = int(d["offset"]), int(d["size"])
        out[layer.get("name")] = np.frombuffer(
            blob[off:off + size], np.float32).reshape(shape)
    return out


# ------------------------------------------------------------------- nets


def _conv(x, w, stride=1, groups=1):
    """NHWC activations, OIHW weights, SAME padding (one more at the far
    edge where the total is odd, as the IR's pads_begin/pads_end say)."""
    import jax
    import jax.numpy as jnp

    return jax.lax.conv_general_dilated(
        x, jnp.asarray(w, jnp.float32), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "OIHW", "NHWC"),
        feature_group_count=groups,
        precision=jax.lax.Precision.HIGHEST)


def _conv_bias_relu(x, weights, name, stride=1, groups=1):
    import jax.numpy as jnp

    w = weights[f"{name}_w"]
    if groups > 1:  # GroupConvolution stores [G, O/G, I/G, k, k]
        w = w.reshape((-1,) + w.shape[2:])
    b = jnp.asarray(weights[f"{name}_b"], jnp.float32).reshape(1, 1, 1, -1)
    return jnp.maximum(_conv(x, w, stride, groups) + b, 0.0)


def ssd_forward(weights, x, shapes):
    """float32 [B,H,W,3] BGR 0..255 -> (loc [B,A,4], scores [B,A,C]):
    the ladder of ``shapes``, 1x1 heads on the marked blocks, softmax
    over classes inside the net (the IR's own)."""
    import jax
    import jax.numpy as jnp

    stem = shapes["stem"]
    x = _conv_bias_relu(x, weights, stem["name"], stem["stride"])
    feats = {}
    for blk in shapes["blocks"]:
        x = _conv_bias_relu(x, weights, f"{blk['name']}_dw", blk["stride"],
                            groups=x.shape[-1])
        x = _conv_bias_relu(x, weights, f"{blk['name']}_pw")
        if "head" in blk:
            feats[blk["head"]] = x
    locs, confs = [], []
    for idx in range(len(shapes["heads"])):
        f = feats[idx]
        b = f.shape[0]
        locs.append(_conv(f, weights[f"head{idx}_loc_w"]).reshape(b, -1, 4))
        conf = _conv(f, weights[f"head{idx}_conf_w"]).reshape(
            b, -1, shapes["num_classes"])
        confs.append(jax.nn.softmax(conf, axis=-1))
    return jnp.concatenate(locs, axis=1), jnp.concatenate(confs, axis=1)


# ------------------------------------------------------------ boxes, NMS


def make_anchors(shapes) -> np.ndarray:
    """Clustered prior boxes, normalised cxcywh, in head order: per cell
    (row-major) one box per (width, height) pair, centred at
    (x + 0.5) * step."""
    ih, iw = shapes["detector_input_hw"]
    out = []
    for head in shapes["heads"]:
        step = head["step"]
        fh, fw = -(-ih // step), -(-iw // step)
        for y, x in itertools.product(range(fh), range(fw)):
            for w, h in zip(head["prior_widths"], head["prior_heights"]):
                out.append([(x + 0.5) * step / iw, (y + 0.5) * step / ih,
                            w / iw, h / ih])
    return np.asarray(out, np.float32)


def decode(loc: np.ndarray, anchors: np.ndarray,
           variances=(0.1, 0.1, 0.2, 0.2)) -> np.ndarray:
    """Centre-offset decode to corner boxes clipped to the unit square."""
    acx, acy, aw, ah = anchors.T
    cx = acx + loc[:, 0] * variances[0] * aw
    cy = acy + loc[:, 1] * variances[1] * ah
    w = aw * np.exp(np.clip(loc[:, 2] * variances[2], -10, 10))
    h = ah * np.exp(np.clip(loc[:, 3] * variances[3], -10, 10))
    boxes = np.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2], axis=1)
    return np.clip(boxes, 0.0, 1.0)


def softmax(x: np.ndarray) -> np.ndarray:
    e = np.exp(x - x.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


def iou_one_to_many(box: np.ndarray, boxes: np.ndarray) -> np.ndarray:
    x0 = np.maximum(box[0], boxes[:, 0])
    y0 = np.maximum(box[1], boxes[:, 1])
    x1 = np.minimum(box[2], boxes[:, 2])
    y1 = np.minimum(box[3], boxes[:, 3])
    inter = np.clip(x1 - x0, 0, None) * np.clip(y1 - y0, 0, None)
    area = (box[2] - box[0]) * (box[3] - box[1])
    areas = (boxes[:, 2] - boxes[:, 0]) * (boxes[:, 3] - boxes[:, 1])
    return inter / np.maximum(area + areas - inter, 1e-12)


def greedy_nms(boxes, scores, labels, top_k=32, iou_thr=0.45,
               score_floor=0.1):
    """The published semantics, sequentially: the top_k anchors by best
    foreground score over the floor, then class-aware greedy
    suppression. Returns the kept anchor indices, best first."""
    order = np.argsort(-scores, kind="stable")[:top_k]
    order = [i for i in order if scores[i] >= score_floor]
    kept: list[int] = []
    for i in order:
        same = [j for j in kept if labels[j] == labels[i]]
        if same and iou_one_to_many(boxes[i], boxes[same]).max() > iou_thr:
            continue
        kept.append(int(i))
    return kept
