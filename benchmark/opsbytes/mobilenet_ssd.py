"""Operations and compulsory bytes of one device step of the detector,
from shapes alone (the configuration file's ``shapes``).

Counted: every convolution of the ladder and of the heads, 2 operations
per multiply-accumulate. Not counted: wire decode, softmax, box decode,
NMS: they are a few per cent and leaving them out can only make a roofline
share read low, never over 100 %.

Bytes are the traffic the algorithm cannot avoid: the wire frames in, the
parameters once per step, the packed result out. Activations are not
counted (a perfect schedule keeps them on chip), so the memory bound is a
floor; ``ops_and_bytes`` lets the caller see which of the two is larger.
"""

from __future__ import annotations


def _conv(h, w, k, cin, cout, groups=1, bias=True):
    """(operations, parameter count) of one conv at output h x w."""
    macs = h * w * k * k * (cin // groups) * cout
    return 2 * macs, k * k * (cin // groups) * cout + (cout if bias else 0)


def detector(shapes: dict) -> tuple[int, int]:
    """(operations per frame, parameter count)."""
    h, w = shapes["detector_input_hw"]
    ops = params = 0

    def add(t):
        nonlocal ops, params
        ops += t[0]
        params += t[1]

    stem = shapes["stem"]
    h, w = -(-h // stem["stride"]), -(-w // stem["stride"])
    add(_conv(h, w, stem["kernel"], 3, stem["out"]))
    c = stem["out"]
    feats = {}
    for blk in shapes["blocks"]:
        h, w = -(-h // blk["stride"]), -(-w // blk["stride"])
        add(_conv(h, w, 3, c, c, groups=c))
        add(_conv(h, w, 1, c, blk["out"]))
        c = blk["out"]
        if "head" in blk:
            feats[blk["head"]] = (h, w, c)
    for idx, head in enumerate(shapes["heads"]):
        fh, fw, fc = feats[idx]
        a = len(head["prior_widths"])
        add(_conv(fh, fw, 1, fc, a * 4, bias=False))
        add(_conv(fh, fw, 1, fc, a * shapes["num_classes"], bias=False))
    return ops, params


def ops_and_bytes(shapes: dict, batch: int) -> dict:
    """One step over ``batch`` frames (the bucket the program ran, pad
    rows included: the device computes them)."""
    d_ops, d_params = detector(shapes)
    wh, ww = shapes["wire_hw"]
    wire = wh * ww * 3 // 2 if shapes["wire_format"] == "i420" else wh * ww * 3
    return {
        "flops": batch * d_ops,
        "bytes": (batch * wire
                  + d_params * shapes["param_bytes_per_value"]
                  + batch * shapes["max_detections"] * 7 * 4),
    }
