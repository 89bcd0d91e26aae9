"""Compile the Pallas int8 GEMM through Mosaic on the chip and check it
against the XLA int8 path.

``pallas_quant_dense`` runs NON-interpreted at the (m, k, n) the int8
zoo nets feed it with ``EVAM_QGEMM=pallas`` (their 1x1 convolutions,
m = batch x pixels), plus the m <= 8 case where the row tile is 8, and
must agree with ``qlinear.quant_dense`` within the tolerance
tests/test_quant.py::TestPallasQGemm uses under the interpreter.
Exits non-zero on any backend other than a TPU, on a Mosaic compile
error, or on a mismatch. Run it alone: one process per chip.

    python tools/check_pallas_qgemm.py
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from evam_tpu.ops.pallas_qgemm import pallas_quant_dense  # noqa: E402
from evam_tpu.ops.qlinear import quant_dense  # noqa: E402

#: (m, k, n): the row tile is min(128, m rounded up to 8)
SHAPES = (
    (8, 512, 256),       # tile_m = 8, below the int8 native sublane tile
    (16, 128, 256),      # emotion_recognition b=1, last 1x1
    (25, 128, 256),      # vehicle_attributes b=1 (pads to 32)
    (45, 512, 256),      # person detector b=1 (pads to 48)
    (64, 512, 256),      # person_vehicle_bike b=1, deepest 1x1
    (200, 128, 256),     # vehicle_attributes at one frame's 8 ROIs
    (256, 256, 512),
    (1024, 128, 256),
    (4096, 64, 128),
    (16384, 32, 64),     # person_vehicle_bike b=1, first 1x1 (k pads to 128)
    (131072, 64, 64),    # the same layer at batch 8
)
RTOL = ATOL = 2e-5


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"check_pallas_qgemm: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    rng = np.random.default_rng(0)
    rows = []
    ok = True
    for m, k, n in SHAPES:
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(k, n)) * 0.2, jnp.float32)
        b = jnp.asarray(rng.normal(size=(n,)) * 0.1, jnp.float32)
        ref = np.asarray(quant_dense(x, w, b))
        got = np.asarray(pallas_quant_dense(x, w, b))  # Mosaic, not interpret
        close = np.isclose(got, ref, rtol=RTOL, atol=ATOL)
        row = {"m": m, "k": k, "n": n, "match": bool(close.all()),
               "mismatched": int((~close).sum()),
               "max_abs_diff": float(np.abs(got - ref).max())}
        ok &= row["match"]
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "pallas_qgemm_ok": ok, "shapes": len(rows),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
