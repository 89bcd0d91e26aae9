"""Pallas TPU kernel: int8 GEMM with fused dynamic activation
quantization (the hot matmul of the quantized serving path).

``quant_dense``/1×1-conv matmuls in ops/qlinear.py lower through XLA
as quantize → int8 dot → dequant; this kernel fuses all three into
one VMEM round-trip per tile: the activation tile is scaled/rounded
to int8 *in VMEM*, hits the MXU against the pre-quantized weight
tile, and the int32 accumulator is rescaled to float on the way out —
activations never return to HBM between the three phases.

Selectable A/B (default stays XLA): ``EVAM_QGEMM=pallas`` routes
qlinear's dense path here. The kernel compiles through Mosaic, so the
knob needs a TPU; the Pallas interpreter runs only where a caller
passes ``interpret=True`` (tests/test_quant.py::TestPallasQGemm pins
correctness against the XLA path that way on the CPU).

Tiling: M blocks of 128 rows (f32 sublane-aligned), full K and
N-block 128 resident in VMEM — detection/classifier matmuls have
K, N ≤ 512·4, well inside the ~16 MB VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from evam_tpu.ops.qlinear import quantize_weight


def _qgemm_kernel(x_ref, wq_ref, wscale_ref, out_ref):
    """One (TILE_M, K) × (K, TILE_N) tile: quantize rows → int8 MXU
    dot → dequantize."""
    x = x_ref[:].astype(jnp.float32)
    # per-row dynamic scale (batch-composition independent, matching
    # qlinear.quantize_act)
    row_max = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    row_scale = jnp.maximum(row_max / 127.0, 1e-8)
    xq = jnp.clip(jnp.round(x / row_scale), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xq, wq_ref[:],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out_ref[:] = acc.astype(jnp.float32) * row_scale * wscale_ref[:]


@functools.partial(
    jax.jit, static_argnames=("tile_m", "tile_n", "interpret"))
def _qgemm(x, wq, w_scale, *, tile_m, tile_n, interpret=False):
    from jax.experimental import pallas as pl

    m, k = x.shape
    n = wq.shape[1]
    grid = (m // tile_m, n // tile_n)
    return pl.pallas_call(
        _qgemm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, tile_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, tile_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, wq, w_scale)


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def pallas_quant_dense(
    x: jnp.ndarray,
    kernel: jnp.ndarray,
    bias: jnp.ndarray | None,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Drop-in for qlinear.quant_dense via the fused pallas kernel.

    Shapes are padded to Mosaic-friendly tiles: lanes (n, k) to
    128-multiples, sublanes (m) to 8-multiples (128 once m exceeds a
    tile). K stays un-tiled — one (tile, K) f32 block plus a
    (K, 128) int8 weight block fit VMEM comfortably for every matmul
    in the zoo (K ≤ 2048).
    """
    m, k = x.shape
    n = kernel.shape[1]
    if m == 0:
        out = jnp.zeros((0, n), jnp.float32)
        return out + bias.astype(jnp.float32) if bias is not None else out
    wq, w_scale = quantize_weight(kernel)

    pm = _round_up(m, 128) if m > 127 else _round_up(m, 8)
    pn = _round_up(n, 128)
    pk = _round_up(k, 128)
    tile_m = min(128, pm)
    tile_n = 128
    xp = jnp.pad(x, ((0, pm - m), (0, pk - k)))
    wqp = jnp.pad(wq, ((0, pk - k), (0, pn - n)))
    wsp = jnp.pad(
        w_scale.reshape(1, -1), ((0, 0), (0, pn - n)), constant_values=1.0)

    out = _qgemm(
        xp, wqp, wsp, tile_m=tile_m, tile_n=tile_n, interpret=interpret,
    )[:m, :n]
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out
