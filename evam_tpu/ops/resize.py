"""Separable-matmul image resize for TPU.

``jax.image.resize`` on an NHWC frame batch contracts over H and W
while the 3-element channel axis rides the 128-wide lane dimension —
~2% MXU utilization — and runs in float32 over the full-resolution
intermediate. Round-2 hardware profiling put the i420-decode +
1080p→512 resize at ~26 ms of the 57 ms fused detect step (the P1/P2
ladder rows looked free only because ending a linear pipeline in
``.sum()`` lets XLA collapse it algebraically; see PROFILE.md).

Bilinear resize is a linear operator per axis, so each axis is one
matmul with a precomputed interpolation matrix: a [B, H, W] *plane*
batch contracts H then W with W riding the lanes at full width —
proper MXU work in bfloat16 with f32 accumulation. The interpolation
matrices are extracted from ``jax.image.resize`` itself (resizing an
identity matrix yields exactly the per-axis weight matrix, antialias
and half-pixel conventions included), so the numerics match the
reference path by construction.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=64)
def resize_matrix(in_size: int, out_size: int) -> np.ndarray:
    """[out, in] bilinear (antialiased) interpolation matrix, float32.

    Pure numpy re-statement of jax.image.resize(method="linear")'s
    per-axis weight computation (triangle kernel at half-pixel
    centers, kernel widened by 1/scale when downscaling, rows
    normalized) — tests/test_ops.py pins equality against
    jax.image.resize itself. Computed host-side so tracing the resize
    path never needs a CPU jax backend (JAX_PLATFORMS may name the
    tpu only).
    """
    scale = out_size / in_size
    kernel_scale = min(scale, 1.0)  # antialias when downscaling
    sample = (np.arange(out_size, dtype=np.float64) + 0.5) / scale - 0.5
    x = (sample[:, None] - np.arange(in_size, dtype=np.float64)[None, :])
    w = np.clip(1.0 - np.abs(x * kernel_scale), 0.0, 1.0)
    total = w.sum(axis=1, keepdims=True)
    return (w / np.where(total == 0.0, 1.0, total)).astype(np.float32)


def resize_planes(
    x: jnp.ndarray,
    out_hw: tuple[int, int],
    compute_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Bilinear-resize a stack of planes [..., H, W] → [..., th, tw].

    Two einsum contractions (rows, then columns) in ``compute_dtype``
    with float32 accumulation; returns float32. The intermediate is
    cast back to ``compute_dtype`` between the contractions so both
    ride the MXU's bf16 path — that round-trip costs ~1 LSB of u8
    luma vs jax.image.resize's all-f32 result (tests/test_ops.py pins
    atol < 2.0 on a 0-255 scale). Pass ``compute_dtype=jnp.float32``
    for near-exact parity (f32 matmul vs compiled gather/scatter
    rounding only).
    """
    th, tw = out_hw
    h, w = x.shape[-2], x.shape[-1]
    if (h, w) == (th, tw):
        return x.astype(jnp.float32)
    my = jnp.asarray(resize_matrix(h, th), compute_dtype)  # [th, h]
    mx = jnp.asarray(resize_matrix(w, tw), compute_dtype)  # [tw, w]
    xc = x.astype(compute_dtype)
    y = jnp.einsum(
        "...hw,yh->...yw", xc, my, preferred_element_type=jnp.float32
    ).astype(compute_dtype)
    return jnp.einsum(
        "...yw,xw->...yx", y, mx, preferred_element_type=jnp.float32
    )


def resize_nhwc(x: jnp.ndarray, out_hw: tuple[int, int]) -> jnp.ndarray:
    """[B, H, W, C] → [B, th, tw, C] float32, planes via channel-major.

    Moves C next to B (cheap relative to the resize itself) so the
    contractions run plane-wise with W in the lanes.
    """
    if x.shape[1:3] == tuple(out_hw):
        return x.astype(jnp.float32)
    xc = jnp.moveaxis(x, -1, 1)  # [B, C, H, W]
    z = resize_planes(xc, out_hw)
    return jnp.moveaxis(z, 1, -1)
