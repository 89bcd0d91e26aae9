"""Pallas TPU kernel: a decode step's one token through a GATED SHORT
CONVOLUTION whose only state is its taps, each row's taps moved in place.

The mixer (models/lm/lfm2_moe.py): ``[B, C, x] = W_in u``; ``z_t = sum_j
w_j * (B * x)_{t-K+1+j}`` over ``K`` taps (depthwise, causal, no bias, no
activation); ``y_t = C_t * z_t``. Between steps a sequence carries the last
``K - 1`` values of ``B * x`` and nothing else: per slot and layer a
bfloat16 row of ``(K - 1) * width`` values, taps side by side, oldest
first, kept as whole tiles (``slot_rows.tiled``).

``decode_rows`` runs one token a row over the WHOLE array ``taps`` [layers,
rows, 16, (K - 1) * width / 16] through ops/slot_rows.py's addressing: the
layer and the slot ids are prefetched scalars, grid step ``b`` brings block
``taps[l, slot[b]]`` into VMEM, computes ``z`` and ``y`` in float32 on the
vector unit and writes back ``[t_1, ..., t_{K-2}, B x]`` where the block
came from; the array is aliased in and out, so no row is gathered,
scattered or copied. A row that is not live writes back what it read and
comes out zero. The row moves 2 x 8 KB at the published width: the kernel's
cost is its grid steps' latency, not its bytes. ``decode_rows_xla`` is the
same through XLA (the named rows gathered, the rows put back with those
that are not live dropped); the CPU tests run it, and check the kernel
against it in the interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from evam_tpu.ops import slot_rows

F32 = jnp.float32
#: a float32 tile's sublanes: a row's ``width`` values are kept as
#: ``[SUBLANES, width / SUBLANES]``, one tap's share of a slot's tile
SUBLANES = 8


def _kernel(l_ref, slot_ref, live_ref, bx_ref, c_ref, w_ref, taps_ref, y_ref,
            out_ref, *, k1: int):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    rows = bx_ref.shape[0]

    @pl.when(live_ref[i] > 0)
    def _():
        # float32 tiles: a tap is SUBLANES whole rows of them
        old = taps_ref[...].astype(F32)
        bx = bx_ref[...]
        z = w_ref[k1] * bx
        for j in range(k1):
            z = z + w_ref[j] * old[j * rows:(j + 1) * rows]
        y_ref[...] = c_ref[...] * z
        out_ref[...] = jnp.concatenate(
            [old[rows:], bx], axis=0).astype(out_ref.dtype)

    @pl.when(live_ref[i] == 0)
    def _():
        slot_rows.keep((taps_ref, out_ref))
        y_ref[...] = jnp.zeros(y_ref.shape, F32)


def _split(x, k1: int, tile: tuple):
    """[B, width] -> float32 [B, tile rows / k1, tile columns]: one tap's
    share of a slot's tile."""
    return x.astype(F32).reshape(x.shape[0], tile[0] // k1, tile[1])


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_rows(l, slot, live, bx, c, w, taps, *, interpret=False):
    """One token a row, the taps moved IN PLACE (ops/slot_rows.py):
    ``bx`` (the newest input ``B * x``), ``c`` [B, width]; ``w`` [K,
    width], oldest tap first; ``taps`` [layers, rows, *tile]
    (``slot_rows.tiled((K - 1) * width)``), of which live row ``i`` reads
    and writes ``taps[l, slot[i]]`` -> (``y`` [B, width] float32, zero
    where not live, ``taps``)."""
    rows, width = bx.shape
    k1 = w.shape[0] - 1
    tile = taps.shape[2:]
    if tile[0] % k1 or (tile[0] // k1) % SUBLANES:
        raise ValueError(f"{k1} taps do not share a tile of {tile[0]} rows "
                         f"in whole float32 tiles")
    part = (tile[0] // k1, tile[1])
    y, taps = slot_rows.call(
        functools.partial(_kernel, k1=k1), "conv_decode_rows", l, slot, live,
        [_split(bx, k1, tile), _split(c, k1, tile),
         w.astype(F32).reshape(k1 + 1, *part)],
        [slot_rows.per_row(*part), slot_rows.per_row(*part),
         slot_rows.shared(k1 + 1, *part)],
        [jax.ShapeDtypeStruct((rows, *part), F32)],
        [slot_rows.per_row(*part)],
        [taps], [slot_rows.at_slot(*tile)], interpret=interpret)
    return y.reshape(rows, width), taps


def decode_rows_xla(l, slot, live, bx, c, w, taps):
    """The same through XLA: the named rows gathered, the convolution as
    written, the rows put back with those that are not live dropped."""
    rows, width = bx.shape
    k1 = w.shape[0] - 1
    bx32, c32, w32 = (jnp.asarray(x, F32) for x in (bx, c, w))
    old = taps[l, slot].reshape(rows, k1, width).astype(F32)
    z = w32[k1] * bx32
    for j in range(k1):
        z = z + w32[j] * old[:, j]
    new = jnp.concatenate([old[:, 1:].reshape(rows, -1), bx32], axis=1)
    return (jnp.where(live[:, None], c32 * z, 0.0),
            slot_rows.put(taps, l, slot, live,
                          new.reshape(rows, *taps.shape[2:]), check=True))
