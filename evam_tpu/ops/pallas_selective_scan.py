"""Pallas TPU kernel: the selective scan (Mamba-1) of a packed prefill
chunk, the state resident in VMEM.

Per channel ``c`` of ``d_inner`` and state index ``n`` of ``d_state`` the
recurrence over a segment's tokens is

    s_t[n, c] = exp(dt_t[c] * A[n, c]) * s_{t-1}[n, c] + dt_t[c] * u_t[c] * B_t[n]
    y_t[c]    = (sum_n s_t[n, c] * C_t[n] + D[c] * u_t[c]) * silu(z_t[c])

Through XLA a chunk's scan materialises ``[tokens, d_state, d_inner]``
float32 several times over (168 MB a layer at 512 x 16 x 5120). Here one
block of channels keeps its state ``[d_state, block]`` in registers while
the chunk's tokens stream past, so the state costs no HBM bytes inside a
chunk: in come ``u``, ``dt``, ``z`` [T, d_inner] and ``B``, ``C``
[T, d_state], out goes ``y`` [T, d_inner].

The chunk is PACKED (engine/generate.py): ``seg`` [T] gives each token's
segment (-1: padding), segments are contiguous, and each starts from its
own initial state ``h0`` [segments, d_state, d_inner] (the prefix
snapshot for a new sequence, the slot's state for a prompt that
continues from the chunk before). The state after each segment's last
token comes back as ``h_end``; a segment with no token here keeps its
``h0``. ``d_inner`` lies on the lanes throughout.

``selective_scan_xla`` is the same arithmetic as a ``lax.scan`` over the
tokens; the CPU tests run it, and check the kernel against it in the
interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from evam_tpu.ops import slot_rows

F32 = jnp.float32
LANES = 128


def _flags(seg):
    """Per token: its segment (0 for padding), whether it opens its
    segment here, whether it closes it here."""
    live = seg >= 0
    prev = jnp.concatenate([jnp.full((1,), -2, seg.dtype), seg[:-1]])
    nxt = jnp.concatenate([seg[1:], jnp.full((1,), -2, seg.dtype)])
    return (jnp.maximum(seg, 0).astype(jnp.int32),
            (live & (prev != seg)).astype(jnp.int32),
            (live & (nxt != seg)).astype(jnp.int32))


def _lanes(x, width: int):
    """[n, 128] -> [n, width]: a token's ``B`` or ``C`` over a block of
    channels."""
    return jnp.concatenate([x] * (width // x.shape[1]), axis=1)


def _kernel(seg_ref, start_ref, end_ref, u_ref, dt_ref, z_ref, b_ref, c_ref,
            a_ref, d_ref, h0_ref, y_ref, hend_ref, *, unroll):
    from jax.experimental import pallas as pl

    t_all = u_ref.shape[0]
    n, bc = a_ref.shape
    a = a_ref[...]
    d = d_ref[...]
    hend_ref[...] = h0_ref[...]

    def token(t, h):
        s = seg_ref[t]
        h = jnp.where(start_ref[t] > 0, h0_ref[s], h)
        row = pl.ds(t, 1)
        u = u_ref[row, :]
        dt = dt_ref[row, :]
        h = jnp.exp(dt * a) * h + (dt * u) * _lanes(b_ref[t], bc)
        y = jnp.sum(h * _lanes(c_ref[t], bc), axis=0, keepdims=True) + d * u
        z = z_ref[row, :]
        y_ref[row, :] = y * (z * jax.nn.sigmoid(z))

        @pl.when(end_ref[t] > 0)
        def _():
            hend_ref[s] = h

        return h

    def body(i, h):  # ``unroll`` tokens a trip, written out
        for k in range(unroll):
            h = token(i * unroll + k, h)
        return h

    jax.lax.fori_loop(0, t_all // unroll, body, jnp.zeros((n, bc), F32))


@functools.partial(jax.jit, static_argnames=("block_c", "unroll",
                                             "interpret"))
def selective_scan(u, dt, z, b, c, a, d, seg, h0, *, block_c=512, unroll=4,
                   interpret=False):
    """``u``, ``dt``, ``z`` [T, C]; ``b``, ``c`` [T, N]; ``a`` [N, C];
    ``d`` [C]; ``seg`` [T] int32; ``h0`` [S, N, C] float32 ->
    (``y`` [T, C] float32, ``h_end`` [S, N, C] float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, ch = u.shape
    n = a.shape[0]
    s = h0.shape[0]
    block_c = min(block_c, ch)
    if t % unroll:
        unroll = 1
    if ch % block_c or block_c % LANES:
        raise ValueError(f"d_inner {ch} is not whole blocks of {block_c} "
                         f"lanes")
    # B_t[n] and C_t[n] multiply a whole row of lanes: broadcast here, so
    # that the kernel reads a token's [N, 128] tile with no relayout
    wide = [jnp.broadcast_to(x.astype(F32)[:, :, None], (t, n, LANES))
            for x in (b, c)]
    cols = lambda i, *_: (0, i)  # noqa: E731
    whole = lambda i, *_: (0, 0, 0)  # noqa: E731
    y, h_end = pl.pallas_call(
        functools.partial(_kernel, unroll=unroll),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(ch // block_c,),
            in_specs=[
                pl.BlockSpec((t, block_c), cols),
                pl.BlockSpec((t, block_c), cols),
                pl.BlockSpec((t, block_c), cols),
                pl.BlockSpec((t, n, LANES), whole),
                pl.BlockSpec((t, n, LANES), whole),
                pl.BlockSpec((n, block_c), cols),
                pl.BlockSpec((1, block_c), cols),
                pl.BlockSpec((s, n, block_c), lambda i, *_: (0, 0, i)),
            ],
            out_specs=[
                pl.BlockSpec((t, block_c), cols),
                pl.BlockSpec((s, n, block_c), lambda i, *_: (0, 0, i)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((t, ch), F32),
                   jax.ShapeDtypeStruct((s, n, ch), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="ssm_selective_scan",
        interpret=interpret,
    )(*_flags(seg), u.astype(F32), dt.astype(F32), z.astype(F32), *wide,
      a.astype(F32), d.astype(F32).reshape(1, ch), h0.astype(F32))
    return y, h_end


def selective_scan_xla(u, dt, z, b, c, a, d, seg, h0):
    """The same through XLA: a ``lax.scan`` over the tokens (float32)."""
    u, dt, z, b, c, a, d, h0 = (jnp.asarray(x, F32) for x in (
        u, dt, z, b, c, a, d, h0))
    seg_i, start, end = _flags(jnp.asarray(seg))

    def step(carry, row):
        h, h_end = carry
        u_t, dt_t, b_t, c_t, s, st, en = row
        h = jnp.where(st > 0, h0[s], h)
        h = jnp.exp(dt_t[None, :] * a) * h + (dt_t * u_t)[None, :] * b_t[:, None]
        y = jnp.sum(h * c_t[:, None], axis=0) + d * u_t
        h_end = jnp.where(en > 0, h_end.at[s].set(h), h_end)
        return (h, h_end), y

    (_, h_end), y = jax.lax.scan(
        step, (jnp.zeros_like(h0[0]), h0), (u, dt, b, c, seg_i, start, end))
    return y * (z * jax.nn.sigmoid(z)), h_end


# ------------------------------------------- a decode step's one token


def _rows_kernel(l_ref, slot_ref, live_ref, dt_ref, u_ref, z_ref, b_ref,
                 c_ref, a_ref, d_ref, taps_ref, h_ref, conv_ref, y_ref,
                 hout_ref, convout_ref, *, block_c):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    row = pl.ds(i % dt_ref.shape[0], 1)
    ch = a_ref.shape[1]

    @pl.when(live_ref[i] > 0)
    def _():
        b, c = _lanes(b_ref[...], block_c), _lanes(c_ref[...], block_c)
        for c0 in range(0, ch, block_c):
            cols = slice(c0, c0 + block_c)
            dt, u, z = (r[row, cols] for r in (dt_ref, u_ref, z_ref))
            h = jnp.exp(dt * a_ref[:, cols]) * h_ref[:, cols] + (dt * u) * b
            hout_ref[:, cols] = h
            y = jnp.sum(h * c, axis=0, keepdims=True) + d_ref[:, cols] * u
            y_ref[row, cols] = y * (z * jax.nn.sigmoid(z))
        convout_ref[...] = taps_ref[...]

    @pl.when(live_ref[i] == 0)
    def _():
        slot_rows.keep((h_ref, hout_ref), (conv_ref, convout_ref))
        y_ref[row, :] = jnp.zeros((1, ch), F32)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def decode_rows(l, slot, live, dt, u, z, b, c, a, d, taps, state, conv, *,
                block_c=512, interpret=False):
    """One token a row, the slot state moved IN PLACE (ops/slot_rows.py):
    ``dt``, ``u``, ``z`` [B, C]; ``b``, ``c`` [B, N]; ``a`` [N, C]; ``d``
    [C]; ``state`` [layers, rows, N, C] float32 and ``conv`` [layers,
    rows, *tile] (``slot_rows.tiled``), of which live row ``i`` reads and
    writes ``state[l, slot[i]]`` and takes ``taps[i]`` [*tile] for
    ``conv[l, slot[i]]`` -> (``y`` [B, C] float32, zero where not live,
    ``state``, ``conv``): the recurrence at the head of this file for one
    token, in float32."""
    rows, ch = dt.shape
    n = a.shape[0]
    tile = conv.shape[2:]
    if ch % block_c or block_c % LANES:
        block_c = ch
    wide = [jnp.broadcast_to(x.astype(F32)[:, :, None],
                             (rows, n, min(LANES, block_c))) for x in (b, c)]
    row = slot_rows.grouped(rows, ch)
    bc = slot_rows.per_row(*wide[0].shape[1:])
    return slot_rows.call(
        functools.partial(_rows_kernel, block_c=block_c), "ssm_decode_rows",
        l, slot, live,
        [dt.astype(F32), u.astype(F32), z.astype(F32), *wide, a.astype(F32),
         d.astype(F32).reshape(1, ch), taps.astype(conv.dtype)],
        [row, row, row, bc, bc, slot_rows.shared(n, ch),
         slot_rows.shared(1, ch), slot_rows.per_row(*tile)],
        [jax.ShapeDtypeStruct((rows, ch), F32)], [row],
        [state, conv], [slot_rows.at_slot(n, ch), slot_rows.at_slot(*tile)],
        interpret=interpret)


def decode_rows_xla(l, slot, live, dt, u, z, b, c, a, d, taps, state, conv):
    """The same through XLA: the named rows gathered, the recurrence as
    written, the rows put back with those that are not live dropped."""
    dt, u, z, b, c, a, d = (jnp.asarray(x, F32) for x in (dt, u, z, b, c, a,
                                                           d))
    h = (jnp.exp(dt[:, None, :] * a[None]) * state[l, slot]
         + (dt * u)[:, None, :] * b[:, :, None])
    y = (h * c[:, :, None]).sum(axis=1) + d * u
    y = y * (z * jax.nn.sigmoid(z))
    return (jnp.where(live[:, None], y, 0.0),
            slot_rows.put(state, l, slot, live, h, check=True),
            slot_rows.put(conv, l, slot, live, taps))
