"""Pallas TPU kernels: the Mamba-2 recurrence (state-space duality,
arXiv:2405.21060) over a packed prefill chunk in its chunkwise dual form, the
state resident in VMEM, and a decode step's one token a row on the slot
state in place.

Per head (``P`` channels, a state ``h`` [P, N] float32, ONE decay a head)
the recurrence over a segment's tokens is

    h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t        y_t = h_t C_t

with ``B_t``, ``C_t`` [N] shared by the heads of a group. Token by token
every token writes the whole state (4 MB for 128 heads of 64 x 128), 2 GB a
chunk and layer. In blocks of ``BLOCK`` tokens, with ``G_t`` the running sum
of ``dt a`` inside the block and ``h_0`` the state before it,

    y_t   = exp(G_t) C_t h_0 + sum_{s <= t} exp(G_t - G_s) (C_t . B_s) dt_s x_s
    h_end = exp(G_end) h_0 + sum_s exp(G_end - G_s) dt_s x_s (x) B_s

so the work is four matrix products a block and pair of heads, on the MXU:
``C B^T`` once a group (its 16 heads share it), the masked and decayed
scores times ``x``, ``C`` times the carried state, and ``B^T`` times the
weighted ``x`` into the state. Every exponent is a difference ``G_t - G_s``
with ``s <= t``, so none is positive and nothing overflows whatever the
decay. ``G`` is a cumulative sum in float32 made by the caller (``inputs``).

The state lies as ``[H/2, N, 2P]``: TWO heads side by side on the lanes
(2 x 64 = one lane tile), the state index down the sublanes. A product with
``x`` then takes a pair's 128 lanes as they lie and no operand is sliced at
half a tile; the decode body's contraction over ``N`` is a sum down the
sublanes, and a head pair's ``y`` comes out as one dense row.

The chunk is PACKED (engine/generate.py): ``seg`` [T] gives each token's
segment (-1: a dead row), a segment's tokens are contiguous, and a segment
may start at ANY token (the family's ``SEGMENT_ALIGN`` is 1). The grid walks
VISITS, as ops/pallas_grouped.py's: the (block, segment) pairs in which a
block of ``BLOCK`` tokens holds tokens of the segment, in order, each with
the range of the block's rows that are the segment's. A visit works on its
range alone (what lies outside decays nothing, writes nothing and is not
written), takes the state its segment carried from the visit before or, where
it OPENS the segment, the segment's own ``h0`` row, and where it CLOSES it
writes the state to ``h_end``. A block that holds two segments is visited
twice, and its output block stays in VMEM between the two. Dead rows are
never written: the caller masks them. A segment with no token here keeps
its ``h0``.

``chunk_scan_xla`` is the same recurrence as a ``lax.scan`` over the tokens
(any packing); the CPU tests run it, and check the kernel against it in the
interpreter. ``decode_rows`` / ``decode_rows_xla``: the recurrence's one
token for every row of a decode step, the state addressed by ``(layer,
slot)`` and moved in place (ops/slot_rows.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from evam_tpu.ops import slot_rows
from evam_tpu.ops.pallas_selective_scan import _flags

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
#: tokens solved together (the family's ``chunk_size``)
BLOCK = 128
LANES = 128


def visits(seg, n_seg: int):
    """The (block, segment) pairs of a packed chunk in which the block
    holds tokens of the segment, in order: ``(blk, row, lo, hi, opens,
    closes, n)``, the first six [T / block + n_seg] int32 of which the
    first ``n`` count (one past them repeats the last block, names the
    spare state row ``n_seg`` and has an empty range). ``lo``/``hi``: the
    block's rows that are the segment's; ``opens``/``closes``: whether the
    segment's first / last token of the chunk lies in them."""
    t, block = seg.shape[0], BLOCK
    v_max = t // block + n_seg
    idx = jnp.arange(t, dtype=jnp.int32)
    seg_i, start, end = _flags(seg)
    live = seg >= 0
    begin = live & ((start > 0) | (idx % block == 0))
    last = live & ((end > 0) | (idx % block == block - 1))
    n = begin.sum().astype(jnp.int32)
    first, final = (
        jnp.argsort(jnp.where(at, 0, 1), stable=True)[:v_max].astype(
            jnp.int32) for at in (begin, last))
    real = jnp.arange(v_max) < n
    keep = jnp.maximum(n - 1, 0)
    blk = jnp.where(real, first // block, first[keep] // block)
    return (blk.astype(jnp.int32),
            jnp.where(real, seg_i[first], n_seg).astype(jnp.int32),
            jnp.where(real, first % block, 0).astype(jnp.int32),
            jnp.where(real, final % block + 1, 0).astype(jnp.int32),
            jnp.where(real, start[first], 0).astype(jnp.int32),
            jnp.where(real, end[final], 0).astype(jnp.int32), n)


def inputs(dt, a, seg, groups: int):
    """What the kernel reads of the step sizes: ``dt`` [T, H] float32
    (dead rows zeroed here), ``a`` [H] -> the running sum of ``dt a``
    inside each block and ``dt``, each twice: heads on the lanes ``[groups,
    T, H / groups]`` (a column per head) and tokens on the lanes ``[groups,
    H / groups, T]`` (a row per head)."""
    t, heads = dt.shape
    dt = jnp.where((seg >= 0)[:, None], dt.astype(F32), 0.0)
    cum = jnp.cumsum((dt * a.astype(F32)).reshape(t // BLOCK, BLOCK, heads),
                     axis=1).reshape(t, heads)
    cols = [x.reshape(t, groups, heads // groups).transpose(1, 0, 2)
            for x in (cum, dt)]
    return (cols[0], cols[0].transpose(0, 2, 1), cols[1],
            cols[1].transpose(0, 2, 1))


def _kernel(blk_ref, row_ref, lo_ref, hi_ref, open_ref, close_ref, n_ref,
            x_ref, b_ref, c_ref, cumc_ref, cumr_ref, dtc_ref, dtr_ref,
            h0_ref, y_ref, hend_ref, s_ref):
    from jax.experimental import pallas as pl

    v = pl.program_id(1)
    block = x_ref.shape[0]
    pairs = s_ref.shape[0]
    half = s_ref.shape[2] // 2

    @pl.when(open_ref[v] > 0)
    def _():
        s_ref[...] = h0_ref[...]

    @pl.when(v < n_ref[0])
    def _():
        lo, hi = lo_ref[v], hi_ref[v]
        t_col = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        s_row = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        mine_col = (t_col >= lo) & (t_col < hi)
        cum_c, cum_r = cumc_ref[...], cumr_ref[...]
        dt_c, dt_r = dtc_ref[...], dtr_ref[...]
        # the running sum before the range's first row and at its last
        base = jnp.sum(jnp.where(t_col == lo - 1, cum_c, 0.0), axis=0,
                       keepdims=True)
        total = jnp.sum(jnp.where(t_col == hi - 1, cum_c, 0.0), axis=0,
                        keepdims=True)
        b, c = b_ref[...], c_ref[...]
        cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)
        causal = (t_col >= s_row) & (s_row >= lo)
        cb = jnp.where(causal, cb, 0.0)
        b32, c32 = b.astype(F32), c.astype(F32)
        x = x_ref[...].astype(F32)
        left = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * half), 1) < half
        ys = []
        for j in range(pairs):
            h_a, h_b = 2 * j, 2 * j + 1
            x_j = x[:, j * 2 * half:(j + 1) * 2 * half]
            y_in = []
            for h in (h_a, h_b):
                dec = jnp.exp(jnp.minimum(
                    cum_c[:, h:h + 1] - cum_r[h:h + 1, :], 0.0))
                y_in.append(jnp.dot(cb * dec * dt_r[h:h + 1, :], x_j,
                                    precision=HI,
                                    preferred_element_type=F32))

            def lanes(col):  # a column a head -> the pair's two halves
                return jnp.where(left, col[:, h_a:h_a + 1],
                                 col[:, h_b:h_b + 1])

            s0 = s_ref[j]
            carried = jnp.exp(jnp.minimum(lanes(cum_c) - lanes(base), 0.0))
            ys.append(jnp.where(left, y_in[0], y_in[1]) + carried * jnp.dot(
                c32, s0, precision=HI, preferred_element_type=F32))
            w = jnp.where(
                mine_col,
                jnp.exp(jnp.minimum(lanes(total) - lanes(cum_c), 0.0))
                * lanes(dt_c), 0.0)
            s_ref[j] = (jnp.exp(lanes(total) - lanes(base)) * s0
                        + jax.lax.dot_general(
                            b32, x_j * w, (((0,), (0,)), ((), ())),
                            precision=HI, preferred_element_type=F32))
        y_ref[...] = jnp.where(mine_col, jnp.concatenate(ys, axis=1),
                               y_ref[...])

    @pl.when(close_ref[v] > 0)
    def _():
        hend_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def chunk_scan(x, dt, a, b, c, seg, h0, *, interpret=False):
    """``x`` [T, H * P] (heads side by side), ``dt`` [T, H] float32 (after
    its softplus), ``a`` [H] (negative), ``b``, ``c`` [T, G * N] (groups
    side by side), ``seg`` [T] int32, ``h0`` [S, H / 2, N, 2P] float32 ->
    (``y`` [T, H * P] float32, rows of no segment unspecified, ``h_end``
    [S, H / 2, N, 2P] float32). T is whole blocks of ``BLOCK``, N and 2P
    one lane tile each."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t = x.shape[0]
    n_seg, pairs, n, p2 = h0.shape
    heads, groups = dt.shape[1], b.shape[1] // n
    per = heads // groups
    if (t % BLOCK or n != LANES or p2 != LANES or per % 2
            or x.shape[1] != pairs * p2 or pairs * 2 != heads):
        raise ValueError(
            f"a chunk of {t} tokens x {x.shape[1]} over a state "
            f"{h0.shape[1:]} is not whole blocks of {BLOCK} tokens and "
            f"pairs of heads that fill a tile of {LANES} lanes each way")
    *flags, count = visits(seg, n_seg)
    wide = per // 2 * p2

    def tok(width):
        return pl.BlockSpec((BLOCK, width), lambda g, v, blk, *_: (blk[v], g))

    col = pl.BlockSpec((None, BLOCK, per),
                       lambda g, v, blk, *_: (g, blk[v], 0))
    row = pl.BlockSpec((None, per, BLOCK),
                       lambda g, v, blk, *_: (g, 0, blk[v]))
    state = pl.BlockSpec((None, per // 2, n, p2),
                         lambda g, v, blk, row, *_: (row[v], g, 0, 0))
    spare = jnp.concatenate([h0.astype(F32),
                             jnp.zeros((1, pairs, n, p2), F32)], axis=0)
    cum_c, cum_r, dt_c, dt_r = inputs(dt, a, seg, groups)
    y, h_end = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(groups, flags[0].shape[0]),
            in_specs=[tok(wide), tok(n), tok(n), col, row, col, row, state],
            out_specs=[tok(wide), state],
            scratch_shapes=[pltpu.VMEM((per // 2, n, p2), F32)]),
        out_shape=[jax.ShapeDtypeStruct((t, heads // 2 * p2), F32),
                   jax.ShapeDtypeStruct(spare.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="ssd_chunk_scan",
        interpret=interpret,
    )(*flags, jnp.reshape(count, (1,)), x, b, c, cum_c, cum_r, dt_c, dt_r,
      spare)
    # a segment with no token here was never visited: it keeps its h0
    visited = (seg[None, :] == jnp.arange(n_seg)[:, None]).any(axis=1)
    return y, jnp.where(visited[:, None, None, None], h_end[:n_seg], h0)


def _pairs(x, pairs: int):
    """A per-head array [..., H, P] as the state's lanes have it: [...,
    H / 2, 2P]."""
    return x.reshape(*x.shape[:-2], pairs, -1)


def chunk_scan_xla(x, dt, a, b, c, seg, h0):
    """The same through XLA: a ``lax.scan`` over the tokens, every product
    elementwise in float32 (dead rows come out zero)."""
    n_seg, pairs, n, p2 = h0.shape
    groups = b.shape[1] // n
    t = x.shape[0]
    x = jnp.asarray(x, F32).reshape(t, pairs, p2)
    dt = jnp.where((seg >= 0)[:, None], jnp.asarray(dt, F32), 0.0)

    def per_pair(v):  # [T, H] -> [T, H / 2, 2P]
        return _pairs(jnp.broadcast_to(v[:, :, None], (*v.shape, p2 // 2)),
                      pairs)

    def per_group(v):  # [T, G * N] -> [T, H / 2, N]
        return jnp.repeat(jnp.asarray(v, F32).reshape(t, groups, n),
                          pairs // groups, axis=1)

    decay = per_pair(jnp.exp(dt * jnp.asarray(a, F32)))
    h0 = jnp.asarray(h0, F32)

    def step(carry, tok):
        s, h_end = carry
        x_t, dt_t, dec_t, b_t, c_t, i, start, end = tok
        s = jnp.where(start > 0, h0[i], s)
        s = dec_t[:, None, :] * s + b_t[:, :, None] * (dt_t * x_t)[:, None, :]
        y = (s * c_t[:, :, None]).sum(axis=1)
        h_end = jnp.where(end > 0, h_end.at[i].set(s), h_end)
        return (s, h_end), y

    (_, h_end), y = jax.lax.scan(
        step, (jnp.zeros_like(h0[0]), h0),
        (x, per_pair(dt), decay, per_group(b), per_group(c),
         *_flags(jnp.asarray(seg))))
    y = jnp.where((seg >= 0)[:, None, None], y, 0.0)
    return y.reshape(t, pairs * p2), h_end


# ------------------------------------------- a decode step's one token


def _rows_kernel(l_ref, slot_ref, live_ref, rows_ref, bc_ref, taps_ref, s_ref,
                 conv_ref, y_ref, sout_ref, convout_ref, *, groups):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    pairs = s_ref.shape[0]
    per = pairs // groups

    @pl.when(live_ref[i] > 0)
    def _():
        # B | C, groups down the rows: a group's are two COLUMNS here, so
        # that they multiply the state's sublanes
        bc = bc_ref[...].T
        for g in range(groups):
            b_col = bc[:, g:g + 1]
            c_col = bc[:, groups + g:groups + g + 1]
            for j in range(g * per, (g + 1) * per):
                s = (rows_ref[j:j + 1, :] * s_ref[j]
                     + b_col * rows_ref[pairs + j:pairs + j + 1, :])
                sout_ref[j] = s
                y_ref[j:j + 1, :] = jnp.sum(s * c_col, axis=0, keepdims=True)
        convout_ref[...] = taps_ref[...]

    @pl.when(live_ref[i] == 0)
    def _():
        slot_rows.keep((s_ref, sout_ref), (conv_ref, convout_ref))
        y_ref[...] = jnp.zeros(y_ref.shape, F32)


def _row_operands(dt, a, x, b, c, pairs: int, n: int):
    """A step row's small operands as the decode body reads them: ``rows``
    [B, 2 * pairs, 2P] (the decay of every head over its lanes, then ``dt
    x``) and ``bc`` [B, N, N] (``B`` then ``C``, a group a row, zero rows
    behind them: a whole tile to turn)."""
    rows, heads = dt.shape
    dt = dt.astype(F32)
    decay = jnp.exp(dt * a.astype(F32))
    p = x.shape[1] // heads
    dec = _pairs(jnp.broadcast_to(decay[:, :, None], (rows, heads, p)), pairs)
    dtx = _pairs(dt[:, :, None] * x.astype(F32).reshape(rows, heads, p),
                 pairs)
    bc = jnp.concatenate([b.astype(F32).reshape(rows, -1, n),
                          c.astype(F32).reshape(rows, -1, n)], axis=1)
    bc = jnp.pad(bc, ((0, 0), (0, n - bc.shape[1]), (0, 0)))
    return jnp.concatenate([dec, dtx], axis=1), bc


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_rows(l, slot, live, dt, a, x, b, c, taps, state, conv, *,
                interpret=False):
    """One token a row, the slot state moved IN PLACE (ops/slot_rows.py):
    ``dt`` [B, H] float32, ``a`` [H], ``x`` [B, H * P], ``b``, ``c`` [B,
    G * N]; ``state`` [layers, rows, H / 2, N, 2P] float32 and ``conv``
    [layers, rows, *tile] (``slot_rows.tiled``), of which live row ``i``
    reads and writes ``state[l, slot[i]]`` and takes ``taps[i]`` [*tile]
    for ``conv[l, slot[i]]`` -> (``y`` [B, H * P] float32, zero where not
    live, ``state``, ``conv``): the recurrence at the head of this file
    for one token, in float32 on the vector unit, 4 MB a row read and
    written where it lies."""
    rows = dt.shape[0]
    pairs, n, p2 = state.shape[2:]
    groups = b.shape[1] // n
    tile = conv.shape[2:]
    ops, bc = _row_operands(dt, a, x, b, c, pairs, n)
    y, state, conv = slot_rows.call(
        functools.partial(_rows_kernel, groups=groups), "ssd_decode_rows",
        l, slot, live, [ops, bc, taps.astype(conv.dtype)],
        [slot_rows.per_row(2 * pairs, p2), slot_rows.per_row(n, n),
         slot_rows.per_row(*tile)],
        [jax.ShapeDtypeStruct((rows, pairs, p2), F32)],
        [slot_rows.per_row(pairs, p2)],
        [state, conv],
        [slot_rows.at_slot(pairs, n, p2), slot_rows.at_slot(*tile)],
        interpret=interpret)
    return y.reshape(rows, pairs * p2), state, conv


def decode_rows_xla(l, slot, live, dt, a, x, b, c, taps, state, conv):
    """The same through XLA: the named rows gathered, the recurrence as
    written, the rows put back with those that are not live dropped."""
    rows = dt.shape[0]
    pairs, n, p2 = state.shape[2:]
    ops, bc = _row_operands(dt, a, x, b, c, pairs, n)
    groups = b.shape[1] // n
    b_n = jnp.repeat(bc[:, :groups], pairs // groups, axis=1)
    c_n = jnp.repeat(bc[:, groups:2 * groups], pairs // groups, axis=1)
    s = (ops[:, :pairs, None, :] * state[l, slot]
         + b_n[..., None] * ops[:, pairs:, None, :])
    y = (s * c_n[..., None]).sum(axis=2).reshape(rows, pairs * p2)
    return (jnp.where(live[:, None], y, 0.0),
            slot_rows.put(state, l, slot, live, s, check=True),
            slot_rows.put(conv, l, slot, live, taps))
