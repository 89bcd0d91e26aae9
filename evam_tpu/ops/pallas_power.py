"""Pallas TPU kernels: power retention of degree 2 (arXiv:2507.04239) over a
packed prefill chunk in its chunkwise form, the state resident in VMEM, and
a decode step's one token a row on the slot state in place.

Per key-value head (``d`` values a head) the layer weighs position ``j <=
i`` by ``exp(G_i - G_j) (q_i . k_j)^2`` (``G`` the running sum of the log
gates) and divides by the sum of the weights. With ``phi(x)`` the ``d (d +
1) / 2`` monomials of degree 2 of ``x``, scaled so that ``phi(q) . phi(k) =
(q . k)^2`` EXACTLY, that is a recurrence over a float32 state:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T      [d (d + 1) / 2, d]
    z_t = g_t z_{t-1} + phi(k_t)            [d (d + 1) / 2]
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + EPS)

**The order of phi's entries** is chosen so that both kernels make them by
ROTATIONS and never gather: entry ``r d + a`` (``r < d / 2``) is ``c_r x_a
x_{(a - r) mod d}`` with ``c_0 = 1`` (the squares) and ``c_r = sqrt 2``: every
unordered pair at cyclic distance ``r`` once. Distance ``d / 2`` pairs each
index with one partner, so its block is HALF a block: entries ``d^2 / 2 + (a
- d / 2)`` for ``a >= d / 2``. 8256 = 64 blocks of 128 and one of 64 at the
published ``d`` = 128. ``S`` lies ``[8256, 128]``: phi's index down the
sublanes (1032 whole tiles of 8), the value's 128 on the lanes. ``z`` lies
as ``[d / 2 + 1, d]`` (``phi_lanes``: block ``r`` a row, ``a`` on the lanes,
the half block's first ``d / 2`` lanes zero), because both kernels make
phi of a ROW vector by lane rotations there.

``chunk_scan``: a packed chunk (engine/generate.py) in blocks of ``BLOCK``
tokens. The family's ``SEGMENT_ALIGN`` is ``BLOCK``: a block holds tokens of
ONE segment, from its first row, dead rows behind them. Inside a block the
attention form, the scores ``(Q K^T)^2`` decayed and masked, on the MXU;
across blocks ``phi(Q) S`` decayed to each row and ``phi(K)^T (V . decay)``
added to ``S``, one tile of 128 of phi at a time (phi of a block is never
written out: a tile is two lane rotations and a product). A segment's
state is read from ``state[l, seg_from]`` when its first block runs and
written to ``state[l, seg_to]`` after its last, the rows PREFETCHED SCALARS
and the arrays aliased in and out: no gather or scatter of 34 MB rows
through XLA (PERF.md section 7, Nemotron's (2)).

``decode_rows``: ops/slot_rows.py's addressing (the layer, ``slot`` and
``live`` prefetched; a row's state read where it lies and written back
there) over a grid of (step row, key-value head): a head's 4.2 MB block is
decayed, updated by ``phi(k) v^T`` and read by the head's query heads in ONE
pass on the vector unit, phi's columns made by sublane rotations of the
vector laid over the lanes. A row that is not live names head 0 of its slot
in every grid step and writes back what it read.

``chunk_scan_xla`` / ``decode_rows_xla``: the recurrence as written, token
by token, phi gathered whole; the CPU tests run them and check the kernels
against them in the interpreter. The twins take any ``d``; the kernels 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from evam_tpu.ops import slot_rows
from evam_tpu.ops.pallas_selective_scan import _flags

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
#: tokens solved together; the packer starts every segment at a multiple
BLOCK = 64
LANES = 128
SQRT2 = 2.0 ** 0.5
#: beside the running sum of weights in the division
EPS = 1e-6


def expanded(d: int) -> int:
    """phi's entries for a head of ``d`` values."""
    return d * (d + 1) // 2


def phi_lanes(x):
    """``phi`` of the last axis as ``z`` lies: [..., d] -> [..., d / 2 + 1,
    d] float32, row ``r`` lane ``a`` = ``c_r x_a x_{(a - r) mod d}``, the
    last row's first ``d / 2`` lanes zero."""
    d = x.shape[-1]
    half = d // 2
    x = x.astype(F32)
    rows = [x * x] + [SQRT2 * x * jnp.roll(x, r, axis=-1)
                      for r in range(1, half)]
    rows.append(jnp.where(jnp.arange(d) >= half,
                          SQRT2 * x * jnp.roll(x, half, axis=-1), 0.0))
    return jnp.stack(rows, axis=-2)


def phi(x):
    """``phi`` of the last axis as the state's rows have it: [..., d] ->
    [..., d (d + 1) / 2] float32."""
    d = x.shape[-1]
    half = d // 2
    p = phi_lanes(x)
    return jnp.concatenate(
        [p[..., :half, :].reshape(*x.shape[:-1], half * d),
         p[..., half, half:]], axis=-1)


def _check(q, state, zsum):
    kvh, g, d = q.shape[-3:]
    if (d != LANES or state.shape[2:] != (kvh, expanded(d), d)
            or zsum.shape[2:] != (kvh, d // 2 + 1, d)):
        raise ValueError(
            f"queries {q.shape} over a state {state.shape} and sums "
            f"{zsum.shape}: the kernels are written for heads of {LANES} "
            f"over [layers, rows, heads, {expanded(LANES)}, {LANES}] and "
            f"[layers, rows, heads, {LANES // 2 + 1}, {LANES}]")


# ------------------------------------------------------- a prefill chunk


def _chunk_kernel(l_ref, rin_ref, rout_ref, open_ref, close_ref, n_ref,
                  q_ref, k_ref, v_ref, gc_ref, gr_ref, sin_ref, zin_ref,
                  y_ref, sout_ref, zout_ref, s_ref, z_ref):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(1)
    heads, blk, d = q_ref.shape
    half = d // 2
    big = s_ref.shape[0]

    @pl.when(open_ref[b] > 0)
    def _():
        s_ref[...] = sin_ref[...]
        z_ref[...] = zin_ref[...]

    n = n_ref[b]

    @pl.when(n > 0)
    def _():
        q = q_ref[...].reshape(heads * blk, d)
        k = k_ref[...]
        v = v_ref[...].astype(F32)
        g_col, g_row = gc_ref[...], gr_ref[...]
        t_col = jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
        s_row = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
        # the running sum at the block's last live row
        total = jnp.sum(jnp.where(t_col == n - 1, g_col, 0.0), axis=0,
                        keepdims=True)
        g_q = jnp.concatenate([g_col] * heads, axis=0)
        t_q = jnp.concatenate([t_col] * heads, axis=0)
        # inside the block: the attention form
        qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)
        a = jnp.where((t_q >= s_row) & (s_row < n),
                      qk * qk * jnp.exp(jnp.minimum(g_q - g_row, 0.0)), 0.0)
        num = jnp.dot(a, v, precision=HI, preferred_element_type=F32)
        den = jnp.sum(a, axis=1, keepdims=True)
        # across blocks: phi one tile at a time
        q32, k32 = q.astype(F32), k.astype(F32)
        w = jnp.where(t_col < n, jnp.exp(jnp.minimum(total - g_col, 0.0)),
                      0.0)
        vw = v * w
        dec = jnp.exp(total)

        def tile(x, r):
            return SQRT2 * x * pltpu.roll(x, r, 1)

        def visit(r, lo, pq, pk, acc_num, acc_den):
            at = pl.ds(lo, d)
            old = s_ref[at, :]
            z_old = z_ref[pl.ds(r, 1), :]
            acc_num = acc_num + jnp.dot(pq, old, precision=HI,
                                        preferred_element_type=F32)
            acc_den = acc_den + pq * z_old
            new = dec * old + jax.lax.dot_general(
                pk, vw, (((0,), (0,)), ((), ())), precision=HI,
                preferred_element_type=F32)
            z_ref[pl.ds(r, 1), :] = dec * z_old + jnp.sum(
                pk * w, axis=0, keepdims=True)
            return new, acc_num, acc_den

        def whole(r, carry, squares=False):
            lo = pl.multiple_of(r * d, d)
            pq, pk = ((q32 * q32, k32 * k32) if squares
                      else (tile(q32, r), tile(k32, r)))
            new, *carry = visit(r, lo, pq, pk, *carry)
            s_ref[pl.ds(lo, d), :] = new
            return tuple(carry)

        zeros = jnp.zeros((heads * blk, d), F32)
        carry = whole(0, (zeros, zeros), squares=True)
        carry = jax.lax.fori_loop(1, half, whole, carry)
        # the half block: the tile's upper rows, phi's upper lanes
        new, acc_num, acc_den = visit(
            half, big - d, jnp.where(lane >= half, tile(q32, half), 0.0),
            jnp.where(lane >= half, tile(k32, half), 0.0), *carry)
        s_ref[big - half:, :] = new[half:]
        carried = jnp.exp(g_q)
        num = num + carried * acc_num
        den = den + carried * jnp.sum(acc_den, axis=1, keepdims=True)
        y_ref[...] = (num / (den + EPS)).reshape(heads, blk, d)

    @pl.when(close_ref[b] > 0)
    def _():
        sout_ref[...] = s_ref[...]
        zout_ref[...] = z_ref[...]


def _blocks(seg, seg_from, seg_to):
    """Per block of a packed chunk: the state row its segment starts from
    and the row its end state goes to, whether it is the first / the last
    block of a run that shares them, and its live rows. A dead block stands
    with the live block before it (with segment 0 where there is none)."""
    nb = seg.shape[0] // BLOCK
    rows = seg.reshape(nb, BLOCK)
    n = (rows >= 0).sum(axis=1).astype(jnp.int32)
    idx = jnp.arange(nb, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(n > 0, idx, -1))
    of = jnp.where(last >= 0, rows[jnp.maximum(last, 0), 0], 0)
    prev = jnp.concatenate([jnp.full((1,), -1, of.dtype), of[:-1]])
    nxt = jnp.concatenate([of[1:], jnp.full((1,), -1, of.dtype)])
    return (seg_from[of].astype(jnp.int32), seg_to[of].astype(jnp.int32),
            (of != prev).astype(jnp.int32), (of != nxt).astype(jnp.int32), n)


def _gates(lg, seg):
    """The running sum of the log gates inside each block (dead rows add
    nothing), a column a token and a row a token: [heads, blocks, BLOCK, 1]
    and [heads, blocks, 1, BLOCK] float32."""
    t, kvh = lg.shape
    lg = jnp.where((seg >= 0)[:, None], lg.astype(F32), 0.0)
    cum = jnp.cumsum(lg.reshape(t // BLOCK, BLOCK, kvh), axis=1)
    cum = cum.transpose(2, 0, 1)
    return cum[..., None], cum[:, :, None, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def chunk_scan(l, q, k, v, lg, seg, seg_from, seg_to, state, zsum, *,
               interpret=False):
    """``q`` [T, heads, G, d] (a key-value head's G query heads side by
    side), ``k``, ``v`` [T, heads, d], ``lg`` [T, heads] float32 log gates
    (<= 0), ``seg`` [T] int32 (every segment starts at a multiple of
    ``BLOCK``), ``seg_from``, ``seg_to`` [S] rows of ``state`` [layers,
    rows, heads, d (d + 1) / 2, d] and ``zsum`` [layers, rows, heads, d / 2
    + 1, d] float32, both moved IN PLACE in layer ``l`` -> (``y`` [T,
    heads, G, d] float32, rows of no segment unspecified, ``state``,
    ``zsum``). Two segments never share a ``seg_to`` row that a third
    starts from."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _check(q, state, zsum)
    t, kvh, g, d = q.shape
    if t % BLOCK:
        raise ValueError(f"a chunk of {t} tokens is not whole blocks of "
                         f"{BLOCK}")
    nb = t // BLOCK
    big, zr = state.shape[3], zsum.shape[3]
    flags = _blocks(seg, seg_from, seg_to)
    g_col, g_row = _gates(lg, seg)

    def tok(*lead):
        return pl.BlockSpec((None, *lead, BLOCK, d),
                            lambda h, b, *_: (h, *(0,) * len(lead), b, 0))

    def at(rows):  # 0: the row a block starts from; 1: the row it ends in
        return lambda h, b, l, *r: (l[0], r[rows][b], h, 0, 0)

    def slot(n, rows):
        return pl.BlockSpec((None, None, None, n, d), at(rows))

    y, state, zsum = pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(kvh, nb),
            in_specs=[tok(g), tok(), tok(),
                      pl.BlockSpec((None, None, BLOCK, 1),
                                   lambda h, b, *_: (h, b, 0, 0)),
                      pl.BlockSpec((None, None, 1, BLOCK),
                                   lambda h, b, *_: (h, b, 0, 0)),
                      slot(big, 0), slot(zr, 0)],
            out_specs=[tok(g), slot(big, 1), slot(zr, 1)],
            scratch_shapes=[pltpu.VMEM((big, d), F32),
                            pltpu.VMEM((zr, d), F32)]),
        out_shape=[jax.ShapeDtypeStruct((kvh, g, t, d), F32),
                   jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct(zsum.shape, F32)],
        input_output_aliases={11: 1, 12: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024),
        name="pow_chunk_scan",
        interpret=interpret,
    )(jnp.reshape(l, (1,)).astype(jnp.int32), *flags,
      q.transpose(1, 2, 0, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
      g_col, g_row, state, zsum)
    return y.transpose(2, 0, 1, 3), state, zsum


def _moved(s, z, q, k, v, gate):
    """One token on states ``s`` [..., D, d] and sums ``z`` [..., d / 2 + 1,
    d]: ``q`` [..., G, d], ``k``, ``v`` [..., d], ``gate`` [...] (the decay
    itself) -> (``y`` [..., G, d], ``s``, ``z``), float32."""
    gate = gate.astype(F32)[..., None, None]
    s = gate * s + phi(k)[..., :, None] * v.astype(F32)[..., None, :]
    z = gate * z + phi_lanes(k)
    num = jnp.einsum("...gi,...iv->...gv", phi(q), s, precision=HI)
    den = jnp.einsum("...gra,...ra->...g", phi_lanes(q), z, precision=HI)
    return num / (den[..., None] + EPS), s, z


def chunk_scan_xla(l, q, k, v, lg, seg, seg_from, seg_to, state, zsum):
    """The same through XLA: a ``lax.scan`` over the tokens, the recurrence
    as written (any packing; dead rows come out zero; a segment with no
    token here carries its ``seg_from`` row to its ``seg_to`` row)."""
    s0, z0 = state[l, seg_from], zsum[l, seg_from]

    def step(carry, tok):
        s, z, s_end, z_end = carry
        q_t, k_t, v_t, lg_t, live, i, start, end = tok
        s = jnp.where(start > 0, s0[i], s)
        z = jnp.where(start > 0, z0[i], z)
        y, s_new, z_new = _moved(s, z, q_t, k_t, v_t, jnp.exp(lg_t))
        s, z = jnp.where(live, s_new, s), jnp.where(live, z_new, z)
        s_end = jnp.where(end > 0, s_end.at[i].set(s), s_end)
        z_end = jnp.where(end > 0, z_end.at[i].set(z), z_end)
        return (s, z, s_end, z_end), jnp.where(live, y, 0.0)

    seg = jnp.asarray(seg)
    (_, _, s_end, z_end), y = jax.lax.scan(
        step, (jnp.zeros_like(s0[0]), jnp.zeros_like(z0[0]), s0, z0),
        (q, k, v, lg.astype(F32), seg >= 0, *_flags(seg)))
    return (y, state.at[l, seg_to].set(s_end),
            zsum.at[l, seg_to].set(z_end))


# ------------------------------------------- a decode step's one token


def _rows_kernel(l_ref, slot_ref, live_ref, x_ref, sin_ref, zin_ref, y_ref,
                 sout_ref, zout_ref, acc_ref, *, heads):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    big, d = sin_ref.shape
    half = d // 2

    @pl.when(live_ref[i] > 0)
    def _():
        x = x_ref[...]
        gate = x[heads + 2:heads + 3]

        def over_lanes(row):  # [1, d] -> [d, d]: value a down row a
            return jnp.broadcast_to(row, (d, d)).T

        def rows(row):
            return jnp.broadcast_to(row, (d, d))

        k_col = over_lanes(x[heads:heads + 1])
        v_row = rows(x[heads + 1:heads + 2])
        kv = SQRT2 * k_col * v_row
        q_col = [over_lanes(x[h:h + 1]) for h in range(heads)]
        # the squares
        first = gate * sin_ref[0:d, :] + k_col * k_col * v_row
        sout_ref[0:d, :] = first
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

        def block(r, _):
            at = pl.ds(pl.multiple_of(r * d, d), d)
            new = gate * sin_ref[at, :] + pltpu.roll(k_col, r, 0) * kv
            sout_ref[at, :] = new
            for h in range(heads):
                acc_ref[h] += pltpu.roll(q_col[h], r, 0) * new
            return 0

        jax.lax.fori_loop(1, half, block, 0)
        # the half block: the upper rows' partners
        last = (gate * sin_ref[big - half:, :]
                + (pltpu.roll(k_col, half, 0) * kv)[half:])
        sout_ref[big - half:, :] = last
        # the sums, phi of a row vector by lane rotations
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
        r_col = jax.lax.broadcasted_iota(jnp.int32, (d, 1), 0)
        scale = jnp.where(r_col == 0, 1.0, SQRT2)

        def lanes(row):  # rows r < d / 2 of phi_lanes, and its last row
            p = scale * rows(row) * pltpu.roll(rows(row), 0, 1, stride=1,
                                               stride_axis=0)
            return p[:half], jnp.where(lane >= half, p[half:half + 1], 0.0)

        pk, pk_last = lanes(x[heads:heads + 1])
        z, z_last = (gate * zin_ref[:half, :] + pk,
                     gate * zin_ref[half:half + 1, :] + pk_last)
        zout_ref[:half, :] = z
        zout_ref[half:half + 1, :] = z_last
        for h in range(heads):
            col = q_col[h]
            num = (jnp.sum(col * (col * first + SQRT2 * acc_ref[h]), axis=0,
                           keepdims=True)
                   + SQRT2 * jnp.sum(
                       (col * pltpu.roll(col, half, 0))[half:] * last,
                       axis=0, keepdims=True))
            pq, pq_last = lanes(x[h:h + 1])
            den = (jnp.sum(jnp.sum(pq * z, axis=0, keepdims=True), axis=1,
                           keepdims=True)
                   + jnp.sum(pq_last * z_last, axis=1, keepdims=True))
            y_ref[h:h + 1, :] = num / (den + EPS)
        y_ref[heads:, :] = jnp.zeros((y_ref.shape[0] - heads, d), F32)

    @pl.when(live_ref[i] == 0)
    def _():
        slot_rows.keep((sin_ref, sout_ref), (zin_ref, zout_ref))
        y_ref[...] = jnp.zeros(y_ref.shape, F32)


def _row_operand(q, k, v, lg):
    """A step row's vectors as one tile a key-value head: [B, heads, G + 3
    (whole sublane tiles), d] float32, the query heads, ``k``, ``v`` and
    the decay over all lanes."""
    rows, kvh, g, d = q.shape
    gate = jnp.broadcast_to(jnp.exp(lg.astype(F32))[..., None, None],
                            (rows, kvh, 1, d))
    x = jnp.concatenate([q.astype(F32), k.astype(F32)[:, :, None, :],
                         v.astype(F32)[:, :, None, :], gate], axis=2)
    return jnp.pad(x, ((0, 0), (0, 0), (0, -(g + 3) % 8), (0, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_rows(l, slot, live, q, k, v, lg, state, zsum, *, interpret=False):
    """One token a row, the slot state moved IN PLACE (ops/slot_rows.py):
    ``q`` [B, heads, G, d], ``k``, ``v`` [B, heads, d], ``lg`` [B, heads]
    float32; of ``state`` and ``zsum`` (``chunk_scan``) live row ``i`` reads
    and writes ``[l, slot[i]]`` -> (``y`` [B, heads, G, d] float32, zero
    where not live, ``state``, ``zsum``)."""
    _check(q, state, zsum)
    rows, kvh, g, d = q.shape
    x = _row_operand(q, k, v, lg)
    y, state, zsum = slot_rows.call(
        functools.partial(_rows_kernel, heads=g), "pow_decode_rows",
        l, slot, live, [x], [slot_rows.per_part(x.shape[2], d)],
        [jax.ShapeDtypeStruct(x.shape, F32)],
        [slot_rows.per_part(x.shape[2], d)],
        [state, zsum],
        [slot_rows.at_slot_part(state.shape[3], d),
         slot_rows.at_slot_part(zsum.shape[3], d)],
        parts=kvh, scratch=[(g, d, d)], interpret=interpret)
    return y[:, :, :g], state, zsum


def decode_rows_xla(l, slot, live, q, k, v, lg, state, zsum):
    """The same through XLA: the named rows gathered, the recurrence as
    written, the rows put back with those that are not live dropped."""
    y, s, z = _moved(state[l, slot], zsum[l, slot], q, k, v, jnp.exp(lg))
    return (jnp.where(live[:, None, None, None], y, 0.0),
            slot_rows.put(state, l, slot, live, s, check=True),
            slot_rows.put(zsum, l, slot, live, z))
