"""Pallas TPU kernel: the gated delta rule with a decay per channel (Kimi
Delta Attention) over a packed prefill chunk, the state resident in VMEM.

Per head, with ``S`` [d_k, d_v] float32, the recurrence over a segment is

    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

(``g_t`` [d_k] <= 0 the log decay, ``b_t`` the write strength). Token by
token through XLA every token writes the whole state (2 MB for 32 heads of
128 x 128), 1 GB a chunk and layer. Here the state of one head stays in
VMEM while the chunk streams past in BLOCKS of ``BLOCK`` tokens, and inside
a block the rule is solved in its chunkwise form. With ``G_t`` the running
sum of ``g`` inside the block and ``S_0`` the state before it,

    A[t, j] = sum_c b_t k_t[c] k_j[c] exp(G_t[c] - G_j[c])      (j <  t)
    B[t, j] = sum_c     q_t[c] k_j[c] exp(G_t[c] - G_j[c])      (j <= t)
    (I + A) U = b V - (b K exp(G)) S_0        (forward substitution)
    O   = (Q exp(G)) S_0 + B U
    S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U

Every exponent is a difference ``G_t - G_j`` with ``j <= t``, so none is
positive and nothing overflows whatever the decay. ``A`` and ``B`` are
built a row at a time on the vector unit in exact float32; the three
products with the state run on the MXU at ``highest`` precision.

The chunk is PACKED (engine/generate.py): ``seg`` [T] gives each token's
segment (-1: a dead row), a segment's tokens are contiguous, and every
segment STARTS at a multiple of ``BLOCK`` (the family's
``SEGMENT_ALIGN``), so a block never holds two segments' tokens; the rows
between are dead. The caller hands ``kb = b k`` and ``vb = b v`` with dead
rows zero, and ``g`` zero there: a dead row moves no state. Each segment
starts from its own ``h0`` [segments, heads, d_k, d_v] and its end state
comes back as ``h_end``; a segment with no token here keeps its ``h0``.

``delta_rule_xla`` is the same recurrence as a ``lax.scan`` over the
tokens (any packing); the CPU tests run it, and check the kernel against
it in the interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from evam_tpu.ops import slot_rows
from evam_tpu.ops.pallas_selective_scan import _flags

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
#: tokens solved together; segment starts are aligned to it
BLOCK = 16


def _block_flags(seg, n_seg: int):
    """Per block of ``BLOCK`` tokens: the row of ``h0``/``h_end`` it works
    on (``n_seg``, a spare row, for a dead block), whether it opens its
    segment, whether it closes it."""
    first = seg.reshape(-1, BLOCK)[:, 0]
    row, start, end = _flags(first)
    return jnp.where(first >= 0, row, n_seg), start, end


def _kernel(row_ref, start_ref, end_ref, q_ref, k_ref, kb_ref, vb_ref, g_ref,
            h0_ref, o_ref, hend_ref, s_ref):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    c, d = q_ref.shape

    @pl.when(start_ref[j] > 0)
    def _():
        s_ref[...] = h0_ref[...]

    q, k, kb, vb, g = (r[...] for r in (q_ref, k_ref, kb_ref, vb_ref, g_ref))
    s0 = s_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)).astype(F32)
    gam = jnp.dot(tri, g, precision=HI, preferred_element_type=F32)
    decay = jnp.exp(gam)
    into = jnp.dot(jnp.concatenate([kb * decay, q * decay], axis=0), s0,
                   precision=HI, preferred_element_type=F32)
    rhs, out = vb - into[:c], into[c:]
    # row t of A and B as columns over j; U by forward substitution
    u = jnp.zeros((c, d), F32)
    b_cols = []
    for t in range(c):
        kd = k * jnp.exp(jnp.minimum(gam[t:t + 1] - gam, 0.0))
        a_col = jnp.sum(kb[t:t + 1] * kd, axis=1, keepdims=True)
        b_col = jnp.sum(q[t:t + 1] * kd, axis=1, keepdims=True)
        b_cols.append(jnp.where(row <= t, b_col, 0.0))
        # rows of ``u`` from t on are still zero: no mask on ``a_col``
        u_t = rhs[t:t + 1] - jnp.sum(a_col * u, axis=0, keepdims=True)
        u = jnp.where(row == t, u_t, u)
    for t in range(c):
        o_t = out[t:t + 1] + jnp.sum(b_cols[t] * u, axis=0, keepdims=True)
        out = jnp.where(row == t, o_t, out)
    o_ref[...] = out
    # Diag(exp(G_C)) S_0: the decay as a column, through the identity
    eye = (jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)).astype(F32)
    last = jnp.sum(eye * decay[c - 1:c], axis=1, keepdims=True)
    k_hat = k * jnp.exp(gam[c - 1:c] - gam)
    s_new = last * s0 + jax.lax.dot_general(
        k_hat, u, (((0,), (0,)), ((), ())), precision=HI,
        preferred_element_type=F32)
    s_ref[...] = s_new

    @pl.when(end_ref[j] > 0)
    def _():
        hend_ref[...] = s_new


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_rule(q, k, kb, vb, g, seg, h0, *, interpret=False):
    """``q``, ``k``, ``kb``, ``vb``, ``g`` [T, H * D] float32 (heads side by
    side, D = 128); ``seg`` [T] int32; ``h0`` [S, H, D, D] float32 ->
    (``o`` [T, H * D] float32, ``h_end`` [S, H, D, D] float32). T is whole
    blocks and every segment starts at a multiple of ``BLOCK``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t = q.shape[0]
    n_seg, heads, d, _ = h0.shape
    if t % BLOCK or q.shape[1] != heads * d or d % 128:
        raise ValueError(f"a chunk of {t} tokens x {q.shape[1]} is not whole "
                         f"blocks of {BLOCK} tokens and {heads} heads of "
                         f"{d} lanes")
    flags = _block_flags(seg, n_seg)
    tok = pl.BlockSpec((BLOCK, d), lambda h, j, *_: (j, h))
    state = pl.BlockSpec((None, None, d, d),
                         lambda h, j, row, *_: (row[j], h, 0, 0))
    spare = jnp.concatenate([h0.astype(F32),
                             jnp.zeros((1, heads, d, d), F32)], axis=0)
    o, h_end = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(heads, t // BLOCK),
            in_specs=[tok, tok, tok, tok, tok, state],
            out_specs=[tok, state],
            scratch_shapes=[pltpu.VMEM((d, d), F32)]),
        out_shape=[jax.ShapeDtypeStruct((t, heads * d), F32),
                   jax.ShapeDtypeStruct((n_seg + 1, heads, d, d), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="kda_delta_rule",
        interpret=interpret,
    )(*flags, *(x.astype(F32) for x in (q, k, kb, vb, g)), spare)
    # a segment with no token here was never visited: it keeps its h0
    visited = (seg[None, :] == jnp.arange(n_seg)[:, None]).any(axis=1)
    return o, jnp.where(visited[:, None, None, None], h_end[:n_seg], h0)


def delta_rule_xla(q, k, kb, vb, g, seg, h0):
    """The same through XLA: a ``lax.scan`` over the tokens, every product
    elementwise in float32."""
    n_seg, heads, d, _ = h0.shape
    q, k, kb, vb, g = (jnp.asarray(x, F32).reshape(-1, heads, d)
                       for x in (q, k, kb, vb, g))
    h0 = jnp.asarray(h0, F32)
    flags = _flags(jnp.asarray(seg))

    def step(carry, row):
        s, h_end = carry
        q_t, k_t, kb_t, vb_t, g_t, i, start, end = row
        s = jnp.where(start > 0, h0[i], s)
        s = s * jnp.exp(g_t)[:, :, None]
        u = vb_t - (kb_t[:, :, None] * s).sum(axis=1)
        s = s + k_t[:, :, None] * u[:, None, :]
        o = (q_t[:, :, None] * s).sum(axis=1)
        h_end = jnp.where(end > 0, h_end.at[i].set(s), h_end)
        return (s, h_end), o

    (_, h_end), o = jax.lax.scan(
        step, (jnp.zeros_like(h0[0]), h0), (q, k, kb, vb, g, *flags))
    return o.reshape(o.shape[0], heads * d), h_end


# ------------------------------------------- a decode step's one token


def _rows_kernel(l_ref, slot_ref, live_ref, cols_ref, vb_ref, taps_ref, s_ref,
                 conv_ref, o_ref, sout_ref, convout_ref):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    heads = vb_ref.shape[0]

    @pl.when(live_ref[b] > 0)
    def _():
        # q | k | kb | g, heads side by side, the key channel down the
        # sublanes: what multiplies a ROW of the state is a column here
        t = cols_ref[...].T
        q_t, k_t, kb_t = (t[:, i * heads:(i + 1) * heads] for i in range(3))
        e_t = jnp.exp(t[:, 3 * heads:])
        kq = jnp.sum(k_t * q_t, axis=0, keepdims=True)
        for h in range(heads):
            decayed = s_ref[h] * e_t[:, h:h + 1]
            u = vb_ref[h:h + 1, :] - jnp.sum(kb_t[:, h:h + 1] * decayed,
                                             axis=0, keepdims=True)
            # S^T q of the state after the write, from the state before
            o_ref[h:h + 1, :] = (
                jnp.sum(q_t[:, h:h + 1] * decayed, axis=0, keepdims=True)
                + u * kq[:, h:h + 1])
            sout_ref[h] = decayed + k_t[:, h:h + 1] * u
        convout_ref[...] = taps_ref[...]

    @pl.when(live_ref[b] == 0)
    def _():
        slot_rows.keep((s_ref, sout_ref), (conv_ref, convout_ref))
        o_ref[...] = jnp.zeros(o_ref.shape, F32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_rows(l, slot, live, q, k, kb, vb, g, taps, state, conv, *,
                interpret=False):
    """One token a row, the slot state moved IN PLACE (ops/slot_rows.py):
    ``q``, ``k``, ``kb``, ``vb``, ``g`` [B, H, D] float32; ``state``
    [layers, rows, H, D, D] float32 and ``conv`` [layers, rows, *tile]
    (``slot_rows.tiled``), of which live row ``b`` reads and writes
    ``state[l, slot[b]]`` and takes ``taps[b]`` [*tile] for ``conv[l,
    slot[b]]`` -> (``o`` [B, H, D] float32, zero where not live,
    ``state``, ``conv``). Per row and head, in float32 on the vector unit:

        S' = Diag(exp(g)) S;  u = vb - S'^T kb;  o = S'^T q + u (k . q)
        S  = S' + k u^T
    """
    rows, heads, d = q.shape
    tile = conv.shape[2:]
    cols = jnp.concatenate([x.astype(F32) for x in (q, k, kb, g)], axis=1)
    return slot_rows.call(
        _rows_kernel, "kda_decode_rows", l, slot, live,
        [cols, vb.astype(F32), taps.astype(conv.dtype)],
        [slot_rows.per_row(4 * heads, d), slot_rows.per_row(heads, d),
         slot_rows.per_row(*tile)],
        [jax.ShapeDtypeStruct((rows, heads, d), F32)],
        [slot_rows.per_row(heads, d)],
        [state, conv],
        [slot_rows.at_slot(heads, d, d), slot_rows.at_slot(*tile)],
        interpret=interpret)


def decode_rows_xla(l, slot, live, q, k, kb, vb, g, taps, state, conv):
    """The same through XLA: the named rows gathered, the recurrence as
    written, the rows put back with those that are not live dropped."""
    decayed = state[l, slot] * jnp.exp(g)[..., None]
    u = vb - (kb[..., None] * decayed).sum(axis=2)
    # S^T q of the state after the write, from the state before it
    o = ((q[..., None] * decayed).sum(axis=2)
         + u * (k * q).sum(axis=-1, keepdims=True))
    s = decayed + k[..., None] * u[:, :, None, :]
    return (jnp.where(live[:, None, None], o, 0.0),
            slot_rows.put(state, l, slot, live, s, check=True),
            slot_rows.put(conv, l, slot, live, taps))
