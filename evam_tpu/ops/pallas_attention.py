"""Pallas TPU kernel: the attention of a packed prefill chunk's queries over
cache rows a key-value head, never materialising the scores. ONE kernel for
every family whose chunks run one (models/lm/attention.py: LFM2-MoE's and
Laguna's grouped queries; models/lm/mla.py: DeepSeek-V2's and Kimi-Linear's
latent attention over materialised heads).

A prefill chunk asks, for every token and query head, a softmax over up to
~3 k cached rows: the shared instruction prefix, the sequence's own earlier
rows and the chunk's own rows. Through XLA that is a [tokens, heads, rows]
float32 tensor a layer (193 MB at LFM2-8B-A1B's widths, 0.8 GB at
DeepSeek-V2's), written and read several times: 1.7 ms a layer of LFM2's
chunk of 33 ms on a v5e, and DeepSeek's chunk 89.9 ms (PERF.md section 6, PRs
40 and 28). Here a block of query rows keeps its running maximum, sum and
output in VMEM while the key blocks stream past (the online softmax of flash
attention), so the scores never leave the chip.

The query heads that read one key-value head are one long list of query
rows (``q`` [kv_heads, R, d], R = tokens x group); keys and values are
``k``, ``v`` [kv_heads, S, d]; the grid's first axis walks the key-value
heads. Which keys a query row may see is three half-open intervals of key
positions per row, ``[0, a) | [b0, b1) | [c0, c1)`` (``bounds`` [R, 4] = a,
b1, c0, c1; ``b0`` static; [R, 6] with a first visible row of the two
leading intervals, for a layer under a window: models/lm/laguna.py): the
prefix rows, the sequence's earlier rows, and the chunk's own rows up to
the token itself; the same for every key-value head. A row whose intervals
are empty (a padded token) comes out 0.

Two things a latent family adds, both absent for the others, whose calls
trace to what they did without them:

* a SECOND SCORE TERM over a part that all heads share: ``q_shared`` [kv_heads,
  R, P] against ``k_shared`` [S, P] (the rope part of a latent row, one list
  for all heads, as it lies in a stored row: never broadcast to the heads);
* the keys as TWO LISTS that the key axis walks one after the other, ``(the
  prefix's [kv_heads, b0, d], the rest's)``: the prefix's heads are held on
  the device for the engine's life (134 MB a layer at DeepSeek-V2's widths)
  and a chunk's 896 new rows are never concatenated behind them. In the
  prefix's list only the first interval can hold, in the rest's only the
  other two, so each asks less of the one rule.

``chunk_attention_xla`` is the same arithmetic through XLA; the CPU tests
check the kernel against it in the interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30


def _visible(col, bounds, b0, part=None):
    """The one visibility rule: ``bounds`` [rows, 4] = a, b1, c0, c1 for
    ``[0, a) | [b0, b1) | [c0, c1)``, or [rows, 6] with a first visible
    row of the two leading intervals behind them, a_lo and b_lo (a window:
    models/lm/common.py ``chunk_bounds``). ``part``: where ``col`` is known
    to lie: "prefix" below ``b0``, where only the first interval can hold
    (``a`` never passes ``b0``), "rest" at or behind it, where only the
    other two can; None: anywhere."""
    a, b1, c0, c1 = (bounds[:, i:i + 1] for i in range(4))
    windowed = bounds.shape[1] == 6
    if windowed:
        a_lo, b_lo = bounds[:, 4:5], bounds[:, 5:6]

    def lead():
        return ((col >= a_lo) & (col < a)) if windowed else (col < a)

    def cont():
        return (col >= (b_lo if windowed else b0)) & (col < b1)

    def own():
        return (col >= c0) & (col < c1)

    if part == "prefix":
        return lead()
    if part == "rest":
        return cont() | own()
    return lead() | cont() | own()


def _kernel(bounds_ref, q_ref, *refs, scale, b0, shared, prefix_blocks):
    """``refs``: ``q_shared`` where there is a ``shared`` term; per key list
    its keys, its values and, with ``shared``, its shared part; the output;
    the running maximum, sum and output. ``prefix_blocks``: the key blocks
    of the first of two lists (None: one list). A list's key block is what
    its refs hold."""
    from jax.experimental import pallas as pl

    if shared:
        qs_ref, *refs = refs
    *lists, o_ref, m_ref, l_ref, acc_ref = refs
    per = 3 if shared else 2
    lists = [lists[i:i + per] for i in range(0, len(lists), per)]
    kv = pl.program_id(2)
    contract_last = (((1,), (1,)), ((), ()))

    @pl.when(kv == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    def visit(k_ref, v_ref, ks_ref=None, skipped=0, col0=0, part=None):
        """One key block of a list whose first row stands at position
        ``col0``, ``skipped`` key blocks into the key axis."""
        rows = k_ref.shape[0]
        v = v_ref[...]
        s = jax.lax.dot_general(q_ref[...], k_ref[...], contract_last,
                                preferred_element_type=F32)
        if shared:
            s = s + jax.lax.dot_general(qs_ref[...], ks_ref[...],
                                        contract_last,
                                        preferred_element_type=F32)
        s = s * scale
        first = (kv - skipped) * rows + col0 if skipped else kv * rows
        col = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = _visible(col, bounds_ref[...], b0, part)
        s = jnp.where(ok, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        m_ref[...] = m_new

    if prefix_blocks is None:
        visit(*lists[0])
    else:
        pl.when(kv < prefix_blocks)(
            lambda: visit(*lists[0], part="prefix"))
        pl.when(kv >= prefix_blocks)(
            lambda: visit(*lists[1], skipped=prefix_blocks, col0=b0,
                          part="rest"))

    @pl.when(kv == pl.num_programs(2) - 1)
    def _():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


def _lists(x):
    return x if isinstance(x, (tuple, list)) else (x,)


#: the scores a grid step holds: 512 keys under 1024 query rows (LFM2's and
#: Laguna's groups of 4 to 8 heads a key-value head), 1024 under 512 (a
#: latent family's heads, each its own key-value head: at 512 keys DeepSeek's
#: 128 heads took 768 grid steps and 1.86 ms a layer for 1.25 at 1024; 2048
#: read 1.88 once and 1.22 once, for twice the scores in VMEM: PERF.md
#: section 6, PR 44)
SCORE_TILE = 1024 * 512


@functools.partial(jax.jit, static_argnames=(
    "scale", "b0", "block_q", "block_k", "interpret"))
def chunk_attention(q, k, v, bounds, q_shared=None, k_shared=None, *, scale,
                    b0, block_q=1024, block_k=None, interpret=False):
    """``q`` [G, R, d], ``k`` [G, S, d], ``v`` [G, S, dv], ``bounds`` [R, 4 |
    6] int32 -> [G, R, dv] (the attention-weighted ``v``), ``G`` the
    key-value heads.
    With ``q_shared`` [G, R, P] and ``k_shared`` [S, P] a row's score is
    ``q . k + q_shared . k_shared``. ``k``, ``v`` (and ``k_shared``) may
    each be a pair of lists, the prefix's ``b0`` rows and the rest's: the
    key axis walks the first, then the second, and neither is copied
    behind the other. R and every list's rows are padded here to whole
    blocks; a list shorter than a key block is one block of its own rows
    in whole lane tiles (a chunk's 896 new rows are not padded to 1024).
    ``block_k`` None: what ``SCORE_TILE`` leaves the query block, at most
    1024."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g, r, d = q.shape
    shared = q_shared is not None
    ks, vs = _lists(k), _lists(v)
    dv = vs[0].shape[2]
    kss = _lists(k_shared) if shared else [None] * len(ks)
    if len(ks) == 2 and ks[0].shape[1] != b0:
        raise ValueError("the first of two key lists holds the prefix's rows")
    block_q = min(block_q, -(-r // 16) * 16)
    if block_k is None:
        block_k = min(1024, SCORE_TILE // block_q // 128 * 128)
    rp = -(-r // block_q) * block_q
    block_ks = [min(block_k, -(-x.shape[1] // 128) * 128) for x in ks]
    blocks = [-(-x.shape[1] // b) for x, b in zip(ks, block_ks)]

    def pad(x, rows):
        """``x`` [..., rows, width] with zero rows behind its own."""
        return jnp.pad(x, [(0, 0)] * (x.ndim - 2)
                       + [(0, rows - x.shape[-2]), (0, 0)])

    def at(skipped, n):
        """The block of a list of ``n`` blocks, ``skipped`` into the key
        axis, that key step ``j`` reads: its own while the axis walks it,
        else the nearest, which is then not fetched again."""
        if len(ks) == 1:
            return lambda j: j
        return lambda j: jnp.clip(j - skipped, 0, n - 1)

    in_specs = [pl.BlockSpec((block_q, bounds.shape[1]),
                             lambda h, i, j: (i, 0)),
                pl.BlockSpec((None, block_q, d), lambda h, i, j: (h, i, 0))]
    args = [pad(bounds, rp), pad(q, rp)]
    if shared:
        in_specs.append(pl.BlockSpec((None, block_q, q_shared.shape[2]),
                                     lambda h, i, j: (h, i, 0)))
        args.append(pad(q_shared, rp))
    skipped = 0
    for one_k, one_v, one_ks, n, rows in zip(ks, vs, kss, blocks, block_ks):
        blk = at(skipped, n)
        in_specs += [pl.BlockSpec((None, rows, width), lambda h, i, j,
                                  blk=blk: (h, blk(j), 0))
                     for width in (d, dv)]
        args += [pad(one_k, n * rows), pad(one_v, n * rows)]
        if shared:
            in_specs.append(pl.BlockSpec(
                (rows, one_ks.shape[1]), lambda h, i, j, blk=blk:
                (blk(j), 0)))
            args.append(pad(one_ks, n * rows))
        skipped += n
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, b0=b0, shared=shared,
            prefix_blocks=blocks[0] if len(ks) == 2 else None),
        grid=(g, rp // block_q, sum(blocks)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, block_q, dv),
                               lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, rp, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 1), F32),
                        pltpu.VMEM((block_q, 1), F32),
                        pltpu.VMEM((block_q, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        name="attn_chunk_attention",
        interpret=interpret,
    )(*args)
    return out[:, :r]


def chunk_attention_xla(q, k, v, bounds, q_shared=None, k_shared=None, *,
                        scale, b0):
    """The same through XLA, scores materialised (float32 softmax), two
    lists behind one another."""
    k, v = (jnp.concatenate(_lists(x), axis=1) for x in (k, v))
    hi = dict(preferred_element_type=F32)
    if jax.default_backend() != "tpu":  # see models/lm/common.py es
        q, k, v = (x.astype(F32) for x in (q, k, v))
        hi = {}
    s = jnp.einsum("grd,gsd->grs", q, k, **hi)
    if q_shared is not None:
        k_shared = jnp.concatenate(_lists(k_shared), axis=0)
        if not hi:
            q_shared, k_shared = q_shared.astype(F32), k_shared.astype(F32)
        s = s + jnp.einsum("grp,sp->grs", q_shared, k_shared, **hi)
    s = s * scale
    ok = _visible(jnp.arange(k.shape[1])[None, :], bounds, b0)[None]
    m = jnp.where(ok, s, NEG).max(axis=-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    p = (p / jnp.where(l > 0, l, 1.0)).astype(jnp.bfloat16)
    if not hi:
        p = p.astype(F32)
    return jnp.einsum("grs,gsd->grd", p, v, **hi).astype(jnp.bfloat16)
