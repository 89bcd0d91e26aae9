"""Pallas TPU kernels of the language models' attention. ``chunk_attention``:
a packed prefill chunk's queries over cache rows a key-value head, never
materialising the scores; ONE kernel for every family whose chunks run one
(models/lm/attention.py: LFM2-MoE's and Laguna's grouped queries;
models/lm/mla.py: DeepSeek-V2's and Kimi-Linear's latent attention over
materialised heads). ``decode_pages`` (at the end of the file): a decode
step's rows, each over its OWN pages of a ``[k ; v]`` cache, read where
they lie by the row's page table (models/lm/attention.py ``attn_decode``:
Laguna's and LFM2-MoE's; the prefix's part of that softmax stays with XLA).

The chunk kernel.

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
heads, several a step (``HEAD_BODIES``). Which keys a query row may see
is three half-open intervals of key positions per row, ``[0, a) | [b0, b1)
| [c0, c1)`` (``bounds`` [R, 4] = a, b1, c0, c1; ``b0`` static; [R, 6]
with a first visible row of the two leading intervals, for a layer under a window: models/lm/laguna.py): the
prefix rows, the sequence's earlier rows, and the chunk's own rows up to
the token itself; the same for every key-value head. A row whose intervals
are empty (a padded token) comes out 0. The rule is asked a score only
where a key block's answer differs from row to row: every (query block, key
block) pair has a class, read off the bounds before the kernel runs
(``block_classes``), and a block every live row sees whole runs without a
mask, one no row sees is not visited.

Two things a latent family adds, both absent for the others, whose calls
hold no operand for them:

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
import operator

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30


def _intervals(bounds, b0, part=None):
    """The visible intervals of key positions per row, ``[(first, end)]``
    (``first`` None: 0): ``bounds`` [..., 4] = a, b1, c0, c1 for ``[0, a) |
    [b0, b1) | [c0, c1)``, or [..., 6] with a first visible row of the two
    leading intervals behind them, a_lo and b_lo (a window:
    models/lm/common.py ``chunk_bounds``). ``part``: where a key is known
    to lie: "prefix" below ``b0``, where only the first interval can hold
    (``a`` never passes ``b0``), "rest" at or behind it, where only the
    other two can; None: anywhere."""
    a, b1, c0, c1 = (bounds[..., i:i + 1] for i in range(4))
    a_lo, b_lo = ((bounds[..., 4:5], bounds[..., 5:6])
                  if bounds.shape[-1] == 6 else (None, b0))
    lead, rest = [(a_lo, a)], [(b_lo, b1), (c0, c1)]
    return {"prefix": lead, "rest": rest, None: lead + rest}[part]


def _visible(col, bounds, b0, part=None):
    """The one visibility rule: whether the key at position ``col`` lies
    in one of the row's ``_intervals``."""
    return functools.reduce(operator.or_, (
        col < end if first is None else (col >= first) & (col < end)
        for first, end in _intervals(bounds, b0, part)))


def _live(bounds, b0):
    """Whether a row sees anything at all (a padded token's intervals are
    all empty)."""
    return functools.reduce(operator.or_, (
        end > (0 if first is None else first)
        for first, end in _intervals(bounds, b0)))


#: the scores a grid step holds: 512 keys under 1024 query rows (LFM2's and
#: Laguna's groups of 4 to 8 heads a key-value head), 1024 under 512 (a
#: latent family's heads, each its own key-value head: at 512 keys DeepSeek's
#: 128 heads took 768 grid steps and 1.86 ms a layer for 1.25 at 1024; 2048
#: read 1.88 once and 1.22 once, for twice the scores in VMEM: PERF.md
#: section 6, PR 44)
SCORE_TILE = 1024 * 512


#: the class of a (query block, key block) pair: ``MIXED``, the rule is
#: asked a score; ``WHOLE``, every live row of the query block sees every
#: key of the block, nothing is asked; ``NONE``, no row sees any: the pair
#: is not visited
MIXED, WHOLE, NONE = 0, 1, 2
CLASSES = ("mixed", "whole", "none")
#: a grid step's heads times the call's key lists: a head's chain (scores,
#: softmax, values) leaves the MXU idle while the vector unit works and
#: the other way round, and a grid step costs ~0.9 us of its own under ten
#: operands; several heads a step, written stage by stage, overlap the one
#: and share the other. The kernel's code holds every head of a step once
#: a list and class (3.3 MB at four heads of two lists), and past this
#: many a call inside a step program slows again (a v5e, PERF.md section
#: 6, PR 51: DeepSeek's layer of two lists 1.19 ms a head a step, 0.96
#: two, 1.02 four, though four read 0.90 called alone; LFM2's of one list
#: 0.356, 0.286, 0.251)
HEAD_BODIES = 4


def chunk_blocks(rows: int, list_rows, block_q: int = 1024, block_k=None):
    """How ``chunk_attention`` cuts ``rows`` query rows and key lists of
    ``list_rows`` rows: the query block and, per list, ``(key block, how
    many)``. The kernel's grid and the engine's count of a chunk's key
    blocks by class (engine/generate.py) both come from here. ``block_k``
    None: what ``SCORE_TILE`` leaves the query block, at most 1024; a list
    shorter than a key block is one block of its own rows in whole lane
    tiles."""
    block_q = min(block_q, -(-rows // 16) * 16)
    if block_k is None:
        block_k = min(1024, SCORE_TILE // block_q // 128 * 128)
    blocks = [min(block_k, -(-n // 128) * 128) for n in list_rows]
    return block_q, [(b, -(-n // b)) for n, b in zip(list_rows, blocks)]


def block_classes(bounds, b0, list_rows, block_q: int = 1024, block_k=None,
                  group: int = 1, xp=jnp):
    """The class of every (query block, key block) pair of the call
    ``chunk_attention`` makes under ``bounds`` [R, 4 | 6] over key lists
    of ``list_rows`` rows: int32 [query blocks, key blocks], ``MIXED``,
    ``WHOLE`` or ``NONE``. The rule stays ``_intervals``, read at
    a block's first and last key: a block is WHOLE where one interval of
    every live row holds both (a dead row, every interval empty, does not
    stop it: the kernel zeroes those rows itself; a list's padded keys
    behind its last row lie in no interval, so their block is MIXED), and
    not visited where no interval of any row reaches into it. On the
    host, for the engine's counter: ``xp`` ``numpy``, and each row of
    ``bounds`` may stand for ``group`` query rows (a token's heads)."""
    block_q, lists = chunk_blocks(bounds.shape[0] * group, list_rows,
                                  block_q, block_k)
    nq = -(-bounds.shape[0] * group // block_q)
    live = _live(bounds, b0)
    inside, reach = [], []
    for at, (block, n) in enumerate(lists):
        # a list's blocks side by side: [rows, its blocks]
        first = (b0 if at else 0) + block * xp.arange(n)[None, :]
        end = first + block
        sees_all = sees_any = False
        for lo, hi in _intervals(
                bounds, b0,
                ("prefix", "rest")[at] if len(lists) == 2 else None):
            lo = 0 if lo is None else lo
            sees_all = sees_all | ((lo <= first) & (end <= hi))
            sees_any = sees_any | ((hi > first) & (lo < end) & (hi > lo))
        inside.append(sees_all & live)
        reach.append(sees_any)
    # how many rows of each query block are live, see all of a key block
    # and see any of it: one product with the rows' membership
    row = xp.arange(bounds.shape[0])[None, :] * group
    block = xp.arange(nq)[:, None] * block_q
    member = (row < block + block_q) & (row + group > block)
    steps = sum(n for _, n in lists)
    n_live, n_inside, n_reach = xp.split(
        member.astype(xp.float32) @ xp.concatenate(
            [live, *inside, *reach], axis=1).astype(xp.float32),
        [1, 1 + steps], axis=1)
    return xp.where(
        n_reach > 0,
        xp.where((n_inside == n_live) & (n_live > 0), WHOLE, MIXED),
        NONE).astype(xp.int32)


def count_classes(classes) -> tuple[int, int, int]:
    """How many pairs of ``block_classes``'s array (on the host) are
    ``CLASSES``: mixed, whole, not visited."""
    return tuple(int((classes == c).sum()) for c in (MIXED, WHOLE, NONE))


def _kernel(cls_ref, bounds_ref, q_ref, *refs, scale, b0, shared,
            prefix_blocks):
    """``cls_ref``: ``block_classes``, flat, a prefetched scalar operand.
    ``q_ref`` [heads a step, query block, d]; ``refs``: ``q_shared`` where
    there is a ``shared`` term; per key list its keys, its values (the
    step's heads') and, with ``shared``, its shared part (one for all
    heads); the output; the running maximum, sum and output. ``prefix_
    blocks``: the key blocks of the first of two lists (None: one list). A
    list's key block is what its refs hold."""
    from jax.experimental import pallas as pl

    if shared:
        qs_ref, *refs = refs
    *lists, o_ref, m_ref, l_ref, acc_ref = refs
    per = 3 if shared else 2
    lists = [lists[i:i + per] for i in range(0, len(lists), per)]
    kv = pl.program_id(2)
    cls = cls_ref[pl.program_id(1) * pl.num_programs(2) + kv]
    contract_last = (((1,), (1,)), ((), ()))
    heads = range(q_ref.shape[0])

    @pl.when(kv == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    def visit(k_ref, v_ref, ks_ref=None, skipped=0, col0=0, part=None,
              masked=True):
        """One key block of a list whose first row stands at position
        ``col0``, ``skipped`` key blocks into the key axis, for each of the
        step's heads. Written STAGE BY STAGE over the heads (every head's
        scores, then every head's softmax, then every head's values): a
        head's chain is products, then vector work that needs them whole,
        then products that need that, and within one head nothing
        overlaps; the heads are independent, so one's products run on the
        MXU beside another's softmax on the vector unit (PERF.md section
        6, PR 51). Not ``masked`` (a WHOLE block): the same arithmetic with
        the rule's answer known to be yes, so a live row's sums are bit
        for bit the masked visit's. With a ``shared`` term the two
        products are ONE over ``[q ; q_shared]`` and ``[k ; k_shared]``:
        the MXU adds its 128-deep passes in float32, which is the sum the
        two products' results made (bit for bit on a v5e, measured)."""
        rows = k_ref.shape[1]
        if masked:
            first = (kv - skipped) * rows + col0 if skipped else kv * rows
            col = first + jax.lax.broadcasted_iota(
                jnp.int32, (q_ref.shape[1], rows), 1)
            ok = _visible(col, bounds_ref[...], b0, part)
        scores = []
        for h in heads:
            if shared:
                s = jax.lax.dot_general(
                    jnp.concatenate([q_ref[h], qs_ref[h]], axis=1),
                    jnp.concatenate([k_ref[h], ks_ref[...]], axis=1),
                    contract_last, preferred_element_type=F32)
            else:
                s = jax.lax.dot_general(q_ref[h], k_ref[h], contract_last,
                                        preferred_element_type=F32)
            s = s * scale
            scores.append(jnp.where(ok, s, NEG) if masked else s)
        weights = []
        for h, s in zip(heads, scores):
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            if masked:
                p = jnp.where(ok, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + p.sum(axis=1, keepdims=True)
            m_ref[h] = m_new
            weights.append((alpha, p))
        for h, (alpha, p) in zip(heads, weights):
            v = v_ref[h]
            acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=F32)

    def walk(here, *refs, **where):
        """The key steps ``here`` of one list, each by its class."""
        pl.when(here & (cls == MIXED))(lambda: visit(*refs, **where))
        pl.when(here & (cls == WHOLE))(
            lambda: visit(*refs, masked=False, **where))

    if prefix_blocks is None:
        walk(True, *lists[0])
    else:
        walk(kv < prefix_blocks, *lists[0], part="prefix")
        walk(kv >= prefix_blocks, *lists[1], skipped=prefix_blocks, col0=b0,
             part="rest")

    @pl.when(kv == pl.num_programs(2) - 1)
    def _():
        # a dead row may have run through WHOLE blocks: zeroed by its
        # bounds, not by its sum
        l = l_ref[...]
        o_ref[...] = jnp.where(
            _live(bounds_ref[...], b0)[None],
            acc_ref[...] / jnp.where(l > 0, l, 1.0), 0.0).astype(o_ref.dtype)


def _lists(x):
    return x if isinstance(x, (tuple, list)) else (x,)


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
    blocks (``chunk_blocks``; a chunk's 896 new rows are not padded to
    1024). Every (query block, key block) pair has a class, read off
    ``bounds`` here (``block_classes``): the rule is asked a score only
    where a block's answer differs from row to row."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g, r, d = q.shape
    shared = q_shared is not None
    ks, vs = _lists(k), _lists(v)
    dv = vs[0].shape[2]
    kss = _lists(k_shared) if shared else [None] * len(ks)
    if len(ks) == 2 and ks[0].shape[1] != b0:
        raise ValueError("the first of two key lists holds the prefix's rows")
    list_rows = [x.shape[1] for x in ks]
    hb = next(n for n in (4, 2, 1)
              if n * len(ks) <= HEAD_BODIES and g % n == 0)
    classes = block_classes(bounds, b0, list_rows, block_q, block_k)
    block_q, blocks = chunk_blocks(r, list_rows, block_q, block_k)
    rp = classes.shape[0] * block_q
    steps = classes.shape[1]

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

    def mine(h, i, j, cls):
        return h, i, 0

    in_specs = [pl.BlockSpec((block_q, bounds.shape[1]),
                             lambda h, i, j, cls: (i, 0)),
                pl.BlockSpec((hb, block_q, d), mine)]
    args = [pad(bounds, rp), pad(q, rp)]
    if shared:
        in_specs.append(pl.BlockSpec((hb, block_q, q_shared.shape[2]),
                                     mine))
        args.append(pad(q_shared, rp))
    skipped = 0
    for one_k, one_v, one_ks, (rows, n) in zip(ks, vs, kss, blocks):
        blk = at(skipped, n)
        in_specs += [pl.BlockSpec((hb, rows, width), lambda h, i, j, cls,
                                  blk=blk: (h, blk(j), 0))
                     for width in (d, dv)]
        args += [pad(one_k, n * rows), pad(one_v, n * rows)]
        if shared:
            in_specs.append(pl.BlockSpec(
                (rows, one_ks.shape[1]), lambda h, i, j, cls, blk=blk:
                (blk(j), 0)))
            args.append(pad(one_ks, n * rows))
        skipped += n
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, b0=b0, shared=shared,
            prefix_blocks=blocks[0][1] if len(ks) == 2 else None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g // hb, rp // block_q, steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((hb, block_q, dv), mine),
            scratch_shapes=[pltpu.VMEM((hb, block_q, 1), F32),
                            pltpu.VMEM((hb, block_q, 1), F32),
                            pltpu.VMEM((hb, block_q, dv), F32)]),
        out_shape=jax.ShapeDtypeStruct((g, rp, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        name="attn_chunk_attention",
        interpret=interpret,
    )(classes.reshape(-1), *args)
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


# ------------------------------------------------- decode: a row's own pages


def _pages_kernel(layer_ref, table_ref, len_ref, q_ref, cache_ref, acc_out,
                  stat_out, buf_ref, sem_ref, qbd_ref, m_ref, l_ref, acc_ref,
                  *, scale, window, d, head_rows, page_tokens, n_pages,
                  group):
    """One row a grid step: its pages copied out of the cache where they
    lie, the next row's while this one's are computed. ``q_ref`` [rows,
    lane]: the row's query heads, ``head_rows`` a key-value head, a head's
    ``d`` values first in its whole lane tiles (``decode_pages``; so is a
    row of ``acc_out``); ``cache_ref`` the whole cache in HBM; ``buf_ref``
    [2, pages x page_tokens, kv_width]: two rows' pages, ``[k ; v]`` a
    token. ``qbd_ref`` [rows, kv_width / 2]: the queries block-diagonal
    over the keys' lanes, so that ONE product scores every head against
    its own key-value head's keys; ``acc_ref`` as wide: every head's
    weights over every head's values, of which a row's own head's lanes
    are its output. The pages are walked ``group`` at a time (one product
    over their tokens, the online softmax between groups)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    tokens = group * page_tokens
    rows, half = qbd_ref.shape
    lane = q_ref.shape[1]
    per = lane // d    # heads under 128 values share a lane tile

    def pages_of(i):
        """The pages row ``i`` needs, ``[first, last)``: none wholly behind
        its rows, none wholly before its window."""
        n = len_ref[i]
        last = jax.lax.div(n + page_tokens - 1, page_tokens)
        if window is None:
            return 0, last
        return jax.lax.div(jnp.maximum(n - window, 0), page_tokens), last

    def copy(i, slot, p):
        return pltpu.make_async_copy(
            cache_ref.at[layer_ref[0], table_ref[i * n_pages + p]],
            buf_ref.at[slot, pl.ds(p * page_tokens, page_tokens)],
            sem_ref.at[slot, p])

    def fetch(i, slot):
        def start(p, carry):
            copy(i, slot, p).start()
            return carry

        jax.lax.fori_loop(*pages_of(i), start, 0)

    slot = jax.lax.rem(b, 2)

    @pl.when(b == 0)
    def _():
        # what no copy has written is masked, and must be finite
        buf_ref[...] = jnp.zeros(buf_ref.shape, buf_ref.dtype)
        fetch(0, 0)

    pl.when(b + 1 < pl.num_programs(0))(lambda: fetch(b + 1, 1 - slot))

    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)

    def rows_of(h):
        """The query rows of key-value head ``h``, whose keys (and values)
        start ``h % per * d`` lanes into lane tile ``h // per`` of a row."""
        return (row >= h * head_rows) & (row < (h + 1) * head_rows)

    n = len_ref[b]
    first, last = pages_of(b)
    m_ref[...] = jnp.full(m_ref.shape, NEG, F32)
    l_ref[...] = jnp.zeros(l_ref.shape, F32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, F32)
    q = q_ref[...].astype(F32)
    for t in range(half // lane):
        tile = jnp.zeros((rows, lane), F32)
        for j in range(per):
            tile = jnp.where(rows_of(t * per + j),
                             pltpu.roll(q, j * d, 1) if j else q, tile)
        qbd_ref[:, t * lane:(t + 1) * lane] = tile.astype(qbd_ref.dtype)

    def visit(gi, carry):
        for k in range(group):
            p = gi * group + k
            pl.when((p >= first) & (p < last))(
                lambda p=p: copy(b, slot, p).wait())
        at = pl.multiple_of(gi * tokens, tokens)
        s = jax.lax.dot_general(
            qbd_ref[...], buf_ref[slot, pl.ds(at, tokens), :half],
            (((1,), (1,)), ((), ())), preferred_element_type=F32) * scale
        col = at + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = col < n
        if window is not None:
            ok &= col >= n - window
        s = jnp.where(ok, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        w = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + w.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            w.astype(buf_ref.dtype), buf_ref[slot, pl.ds(at, tokens), half:],
            (((1,), (0,)), ((), ())), preferred_element_type=F32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(jax.lax.div(first, group),
                      jax.lax.div(last + group - 1, group), visit, 0)
    out = jnp.zeros(acc_out.shape, F32)
    for t in range(half // lane):
        tile = acc_ref[:, t * lane:(t + 1) * lane]
        for j in range(per):
            out = jnp.where(
                rows_of(t * per + j),
                pltpu.roll(tile, lane - j * d, 1) if j else tile, out)
    acc_out[...] = out
    lead = jax.lax.broadcasted_iota(
        jnp.int32, stat_out.shape, 1) < stat_out.shape[1] // 2
    stat_out[...] = jnp.where(lead, m_ref[...], l_ref[...])


#: what two rows' pages may take on the chip (the table's width is the
#: program's: a longer one goes through XLA)
PAGES_VMEM = 8 << 20
#: pages a row walks at once, one product over their tokens: a visit a
#: page is a chain of two products and a softmax that waits 0.5 us for
#: itself (PERF.md section 6, PR 49: LFM2's layer 138 us a page at a
#: time for 99 three at once, the copies alone 98)
PAGE_GROUP = 4


def decode_pages_fits(head_dim: int, kv_width: int, page_tokens: int,
                      n_pages: int) -> bool:
    """Whether ``decode_pages`` takes a cache of such rows under such a
    table: heads that fill or evenly share the lanes of whole tiles, keys
    of whole tiles, pages of whole bfloat16 row tiles, and two rows' pages
    within ``PAGES_VMEM``. (A rehearsal's tiny widths do not fit, and go
    through XLA wherever they run.)"""
    return ((kv_width // 2) % 128 == 0 and page_tokens % 16 == 0
            and (128 % head_dim == 0 or head_dim % 128 == 0)
            and 2 * n_pages * page_tokens * kv_width * 2 <= PAGES_VMEM)


@functools.partial(jax.jit, static_argnames=(
    "kv_heads", "scale", "window", "interpret"))
def decode_pages(q, cache, layer, page_table, ctx_len, *, kv_heads, scale,
                 window=None, interpret=False):
    """The OWN-rows part of a decode step's softmax, each row's pages read
    where they lie. ``q`` [B, heads, d]; ``cache`` [layers, pages,
    page_tokens, kv_width], the WHOLE cache, rows ``[k ; v]`` of
    ``kv_heads`` heads of ``d``; ``layer`` an int32 scalar (traced: the
    layers run in one scan); ``page_table`` [B, P]; ``ctx_len`` [B]: row
    ``b`` sees the first ``ctx_len[b]`` tokens of its pages, under a
    ``window`` the last ``window`` of those. Returns what
    ``models/lm/common.py`` ``softmax_sums`` returns for those rows:
    ``(m, l, acc)`` float32, [B, kv_heads, group, 1 | 1 | d], not divided;
    a row that sees nothing ``(NEG, 0, 0)``.

    Grid (B,): step ``b`` copies row ``b + 1``'s pages ``cache[layer,
    page_table[b + 1, p]]`` into one half of a buffer while it walks row
    ``b``'s in the other, so nothing is gathered and a page is read once.
    A page the row does not need (wholly behind ``ctx_len``, or wholly
    before the window) is not copied; the pages are computed
    ``PAGE_GROUP`` at a time, what is not visible masked, and a group
    none of whose pages the row needs is not computed. The group's query
    rows are padded to 8, a head's values to whole lane tiles."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, d = q.shape
    page_tokens, width = cache.shape[2:]
    n_pages = page_table.shape[1]
    half, g = width // 2, heads // kv_heads
    if not decode_pages_fits(d, width, page_tokens, n_pages):
        raise ValueError(f"heads of {d} in rows of {width}, {n_pages} pages "
                         f"of {page_tokens}: not whole tiles, or too many")
    gpad = -(-g // 8) * 8
    rows = -(-kv_heads * gpad // 16) * 16
    lane = -(-d // 128) * 128
    qg = jnp.pad(q.reshape(b, kv_heads, g, d),
                 ((0, 0), (0, 0), (0, gpad - g), (0, lane - d)))
    qg = jnp.pad(qg.reshape(b, kv_heads * gpad, lane),
                 ((0, 0), (0, rows - kv_heads * gpad), (0, 0)))

    def mine(i, *_):
        return i, 0, 0

    group = min(n_pages, PAGE_GROUP)
    held = -(-n_pages // group) * group * page_tokens
    acc, stat = pl.pallas_call(
        functools.partial(_pages_kernel, scale=scale, window=window, d=d,
                          head_rows=gpad, page_tokens=page_tokens,
                          n_pages=n_pages, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[pl.BlockSpec((None, rows, lane), mine),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((None, rows, lane), mine),
                       pl.BlockSpec((None, rows, 128), mine)],
            scratch_shapes=[
                pltpu.VMEM((2, held, width), cache.dtype),
                pltpu.SemaphoreType.DMA((2, n_pages)),
                pltpu.VMEM((rows, half), q.dtype),
                pltpu.VMEM((rows, 1), F32),
                pltpu.VMEM((rows, 1), F32),
                pltpu.VMEM((rows, half), F32)]),
        out_shape=[jax.ShapeDtypeStruct((b, rows, lane), F32),
                   jax.ShapeDtypeStruct((b, rows, 128), F32)],
        compiler_params=pltpu.CompilerParams(
            # a step starts the next row's copies: the rows run in order
            dimension_semantics=("arbitrary",),
            # half the chip's 128 MiB, far more than the buffers above:
            # what the call may take XLA cannot hold on chip ACROSS it.
            # At 32 MiB XLA used the room the gathered rows had left to
            # fetch LFM2's dense weights (59 MB) behind the mixers of
            # EVERY layer, for the two that read them: 0.9 ms a step
            # (PERF.md section 6, PR 49)
            vmem_limit_bytes=64 * 1024 * 1024),
        # the pages it reads, not the cache it is handed
        cost_estimate=pl.CostEstimate(
            flops=4 * b * rows * half * n_pages * page_tokens,
            transcendentals=b * rows * n_pages * page_tokens,
            bytes_accessed=b * n_pages * page_tokens * width
            * cache.dtype.itemsize),
        name="attn_decode_pages",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      page_table.astype(jnp.int32).reshape(-1), ctx_len.astype(jnp.int32),
      qg, cache)
    acc = acc[:, :kv_heads * gpad, :d].reshape(b, kv_heads, gpad, d)[:, :, :g]
    stat = stat[:, :kv_heads * gpad].reshape(b, kv_heads, gpad, 128)[:, :, :g]
    return stat[..., :1], stat[..., 64:65], acc
