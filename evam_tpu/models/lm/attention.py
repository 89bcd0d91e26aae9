"""Plain key-value attention with grouped queries, for every family that
has it (models/lm/jamba.py: 20 query heads over ONE key-value head, no
positional term, no head norms; models/lm/lfm2_moe.py: 32 query heads over
8 key-value heads, an RMSNorm on every query and key head, rotary
positions; models/lm/laguna.py: TWO kinds of layer in one model, 48 query
heads that see everything earlier under a rescaled rotation of half of
each head, and 64 that see the last 512 positions under a plain rotation
of the whole head, both over 8 key-value heads with head norms and a gate
on every head's output).

``q = W_q h`` as ``heads`` of ``head_dim``, ``k = W_k h`` and ``v = W_v h``
as ``kv_heads`` of ``head_dim``, no bias; query head ``a`` reads key-value
head ``a // (heads / kv_heads)``; causal softmax, scale head_dim^-1/2, over
every earlier position or, under a ``window``, over the token and the
``window - 1`` before it; out = ``W_o [heads]``, each head's output first
multiplied by ``sigmoid(W_g h)`` where the layer has a ``gate``. The cache
row of a token is ``[k ; v]`` (``kv_width`` values: the keys of every
key-value head, then the values), keys stored as they are scored (normed
and rotated). A prefill chunk attends over ONE list of cache rows, the
shared prefix's, the continued sequence's and the chunk's own, under the
bounds ``common.chunk_bounds`` gives every family (under a window with a
first visible row of each list beside the last); a decode step in two
parts merged by their softmax sums in float32: each row against its OWN
cached rows, and ALL rows' queries against the shared prefix's rows in one
product per key-value head, read once a step. Under a window the prefix's
rows are only those a token behind the prefix can still see
(``window_pages``: the last pages of it), each row masked from its own
lower bound. The prefix's part goes through XLA; two parts are Pallas
kernels (ops/pallas_attention.py). A chunk of a kind that says so
(``chunk_kernel``) runs in one that keeps the scores on the chip:
measured on a v5e (PERF.md section 6, PR 40) XLA's materialised scores
are 1.67 ms a layer of LFM2's chunk (8 key-value heads of 64 under groups
of 4, six layers: 30 % of the chunk) for the kernel's 0.36, and 0.16 ms a
layer of Jamba's (one key-value head of 128, two layers of 28) for the
kernel's 0.22. A decode step's OWN part runs in one that walks each row's
page table over the whole cache (``decode_pages``): the pages are read
once, where they lie, where XLA gathered them (100 MB a layer of Laguna's
64-row step), split keys from values and turned both; which rows take it
is read off their size (``_own_pages_kernel``: 0.14 ms a layer of Laguna's
step for XLA's 0.48, 0.075 of LFM2's for 0.13, and Jamba's 196 KB a row
left to XLA, 0.03 for the kernel's 0.05; PERF.md section 6, PR 49).

What differs is data of the LAYER KIND, whatever object carries it (a
family with one kind of attention layer hands its config, one with several
a ``Kind`` each): ``hidden``, ``heads``, ``kv_heads``, ``head_dim``,
``eps``, ``chunk_kernel``, ``window`` (None: none) and ``rope`` (None: no
rotation; else a ``Rope``: a table of ``rotated / 2`` frequencies, static,
over the FIRST ``rotated`` values of a head in the half-split pairing
``(x_j, x_{j + rotated/2})`` at the token's position, the turned values
times ``scale``, the rest of the head passed as it is); and of the layer:
``q_norm`` / ``k_norm`` (one gain of ``head_dim`` each, applied to every
head before the rotation; absent: no head norms) and ``gate`` ([hidden,
heads]; absent: no gate).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from evam_tpu.models.lm import common
from evam_tpu.models.lm.common import BF16, F32, mm, rms_norm
from evam_tpu.ops import pallas_attention


@dataclass(frozen=True)
class Rope:
    """A rotation as data. ``rotated``: how many leading values of a head
    turn (None: all). ``yarn``: None, or ``(factor, original positions,
    beta_fast, beta_slow)``: frequencies that turn more than ``beta_fast``
    times in the original positions stay, those that turn fewer than
    ``beta_slow`` times are divided by ``factor``, a linear ramp between."""
    theta: float
    rotated: int | None = None
    scale: float = 1.0
    yarn: tuple | None = None


@dataclass(frozen=True)
class Kind:
    """One kind of attention layer of a family that has several."""
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    chunk_kernel: bool = True
    rope: Rope | None = None
    window: int | None = None


def tensor_shapes(kind, head_norms: bool,
                  gate: bool = False) -> dict[str, tuple]:
    """The mixer's tensors (the norm before it is the layer's)."""
    h, q, kv = kind.hidden, kind.heads * kind.head_dim, kv_width(kind) // 2
    out = {"q": (h, q), "k": (h, kv), "v": (h, kv), "o": (q, h)}
    if head_norms:
        out.update(q_norm=(kind.head_dim,), k_norm=(kind.head_dim,))
    if gate:
        out["gate"] = (h, kind.heads)
    return out


def kv_width(kind) -> int:
    """Values a page row holds: every key-value head's key and value."""
    return 2 * kind.kv_heads * kind.head_dim


def rope_table(rope: Rope, head_dim: int):
    """The ``rotated / 2`` frequencies (float32), a constant of the
    program: ``theta^(-2j / rotated)``, under ``yarn`` blended with the
    same divided by the factor. It does not depend on a sequence's
    length."""
    r = rope.rotated or head_dim
    half = r // 2
    inv = 1.0 / rope.theta ** (jnp.arange(half, dtype=F32) / half)
    if rope.yarn is None:
        return inv
    factor, original, fast, slow = rope.yarn

    def dim(turns):
        return (r * math.log(original / (2 * math.pi * turns))
                / (2 * math.log(rope.theta)))

    lo = max(math.floor(dim(fast)), 0)
    hi = min(math.ceil(dim(slow)), r - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=F32) - lo)
                    / (hi - lo if hi > lo else 0.001), 0.0, 1.0)
    return inv / factor * ramp + inv * (1.0 - ramp)


def rotate_half(x, pos, rope: Rope):
    """Rotary positions in the half-split pairing: ``x`` [T, heads,
    head_dim] (float32 inside), of its first ``rotated`` values pair
    ``(x_j, x_{j + rotated/2})`` turned by ``pos * f_j`` (``rope_table``)
    and multiplied by ``scale``; the values behind them pass."""
    r = rope.rotated or x.shape[-1]
    half = r // 2
    ang = pos.astype(F32)[:, None] * rope_table(rope, x.shape[-1])
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x = x.astype(F32)
    a, b = x[..., :half], x[..., half:r]
    parts = [a * cos - b * sin, b * cos + a * sin]
    if rope.scale != 1.0:
        parts = [p * rope.scale for p in parts]
    if r < x.shape[-1]:
        parts.append(x[..., r:])
    return jnp.concatenate(parts, axis=-1)


def qkv(kind, lp: dict, h, pos=None):
    """Per token of the normed rows ``h``: the queries [T, heads,
    head_dim] and the cache row ``[k ; v]``; heads normed where the layer
    has ``q_norm`` / ``k_norm``, then rotated to ``pos`` where the kind
    has a ``rope``."""
    t, hd = h.shape[0], kind.head_dim
    q = mm(h, lp["q"]).reshape(t, kind.heads, hd)
    k, v = mm(h, lp["k"]), mm(h, lp["v"])
    if "q_norm" in lp or kind.rope is not None:
        k = k.reshape(t, kind.kv_heads, hd)
        if "q_norm" in lp:
            q = rms_norm(q, lp["q_norm"], kind.eps)
            k = rms_norm(k, lp["k_norm"], kind.eps)
        if kind.rope is not None:
            q = rotate_half(q, pos, kind.rope).astype(BF16)
            k = rotate_half(k, pos, kind.rope).astype(BF16)
        k = k.reshape(t, -1)
    return q, jnp.concatenate([k, v], axis=-1)


def head_gates(lp: dict, h):
    """Per token and query head ``sigmoid(W_g h)`` (float32), or None for
    a layer without a ``gate``."""
    if "gate" not in lp:
        return None
    with jax.named_scope("gate"):
        return jax.nn.sigmoid(jnp.dot(h, lp["gate"],
                                      preferred_element_type=F32))


def window_span(window, n_pages: int, n_prefix, page_tokens: int, xp=jnp):
    """Of a shared prefix of ``n_pages`` pages whose length ``n_prefix`` is
    an argument of the program (a chunk, the prefix's own among them): how
    many pages a token BEHIND the prefix can see into under ``window``
    (positions ``n_prefix - window + 1`` and later), a static length, one
    page more where ``n_prefix`` need not end a page, and the first of
    them. No window: all of them, from 0. ``xp``: ``numpy`` on the
    host."""
    if window is None:
        return n_pages, 0
    keep = min(n_pages, -(-(window - 1) // page_tokens) + 1)
    return keep, xp.clip(-(-n_prefix // page_tokens) - keep, 0,
                         n_pages - keep)


def window_pages(window, pages, n_prefix, page_tokens: int):
    """The shared prefix's pages that hold a row some token BEHIND the
    prefix can see under ``window``, and the position of their first row.
    No window: all of them, from 0. With ``n_prefix`` a Python int (a
    decode step: the whole prefix is there) the slice is static, exactly
    those pages; traced it is a dynamic slice of ``window_span``'s static
    length."""
    if pages is None or window is None:
        return pages, 0
    if isinstance(n_prefix, int):
        first = max(n_prefix - window + 1, 0) // page_tokens
        return pages[first:-(-n_prefix // page_tokens)], first * page_tokens
    keep, first = window_span(window, len(pages), n_prefix, page_tokens)
    return (jax.lax.dynamic_slice(jnp.asarray(pages), (first,), (keep,)),
            first * page_tokens)


def _sums(kind, score_expr, value_expr, q, rows, visible):
    """``common.softmax_sums`` of grouped queries ``q`` [..., kv_heads,
    group, head_dim] over cache rows ``rows`` [..., kv_width]."""
    hd, half = kind.head_dim, kv_width(kind) // 2
    lead = rows.shape[:-1]
    keys = rows[..., :half].reshape(*lead, kind.kv_heads, hd)
    values = rows[..., half:].reshape(*lead, kind.kv_heads, hd)
    return common.softmax_sums(hd ** -0.5, score_expr, value_expr, q, keys,
                               values, visible)


def _grouped(kind, q):
    """[T, heads, head_dim] -> [T, kv_heads, heads / kv_heads, head_dim]."""
    return q.reshape(q.shape[0], kind.kv_heads, kind.heads // kind.kv_heads,
                     kind.head_dim)


def _out(lp: dict, o, gates):
    """``W_o`` over the heads' outputs ``o`` [T, kv_heads, group,
    head_dim], each first times its gate where there are ``gates`` [T,
    heads]."""
    if gates is not None:
        o = o.astype(F32) * gates.reshape(*o.shape[:-1], 1)
    return mm(o.astype(BF16).reshape(o.shape[0], -1), lp["o"])


def attn_prefill(kind, lp: dict, q, kv, seg, prefix, n_prefix, cont, n_cont,
                 gates=None, prefix_first=0):
    """A packed chunk: every (token, head) over ONE list of cache rows,
    the shared prefix's, the continued sequence's and the chunk's own,
    under ``common.chunk_bounds`` (with the kind's ``window``, where it
    has one). ``prefix`` and ``cont`` may be None; ``prefix_first`` is the
    position of ``prefix``'s first row (``window_pages``). On the chip,
    for a kind with ``chunk_kernel``, the query heads of a key-value head
    are one list of query rows over that head's keys and values in
    ops/pallas_attention.py."""
    t, hd, g = kv.shape[0], kind.head_dim, kind.kv_heads
    rows = jnp.concatenate(
        [r for r in (prefix, cont, kv) if r is not None], axis=0)
    bounds, b0 = common.chunk_bounds(
        seg, n_prefix, n_cont, 0 if prefix is None else prefix.shape[0],
        0 if cont is None else cont.shape[0], kind.window, prefix_first)
    if kind.chunk_kernel and common.on_tpu():
        group = kind.heads // g
        keys, values = (
            part.reshape(-1, g, hd).transpose(1, 0, 2)
            for part in jnp.split(rows, 2, axis=1))
        o = pallas_attention.chunk_attention(
            _grouped(kind, q).transpose(1, 0, 2, 3).reshape(g, -1, hd), keys,
            values, jnp.repeat(bounds, group, axis=0), scale=hd ** -0.5,
            b0=b0)
        o = o.reshape(g, t, group, hd).transpose(1, 0, 2, 3)
    else:
        seen = pallas_attention._visible(
            jnp.arange(rows.shape[0])[None, :], bounds, b0)
        o = common.merge_softmax_sums(
            _sums(kind, "tkgd,skd->tkgs", "tkgs,skd->tkgd", _grouped(kind, q),
                  rows, seen[:, None, None, :]), None)
    return _out(lp, o, gates)


def chunk_key_blocks(kind, seg, n_prefix: int, n_cont: int, prefix_pages: int,
                     cont_rows: int, page_tokens: int):
    """On the host: the classes of the (query block, key block) pairs
    (ops/pallas_attention.py ``block_classes``) of the kernel call that
    ``attn_prefill`` makes for such a chunk of a ``kind`` with
    ``chunk_kernel``, handed the prefix's pages as a chunk's program hands
    them (``window_span`` of ``prefix_pages``) and ``cont_rows`` continued
    rows; ``seg`` a numpy array."""
    keep, first = window_span(kind.window, prefix_pages, n_prefix,
                              page_tokens, xp=np)
    prefix_rows = keep * page_tokens
    bounds, b0 = common.chunk_bounds(
        seg, n_prefix, n_cont, prefix_rows, cont_rows, kind.window,
        first * page_tokens, xp=np)
    return pallas_attention.block_classes(
        bounds, b0, (prefix_rows + cont_rows + len(seg),),
        group=kind.heads // kind.kv_heads, xp=np)


#: bytes of a row's own pages from which the kernel is the faster: under
#: them a grid step a row costs more than the copies it saves (measured on
#: a v5e, PERF.md section 6, PR 49: Jamba's 196 KB a row, 64 rows a layer,
#: 50 us through the kernel for XLA's 31; LFM2's 786 KB 99 for 133;
#: Laguna's 1.5 MB 143 for 482)
OWN_PAGES_KERNEL_FROM = 512 << 10


def _own_pages_kernel(kind, cache, page_table) -> bool:
    """Whether a decode step's own part runs in the kernel: on the chip,
    for rows the kernel takes (a rehearsal's tiny heads go through XLA)
    and that are worth a grid step each."""
    page_tokens, width = cache.shape[-2:]
    n_pages = page_table.shape[1]
    return (common.on_tpu() and pallas_attention.decode_pages_fits(
        kind.head_dim, width, page_tokens, n_pages)
        and n_pages * page_tokens * width * cache.dtype.itemsize
        >= OWN_PAGES_KERNEL_FROM)


def _own_sums(kind, q, cache, layer, page_table, ctx_len):
    """``common.softmax_sums`` of each row's queries ``q`` [B, heads,
    head_dim] over its OWN rows: the pages ``page_table`` [B, P] names of
    ``layer`` of the whole ``cache``, visible below ``ctx_len`` and, under
    the kind's ``window``, from ``ctx_len - window`` on."""
    if _own_pages_kernel(kind, cache, page_table):
        return pallas_attention.decode_pages(
            q, cache, layer, page_table, ctx_len, kv_heads=kind.kv_heads,
            scale=kind.head_dim ** -0.5, window=kind.window)
    ctx = common.layer_page_rows(cache, layer, page_table)
    at = jnp.arange(ctx.shape[1])[None, None, None, :]
    n_own = ctx_len[:, None, None, None]
    own = at < n_own
    if kind.window is not None:
        own &= at >= n_own - kind.window
    return _sums(kind, "bkgd,btkd->bkgt", "bkgt,btkd->bkgd",
                 _grouped(kind, q), ctx, own)


def attn_decode(kind, lp: dict, q, cache, layer, page_table, ctx_len, prefix,
                n_prefix, gates=None, prefix_first=0):
    """One new token per row, its softmax in two parts (as
    mla.mla_decode): each row against its OWN cached rows, the pages
    ``page_table`` [B, P] names of ``layer`` of the whole ``cache``
    [layers, pages, page_tokens, kv_width], visible below ``ctx_len`` (the
    new token's own row the last of them); all rows' queries against the
    shared prefix rows ``prefix`` (the first of them at position
    ``prefix_first``) in one product per key-value head. Under the kind's
    ``window`` a row whose token stands at position ``n_prefix + ctx_len -
    1`` sees the ``window`` positions that end there: of its own rows
    those from ``ctx_len - window`` on, of the prefix those from
    ``n_prefix + ctx_len - window`` on. On the chip the own part is
    ops/pallas_attention.py ``decode_pages``, which reads each row's pages
    where they lie (rows worth a grid step each: ``_own_pages_kernel``);
    elsewhere the pages are gathered and the part goes through XLA, which
    is what the kernel is checked against."""
    sums = _own_sums(kind, q, cache, layer, page_table, ctx_len)
    shared = None
    if prefix is not None:
        at = prefix_first + jnp.arange(prefix.shape[0])
        seen = at < n_prefix
        if kind.window is not None:
            seen = seen & (at >= n_prefix + ctx_len[:, None, None, None]
                           - kind.window)
        shared = _sums(kind, "bkgd,skd->bkgs", "bkgs,skd->bkgd",
                       _grouped(kind, q), prefix, seen)
    return _out(lp, common.merge_softmax_sums(sums, shared), gates)
