"""Plain key-value attention with grouped queries, for every family that
has it (models/lm/jamba.py: 20 query heads over ONE key-value head, no
positional term, no head norms; models/lm/lfm2_moe.py: 32 query heads over
8 key-value heads, an RMSNorm on every query and key head, rotary
positions).

``q = W_q h`` as ``heads`` of ``head_dim``, ``k = W_k h`` and ``v = W_v h``
as ``kv_heads`` of ``head_dim``, no bias; query head ``a`` reads key-value
head ``a // (heads / kv_heads)``; causal softmax, scale head_dim^-1/2; out
= ``W_o [heads]``. The cache row of a token is ``[k ; v]`` (``kv_width``
values: the keys of every key-value head, then the values), keys stored as
they are scored (normed and rotated). A prefill chunk attends over ONE
list of cache rows, the shared prefix's, the continued sequence's and the
chunk's own, under the bounds ``common.chunk_bounds`` gives every family;
a decode step in two parts merged by their softmax sums in float32: each
row against its OWN cached rows, and ALL rows' queries against the shared
prefix's rows in one product per key-value head, read once a step. Both
go through XLA, but a chunk of a family that says so (``chunk_kernel``)
runs in a Pallas kernel that keeps the scores on the chip
(ops/pallas_attention.py): measured on a v5e (PERF.md section 6, PR 40)
XLA's materialised scores are 1.67 ms a layer of LFM2's chunk (8 key-value
heads of 64 under groups of 4, six layers: 30 % of the chunk) for the
kernel's 0.36, and 0.16 ms a layer of Jamba's (one key-value head of 128,
two layers of 28) for the kernel's 0.22.

What differs between the families is data: of the config ``heads``,
``kv_heads``, ``head_dim``, ``eps``, ``chunk_kernel`` and ``rope_theta``
(None: no rotation; else the half-split pairing ``(x_j, x_{j +
head_dim/2})`` at the token's position); of the layer ``q_norm`` / ``k_norm`` (one gain of ``head_dim``
each, applied to every head before the rotation; absent: no head norms).
"""

from __future__ import annotations

import jax.numpy as jnp

from evam_tpu.models.lm import common
from evam_tpu.models.lm.common import BF16, F32, mm, rms_norm
from evam_tpu.ops import pallas_attention, pallas_mla


def tensor_shapes(cfg, head_norms: bool) -> dict[str, tuple]:
    """The mixer's tensors (the norm before it is the layer's)."""
    h, q, kv = cfg.hidden, cfg.heads * cfg.head_dim, kv_width(cfg) // 2
    out = {"q": (h, q), "k": (h, kv), "v": (h, kv), "o": (q, h)}
    if head_norms:
        out.update(q_norm=(cfg.head_dim,), k_norm=(cfg.head_dim,))
    return out


def kv_width(cfg) -> int:
    """Values a page row holds: every key-value head's key and value."""
    return 2 * cfg.kv_heads * cfg.head_dim


def rotate_half(x, pos, theta: float):
    """Rotary positions in the half-split pairing: ``x`` [T, heads,
    head_dim] (float32 inside), pair ``(x_j, x_{j + head_dim/2})`` turned
    by ``pos * theta^(-2j / head_dim)``."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x = x.astype(F32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def qkv(cfg, lp: dict, h, pos=None):
    """Per token of the normed rows ``h``: the queries [T, heads,
    head_dim] and the cache row ``[k ; v]``; heads normed where the layer
    has ``q_norm`` / ``k_norm``, then rotated to ``pos`` where the config
    has a ``rope_theta``."""
    t, hd = h.shape[0], cfg.head_dim
    q = mm(h, lp["q"]).reshape(t, cfg.heads, hd)
    k, v = mm(h, lp["k"]), mm(h, lp["v"])
    if "q_norm" in lp or cfg.rope_theta is not None:
        k = k.reshape(t, cfg.kv_heads, hd)
        if "q_norm" in lp:
            q = rms_norm(q, lp["q_norm"], cfg.eps)
            k = rms_norm(k, lp["k_norm"], cfg.eps)
        if cfg.rope_theta is not None:
            q = rotate_half(q, pos, cfg.rope_theta).astype(BF16)
            k = rotate_half(k, pos, cfg.rope_theta).astype(BF16)
        k = k.reshape(t, -1)
    return q, jnp.concatenate([k, v], axis=-1)


def _sums(cfg, score_expr, value_expr, q, rows, visible):
    """``common.softmax_sums`` of grouped queries ``q`` [..., kv_heads,
    group, head_dim] over cache rows ``rows`` [..., kv_width]."""
    hd, half = cfg.head_dim, kv_width(cfg) // 2
    lead = rows.shape[:-1]
    keys = rows[..., :half].reshape(*lead, cfg.kv_heads, hd)
    values = rows[..., half:].reshape(*lead, cfg.kv_heads, hd)
    return common.softmax_sums(hd ** -0.5, score_expr, value_expr, q, keys,
                               values, visible)


def _grouped(cfg, q):
    """[T, heads, head_dim] -> [T, kv_heads, heads / kv_heads, head_dim]."""
    return q.reshape(q.shape[0], cfg.kv_heads, cfg.heads // cfg.kv_heads,
                     cfg.head_dim)


def attn_prefill(cfg, lp: dict, q, kv, seg, prefix, n_prefix, cont, n_cont):
    """A packed chunk: every (token, head) over ONE list of cache rows,
    the shared prefix's, the continued sequence's and the chunk's own,
    under ``common.chunk_bounds``. ``prefix`` and ``cont`` may be None. On
    the chip, for a family with ``chunk_kernel``, the query heads of a
    key-value head are one list of query rows over that head's keys and
    values in ops/pallas_attention.py."""
    t, hd, g = kv.shape[0], cfg.head_dim, cfg.kv_heads
    rows = jnp.concatenate(
        [r for r in (prefix, cont, kv) if r is not None], axis=0)
    bounds, b0 = common.chunk_bounds(
        seg, n_prefix, n_cont, 0 if prefix is None else prefix.shape[0],
        0 if cont is None else cont.shape[0])
    if cfg.chunk_kernel and common.on_tpu():
        group = cfg.heads // g
        keys, values = (
            part.reshape(-1, g, hd).transpose(1, 0, 2)
            for part in jnp.split(rows, 2, axis=1))
        o = pallas_attention.chunk_attention(
            _grouped(cfg, q).transpose(1, 0, 2, 3).reshape(g, -1, hd), keys,
            values, jnp.repeat(bounds, group, axis=0), scale=hd ** -0.5,
            b0=b0)
        o = o.reshape(g, t, group, hd).transpose(1, 0, 2, 3)
    else:
        seen = pallas_mla._visible(jnp.arange(rows.shape[0])[None, :],
                                   bounds, b0)
        o = common.merge_softmax_sums(
            _sums(cfg, "tkgd,skd->tkgs", "tkgs,skd->tkgd", _grouped(cfg, q),
                  rows, seen[:, None, None, :]), None)
    return mm(o.astype(BF16).reshape(t, -1), lp["o"])


def attn_decode(cfg, lp: dict, q, ctx, ctx_len, prefix, n_prefix):
    """One new token per row, its softmax in two parts (as
    mla.mla_decode): each row against its OWN cached rows ``ctx`` [B, T,
    kv_width], visible below ``ctx_len``; all rows' queries against the
    shared prefix rows ``prefix`` in one product per key-value head."""
    q = _grouped(cfg, q)
    own = (jnp.arange(ctx.shape[1])[None, None, None, :]
           < ctx_len[:, None, None, None])
    sums = _sums(cfg, "bkgd,btkd->bkgt", "bkgt,btkd->bkgd", q, ctx, own)
    shared = None
    if prefix is not None:
        seen = jnp.arange(prefix.shape[0]) < n_prefix
        shared = _sums(cfg, "bkgd,skd->bkgs", "bkgs,skd->bkgd", q, prefix,
                       seen)
    o = common.merge_softmax_sums(sums, shared).astype(BF16)
    return mm(o.reshape(o.shape[0], -1), lp["o"])
