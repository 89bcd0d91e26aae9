"""Latent attention (MLA), for every family that has it
(models/lm/deepseek_v2.py: rotated, with a query down-projection;
models/lm/kimi_linear.py: position-free, the query projected directly).

``[c_kv ; k_r] = W_kva h``, ``c_kv = norm(c_kv)``; the cache holds ``[c_kv
; k_r ; zeros]`` per token and layer: the model's ``latent`` values (576
at the published widths) in a row of whole lane tiles
(``common.row_width``: 640), so that the cache lies rows-minor on the chip
and enters and leaves both step programs where it lies; ``[k_nope ; v] =
W_kvb c_kv`` per head; ``score = (q_nope . k_nope + q_r . k_r) * scale``.

The two step programs take the two sides of one trade. A DECODE step (one
query a row, bound by the bytes of the cache) runs in the ABSORBED form:
``W_kvb``'s key half is folded into the query and its value half into the
output, so every key is read as a latent row (the absorbed query's rope
part is followed by as many zeros as the stored row's, zeros against
zeros, so a product over "the whole row" is the one over ``latent``), in
two parts, all rows' queries against the shared prefix's rows in one
product and each row against its own pages, merged by their softmax sums.
A PREFILL chunk (512 tokens a row of the cache, bound by its products)
runs over MATERIALISED heads, the form the references compute: a query-key
pair costs ``nope + rope + v_dim`` multiply-adds (320; 384 with the zeros
of a row's last lane tile) where the absorbed form spends ``2 kv_rank +
rope`` (1 088; 1 152 with those zeros). The shared
prefix's heads are expanded ONCE, when warm-up has prefilled its rows
(``expand``), and held on the device beside the weights
(engine/generate.py); the continued and the chunk's own rows are expanded
a chunk; the rope part stays the ONE list all heads share, as it lies in a
stored row. The chunk kernel (ops/pallas_attention.py) walks the held
heads and the new rows' as two lists, so nothing is copied behind the
prefix's 134 MB a layer.

The up-projections are HELD as their products read them, laid once where
the weights are made (``store``): every tensor is made under its published
name, key and shape (``tensor_shapes``: the benchmark's plain references
make the same values from the same seed), then ``kv_b`` is kept per head
with the contraction last, ``w_uk`` [heads, nope, kv_rank] and ``w_uv``
[heads, v_dim, kv_rank], the query's projection (``q_b``, or ``q`` where
there is no down-projection) the same way in its two parts, ``w_qn``
[heads, nope, in] and ``w_qr`` (rope part), and ``o`` as [heads, v_dim,
hidden]. So the query comes out of its products heads-major, which is how
the chunk kernel takes it and how ``W_uk`` folds into it; the kernel's
output goes into ``W_o`` where it lies; and no step program reshapes,
turns or slices a parameter (at the published shapes a decode step turned
805 MB of ``kv_b`` that way, tests/test_tpu_compile.py).

What differs between the families is data of the config: ``q_rank`` (0:
no query down-projection, the layer holds ``q``; else ``q_a``,
``q_a_norm``, ``q_b``), ``softmax_scale``, and whether the caller hands
``cos_sin`` (the rotation of ``q_r`` and ``k_r``; None: none). A config
gives ``heads``, ``kv_rank``, ``nope``, ``rope``, ``v_dim``, ``latent``,
``eps``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from evam_tpu.models.lm import common
from evam_tpu.models.lm.common import BF16, F32, es, mm, rms_norm
from evam_tpu.ops import pallas_attention


def tensor_shapes(cfg) -> dict[str, tuple]:
    """The mixer's tensors as PUBLISHED (``input_norm`` is the layer's):
    the names and shapes that make the values (``store`` lays them)."""
    h, hd = cfg.hidden, cfg.heads
    q_out = hd * (cfg.nope + cfg.rope)
    q = ({"q_a": (h, cfg.q_rank), "q_a_norm": (cfg.q_rank,),
          "q_b": (cfg.q_rank, q_out)} if cfg.q_rank else {"q": (h, q_out)})
    return {**q, "kv_a": (h, cfg.latent), "kv_a_norm": (cfg.kv_rank,),
            "kv_b": (cfg.kv_rank, hd * (cfg.nope + cfg.v_dim)),
            "o": (hd * cfg.v_dim, h)}


#: the published tensors that ``store`` lays anew
LAID = ("q", "q_b", "kv_b", "o")


def _rope_order(cfg) -> str:
    """The axis order of ``w_qr``, the contraction last: the smaller of
    ``rope`` and ``heads`` outermost. XLA writes this 64-wide product as a
    convolution whose window is that axis and whose batch the other, and
    re-lays a weight that has them the other way round in every program
    (tests/test_tpu_compile.py counts such copies)."""
    return "dhc" if cfg.rope < cfg.heads else "hdc"


def _lay(cfg, made: dict) -> dict:
    def per_head(w):
        return w.reshape(w.shape[0], cfg.heads, -1).transpose(1, 2, 0)

    q = per_head(made["q_b" if cfg.q_rank else "q"])
    kv = per_head(made["kv_b"])
    return {"w_qn": q[:, :cfg.nope],
            "w_qr": jnp.einsum("hdc->" + _rope_order(cfg), q[:, cfg.nope:]),
            "w_uk": kv[:, :cfg.nope], "w_uv": kv[:, cfg.nope:],
            "o": made["o"].reshape(cfg.heads, cfg.v_dim, -1)}


_lay_jit = jax.jit(_lay, static_argnums=0)


def store(cfg, made: dict) -> dict:
    """A latent layer's tensors as the step programs hold them, from those
    ``made`` under ``tensor_shapes``' names and shapes: the up-projections
    per head with the contraction last, ``w_qn`` [heads, nope, q_rank or
    hidden] and ``w_qr`` (``_rope_order``) for ``q_b`` (``q`` where
    ``q_rank`` is 0), ``w_uk`` [heads, nope, kv_rank] and ``w_uv`` [heads,
    v_dim, kv_rank] for ``kv_b``; ``o`` as [heads, v_dim, hidden]; every
    other tensor as it is. The published arrays are not kept."""
    return {**{k: v for k, v in made.items() if k not in LAID},
            **_lay_jit(cfg, {k: made[k] for k in LAID if k in made})}


def rope(x, cos, sin):
    """Rotate the pairs (2i, 2i+1) of the last axis; ``cos``/``sin``
    broadcast against ``x[..., ::2]``."""
    x = x.astype(F32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _tail(cfg, rope_part):
    """A stored row's last lane tile: ``[rope_part | zeros]``, what a row
    of ``common.row_width(latent)`` values holds behind its ``kv_rank``."""
    pad = common.row_width(cfg.latent) - cfg.latent
    return jnp.pad(rope_part, [(0, 0)] * (rope_part.ndim - 1) + [(0, pad)])


def qkv(cfg, lp: dict, x, cos_sin=None):
    """Per token: the query, heads-major as its two products write it
    (``q_nope`` [h,T,nope], ``q_rope`` [h,T,rope]), and the row ``[c_kv ;
    k_r ; zeros]`` that the cache holds (``common.row_width(latent)``
    wide); ``q_rope`` and ``k_r`` rotated by ``cos_sin`` where it is
    given."""
    c_q = (rms_norm(mm(x, lp["q_a"]), lp["q_a_norm"], cfg.eps) if cfg.q_rank
           else x)
    q_nope = es("tc,hdc->htd", c_q, lp["w_qn"]).astype(BF16)
    q_rope = es(f"tc,{_rope_order(cfg)}->htd", c_q, lp["w_qr"]).astype(BF16)
    kv = mm(x, lp["kv_a"])
    c_kv = rms_norm(kv[:, :cfg.kv_rank], lp["kv_a_norm"], cfg.eps)
    k_r = kv[:, cfg.kv_rank:]
    if cos_sin is not None:
        cos, sin = cos_sin
        q_rope = rope(q_rope, cos, sin).astype(BF16)
        k_r = rope(k_r, cos, sin).astype(BF16)
    return q_nope, q_rope, jnp.concatenate([c_kv, _tail(cfg, k_r)], axis=-1)


def absorb_q(cfg, w_uk, q_nope, q_rope):
    """The query in the cache's own space, in a stored row's two parts:
    against ``c_kv`` [h, T, kv_rank] and against the row's last lane tile
    [h, T, width - kv_rank], zeros behind the rope part."""
    q_lat = es("htd,hdc->htc", q_nope, w_uk).astype(BF16)
    return q_lat, _tail(cfg, q_rope)


def _softmax_sums(cfg, score_expr, value_expr, q, rows, visible):
    """``common.softmax_sums`` over stored rows: a row's key is the whole
    row (its zeros meet the query's), its value the row's ``c_kv``."""
    return common.softmax_sums(cfg.softmax_scale, score_expr, value_expr, q,
                               rows, rows[..., :cfg.kv_rank], visible)


def mla_decode(cfg, lp: dict, q_nope, q_rope, ctx, ctx_len, prefix,
               n_prefix):
    """One new token per row (the query heads-major, ``qkv``), absorbed
    form, its softmax in two parts.
    OWN: each row against its own cached rows ``ctx`` [B, T, width]
    (stored rows, ``qkv``; the new token's among them), visible below
    ``ctx_len`` [B].
    SHARED: the queries of all rows and heads against the prefix rows
    ``prefix`` [Tp, width] (visible below ``n_prefix``), which every
    row shares and which are read once: one dense product. The parts
    are merged by their softmax sums in float32 (the arithmetic of the
    one softmax over prefix and own rows) before ``W_uv``. ``prefix``
    may be None: the own part alone."""
    q = jnp.concatenate(absorb_q(cfg, lp["w_uk"], q_nope, q_rope), axis=-1)
    own = jnp.arange(ctx.shape[1])[None, None, :] < ctx_len[None, :, None]
    sums = _softmax_sums(cfg, "hbc,btc->hbt", "hbt,btc->hbc", q, ctx, own)
    shared = None
    if prefix is not None:
        seen = jnp.arange(prefix.shape[0]) < n_prefix
        shared = _softmax_sums(cfg, "hbc,sc->hbs", "hbs,sc->hbc", q, prefix,
                               seen)
    o_lat = common.merge_softmax_sums(sums, shared).astype(BF16)
    o = es("hbc,hvc->hbv", o_lat, lp["w_uv"]).astype(BF16)
    return es("hbv,hvo->bo", o, lp["o"]).astype(BF16)


def prefix_heads_shapes(cfg, rows: int, layers: int) -> list:
    """What the engine holds of a shared prefix of ``rows`` rows beside the
    weights: per latent layer its heads ``(k_nope, v)`` as ``expand`` gives
    them."""
    return [(jax.ShapeDtypeStruct((cfg.heads, rows, cfg.nope), BF16),
             jax.ShapeDtypeStruct((cfg.heads, rows, cfg.v_dim), BF16))
            ] * layers


def prefix_heads(cfg, layers: list, pages, prefix_pages) -> list:
    """The heads of the prefix's cached rows as they lie in ``prefix_pages``
    of ``pages`` [latent layers, pages, page_tokens, width] now, per latent
    layer (``layers``: their tensors, in the cache's order)."""
    return [expand(cfg, lp, common.layer_page_rows(pages, i, prefix_pages))
            for i, lp in enumerate(layers)]


def expand(cfg, lp: dict, rows):
    """The heads of stored rows ``rows`` [S, width] (``qkv``): ``(k_nope
    [heads, S, nope], v [heads, S, v_dim])``, written heads-major by the
    products themselves."""
    c_kv = rows[:, :cfg.kv_rank]
    return (es("sc,hdc->hsd", c_kv, lp["w_uk"]).astype(BF16),
            es("sc,hvc->hsv", c_kv, lp["w_uv"]).astype(BF16))


def mla_prefill(cfg, lp: dict, q_nope, q_rope, lat, seg, prefix,
                n_prefix, cont, n_cont, prefix_heads=None):
    """A packed chunk over materialised heads (the query heads-major, as
    ``qkv`` writes it and the chunk kernel takes it): every head's tokens
    are its query rows over that head's keys and values of the shared
    prefix rows ``prefix`` [Tp, width] (visible below ``n_prefix``), the earlier rows
    ``cont`` [Tc, width] of the sequence that continues in this chunk
    (below ``n_cont``, to segment 0 only) and the chunk's own rows ``lat``
    (a token sees its segment's, up to itself). ``prefix`` and ``cont`` may
    be None. ``prefix_heads``: ``expand`` of ``prefix``, which the engine
    holds (None: expanded here, a chunk); the continued and own rows' heads
    are expanded here, and the two lists go to the chunk kernel as they are
    (ops/pallas_attention.py), with the rope part as the one list all heads
    share, as it lies: a row's last lane tile, zeros included. The scores
    stay on the chip."""
    new = lat if cont is None else jnp.concatenate([cont, lat], axis=0)
    k, v = expand(cfg, lp, new)
    k_r = new[:, cfg.kv_rank:]
    if prefix is not None:
        k_p, v_p = (expand(cfg, lp, prefix) if prefix_heads is None
                    else prefix_heads)
        k, v, k_r = (k_p, k), (v_p, v), (prefix[:, cfg.kv_rank:], k_r)
    bounds, b0 = common.chunk_bounds(
        seg, n_prefix, n_cont, 0 if prefix is None else prefix.shape[0],
        0 if cont is None else cont.shape[0])
    attend = (pallas_attention.chunk_attention if common.on_tpu()
              else pallas_attention.chunk_attention_xla)
    o = attend(q_nope, k, v, bounds, _tail(cfg, q_rope), k_r,
               scale=cfg.softmax_scale, b0=b0)
    return es("htv,hvo->to", o, lp["o"]).astype(BF16)


def chunk_key_blocks(seg, n_prefix: int, n_cont: int, prefix_rows: int,
                     cont_rows: int):
    """On the host: the classes of the (query block, key block) pairs
    (ops/pallas_attention.py ``block_classes``) of the kernel call that
    ``mla_prefill`` makes for such a chunk over ``prefix_rows`` prefix rows
    and ``cont_rows`` continued ones; ``seg`` a numpy array."""
    bounds, b0 = common.chunk_bounds(seg, n_prefix, n_cont, prefix_rows,
                                     cont_rows, xp=np)
    new = cont_rows + len(seg)
    return pallas_attention.block_classes(
        bounds, b0, (prefix_rows, new) if prefix_rows else (new,), xp=np)
