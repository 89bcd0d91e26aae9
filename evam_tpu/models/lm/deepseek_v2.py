"""DeepSeek-V2 in plain JAX: one chip's share of an expert-parallel layer.

Per layer, pre-norm residual, RMSNorm:

* **MLA.** ``c_q = norm(W_qa h)``, ``q = W_qb c_q`` -> heads x (nope +
  rope). ``[c_kv ; k_r] = W_kva h``, ``c_kv = norm(c_kv)``, ``k_r =
  rope(k_r)`` (one rope key for all heads). ``[k_nope ; v] = W_kvb c_kv``.
  ``score = (q_nope . k_nope + rope(q_rope) . k_r) * s``. The cache holds
  ``[c_kv ; k_r]`` per token and layer. Attention runs in the ABSORBED
  form (``W_kvb``'s key half folded into the query, its value half into
  the output), so every key is read as a latent row: a decode step in
  two parts, all rows' queries against the shared prefix's rows in one
  product and each row against its own pages through its page table,
  merged by their softmax sums; a prefill chunk over prefix, continued
  and own rows in one kernel (ops/pallas_mla.py). Heads are
  materialised only in the reference.
* **YaRN** rope (blended inverse frequencies) and its softmax scale.
* **Layer 0** a dense SwiGLU; **later layers** a router over ALL
  ``n_routed_experts`` (softmax in float32, group-limited top-k), the
  routed experts this chip HOLDS (``held_group``: experts
  ``[g*E/n_group, (g+1)*E/n_group)``) and the shared experts. The chip
  adds, for each token, only its held experts' terms (none for a token
  whose kept groups exclude ``g``) and the shared experts; that partial
  sum goes on to the next layer. Nothing stands in for absent chips.
  The held experts' work follows the tokens routed to them: assignments
  are sorted by expert and run through ``jax.lax.ragged_dot``.

Weights: every tensor is ``normal(key) * initializer_range`` in float32,
``key = fold_in(fold_in(fold_in(PRNGKey(seed), layer), crc32(name)),
expert)``, stored bfloat16 (norm gains: 1 + that). Made on the device.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from evam_tpu.models.lm import common
from evam_tpu.models.lm.common import (  # noqa: F401 - the family's names
    BF16,
    F32,
    GLOBAL_LAYER,
    TOP_LOGITS,
    rms_norm,
    swiglu,
    tensor_key,
)
from evam_tpu.models.lm.common import es as _es
from evam_tpu.models.lm.common import make as _make
from evam_tpu.models.lm.common import make_one as _make_one
from evam_tpu.models.lm.common import mm as _mm
from evam_tpu.ops import pallas_mla


@dataclass(frozen=True)
class Config:
    hidden: int
    dense_inter: int
    moe_inter: int
    layers: int
    first_dense: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    n_experts: int
    n_shared: int
    top_k: int
    n_group: int
    topk_group: int
    routed_scale: float
    norm_topk: bool
    eps: float
    rope_theta: float
    yarn_factor: float
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    yarn_orig_max: int
    vocab: int          # rows of the vocabulary held here
    held_group: int
    seed: int
    init_range: float

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        rs = d["rope_scaling"]
        return cls(
            hidden=d["hidden_size"], dense_inter=d["intermediate_size"],
            moe_inter=d["moe_intermediate_size"],
            layers=d["num_hidden_layers"],
            first_dense=d["first_k_dense_replace"],
            heads=d["num_attention_heads"], q_rank=d["q_lora_rank"],
            kv_rank=d["kv_lora_rank"], nope=d["qk_nope_head_dim"],
            rope=d["qk_rope_head_dim"], v_dim=d["v_head_dim"],
            n_experts=d["n_routed_experts"], n_shared=d["n_shared_experts"],
            top_k=d["num_experts_per_tok"], n_group=d["n_group"],
            topk_group=d["topk_group"],
            routed_scale=float(d["routed_scaling_factor"]),
            norm_topk=bool(d["norm_topk_prob"]), eps=d["rms_norm_eps"],
            rope_theta=float(d["rope_theta"]),
            yarn_factor=float(rs["factor"]),
            yarn_beta_fast=float(rs["beta_fast"]),
            yarn_beta_slow=float(rs["beta_slow"]),
            yarn_mscale=float(rs["mscale"]),
            yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
            yarn_orig_max=int(rs["original_max_position_embeddings"]),
            vocab=d["vocab_held"], held_group=d["held_group"],
            seed=d["weights_seed"], init_range=d["initializer_range"])

    @property
    def per_group(self) -> int:
        return self.n_experts // self.n_group

    @property
    def held_lo(self) -> int:
        return self.held_group * self.per_group

    @property
    def latent(self) -> int:
        """Values the cache holds per token and layer."""
        return self.kv_rank + self.rope

    @property
    def moe_layers(self) -> int:
        return self.layers - self.first_dense


# ------------------------------------------------------------------ YaRN


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: Config) -> np.ndarray:
    """Blend of the plain and the interpolated inverse frequencies: the
    fast dimensions (more than ``beta_fast`` turns inside the original
    context) keep theirs, the slow ones (fewer than ``beta_slow``) are
    divided by ``factor``, a linear ramp between."""
    dim = cfg.rope
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / cfg.rope_theta ** exps
    inter = extra / cfg.yarn_factor

    def corr(turns):
        return dim * math.log(cfg.yarn_orig_max / (turns * 2 * math.pi)) / (
            2 * math.log(cfg.rope_theta))

    low = max(math.floor(corr(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def softmax_scale(cfg: Config) -> float:
    m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    return (cfg.nope + cfg.rope) ** -0.5 * m * m


def _cos_sin(cfg: Config, pos):
    ang = pos.astype(F32)[:, None] * jnp.asarray(yarn_inv_freq(cfg))
    m = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
         / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def _rope(x, cos, sin):
    """Rotate the pairs (2i, 2i+1) of the last axis; ``cos``/``sin``
    broadcast against ``x[..., ::2]``."""
    x = x.astype(F32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


# --------------------------------------------------------------- weights


def tensor_shapes(cfg: Config, layer: int) -> dict[str, tuple]:
    h, hd = cfg.hidden, cfg.heads
    out = {
        "input_norm": (h,), "post_norm": (h,),
        "q_a": (h, cfg.q_rank), "q_a_norm": (cfg.q_rank,),
        "q_b": (cfg.q_rank, hd * (cfg.nope + cfg.rope)),
        "kv_a": (h, cfg.latent), "kv_a_norm": (cfg.kv_rank,),
        "kv_b": (cfg.kv_rank, hd * (cfg.nope + cfg.v_dim)),
        "o": (hd * cfg.v_dim, h),
    }
    if layer < cfg.first_dense:
        out.update(mlp_gate=(h, cfg.dense_inter), mlp_up=(h, cfg.dense_inter),
                   mlp_down=(cfg.dense_inter, h))
    else:
        s = cfg.n_shared * cfg.moe_inter
        out.update(router=(h, cfg.n_experts),
                   shared_gate=(h, s), shared_up=(h, s), shared_down=(s, h),
                   expert_gate=(h, cfg.moe_inter), expert_up=(h, cfg.moe_inter),
                   expert_down=(cfg.moe_inter, h))
    return out


_make_experts = jax.jit(
    lambda key, ids, shape, scale: jax.vmap(
        lambda e: _make(jax.random.fold_in(key, e), shape, scale, False))(ids),
    static_argnums=(2, 3))


def make_layer(cfg: Config, layer: int, experts=None) -> dict:
    """One layer's tensors; ``experts`` are the routed experts held
    (default: the config's held group)."""
    if experts is None:
        experts = range(cfg.held_lo, cfg.held_lo + cfg.per_group)
    ids = jnp.asarray(list(experts), jnp.uint32)
    out = {}
    for name, shape in tensor_shapes(cfg, layer).items():
        key = tensor_key(cfg.seed, layer, name)
        if name.startswith("expert_"):
            out[name] = _make_experts(key, ids, shape, cfg.init_range)
        else:
            out[name] = _make_one(key, shape, cfg.init_range,
                                  name.endswith("norm"))
    return out


def make_params(cfg: Config) -> dict:
    glob = {"embed": (cfg.vocab, cfg.hidden), "final_norm": (cfg.hidden,),
            "head": (cfg.hidden, cfg.vocab)}
    params = {name: _make_one(tensor_key(cfg.seed, GLOBAL_LAYER, name), shape,
                              cfg.init_range, name.endswith("norm"))
              for name, shape in glob.items()}
    params["layers"] = [make_layer(cfg, i) for i in range(cfg.layers)]
    return params


def param_count(cfg: Config) -> int:
    n = 2 * cfg.vocab * cfg.hidden + cfg.hidden
    for i in range(cfg.layers):
        for name, shape in tensor_shapes(cfg, i).items():
            k = cfg.per_group if name.startswith("expert_") else 1
            n += k * math.prod(shape)
    return n


# ---------------------------------------------------------------- layers


def route(cfg: Config, x, router):
    """Group-limited top-k over ALL experts: ``(weights [T,k], ids
    [T,k])``. Scores are a float32 softmax; a group's score is its best
    expert's; the best ``topk_group`` groups are kept (ties: the lower
    index, as ``lax.top_k``), the rest set to 0; then the best ``top_k``
    of what is left."""
    logits = jnp.dot(x.astype(F32), router.astype(F32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    t = scores.shape[0]
    group = scores.reshape(t, cfg.n_group, cfg.per_group).max(-1)
    _, keep = jax.lax.top_k(group, cfg.topk_group)
    kept = jnp.zeros((t, cfg.n_group), bool).at[
        jnp.arange(t)[:, None], keep].set(True)
    masked = jnp.where(jnp.repeat(kept, cfg.per_group, axis=1), scores, 0.0)
    w, ids = jax.lax.top_k(masked, cfg.top_k)
    if cfg.norm_topk and cfg.top_k > 1:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    else:
        w = w * cfg.routed_scale
    return w, ids


def held_experts(cfg: Config, lp: dict, x, w, ids, live):
    """The held experts' part of the routed sum, with work that follows
    the assignments routed here: the ``T*k`` assignments are sorted by
    held expert (those of other chips' experts, and of dead rows, last),
    and the sorted rows go through grouped products. Every assignment to
    a held expert is computed, however uneven the routing: the grouped
    product runs over the first ``T*k/4`` rows where they hold all of
    them (twice the even share at 8 groups, top 3), else over all.
    Returns the sum [T, hidden] and the number of held assignments."""
    t, k = ids.shape
    n_held = lp["expert_gate"].shape[0]
    local = ids - cfg.held_lo
    mine = (local >= 0) & (local < n_held) & live[:, None]
    sort_key = jnp.where(mine, local, n_held).reshape(-1)
    order = jnp.argsort(sort_key, stable=True)
    sizes = jnp.bincount(sort_key, length=n_held + 1)[:n_held].astype(
        jnp.int32)
    n_mine = sizes.sum()
    rows = x[order // k]
    m = t * k
    m_small = max(8, m // 4)

    def run(r):
        g = jax.lax.ragged_dot(r, lp["expert_gate"], sizes,
                               preferred_element_type=F32)
        u = jax.lax.ragged_dot(r, lp["expert_up"], sizes,
                               preferred_element_type=F32)
        hmid = (jax.nn.silu(g.astype(BF16)) * u.astype(BF16))
        return jax.lax.ragged_dot(hmid, lp["expert_down"], sizes,
                                  preferred_element_type=F32).astype(BF16)

    def small():
        return jnp.zeros((m, x.shape[1]), BF16).at[:m_small].set(
            run(rows[:m_small]))

    y = jax.lax.cond(n_mine <= m_small, small, lambda: run(rows))
    # rows past the last group hold whatever the kernel left there
    y = jnp.where((jnp.arange(m) < n_mine)[:, None], y, 0)
    back = jnp.argsort(order)
    y = y[back].reshape(t, k, -1).astype(F32)
    out = (y * jnp.where(mine, w, 0.0)[..., None]).sum(1)
    return out.astype(BF16), n_mine


def moe(cfg: Config, lp: dict, x, live):
    with jax.named_scope("router"):
        w, ids = route(cfg, x, lp["router"])
    with jax.named_scope("experts"):
        routed, n_mine = held_experts(cfg, lp, x, w, ids, live)
    with jax.named_scope("shared"):
        shared = swiglu(x, lp["shared_gate"], lp["shared_up"],
                        lp["shared_down"])
    return routed + shared, n_mine


def _mlp(cfg: Config, lp: dict, x, live):
    if "router" in lp:
        return moe(cfg, lp, x, live)
    with jax.named_scope("dense_mlp"):
        return swiglu(x, lp["mlp_gate"], lp["mlp_up"],
                      lp["mlp_down"]), jnp.int32(0)


def _qkv(cfg: Config, lp: dict, x, pos):
    """Per token: roped query ``(q_nope [T,h,nope], q_rope [T,h,rope])``
    and the latent row ``[c_kv ; k_r]`` that the cache holds."""
    t = x.shape[0]
    cos, sin = _cos_sin(cfg, pos)
    c_q = rms_norm(_mm(x, lp["q_a"]), lp["q_a_norm"], cfg.eps)
    q = _mm(c_q, lp["q_b"]).reshape(t, cfg.heads, cfg.nope + cfg.rope)
    q_nope, q_rope = q[..., :cfg.nope], q[..., cfg.nope:]
    q_rope = _rope(q_rope, cos[:, None], sin[:, None]).astype(BF16)
    kv = _mm(x, lp["kv_a"])
    c_kv = rms_norm(kv[:, :cfg.kv_rank], lp["kv_a_norm"], cfg.eps)
    k_r = _rope(kv[:, cfg.kv_rank:], cos, sin).astype(BF16)
    return q_nope, q_rope, jnp.concatenate([c_kv, k_r], axis=-1)


def _kv_b(cfg: Config, lp: dict):
    """``W_kvb`` as ``(W_uk, W_uv)``, each [heads, kv_rank, 128]."""
    w = lp["kv_b"].reshape(cfg.kv_rank, cfg.heads, cfg.nope + cfg.v_dim)
    w = w.transpose(1, 0, 2)
    return w[..., :cfg.nope], w[..., cfg.nope:]


def _absorb_q(cfg, w_uk, q_nope, q_rope):
    """The query in the cache's own space: [T, h, kv_rank + rope]."""
    q_lat = _es("thd,hcd->thc", q_nope, w_uk).astype(BF16)
    return jnp.concatenate([q_lat, q_rope], axis=-1)


def _softmax_sums(cfg: Config, score_expr, value_expr, q, rows, visible):
    """``common.softmax_sums`` over latent rows: a row's key is the whole
    row, its value the row's ``c_kv``."""
    return common.softmax_sums(softmax_scale(cfg), score_expr, value_expr, q,
                               rows, rows[..., :cfg.kv_rank], visible)


def mla_decode(cfg: Config, lp: dict, q_nope, q_rope, ctx, ctx_len, prefix,
               n_prefix):
    """One new token per row, absorbed form, its softmax in two parts.
    OWN: each row against its own cached rows ``ctx`` [B, T, latent]
    (the new token's row among them), visible below ``ctx_len`` [B].
    SHARED: the queries of all rows and heads against the prefix rows
    ``prefix`` [Tp, latent] (visible below ``n_prefix``), which every
    row shares and which are read once: one dense product. The parts
    are merged by their softmax sums in float32 (the arithmetic of the
    one softmax over prefix and own rows) before ``W_uv``. ``prefix``
    may be None: the own part alone."""
    w_uk, w_uv = _kv_b(cfg, lp)
    q = _absorb_q(cfg, w_uk, q_nope, q_rope)
    own = jnp.arange(ctx.shape[1])[None, None, :] < ctx_len[:, None, None]
    sums = _softmax_sums(cfg, "bhc,btc->bht", "bht,btc->bhc", q, ctx, own)
    shared = None
    if prefix is not None:
        seen = jnp.arange(prefix.shape[0]) < n_prefix
        shared = _softmax_sums(cfg, "bhc,sc->bhs", "bhs,sc->bhc", q, prefix,
                               seen)
    o_lat = common.merge_softmax_sums(sums, shared).astype(BF16)
    o = _es("bhc,hcv->bhv", o_lat, w_uv).astype(BF16)
    return _mm(o.reshape(o.shape[0], -1), lp["o"])


def mla_prefill(cfg: Config, lp: dict, q_nope, q_rope, lat, seg, prefix,
                n_prefix, cont, n_cont):
    """A packed chunk, absorbed form throughout: every (token, head) is
    one query row over ONE list of latent rows: the shared prefix rows
    ``prefix`` [Tp, latent] (visible below ``n_prefix``), the earlier
    rows ``cont`` [Tc, latent] of the sequence that continues in this
    chunk (below ``n_cont``, to segment 0 only) and the chunk's own rows
    ``lat`` (a token sees its segment's, up to itself). ``prefix`` and
    ``cont`` may be None. The scores stay on the chip
    (ops/pallas_mla.py)."""
    t = lat.shape[0]
    w_uk, w_uv = _kv_b(cfg, lp)
    q = _absorb_q(cfg, w_uk, q_nope, q_rope)
    keys = jnp.concatenate(
        [rows for rows in (prefix, cont, lat) if rows is not None], axis=0)
    bounds, b0 = common.chunk_bounds(
        seg, n_prefix, n_cont, 0 if prefix is None else prefix.shape[0],
        0 if cont is None else cont.shape[0])
    attend = (pallas_mla.latent_attention if common.on_tpu()
              else pallas_mla.latent_attention_xla)
    q = q.reshape(t * cfg.heads, cfg.latent)
    o_lat = attend(
        q[:, :cfg.kv_rank], q[:, cfg.kv_rank:],
        keys[:, :cfg.kv_rank], keys[:, cfg.kv_rank:],
        jnp.repeat(bounds, cfg.heads, axis=0),
        scale=softmax_scale(cfg), b0=b0)
    o = _es("thc,hcv->thv", o_lat.reshape(t, cfg.heads, cfg.kv_rank), w_uv)
    return _mm(o.astype(BF16).reshape(t, -1), lp["o"])


def head(cfg: Config, params: dict, x):
    """Float32 logits over the held slice and the top of each row
    (``common.head``)."""
    return common.head(x, params["final_norm"], cfg.eps, params["head"])


# ----------------------------------------------------------- step bodies


def _rows(cfg: Config, layer_cache, pages):
    """The latent rows of ``pages``, in order: [len(pages) * page, latent]."""
    return common.page_rows(layer_cache, pages)


def state_shapes(cfg: Config, n_pages: int, page_tokens: int,
                 slots: int) -> dict:
    """The device state of this family's sequences: latent rows in pages,
    in every layer, and nothing per slot."""
    return {"pages": jax.ShapeDtypeStruct(
        (cfg.layers, n_pages, page_tokens, cfg.latent), BF16)}


def prefill_chunk(cfg: Config, params: dict, state, tokens, seg, pos,
                  dest_page, dest_off, prefix_pages, n_prefix, cont_pages,
                  n_cont, last_idx, seg_from=None, seg_to=None):
    """A packed chunk of new tokens through every layer. Writes their
    latent rows to ``state["pages"][layer, dest_page, dest_off]`` and
    returns the state, the logits rows ``last_idx`` (each segment's last
    token) as ``(top, ids)``, and the held assignments summed over the
    layers. ``prefix_pages``/``cont_pages`` may be ``None`` (no shared
    prefix; no sequence that continues from an earlier chunk).
    ``seg_from``/``seg_to`` name slot state, of which this family has
    none."""
    cache = state["pages"]
    live = seg >= 0
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    held = jnp.int32(0)
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope("mla"):
            h = rms_norm(x, lp["input_norm"], cfg.eps)
            qn, qr, lat = _qkv(cfg, lp, h, pos)
            a = mla_prefill(cfg, lp, qn, qr, lat, seg,
                            _rows(cfg, cache[i], prefix_pages), n_prefix,
                            _rows(cfg, cache[i], cont_pages), n_cont)
            cache = cache.at[i, dest_page, dest_off].set(lat)
            x = x + a
        h = rms_norm(x, lp["post_norm"], cfg.eps)
        y, n = _mlp(cfg, lp, h, live)
        x = x + y
        held = held + n
    _, top, ids = head(cfg, params, x[last_idx])
    return {"pages": cache}, top, ids, held


def decode_tokens(cfg: Config, params: dict, state, tokens, pos, page_table,
                  ctx_len, dest_page, dest_off, live, prefix_pages, n_prefix,
                  slot=None):
    """One token per row. Each row's latent is written to its page, then
    the row attends to the ``n_prefix`` rows of the shared prefix
    (``prefix_pages``, read once a layer for all rows; may be ``None``)
    and, through its table of its OWN pages ``page_table`` [B, pages],
    to its ``ctx_len`` own cached rows (the new one among them).
    ``slot`` names slot state, of which this family has none."""
    cache = state["pages"]
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    b = tokens.shape[0]
    held = jnp.int32(0)
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope("mla"):
            h = rms_norm(x, lp["input_norm"], cfg.eps)
            qn, qr, lat = _qkv(cfg, lp, h, pos)
            cache = cache.at[i, dest_page, dest_off].set(lat)
            ctx = cache[i][page_table].reshape(b, -1, cfg.latent)
            x = x + mla_decode(cfg, lp, qn, qr, ctx, ctx_len,
                               _rows(cfg, cache[i], prefix_pages), n_prefix)
        h = rms_norm(x, lp["post_norm"], cfg.eps)
        y, n = _mlp(cfg, lp, h, live)
        x = x + y
        held = held + n
    _, top, ids = head(cfg, params, x)
    return {"pages": cache}, top, ids, held
