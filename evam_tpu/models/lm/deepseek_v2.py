"""DeepSeek-V2 in plain JAX: one chip's share of an expert-parallel layer.

Per layer, pre-norm residual, RMSNorm:

* **MLA.** ``c_q = norm(W_qa h)``, ``q = W_qb c_q`` -> heads x (nope +
  rope). ``[c_kv ; k_r] = W_kva h``, ``c_kv = norm(c_kv)``, ``k_r =
  rope(k_r)`` (one rope key for all heads). ``[k_nope ; v] = W_kvb c_kv``.
  ``score = (q_nope . k_nope + rope(q_rope) . k_r) * s``. The cache holds
  ``[c_kv ; k_r ; zeros]`` per token and layer (576 values stored 640
  wide: whole lane tiles). A decode step runs in the ABSORBED
  form (``W_kvb``'s key half folded into the query, its value half into
  the output), so every key is read as a latent row, in
  two parts, all rows' queries against the shared prefix's rows in one
  product and each row against its own pages through its page table,
  merged by their softmax sums; a prefill chunk over MATERIALISED heads,
  the prefix's expanded once and held by the engine (``prefix_heads``),
  the continued and own rows' a chunk, in the one chunk kernel
  (ops/pallas_attention.py). The latent attention itself lives
  in models/lm/mla.py (``qkv``, ``mla_decode``, ``mla_prefill``), which
  models/lm/kimi_linear.py calls too; this family hands it the rotation
  and a query down-projection.
* **YaRN** rope (blended inverse frequencies) and its softmax scale.
* **Layer 0** a dense SwiGLU; **later layers** a router over ALL
  ``n_routed_experts`` (softmax in float32, group-limited top-k), the
  routed experts this chip HOLDS (``held_group``: experts
  ``[g*E/n_group, (g+1)*E/n_group)``) and the shared experts. The chip
  adds, for each token, only its held experts' terms (none for a token
  whose kept groups exclude ``g``) and the shared experts; that partial
  sum goes on to the next layer. Nothing stands in for absent chips.
  The held experts' work follows the tokens routed to them: assignments
  are sorted by expert and run through ``jax.lax.ragged_dot``. The layer
  itself lives in models/lm/experts.py (``route``, ``held_experts``,
  ``moe``), which models/lm/kimi_linear.py calls too; this family's
  config says softmax scores, ``n_group`` groups and the held group.

Weights: every tensor is ``normal(key) * initializer_range`` in float32,
``key = fold_in(fold_in(fold_in(PRNGKey(seed), layer), crc32(name)),
expert)``, stored bfloat16 (norm gains: 1 + that). Made on the device;
the latent mixer's up-projections are then laid as its products read
them (``mla.store``, once a layer).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from evam_tpu.models.lm import common, experts, mla
from evam_tpu.models.lm.common import (  # noqa: F401 - the family's names
    BF16,
    F32,
    GLOBAL_LAYER,
    TOP_LOGITS,
    rms_norm,
    swiglu,
    tensor_key,
)
from evam_tpu.models.lm.common import make as _make
from evam_tpu.models.lm.common import make_one as _make_one
from evam_tpu.models.lm.experts import (  # noqa: F401 - the family's names
    held_experts,
    moe,
    route,
)
from evam_tpu.models.lm.mla import (  # noqa: F401 - the family's names
    mla_decode,
    mla_prefill,
)

#: the packer may start a segment at any token of a chunk
SEGMENT_ALIGN = 1


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

    #: what models/lm/experts.py reads beside the fields
    score_func = "softmax"
    expert_act = "swiglu"
    moe_latent = None
    topk_eps = 1e-20

    @property
    def scale_routed(self) -> bool:
        return not (self.norm_topk and self.top_k > 1)

    @property
    def softmax_scale(self) -> float:
        return softmax_scale(self)

    @property
    def per_group(self) -> int:
        return self.n_experts // self.n_group

    @property
    def held_lo(self) -> int:
        return self.held_group * self.per_group

    @property
    def latent(self) -> int:
        """The model's values per token and layer (the cache stores them
        in ``common.row_width`` of them)."""
        return self.kv_rank + self.rope

    @property
    def moe_layers(self) -> int:
        return self.layers - self.first_dense

    @property
    def moe_ids(self) -> tuple:
        """The model layers that have the expert layer, in order."""
        return tuple(range(self.first_dense, self.layers))


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


# --------------------------------------------------------------- weights


def tensor_shapes(cfg: Config, layer: int) -> dict[str, tuple]:
    h = cfg.hidden
    out = {"input_norm": (h,), "post_norm": (h,), **mla.tensor_shapes(cfg)}
    if layer < cfg.first_dense:
        out.update(mlp_gate=(h, cfg.dense_inter), mlp_up=(h, cfg.dense_inter),
                   mlp_down=(cfg.dense_inter, h))
    else:
        out.update(experts.tensor_shapes(cfg, bias=False))
    return out


_make_experts = jax.jit(
    lambda key, ids, shape, scale: jax.vmap(
        lambda e: _make(jax.random.fold_in(key, e), shape, scale, False))(ids),
    static_argnums=(2, 3))


def make_layer(cfg: Config, layer: int, experts=None) -> dict:
    """One layer's tensors, the latent mixer's as ``mla.store`` lays them;
    ``experts`` are the routed experts held (default: the config's held
    group)."""
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
    return mla.store(cfg, out)


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


def _mlp(cfg: Config, lp: dict, x, live):
    if "router" in lp:
        return moe(cfg, lp, x, live)
    with jax.named_scope("dense_mlp"):
        return swiglu(x, lp["mlp_gate"], lp["mlp_up"],
                      lp["mlp_down"]), jnp.zeros((3,), jnp.int32)


def _qkv(cfg: Config, lp: dict, x, pos):
    """``mla.qkv`` with this family's rotation at ``pos``."""
    return mla.qkv(cfg, lp, x, _cos_sin(cfg, pos))


def head(cfg: Config, params: dict, x):
    """Float32 logits over the held slice and the top of each row
    (``common.head``)."""
    return common.head(x, params["final_norm"], cfg.eps, params["head"])


# ----------------------------------------------------------- step bodies


def state_shapes(cfg: Config, n_pages: int, page_tokens: int,
                 slots: int) -> dict:
    """The device state of this family's sequences: latent rows in pages,
    in every layer, each stored in whole lane tiles (``mla.qkv``), and
    nothing per slot."""
    return {"pages": jax.ShapeDtypeStruct(
        (cfg.layers, n_pages, page_tokens, common.row_width(cfg.latent)),
        BF16)}


def prefix_heads_shapes(cfg: Config, rows: int) -> list:
    """``mla.prefix_heads_shapes``: every layer is a latent one."""
    return mla.prefix_heads_shapes(cfg, rows, cfg.layers)


def prefix_heads(cfg: Config, params: dict, state, prefix_pages) -> list:
    """``mla.prefix_heads`` of every layer."""
    return mla.prefix_heads(cfg, params["layers"], state["pages"],
                            prefix_pages)


def chunk_key_blocks(cfg: Config, seg, n_prefix: int, n_cont: int,
                     prefix_pages: int, cont_pages: int,
                     page_tokens: int) -> list:
    """What the engine counts a chunk's key blocks by: per kind of layer
    whose chunks run the chunk kernel ``(its name, its layers, the classes
    of one layer's call)`` for a chunk of segments ``seg`` (numpy) over a
    prefix of ``prefix_pages`` pages and ``cont_pages`` continued ones."""
    return [("mla", cfg.layers, mla.chunk_key_blocks(
        seg, n_prefix, n_cont, prefix_pages * page_tokens,
        cont_pages * page_tokens))]


def prefill_chunk(cfg: Config, params: dict, state, tokens, seg, pos,
                  dest_page, dest_off, prefix_pages, n_prefix, cont_pages,
                  n_cont, last_idx, seg_from=None, seg_to=None,
                  prefix_heads=None):
    """A packed chunk of new tokens through every layer. Writes their
    latent rows to ``state["pages"][layer, dest_page, dest_off]`` and
    returns the state, the logits rows ``last_idx`` (each segment's last
    token) as ``(top, ids)``, and the expert layers' counts (``experts.moe``)
    summed over the layers. ``prefix_pages``/``cont_pages`` may be
    ``None`` (no shared prefix; no sequence that continues from an
    earlier chunk). ``prefix_heads``: the prefix's held heads
    (``prefix_heads``), read and never written (None: each layer expands
    the prefix's rows itself).
    ``seg_from``/``seg_to`` name slot state, of which this family has
    none."""
    cache = state["pages"]
    live = seg >= 0
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    held = jnp.zeros((3,), jnp.int32)
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope("mla"):
            h = rms_norm(x, lp["input_norm"], cfg.eps)
            qn, qr, lat = _qkv(cfg, lp, h, pos)
            a = mla_prefill(
                cfg, lp, qn, qr, lat, seg,
                common.layer_page_rows(cache, i, prefix_pages), n_prefix,
                common.layer_page_rows(cache, i, cont_pages), n_cont,
                prefix_heads[i] if prefix_heads else None)
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
    held = jnp.zeros((3,), jnp.int32)
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope("mla"):
            h = rms_norm(x, lp["input_norm"], cfg.eps)
            qn, qr, lat = _qkv(cfg, lp, h, pos)
            cache = cache.at[i, dest_page, dest_off].set(lat)
            x = x + mla_decode(
                cfg, lp, qn, qr,
                common.layer_page_rows(cache, i, page_table), ctx_len,
                common.layer_page_rows(cache, i, prefix_pages), n_prefix)
        h = rms_norm(x, lp["post_norm"], cfg.eps)
        y, n = _mlp(cfg, lp, h, live)
        x = x + y
        held = held + n
    _, top, ids = head(cfg, params, x)
    return {"pages": cache}, top, ids, held
