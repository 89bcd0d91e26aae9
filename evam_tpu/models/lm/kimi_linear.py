"""Kimi-Linear in plain JAX: delta-rule linear attention (KDA) with a latent
attention layer (MLA, position-free) after every third, over sparse
experts; one chip's share of an expert-parallel layer.

Layers are numbered from 1 as ``linear_attn_config`` numbers them:
``kda_layers`` 1, 2, 3, 5, 6, 7, ... and ``full_attn_layers`` 4, 8, ...;
the first ``first_k_dense_replace`` layers have a dense SwiGLU behind their
mixer, every other layer the expert layer. Pre-norm residual, RMSNorm;
untied head.

* **KDA mixer** (H heads of D = 128 keys and values). ``q, k, v = W_q h,
  W_k h, W_v h``; each through its OWN causal depthwise convolution of
  ``d_conv`` taps (no bias) and a silu; ``q``, ``k`` L2-normalised per
  head, ``q`` times D^-1/2. Log decay per head AND channel ``g = -exp(A_log
  [head]) * softplus(W_f2 (W_f1 h) + dt_bias)``; write strength per head
  ``b = sigmoid(W_b h)``. ``S_t = (I - b k k^T) Diag(exp(g)) S_{t-1} + b k
  v^T``, ``o = S^T q``; out = ``W_o (rms_norm_head(o) * sigmoid(W_g2 (W_g1
  h)))``. No positional term: the recurrence carries order. Between steps
  a sequence carries, per KDA layer, the float32 state ``S`` [H, D, D] and
  the last ``d_conv - 1`` inputs of the three convolutions: per SLOT of
  the generate engine, not per page (``state_shapes``). A prefill chunk
  runs the recurrence in a Pallas kernel with the state in VMEM
  (ops/pallas_kda.py), in blocks of ``SEGMENT_ALIGN`` tokens to which the
  engine's packer aligns every segment's start; a decode step's one-token
  update is a second kernel over the step's rows that moves each row's
  state in place (``kda_decode``).
* **MLA mixer**: models/lm/mla.py, as DeepSeek-V2's but with no rotation
  of ``q_r`` / ``k_r`` (``mla_use_nope``) and no query down-projection
  (``q_lora_rank`` null); the cache row is the same 576 values, stored
  640 wide.
* **Expert layer**: models/lm/experts.py, as DeepSeek-V2's but with
  sigmoid scores, a selection bias (chosen by ``s + b``, weighted by
  ``s``), one group, renormalised weights times ``routed_scaling_factor``,
  and a held RANGE of experts ``[held_lo, held_lo + experts_held)``.

bfloat16 weights and activations; the KDA state, the decay and the delta
update float32. The KDA mixers are STACKED and ONE ``lax.scan`` runs over
them, so that the kernel has one name in a device trace: a trip is a KDA
mixer, its feed-forward and, where an MLA layer follows it, that layer with
ITS feed-forward. The feed-forwards and the MLA layers are NOT stacked:
each trip picks what follows its mixer by a ``lax.switch`` over branches
that hold their weights as they are (a grouped product can address a stack
of expert weights by a prefetched layer id, ops/pallas_grouped.py; nobody
has restacked this family's: ROADMAP.md Queue 3 item 13(b)). The page
cache is an operand of those branches and never a result: a branch reads
the pages it gathers and returns the MLA layer's new latent rows, and the
loop body makes the cache's one write, under a two-branch ``lax.cond``
that holds the write alone (``_layers``): returned by the ``switch``, the
compiler copies the cache whole in the branches that hand it through (131
MB, twice a decode step and three times a chunk); through that ``cond`` it
passes in place, which tests/test_tpu_compile.py holds. A chunk attends to its
new rows as values, so one ``lax.switch`` holds its trip; a decode row
attends to its own new row through the cache, so its trip is ``switch``
(feed-forward, ``qkv``), write, ``switch`` (attention and the MLA layer's
feed-forward).

Weights (``common.tensor_key``): ``normal * initializer_range``, gains ``1
+ that``; the convolutions uniform in +-d_conv^-1/2; ``A_log = log U(1,
16)`` a head; ``dt_bias`` the inverse softplus of a step drawn
log-uniformly from [0.001, 0.1] (the family's own initialisation: a state
that neither dies in ten tokens nor never forgets); ``router_bias`` as any
tensor (small and not zero, so that selection and weighting differ); the
MLA layers' ``q`` and ``kv_a`` ``mla_qk_init_scale`` times as wide (at
``initializer_range`` alone a softmax over 2.4 k rows is flat and the layer
adds a hundredth of what a KDA mixer adds: no comparison would see which
rows it weighs).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from evam_tpu.models.lm import common, experts, mla
from evam_tpu.models.lm.common import BF16, F32, GLOBAL_LAYER, rms_norm
from evam_tpu.models.lm.common import mm as _mm
from evam_tpu.ops import pallas_kda, slot_rows

DT_MIN, DT_MAX = 0.001, 0.1
A_MAX = 16.0
L2_EPS = 1e-6
#: the packer starts every segment at a multiple of the kernel's block
SEGMENT_ALIGN = pallas_kda.BLOCK


@dataclass(frozen=True)
class Config:
    hidden: int
    dense_inter: int
    moe_inter: int
    layers: int
    first_dense: int
    kda_ids: tuple      # 0-based model layers, in order
    mla_ids: tuple
    kda_heads: int
    kda_dim: int
    d_conv: int
    gate_rank: int
    heads: int          # of the latent attention
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    n_experts: int      # the router's outputs
    n_held: int
    held_lo: int
    n_shared: int
    top_k: int
    routed_scale: float
    norm_topk: bool
    eps: float
    vocab: int          # rows of the vocabulary held here
    seed: int
    init_range: float
    qk_init_scale: float    # the MLA layers' q and kv_a: init_range times it

    #: what models/lm/mla.py and models/lm/experts.py read beside the fields
    q_rank = 0
    score_func = "sigmoid"
    expert_act = "swiglu"
    moe_latent = None
    scale_routed = True
    topk_eps = 1e-20
    n_group = 1
    topk_group = 1

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        la = d["linear_attn_config"]
        n = d["num_hidden_layers"]
        kda = tuple(i - 1 for i in la["kda_layers"] if i <= n)
        full = tuple(i - 1 for i in la["full_attn_layers"] if i <= n)
        dense = d["first_k_dense_replace"]
        if (sorted(kda + full) != list(range(n)) or d["q_lora_rank"]
                or not d["mla_use_nope"] or d["num_expert_group"] != 1
                or d["moe_router_activation_func"] != "sigmoid"
                or d["tie_word_embeddings"] or dense < 1
                or any(i < dense or i - 1 not in kda for i in full)):
            raise ValueError(
                "the kimi_linear family is written for layers that are each "
                "KDA or full attention, latent attention without rotation "
                "or a query down-projection behind a KDA layer and over "
                "experts, one expert group, sigmoid scores, an untied head")
        return cls(
            hidden=d["hidden_size"], dense_inter=d["intermediate_size"],
            moe_inter=d["moe_intermediate_size"], layers=n, first_dense=dense,
            kda_ids=kda, mla_ids=full, kda_heads=la["num_heads"],
            kda_dim=la["head_dim"], d_conv=la["short_conv_kernel_size"],
            gate_rank=d["kda_gate_rank"], heads=d["num_attention_heads"],
            kv_rank=d["kv_lora_rank"], nope=d["qk_nope_head_dim"],
            rope=d["qk_rope_head_dim"], v_dim=d["v_head_dim"],
            n_experts=d["num_experts"], n_held=d["experts_held"],
            held_lo=d["held_lo"], n_shared=d["num_shared_experts"],
            top_k=d["num_experts_per_token"],
            routed_scale=float(d["routed_scaling_factor"]),
            norm_topk=bool(d["moe_renormalize"]), eps=d["rms_norm_eps"],
            vocab=d["vocab_held"], seed=d["weights_seed"],
            init_range=d["initializer_range"],
            qk_init_scale=float(d["mla_qk_init_scale"]))

    @property
    def latent(self) -> int:
        """The model's values per token and MLA layer (the cache stores
        them in ``common.row_width`` of them)."""
        return self.kv_rank + self.rope

    @property
    def softmax_scale(self) -> float:
        return (self.nope + self.rope) ** -0.5

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_dim

    @property
    def moe_ids(self) -> tuple:
        """The model layers that have the expert layer, in order."""
        return tuple(range(self.first_dense, self.layers))

    @property
    def mla_after(self) -> tuple:
        """Per KDA layer: the MLA layer (its index among them) right
        behind it, -1 where none is."""
        return tuple(self.mla_ids.index(i + 1) if i + 1 in self.mla_ids
                     else -1 for i in self.kda_ids)


# --------------------------------------------------------------- weights


def kda_shapes(cfg: Config) -> dict[str, tuple]:
    h, w, r = cfg.hidden, cfg.kda_width, cfg.gate_rank
    return {"input_norm": (h,), "post_norm": (h,),
            "q": (h, w), "k": (h, w), "v": (h, w),
            "q_conv": (cfg.d_conv, w), "k_conv": (cfg.d_conv, w),
            "v_conv": (cfg.d_conv, w),
            "f_a": (h, r), "f_b": (r, w), "dt_bias": (w,),
            "A_log": (cfg.kda_heads,), "b_proj": (h, cfg.kda_heads),
            "g_a": (h, r), "g_b": (r, w), "o_norm": (cfg.kda_dim,),
            "o": (w, h)}


def mla_shapes(cfg: Config) -> dict[str, tuple]:
    h = cfg.hidden
    return {"input_norm": (h,), "post_norm": (h,), **mla.tensor_shapes(cfg)}


def dense_shapes(cfg: Config) -> dict[str, tuple]:
    h, i = cfg.hidden, cfg.dense_inter
    return {"mlp_gate": (h, i), "mlp_up": (h, i), "mlp_down": (i, h)}


def moe_shapes(cfg: Config) -> dict[str, tuple]:
    return experts.tensor_shapes(cfg, bias=True)


def _kind(name: str) -> str:
    if name.endswith("_conv"):
        return "conv"
    if name in ("dt_bias", "A_log"):
        return name
    return "gain" if name.endswith("norm") else "normal"


def _tensor(key, kind: str, shape: tuple, std: float):
    """One tensor from its key, by the rule of its ``kind``."""
    if kind == "conv":
        bound = shape[0] ** -0.5
        w = jax.random.uniform(key, shape, F32, -bound, bound)
    elif kind == "dt_bias":
        w = common.step_bias(key, shape, DT_MIN, DT_MAX)
    elif kind == "A_log":
        w = jnp.log(jax.random.uniform(key, shape, F32, 1.0, A_MAX))
    else:
        w = jax.random.normal(key, shape, F32) * std
        if kind == "gain":
            w = 1.0 + w
    return w.astype(BF16)


#: compiled once per kind and shape, whatever the name and the layer
_make_one = jax.jit(_tensor, static_argnums=(1, 2, 3))
#: one tensor per expert id, ``fold_in(key, expert)``, on a leading axis
_make_experts = jax.jit(
    lambda key, ids, kind, shape, std: jax.vmap(lambda e: _tensor(
        jax.random.fold_in(key, e), kind, shape, std))(ids),
    static_argnums=(2, 3, 4))


def make_layer(cfg: Config, layer: int, shapes: dict, held=None,
               wider=()) -> dict:
    """The tensors of model layer ``layer`` (0-based) named in ``shapes``;
    each ``expert_*`` tensor once per expert of ``held`` (global ids);
    those named in ``wider`` drawn ``qk_init_scale`` times as wide."""
    out = {}
    for name, shape in shapes.items():
        key = common.tensor_key(cfg.seed, layer, name)
        if name.startswith("expert_"):
            out[name] = _make_experts(
                key, jnp.asarray(list(held), jnp.uint32), _kind(name), shape,
                cfg.init_range)
        else:
            std = cfg.init_range * (cfg.qk_init_scale if name in wider else 1)
            out[name] = _make_one(key, _kind(name), shape, std)
    return out


def ffn_shapes(cfg: Config, layer: int) -> dict[str, tuple]:
    return dense_shapes(cfg) if layer < cfg.first_dense else moe_shapes(cfg)


def make_params(cfg: Config, held=None) -> dict:
    """``kda``: every KDA mixer's tensors with its two norms, stacked;
    ``mla``: a list, one dict an MLA layer, its up-projections as
    ``mla.store`` lays them; ``ffn``: a list, one dict a
    model layer (dense or experts). ``held``: the routed experts held
    (default: the config's range)."""
    if held is None:
        held = range(cfg.held_lo, cfg.held_lo + cfg.n_held)
    glob = {"embed": (cfg.vocab, cfg.hidden), "final_norm": (cfg.hidden,),
            "head": (cfg.hidden, cfg.vocab)}
    params = make_layer(cfg, GLOBAL_LAYER, glob)
    kda = [make_layer(cfg, i, kda_shapes(cfg)) for i in cfg.kda_ids]
    params["kda"] = {name: jnp.stack([lp[name] for lp in kda])
                     for name in kda[0]}
    params["mla"] = [mla.store(cfg, make_layer(
        cfg, i, mla_shapes(cfg), wider=("q", "kv_a"))) for i in cfg.mla_ids]
    params["ffn"] = [make_layer(cfg, i, ffn_shapes(cfg, i), held)
                     for i in range(cfg.layers)]
    return params


def param_count(cfg: Config) -> int:
    def total(shapes):
        return sum((cfg.n_held if name.startswith("expert_") else 1)
                   * math.prod(s) for name, s in shapes.items())

    return (2 * cfg.vocab * cfg.hidden + cfg.hidden
            + len(cfg.kda_ids) * total(kda_shapes(cfg))
            + len(cfg.mla_ids) * total(mla_shapes(cfg))
            + sum(total(ffn_shapes(cfg, i)) for i in range(cfg.layers)))


def state_shapes(cfg: Config, n_pages: int, page_tokens: int,
                 slots: int) -> dict:
    """The device state of this family's sequences. ``pages``: latent rows
    of the MLA layers, each stored in whole lane tiles (``mla.qkv``). Per
    SLOT (and two rows more: row ``slots`` for rows of a step that carry
    no sequence, row ``slots + 1`` the snapshot after the shared
    prefix's last token) and KDA layer: ``kda``, the float32
    matrix state of every head, and ``conv``, the last ``d_conv - 1``
    inputs of the three convolutions (``q | k | v``, taps side by side),
    each slot's row as whole bfloat16 tiles (``slot_rows.tiled``). A
    decode step's kernel addresses both by ``[layer, slot]`` and moves
    the rows it names in place (``kda_decode``)."""
    rows = slots + 2
    n = len(cfg.kda_ids)
    return {
        "pages": jax.ShapeDtypeStruct(
            (len(cfg.mla_ids), n_pages, page_tokens,
             common.row_width(cfg.latent)), BF16),
        "kda": jax.ShapeDtypeStruct(
            (n, rows, cfg.kda_heads, cfg.kda_dim, cfg.kda_dim), F32),
        "conv": jax.ShapeDtypeStruct(
            (n, rows, *slot_rows.tiled((cfg.d_conv - 1) * 3 * cfg.kda_width)),
            BF16),
    }


# ---------------------------------------------------------------- layers


def _kda_inputs(cfg: Config, lp: dict, h, taps, live):
    """From the normed residual rows ``h`` [T, hidden] and the
    convolutions' taps (oldest first, the newest the rows' own ``q | k |
    v``): what the recurrence reads, each [T, heads, dim] float32: ``q``,
    ``k``, ``b k``, ``b v``, the log decay ``g``; rows that are not
    ``live`` write nothing and decay nothing."""
    t, hd, d = h.shape[0], cfg.kda_heads, cfg.kda_dim
    w = jnp.concatenate([lp["q_conv"], lp["k_conv"], lp["v_conv"]],
                        axis=1).astype(F32)
    acc = sum(w[i] * tap.astype(F32) for i, tap in enumerate(taps))
    qkv = jax.nn.silu(acc).reshape(t, 3, hd, d)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

    q, k, v = unit(qkv[:, 0]) * d ** -0.5, unit(qkv[:, 1]), qkv[:, 2]
    step = jax.nn.softplus(
        jnp.dot(_mm(h, lp["f_a"]), lp["f_b"], preferred_element_type=F32)
        + lp["dt_bias"].astype(F32)).reshape(t, hd, d)
    g = -jnp.exp(lp["A_log"].astype(F32))[None, :, None] * step
    beta = jax.nn.sigmoid(jnp.dot(h, lp["b_proj"],
                                  preferred_element_type=F32))
    beta = jnp.where(live[:, None], beta, 0.0)[:, :, None]
    return q, k, beta * k, beta * v, jnp.where(live[:, None, None], g, 0.0)


def _kda_out(cfg: Config, lp: dict, h, o):
    """``W_o (rms_norm_head(o) * sigmoid(W_g2 (W_g1 h)))``; ``o`` [T,
    heads, dim] float32."""
    t = h.shape[0]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.eps)
    o = o * lp["o_norm"].astype(F32)
    gate = jax.nn.sigmoid(jnp.dot(_mm(h, lp["g_a"]), lp["g_b"],
                                  preferred_element_type=F32))
    return _mm((o.reshape(t, -1) * gate).astype(BF16), lp["o"])


def _qkv_pre(lp: dict, h):
    return jnp.concatenate([_mm(h, lp["q"]), _mm(h, lp["k"]),
                            _mm(h, lp["v"])], axis=1)


def kda_prefill(cfg: Config, lp: dict, x, seg, conv0, s0):
    """A packed chunk through one KDA mixer. ``conv0`` [S, (d_conv-1) * 3 *
    width] and ``s0`` [S, heads, dim, dim]: what each segment starts
    from. Returns the mixer's output [T, hidden] and each segment's
    convolution inputs and state after its last token here."""
    t = x.shape[0]
    h = rms_norm(x, lp["input_norm"], cfg.eps)
    pre = _qkv_pre(lp, h)
    taps, conv_end = common.packed_conv_inputs(pre, seg, conv0,
                                               cfg.d_conv - 1)
    flat = [a.reshape(t, -1) for a in _kda_inputs(
        cfg, lp, h, taps + [pre], seg >= 0)]
    rule = (pallas_kda.delta_rule if common.on_tpu()
            else pallas_kda.delta_rule_xla)
    o, s_end = rule(*flat, seg, s0)
    o = o.reshape(t, cfg.kda_heads, cfg.kda_dim)
    return _kda_out(cfg, lp, h, o), conv_end, s_end


def kda_decode(cfg: Config, lp: dict, x, slot, live, conv_all, s_all, l=None):
    """One token per row through one KDA mixer, each live row's slot
    state moved IN PLACE: ``slot`` [B] names row ``b``'s row of the slot
    state, of which ``conv_all`` [layers, R, *tile] (the taps' (d_conv-1)
    * 3 * width values, ``state_shapes``) and ``s_all`` [layers, R, heads,
    dim, dim] float32 are the WHOLE arrays
    and ``l`` this mixer's layer (``l`` None: one layer's arrays, a
    leading axis less). Returns the output [B, hidden] and both arrays,
    the rows that ``live`` rows name moved on by their token and every
    other row as it was, bit for bit. On the chip the recurrence is the
    Pallas kernel ``kda_decode_rows`` over the step's rows, the layer and
    the slot ids its prefetched scalars and the state aliased in and out
    (ops/pallas_kda.py ``decode_rows``, ops/slot_rows.py): nothing is
    sliced out a layer, gathered or scattered; elsewhere its twin
    gathers the rows and puts them back. A row that carries no sequence
    names the null row, writes back what it read and comes out zero. The
    convolutions' rows (72 KB each) are gathered through XLA before the
    mixer's inputs can be made, and written by the same kernel."""
    one = l is None
    if one:
        l, conv_all, s_all = jnp.int32(0), conv_all[None], s_all[None]
    c = 3 * cfg.kda_width
    h = rms_norm(x, lp["input_norm"], cfg.eps)
    pre = _qkv_pre(lp, h)
    conv_old = conv_all[l, slot].reshape(x.shape[0], -1)
    taps = [conv_old[:, i * c:(i + 1) * c] for i in range(cfg.d_conv - 1)]
    conv_new = jnp.concatenate([conv_old[:, c:], pre], axis=1)
    rows = (pallas_kda.decode_rows if common.on_tpu()
            else pallas_kda.decode_rows_xla)
    o, s_all, conv_all = rows(
        l, slot, live, *_kda_inputs(cfg, lp, h, taps + [pre], live),
        conv_new.reshape(-1, *conv_all.shape[2:]), s_all, conv_all)
    y = _kda_out(cfg, lp, h, o)
    return (y, conv_all[0], s_all[0]) if one else (y, conv_all, s_all)


def _mla_qkv(cfg: Config, lp: dict, x):
    """Of an MLA layer, from the residual rows ``x``: the query's two
    parts and the rows the cache holds (``mla.qkv``)."""
    qn, qr, lat = mla.qkv(cfg, lp, rms_norm(x, lp["input_norm"], cfg.eps))
    return (qn, qr), lat


def head(cfg: Config, params: dict, x):
    return common.head(x, params["final_norm"], cfg.eps, params["head"])


# ----------------------------------------------------------- step bodies


def _layers(cfg: Config, params: dict, x, state, live, dest, kda_layer,
            attend, through_cache: bool):
    """Every layer in its order, as ONE ``lax.scan`` over the stacked KDA
    mixers: ``kda_layer(lp, l, x, kda, conv)`` with ``l`` the layer's row
    of the slot state, then that layer's feed-forward, then, where MLA
    layer ``j`` follows, its rows' ``mla.qkv``, ``attend(lp, j, qn, qr, lat,
    pages)`` (the mixer's output, before ``x`` is added) and its
    feed-forward. The branches that hold the layers read the page cache
    and never return it: they return the rows' new latents ``lat``
    (zeros where the trip has no MLA layer), which the loop body writes
    to ``pages[j, *dest]`` (``dest``: the rows' ``(dest_page,
    dest_off)``) under a ``lax.cond`` of its own that holds nothing else.
    ``through_cache``: the rows attend to their own new latents in the
    cache, so the write stands between ``qkv`` and ``attend`` (handed
    ``lat`` None), which a second ``lax.switch`` then holds; else
    ``attend`` reads ``lat`` itself and one ``lax.switch`` holds the
    whole trip. Returns ``x``, the state and the expert layers' counts
    (``experts.moe``) summed over the layers."""
    none = jnp.zeros((3,), jnp.int32)
    dest_page, dest_off = dest

    def ffn(layer: int, post_norm, x):
        """The feed-forward of model layer ``layer``, residual added."""
        w = params["ffn"][layer]
        h = rms_norm(x, post_norm, cfg.eps)
        if "router" in w:
            y, n = experts.moe(cfg, w, h, live)
        else:
            with jax.named_scope("dense_mlp"):
                y, n = common.swiglu(h, w["mlp_gate"], w["mlp_up"],
                                     w["mlp_down"]), none
        return x + y, n

    def mixed(j: int, x, q, pages, lat=None):
        """MLA layer ``j`` from its query on, and its feed-forward."""
        lp = params["mla"][j]
        with jax.named_scope("mla"):
            x = x + attend(lp, j, *q, lat, pages)
        return ffn(cfg.mla_ids[j], lp["post_norm"], x)

    def behind(l: int):
        """What follows KDA mixer ``l`` up to the cache's write: its
        feed-forward and, where an MLA layer is next, that layer's
        ``qkv``; where its rows do not attend through the cache, the
        rest of the trip too. Returns ``x``, the counts, the new latent
        rows and what the second conditional is handed of the query."""
        def run(post_norm, x, pages):
            x, n = ffn(cfg.kda_ids[l], post_norm, x)
            j = cfg.mla_after[l]
            if j < 0:
                return x, n, *jax.tree.map(
                    lambda a: jnp.zeros(a.shape, a.dtype), blank)
            lp = params["mla"][j]
            with jax.named_scope("mla"):
                q, lat = _mla_qkv(cfg, lp, x)
            if through_cache:
                return x, n, lat, q
            x, m = mixed(j, x, q, pages, lat)
            return x, n + m, lat, ()
        return run

    q, lat = jax.eval_shape(lambda: _mla_qkv(cfg, params["mla"][0], x))
    blank = (lat, q if through_cache else ())
    first = [behind(l) for l in range(len(cfg.kda_ids))]
    second = [lambda x, q, pages: (x, none),
              *(partial(mixed, j) for j in range(len(cfg.mla_ids)))]

    def body(carry, xs):
        lp, l, j = xs
        x, pages, kda, conv, held = carry
        x, kda, conv = kda_layer(lp, l, x, kda, conv)
        x, n, lat, q = jax.lax.switch(l, first, lp["post_norm"], x, pages)
        pages = jax.lax.cond(
            j >= 0, lambda pages: pages.at[j, dest_page, dest_off].set(lat),
            lambda pages: pages, pages)
        if through_cache:
            x, m = jax.lax.switch(j + 1, second, x, q, pages)
            n = n + m
        return (x, pages, kda, conv, held + n), None

    n = len(cfg.kda_ids)
    carry = (x, state["pages"], state["kda"], state["conv"], none)
    (x, pages, kda, conv, held), _ = jax.lax.scan(
        body, carry, (params["kda"], jnp.arange(n, dtype=jnp.int32),
                      jnp.asarray(cfg.mla_after, jnp.int32)))
    return x, {"pages": pages, "kda": kda, "conv": conv}, held


def prefix_heads_shapes(cfg: Config, rows: int) -> list:
    """``mla.prefix_heads_shapes`` of the MLA layers."""
    return mla.prefix_heads_shapes(cfg, rows, len(cfg.mla_ids))


def prefix_heads(cfg: Config, params: dict, state, prefix_pages) -> list:
    """``mla.prefix_heads`` of the MLA layers."""
    return mla.prefix_heads(cfg, params["mla"], state["pages"], prefix_pages)


def chunk_key_blocks(cfg: Config, seg, n_prefix: int, n_cont: int,
                     prefix_pages: int, cont_pages: int,
                     page_tokens: int) -> list:
    """What the engine counts a chunk's key blocks by: per kind of layer
    whose chunks run the chunk kernel ``(its name, its layers, the classes
    of one layer's call)`` for a chunk of segments ``seg`` (numpy) over a
    prefix of ``prefix_pages`` pages and ``cont_pages`` continued ones."""
    return [("mla", len(cfg.mla_ids), mla.chunk_key_blocks(
        seg, n_prefix, n_cont, prefix_pages * page_tokens,
        cont_pages * page_tokens))]


def prefill_chunk(cfg: Config, params: dict, state, tokens, seg, pos,
                  dest_page, dest_off, prefix_pages, n_prefix, cont_pages,
                  n_cont, last_idx, seg_from, seg_to, prefix_heads=None):
    """A packed chunk of new tokens through every layer. MLA layers write
    the tokens' latent rows to ``state["pages"][layer, dest_page,
    dest_off]``; KDA layers start segment ``s`` from slot-state row
    ``seg_from[s]`` (the snapshot's for a new sequence, the slot's own
    for a prompt that continues) and leave its end state in row
    ``seg_to[s]``. Rows of no segment (``seg`` -1: the chunk's tail, and
    the rows before a segment's aligned start) move no state. Returns the
    state, the logits rows ``last_idx`` as ``(top, ids)`` and the expert
    layers' counts (``experts.moe``). ``pos`` is not used: no layer has a
    positional term. ``prefix_heads``: the prefix's held heads
    (``prefix_heads``), read and never written (None: an MLA layer expands
    the prefix's rows itself)."""

    def kda_layer(lp, l, x, kda, conv):
        with jax.named_scope("kda"):
            y, conv_end, s_end = kda_prefill(
                cfg, lp, x, seg, conv[l, seg_from].reshape(len(seg_from), -1),
                kda[l, seg_from])
            kda = kda.at[l, seg_to].set(s_end)
            conv = conv.at[l, seg_to].set(
                conv_end.reshape(-1, *conv.shape[2:]))
        return x + y, kda, conv

    def attend(lp, j, qn, qr, lat, pages):
        return mla.mla_prefill(
            cfg, lp, qn, qr, lat, seg,
            common.layer_page_rows(pages, j, prefix_pages), n_prefix,
            common.layer_page_rows(pages, j, cont_pages), n_cont,
            prefix_heads[j] if prefix_heads else None)

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    x, state, held = _layers(cfg, params, x, state, seg >= 0,
                             (dest_page, dest_off), kda_layer, attend,
                             through_cache=False)
    _, top, ids = head(cfg, params, x[last_idx])
    return state, top, ids, held


def decode_tokens(cfg: Config, params: dict, state, tokens, pos, page_table,
                  ctx_len, dest_page, dest_off, live, prefix_pages, n_prefix,
                  slot):
    """One token per row. KDA layers move row ``slot[b]`` of the slot
    state on IN PLACE for every ``live`` row (``kda_decode``: the whole
    arrays go in and come out, the layer an index) and leave every other
    row as it was (the null row that the others name among them); MLA
    layers write the row's latent to its page and attend to the shared
    prefix (read once for all rows) and, through the table of its OWN
    pages, to its ``ctx_len`` own cached rows."""

    def kda_layer(lp, l, x, kda, conv):
        with jax.named_scope("kda"):
            y, conv, kda = kda_decode(cfg, lp, x, slot, live, conv, kda, l)
        return x + y, kda, conv

    def attend(lp, j, qn, qr, lat, pages):
        return mla.mla_decode(
            cfg, lp, qn, qr,
            common.layer_page_rows(pages, j, page_table), ctx_len,
            common.layer_page_rows(pages, j, prefix_pages), n_prefix)

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    x, state, held = _layers(cfg, params, x, state, live,
                             (dest_page, dest_off), kda_layer, attend,
                             through_cache=True)
    _, top, ids = head(cfg, params, x)
    return state, top, ids, held
