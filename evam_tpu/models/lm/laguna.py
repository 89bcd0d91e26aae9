"""Laguna in plain JAX: window and full attention layers of DIFFERENT head
counts, each head's output gated, over a layer of many small sparse experts
and a shared one; one pipeline stage of the model, every layer of it whole.

Layer ``i`` (0-based) is what ``layer_types[i]`` says, ``full_attention``
or ``sliding_attention``, with ``num_attention_heads_per_layer[i]`` query
heads, and behind it what ``mlp_layer_types[i]`` says, a ``dense`` SwiGLU or
the ``sparse`` expert layer. Pre-norm residual (``input_norm``,
``post_norm``), RMSNorm; an untied head behind the final norm.

* **Attention, both kinds**: models/lm/attention.py (Jamba's and LFM2's
  too), here as TWO ``attention.Kind``s over the same 8 key-value heads of
  ``head_dim``: the full layers' (48 query heads; everything earlier; of
  each head the first ``partial_rotary_factor`` turns, under YaRN's static
  frequency table and times its ``attention_factor``) and the window
  layers' (64 query heads; the token and the ``sliding_window - 1`` before
  it; the plain rotation of the whole head). Both with an RMSNorm on every
  query and key head before the rotation, and with ``sigmoid(W_g u)`` per
  token and query head on the head's output before ``W_o``. The cache row
  of a token is ``[k ; v]``, keys normed and rotated, in pages
  (engine/pages.py), in EVERY layer: ``state_shapes`` is pages alone. A
  window layer reads of the shared prefix only the pages a token behind it
  can still see (``attention.window_pages``).
* **Expert layer**: models/lm/experts.py, as Kimi-Linear's (sigmoid scores
  over all experts, chosen by ``s + router_bias``, weighted by ``s``,
  renormalised with the family's epsilon, times
  ``moe_routed_scaling_factor``, one shared expert on every token) with
  ALL of a layer's experts held (``experts_held`` = ``num_experts`` in the
  published cut; a smaller held range still means what it says).

What the published config leaves open, and how it is read here (each with
its ground in ``benchmark/configs/laguna_xs2_pp8.json`` ``assumed``): the
gate is per head (``gating: true``; the sibling config spells it
``per-head``); the router's scores are sigmoids renormalised over the
chosen (``moe_routed_scaling_factor`` beside top-k renormalisation is that
convention's) under a selection bias; the head norms are there; the
rotation pairs ``(x_j, x_{j + r/2})`` within the rotated part.

bfloat16 weights and activations; the softmax, the gates and the router's
scores float32; nothing float32 is kept between steps. Every kind's
weights are STACKED and ONE ``lax.scan`` runs over the layers: a trip
picks its mixer by a ``lax.cond`` (full layer ``m`` or window layer ``m``,
each reading its slice of its stack) and its feed-forward by another
(dense layer ``f`` or expert layer ``f``). The expert layers' tensors are
ONE stack [expert layers, held, ...] that the grouped products read in
place, the layer a prefetched scalar of their kernel
(ops/pallas_grouped.py): each kernel has one name a branch in a device
trace (``expert_gate_up``, ``expert_down``; ``attn_chunk_attention``
twice, once a kind) and no expert tensor is ever copied.

Weights (``common.tensor_key``): ``normal * initializer_range``; gains ``1
+ that``, but the query and key head norms' ``qk_norm_gain + that`` (at a
gain of 1 a seeded softmax over 2.4 k rows is flat, and a flat softmax
hides a missing window as it hides a missing rotation); ``gate`` and
``router_bias`` as any tensor (the gates spread around a half, the bias is
small and not zero, so that selection and weighting differ).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from evam_tpu.models.lm import attention, common, experts
from evam_tpu.models.lm.common import BF16, F32, GLOBAL_LAYER, rms_norm

#: the packer may start a segment at any token of a chunk
SEGMENT_ALIGN = 1
FULL, WINDOW = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


def _rope(d: dict, head_dim: int) -> attention.Rope:
    """One entry of ``rope_parameters`` as the rotation's data."""
    rotated = int(head_dim * d.get("partial_rotary_factor", 1))
    if d["rope_type"] == "default":
        return attention.Rope(float(d["rope_theta"]), rotated)
    if d["rope_type"] != "yarn":
        raise ValueError(f"rope_type {d['rope_type']!r} is not written")
    return attention.Rope(
        float(d["rope_theta"]), rotated, float(d["attention_factor"]),
        (float(d["factor"]), float(d["original_max_position_embeddings"]),
         float(d["beta_fast"]), float(d["beta_slow"])))


@dataclass(frozen=True)
class Config:
    hidden: int
    dense_inter: int
    moe_inter: int
    layer_types: tuple
    mlp_types: tuple
    full: attention.Kind
    windowed: attention.Kind
    n_experts: int      # the router's outputs
    n_held: int
    held_lo: int
    n_shared: int
    top_k: int
    routed_scale: float
    eps: float
    vocab: int          # rows of the vocabulary held here
    seed: int
    init_range: float
    qk_norm_gain: float     # the mean of the head norms' gains

    #: what models/lm/experts.py reads beside the fields
    score_func = "sigmoid"
    expert_act = "swiglu"
    moe_latent = None
    norm_topk = True
    scale_routed = True
    n_group = 1
    topk_group = 1
    topk_eps = 1e-20

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        n = d["num_hidden_layers"]
        kinds = tuple(d["layer_types"][:n])
        mlps = tuple(d["mlp_layer_types"][:n])
        heads = tuple(d["num_attention_heads_per_layer"][:n])
        per_kind = {k: {a for a, kind in zip(heads, kinds) if kind == k}
                    for k in (FULL, WINDOW)}
        if (len(kinds) != n or len(mlps) != n or len(heads) != n
                or set(kinds) - {FULL, WINDOW} or set(mlps) - {DENSE, SPARSE}
                or any(len(v) != 1 for v in per_kind.values())
                or any(a % d["num_key_value_heads"] for a in heads)
                or d["attention_bias"] or d["tie_word_embeddings"]
                or not d["gating"] or d["moe_apply_router_weight_on_input"]
                or d["shared_expert_intermediate_size"]
                % d["moe_intermediate_size"]):
            raise ValueError(
                "the laguna family is written for layers that are each full "
                "or window attention (both kinds present, one head count a "
                "kind, in whole groups over the key-value heads, gated, no "
                "bias) before a dense or a sparse feed-forward, shared "
                "experts of the routed experts' width, the router's weight "
                "on an expert's output and an untied head")
        hd, ropes = d["head_dim"], d["rope_parameters"]

        def kind(name, window):
            return attention.Kind(
                hidden=d["hidden_size"], heads=per_kind[name].pop(),
                kv_heads=d["num_key_value_heads"], head_dim=hd,
                eps=d["rms_norm_eps"], rope=_rope(ropes[name], hd),
                window=window)

        return cls(
            hidden=d["hidden_size"], dense_inter=d["intermediate_size"],
            moe_inter=d["moe_intermediate_size"], layer_types=kinds,
            mlp_types=mlps, full=kind(FULL, None),
            windowed=kind(WINDOW, int(d["sliding_window"])),
            n_experts=d["num_experts"], n_held=d["experts_held"],
            held_lo=d["held_lo"],
            n_shared=(d["shared_expert_intermediate_size"]
                      // d["moe_intermediate_size"]),
            top_k=d["num_experts_per_tok"],
            routed_scale=float(d["moe_routed_scaling_factor"]),
            eps=d["rms_norm_eps"], vocab=d["vocab_held"],
            seed=d["weights_seed"], init_range=d["initializer_range"],
            qk_norm_gain=float(d["qk_norm_gain"]))

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def window(self) -> int:
        """What the engine counts a window layer's rows by."""
        return self.windowed.window

    @property
    def full_ids(self) -> tuple:
        """The full-attention layers' model layer indices, in order."""
        return tuple(i for i, k in enumerate(self.layer_types) if k == FULL)

    @property
    def window_ids(self) -> tuple:
        return tuple(i for i, k in enumerate(self.layer_types) if k == WINDOW)

    @property
    def dense_ids(self) -> tuple:
        return tuple(i for i, k in enumerate(self.mlp_types) if k == DENSE)

    @property
    def moe_ids(self) -> tuple:
        """The model layers that have the expert layer, in order."""
        return tuple(i for i, k in enumerate(self.mlp_types) if k == SPARSE)

    @property
    def kv_width(self) -> int:
        """Values a page row holds: 8 keys and 8 values of 128, in either
        kind of layer."""
        return attention.kv_width(self.full)

    @property
    def schedule(self) -> tuple:
        """Per model layer ``(is window, its index among its kind, is
        dense, its index among the layers with its feed-forward)``."""
        at = dict.fromkeys((FULL, WINDOW, DENSE, SPARSE), 0)
        out = []
        for kind, mlp in zip(self.layer_types, self.mlp_types):
            out.append((int(kind == WINDOW), at[kind], int(mlp == DENSE),
                        at[mlp]))
            at[kind] += 1
            at[mlp] += 1
        return tuple(out)


# --------------------------------------------------------------- weights


def norm_shapes(cfg: Config) -> dict[str, tuple]:
    return {"input_norm": (cfg.hidden,), "post_norm": (cfg.hidden,)}


def attn_shapes(kind: attention.Kind) -> dict[str, tuple]:
    return attention.tensor_shapes(kind, head_norms=True, gate=True)


def dense_shapes(cfg: Config) -> dict[str, tuple]:
    h, i = cfg.hidden, cfg.dense_inter
    return {"mlp_gate": (h, i), "mlp_up": (h, i), "mlp_down": (i, h)}


def moe_shapes(cfg: Config) -> dict[str, tuple]:
    return experts.tensor_shapes(cfg, bias=True)


def _kind(name: str) -> str:
    if name in ("q_norm", "k_norm"):
        return "head_gain"
    return "gain" if name.endswith("norm") else "normal"


def _tensor(key, kind: str, shape: tuple, std: float, mean: float):
    """One tensor from its key, by the rule of its ``kind``."""
    w = jax.random.normal(key, shape, F32) * std
    if kind == "gain":
        w = 1.0 + w
    elif kind == "head_gain":
        w = mean + w
    return w.astype(BF16)


#: compiled once per kind and shape, whatever the name and the layer
_make_one = jax.jit(_tensor, static_argnums=(1, 2, 3, 4))


def make_tensor(cfg: Config, layer: int, name: str, shape: tuple):
    return _make_one(common.tensor_key(cfg.seed, layer, name), _kind(name),
                     shape, cfg.init_range, cfg.qk_norm_gain)


def make_layers(cfg: Config, layers, shapes: dict, held=None) -> dict:
    """The tensors of ``layers`` (model layer indices), each name's
    stacked on a leading axis; each ``expert_*`` tensor once per expert
    of ``held`` (global ids) on a second (``common.make_layers``: a
    layer's experts written into the stack in place)."""
    return common.make_layers(
        lambda i, name, shape: make_tensor(cfg, i, name, shape), cfg.seed,
        cfg.init_range, layers, shapes, held)


def make_params(cfg: Config, held=None) -> dict:
    """``norms``: every layer's two; ``full``, ``window``, ``dense``,
    ``moe``: the layers of a kind, stacked. ``held``: the routed experts
    held (default: the config's range)."""
    if held is None:
        held = range(cfg.held_lo, cfg.held_lo + cfg.n_held)
    return {
        "embed": make_tensor(cfg, GLOBAL_LAYER, "embed",
                             (cfg.vocab, cfg.hidden)),
        "final_norm": make_tensor(cfg, GLOBAL_LAYER, "final_norm",
                                  (cfg.hidden,)),
        "head": make_tensor(cfg, GLOBAL_LAYER, "head",
                            (cfg.hidden, cfg.vocab)),
        "norms": make_layers(cfg, range(cfg.layers), norm_shapes(cfg)),
        "full": make_layers(cfg, cfg.full_ids, attn_shapes(cfg.full)),
        "window": make_layers(cfg, cfg.window_ids, attn_shapes(cfg.windowed)),
        "dense": make_layers(cfg, cfg.dense_ids, dense_shapes(cfg)),
        "moe": make_layers(cfg, cfg.moe_ids, moe_shapes(cfg), held),
    }


def param_count(cfg: Config) -> int:
    def total(shapes):
        return sum((cfg.n_held if name.startswith("expert_") else 1)
                   * math.prod(s) for name, s in shapes.items())

    return (2 * cfg.vocab * cfg.hidden + cfg.hidden
            + cfg.layers * total(norm_shapes(cfg))
            + len(cfg.full_ids) * total(attn_shapes(cfg.full))
            + len(cfg.window_ids) * total(attn_shapes(cfg.windowed))
            + len(cfg.dense_ids) * total(dense_shapes(cfg))
            + len(cfg.moe_ids) * total(moe_shapes(cfg)))


def state_shapes(cfg: Config, n_pages: int, page_tokens: int,
                 slots: int) -> dict:
    """The device state of this family's sequences: key and value rows of
    every layer, in pages, and nothing per slot (the shared prefix is its
    pinned pages)."""
    return {"pages": jax.ShapeDtypeStruct(
        (cfg.layers, n_pages, page_tokens, cfg.kv_width), BF16)}


def head(cfg: Config, params: dict, x):
    return common.head(x, params["final_norm"], cfg.eps, params["head"])


# ----------------------------------------------------------- step bodies


def _at(stack: dict, i):
    """Layer ``i`` (traced) of a kind's stacked tensors."""
    return jax.tree.map(lambda a: a[i], stack)


def _layers(cfg: Config, params: dict, x, pages, live, attn_layer):
    """Every layer in its order, as ONE ``lax.scan`` over the stage's
    layers: a trip runs ``attn_layer(kind, lp, i, h, pages)`` (``pages``
    the whole cache, the layer's at index ``i``) on the normed rows ``h``
    as a full or a window layer, then the dense feed-forward or the expert
    layer, each a slice of its kind's stack (the experts' stack as it is).
    Returns ``x``, the pages and the expert layers' counts
    (``experts.moe``) summed over the layers."""
    none = jnp.zeros((3,), jnp.int32)

    def mixer(name, kind, scope):
        def run(h, m, i, pages):
            with jax.named_scope(scope):
                return attn_layer(kind, _at(params[name], m), i, h, pages)
        return run

    def dense_ffn(h, f):
        with jax.named_scope("dense_mlp"):
            w = _at(params["dense"], f)
            return common.swiglu(h, w["mlp_gate"], w["mlp_up"],
                                 w["mlp_down"]), none

    def moe_ffn(h, f):
        return experts.moe(cfg, params["moe"], h, live, f)

    def body(carry, xs):
        norms, (i, is_window, m, is_dense, f) = xs
        x, pages, held = carry
        h = rms_norm(x, norms["input_norm"], cfg.eps)
        y, pages = jax.lax.cond(
            is_window > 0, mixer("window", cfg.windowed, "attn_window"),
            mixer("full", cfg.full, "attn_full"), h, m, i, pages)
        x = x + y
        h = rms_norm(x, norms["post_norm"], cfg.eps)
        y, n = jax.lax.cond(is_dense > 0, dense_ffn, moe_ffn, h, f)
        return (x + y, pages, held + n), None

    sched = jnp.asarray(cfg.schedule, jnp.int32)
    steps = (jnp.arange(cfg.layers, dtype=jnp.int32),
             *(sched[:, i] for i in range(4)))
    (x, pages, held), _ = jax.lax.scan(
        body, (x, pages, none), (params["norms"], steps))
    return x, pages, held


def chunk_key_blocks(cfg: Config, seg, n_prefix: int, n_cont: int,
                     prefix_pages: int, cont_pages: int,
                     page_tokens: int) -> list:
    """What the engine counts a chunk's key blocks by: per kind of layer
    whose chunks run the chunk kernel ``(its name, its layers, the classes
    of one layer's call)`` for a chunk of segments ``seg`` (numpy) over a
    prefix of ``prefix_pages`` pages and ``cont_pages`` continued ones."""
    return [(name, len(ids), attention.chunk_key_blocks(
        kind, seg, n_prefix, n_cont, prefix_pages, cont_pages * page_tokens,
        page_tokens))
        for name, ids, kind in (("attn_full", cfg.full_ids, cfg.full),
                                ("attn_window", cfg.window_ids,
                                 cfg.windowed))]


def prefill_chunk(cfg: Config, params: dict, state, tokens, seg, pos,
                  dest_page, dest_off, prefix_pages, n_prefix, cont_pages,
                  n_cont, last_idx, seg_from, seg_to):
    """A packed chunk of new tokens through every layer, each writing the
    tokens' rows (keys rotated to ``pos``) to ``state["pages"][layer,
    dest_page, dest_off]``. ``seg_from`` / ``seg_to`` name slot-state rows
    and this family has none. Returns the state, the logits rows
    ``last_idx`` as ``(top, ids)`` and the expert layers' counts
    (``experts.moe``)."""
    del seg_from, seg_to
    page_tokens = state["pages"].shape[2]

    def attn_layer(kind, lp, i, h, pages):
        q, kv = attention.qkv(kind, lp, h, pos)
        seen, first = attention.window_pages(kind.window, prefix_pages,
                                             n_prefix, page_tokens)
        y = attention.attn_prefill(
            kind, lp, q, kv, seg, common.layer_page_rows(pages, i, seen),
            n_prefix, common.layer_page_rows(pages, i, cont_pages), n_cont,
            attention.head_gates(lp, h), first)
        return y, pages.at[i, dest_page, dest_off].set(kv)

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    x, pages, held = _layers(cfg, params, x, state["pages"], seg >= 0,
                             attn_layer)
    _, top, ids = head(cfg, params, x[last_idx])
    return {"pages": pages}, top, ids, held


def decode_tokens(cfg: Config, params: dict, state, tokens, pos, page_table,
                  ctx_len, dest_page, dest_off, live, prefix_pages, n_prefix,
                  slot):
    """One token per row: every layer writes the row's ``[k ; v]`` (the key
    rotated to ``pos``) to its page and attends to the shared prefix (read
    once for all rows; in a window layer only the pages its window still
    reaches) and, through the table of its OWN pages, to its ``ctx_len``
    own cached rows (in a window layer the last ``sliding_window`` of the
    two together)."""
    del slot
    page_tokens = state["pages"].shape[2]

    def attn_layer(kind, lp, i, h, pages):
        q, kv = attention.qkv(kind, lp, h, pos)
        pages = pages.at[i, dest_page, dest_off].set(kv)
        seen, first = attention.window_pages(kind.window, prefix_pages,
                                             n_prefix, page_tokens)
        y = attention.attn_decode(
            kind, lp, q, pages, i, page_table, ctx_len,
            common.layer_page_rows(pages, i, seen), n_prefix,
            attention.head_gates(lp, h), first)
        return y, pages

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    x, pages, held = _layers(cfg, params, x, state["pages"], live, attn_layer)
    _, top, ids = head(cfg, params, x)
    return {"pages": pages}, top, ids, held
