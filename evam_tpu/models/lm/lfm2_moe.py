"""LFM2-MoE in plain JAX: gated short convolutions with a few grouped-query
attention layers between, over sparse experts; one chip's share of an
expert-parallel layer, at the model's whole depth.

Layer ``i`` (0-based) is what ``layer_types[i]`` says, ``conv`` or
``full_attention`` (24 layers: attention at 2, 6, 10, 14, 18, 21, NOT at a
regular period); the first ``num_dense_layers`` layers have a dense SwiGLU
behind their mixer, every other layer the expert layer. Pre-norm residual
(``operator_norm``, ``ffn_norm``), RMSNorm; the head is the embedding
(tied), behind the model's ``embedding_norm``.

* **Gated short convolution.** ``[B, C, x] = W_in u`` (three parts of
  ``hidden``, in that order, no bias); ``z_t = sum_j w_j * (B * x)_{t-2+j}``
  (depthwise, causal, ``conv_L_cache`` = 3 taps, no bias, NO activation);
  out = ``W_out (C * z)``. Between steps a sequence carries the last 2
  values of ``B * x`` per layer and nothing else: per SLOT of the generate
  engine, never paged (``state_shapes``). A prefill chunk takes each
  segment's carried inputs through ``common.packed_conv_inputs``; a decode
  step's one token is ONE Pallas kernel over the step's rows that reads a
  row's taps, convolves, gates and writes the taps back where they were
  (ops/pallas_short_conv.py ``conv_decode_rows``, ops/slot_rows.py).
* **Attention mixer**: models/lm/attention.py (Jamba's too), here with 32
  query heads over 8 key-value heads, an RMSNorm on every query and key
  head and rotary positions (``rope_theta``, the half-split pairing). The
  cache row of a token is ``[k ; v]``, keys rotated, in pages
  (engine/pages.py), in the attention layers only.
* **Expert layer**: models/lm/experts.py, as Kimi-Linear's (sigmoid scores,
  chosen by ``s + expert_bias``, weighted by ``s``, renormalised with the
  family's epsilon, times ``routed_scaling_factor``) with NO shared expert
  and a held RANGE of experts ``[held_lo, held_lo + experts_held)``.

bfloat16 weights and activations; the convolution's sums, the softmax and
the router's scores float32; nothing float32 is kept between steps. Every
layer kind's weights are STACKED and ONE ``lax.scan`` runs over the 24
layers: a trip picks its mixer by a ``lax.cond`` (convolution ``c`` or
attention layer ``j``, each reading its slice of its stack) and its
feed-forward by another (dense layer ``d`` or expert layer ``e``). The
expert layers' tensors are ONE stack [expert layers, held, ...] that the
grouped products read in place, the layer a prefetched scalar of their
kernel (ops/pallas_grouped.py): one loop body a program, so each kernel
has ONE name in a device trace (``conv_decode_rows``, ``expert_gate_up``,
``expert_down``) and compile time does not grow with the 22 expert layers.

Weights (``common.tensor_key``): ``normal * initializer_range``; gains ``1
+ that``, but the query and key head norms' ``qk_norm_gain + that`` (at a
gain of 1 a seeded score has a deviation near 1 and a softmax over 2.4 k
rows is flat: nothing downstream would see which rows a query weighs, the
rotation among it); ``conv_w`` uniform in +-taps^-1/2; ``router_bias`` as
any tensor (small and not zero, so that selection and weighting differ).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from evam_tpu.models.lm import attention, common, experts
from evam_tpu.models.lm.common import BF16, F32, GLOBAL_LAYER, rms_norm
from evam_tpu.models.lm.common import mm as _mm
from evam_tpu.ops import pallas_short_conv, slot_rows

#: the packer may start a segment at any token of a chunk
SEGMENT_ALIGN = 1
CONV, ATTN = "conv", "full_attention"


@dataclass(frozen=True)
class Config:
    hidden: int
    dense_inter: int
    moe_inter: int
    layer_types: tuple
    n_dense: int
    taps: int
    heads: int
    kv_heads: int
    rope_theta: float
    n_experts: int      # the router's outputs
    n_held: int
    held_lo: int
    top_k: int
    routed_scale: float
    norm_topk: bool
    eps: float
    vocab: int          # rows of the vocabulary held here
    seed: int
    init_range: float
    qk_norm_gain: float     # the mean of the head norms' gains

    #: what models/lm/attention.py reads beside the fields
    chunk_kernel = True
    window = None
    #: what models/lm/experts.py reads beside the fields
    score_func = "sigmoid"
    expert_act = "swiglu"
    moe_latent = None
    scale_routed = True
    n_group = 1
    topk_group = 1
    n_shared = 0
    topk_eps = 1e-6

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        kinds = tuple(d["layer_types"][:d["num_hidden_layers"]])
        dense = d["num_dense_layers"]
        if (len(kinds) != d["num_hidden_layers"] or d["conv_bias"]
                or set(kinds) - {CONV, ATTN} or not d["use_expert_bias"]
                or not 0 < dense < len(kinds) or ATTN in kinds[:dense]
                or d["num_attention_heads"] % d["num_key_value_heads"]
                or d["hidden_size"] % d["num_attention_heads"]):
            raise ValueError(
                "the lfm2_moe family is written for layers that are each a "
                "short convolution or full attention, convolutions without "
                "bias, dense feed-forwards behind the leading convolution "
                "layers only and experts behind the rest, a selection bias, "
                "query heads in whole groups over the key-value heads")
        return cls(
            hidden=d["hidden_size"], dense_inter=d["intermediate_size"],
            moe_inter=d["moe_intermediate_size"], layer_types=kinds,
            n_dense=dense, taps=d["conv_L_cache"],
            heads=d["num_attention_heads"],
            kv_heads=d["num_key_value_heads"],
            rope_theta=float(d["rope_theta"]), n_experts=d["num_experts"],
            n_held=d["experts_held"], held_lo=d["held_lo"],
            top_k=d["num_experts_per_tok"],
            routed_scale=float(d["routed_scaling_factor"]),
            norm_topk=bool(d["norm_topk_prob"]), eps=d["norm_eps"],
            vocab=d["vocab_held"], seed=d["weights_seed"],
            init_range=d["initializer_range"],
            qk_norm_gain=float(d["qk_norm_gain"]))

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def rope(self) -> attention.Rope:
        """The plain rotation over the whole head."""
        return attention.Rope(self.rope_theta)

    @property
    def conv_ids(self) -> tuple:
        """The convolution layers' model layer indices, in order."""
        return tuple(i for i, k in enumerate(self.layer_types) if k == CONV)

    @property
    def attn_ids(self) -> tuple:
        return tuple(i for i, k in enumerate(self.layer_types) if k == ATTN)

    @property
    def moe_ids(self) -> tuple:
        """The model layers that have the expert layer, in order."""
        return tuple(range(self.n_dense, self.layers))

    @property
    def kv_width(self) -> int:
        """Values a page row holds: 8 keys and 8 values of 64."""
        return attention.kv_width(self)

    @property
    def schedule(self) -> tuple:
        """Per model layer ``(is attention, its index among its kind, is
        dense, its index among the layers with its feed-forward)``."""
        at = {kind: 0 for kind in (CONV, ATTN)}
        out = []
        for i, kind in enumerate(self.layer_types):
            dense = i < self.n_dense
            out.append((int(kind == ATTN), at[kind], int(dense),
                        i if dense else i - self.n_dense))
            at[kind] += 1
        return tuple(out)


# --------------------------------------------------------------- weights


def norm_shapes(cfg: Config) -> dict[str, tuple]:
    return {"operator_norm": (cfg.hidden,), "ffn_norm": (cfg.hidden,)}


def conv_shapes(cfg: Config) -> dict[str, tuple]:
    h = cfg.hidden
    return {"in_proj": (h, 3 * h), "conv_w": (cfg.taps, h),
            "out_proj": (h, h)}


def attn_shapes(cfg: Config) -> dict[str, tuple]:
    return attention.tensor_shapes(cfg, head_norms=True)


def dense_shapes(cfg: Config) -> dict[str, tuple]:
    h, i = cfg.hidden, cfg.dense_inter
    return {"mlp_gate": (h, i), "mlp_up": (h, i), "mlp_down": (i, h)}


def moe_shapes(cfg: Config) -> dict[str, tuple]:
    return experts.tensor_shapes(cfg, bias=True)


def _kind(name: str) -> str:
    if name == "conv_w":
        return "conv"
    if name in ("q_norm", "k_norm"):
        return "head_gain"
    return "gain" if name.endswith("norm") else "normal"


def _tensor(key, kind: str, shape: tuple, std: float, mean: float):
    """One tensor from its key, by the rule of its ``kind``."""
    if kind == "conv":
        bound = shape[0] ** -0.5
        w = jax.random.uniform(key, shape, F32, -bound, bound)
    else:
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
    """``norms``: every layer's two; ``conv``, ``attn``, ``dense``,
    ``moe``: the layers of a kind, stacked. ``held``: the routed experts
    held (default: the config's range)."""
    if held is None:
        held = range(cfg.held_lo, cfg.held_lo + cfg.n_held)
    return {
        "embed": make_tensor(cfg, GLOBAL_LAYER, "embed",
                             (cfg.vocab, cfg.hidden)),
        "final_norm": make_tensor(cfg, GLOBAL_LAYER, "final_norm",
                                  (cfg.hidden,)),
        "norms": make_layers(cfg, range(cfg.layers), norm_shapes(cfg)),
        "conv": make_layers(cfg, cfg.conv_ids, conv_shapes(cfg)),
        "attn": make_layers(cfg, cfg.attn_ids, attn_shapes(cfg)),
        "dense": make_layers(cfg, range(cfg.n_dense), dense_shapes(cfg)),
        "moe": make_layers(cfg, cfg.moe_ids, moe_shapes(cfg), held),
    }


def param_count(cfg: Config) -> int:
    def total(shapes):
        return sum((cfg.n_held if name.startswith("expert_") else 1)
                   * math.prod(s) for name, s in shapes.items())

    return (cfg.vocab * cfg.hidden + cfg.hidden
            + cfg.layers * total(norm_shapes(cfg))
            + len(cfg.conv_ids) * total(conv_shapes(cfg))
            + len(cfg.attn_ids) * total(attn_shapes(cfg))
            + cfg.n_dense * total(dense_shapes(cfg))
            + len(cfg.moe_ids) * total(moe_shapes(cfg)))


def state_shapes(cfg: Config, n_pages: int, page_tokens: int,
                 slots: int) -> dict:
    """The device state of this family's sequences. ``pages``: key and
    value rows of the attention layers. Per SLOT (and two rows more: row
    ``slots`` for rows of a step that carry no sequence, row ``slots +
    1`` the snapshot after the shared prefix's last token) and
    convolution layer: ``conv``, the last ``taps - 1`` values of ``B *
    x``, taps side by side, each slot's row as whole bfloat16 tiles
    (``slot_rows.tiled``). A decode step takes a layer's rows out, its
    kernel addresses them by slot and moves the rows it names in place
    (``conv_decode``, ``_layers``). Nothing float32 is kept between
    steps."""
    return {
        "pages": jax.ShapeDtypeStruct(
            (len(cfg.attn_ids), n_pages, page_tokens, cfg.kv_width), BF16),
        "conv": jax.ShapeDtypeStruct(
            (len(cfg.conv_ids), slots + 2,
             *slot_rows.tiled((cfg.taps - 1) * cfg.hidden)), BF16),
    }


# ---------------------------------------------------------------- layers


def _gates(cfg: Config, lp: dict, h):
    """From the normed rows: ``B * x`` (bfloat16, what the taps hold) and
    ``C``."""
    n = cfg.hidden
    bcx = _mm(h, lp["in_proj"])
    return bcx[:, :n] * bcx[:, 2 * n:], bcx[:, n:2 * n]


def conv_prefill(cfg: Config, lp: dict, h, seg, conv0):
    """A packed chunk through one short convolution. ``conv0`` [S, (taps -
    1) * hidden]: the inputs each segment carried in. Returns the mixer's
    output [T, hidden] and each segment's carried inputs after its last
    token here."""
    bx, c = _gates(cfg, lp, h)
    taps, conv_end = common.packed_conv_inputs(bx, seg, conv0, cfg.taps - 1)
    w = lp["conv_w"].astype(F32)
    z = w[cfg.taps - 1] * bx.astype(F32)
    for j, tap in enumerate(taps):
        z = z + w[j] * tap.astype(F32)
    y = (c.astype(F32) * z).astype(BF16)
    return _mm(y, lp["out_proj"]), conv_end


def conv_decode(cfg: Config, lp: dict, h, slot, live, taps):
    """One token per row through one short convolution, each live row's
    taps moved IN PLACE: ``slot`` [B] names row ``b``'s row of ``taps``
    [R, *tile], the layer's rows of the slot state (``state_shapes``).
    Returns the output [B, hidden] and the rows, those that ``live`` rows
    name moved on by their token, every other row as it was, bit for bit.
    On the chip this is the Pallas kernel ``conv_decode_rows`` over the
    step's rows, the slot ids its prefetched scalars and the array aliased
    in and out (ops/pallas_short_conv.py, ops/slot_rows.py): nothing is
    gathered or scattered; elsewhere its twin gathers the rows and puts
    them back. A row that carries no sequence names the null row, writes
    back what it read and comes out zero."""
    bx, c = _gates(cfg, lp, h)
    rows = (pallas_short_conv.decode_rows if common.on_tpu()
            else pallas_short_conv.decode_rows_xla)
    y, taps = rows(jnp.int32(0), slot, live, bx, c, lp["conv_w"], taps[None])
    return _mm(y.astype(BF16), lp["out_proj"]), taps[0]


def head(cfg: Config, params: dict, x):
    return common.head(x, params["final_norm"], cfg.eps, params["embed"],
                       tied=True)


# ----------------------------------------------------------- step bodies


def _at(stack: dict, i):
    """Layer ``i`` (traced) of a kind's stacked tensors."""
    return jax.tree.map(lambda a: a[i], stack)


def _layers(cfg: Config, params: dict, x, state, live, conv_layer,
            attn_layer):
    """Every layer in its order, as ONE ``lax.scan`` over the model's
    layers: a trip runs ``conv_layer(lp, h, taps)`` (``taps`` the layer's
    rows of the slot state) or ``attn_layer(lp, j, h, pages)`` (pages at
    index ``j``) on the normed rows ``h``, then the dense feed-forward or
    the expert layer, each a slice of its kind's stack (the experts'
    stack as it is). Returns ``x``, the state and the expert layers'
    counts (``experts.moe``) summed over the layers.

    The slot state does NOT go through the mixers' ``lax.cond``: a trip
    takes one layer's rows out of it (1 MB of 19 at the published size)
    and puts them back behind the cond, whichever mixer ran (an attention
    trip puts back what it took). Handed whole, the array is small enough
    for XLA to stage ALL of it through VMEM around the kernel's every
    call and to copy it in the cond's other branch: 24 x 38 MB a step
    (the described-chip compile, tests/test_tpu_compile.py)."""
    none = jnp.zeros((3,), jnp.int32)

    def attn_mixer(h, m, pages, taps):
        y, pages = attn_layer(_at(params["attn"], m), m, h, pages)
        return y, pages, taps

    def conv_mixer(h, m, pages, taps):
        y, taps = conv_layer(_at(params["conv"], m), h, taps)
        return y, pages, taps

    def dense_ffn(h, i):
        with jax.named_scope("dense_mlp"):
            w = _at(params["dense"], i)
            return common.swiglu(h, w["mlp_gate"], w["mlp_up"],
                                 w["mlp_down"]), none

    def moe_ffn(h, i):
        return experts.moe(cfg, params["moe"], h, live, i)

    def body(carry, xs):
        norms, (is_attn, m, is_dense, f) = xs
        x, pages, conv, held = carry
        h = rms_norm(x, norms["operator_norm"], cfg.eps)
        # ``m`` counts the trip's layer among its kind: on an attention
        # trip it names a convolution layer too, whose rows come back as
        # they were
        taps = jax.lax.dynamic_index_in_dim(conv, m, 0, keepdims=False)
        y, pages, taps = jax.lax.cond(is_attn > 0, attn_mixer, conv_mixer,
                                      h, m, pages, taps)
        conv = jax.lax.dynamic_update_index_in_dim(conv, taps, m, 0)
        x = x + y
        h = rms_norm(x, norms["ffn_norm"], cfg.eps)
        y, n = jax.lax.cond(is_dense > 0, dense_ffn, moe_ffn, h, f)
        return (x + y, pages, conv, held + n), None

    sched = jnp.asarray(cfg.schedule, jnp.int32)
    carry = (x, state["pages"], state["conv"], none)
    (x, pages, conv, held), _ = jax.lax.scan(
        body, carry, (params["norms"], tuple(sched[:, i] for i in range(4))))
    return x, {"pages": pages, "conv": conv}, held


def chunk_key_blocks(cfg: Config, seg, n_prefix: int, n_cont: int,
                     prefix_pages: int, cont_pages: int,
                     page_tokens: int) -> list:
    """What the engine counts a chunk's key blocks by: per kind of layer
    whose chunks run the chunk kernel ``(its name, its layers, the classes
    of one layer's call)`` for a chunk of segments ``seg`` (numpy) over a
    prefix of ``prefix_pages`` pages and ``cont_pages`` continued ones."""
    return [("attn", len(cfg.attn_ids), attention.chunk_key_blocks(
        cfg, seg, n_prefix, n_cont, prefix_pages, cont_pages * page_tokens,
        page_tokens))]


def prefill_chunk(cfg: Config, params: dict, state, tokens, seg, pos,
                  dest_page, dest_off, prefix_pages, n_prefix, cont_pages,
                  n_cont, last_idx, seg_from, seg_to):
    """A packed chunk of new tokens through every layer. Attention layers
    write the tokens' rows (keys rotated to ``pos``) to
    ``state["pages"][layer, dest_page, dest_off]``; convolution layers
    start segment ``s`` from slot-state row ``seg_from[s]`` (the
    snapshot's for a new sequence, the slot's own for a prompt that
    continues) and leave its carried inputs in row ``seg_to[s]``. Returns
    the state, the logits rows ``last_idx`` as ``(top, ids)`` and the
    expert layers' counts (``experts.moe``)."""

    def conv_layer(lp, h, taps):
        with jax.named_scope("conv"):
            y, conv_end = conv_prefill(
                cfg, lp, h, seg, taps[seg_from].reshape(len(seg_from), -1))
            taps = taps.at[seg_to].set(conv_end.reshape(-1, *taps.shape[1:]))
        return y, taps

    def attn_layer(lp, j, h, pages):
        with jax.named_scope("attn"):
            q, kv = attention.qkv(cfg, lp, h, pos)
            y = attention.attn_prefill(
                cfg, lp, q, kv, seg,
                common.layer_page_rows(pages, j, prefix_pages), n_prefix,
                common.layer_page_rows(pages, j, cont_pages), n_cont)
            pages = pages.at[j, dest_page, dest_off].set(kv)
        return y, pages

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    x, state, held = _layers(cfg, params, x, state, seg >= 0, conv_layer,
                             attn_layer)
    _, top, ids = head(cfg, params, x[last_idx])
    return state, top, ids, held


def decode_tokens(cfg: Config, params: dict, state, tokens, pos, page_table,
                  ctx_len, dest_page, dest_off, live, prefix_pages, n_prefix,
                  slot):
    """One token per row. Convolution layers move row ``slot[b]`` of the
    slot state on IN PLACE for every ``live`` row (``conv_decode``; a row
    that carries no sequence names the null row and leaves it as it was);
    attention layers write the row's ``[k ; v]`` (the key rotated to
    ``pos``) to its page and attend to the shared prefix (read once for
    all rows) and, through the table of its OWN pages, to its ``ctx_len``
    own cached rows."""

    def conv_layer(lp, h, taps):
        with jax.named_scope("conv"):
            return conv_decode(cfg, lp, h, slot, live, taps)

    def attn_layer(lp, j, h, pages):
        with jax.named_scope("attn"):
            q, kv = attention.qkv(cfg, lp, h, pos)
            pages = pages.at[j, dest_page, dest_off].set(kv)
            y = attention.attn_decode(
                cfg, lp, q, pages, j, page_table, ctx_len,
                common.layer_page_rows(pages, j, prefix_pages), n_prefix)
        return y, pages

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    x, state, held = _layers(cfg, params, x, state, live, conv_layer,
                             attn_layer)
    _, top, ids = head(cfg, params, x)
    return state, top, ids, held
