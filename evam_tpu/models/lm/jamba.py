"""Jamba in plain JAX: Mamba-1 layers with a few attention layers between,
whole on one chip.

Layer ``i`` is an ATTENTION layer where ``i % attn_layer_period ==
attn_layer_offset`` and a MAMBA layer otherwise (28 layers, period 14,
offset 7: attention at 7 and 21). Every layer is pre-norm residual with a
dense SwiGLU feed-forward behind its mixer (``num_experts`` 1); the head
is the embedding (tied).

* **Mamba mixer.** ``[u, z] = W_in h``; a causal depthwise convolution of
  ``d_conv`` taps with bias, then silu; ``[d, B, C] = W_x u``, each
  RMS-normed (Jamba's addition to Mamba-1); ``dt = softplus(W_dt d +
  b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t
  * u_t) (x) B_t``; ``y_t = s_t . C_t + D * u_t``; out = ``W_out (y *
  silu(z))``. Between steps a sequence carries, per Mamba layer, the
  float32 state ``s`` [d_state, d_inner] and the last ``d_conv - 1``
  inputs of the convolution: per SLOT of the generate engine, not per
  page (``state_shapes``). A prefill chunk runs the recurrence in a Pallas
  kernel with the state in VMEM (ops/pallas_selective_scan.py); a decode
  step's one-token update is a second kernel over the step's rows that
  moves each row's state in place (``mamba_decode``).
* **Attention mixer**: models/lm/attention.py (the module
  models/lm/lfm2_moe.py calls too), here with ``num_attention_heads``
  query heads over ONE key-value head, no positional term and no head
  norms. The cache row of a token is ``[k ; v]`` in pages
  (engine/pages.py), in the attention layers only.

What it shares with the other families is in models/lm/common.py (the
packed convolution's inputs among it); the latent attention
(models/lm/mla.py) and the expert layer (models/lm/experts.py) are other
families' and are not called here.

bfloat16 weights and activations; the recurrence, ``dt`` and the state in
float32. ``d_inner`` lies on the lanes of every array: ``conv_w`` [d_conv,
d_inner] and ``A_log`` [d_state, d_inner] are the published tensors
transposed. The Mamba layers are identical, so their weights are STACKED
and ONE ``lax.scan`` runs over them; an attention layer runs inside that
loop, under a ``lax.cond``, in the trip of the Mamba layer it precedes: one
loop body a program instead of 28 layers written out (a ninth of the
compile time, and one name for the scan kernel in a device trace).

Weights (``common.tensor_key``): ``normal * initializer_range``, gains and
``D`` ``1 + that``; ``conv_w`` uniform in +-d_conv^-1/2; ``A_log =
log(1..d_state)`` per channel plus ``normal * initializer_range``;
``b_dt`` the inverse softplus of a step size drawn log-uniformly from
[0.001, 0.1] (Mamba's own initialisation: a state that neither dies in ten
tokens nor never forgets).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from evam_tpu.models.lm import attention, common
from evam_tpu.models.lm.common import BF16, F32, GLOBAL_LAYER, rms_norm
from evam_tpu.models.lm.common import mm as _mm
from evam_tpu.ops import pallas_selective_scan, slot_rows

DT_MIN, DT_MAX = 0.001, 0.1
#: the packer may start a segment at any token of a chunk
SEGMENT_ALIGN = 1


@dataclass(frozen=True)
class Config:
    hidden: int
    inter: int
    layers: int
    attn_period: int
    attn_offset: int
    heads: int
    d_inner: int
    d_state: int
    dt_rank: int
    d_conv: int
    eps: float
    vocab: int          # rows of the vocabulary held here
    seed: int
    init_range: float

    #: what models/lm/attention.py reads beside the fields
    kv_heads = 1
    rope_theta = None   # the published config has no positional term
    rope = None
    window = None
    chunk_kernel = False

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        if (d["num_experts"] != 1 or d["num_key_value_heads"] != 1
                or not d["tie_word_embeddings"] or not d["mamba_conv_bias"]
                or d["mamba_proj_bias"] or d["attn_layer_period"] < 2
                or (d["num_hidden_layers"] - 1) % d["attn_layer_period"]
                == d["attn_layer_offset"]):
            raise ValueError(
                "the jamba family is written for a dense feed-forward, one "
                "key-value head, a tied head, a biased convolution, "
                "unbiased projections, and attention layers that each "
                "have a Mamba layer behind them")
        return cls(
            hidden=d["hidden_size"], inter=d["intermediate_size"],
            layers=d["num_hidden_layers"],
            attn_period=d["attn_layer_period"],
            attn_offset=d["attn_layer_offset"],
            heads=d["num_attention_heads"],
            d_inner=d["mamba_expand"] * d["hidden_size"],
            d_state=d["mamba_d_state"], dt_rank=d["mamba_dt_rank"],
            d_conv=d["mamba_d_conv"], eps=d["rms_norm_eps"],
            vocab=d["vocab_held"], seed=d["weights_seed"],
            init_range=d["initializer_range"])

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def attn_layers(self) -> tuple[int, ...]:
        return tuple(i for i in range(self.layers)
                     if i % self.attn_period == self.attn_offset)

    @property
    def mamba_ids(self) -> tuple[int, ...]:
        """The Mamba layers' model layer indices, in order."""
        return tuple(i for i in range(self.layers)
                     if i not in self.attn_layers)

    @property
    def attn_before(self) -> tuple[int, ...]:
        """Per Mamba layer, in order: which attention layer (its index
        among them) sits right before it, -1 where none does."""
        return tuple(
            self.attn_layers.index(i - 1) if i - 1 in self.attn_layers
            else -1 for i in self.mamba_ids)

    @property
    def mamba_layers(self) -> int:
        return self.layers - len(self.attn_layers)

    @property
    def kv_width(self) -> int:
        """Values a page row holds: one key and one value."""
        return attention.kv_width(self)


# --------------------------------------------------------------- weights


def mlp_shapes(cfg: Config) -> dict[str, tuple]:
    h = cfg.hidden
    return {"in_norm": (h,), "ff_norm": (h,), "ff_gate": (h, cfg.inter),
            "ff_up": (h, cfg.inter), "ff_down": (cfg.inter, h)}


def mamba_shapes(cfg: Config) -> dict[str, tuple]:
    h, c, n, r = cfg.hidden, cfg.d_inner, cfg.d_state, cfg.dt_rank
    return {**mlp_shapes(cfg), "in_proj": (h, 2 * c),
            "conv_w": (cfg.d_conv, c), "conv_b": (c,),
            "x_proj": (c, r + 2 * n), "dt_norm": (r,), "b_norm": (n,),
            "c_norm": (n,), "dt_proj": (r, c), "dt_bias": (c,),
            "A_log": (n, c), "D": (c,), "out_proj": (c, h)}


def attn_shapes(cfg: Config) -> dict[str, tuple]:
    return {**mlp_shapes(cfg),
            **attention.tensor_shapes(cfg, head_norms=False)}


def _tensor(key, kind: str, shape: tuple, std: float):
    """One tensor from its key, by the rule of its ``kind``."""
    if kind == "conv_w":
        bound = shape[0] ** -0.5
        w = jax.random.uniform(key, shape, F32, -bound, bound)
    elif kind == "dt_bias":
        w = common.step_bias(key, shape, DT_MIN, DT_MAX)
    else:
        w = jax.random.normal(key, shape, F32) * std
        if kind == "A_log":
            w = w + jnp.log(jnp.arange(1, shape[0] + 1, dtype=F32))[:, None]
        elif kind == "gain":
            w = 1.0 + w
    return w.astype(BF16)


#: compiled once per kind and shape, whatever the name and the layer
_make_one = jax.jit(_tensor, static_argnums=(1, 2, 3))


def _kind(name: str) -> str:
    if name in ("conv_w", "dt_bias", "A_log"):
        return name
    return "gain" if name.endswith("norm") or name == "D" else "normal"


def make_tensor(cfg: Config, layer: int, name: str, shape: tuple):
    return _make_one(common.tensor_key(cfg.seed, layer, name), _kind(name),
                     shape, cfg.init_range)


def make_layers(cfg: Config, layers, shapes: dict) -> dict:
    """The tensors of ``layers`` (model layer indices), each name's
    stacked on a leading axis."""
    return {name: jnp.stack([make_tensor(cfg, i, name, shape)
                             for i in layers])
            for name, shape in shapes.items()}


def make_params(cfg: Config) -> dict:
    return {
        "embed": make_tensor(cfg, GLOBAL_LAYER, "embed",
                             (cfg.vocab, cfg.hidden)),
        "final_norm": make_tensor(cfg, GLOBAL_LAYER, "final_norm",
                                  (cfg.hidden,)),
        "mamba": make_layers(cfg, cfg.mamba_ids, mamba_shapes(cfg)),
        "attn": make_layers(cfg, cfg.attn_layers, attn_shapes(cfg)),
    }


def param_count(cfg: Config) -> int:
    n = cfg.vocab * cfg.hidden + cfg.hidden
    n += cfg.mamba_layers * sum(
        math.prod(s) for s in mamba_shapes(cfg).values())
    n += len(cfg.attn_layers) * sum(
        math.prod(s) for s in attn_shapes(cfg).values())
    return n


def state_shapes(cfg: Config, n_pages: int, page_tokens: int,
                 slots: int) -> dict:
    """The device state of this family's sequences. ``pages``: key and
    value rows of the attention layers. Per SLOT (and two rows more: row
    ``slots`` for rows of a step that carry no sequence, row ``slots +
    1`` the snapshot after the shared prefix's last token) and Mamba
    layer: ``ssm``, the float32 state, and ``conv``, the convolution's
    last ``d_conv - 1`` inputs (taps side by side), each slot's row as
    whole bfloat16 tiles (``slot_rows.tiled``). A decode step's kernel
    addresses both by ``[layer, slot]`` and moves the rows it names in
    place (``mamba_decode``)."""
    rows = slots + 2
    return {
        "pages": jax.ShapeDtypeStruct(
            (len(cfg.attn_layers), n_pages, page_tokens, cfg.kv_width), BF16),
        "ssm": jax.ShapeDtypeStruct(
            (cfg.mamba_layers, rows, cfg.d_state, cfg.d_inner), F32),
        "conv": jax.ShapeDtypeStruct(
            (cfg.mamba_layers, rows,
             *slot_rows.tiled((cfg.d_conv - 1) * cfg.d_inner)), BF16),
    }


# ---------------------------------------------------------------- layers


def _feed_forward(cfg: Config, lp: dict, x):
    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["ff_norm"], cfg.eps)
        return x + common.swiglu(h, lp["ff_gate"], lp["ff_up"], lp["ff_down"])


def _dt_b_c(cfg: Config, lp: dict, u):
    """From the convolved input: the float32 step size [T, d_inner] and
    the normed ``B``, ``C`` [T, d_state]."""
    r, n = cfg.dt_rank, cfg.d_state
    dbc = _mm(u, lp["x_proj"])
    delta = rms_norm(dbc[:, :r], lp["dt_norm"], cfg.eps)
    b = rms_norm(dbc[:, r:r + n], lp["b_norm"], cfg.eps)
    c = rms_norm(dbc[:, r + n:], lp["c_norm"], cfg.eps)
    dt = jax.nn.softplus(
        jnp.dot(delta, lp["dt_proj"], preferred_element_type=F32)
        + lp["dt_bias"].astype(F32))
    return dt, b, c


def mamba_prefill(cfg: Config, lp: dict, x, seg, conv0, h0):
    """A packed chunk through one Mamba mixer. ``conv0`` [S, (d_conv-1) *
    d_inner] and ``h0`` [S, d_state, d_inner]: what each segment starts
    from. Returns the mixer's output [T, hidden] and each segment's
    convolution inputs and state after its last token here."""
    c, k1 = cfg.d_inner, cfg.d_conv - 1
    uz = _mm(rms_norm(x, lp["in_norm"], cfg.eps), lp["in_proj"])
    u_pre, z = uz[:, :c], uz[:, c:]
    taps, conv_end = common.packed_conv_inputs(u_pre, seg, conv0, k1)
    u = common.conv_silu(lp["conv_w"], lp["conv_b"], taps + [u_pre])
    dt, b, cc = _dt_b_c(cfg, lp, u)
    a = -jnp.exp(lp["A_log"].astype(F32))
    scan = (pallas_selective_scan.selective_scan if common.on_tpu()
            else pallas_selective_scan.selective_scan_xla)
    y, h_end = scan(u, dt, z, b, cc, a, lp["D"], seg, h0)
    return _mm(y.astype(BF16), lp["out_proj"]), conv_end, h_end


def mamba_decode(cfg: Config, lp: dict, l, x, slot, live, conv_all, ssm):
    """One token per row through Mamba mixer ``l``, each live row's slot
    state moved IN PLACE: ``slot`` [B] names row ``b``'s row of
    ``conv_all`` [layers, R, *tile] (``state_shapes``) and ``ssm`` [layers,
    R, d_state, d_inner] float32, the WHOLE arrays. Returns the output
    [B, hidden] and both arrays, the rows that ``live`` rows name moved
    on by their token, every other row as it was. On the chip the
    recurrence is the Pallas kernel ``ssm_decode_rows`` over the step's
    rows, the layer and the slot ids its prefetched scalars and the
    state aliased in and out (ops/pallas_selective_scan.py
    ``decode_rows``, ops/slot_rows.py); elsewhere its twin gathers the
    rows and puts them back. A row that carries no sequence names the
    null row, writes back what it read and comes out zero. The
    convolution's rows (30 KB each) are gathered through XLA before the
    mixer's inputs can be made, and written by the same kernel."""
    c = cfg.d_inner
    uz = _mm(rms_norm(x, lp["in_norm"], cfg.eps), lp["in_proj"])
    u_pre, z = uz[:, :c], uz[:, c:]
    conv_old = conv_all[l, slot].reshape(x.shape[0], -1)
    taps = [conv_old[:, k * c:(k + 1) * c] for k in range(cfg.d_conv - 1)]
    conv_new = jnp.concatenate([conv_old[:, c:], u_pre], axis=1)
    u = common.conv_silu(lp["conv_w"], lp["conv_b"], taps + [u_pre])
    dt, b, cc = _dt_b_c(cfg, lp, u)
    a = -jnp.exp(lp["A_log"].astype(F32))
    rows = (pallas_selective_scan.decode_rows if common.on_tpu()
            else pallas_selective_scan.decode_rows_xla)
    y, ssm, conv_all = rows(
        l, slot, live, dt, u, z, b, cc, a, lp["D"],
        conv_new.reshape(-1, *conv_all.shape[2:]), ssm, conv_all)
    return _mm(y.astype(BF16), lp["out_proj"]), conv_all, ssm


def _qkv(cfg: Config, lp: dict, x):
    """Per token: the queries [T, heads, head_dim] and the cache row
    ``[k ; v]``."""
    return attention.qkv(cfg, lp, rms_norm(x, lp["in_norm"], cfg.eps))


def head(cfg: Config, params: dict, x):
    return common.head(x, params["final_norm"], cfg.eps, params["embed"],
                       tied=True)


# ----------------------------------------------------------- step bodies


def _layers(cfg: Config, params: dict, x, state, mamba_layer, attn_layer):
    """Every layer in its order, as ONE ``lax.scan`` over the stacked
    Mamba layers: ``mamba_layer(lp, l, x, ssm, conv)`` with ``l`` the
    layer's row of the slot state. A trip whose Mamba layer has an
    attention layer right before it runs that first
    (``attn_layer(lp, j, x, pages)``, weights and pages at index ``j``)."""
    attn = params["attn"]

    def body(carry, xs):
        lp, l, j = xs
        x, pages, ssm, conv = carry
        x, pages = jax.lax.cond(
            j >= 0,
            lambda x, pages: attn_layer(
                jax.tree.map(lambda a: a[j], attn), j, x, pages),
            lambda x, pages: (x, pages), x, pages)
        x, ssm, conv = mamba_layer(lp, l, x, ssm, conv)
        return (x, pages, ssm, conv), None

    n = cfg.mamba_layers
    carry = (x, state["pages"], state["ssm"], state["conv"])
    (x, pages, ssm, conv), _ = jax.lax.scan(
        body, carry, (params["mamba"], jnp.arange(n, dtype=jnp.int32),
                      jnp.asarray(cfg.attn_before, jnp.int32)))
    return x, {"pages": pages, "ssm": ssm, "conv": conv}


def prefill_chunk(cfg: Config, params: dict, state, tokens, seg, pos,
                  dest_page, dest_off, prefix_pages, n_prefix, cont_pages,
                  n_cont, last_idx, seg_from, seg_to):
    """A packed chunk of new tokens through every layer. Attention
    layers write the tokens' rows to ``state["pages"][layer, dest_page,
    dest_off]``; Mamba layers start segment ``s`` from slot-state row
    ``seg_from[s]`` (the snapshot's for a new sequence, the slot's own
    for a prompt that continues) and leave its end state in row
    ``seg_to[s]``. Returns the state, the logits rows ``last_idx`` as
    ``(top, ids)`` and ``[0, 0, 0]`` (no held experts). ``pos`` is not used: no
    layer has a positional term."""

    def mamba_layer(lp, l, x, ssm, conv):
        with jax.named_scope("mamba"):
            y, conv_end, h_end = mamba_prefill(
                cfg, lp, x, seg, conv[l, seg_from].reshape(len(seg_from), -1),
                ssm[l, seg_from])
            ssm = ssm.at[l, seg_to].set(h_end)
            conv = conv.at[l, seg_to].set(
                conv_end.reshape(-1, *conv.shape[2:]))
            x = x + y
        return _feed_forward(cfg, lp, x), ssm, conv

    def attn_layer(lp, j, x, pages):
        with jax.named_scope("attn"):
            q, kv = _qkv(cfg, lp, x)
            x = x + attention.attn_prefill(
                cfg, lp, q, kv, seg, common.page_rows(pages[j], prefix_pages),
                n_prefix, common.page_rows(pages[j], cont_pages), n_cont)
            pages = pages.at[j, dest_page, dest_off].set(kv)
        return _feed_forward(cfg, lp, x), pages

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    x, state = _layers(cfg, params, x, state, mamba_layer, attn_layer)
    _, top, ids = head(cfg, params, x[last_idx])
    return state, top, ids, jnp.zeros((3,), jnp.int32)


def decode_tokens(cfg: Config, params: dict, state, tokens, pos, page_table,
                  ctx_len, dest_page, dest_off, live, prefix_pages, n_prefix,
                  slot):
    """One token per row. Mamba layers move row ``slot[b]`` of the slot
    state on IN PLACE for every ``live`` row (``mamba_decode``; a row that
    carries no sequence names the null row and leaves it as it was);
    attention layers write the row's ``[k ; v]`` to its page and attend
    to the shared prefix (read once for all rows) and, through the
    table of its OWN pages, to its ``ctx_len`` own cached rows."""
    b = tokens.shape[0]

    def mamba_layer(lp, l, x, ssm, conv):
        with jax.named_scope("mamba"):
            y, conv, ssm = mamba_decode(cfg, lp, l, x, slot, live, conv, ssm)
            x = x + y
        return _feed_forward(cfg, lp, x), ssm, conv

    def attn_layer(lp, j, x, pages):
        with jax.named_scope("attn"):
            q, kv = _qkv(cfg, lp, x)
            pages = pages.at[j, dest_page, dest_off].set(kv)
            x = x + attention.attn_decode(
                cfg, lp, q, pages, j, page_table, ctx_len,
                common.layer_page_rows(pages, j, prefix_pages), n_prefix)
        return _feed_forward(cfg, lp, x), pages

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    x, state = _layers(cfg, params, x, state, mamba_layer, attn_layer)
    _, top, ids = head(cfg, params, x)
    return state, top, ids, jnp.zeros((3,), jnp.int32)
