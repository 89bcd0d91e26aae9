"""Nemotron-H in plain JAX: blocks that are each ONE sublayer, Mamba-2, a
latent expert layer or attention, by a pattern string; one chip's share of
a stage whose blocks are each divided over several chips.

``hybrid_override_pattern`` names block ``i``: ``M`` a Mamba-2 mixer, ``E``
the expert layer, ``*`` attention. Every block is ``x <- x + f(RMSNorm(x;
norm_i))``, the residual bfloat16; a final RMSNorm and an untied head.

* **Mamba-2 mixer** (``mamba_num_heads`` H heads of ``mamba_head_dim`` P
  channels, a state ``ssm_state_size`` N, ``n_groups`` G groups of heads
  that share ``B`` and ``C``). ``[z | xBC] = W_in u``, ``dt = W_dt u`` (the
  published ``in_proj``'s columns ``z | xBC | dt``, kept as two tensors so
  that ``dt`` leaves its product in float32); ``xBC <- silu(conv4(xBC) +
  b_conv)``, a causal depthwise convolution over its ``H P + 2 G N``
  channels, split into ``x | B | C``; ``delta = softplus(dt + dt_bias)``,
  ``a = -exp(A_log)`` (ONE decay a head); per head ``h_t = exp(delta_t a)
  h_{t-1} + delta_t x_t (x) B_t``, ``y_t = h_t C_t + D x_t`` (float32); ``y
  <- GroupRMSNorm(y * silu(z))`` (the gate BEFORE the norm, each of the G
  groups of ``H P / G`` channels normalised alone, one gain a channel);
  out = ``W_out y``. No positional term: the recurrence carries order.
  Between steps a sequence carries, per Mamba layer, the float32 state
  (``[H/2, N, 2P]``: two heads side by side on the lanes,
  ops/pallas_ssd.py) and the convolution's last ``conv_kernel - 1``
  inputs: per SLOT of the generate engine, not per page
  (``state_shapes``). A prefill chunk runs the recurrence in its chunkwise
  dual form in a Pallas kernel (``ssd_chunk_scan``: products over blocks of
  128 tokens, the state in VMEM between them, segments of a packed chunk
  kept apart inside a block: ``SEGMENT_ALIGN`` 1); a decode step's
  one-token update is a second kernel over the step's rows that moves each
  row's 4 MB of state in place (``ssd_decode_rows``).
* **Attention**: models/lm/attention.py as one ``Kind``,
  ``num_attention_heads`` query heads over ``num_key_value_heads`` of
  ``head_dim`` (32 over 2: groups of 16), no bias, no head norms, no gate
  and NO positional term (the family's published description; the config's
  ``rope_theta`` is not read). The cache row of a token is ``[k ; v]`` in
  pages (engine/pages.py), in the attention layers only.
* **Expert layer**: models/lm/experts.py with ``moe_latent`` and
  ``expert_act`` ``relu2``: sigmoid scores over all ``n_routed_experts``,
  the ``num_experts_per_tok`` chosen by ``s + router_bias``, weighted by
  ``s`` renormalised times ``routed_scaling_factor``; tokens projected once
  to ``moe_latent_size``, the held experts ``W2 max(W1 l, 0)^2`` there, the
  weighted sum projected back; a shared expert of the same form on the
  hidden. A held RANGE of experts ``[held_lo, held_lo + experts_held)``.

The pattern this is written for is ``(M *? E)+``: every Mamba-2 block has,
behind an attention block or none, an expert layer behind it (the whole
published pattern is that). So the Mamba-2 mixers and the expert layers are
STACKED and ONE ``lax.scan`` runs over the pairs: a trip is a Mamba-2 block,
under a ``lax.cond`` the attention block where one follows, and the expert
layer, its tensors one stack that the grouped products read in place
(ops/pallas_grouped.py, the layer a prefetched scalar). Each kernel has one
name in a device trace (``ssd_chunk_scan``, ``ssd_decode_rows``,
``expert_up``, ``expert_down``), and the slot state is no operand of the
``cond``.

bfloat16 weights and activations; the scores, ``delta``, the decay, the
recurrence and the state float32. Weights (``common.tensor_key``): ``normal
* initializer_range``; gains and ``D`` ``1 + that``; the convolution
uniform in +-conv_kernel^-1/2; ``A_log = log U(1, mamba_a_init_max)`` a
head; ``dt_bias`` the inverse softplus of a step drawn log-uniformly from
[``time_step_min``, ``mamba_dt_init_max``] (Mamba-2's own initialisation,
whose ranges, 16 and ``time_step_max``, a config that names neither key
gets; ``time_step_floor`` lies under ``time_step_min`` and never binds);
``router_bias`` as any tensor (small and not zero, so that
selection and weighting differ); the attention's ``q`` and ``k``
``attn_qk_init_scale`` times as wide (``benchmark/configs/
nemotron3_super_ep8.json`` ``assumed`` says what that is for).
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from evam_tpu.models.lm import attention, common, experts
from evam_tpu.models.lm.common import BF16, F32, GLOBAL_LAYER, rms_norm
from evam_tpu.models.lm.common import mm as _mm
from evam_tpu.ops import pallas_ssd, slot_rows

A_MAX = 16.0
#: the packer may start a segment at any token of a chunk
SEGMENT_ALIGN = 1
MAMBA, MOE, ATTN = "M", "E", "*"


@dataclass(frozen=True)
class Config:
    hidden: int
    pattern: str        # one letter a block, cut to the blocks held
    m_heads: int
    m_dim: int
    d_state: int
    m_groups: int
    d_conv: int
    attn: attention.Kind
    moe_inter: int
    moe_latent: int
    n_experts: int      # the router's outputs
    n_held: int
    held_lo: int
    n_shared: int
    top_k: int
    routed_scale: float
    norm_topk: bool
    eps: float
    dt_min: float       # the seeded steps: log-uniform in
    dt_init_max: float  # [dt_min, dt_init_max]
    a_init_max: float   # the seeded decays: uniform in [1, a_init_max]
    vocab: int          # rows of the vocabulary held here
    seed: int
    init_range: float
    qk_init_scale: float    # the attention's q and k: init_range times it

    #: what models/lm/experts.py reads beside the fields
    score_func = "sigmoid"
    expert_act = "relu2"
    scale_routed = True
    topk_eps = 1e-20
    n_group = 1
    topk_group = 1

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        n = d["num_hidden_layers"]
        pattern = d["hybrid_override_pattern"][:n]
        shared = d["moe_shared_expert_intermediate_size"]
        width = d["mamba_num_heads"] * d["mamba_head_dim"]
        if (len(pattern) != n or not re.fullmatch(r"(M\*?E)+", pattern)
                or d["n_group"] != 1 or d["mlp_hidden_act"] != "relu2"
                or d["mamba_hidden_act"] != "silu" or not d["use_conv_bias"]
                or d["use_bias"] or d["mamba_proj_bias"] or d["mlp_bias"]
                or d["attention_bias"] or d["tie_word_embeddings"]
                or not d["moe_latent_size"] or d["residual_in_fp32"]
                or shared % d["moe_intermediate_size"]
                or d["mamba_num_heads"] % (2 * d["n_groups"])
                or width % d["n_groups"]
                or d["num_attention_heads"] % d["num_key_value_heads"]):
            raise ValueError(
                "the nemotron_h family is written for blocks (M *? E)+ (a "
                "Mamba-2 mixer, attention behind it or none, the expert "
                "layer), relu^2 experts in a latent under one routing group, "
                "a shared expert a multiple of an expert wide, a biased "
                "convolution and no other bias, pairs of heads within a "
                "group, a bfloat16 residual and an untied head")
        return cls(
            hidden=d["hidden_size"], pattern=pattern,
            m_heads=d["mamba_num_heads"], m_dim=d["mamba_head_dim"],
            d_state=d["ssm_state_size"], m_groups=d["n_groups"],
            d_conv=d["conv_kernel"],
            attn=attention.Kind(
                hidden=d["hidden_size"], heads=d["num_attention_heads"],
                kv_heads=d["num_key_value_heads"], head_dim=d["head_dim"],
                eps=d["layer_norm_epsilon"]),
            moe_inter=d["moe_intermediate_size"],
            moe_latent=d["moe_latent_size"],
            n_experts=d["n_routed_experts"], n_held=d["experts_held"],
            held_lo=d["held_lo"],
            n_shared=shared // d["moe_intermediate_size"],
            top_k=d["num_experts_per_tok"],
            routed_scale=float(d["routed_scaling_factor"]),
            norm_topk=bool(d["norm_topk_prob"]),
            eps=d["layer_norm_epsilon"], dt_min=d["time_step_min"],
            dt_init_max=d.get("mamba_dt_init_max", d["time_step_max"]),
            a_init_max=d.get("mamba_a_init_max", A_MAX),
            vocab=d["vocab_held"], seed=d["weights_seed"],
            init_range=d["initializer_range"],
            qk_init_scale=float(d["attn_qk_init_scale"]))

    def _ids(self, kind: str) -> tuple:
        return tuple(i for i, k in enumerate(self.pattern) if k == kind)

    @property
    def mamba_ids(self) -> tuple:
        """The Mamba-2 blocks' indices in the model, in order."""
        return self._ids(MAMBA)

    @property
    def attn_ids(self) -> tuple:
        return self._ids(ATTN)

    @property
    def moe_ids(self) -> tuple:
        """The blocks that are the expert layer, in order."""
        return self._ids(MOE)

    @property
    def attn_after(self) -> tuple:
        """Per Mamba-2 block: the attention block (its index among them)
        right behind it, -1 where none is."""
        return tuple(self.attn_ids.index(i + 1) if i + 1 in self.attn_ids
                     else -1 for i in self.mamba_ids)

    @property
    def d_inner(self) -> int:
        return self.m_heads * self.m_dim

    @property
    def bc_width(self) -> int:
        """``B`` or ``C`` of a token: a state's worth a group."""
        return self.m_groups * self.d_state

    @property
    def conv_width(self) -> int:
        """The convolution's channels: ``x | B | C``."""
        return self.d_inner + 2 * self.bc_width

    @property
    def kv_width(self) -> int:
        return attention.kv_width(self.attn)


# --------------------------------------------------------------- weights


def mamba_shapes(cfg: Config) -> dict[str, tuple]:
    h, c, w = cfg.hidden, cfg.d_inner, cfg.conv_width
    return {"norm": (h,), "in_proj": (h, c + w), "dt_proj": (h, cfg.m_heads),
            "conv_w": (cfg.d_conv, w), "conv_b": (w,),
            "dt_bias": (cfg.m_heads,), "A_log": (cfg.m_heads,),
            "D": (cfg.m_heads,), "gate_norm": (c,), "out_proj": (c, h)}


def attn_shapes(cfg: Config) -> dict[str, tuple]:
    return {"norm": (cfg.hidden,),
            **attention.tensor_shapes(cfg.attn, head_norms=False)}


def moe_shapes(cfg: Config) -> dict[str, tuple]:
    return {"norm": (cfg.hidden,), **experts.tensor_shapes(cfg, bias=True)}


def _kind(name: str) -> str:
    if name in ("conv_w", "dt_bias", "A_log"):
        return name
    return "gain" if name.endswith("norm") or name == "D" else "normal"


def _tensor(key, kind: str, shape: tuple, std: float, draw: tuple):
    """One tensor from its key, by the rule of its ``kind``; ``draw``:
    the config's ``(dt_min, dt_init_max, a_init_max)``."""
    if kind == "conv_w":
        bound = shape[0] ** -0.5
        w = jax.random.uniform(key, shape, F32, -bound, bound)
    elif kind == "dt_bias":
        w = common.step_bias(key, shape, *draw[:2])
    elif kind == "A_log":
        w = jnp.log(jax.random.uniform(key, shape, F32, 1.0, draw[2]))
    else:
        w = jax.random.normal(key, shape, F32) * std
        if kind == "gain":
            w = 1.0 + w
    return w.astype(BF16)


#: compiled once per kind and shape, whatever the name and the layer
_make_one = jax.jit(_tensor, static_argnums=(1, 2, 3, 4))


def make_tensor(cfg: Config, layer: int, name: str, shape: tuple,
                wider: bool = False):
    return _make_one(
        common.tensor_key(cfg.seed, layer, name), _kind(name), shape,
        cfg.init_range * (cfg.qk_init_scale if wider else 1.0),
        (cfg.dt_min, cfg.dt_init_max, cfg.a_init_max))


def make_layers(cfg: Config, layers, shapes: dict, held=None,
                wider=()) -> dict:
    """The tensors of ``layers`` (block indices), each name's stacked on a
    leading axis; each ``expert_*`` tensor once per expert of ``held``
    (global ids) on a second (``common.make_layers``); those named in
    ``wider`` drawn ``qk_init_scale`` times as wide."""
    return common.make_layers(
        lambda i, name, shape: make_tensor(cfg, i, name, shape,
                                           name in wider),
        cfg.seed, cfg.init_range, layers, shapes, held)


def make_params(cfg: Config, held=None) -> dict:
    """``mamba``, ``attn``, ``moe``: the blocks of a kind, each with its
    norm, stacked. ``held``: the routed experts held (default: the
    config's range)."""
    if held is None:
        held = range(cfg.held_lo, cfg.held_lo + cfg.n_held)
    return {
        "embed": make_tensor(cfg, GLOBAL_LAYER, "embed",
                             (cfg.vocab, cfg.hidden)),
        "final_norm": make_tensor(cfg, GLOBAL_LAYER, "final_norm",
                                  (cfg.hidden,)),
        "head": make_tensor(cfg, GLOBAL_LAYER, "head",
                            (cfg.hidden, cfg.vocab)),
        "mamba": make_layers(cfg, cfg.mamba_ids, mamba_shapes(cfg)),
        "attn": make_layers(cfg, cfg.attn_ids, attn_shapes(cfg),
                            wider=("q", "k")),
        "moe": make_layers(cfg, cfg.moe_ids, moe_shapes(cfg), held),
    }


def param_count(cfg: Config) -> int:
    def total(shapes):
        return sum((cfg.n_held if name.startswith("expert_") else 1)
                   * math.prod(s) for name, s in shapes.items())

    return (2 * cfg.vocab * cfg.hidden + cfg.hidden
            + len(cfg.mamba_ids) * total(mamba_shapes(cfg))
            + len(cfg.attn_ids) * total(attn_shapes(cfg))
            + len(cfg.moe_ids) * total(moe_shapes(cfg)))


def state_shapes(cfg: Config, n_pages: int, page_tokens: int,
                 slots: int) -> dict:
    """The device state of this family's sequences. ``pages``: key and
    value rows of the attention layers. Per SLOT (and two rows more: row
    ``slots`` for rows of a step that carry no sequence, row ``slots +
    1`` the snapshot after the shared prefix's last token) and Mamba-2
    layer: ``ssm``, the float32 state, two heads side by side
    (ops/pallas_ssd.py: 4 MB a row at the published widths), and ``conv``,
    the convolution's last ``d_conv - 1`` inputs (taps side by side), each
    slot's row as whole bfloat16 tiles (``slot_rows.tiled``). A decode
    step's kernel addresses both by ``[layer, slot]`` and moves the rows it
    names in place (``mamba_decode``)."""
    rows = slots + 2
    n = len(cfg.mamba_ids)
    return {
        "pages": jax.ShapeDtypeStruct(
            (len(cfg.attn_ids), n_pages, page_tokens, cfg.kv_width), BF16),
        "ssm": jax.ShapeDtypeStruct(
            (n, rows, cfg.m_heads // 2, cfg.d_state, 2 * cfg.m_dim), F32),
        "conv": jax.ShapeDtypeStruct(
            (n, rows, *slot_rows.tiled((cfg.d_conv - 1) * cfg.conv_width)),
            BF16),
    }


# ---------------------------------------------------------------- layers


def _mamba_inputs(cfg: Config, lp: dict, h, taps):
    """From the normed rows ``h`` and the convolution's taps (oldest first,
    the newest the rows' own ``xBC``): ``x`` [T, H P], ``B``, ``C`` [T, G N]
    (bfloat16), the float32 step ``delta`` [T, H] and the decay rate ``a``
    [H]."""
    xbc = common.conv_silu(lp["conv_w"], lp["conv_b"], taps)
    c, g = cfg.d_inner, cfg.bc_width
    delta = jax.nn.softplus(
        jnp.dot(h, lp["dt_proj"], preferred_element_type=F32)
        + lp["dt_bias"].astype(F32))
    return (xbc[:, :c], xbc[:, c:c + g], xbc[:, c + g:], delta,
            -jnp.exp(lp["A_log"].astype(F32)))


def _mamba_out(cfg: Config, lp: dict, y, x, z):
    """``W_out GroupRMSNorm((y + D x) * silu(z))``: ``y`` [T, H P] float32
    from the recurrence, the gate before the norm, every group of ``H P /
    G`` channels normalised alone."""
    t = y.shape[0]
    d = jnp.repeat(lp["D"].astype(F32), cfg.m_dim)
    y = (y + d * x.astype(F32)) * jax.nn.silu(z.astype(F32))
    y = y.reshape(t, cfg.m_groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.eps)
    y = y.reshape(t, -1) * lp["gate_norm"].astype(F32)
    return _mm(y.astype(BF16), lp["out_proj"])


def mamba_prefill(cfg: Config, lp: dict, x, seg, conv0, h0):
    """A packed chunk through one Mamba-2 mixer. ``conv0`` [S, (d_conv-1) *
    conv_width] and ``h0`` [S, H/2, N, 2P]: what each segment starts from.
    Returns the mixer's output [T, hidden] and each segment's convolution
    inputs and state after its last token here."""
    c = cfg.d_inner
    h = rms_norm(x, lp["norm"], cfg.eps)
    zxbc = _mm(h, lp["in_proj"])
    z, pre = zxbc[:, :c], zxbc[:, c:]
    taps, conv_end = common.packed_conv_inputs(pre, seg, conv0,
                                               cfg.d_conv - 1)
    xs, b, cc, delta, a = _mamba_inputs(cfg, lp, h, taps + [pre])
    scan = (pallas_ssd.chunk_scan if common.on_tpu()
            else pallas_ssd.chunk_scan_xla)
    y, h_end = scan(xs, delta, a, b, cc, seg, h0)
    # rows of no segment are whatever the kernel's memory held
    y = jnp.where((seg >= 0)[:, None], y, 0.0)
    return _mamba_out(cfg, lp, y, xs, z), conv_end, h_end


def mamba_decode(cfg: Config, lp: dict, l, x, slot, live, conv_all, ssm):
    """One token per row through Mamba-2 mixer ``l``, each live row's slot
    state moved IN PLACE: ``slot`` [B] names row ``b``'s row of ``conv_all``
    [layers, R, *tile] (``state_shapes``) and ``ssm`` [layers, R, H/2, N,
    2P] float32, the WHOLE arrays. Returns the output [B, hidden] and both
    arrays, the rows that ``live`` rows name moved on by their token, every
    other row as it was. On the chip the recurrence is the Pallas kernel
    ``ssd_decode_rows`` over the step's rows, the layer and the slot ids
    its prefetched scalars and the state aliased in and out
    (ops/pallas_ssd.py ``decode_rows``, ops/slot_rows.py); elsewhere its
    twin gathers the rows and puts them back. A row that carries no
    sequence names the null row, writes back what it read and comes out
    zero. The convolution's rows (61 KB each) are gathered through XLA
    before the mixer's inputs can be made, and written by the same
    kernel."""
    c, w = cfg.d_inner, cfg.conv_width
    h = rms_norm(x, lp["norm"], cfg.eps)
    zxbc = _mm(h, lp["in_proj"])
    z, pre = zxbc[:, :c], zxbc[:, c:]
    conv_old = conv_all[l, slot].reshape(x.shape[0], -1)
    taps = [conv_old[:, k * w:(k + 1) * w] for k in range(cfg.d_conv - 1)]
    conv_new = jnp.concatenate([conv_old[:, w:], pre], axis=1)
    xs, b, cc, delta, a = _mamba_inputs(cfg, lp, h, taps + [pre])
    rows = (pallas_ssd.decode_rows if common.on_tpu()
            else pallas_ssd.decode_rows_xla)
    y, ssm, conv_all = rows(
        l, slot, live, delta, a, xs, b, cc,
        conv_new.reshape(-1, *conv_all.shape[2:]), ssm, conv_all)
    return _mamba_out(cfg, lp, y, xs, z), conv_all, ssm


def head(cfg: Config, params: dict, x):
    return common.head(x, params["final_norm"], cfg.eps, params["head"])


# ----------------------------------------------------------- step bodies


def _layers(cfg: Config, params: dict, x, state, live, mamba_layer,
            attn_layer):
    """Every block in its order, as ONE ``lax.scan`` over the stacked
    Mamba-2 mixers: a trip runs ``mamba_layer(lp, l, x, ssm, conv)`` (``l``
    the layer's row of the slot state), then, where an attention block
    follows, ``attn_layer(lp, j, x, pages)`` (weights and pages at index
    ``j``), then expert layer ``l`` out of the experts' stack. Returns
    ``x``, the state and the expert layers' counts (``experts.moe``)
    summed over the layers."""
    attn = params["attn"]

    def body(carry, xs):
        lp, l, j = xs
        x, pages, ssm, conv, held = carry
        x, ssm, conv = mamba_layer(lp, l, x, ssm, conv)
        x, pages = jax.lax.cond(
            j >= 0,
            lambda x, pages: attn_layer(
                jax.tree.map(lambda a: a[j], attn), j, x, pages),
            lambda x, pages: (x, pages), x, pages)
        h = rms_norm(x, params["moe"]["norm"][l], cfg.eps)
        y, n = experts.moe(cfg, params["moe"], h, live, l)
        return (x + y, pages, ssm, conv, held + n), None

    n = len(cfg.mamba_ids)
    carry = (x, state["pages"], state["ssm"], state["conv"],
             jnp.zeros((3,), jnp.int32))
    (x, pages, ssm, conv, held), _ = jax.lax.scan(
        body, carry, (params["mamba"], jnp.arange(n, dtype=jnp.int32),
                      jnp.asarray(cfg.attn_after, jnp.int32)))
    return x, {"pages": pages, "ssm": ssm, "conv": conv}, held


def chunk_key_blocks(cfg: Config, seg, n_prefix: int, n_cont: int,
                     prefix_pages: int, cont_pages: int,
                     page_tokens: int) -> list:
    """What the engine counts a chunk's key blocks by: per kind of layer
    whose chunks run the chunk kernel ``(its name, its layers, the classes
    of one layer's call)`` for a chunk of segments ``seg`` (numpy) over a
    prefix of ``prefix_pages`` pages and ``cont_pages`` continued ones."""
    return [("attn", len(cfg.attn_ids), attention.chunk_key_blocks(
        cfg.attn, seg, n_prefix, n_cont, prefix_pages,
        cont_pages * page_tokens, page_tokens))]


def prefill_chunk(cfg: Config, params: dict, state, tokens, seg, pos,
                  dest_page, dest_off, prefix_pages, n_prefix, cont_pages,
                  n_cont, last_idx, seg_from, seg_to):
    """A packed chunk of new tokens through every block. The attention
    blocks write the tokens' rows to ``state["pages"][layer, dest_page,
    dest_off]``; Mamba-2 blocks start segment ``s`` from slot-state row
    ``seg_from[s]`` (the snapshot's for a new sequence, the slot's own for
    a prompt that continues) and leave its end state in row ``seg_to[s]``.
    Returns the state, the logits rows ``last_idx`` as ``(top, ids)`` and
    the expert layers' counts (``experts.moe``). ``pos`` is not used: no
    block has a positional term."""

    def mamba_layer(lp, l, x, ssm, conv):
        with jax.named_scope("mamba2"):
            y, conv_end, h_end = mamba_prefill(
                cfg, lp, x, seg, conv[l, seg_from].reshape(len(seg_from), -1),
                ssm[l, seg_from])
            ssm = ssm.at[l, seg_to].set(h_end)
            conv = conv.at[l, seg_to].set(
                conv_end.reshape(-1, *conv.shape[2:]))
        return x + y, ssm, conv

    def attn_layer(lp, j, x, pages):
        with jax.named_scope("attn"):
            q, kv = attention.qkv(cfg.attn, lp,
                                  rms_norm(x, lp["norm"], cfg.eps))
            x = x + attention.attn_prefill(
                cfg.attn, lp, q, kv, seg,
                common.layer_page_rows(pages, j, prefix_pages), n_prefix,
                common.layer_page_rows(pages, j, cont_pages), n_cont)
            pages = pages.at[j, dest_page, dest_off].set(kv)
        return x, pages

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    x, state, held = _layers(cfg, params, x, state, seg >= 0, mamba_layer,
                             attn_layer)
    _, top, ids = head(cfg, params, x[last_idx])
    return state, top, ids, held


def decode_tokens(cfg: Config, params: dict, state, tokens, pos, page_table,
                  ctx_len, dest_page, dest_off, live, prefix_pages, n_prefix,
                  slot):
    """One token per row. Mamba-2 blocks move row ``slot[b]`` of the slot
    state on IN PLACE for every ``live`` row (``mamba_decode``; a row that
    carries no sequence names the null row and leaves it as it was);
    attention blocks write the row's ``[k ; v]`` to its page and attend to
    the shared prefix (read once for all rows) and, through the table of
    its OWN pages, to its ``ctx_len`` own cached rows."""

    def mamba_layer(lp, l, x, ssm, conv):
        with jax.named_scope("mamba2"):
            y, conv, ssm = mamba_decode(cfg, lp, l, x, slot, live, conv, ssm)
        return x + y, ssm, conv

    def attn_layer(lp, j, x, pages):
        with jax.named_scope("attn"):
            q, kv = attention.qkv(cfg.attn, lp,
                                  rms_norm(x, lp["norm"], cfg.eps))
            pages = pages.at[j, dest_page, dest_off].set(kv)
            x = x + attention.attn_decode(
                cfg.attn, lp, q, pages, j, page_table, ctx_len,
                common.layer_page_rows(pages, j, prefix_pages), n_prefix)
        return x, pages

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    x, state, held = _layers(cfg, params, x, state, live, mamba_layer,
                             attn_layer)
    _, top, ids = head(cfg, params, x)
    return state, top, ids, held
