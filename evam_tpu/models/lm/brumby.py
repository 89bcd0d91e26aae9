"""Brumby in plain JAX: Qwen3's skeleton with the softmax attention of
EVERY layer replaced by power retention of degree 2 (Manifest AI, "Scaling
Context Requires Rethinking Attention", arXiv:2507.04239); one pipeline
stage's layers, each whole.

Layer ``i``: ``x <- x + mixer_i(RMSNorm(x))``, ``x <- x + W_down (silu(W_gate
n) * W_up n)`` with ``n = RMSNorm(x)``; a final RMSNorm and an untied head.

* **The projections** are models/lm/attention.py's as one ``Kind``:
  ``num_attention_heads`` query heads over ``num_key_value_heads`` of
  ``head_dim`` (40 over 8: groups of 5), no bias, an RMSNorm on every query
  and key head, then the rotation (``rope_theta``, the rotate-half pairing
  over the whole head) at the token's position, counted from the start of
  the shared prefix. Called, not copied.
* **The gate**: one a key-value head and token, float32: ``l_t = log
  sigmoid(w_g . u_t + b_g)`` of the normed hidden, ``g_t = exp l_t``.
* **The mixer** weighs position ``j <= i`` by ``exp(G_i - G_j) (q_i .
  k_j)^2`` (``G`` the running sum of ``l``; no softmax, no maximum: the
  degree is even) and divides the weighted values by the weights' sum + 1e-6.
  Served in its RECURRENT form at every length: per key-value head a float32
  state ``S`` [8256, 128] (``phi(k) v^T`` summed under the decay, ``phi`` the
  8256 monomials of degree 2 of a head of 128, ops/pallas_power.py) and the
  running sum ``z`` of ``phi(k)``; ``y = phi(q)^T S / (phi(q)^T z + 1e-6)``
  for each of the head's five query heads. 34 MB a row and layer, per SLOT
  of the generate engine; this family has NO cache rows: ``state_shapes``
  has no ``pages``, the shared prefix is the snapshot row and nothing else.
  A prefill chunk runs the chunkwise form in a Pallas kernel
  (``pow_chunk_scan``: blocks of 64 tokens, ``SEGMENT_ALIGN``; a segment's
  state read from and written to its slot row by prefetched scalars); a
  decode step's one token is a second kernel over (row, key-value head)
  that moves each row's state in place (``pow_decode_rows``).

The layers are all of the one kind: their tensors are ONE stack and one
``lax.scan`` runs over them, the slot state its carry.

bfloat16 weights and activations; the gate, the scores, the state and its
sum float32. Weights (``common.tensor_key``): ``normal * initializer_range``;
gains ``1 +`` that; ``gate_b`` the logit of a decay that remembers ``tau``
tokens (``1 - 1 / tau``), ``tau`` drawn log-uniformly from
[``gate_memory_min``, ``gate_memory_max``]
(``benchmark/configs/brumby_14b_pp8.json`` ``assumed`` says what for).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from evam_tpu.models.lm import attention, common
from evam_tpu.models.lm.common import BF16, F32, GLOBAL_LAYER, mm, rms_norm
from evam_tpu.ops import pallas_power

#: the packer starts every segment at a block of the chunk kernel
SEGMENT_ALIGN = pallas_power.BLOCK


@dataclass(frozen=True)
class Config:
    hidden: int
    inter: int
    layers: int
    attn: attention.Kind
    eps: float
    vocab: int          # rows of the vocabulary held here
    seed: int
    init_range: float
    memory: tuple           # the seeded decays remember [min, max] tokens

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        if (d["attention_bias"] or d["tie_word_embeddings"]
                or d["hidden_act"] != "silu" or d["use_sliding_window"]
                or d["rope_scaling"] is not None or d["head_dim"] % 2
                or d["num_attention_heads"] % d["num_key_value_heads"]):
            raise ValueError(
                "the brumby family is written for layers that are all power "
                "retention (no window), query heads in whole groups over the "
                "key-value heads, a plain rotation, silu, no bias and an "
                "untied head")
        return cls(
            hidden=d["hidden_size"], inter=d["intermediate_size"],
            layers=d["num_hidden_layers"],
            attn=attention.Kind(
                hidden=d["hidden_size"], heads=d["num_attention_heads"],
                kv_heads=d["num_key_value_heads"], head_dim=d["head_dim"],
                eps=d["rms_norm_eps"], chunk_kernel=False,
                rope=attention.Rope(float(d["rope_theta"]))),
            eps=d["rms_norm_eps"], vocab=d["vocab_held"],
            seed=d["weights_seed"], init_range=d["initializer_range"],
            memory=(float(d["gate_memory_min"]),
                    float(d["gate_memory_max"])))

    @property
    def group(self) -> int:
        """Query heads that read one key-value head."""
        return self.attn.heads // self.attn.kv_heads


# --------------------------------------------------------------- weights


def layer_shapes(cfg: Config) -> dict[str, tuple]:
    h, i, kvh = cfg.hidden, cfg.inter, cfg.attn.kv_heads
    return {"input_norm": (h,), "post_norm": (h,),
            **attention.tensor_shapes(cfg.attn, head_norms=True),
            "gate_w": (h, kvh), "gate_b": (kvh,),
            "mlp_gate": (h, i), "mlp_up": (h, i), "mlp_down": (i, h)}


def _kind(name: str) -> str:
    if name == "gate_b":
        return name
    return "gain" if name.endswith("norm") else "normal"


def _tensor(key, kind: str, shape: tuple, std: float, memory: tuple):
    """One tensor from its key, by the rule of its ``kind``."""
    if kind == "gate_b":
        lo, hi = (math.log(m) for m in memory)
        tau = jnp.exp(jax.random.uniform(key, shape, F32, lo, hi))
        return jnp.log(tau - 1.0).astype(BF16)
    w = jax.random.normal(key, shape, F32) * std
    if kind == "gain":
        w = 1.0 + w
    return w.astype(BF16)


#: compiled once per kind and shape, whatever the name and the layer
_make_one = jax.jit(_tensor, static_argnums=(1, 2, 3, 4))


def make_tensor(cfg: Config, layer: int, name: str, shape: tuple):
    return _make_one(common.tensor_key(cfg.seed, layer, name), _kind(name),
                     shape, cfg.init_range, cfg.memory)


def make_params(cfg: Config) -> dict:
    """``layers``: every layer's tensors, each name's stacked on a leading
    axis."""
    return {
        "embed": make_tensor(cfg, GLOBAL_LAYER, "embed",
                             (cfg.vocab, cfg.hidden)),
        "final_norm": make_tensor(cfg, GLOBAL_LAYER, "final_norm",
                                  (cfg.hidden,)),
        "head": make_tensor(cfg, GLOBAL_LAYER, "head",
                            (cfg.hidden, cfg.vocab)),
        "layers": common.make_layers(
            lambda i, name, shape: make_tensor(cfg, i, name, shape),
            cfg.seed, cfg.init_range, range(cfg.layers), layer_shapes(cfg)),
    }


def param_count(cfg: Config) -> int:
    return (2 * cfg.vocab * cfg.hidden + cfg.hidden + cfg.layers * sum(
        math.prod(s) for s in layer_shapes(cfg).values()))


def state_shapes(cfg: Config, n_pages: int, page_tokens: int,
                 slots: int) -> dict:
    """The device state of this family's sequences: NO ``pages`` (no layer
    keeps rows). Per SLOT (and two rows more: row ``slots`` for rows of a
    step that carry no sequence, row ``slots + 1`` the snapshot after the
    shared prefix's last token), layer and key-value head: ``pow``, the
    float32 state ``[d (d + 1) / 2, d]`` (34 MB a row and layer at the
    published widths), and ``pow_z``, the running sum of ``phi(k)`` as
    ops/pallas_power.py ``phi_lanes`` lays it. Both kernels address them by
    ``[layer, slot]`` and move the rows they name in place."""
    rows = slots + 2
    kvh, d = cfg.attn.kv_heads, cfg.attn.head_dim
    return {
        "pow": jax.ShapeDtypeStruct(
            (cfg.layers, rows, kvh, pallas_power.expanded(d), d), F32),
        "pow_z": jax.ShapeDtypeStruct(
            (cfg.layers, rows, kvh, d // 2 + 1, d), F32),
    }


# ---------------------------------------------------------------- layers


def mixer_inputs(cfg: Config, lp: dict, h, pos):
    """From the normed rows ``h`` at positions ``pos``: the queries [T, key-
    value heads, group, head_dim], keys and values [T, key-value heads,
    head_dim] (bfloat16; heads normed, then rotated) and the float32 log
    gates [T, key-value heads]."""
    t, kind = h.shape[0], cfg.attn
    q, kv = attention.qkv(kind, lp, h, pos)
    k, v = (a.reshape(t, kind.kv_heads, kind.head_dim)
            for a in jnp.split(kv, 2, axis=1))
    with jax.named_scope("gate"):
        lg = jax.nn.log_sigmoid(
            jnp.dot(h, lp["gate_w"], preferred_element_type=F32)
            + lp["gate_b"].astype(F32))
    return (q.reshape(t, kind.kv_heads, cfg.group, kind.head_dim), k, v, lg)


def head(cfg: Config, params: dict, x):
    return common.head(x, params["final_norm"], cfg.eps, params["head"])


# ----------------------------------------------------------- step bodies


def _layers(cfg: Config, params: dict, x, state, live, pos, retain):
    """Every layer in its order, as ONE ``lax.scan`` over the stack:
    ``retain(l, q, k, v, lg, pow, pow_z)`` is the mixer's recurrence over
    the step's rows (a chunk's or a decode step's), which moves the slot
    state of layer ``l`` in place."""

    def body(carry, xs):
        lp, l = xs
        x, s, z = carry
        with jax.named_scope("power_retention"):
            h = rms_norm(x, lp["input_norm"], cfg.eps)
            y, s, z = retain(l, *mixer_inputs(cfg, lp, h, pos), s, z)
            # rows of no sequence are whatever the kernel's memory held
            y = jnp.where(live[:, None, None, None], y, 0.0).astype(BF16)
            x = x + mm(y.reshape(x.shape[0], -1), lp["o"])
        with jax.named_scope("mlp"):
            x = x + common.swiglu(
                rms_norm(x, lp["post_norm"], cfg.eps), lp["mlp_gate"],
                lp["mlp_up"], lp["mlp_down"])
        return (x, s, z), None

    (x, s, z), _ = jax.lax.scan(
        body, (x, state["pow"], state["pow_z"]),
        (params["layers"], jnp.arange(cfg.layers, dtype=jnp.int32)))
    return x, {"pow": s, "pow_z": z}


#: a family without experts
_NO_EXPERTS = (0, 0, 0)


def prefill_chunk(cfg: Config, params: dict, state, tokens, seg, pos,
                  dest_page, dest_off, prefix_pages, n_prefix, cont_pages,
                  n_cont, last_idx, seg_from, seg_to):
    """A packed chunk of new tokens through every layer: segment ``s``
    starts from slot-state row ``seg_from[s]`` (the snapshot's for a new
    sequence, the slot's own for a prompt that continues) and leaves its
    end state in row ``seg_to[s]``. Returns the state, the logits rows
    ``last_idx`` as ``(top, ids)`` and zeros for the expert counts. No page
    argument is used: nothing is cached by row."""
    scan = (pallas_power.chunk_scan if common.on_tpu()
            else pallas_power.chunk_scan_xla)

    def retain(l, q, k, v, lg, s, z):
        return scan(l, q, k, v, lg, seg, seg_from, seg_to, s, z)

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    x, state = _layers(cfg, params, x, state, seg >= 0, pos, retain)
    _, top, ids = head(cfg, params, x[last_idx])
    return state, top, ids, jnp.asarray(_NO_EXPERTS, jnp.int32)


def decode_tokens(cfg: Config, params: dict, state, tokens, pos, page_table,
                  ctx_len, dest_page, dest_off, live, prefix_pages, n_prefix,
                  slot):
    """One token per row: every layer moves row ``slot[b]`` of the slot
    state on IN PLACE for every ``live`` row (a row that carries no sequence
    names the null row and leaves it as it was)."""
    rows = (pallas_power.decode_rows if common.on_tpu()
            else pallas_power.decode_rows_xla)

    def retain(l, q, k, v, lg, s, z):
        return rows(l, slot, live, q, k, v, lg, s, z)

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    x, state = _layers(cfg, params, x, state, live, pos, retain)
    _, top, ids = head(cfg, params, x)
    return state, top, ids, jnp.asarray(_NO_EXPERTS, jnp.int32)
