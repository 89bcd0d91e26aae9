"""Laguna's forward pass, plain: ``jax.numpy``, float32, matrix products at
``highest`` precision, attention with every head materialised, the expert
layer as a loop over the experts, no cache, no pages, no packing, no
kernels, one layer's weights alive at a time. Imports nothing of the
program.

It follows the published description (config.json of
https://huggingface.co/poolside/Laguna-XS.2, ``model_type: laguna``).
Layer ``i`` (0-based): ``h <- h + attn_i(input_norm_i(h))``, ``h <- h +
ff_i(post_norm_i(h))``, every norm an RMSNorm with a learned gain
(``rms_norm_eps``), no bias anywhere. ``layer_types[i]`` says whether the
layer's attention is ``full_attention`` or ``sliding_attention``,
``num_attention_heads_per_layer[i]`` how many query heads it has,
``mlp_layer_types[i]`` whether its feed-forward is ``dense`` or ``sparse``.

* **Attention, both kinds.** ``q = W_q u`` as ``a_i`` heads, ``k = W_k u``,
  ``v = W_v u`` as ``num_key_value_heads`` heads of ``head_dim``; ``q <-
  q_norm(q)``, ``k <- k_norm(k)`` (RMSNorm over each head's values, one gain
  each: ASSUMED (3), the config has no key for it); the rotation on both
  after the norm; query head ``j`` reads key-value head ``j // (a_i /
  kv)``; softmax, scale head_dim^-1/2, over the positions ``s <= t`` (full)
  or ``t - sliding_window < s <= t`` (window: the token and the
  ``sliding_window - 1`` before it); ``g = sigmoid(W_g u)``, one value per
  query head and token (ASSUMED (1): ``gating: true``, which the sibling
  config Laguna-S-2.1 spells ``per-head``); out = ``W_o [g_j * o_j]_j``.
* **Rotation** (``rope_parameters[<layer type>]``). Of a head's first ``r =
  partial_rotary_factor * head_dim`` values the pair ``(x_j, x_{j + r/2})``
  (ASSUMED (4): the half-split pairing within the rotated part) is turned
  by ``pos * f_j`` and both results multiplied by ``m``; the other values
  pass. ``default``: ``f_j = theta^(-2j/r)``, ``m`` = 1. ``yarn``: ``m =
  attention_factor`` and, with ``e_j = theta^(-2j/r)``, ``dim(n) = r ln(L /
  (2 pi n)) / (2 ln theta)`` (``L = original_max_position_embeddings``),
  ``lo = max(floor(dim(beta_fast)), 0)``, ``hi = min(ceil(dim(beta_slow)),
  r - 1)``, ``ramp_j = clip((j - lo) / (hi - lo), 0, 1)``: ``f_j = (e_j /
  factor) ramp_j + e_j (1 - ramp_j)``. The table does not depend on the
  sequence's length.
* **Expert layer**: ``s = sigmoid(W_r u)`` over all ``num_experts``; the
  ``num_experts_per_tok`` experts are the best of ``s + router_bias``;
  their weights are ``s`` without the bias, divided by their sum + 1e-20,
  times ``moe_routed_scaling_factor`` (ASSUMED (2): that factor beside a
  renormalised top-k is the convention of sigmoid scores under a selection
  bias); expert ``e``: ``W2_e (silu(W1_e u) * W3_e u)``, the weight on its
  OUTPUT; plus one shared expert of ``shared_expert_intermediate_size`` on
  every token, unweighted. Only the terms of the experts ``[held_lo,
  held_lo + experts_held)`` are added (all of them in the published cut).
* **Dense feed-forward**: ``W_down (silu(W_gate h) * (W_up h))``.

After the last layer the final norm; logits = ``norm(x) . W_head`` over
the ``vocab_held`` columns (untied). Departures, all of them the
configuration's and none of them arithmetic: linear weights are [in, out];
weights are seeded (``tensor``: the head norms' gains around
``qk_norm_gain``, so that the softmax is peaked as a trained one is), read
here as the float32 values the bfloat16 tensors are. ``weight_dtype``
rounds them once more; ``window=False`` lets the window layers see
everything earlier; ``rotated=False`` leaves both rotations out;
``gated=False`` sets every gate to 1. These are the four readings that the
comparison has to refuse. ``act_dtype`` rounds the residual stream and
every block's input and output to that type (``lax.reduce_precision``),
still with no cache, no kernel and float32 products: with bfloat16 it is a
reading of what the served path's OWN precision costs against this
reference (routing decisions that flip among them), which the comparison
has to accept.

``omit`` leaves one term out, for the tests that show the comparison
notices: ``partial`` (the whole head turns on every layer), ``yarn`` (the
plain table ``e_j`` on the full layers), ``rope_scale`` (``m`` = 1),
``head_norms``, ``pairing`` (interleaved pairs ``(x_2j, x_2j+1)`` instead),
``router_bias``, ``renormalize``, ``shared``, ``expert:<id>``.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

GLOBAL_LAYER = 1_000_000
HI = jax.lax.Precision.HIGHEST
TOPK_EPS = 1e-20
HEAD_NORMS = ("q_norm", "k_norm")


def is_window(cfg, layer: int) -> bool:
    return cfg["layer_types"][layer] == "sliding_attention"


def is_dense(cfg, layer: int) -> bool:
    return cfg["mlp_layer_types"][layer] == "dense"


def tensor(cfg, layer, name, shape, expert=None, weight_dtype=None):
    key = jax.random.fold_in(jax.random.PRNGKey(cfg["weights_seed"]), layer)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if expert is not None:
        key = jax.random.fold_in(key, expert)
    w = jax.random.normal(key, shape, jnp.float32) * cfg["initializer_range"]
    if name in HEAD_NORMS:
        w = cfg["qk_norm_gain"] + w
    elif name.endswith("norm"):
        w = 1.0 + w
    w = w.astype(jnp.bfloat16)
    if weight_dtype is not None:
        w = w.astype(weight_dtype)
    return w.astype(jnp.float32)


def layer_shapes(cfg, layer: int) -> dict[str, tuple]:
    """Every tensor of the layer but the routed experts' (made one at a
    time inside ``moe``)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    a = cfg["num_attention_heads_per_layer"][layer]
    kv = cfg["num_key_value_heads"] * d
    out = {"input_norm": (h,), "post_norm": (h,), "q": (h, a * d),
           "k": (h, kv), "v": (h, kv), "o": (a * d, h), "q_norm": (d,),
           "k_norm": (d,), "gate": (h, a)}
    if is_dense(cfg, layer):
        i = cfg["intermediate_size"]
        out.update(mlp_gate=(h, i), mlp_up=(h, i), mlp_down=(i, h))
    else:
        s = cfg["shared_expert_intermediate_size"]
        out.update(router=(h, cfg["num_experts"]),
                   router_bias=(cfg["num_experts"],), shared_gate=(h, s),
                   shared_up=(h, s), shared_down=(s, h))
    return out


def layer_weights(cfg, layer, weight_dtype=None):
    return {name: tensor(cfg, layer, name, shape, None, weight_dtype)
            for name, shape in layer_shapes(cfg, layer).items()}


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def swiglu(x, gate, up, down):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def frequencies(rope: dict, r: int, omit=frozenset()):
    """The ``r / 2`` frequencies of one entry of ``rope_parameters`` and
    the factor ``m`` on the turned values."""
    theta = float(rope["rope_theta"])
    j = np.arange(r // 2, dtype=np.float64)
    e = theta ** (-2.0 * j / r)
    if rope["rope_type"] == "default":
        return e.astype(np.float32), 1.0
    m = 1.0 if "rope_scale" in omit else float(rope["attention_factor"])
    if "yarn" in omit:
        return e.astype(np.float32), m

    def dim(turns):
        return (r * math.log(rope["original_max_position_embeddings"]
                             / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    lo = max(math.floor(dim(rope["beta_fast"])), 0)
    hi = min(math.ceil(dim(rope["beta_slow"])), r - 1)
    ramp = np.clip((j - lo) / (hi - lo if hi > lo else 0.001), 0.0, 1.0)
    f = e / rope["factor"] * ramp + e * (1.0 - ramp)
    return f.astype(np.float32), m


def rotate(x, pos, freqs, m: float, interleaved=False):
    """The first ``2 len(freqs)`` values of the last axis of x [T, heads,
    d] turned to ``pos`` in the half-split pairing (``interleaved``: the
    pairs ``(x_2j, x_2j+1)``, which is NOT assumed) and multiplied by
    ``m``; the rest pass."""
    r = 2 * len(freqs)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    part, rest = x[..., :r], x[..., r:]
    if interleaved:
        a, b = part[..., 0::2], part[..., 1::2]
        turned = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).reshape(part.shape)
    else:
        a, b = part[..., :r // 2], part[..., r // 2:]
        turned = jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                                 axis=-1)
    return jnp.concatenate([turned * m, rest], axis=-1)


def attention(cfg, layer, w, x, omit=frozenset(), window=True, rotated=True,
              gated=True, block=256):
    """Grouped-query attention over one sequence, every query head with
    its own copy of its key-value head; causal, and in a window layer
    (unless ``window`` is False) over the last ``sliding_window`` positions
    only."""
    t = x.shape[0]
    heads = cfg["num_attention_heads_per_layer"][layer]
    kvh, d = cfg["num_key_value_heads"], cfg["head_dim"]
    kind = "sliding_attention" if is_window(cfg, layer) else "full_attention"
    q = mm(x, w["q"]).reshape(t, heads, d)
    k = mm(x, w["k"]).reshape(t, kvh, d)
    v = mm(x, w["v"]).reshape(t, kvh, d)
    if "head_norms" not in omit:
        q = rms_norm(q, w["q_norm"], cfg["rms_norm_eps"])
        k = rms_norm(k, w["k_norm"], cfg["rms_norm_eps"])
    if rotated:
        rope = cfg["rope_parameters"][kind]
        r = d if "partial" in omit else int(
            d * rope.get("partial_rotary_factor", 1))
        freqs, m = frequencies(rope, r, omit)
        pos = jnp.arange(t)
        q = rotate(q, pos, freqs, m, "pairing" in omit)
        k = rotate(k, pos, freqs, m, "pairing" in omit)
    k, v = (jnp.repeat(a, heads // kvh, axis=1) for a in (k, v))
    reach = (cfg["sliding_window"] if window and is_window(cfg, layer)
             else t + 1)
    outs = []
    for lo in range(0, t, block):
        hi = min(t, lo + block)
        s = jnp.einsum("thd,shd->hts", q[lo:hi], k, precision=HI) * d ** -0.5
        at, key_at = jnp.arange(lo, hi)[:, None], jnp.arange(t)[None, :]
        seen = (key_at <= at) & (key_at > at - reach)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hts,shd->thd", p, v, precision=HI))
    o = jnp.concatenate(outs, 0)
    if gated:
        o = o * jax.nn.sigmoid(mm(x, w["gate"]))[:, :, None]
    return mm(o.reshape(t, heads * d), w["o"])


def route(cfg, scores, bias, renormalize=True):
    """``(weights [T, k], ids [T, k])`` of float32 ``scores`` [T, experts]:
    the best ``num_experts_per_tok`` of ``scores + bias`` (ties: the lower
    id), weighted by the scores alone, renormalised and scaled."""
    scores = np.asarray(scores, np.float32)
    chosen_by = scores + np.asarray(bias, np.float32)
    ids = np.argsort(-chosen_by, axis=1, kind="stable")[
        :, :cfg["num_experts_per_tok"]]
    w = np.take_along_axis(scores, ids, axis=1)
    if renormalize:
        w = w / (w.sum(-1, keepdims=True) + np.float32(TOPK_EPS))
    return w * np.float32(cfg["moe_routed_scaling_factor"]), ids


def moe(cfg, layer, w, x, experts, omit=frozenset(), weight_dtype=None,
        shared=True):
    """The routed terms of ``experts`` (global ids) and, with ``shared``,
    the shared expert."""
    scores = jax.nn.sigmoid(mm(x, w["router"]))
    bias = (jnp.zeros_like(w["router_bias"]) if "router_bias" in omit
            else w["router_bias"])
    rw, ids = route(cfg, scores, bias, "renormalize" not in omit)
    hdim, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    y = jnp.zeros_like(x)
    if shared and "shared" not in omit:
        y = swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"])
    for e in experts:
        if f"expert:{e}" in omit:
            continue
        we = jnp.asarray(np.where(ids == e, rw, 0.0).sum(-1), jnp.float32)
        if not bool((we > 0).any()):
            continue
        mats = [tensor(cfg, layer, n, s, e, weight_dtype) for n, s in (
            ("expert_gate", (hdim, inter)), ("expert_up", (hdim, inter)),
            ("expert_down", (inter, hdim)))]
        y = y + we[:, None] * swiglu(x, *mats)
    return y


def held_experts(cfg):
    return range(cfg["held_lo"], cfg["held_lo"] + cfg["experts_held"])


def forward(cfg, tokens, rows=None, omit=frozenset(), weight_dtype=None,
            window=True, rotated=True, gated=True, experts=None,
            act_dtype=None, shared=True):
    """Logits [len(rows), vocab_held] of one sequence (all rows where
    ``rows`` is None), float32. ``experts``: the routed experts whose
    terms are added (default: the held range); ``shared``: whether the
    shared expert's is."""
    eps = cfg["rms_norm_eps"]
    tokens = jnp.asarray(tokens, jnp.int32)
    if experts is None:
        experts = held_experts(cfg)

    def low(a):
        if act_dtype is None:
            return a
        info = jnp.finfo(act_dtype)
        return jax.lax.reduce_precision(a, info.nexp, info.nmant)

    with jax.default_matmul_precision("highest"):
        x = tensor(cfg, GLOBAL_LAYER, "embed",
                   (cfg["vocab_held"], cfg["hidden_size"]), None,
                   weight_dtype)[tokens]
        for layer in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, layer, weight_dtype)
            h = low(rms_norm(x, w["input_norm"], eps))
            x = low(x + low(attention(cfg, layer, w, h, omit, window,
                                      rotated, gated)))
            h = low(rms_norm(x, w["post_norm"], eps))
            if "router" in w:
                y = moe(cfg, layer, w, h, experts, omit, weight_dtype, shared)
            else:
                y = swiglu(h, w["mlp_gate"], w["mlp_up"], w["mlp_down"])
            x = low(x + low(y))
            del w
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, tensor(cfg, GLOBAL_LAYER, "final_norm",
                               (cfg["hidden_size"],), None, weight_dtype),
                     eps)
        logits = mm(x, tensor(cfg, GLOBAL_LAYER, "head",
                              (cfg["hidden_size"], cfg["vocab_held"]), None,
                              weight_dtype))
    return logits
