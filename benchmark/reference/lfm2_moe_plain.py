"""LFM2-MoE's forward pass, plain: ``jax.numpy``, float32, matrix products
at ``highest`` precision, the short convolution as shifted sums, attention
with every head materialised, the expert layer as a loop over the held
experts, no cache, no slots, no packing, no kernels, one layer's weights
alive at a time. Imports nothing of the program.

It follows the published description (config.json of
https://huggingface.co/LiquidAI/LFM2-8B-A1B, ``model_type: lfm2_moe``).
Layer ``i`` (0-based) is what ``layer_types[i]`` says; the first
``num_dense_layers`` layers have a dense SwiGLU behind the mixer, every
other the expert layer. Per layer, pre-norm residual, RMSNorm with a
learned gain (``norm_eps``): ``h <- h + mixer(operator_norm(h))``, ``h <- h
+ ff(ffn_norm(h))``.

* **Gated short convolution** (``conv``): ``[B, C, x] = W_in u`` (three
  parts of ``hidden_size``, in that order); ``z_t = sum_{j=0..2} w_j * (B *
  x)_{t-2+j}`` per channel, causal, ``conv_L_cache`` taps, no bias, no
  activation; out = ``W_out (C * z)``.
* **Attention** (``full_attention``): ``q = W_q u`` as
  ``num_attention_heads`` heads, ``k = W_k u``, ``v = W_v u`` as
  ``num_key_value_heads`` heads of ``hidden_size / num_attention_heads``;
  ``q <- q_norm(q)``, ``k <- k_norm(k)`` (RMSNorm over each head's values,
  one gain each); rotary positions on both after the norm, ``rope_theta``,
  the half-split pairing ``(x_j, x_{j + d/2})``; causal softmax, scale
  d^-1/2, query head ``a`` reads key-value head ``a // group``; out =
  ``W_o [heads]``.
* **Expert layer**: ``s = sigmoid(W_r u)``; the ``num_experts_per_tok``
  experts are the best of ``s + expert_bias``; their weights are ``s``
  without the bias, divided by their sum + 1e-6 (``norm_topk_prob``), times
  ``routed_scaling_factor``; no shared expert. Only the terms of the HELD
  experts ``[held_lo, held_lo + experts_held)`` are added: the share of one
  chip of a deployment that spreads the experts over several.
* **Dense feed-forward**: ``W_down (silu(W_gate h) * (W_up h))``.

After the last layer the model's ``embedding_norm``; logits = ``norm(x) .
E^T`` over the ``vocab_held`` rows of the embedding held (tied).
Departures, all of them the configuration's and none of them arithmetic:
linear weights are [in, out], the convolution [taps, channels]; weights are
seeded (``tensor``: the head norms' gains around ``qk_norm_gain``, so that
the softmax is peaked as a trained one is), read here as the float32
values the bfloat16 tensors are. ``weight_dtype`` rounds them once more;
``rotate=False`` leaves the rotary positions out; ``taps_lost_from`` gives
every convolution zeros in place of the two inputs before each token at or
after that position (what a lost or wrong slot row does to a decode step).
These are the three readings that the comparison has to refuse.
``act_dtype`` rounds the residual stream and every block's input and
output to that type (``lax.reduce_precision``), still with no cache, no
kernel and float32 products: with bfloat16 it is a reading of what the
served path's OWN precision costs against this reference (routing
decisions that flip among them), which the comparison has to accept.

``omit`` leaves one term out, for the tests that show the comparison
notices: ``taps`` (the two earlier inputs, everywhere), ``gate_b``,
``gate_c``, ``head_norms``, ``pairing`` (the rotation over interleaved
pairs ``(x_2j, x_2j+1)`` instead), ``router_bias``, ``renormalize``,
``expert:<id>``.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

GLOBAL_LAYER = 1_000_000
HI = jax.lax.Precision.HIGHEST
TOPK_EPS = 1e-6
HEAD_NORMS = ("q_norm", "k_norm")


def is_conv(cfg, layer: int) -> bool:
    return cfg["layer_types"][layer] == "conv"


def tensor(cfg, layer, name, shape, expert=None, weight_dtype=None):
    key = jax.random.fold_in(jax.random.PRNGKey(cfg["weights_seed"]), layer)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if expert is not None:
        key = jax.random.fold_in(key, expert)
    if name == "conv_w":
        bound = shape[0] ** -0.5
        w = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    else:
        w = jax.random.normal(key, shape, jnp.float32) * cfg[
            "initializer_range"]
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
    h = cfg["hidden_size"]
    out = {"operator_norm": (h,), "ffn_norm": (h,)}
    if is_conv(cfg, layer):
        out.update(in_proj=(h, 3 * h), conv_w=(cfg["conv_L_cache"], h),
                   out_proj=(h, h))
    else:
        d = h // cfg["num_attention_heads"]
        kv = cfg["num_key_value_heads"] * d
        out.update(q=(h, h), k=(h, kv), v=(h, kv), o=(h, h), q_norm=(d,),
                   k_norm=(d,))
    if layer < cfg["num_dense_layers"]:
        i = cfg["intermediate_size"]
        out.update(mlp_gate=(h, i), mlp_up=(h, i), mlp_down=(i, h))
    else:
        out.update(router=(h, cfg["num_experts"]),
                   router_bias=(cfg["num_experts"],))
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


def short_conv(cfg, w, x, omit=frozenset(), taps_lost_from=None):
    """The gated short convolution over one sequence x [T, hidden], from
    no earlier inputs."""
    t, h = x.shape
    k = cfg["conv_L_cache"]
    bcx = mm(x, w["in_proj"])
    b, c, u = bcx[:, :h], bcx[:, h:2 * h], bcx[:, 2 * h:]
    bx = u if "gate_b" in omit else b * u
    padded = jnp.concatenate([jnp.zeros((k - 1, h), jnp.float32), bx], axis=0)
    earlier = sum(w["conv_w"][j] * padded[j:j + t] for j in range(k - 1))
    if "taps" in omit:
        earlier = jnp.zeros_like(earlier)
    elif taps_lost_from is not None:
        earlier = jnp.where((jnp.arange(t) >= taps_lost_from)[:, None], 0.0,
                            earlier)
    z = earlier + w["conv_w"][k - 1] * bx
    return mm(z if "gate_c" in omit else c * z, w["out_proj"])


def rotate(cfg, x, pos, interleaved=False):
    """Rotary positions over the last axis of x [T, heads, d]: the
    half-split pairing ``(x_j, x_{j + d/2})`` (``interleaved``: the pairs
    ``(x_2j, x_2j+1)``, which this model does NOT use)."""
    d = x.shape[-1]
    inv = 1.0 / cfg["rope_theta"] ** (jnp.arange(0, d, 2) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(cfg, w, x, omit=frozenset(), rotated=True, block=256):
    """Causal grouped-query attention over one sequence, every query
    head with its own copy of its key-value head."""
    t, h = x.shape
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    q = mm(x, w["q"]).reshape(t, heads, d)
    k = mm(x, w["k"]).reshape(t, kvh, d)
    v = mm(x, w["v"]).reshape(t, kvh, d)
    if "head_norms" not in omit:
        q = rms_norm(q, w["q_norm"], cfg["norm_eps"])
        k = rms_norm(k, w["k_norm"], cfg["norm_eps"])
    if rotated:
        pos = jnp.arange(t)
        q = rotate(cfg, q, pos, "pairing" in omit)
        k = rotate(cfg, k, pos, "pairing" in omit)
    k, v = (jnp.repeat(a, heads // kvh, axis=1) for a in (k, v))
    outs = []
    for lo in range(0, t, block):
        hi = min(t, lo + block)
        s = jnp.einsum("thd,shd->hts", q[lo:hi], k, precision=HI) * d ** -0.5
        seen = jnp.arange(t)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hts,shd->thd", p, v, precision=HI))
    return mm(jnp.concatenate(outs, 0).reshape(t, h), w["o"])


def route(cfg, scores, bias):
    """``(weights [T, k], ids [T, k])`` of float32 ``scores`` [T, experts]:
    the best ``num_experts_per_tok`` of ``scores + bias`` (ties: the lower
    id), weighted by the scores alone, renormalised and scaled."""
    scores = np.asarray(scores, np.float32)
    chosen_by = scores + np.asarray(bias, np.float32)
    ids = np.argsort(-chosen_by, axis=1, kind="stable")[
        :, :cfg["num_experts_per_tok"]]
    w = np.take_along_axis(scores, ids, axis=1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + np.float32(TOPK_EPS))
    return w * np.float32(cfg["routed_scaling_factor"]), ids


def moe(cfg, layer, w, x, experts, omit=frozenset(), weight_dtype=None):
    """The routed terms of ``experts`` (global ids); no shared expert."""
    scores = jax.nn.sigmoid(mm(x, w["router"]))
    bias = (jnp.zeros_like(w["router_bias"]) if "router_bias" in omit
            else w["router_bias"])
    rw, ids = route({**cfg, "norm_topk_prob": cfg["norm_topk_prob"]
                     and "renormalize" not in omit}, scores, bias)
    hdim, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    y = jnp.zeros_like(x)
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
            rotated=True, taps_lost_from=None, experts=None, act_dtype=None):
    """Logits [len(rows), vocab_held] of one sequence (all rows where
    ``rows`` is None), float32. ``experts``: the routed experts whose
    terms are added (default: the held range)."""
    eps = cfg["norm_eps"]
    tokens = jnp.asarray(tokens, jnp.int32)
    if experts is None:
        experts = held_experts(cfg)

    def low(a):
        if act_dtype is None:
            return a
        info = jnp.finfo(act_dtype)
        return jax.lax.reduce_precision(a, info.nexp, info.nmant)

    with jax.default_matmul_precision("highest"):
        embed = tensor(cfg, GLOBAL_LAYER, "embed",
                       (cfg["vocab_held"], cfg["hidden_size"]), None,
                       weight_dtype)
        x = embed[tokens]
        for layer in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, layer, weight_dtype)
            h = low(rms_norm(x, w["operator_norm"], eps))
            if is_conv(cfg, layer):
                x = low(x + low(short_conv(cfg, w, h, omit, taps_lost_from)))
            else:
                x = low(x + low(attention(cfg, w, h, omit, rotated)))
            h = low(rms_norm(x, w["ffn_norm"], eps))
            if "router" in w:
                y = moe(cfg, layer, w, h, experts, omit, weight_dtype)
            else:
                y = swiglu(h, w["mlp_gate"], w["mlp_up"], w["mlp_down"])
            x = low(x + low(y))
            del w
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, tensor(cfg, GLOBAL_LAYER, "final_norm",
                               (cfg["hidden_size"],), None, weight_dtype),
                     eps)
        logits = mm(x, embed.T)
    return logits
