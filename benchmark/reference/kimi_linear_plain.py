"""Kimi-Linear's forward pass, plain: ``jax.numpy``, float32, matrix products
at ``highest`` precision, the delta rule as a ``lax.scan`` over tokens,
attention with materialised heads, the expert layer as a loop over the held
experts, no cache, no slots, no packing, no kernels, one layer's weights
alive at a time. Imports nothing of the program.

It follows the published description (config.json of
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct and the Kimi
Linear report's Kimi Delta Attention). Layers are numbered from 1 as
``linear_attn_config`` numbers them: ``kda_layers`` are KDA, the
``full_attn_layers`` latent attention; the first ``first_k_dense_replace``
layers have a dense SwiGLU behind the mixer, every other the expert layer.
Per layer, pre-norm residual, RMSNorm with a learned gain:

* **KDA** (H heads of D keys and values): ``q, k, v = W_q h, W_k h, W_v h``;
  each ``x_t <- silu(sum_j w[j] * x_{t-3+j})`` per channel, causal, its own
  weights, no bias; ``q``, ``k`` divided by their norm per head (``x *
  rsqrt(sum x^2 + 1e-6)``), ``q`` times D^-1/2; ``g_t = -exp(A_log[head])
  * softplus(W_f2 (W_f1 h) + dt_bias)`` per head and channel; ``b_t =
  sigmoid(W_b h)`` per head; ``S_t = (I - b_t k_t k_t^T) Diag(exp(g_t))
  S_{t-1} + b_t k_t v_t^T`` from ``S = 0``; ``o_t = S_t^T q_t``; out =
  ``W_o (rms_norm_head(o_t) * sigmoid(W_g2 (W_g1 h)))``.
* **MLA** (``q_lora_rank`` null, ``mla_use_nope``): ``q = W_q h`` per head
  (nope + rope values); ``[c ; k_r] = W_kva h``, ``c <- norm(c)``; ``[k_nope
  ; v] = W_kvb c`` per head; score = ``(q_nope . k_nope + q_r . k_r) *
  (nope + rope)^-1/2``, causal; NO rotation of ``q_r`` or ``k_r``; out =
  ``W_o`` over the heads' values.
* **Expert layer**: ``s = sigmoid(W_r h)``; the ``num_experts_per_token``
  experts are the best of ``s + b`` (one group); their weights are ``s``
  without ``b``, divided by their sum (``moe_renormalize``), times
  ``routed_scaling_factor``; plus one shared SwiGLU. Only the terms of the
  HELD experts ``[held_lo, held_lo + experts_held)`` are added: the share
  of one chip of a deployment that spreads the experts over several.
* **Dense feed-forward**: ``W_down (silu(W_gate h) * (W_up h))``.

After the last layer a final norm; logits = ``norm(x) . W_head`` over the
``vocab_held`` rows held. Departures, all of them the configuration's and
none of them arithmetic: linear weights are [in, out], the convolutions
[taps, channels]; weights are seeded (``tensor``: the latent attention's
``q`` and ``kv_a`` ``mla_qk_init_scale`` times as wide as the rest, so that
its softmax is peaked as a trained one is), read here as the float32
values the bfloat16 tensors are. ``weight_dtype`` rounds them once more;
``state_dtype`` holds the KDA recurrence in that type (the decay, the
update and the state each rounded to it); ``rotate`` turns ``q_r`` and
``k_r`` by the plain rotary embedding of ``rope_theta``, as DeepSeek's
latent attention does. These are the three readings that the comparison has
to refuse.

``omit`` leaves one term out, for the tests that show the comparison
notices: ``conv`` (the three earlier taps), ``decay``, ``beta`` (the write
strength: 1), ``delta`` (the correction ``k k^T S``: plain linear
attention), ``out_gate``, ``k_rope`` (the unrotated 64 values of the
score), ``router_bias``, ``renormalize``, ``shared``, ``expert:<id>``.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

GLOBAL_LAYER = 1_000_000
HI = jax.lax.Precision.HIGHEST
DT_MIN, DT_MAX = 0.001, 0.1
A_MAX = 16.0
#: seeded ``mla_qk_init_scale`` times wider: scores of the size a trained
#: attention has, so that WHICH rows a query weighs shows in the logits
MLA_SCORE_TENSORS = ("q", "kv_a")


def dims(cfg) -> dict:
    la = cfg["linear_attn_config"]
    return dict(h=cfg["hidden_size"], heads=la["num_heads"], d=la["head_dim"],
                taps=la["short_conv_kernel_size"], r=cfg["kda_gate_rank"],
                mla_heads=cfg["num_attention_heads"],
                rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
                rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"])


def is_kda(cfg, layer: int) -> bool:
    """``layer`` counts from 0; the config's lists count from 1."""
    return layer + 1 in cfg["linear_attn_config"]["kda_layers"]


def tensor(cfg, layer, name, shape, expert=None, weight_dtype=None):
    key = jax.random.fold_in(jax.random.PRNGKey(cfg["weights_seed"]), layer)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if expert is not None:
        key = jax.random.fold_in(key, expert)
    if name.endswith("_conv"):
        bound = shape[0] ** -0.5
        w = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(DT_MAX) - math.log(DT_MIN))
                     + math.log(DT_MIN))
        w = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "A_log":
        w = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, A_MAX))
    else:
        std = cfg["initializer_range"]
        if name in MLA_SCORE_TENSORS and not is_kda(cfg, layer):
            std *= cfg["mla_qk_init_scale"]
        w = jax.random.normal(key, shape, jnp.float32) * std
        if name.endswith("norm"):
            w = 1.0 + w
    w = w.astype(jnp.bfloat16)
    if weight_dtype is not None:
        w = w.astype(weight_dtype)
    return w.astype(jnp.float32)


def layer_shapes(cfg, layer: int) -> dict[str, tuple]:
    """Every tensor of the layer but the routed experts' (made one at a
    time inside ``moe``)."""
    g = dims(cfg)
    h = g["h"]
    out = {"input_norm": (h,), "post_norm": (h,)}
    if is_kda(cfg, layer):
        w = g["heads"] * g["d"]
        out.update(q=(h, w), k=(h, w), v=(h, w), q_conv=(g["taps"], w),
                   k_conv=(g["taps"], w), v_conv=(g["taps"], w),
                   f_a=(h, g["r"]), f_b=(g["r"], w), dt_bias=(w,),
                   A_log=(g["heads"],), b_proj=(h, g["heads"]),
                   g_a=(h, g["r"]), g_b=(g["r"], w), o_norm=(g["d"],),
                   o=(w, h))
    else:
        hd = g["mla_heads"]
        out.update(q=(h, hd * (g["nope"] + g["rope"])),
                   kv_a=(h, g["rank"] + g["rope"]), kv_a_norm=(g["rank"],),
                   kv_b=(g["rank"], hd * (g["nope"] + g["v"])),
                   o=(hd * g["v"], h))
    if layer < cfg["first_k_dense_replace"]:
        i = cfg["intermediate_size"]
        out.update(mlp_gate=(h, i), mlp_up=(h, i), mlp_down=(i, h))
    else:
        s = cfg["num_shared_experts"] * cfg["moe_intermediate_size"]
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


def kda(cfg, w, x, omit=frozenset(), state_dtype=None):
    """The KDA mixer over one sequence x [T, hidden], from a zero state."""
    g = dims(cfg)
    t, heads, d, taps = x.shape[0], g["heads"], g["d"], g["taps"]

    def conv(u, weights):
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, u.shape[1]), jnp.float32), u], axis=0)
        which = range(taps - 1, taps) if "conv" in omit else range(taps)
        return jax.nn.silu(sum(weights[j] * padded[j:j + t] for j in which))

    def unit(u):
        return u * jax.lax.rsqrt(jnp.sum(u * u, -1, keepdims=True) + 1e-6)

    q, k, v = (conv(mm(x, w[n]), w[n + "_conv"]).reshape(t, heads, d)
               for n in ("q", "k", "v"))
    q, k = unit(q) * d ** -0.5, unit(k)
    step = jax.nn.softplus(mm(mm(x, w["f_a"]), w["f_b"]) + w["dt_bias"])
    log_decay = -jnp.exp(w["A_log"])[None, :, None] * step.reshape(
        t, heads, d)
    if "decay" in omit:
        log_decay = jnp.zeros_like(log_decay)
    beta = jax.nn.sigmoid(mm(x, w["b_proj"]))
    if "beta" in omit:
        beta = jnp.ones_like(beta)

    def low(a):
        """The recurrence's precision: float32 unless asked.
        ``reduce_precision`` and not a pair of casts, which XLA is free to
        drop on a TPU (excess precision is allowed there)."""
        if state_dtype is None:
            return a
        info = jnp.finfo(state_dtype)
        return jax.lax.reduce_precision(a, info.nexp, info.nmant)

    def token(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        s = low(low(jnp.exp(g_t))[:, :, None] * s)
        seen = jnp.einsum("hk,hkv->hv", k_t, s, precision=HI)
        if "delta" in omit:
            seen = jnp.zeros_like(seen)
        s = low(s + low(k_t[:, :, None]
                        * (b_t[:, None] * (v_t - seen))[:, None, :]))
        return s, jnp.einsum("hk,hkv->hv", q_t, s, precision=HI)

    _, o = jax.lax.scan(token, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v, log_decay, beta))
    o = rms_norm(o, w["o_norm"], cfg["rms_norm_eps"]).reshape(t, heads * d)
    if "out_gate" not in omit:
        o = o * jax.nn.sigmoid(mm(mm(x, w["g_a"]), w["g_b"]))
    return mm(o, w["o"])


def _rotate(cfg, x, pos):
    """The plain rotary embedding over pairs (2i, 2i+1) of the last axis
    (``rotate``: a control, not the model)."""
    dim = x.shape[-1]
    inv = 1.0 / cfg["rope_theta"] ** (jnp.arange(0, dim, 2) / dim)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(cfg, w, x, omit=frozenset(), rotate=False, block=256):
    """Causal latent attention over one sequence, heads materialised."""
    g = dims(cfg)
    t, hd, nope, rope, vd = (x.shape[0], g["mla_heads"], g["nope"],
                             g["rope"], g["v"])
    q = mm(x, w["q"]).reshape(t, hd, nope + rope)
    q_nope, q_r = q[..., :nope], q[..., nope:]
    kv = mm(x, w["kv_a"])
    c = rms_norm(kv[:, :g["rank"]], w["kv_a_norm"], cfg["rms_norm_eps"])
    k_r = kv[:, g["rank"]:]
    if rotate:
        pos = jnp.arange(t)
        q_r, k_r = _rotate(cfg, q_r, pos), _rotate(cfg, k_r, pos)
    if "k_rope" in omit:
        k_r = jnp.zeros_like(k_r)
    kvb = mm(c, w["kv_b"]).reshape(t, hd, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    scale = (nope + rope) ** -0.5
    outs = []
    for lo in range(0, t, block):
        hi = min(t, lo + block)
        s = (jnp.einsum("thd,shd->hts", q_nope[lo:hi], k_nope, precision=HI)
             + jnp.einsum("thd,sd->hts", q_r[lo:hi], k_r, precision=HI))
        seen = jnp.arange(t)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s * scale, -jnp.inf),
                           axis=-1)
        outs.append(jnp.einsum("hts,shd->thd", p, v, precision=HI))
    return mm(jnp.concatenate(outs, 0).reshape(t, hd * vd), w["o"])


def route(cfg, scores, bias):
    """``(weights [T, k], ids [T, k])`` of float32 ``scores`` [T, experts]:
    the best ``num_experts_per_token`` of ``scores + bias`` (ties: the
    lower id), weighted by the scores alone, renormalised and scaled."""
    scores = np.asarray(scores, np.float32)
    chosen_by = scores + np.asarray(bias, np.float32)
    ids = np.argsort(-chosen_by, axis=1, kind="stable")[
        :, :cfg["num_experts_per_token"]]
    w = np.take_along_axis(scores, ids, axis=1)
    if cfg["moe_renormalize"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * np.float32(cfg["routed_scaling_factor"]), ids


def moe(cfg, layer, w, x, experts, omit=frozenset(), weight_dtype=None):
    """The routed terms of ``experts`` (global ids) plus the shared
    expert."""
    scores = jax.nn.sigmoid(mm(x, w["router"]))
    bias = (jnp.zeros_like(w["router_bias"]) if "router_bias" in omit
            else w["router_bias"])
    rw, ids = route({**cfg, "moe_renormalize": cfg["moe_renormalize"]
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
    if "shared" not in omit:
        y = y + swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"])
    return y


def held_experts(cfg):
    return range(cfg["held_lo"], cfg["held_lo"] + cfg["experts_held"])


def forward(cfg, tokens, rows=None, omit=frozenset(), weight_dtype=None,
            state_dtype=None, rotate=False):
    """Logits [len(rows), vocab_held] of one sequence (all rows where
    ``rows`` is None), float32."""
    eps = cfg["rms_norm_eps"]
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = tensor(cfg, GLOBAL_LAYER, "embed",
                   (cfg["vocab_held"], cfg["hidden_size"]), None,
                   weight_dtype)[tokens]
        for layer in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, layer, weight_dtype)
            h = rms_norm(x, w["input_norm"], eps)
            if is_kda(cfg, layer):
                x = x + kda(cfg, w, h, omit, state_dtype)
            else:
                x = x + attention(cfg, w, h, omit, rotate)
            h = rms_norm(x, w["post_norm"], eps)
            if "router" in w:
                x = x + moe(cfg, layer, w, h, held_experts(cfg), omit,
                            weight_dtype)
            else:
                x = x + swiglu(h, w["mlp_gate"], w["mlp_up"], w["mlp_down"])
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
