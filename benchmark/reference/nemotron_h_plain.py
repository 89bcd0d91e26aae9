"""Nemotron-H's forward pass, plain: ``jax.numpy``, float32, matrix products
at ``highest`` precision, the Mamba-2 recurrence as a plain loop over the
tokens, attention with every head materialised, the expert layer as a loop
over the experts, no cache, no pages, no packing, no batching, no kernels,
one block's weights alive at a time. Imports nothing of the program.

It follows the published description (config.json of
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
``model_type: nemotron_h``; the family's report arXiv:2504.03624; Mamba-2,
arXiv:2405.21060). Block ``i`` is what ``hybrid_override_pattern[i]`` says,
``M`` | ``E`` | ``*``, and is ONE sublayer: ``x <- x + f_i(RMSNorm(x;
norm_i))`` (``layer_norm_epsilon``, a learned gain, no bias anywhere but the
convolution's).

* ``M``, **Mamba-2** (H = ``mamba_num_heads`` heads of P =
  ``mamba_head_dim``, N = ``ssm_state_size``, G = ``n_groups``): ``[z | xBC
  | dt] = u W_in`` of widths ``H P | H P + 2 G N | H`` (ASSUMED (3): this
  order, Mamba-2's own; the program keeps the ``dt`` columns as a tensor of
  their own, ``dt_proj``, which is the same numbers); ``xBC <-
  silu(conv(xBC) + b_conv)``, a causal depthwise convolution of
  ``conv_kernel`` taps over the channels; ``x | B | C`` of widths ``H P | G
  N | G N``; ``delta = softplus(dt + dt_bias)`` [H]; ``a = -exp(A_log)``
  [H], ONE decay a head; per head ``h`` with group ``g = h // (H / G)`` and
  a state ``s`` [P, N]: ``s_t = exp(delta_t a) s_{t-1} + delta_t x_t (x)
  B_t^g``, ``y_t = s_t C_t^g + D x_t``; ``y <- GroupRMSNorm(y * silu(z))``
  (ASSUMED (3): the gate BEFORE the norm, each of the G groups of ``H P /
  G`` channels normalised alone, one gain of ``H P``); out = ``y W_out``.
* ``*``, **attention**: ``q = u W_q`` as ``num_attention_heads`` heads,
  ``k = u W_k``, ``v = u W_v`` as ``num_key_value_heads`` heads of
  ``head_dim``; query head ``j`` reads key-value head ``j // (heads /
  kv)``; causal softmax of ``q k^T / sqrt(head_dim)``; NO positional term
  (ASSUMED (1): the family's published description; the config carries
  ``rope_theta`` and ``partial_rotary_factor``, which the family's model
  code does not read); out = ``[o_j]_j W_o``.
* ``E``, **LatentMoE**: ``s = sigmoid(u W_r)`` over all
  ``n_routed_experts`` (ASSUMED (2): the router reads the hidden);
  the ``num_experts_per_tok`` experts are the best of ``s + router_bias``;
  their weights are ``s`` without the bias, divided by their sum + 1e-20
  (``norm_topk_prob``), times ``routed_scaling_factor``; ``l = u W_down``
  (hidden -> ``moe_latent_size``, shared by all experts, no bias: ASSUMED
  (2)); expert ``e``: ``W2_e relu2(l W1_e)`` with ``relu2(t) = max(t,
  0)^2``, TWO matrices and no gate; ``r`` the weighted sum over the chosen
  experts THAT ARE HELD (``[held_lo, held_lo + experts_held)``; absent
  experts' terms are left out and the partial result handed on); out = ``r
  W_up + relu2(u S1) S2`` (``W_up`` latent -> hidden, shared; the shared
  expert of ``moe_shared_expert_intermediate_size`` on the hidden itself).
* The multi-token-prediction head (``num_nextn_predict_layers``) is beside
  the model; the model's own logits do not pass through it, and it is not
  here.

After the last block the final norm; logits = ``norm(x) W_head`` over the
``vocab_held`` columns (untied). Departures, all of them the configuration's
and none of them arithmetic: linear weights are [in, out]; weights are
seeded (``tensor``: ASSUMED (4) ``A_log = log U(1, mamba_a_init_max)`` a
head, ``dt_bias`` the inverse softplus of a step drawn log-uniformly from
[``time_step_min``, ``mamba_dt_init_max``] and floored at
``time_step_floor`` (Mamba-2's initialisation; absent keys: its own ranges,
16 and ``time_step_max``; the configuration draws from their LOW end, ASSUMED
(6), so that the state remembers across a prompt), ``D`` and the gains
``1 + normal * initializer_range``, the convolution uniform in
+-conv_kernel^-1/2, a small non-zero ``router_bias``; ASSUMED (5) the
attention's ``q`` and ``k`` ``attn_qk_init_scale`` times as wide), read here
as the float32 values the bfloat16 tensors are.

The readings that the comparison has to refuse: ``weight_dtype`` rounds the
weights once more; ``carried=False`` starts the recurrence's state and the
convolution's inputs anew at token ``fresh_at`` (a decode step that did not
get the chunk's state); ``state_dtype`` rounds the state after every token;
``latent=False`` drops both latent projections (the experts read the
hidden's first ``moe_latent_size`` values and add to them); ``act="silu"``
puts ``silu`` for ``relu2``; ``top_k`` chooses another number of experts;
``rotated=True`` turns ``q`` and ``k`` by the config's ``rope_theta`` (the
half-split pairing over the whole head). ``act_dtype`` rounds the residual
stream and every block's input and output to that type
(``lax.reduce_precision``): with bfloat16 a reading of what the served
path's OWN precision costs, which the comparison has to accept.

``omit`` leaves one term out, for the tests that show the comparison
notices: ``conv_bias``, ``D``, ``gate`` (no ``silu(z)``), ``group_norm``
(one norm over all channels), ``router_bias``, ``renormalize``, ``shared``,
``expert:<id>``.
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
A_MAX = 16.0


def kind_of(name: str) -> str:
    if name in ("conv_w", "dt_bias", "A_log"):
        return name
    return "gain" if name.endswith("norm") or name == "D" else "normal"


def tensor(cfg, layer, name, shape, expert=None, weight_dtype=None):
    key = jax.random.fold_in(jax.random.PRNGKey(cfg["weights_seed"]), layer)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if expert is not None:
        key = jax.random.fold_in(key, expert)
    kind = kind_of(name)
    if kind == "conv_w":
        bound = shape[0] ** -0.5
        w = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    elif kind == "dt_bias":
        lo = cfg["time_step_min"]
        hi = cfg.get("mamba_dt_init_max", cfg["time_step_max"])
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                       * (math.log(hi) - math.log(lo)) + math.log(lo))
        step = jnp.maximum(step, cfg["time_step_floor"])
        w = step + jnp.log(-jnp.expm1(-step))
    elif kind == "A_log":
        w = jnp.log(jax.random.uniform(
            key, shape, jnp.float32, 1.0, cfg.get("mamba_a_init_max", A_MAX)))
    else:
        std = cfg["initializer_range"]
        if name in ("q", "k") and layer != GLOBAL_LAYER:
            std = std * cfg["attn_qk_init_scale"]
        w = jax.random.normal(key, shape, jnp.float32) * std
        if kind == "gain":
            w = 1.0 + w
    w = w.astype(jnp.bfloat16)
    if weight_dtype is not None:
        w = w.astype(weight_dtype)
    return w.astype(jnp.float32)


def block_shapes(cfg, layer: int) -> dict[str, tuple]:
    """Every tensor of the block but the routed experts' (made one at a
    time inside ``moe``)."""
    h = cfg["hidden_size"]
    kind = cfg["hybrid_override_pattern"][layer]
    if kind == "M":
        heads = cfg["mamba_num_heads"]
        c = heads * cfg["mamba_head_dim"]
        w = c + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
        return {"norm": (h,), "in_proj": (h, c + w), "dt_proj": (h, heads),
                "conv_w": (cfg["conv_kernel"], w), "conv_b": (w,),
                "dt_bias": (heads,), "A_log": (heads,), "D": (heads,),
                "gate_norm": (c,), "out_proj": (c, h)}
    if kind == "*":
        d = cfg["head_dim"]
        q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
        return {"norm": (h,), "q": (h, q), "k": (h, kv), "v": (h, kv),
                "o": (q, h)}
    lat, s = cfg["moe_latent_size"], cfg["moe_shared_expert_intermediate_size"]
    return {"norm": (h,), "router": (h, cfg["n_routed_experts"]),
            "router_bias": (cfg["n_routed_experts"],),
            "latent_down": (h, lat), "latent_up": (lat, h),
            "shared_up": (h, s), "shared_down": (s, h)}


def block_weights(cfg, layer, weight_dtype=None):
    return {name: tensor(cfg, layer, name, shape, None, weight_dtype)
            for name, shape in block_shapes(cfg, layer).items()}


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def relu2(t):
    return jnp.square(jnp.maximum(t, 0.0))


def mamba2(cfg, w, u, omit=frozenset(), carried=True, fresh_at=None,
           state_dtype=None):
    """One sequence through a Mamba-2 mixer, the recurrence token by
    token."""
    t = u.shape[0]
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, g, k = cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"]
    c = heads * p
    zxbc = mm(u, w["in_proj"])
    z, pre = zxbc[:, :c], zxbc[:, c:]
    fresh = jnp.full((t,), -1) if carried or fresh_at is None else jnp.where(
        jnp.arange(t) >= fresh_at, fresh_at, -1)
    # tap d back of token t: the input at t - d, nothing before the start
    # (or before ``fresh_at`` for a token at or behind it)
    acc = jnp.zeros_like(pre) if "conv_bias" in omit else (
        jnp.zeros_like(pre) + w["conv_b"])
    for d in range(k):
        tap = jnp.pad(pre, ((d, 0), (0, 0)))[:t]
        seen = (jnp.arange(t) - d >= jnp.maximum(fresh, 0))[:, None]
        acc = acc + w["conv_w"][k - 1 - d] * jnp.where(seen, tap, 0.0)
    xbc = jax.nn.silu(acc)
    x = xbc[:, :c].reshape(t, heads, p)
    b = jnp.repeat(xbc[:, c:c + g * n].reshape(t, g, n), heads // g, axis=1)
    cc = jnp.repeat(xbc[:, c + g * n:].reshape(t, g, n), heads // g, axis=1)
    delta = jax.nn.softplus(mm(u, w["dt_proj"]) + w["dt_bias"])
    a = -jnp.exp(w["A_log"])

    def low(s):
        if state_dtype is None:
            return s
        info = jnp.finfo(state_dtype)
        return jax.lax.reduce_precision(s, info.nexp, info.nmant)

    def token(s, row):
        x_t, b_t, c_t, d_t, anew = row
        s = jnp.where(anew, 0.0, s)
        s = (jnp.exp(d_t * a)[:, None, None] * s
             + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        s = low(s)
        return s, (s * c_t[:, None, :]).sum(-1)

    anew = jnp.arange(t) == (-1 if carried or fresh_at is None else fresh_at)
    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), jnp.float32),
                        (x, b, cc, delta, anew))
    if "D" not in omit:
        y = y + w["D"][:, None] * x
    y = y.reshape(t, c)
    if "gate" not in omit:
        y = y * jax.nn.silu(z)
    groups = 1 if "group_norm" in omit else g
    y = y.reshape(t, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    return mm(y.reshape(t, c) * w["gate_norm"], w["out_proj"])


def rotate(x, pos, theta: float):
    """The whole head turned to ``pos`` in the half-split pairing (what
    the model is ASSUMED not to do)."""
    half = x.shape[-1] // 2
    f = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(f, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(cfg, w, u, rotated=False, block=256):
    """Grouped-query attention over one sequence, every query head with
    its own copy of its key-value head; causal, no positional term."""
    t = u.shape[0]
    heads, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    q = mm(u, w["q"]).reshape(t, heads, d)
    k = mm(u, w["k"]).reshape(t, kvh, d)
    v = mm(u, w["v"]).reshape(t, kvh, d)
    if rotated:
        pos = jnp.arange(t)
        q = rotate(q, pos, float(cfg["rope_theta"]))
        k = rotate(k, pos, float(cfg["rope_theta"]))
    k, v = (jnp.repeat(a, heads // kvh, axis=1) for a in (k, v))
    outs = []
    for lo in range(0, t, block):
        hi = min(t, lo + block)
        s = jnp.einsum("thd,shd->hts", q[lo:hi], k, precision=HI) * d ** -0.5
        seen = jnp.arange(t)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hts,shd->thd", p, v, precision=HI))
    return mm(jnp.concatenate(outs, 0).reshape(t, heads * d), w["o"])


def route(cfg, scores, bias, renormalize=True, top_k=None):
    """``(weights [T, k], ids [T, k])`` of float32 ``scores`` [T, experts]:
    the best ``num_experts_per_tok`` of ``scores + bias`` (ties: the lower
    id), weighted by the scores alone, renormalised and scaled."""
    scores = np.asarray(scores, np.float32)
    chosen_by = scores + np.asarray(bias, np.float32)
    ids = np.argsort(-chosen_by, axis=1, kind="stable")[
        :, :top_k or cfg["num_experts_per_tok"]]
    w = np.take_along_axis(scores, ids, axis=1)
    if renormalize and cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + np.float32(TOPK_EPS))
    return w * np.float32(cfg["routed_scaling_factor"]), ids


def moe(cfg, layer, w, u, experts, omit=frozenset(), weight_dtype=None,
        shared=True, latent=True, act="relu2", top_k=None):
    """The routed terms of ``experts`` (global ids) through the latent
    and, with ``shared``, the shared expert on the hidden."""
    f = relu2 if act == "relu2" else jax.nn.silu
    scores = jax.nn.sigmoid(mm(u, w["router"]))
    bias = (jnp.zeros_like(w["router_bias"]) if "router_bias" in omit
            else w["router_bias"])
    rw, ids = route(cfg, scores, bias, "renormalize" not in omit, top_k)
    lat, inter = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    low = mm(u, w["latent_down"]) if latent else u[:, :lat]
    r = jnp.zeros_like(low)
    for e in experts:
        if f"expert:{e}" in omit:
            continue
        we = jnp.asarray(np.where(ids == e, rw, 0.0).sum(-1), jnp.float32)
        if not bool((we > 0).any()):
            continue
        up = tensor(cfg, layer, "expert_up", (lat, inter), e, weight_dtype)
        down = tensor(cfg, layer, "expert_down", (inter, lat), e,
                      weight_dtype)
        r = r + we[:, None] * mm(f(mm(low, up)), down)
    y = mm(r, w["latent_up"]) if latent else jnp.pad(
        r, ((0, 0), (0, u.shape[1] - lat)))
    if shared and "shared" not in omit:
        y = y + mm(f(mm(u, w["shared_up"])), w["shared_down"])
    return y


def held_experts(cfg):
    return range(cfg["held_lo"], cfg["held_lo"] + cfg["experts_held"])


def forward(cfg, tokens, rows=None, omit=frozenset(), weight_dtype=None,
            experts=None, act_dtype=None, shared=True, carried=True,
            fresh_at=None, state_dtype=None, latent=True, act="relu2",
            top_k=None, rotated=False):
    """Logits [len(rows), vocab_held] of one sequence (all rows where
    ``rows`` is None), float32. ``experts``: the routed experts whose
    terms are added (default: the held range); ``shared``: whether the
    shared expert's is."""
    eps = cfg["layer_norm_epsilon"]
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
            w = block_weights(cfg, layer, weight_dtype)
            u = low(rms_norm(x, w["norm"], eps))
            kind = cfg["hybrid_override_pattern"][layer]
            if kind == "M":
                y = mamba2(cfg, w, u, omit, carried, fresh_at, state_dtype)
            elif kind == "*":
                y = attention(cfg, w, u, rotated)
            else:
                y = moe(cfg, layer, w, u, experts, omit, weight_dtype, shared,
                        latent, act, top_k)
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
