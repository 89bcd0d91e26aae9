"""DeepSeek-V2's forward pass, plain: ``jax.numpy``, float32, matrix
products at ``highest`` precision, no cache, no batching, no kernels,
attention with materialised heads (never absorbed), a loop over the held
experts, one layer's weights alive at a time. Imports nothing of the
program.

It follows the published description (config.json and modeling code of
https://huggingface.co/deepseek-ai/DeepSeek-V2). Departures, all of them
the configuration's stated cut and none of them arithmetic:

* one chip's share of an expert-parallel layer: the router scores ALL
  ``n_routed_experts``; only the experts in ``experts`` (the held routing
  group) add their terms, plus the shared experts; that partial sum goes
  on to the next layer;
* ``vocab_held`` rows of the embedding and columns of the head;
* ``num_hidden_layers`` as the configuration gives it;
* weights are ``normal(key) * initializer_range`` rounded to bfloat16
  (norm gains: 1 + that), ``key = fold_in(fold_in(fold_in(PRNGKey(seed),
  layer), crc32(name)), expert)``, read here as the float32 values they
  are. ``weight_dtype`` rounds them once more (the lower-precision
  reading that the comparison has to refuse);
* rope rotates the pairs (2i, 2i+1) in place. The published code first
  gathers even and odd lanes into halves and rotates the halves; query
  and key get the same permutation, so every score is the same;
* a tie between groups or experts goes to the lower index.

``omit`` leaves one term out, for the tests that show the comparison
notices: ``shared``, ``rope``, ``routed_scale``, ``expert:<id>``.
``route_margin`` says how near each token came to a routing decision that
moves its held experts: the comparison excuses a token whose logits
differ only there (``lm_compare``).
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

GLOBAL_LAYER = 1_000_000
HI = jax.lax.Precision.HIGHEST


def tensor(cfg, layer, name, shape, expert=None, weight_dtype=None):
    key = jax.random.fold_in(jax.random.PRNGKey(cfg["weights_seed"]), layer)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if expert is not None:
        key = jax.random.fold_in(key, expert)
    w = jax.random.normal(key, shape, jnp.float32) * cfg["initializer_range"]
    if name.endswith("norm"):
        w = 1.0 + w
    w = w.astype(jnp.bfloat16)
    if weight_dtype is not None:
        w = w.astype(weight_dtype)
    return w.astype(jnp.float32)


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg[
        "rope_theta"]
    freq = np.array([base ** (-2.0 * i / dim) for i in range(dim // 2)])

    def dim_of(turns):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        keep = 1.0 - ramp  # 1: the dimension keeps its own frequency
        out.append(freq[i] / rs["factor"] * (1 - keep) + freq[i] * keep)
    return np.asarray(out, np.float32)


def rope(x, pos, cfg):
    """x [T, ..., rope]; pairs (2i, 2i+1) turn by pos * inv_freq[i]."""
    rs = cfg["rope_scaling"]
    m = (yarn_mscale(rs["factor"], rs["mscale"])
         / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(cfg))
    while ang.ndim < x.ndim:
        ang = ang[:, None]
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def attention(cfg, w, x, pos, omit=frozenset(), block=256):
    """Causal MLA over one sequence x [T, hidden], heads materialised."""
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    nope, rp, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    rs = cfg["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    scale = (nope + rp) ** -0.5 * m * m
    c_q = rms_norm(mm(x, w["q_a"]), w["q_a_norm"], cfg["rms_norm_eps"])
    q = mm(c_q, w["q_b"]).reshape(t, h, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, cfg)
    kv = mm(x, w["kv_a"])
    c_kv = rms_norm(kv[:, :rank], w["kv_a_norm"], cfg["rms_norm_eps"])
    k_rope = rope(kv[:, rank:], pos, cfg)
    kvb = mm(c_kv, w["kv_b"]).reshape(t, h, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    outs = []
    for lo in range(0, t, block):
        hi = min(t, lo + block)
        s = jnp.einsum("thd,shd->hts", q_nope[lo:hi], k_nope, precision=HI)
        if "rope" not in omit:
            s = s + jnp.einsum("thd,sd->hts", q_rope[lo:hi], k_rope,
                               precision=HI)
        s = s * scale
        causal = jnp.arange(t)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hts,shv->thv", p, v, precision=HI))
    return mm(jnp.concatenate(outs, 0).reshape(t, h * vd), w["o"])


def swiglu(x, gate, up, down):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def route(cfg, scores):
    """scores [T, E] -> (weights [T, k], expert ids [T, k]), host side:
    group score = best expert of the group; keep the ``topk_group`` best
    groups; zero the rest; take the ``num_experts_per_tok`` best."""
    scores = np.asarray(scores, np.float32)
    t, e = scores.shape
    g, per = cfg["n_group"], e // cfg["n_group"]
    group = scores.reshape(t, g, per).max(-1)
    keep = np.argsort(-group, axis=1, kind="stable")[:, :cfg["topk_group"]]
    mask = np.zeros((t, g), bool)
    np.put_along_axis(mask, keep, True, axis=1)
    masked = np.where(np.repeat(mask, per, axis=1), scores, 0.0)
    ids = np.argsort(-masked, axis=1, kind="stable")[
        :, :cfg["num_experts_per_tok"]]
    w = np.take_along_axis(masked, ids, axis=1)
    if cfg["norm_topk_prob"] and cfg["num_experts_per_tok"] > 1:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w, ids


def route_margin(cfg, scores):
    """For each token, the log ratio of router scores by which the nearest
    routing decision is made that changes which HELD experts serve it: the
    held group entering or leaving the kept groups, the kept groups
    changing while the held one is among them, a held expert entering or
    leaving the ``num_experts_per_tok`` best. A path in another precision
    decides the same wherever this is wider than its rounding."""
    s = np.log(np.maximum(np.asarray(scores, np.float64), 1e-300))
    t, e = s.shape
    g, per, held = cfg["n_group"], e // cfg["n_group"], cfg["held_group"]
    k, kg = cfg["num_experts_per_tok"], cfg["topk_group"]
    group = s.reshape(t, g, per).max(-1)
    best = -np.sort(-group, axis=1)
    last_kept = best[:, kg - 1]
    first_cut = best[:, kg] if kg < g else np.full(t, -np.inf)
    kept = group[:, held] >= last_kept
    margin = np.where(kept, last_kept - first_cut,
                      last_kept - group[:, held])
    mask = np.repeat(group >= last_kept[:, None], per, axis=1)
    ranked = -np.sort(-np.where(mask, s, -np.inf), axis=1)
    if k < e:
        mine = s[:, held * per:(held + 1) * per]
        chosen = mine >= ranked[:, k - 1:k]
        gap = np.where(chosen, mine - ranked[:, k:k + 1],
                       ranked[:, k - 1:k] - mine).min(axis=1)
        margin = np.where(kept, np.minimum(margin, gap), margin)
    return margin


def moe(cfg, layer, w, x, experts, omit=frozenset(), weight_dtype=None,
        margins=None):
    """The routed terms of ``experts`` (global ids) plus the shared
    experts. ``margins``: a list that gains this layer's
    ``route_margin``."""
    scores = jax.nn.softmax(mm(x, w["router"]), axis=-1)
    rw, ids = route(cfg, scores)
    if margins is not None:
        margins.append(route_margin(cfg, scores))
    if not cfg["norm_topk_prob"] and "routed_scale" not in omit:
        rw = rw * cfg["routed_scaling_factor"]
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


def layer_weights(cfg, layer, weight_dtype=None):
    """Every tensor of the layer but the routed experts' (made one at a
    time inside ``moe``)."""
    h, hd = cfg["hidden_size"], cfg["num_attention_heads"]
    shapes = {
        "input_norm": (h,), "post_norm": (h,),
        "q_a": (h, cfg["q_lora_rank"]), "q_a_norm": (cfg["q_lora_rank"],),
        "q_b": (cfg["q_lora_rank"],
                hd * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])),
        "kv_a": (h, cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]),
        "kv_a_norm": (cfg["kv_lora_rank"],),
        "kv_b": (cfg["kv_lora_rank"],
                 hd * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])),
        "o": (hd * cfg["v_head_dim"], h),
    }
    if layer < cfg["first_k_dense_replace"]:
        i = cfg["intermediate_size"]
        shapes.update(mlp_gate=(h, i), mlp_up=(h, i), mlp_down=(i, h))
    else:
        s = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
        shapes.update(router=(h, cfg["n_routed_experts"]), shared_gate=(h, s),
                      shared_up=(h, s), shared_down=(s, h))
    return {n: tensor(cfg, layer, n, s, None, weight_dtype)
            for n, s in shapes.items()}


def held_experts(cfg):
    per = cfg["n_routed_experts"] // cfg["n_group"]
    return range(cfg["held_group"] * per, (cfg["held_group"] + 1) * per)


def forward(cfg, tokens, rows=None, omit=frozenset(), weight_dtype=None,
            margins=False):
    """Logits [len(rows), vocab_held] of one sequence (all rows where
    ``rows`` is None), float32; with ``margins`` also each of those rows'
    least ``route_margin`` over the expert layers."""
    per_layer = [] if margins else None
    tokens = jnp.asarray(tokens, jnp.int32)
    pos = jnp.arange(tokens.shape[0])
    with jax.default_matmul_precision("highest"):
        x = tensor(cfg, GLOBAL_LAYER, "embed",
                   (cfg["vocab_held"], cfg["hidden_size"]), None,
                   weight_dtype)[tokens]
        for layer in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, layer, weight_dtype)
            x = x + attention(
                cfg, w, rms_norm(x, w["input_norm"], cfg["rms_norm_eps"]),
                pos, omit)
            h = rms_norm(x, w["post_norm"], cfg["rms_norm_eps"])
            if "router" in w:
                x = x + moe(cfg, layer, w, h, held_experts(cfg), omit,
                            weight_dtype, per_layer)
            else:
                x = x + swiglu(h, w["mlp_gate"], w["mlp_up"], w["mlp_down"])
            del w
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, tensor(cfg, GLOBAL_LAYER, "final_norm",
                               (cfg["hidden_size"],), None, weight_dtype),
                     cfg["rms_norm_eps"])
        logits = mm(x, tensor(cfg, GLOBAL_LAYER, "head",
                              (cfg["hidden_size"], cfg["vocab_held"]), None,
                              weight_dtype))
    if not margins:
        return logits
    least = np.min(per_layer, axis=0)
    return logits, least if rows is None else least[np.asarray(rows)]
