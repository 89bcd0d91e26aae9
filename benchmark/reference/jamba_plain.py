"""Jamba's forward pass, plain: ``jax.numpy``, float32, matrix products at
``highest`` precision, the selective scan as a ``lax.scan`` over tokens,
no cache, no slots, no packing, no kernels, one layer's weights alive at a
time. Imports nothing of the program.

It follows the published description (config.json and the ``jamba`` model
code of https://huggingface.co/ai21labs/AI21-Jamba2-3B). Layer ``i`` is an
attention layer where ``i % attn_layer_period == attn_layer_offset`` and a
Mamba layer otherwise; ``num_experts`` 1 makes every feed-forward a dense
SwiGLU. Per layer, pre-norm residual, RMSNorm with a learned gain:

* **Mamba** (Mamba-1 with Jamba's three inner norms): ``[u, z] = W_in h``;
  ``u_t <- silu(b_c + sum_k w_c[k] * u_{t-3+k})`` per channel, causal;
  ``[d, B, C] = W_x u`` (dt_rank, d_state, d_state), each RMS-normed;
  ``D_t = softplus(W_dt d + b_dt)``; ``A = -exp(A_log)``;
  ``s_t = exp(D_t (x) A) * s_{t-1} + (D_t * u_t) (x) B_t``;
  ``y_t = s_t . C_t + D * u_t``; out = ``W_out (y * silu(z))``.
* **Attention**: ``num_attention_heads`` query heads, ONE key-value head
  that all share, no bias, NO positional term, scale head_dim^-1/2, causal.
* **Feed-forward**: ``W_down (silu(W_gate h) * (W_up h))``.

After the last layer a final norm; logits = ``norm(x) . E^T`` with the
embedding ``E`` (tied). Departures, all of them the configuration's and
none of them arithmetic:

* the layouts keep ``d_inner`` last: ``conv_w`` [d_conv, d_inner] and
  ``A_log`` [d_state, d_inner] are the published [d_inner, 1, d_conv] and
  [d_inner, d_state] transposed; linear weights are [in, out];
* weights are seeded (``tensor``): ``normal * initializer_range`` rounded to
  bfloat16, norm gains and ``D`` ``1 + that``; ``conv_w`` uniform in
  +-d_conv^-1/2 (the depthwise convolution's default in Mamba's code);
  ``A_log = log(1..d_state)`` per channel + ``normal * initializer_range``;
  ``b_dt`` the inverse softplus of ``exp(uniform * (log 0.1 - log 0.001) +
  log 0.001)`` (Mamba's own initialisation of the step size), all read
  here as the float32 values the bfloat16 tensors are. ``weight_dtype``
  rounds them once more, and ``scan_dtype`` runs the recurrence in that
  type (the decay ``exp(D_t (x) A)``, the input term and the state each
  rounded to it: what a scan left in the model's bfloat16 would compute).
  These are the two lower-precision readings that the comparison has to
  refuse;
* ``vocab_held`` rows of the embedding.

``omit`` leaves one term out, for the tests that show the comparison
notices: ``conv`` (the three earlier taps), ``state`` (the recurrence:
``y_t = D * u_t``), ``gate`` (``silu(z)``), ``inner_norms``, ``prefix_kv``
(attention to the first half of the sequence).
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

GLOBAL_LAYER = 1_000_000
HI = jax.lax.Precision.HIGHEST
DT_MIN, DT_MAX = 0.001, 0.1


def dims(cfg) -> dict:
    h = cfg["hidden_size"]
    return dict(h=h, inter=cfg["intermediate_size"],
                c=cfg["mamba_expand"] * h, n=cfg["mamba_d_state"],
                r=cfg["mamba_dt_rank"], k=cfg["mamba_d_conv"],
                heads=cfg["num_attention_heads"],
                hd=h // cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"])


def is_attention(cfg, layer: int) -> bool:
    return layer % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def tensor(cfg, layer, name, shape, weight_dtype=None):
    key = jax.random.fold_in(jax.random.PRNGKey(cfg["weights_seed"]), layer)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    std = cfg["initializer_range"]
    if name == "conv_w":
        bound = shape[0] ** -0.5
        w = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(DT_MAX) - math.log(DT_MIN))
                     + math.log(DT_MIN))
        w = dt + jnp.log(-jnp.expm1(-dt))
    else:
        w = jax.random.normal(key, shape, jnp.float32) * std
        if name == "A_log":
            w = w + jnp.log(jnp.arange(1, shape[0] + 1,
                                       dtype=jnp.float32))[:, None]
        elif name.endswith("norm") or name == "D":
            w = 1.0 + w
    w = w.astype(jnp.bfloat16)
    if weight_dtype is not None:
        w = w.astype(weight_dtype)
    return w.astype(jnp.float32)


def layer_shapes(cfg, layer: int) -> dict[str, tuple]:
    d = dims(cfg)
    h, c, n, r = d["h"], d["c"], d["n"], d["r"]
    out = {"in_norm": (h,), "ff_norm": (h,),
           "ff_gate": (h, d["inter"]), "ff_up": (h, d["inter"]),
           "ff_down": (d["inter"], h)}
    if is_attention(cfg, layer):
        out.update(q=(h, d["heads"] * d["hd"]), k=(h, d["kv"] * d["hd"]),
                   v=(h, d["kv"] * d["hd"]), o=(d["heads"] * d["hd"], h))
    else:
        out.update(in_proj=(h, 2 * c), conv_w=(d["k"], c), conv_b=(c,),
                   x_proj=(c, r + 2 * n), dt_norm=(r,), b_norm=(n,),
                   c_norm=(n,), dt_proj=(r, c), dt_bias=(c,),
                   A_log=(n, c), D=(c,), out_proj=(c, h))
    return out


def layer_weights(cfg, layer, weight_dtype=None):
    return {name: tensor(cfg, layer, name, shape, weight_dtype)
            for name, shape in layer_shapes(cfg, layer).items()}


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def mamba(cfg, w, x, omit=frozenset(), scan_dtype=None):
    """The Mamba mixer over one sequence x [T, hidden], from a zero state."""
    d = dims(cfg)
    c, n, r, k = d["c"], d["n"], d["r"], d["k"]
    eps = cfg["rms_norm_eps"]
    t = x.shape[0]
    uz = mm(x, w["in_proj"])
    u, z = uz[:, :c], uz[:, c:]
    padded = jnp.concatenate([jnp.zeros((k - 1, c), jnp.float32), u], axis=0)
    taps = range(k - 1, k) if "conv" in omit else range(k)
    u = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][j] * padded[j:j + t] for j in taps))
    dbc = mm(u, w["x_proj"])
    delta, b, cc = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    if "inner_norms" not in omit:
        delta = rms_norm(delta, w["dt_norm"], eps)
        b = rms_norm(b, w["b_norm"], eps)
        cc = rms_norm(cc, w["c_norm"], eps)
    dt = jax.nn.softplus(mm(delta, w["dt_proj"]) + w["dt_bias"])
    a = -jnp.exp(w["A_log"])

    def low(v):
        """The recurrence's precision: float32 unless asked.
        ``reduce_precision`` and not a pair of casts, which XLA is free to
        drop on a TPU (excess precision is allowed there)."""
        if scan_dtype is None:
            return v
        info = jnp.finfo(scan_dtype)
        return jax.lax.reduce_precision(v, info.nexp, info.nmant)

    def step(s, row):
        u_t, dt_t, b_t, c_t = row
        s = low(low(jnp.exp(dt_t[None, :] * a)) * s
                + low((dt_t * u_t)[None, :] * b_t[:, None]))
        return s, jnp.sum(s * c_t[:, None], axis=0)

    if "state" in omit:
        y = jnp.zeros_like(u)
    else:
        _, y = jax.lax.scan(step, jnp.zeros((n, c), jnp.float32),
                            (u, dt, b, cc))
    y = y + w["D"] * u
    if "gate" not in omit:
        y = y * jax.nn.silu(z)
    return mm(y, w["out_proj"])


def attention(cfg, w, x, omit=frozenset(), block=256):
    """Causal attention over one sequence, every query head against the
    one key-value head, no positional term."""
    d = dims(cfg)
    t, heads, hd = x.shape[0], d["heads"], d["hd"]
    if d["kv"] != 1:
        raise ValueError("this reference is written for one key-value head")
    q = mm(x, w["q"]).reshape(t, heads, hd)
    k, v = mm(x, w["k"]), mm(x, w["v"])
    outs = []
    for lo in range(0, t, block):
        hi = min(t, lo + block)
        s = jnp.einsum("thd,sd->hts", q[lo:hi], k, precision=HI) * hd ** -0.5
        col, row = jnp.arange(t)[None, :], jnp.arange(lo, hi)[:, None]
        seen = col <= row
        if "prefix_kv" in omit:
            seen = seen & ((col >= t // 2) | (col == row))
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hts,sd->thd", p, v, precision=HI))
    return mm(jnp.concatenate(outs, 0).reshape(t, heads * hd), w["o"])


def swiglu(x, gate, up, down):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def forward(cfg, tokens, rows=None, omit=frozenset(), weight_dtype=None,
            scan_dtype=None):
    """Logits [len(rows), vocab_held] of one sequence (all rows where
    ``rows`` is None), float32."""
    if cfg["num_experts"] != 1 or not cfg["tie_word_embeddings"]:
        raise ValueError("this reference is written for a dense "
                         "feed-forward and a tied head")
    eps = cfg["rms_norm_eps"]
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        embed = tensor(cfg, GLOBAL_LAYER, "embed",
                       (cfg["vocab_held"], cfg["hidden_size"]), weight_dtype)
        x = embed[tokens]
        for layer in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, layer, weight_dtype)
            h = rms_norm(x, w["in_norm"], eps)
            if is_attention(cfg, layer):
                x = x + attention(cfg, w, h, omit)
            else:
                x = x + mamba(cfg, w, h, omit, scan_dtype)
            x = x + swiglu(rms_norm(x, w["ff_norm"], eps), w["ff_gate"],
                           w["ff_up"], w["ff_down"])
            del w
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, tensor(cfg, GLOBAL_LAYER, "final_norm",
                               (cfg["hidden_size"],), weight_dtype), eps)
        return mm(x, embed.T)
