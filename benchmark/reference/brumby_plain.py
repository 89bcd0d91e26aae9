"""Brumby's forward pass, plain: ``jax.numpy``, float32, matrix products at
``highest`` precision, the mixer in its ATTENTION form with every query head
materialised, no state, no kernels, no batching, no packing, one layer's
weights alive at a time, the scores computed in blocks of query rows.
Imports nothing of the program.

It follows the published description (config.json of
https://huggingface.co/manifestai/Brumby-14B-Base, ``model_type: brumby``:
Qwen3-14B's skeleton; Manifest AI, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239, and the model's release notes). Layer ``i``
(0-based): ``x <- x + mixer_i(input_norm_i(x))``, ``x <- x +
W_down(silu(W_gate n) * W_up n)`` with ``n = post_norm_i(x)``, every norm an
RMSNorm with a learned gain (``rms_norm_eps``), no bias but the gate's.

* ``q = W_q u`` as ``num_attention_heads`` heads of ``head_dim``, ``k = W_k
  u``, ``v = W_v u`` as ``num_key_value_heads``; ``q <- rot(q_norm(q))``,
  ``k <- rot(k_norm(k))``: an RMSNorm over each head's values, one gain of
  ``head_dim`` a projection, then the rotation of the pairs ``(x_j, x_{j +
  head_dim / 2})`` by ``pos * rope_theta^(-2j / head_dim)``.
* a log gate per key-value head and token: ``l_t = log sigmoid(w_g . u_t +
  b_g)``, ``G_t`` its running sum over the sequence.
* query head ``h`` reads key-value head ``h // (heads / kv heads)``; for ``j
  <= i``: ``a_ij = exp(G_i - G_j) (q_i . k_j)^2``, ``y_i = sum_j a_ij v_j /
  (sum_j a_ij + 1e-6)``. No softmax and no maximum: the degree is even. A
  scalar scale on the scores would cancel, so there is none.
* out = ``W_o [y_1 ... y_heads]``.

After the last layer the final norm; logits = ``norm(x) . W_head`` over the
``vocab_held`` columns (untied).

**What the published config does not carry, ASSUMED** (the configuration
file's ``assumed`` says the same, each with its ground):

1. the degree, 2: the release's setting (its state is ``128 * 129 / 2`` =
   8256 wide);
2. one gate a key-value head and token, ``log sigmoid`` of a biased linear
   read of the normed hidden, float32;
3. the division by the running sum of the weights (the published inference
   kernel carries a ``sum_of_keys`` beside its state), ``+ 1e-6``;
4. Qwen3's conventions, whose keys the config carries: head norms BEFORE
   the rotation, the rotate-half pairing, no bias, an untied head;
5. the state and its sum in float32 (the served path's; this form has no
   state at all);
6. the seeding: ``b_g`` the logit of a decay ``1 - 1 / tau``, ``tau`` drawn
   log-uniformly from [``gate_memory_min``, ``gate_memory_max``] tokens;
   gains ``1 + 0.02 n``; everything else ``N(0, initializer_range)``.

Departures, all of them the configuration's and none of them arithmetic:
linear weights are [in, out]; weights are seeded, read here as the float32
values the bfloat16 tensors are. The readings that the comparison has to
refuse: ``weight_dtype`` rounds the weights once more; ``degree=4``;
``gated=False`` (every decay 1); ``normalised=False`` (no division);
``rotated=False``; ``state_dtype`` computes the mixer in its RECURRENT form
instead, token by token, the state and its sum rounded to that type after
every token (with float32 it is the attention form again, to rounding).
``act_dtype`` rounds the residual stream and every block's input and output
to that type: with bfloat16 a reading of what the served path's own
precision costs, which the comparison has to accept.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

GLOBAL_LAYER = 1_000_000
HI = jax.lax.Precision.HIGHEST
EPS = 1e-6


def tensor(cfg, layer, name, shape, weight_dtype=None):
    key = jax.random.fold_in(jax.random.PRNGKey(cfg["weights_seed"]), layer)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if name == "gate_b":
        lo, hi = (math.log(cfg[k]) for k in ("gate_memory_min",
                                             "gate_memory_max"))
        w = jnp.log(jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                               lo, hi)) - 1.0)
    else:
        w = jax.random.normal(key, shape, jnp.float32) \
            * cfg["initializer_range"]
        if name.endswith("norm"):
            w = 1.0 + w
    w = w.astype(jnp.bfloat16)
    if weight_dtype is not None:
        w = w.astype(weight_dtype)
    return w.astype(jnp.float32)


def layer_shapes(cfg) -> dict[str, tuple]:
    h, d, i = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return {"input_norm": (h,), "post_norm": (h,), "q": (h, q), "k": (h, kv),
            "v": (h, kv), "o": (q, h), "q_norm": (d,), "k_norm": (d,),
            "gate_w": (h, cfg["num_key_value_heads"]),
            "gate_b": (cfg["num_key_value_heads"],),
            "mlp_gate": (h, i), "mlp_up": (h, i), "mlp_down": (i, h)}


def layer_weights(cfg, layer, weight_dtype=None):
    return {name: tensor(cfg, layer, name, shape, weight_dtype)
            for name, shape in layer_shapes(cfg).items()}


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def swiglu(x, gate, up, down):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def rotate(x, pos, theta: float):
    """x [T, heads, d] turned to ``pos`` in the half-split pairing."""
    d = x.shape[-1]
    freqs = theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention_form(q, k, v, big_g, degree=2, normalised=True, block=256):
    """``q``, ``k``, ``v`` [T, heads, d] (every query head with its own copy
    of its key-value head), ``big_g`` [T, heads] the running sum of the log
    gates -> ``y`` [T, heads, d]."""
    t = q.shape[0]
    outs = []
    for lo in range(0, t, block):
        hi = min(t, lo + block)
        s = jnp.einsum("thd,shd->hts", q[lo:hi], k, precision=HI)
        at, key_at = jnp.arange(lo, hi)[:, None], jnp.arange(t)[None, :]
        decay = jnp.exp(jnp.minimum(
            big_g[lo:hi].T[:, :, None] - big_g.T[:, None, :], 0.0))
        a = jnp.where((key_at <= at)[None], s ** degree * decay, 0.0)
        y = jnp.einsum("hts,shd->thd", a, v, precision=HI)
        if normalised:
            y = y / (a.sum(-1).T[:, :, None] + EPS)
        outs.append(y)
    return jnp.concatenate(outs, 0)


def phi(x):
    """The monomials of degree 2 of the last axis, scaled so that ``phi(q) .
    phi(k) = (q . k)^2``: the squares, then ``sqrt 2 x_a x_b`` for ``a <
    b``."""
    d = x.shape[-1]
    a, b = np.triu_indices(d, 1)
    return jnp.concatenate(
        [x * x, math.sqrt(2.0) * x[..., a] * x[..., b]], axis=-1)


def recurrent_form(q, k, v, lg, group: int, state_dtype):
    """The same layer as a recurrence over the tokens (degree 2, gated,
    normalised): ``q`` [T, heads, d], ``k``, ``v`` [T, kv heads, d], ``lg``
    [T, kv heads]; the state ``S`` and the sum ``z`` rounded to
    ``state_dtype`` after every token."""
    t, heads, d = q.shape
    kvh = k.shape[1]
    wide = d * (d + 1) // 2

    def low(a):
        # not ``astype`` there and back: the compiler may keep the excess
        info = jnp.finfo(state_dtype)
        return jax.lax.reduce_precision(a, info.nexp, info.nmant)

    def step(carry, tok):
        s, z = carry
        q_t, k_t, v_t, lg_t = tok
        gate = jnp.exp(lg_t)
        pk = phi(k_t)
        s = low(gate[:, None, None] * s + pk[:, :, None] * v_t[:, None, :])
        z = low(gate[:, None] * z + pk)
        pq = phi(q_t).reshape(kvh, group, wide)
        num = jnp.einsum("kgi,kiv->kgv", pq, s, precision=HI)
        den = jnp.einsum("kgi,ki->kg", pq, z, precision=HI)
        return (s, z), (num / (den[..., None] + EPS)).reshape(heads, d)

    _, y = jax.lax.scan(
        step, (jnp.zeros((kvh, wide, d), jnp.float32),
               jnp.zeros((kvh, wide), jnp.float32)), (q, k, v, lg))
    return y


def mixer(cfg, w, x, degree=2, gated=True, normalised=True, rotated=True,
          state_dtype=None):
    t = x.shape[0]
    heads, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = rms_norm(mm(x, w["q"]).reshape(t, heads, d), w["q_norm"], eps)
    k = rms_norm(mm(x, w["k"]).reshape(t, kvh, d), w["k_norm"], eps)
    v = mm(x, w["v"]).reshape(t, kvh, d)
    if rotated:
        pos = jnp.arange(t)
        q = rotate(q, pos, float(cfg["rope_theta"]))
        k = rotate(k, pos, float(cfg["rope_theta"]))
    lg = jax.nn.log_sigmoid(mm(x, w["gate_w"]) + w["gate_b"])
    if not gated:
        lg = jnp.zeros_like(lg)
    group = heads // kvh
    if state_dtype is not None:
        y = recurrent_form(q, k, v, lg, group, state_dtype)
    else:
        k, v, big_g = (jnp.repeat(a, group, axis=1)
                       for a in (k, v, jnp.cumsum(lg, axis=0)))
        y = attention_form(q, k, v, big_g, degree, normalised)
    return mm(y.reshape(t, heads * d), w["o"])


def forward(cfg, tokens, rows=None, weight_dtype=None, degree=2, gated=True,
            normalised=True, rotated=True, act_dtype=None, state_dtype=None):
    """Logits [len(rows), vocab_held] of one sequence (all rows where
    ``rows`` is None), float32."""
    eps = cfg["rms_norm_eps"]
    tokens = jnp.asarray(tokens, jnp.int32)

    def low(a):
        if act_dtype is None:
            return a
        info = jnp.finfo(act_dtype)
        return jax.lax.reduce_precision(a, info.nexp, info.nmant)

    with jax.default_matmul_precision("highest"):
        x = tensor(cfg, GLOBAL_LAYER, "embed",
                   (cfg["vocab_held"], cfg["hidden_size"]),
                   weight_dtype)[tokens]
        for layer in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, layer, weight_dtype)
            h = low(rms_norm(x, w["input_norm"], eps))
            x = low(x + low(mixer(cfg, w, h, degree, gated, normalised,
                                  rotated, state_dtype)))
            h = low(rms_norm(x, w["post_norm"], eps))
            x = low(x + low(swiglu(h, w["mlp_gate"], w["mlp_up"],
                                   w["mlp_down"])))
            del w
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, tensor(cfg, GLOBAL_LAYER, "final_norm",
                               (cfg["hidden_size"],), weight_dtype), eps)
        logits = mm(x, tensor(cfg, GLOBAL_LAYER, "head",
                              (cfg["hidden_size"], cfg["vocab_held"]),
                              weight_dtype))
    return logits
