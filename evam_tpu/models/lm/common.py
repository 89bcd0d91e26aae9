"""What the language-model families share (models/lm/deepseek_v2.py,
models/lm/jamba.py, models/lm/kimi_linear.py, models/lm/lfm2_moe.py,
models/lm/laguna.py): seeded tensors, norms and products, the dense
SwiGLU, the pieces of a softmax that is split over its key rows, a packed
chunk's visibility bounds (with a first visible row under a window: only
Laguna's window layers hand one), the inputs of a short causal convolution
over a packed chunk, and the head with its top logits. The latent
attention is in models/lm/mla.py, the plain attention in
models/lm/attention.py and the expert layer in models/lm/experts.py, each
called by the families that have it.

Weights: ``key = fold_in(fold_in(PRNGKey(seed), layer), crc32(name))``;
a tensor is ``normal(key) * initializer_range`` in float32, stored
bfloat16 (gains: 1 + that), made on the device.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

from evam_tpu.ops.pallas_attention import NEG

F32 = jnp.float32
BF16 = jnp.bfloat16
#: the layer index of tensors that belong to no layer
GLOBAL_LAYER = 1_000_000
TOP_LOGITS = 8
#: None: ask the backend. A compile for a described TPU (no chip
#: attached, the backend reads "cpu") sets True.
TARGET_TPU: bool | None = None


def on_tpu() -> bool:
    return (jax.default_backend() == "tpu") if TARGET_TPU is None \
        else TARGET_TPU


# --------------------------------------------------------------- weights


def tensor_key(seed: int, layer, name: str):
    key = jax.random.fold_in(jax.random.PRNGKey(seed), layer)
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def make(key, shape, scale, gain: bool):
    w = jax.random.normal(key, shape, F32) * scale
    return ((1.0 + w) if gain else w).astype(BF16)


make_one = jax.jit(make, static_argnums=(1, 2, 3))
#: one tensor per expert id, ``fold_in(key, expert)``, on a leading axis
make_experts = jax.jit(
    lambda key, ids, shape, scale: jax.vmap(lambda e: make(
        jax.random.fold_in(key, e), shape, scale, False))(ids),
    static_argnums=(2, 3))
#: layer ``l`` of a stack written in place (the stack is donated): one
#: layer's experts at a time, never a second copy of the whole stack
put_layer = jax.jit(lambda stack, l, one: stack.at[l].set(one),
                    donate_argnums=0)


def make_layers(make_tensor, seed: int, scale: float, layers, shapes: dict,
                held=None) -> dict:
    """The tensors of ``layers`` (model layer indices), each name's
    stacked on a leading axis, ``make_tensor(layer, name, shape)`` each;
    each ``expert_*`` tensor once per expert of ``held`` (global ids) on a
    second axis, ``normal * scale``."""
    out = {}
    for name, shape in shapes.items():
        if not name.startswith("expert_"):
            out[name] = jnp.stack([make_tensor(i, name, shape)
                                   for i in layers])
            continue
        ids = jnp.asarray(list(held), jnp.uint32)
        stack = jnp.zeros((len(layers), len(ids), *shape), BF16)
        for l, i in enumerate(layers):
            stack = put_layer(stack, l, make_experts(
                tensor_key(seed, i, name), ids, shape, scale))
        out[name] = stack
    return out


def step_bias(key, shape, lo: float, hi: float):
    """The bias of a recurrence's step size (float32): the inverse
    softplus of a step drawn log-uniformly from [``lo``, ``hi``], the
    state-space families' own initialisation (a state that neither dies
    in ten tokens nor never forgets)."""
    dt = jnp.exp(jax.random.uniform(key, shape, F32)
                 * (math.log(hi) - math.log(lo)) + math.log(lo))
    return dt + jnp.log(-jnp.expm1(-dt))


def conv_silu(w, bias, taps):
    """``silu(bias + sum_k w[k] * tap_k)`` of a short causal depthwise
    convolution whose taps are given oldest first (``packed_conv_inputs``,
    or a decode row's carried inputs): float32 sums, bfloat16 out."""
    w = w.astype(F32)
    acc = bias.astype(F32)
    for k, tap in enumerate(taps):
        acc = acc + w[k] * tap.astype(F32)
    return jax.nn.silu(acc).astype(BF16)


# ---------------------------------------------------------------- layers


def rms_norm(x, gain, eps):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * gain.astype(F32)).astype(x.dtype)


def mm(x, w):
    return jnp.dot(x, w, preferred_element_type=F32).astype(BF16)


def es(expr, a, b):
    """Einsum of bfloat16 operands accumulated in float32. XLA's CPU dot
    lacks bf16 x bf16 -> f32 for some layouts; float32 operands there
    give the same sums (every product of two bfloat16 values is exact in
    float32)."""
    if not on_tpu():
        return jnp.einsum(expr, a.astype(F32), b.astype(F32))
    return jnp.einsum(expr, a, b, preferred_element_type=F32)


def swiglu(x, gate, up, down):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def softmax_sums(scale, score_expr, value_expr, q, keys, values, visible):
    """One part of a softmax that is split over its key rows: per query
    row the float32 maximum ``m`` of its ``visible`` scores, the sum
    ``l`` of ``exp(score - m)`` over them and those weights' sum of the
    rows' ``values``, ``acc``, not yet divided by ``l``. A query that
    sees no row gives ``l`` and ``acc`` 0."""
    s = es(score_expr, q, keys) * scale
    s = jnp.where(visible, s, NEG)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(visible, jnp.exp(s - m), 0.0)
    acc = es(value_expr, p.astype(BF16), values)
    return m, p.sum(axis=-1, keepdims=True), acc


def merge_softmax_sums(own, shared):
    """The two parts' sums as the one softmax's over both lists of rows
    (float32), divided through: the weighted values per query row.
    ``shared`` may be None: the own part alone."""
    m, l, acc = own
    if shared is not None:
        m_p, l_p, acc_p = shared
        top = jnp.maximum(m, m_p)
        w, w_p = jnp.exp(m - top), jnp.exp(m_p - top)
        l, acc = w * l + w_p * l_p, w * acc + w_p * acc_p
    return acc / jnp.where(l > 0, l, 1.0)


def chunk_bounds(seg, n_prefix, n_cont, prefix_rows: int, cont_rows: int,
                 window: int | None = None, prefix_first=0, xp=jnp):
    """Which rows of ``[prefix rows | continued rows | the chunk's own
    rows]`` each token of a packed chunk may see, as ops/pallas_attention.py's
    three half-open intervals ``[0, a) | [b0, b1) | [c0, c1)`` per token
    (``bounds`` [T, 4] = a, b1, c0, c1 and the static ``b0``): the
    prefix rows below ``n_prefix``; the earlier rows of the sequence
    that continues in this chunk, below ``n_cont`` and to segment 0
    only; its own segment's rows up to itself. A padded token (segment
    -1) sees nothing. ``prefix_rows`` / ``cont_rows``: how many rows the
    two lists hold (0 where there is none).

    Under a ``window`` a token sees the ``window`` positions that end at
    its own, and each interval gets a FIRST visible row: ``bounds`` [T, 6]
    = a, b1, c0, c1, a_lo, b_lo for ``[a_lo, a) | [b_lo, b1) | [c0, c1)``.
    The position of a prefix row is ``prefix_first`` + its index (the list
    may start behind the prefix's first row: what no token here can see
    need not be handed); of a continued row ``n_prefix`` + its index; of
    an own row ``n_prefix`` + its place in its sequence. ``xp``: ``numpy``
    on the host, where the engine counts a chunk's key blocks by class."""
    t = seg.shape[0]
    b0 = prefix_rows
    c_base = b0 + cont_rows
    live = seg >= 0
    idx = xp.arange(t)
    start = xp.argmax(seg[:, None] == seg[None, :], axis=1)
    before = xp.where(live & (seg == 0), n_cont if cont_rows else 0, 0)
    cols = [xp.where(live, (n_prefix - prefix_first) if prefix_rows else 0,
                      0),
            b0 + before,
            xp.where(live, c_base + start, 0),
            xp.where(live, c_base + idx + 1, 0)]
    if window is not None:
        # the first position the token sees, counted from the prefix's end
        lo = before + idx - start - window + 1
        cols[2] = xp.where(
            live, c_base + xp.maximum(start, idx - window + 1), 0)
        cols += [xp.maximum(lo + n_prefix - prefix_first, 0),
                 b0 + xp.maximum(lo, 0)]
    return xp.stack(cols, axis=1).astype(xp.int32), b0


def packed_conv_inputs(u_pre, seg, conv0, k1: int):
    """A causal depthwise convolution of ``k1 + 1`` taps over a packed
    chunk, each segment continuing from inputs it carried in. ``u_pre``
    [T, C] the chunk's inputs, ``seg`` [T] (a segment's tokens are
    contiguous; -1: a dead row), ``conv0`` [S, k1 * C] each segment's last
    ``k1`` inputs before this chunk, taps side by side. Returns the taps
    of every token, oldest first (``k1`` arrays [T, C]; the newest tap is
    ``u_pre`` itself), and ``conv_end`` [S, k1 * C]: each segment's last
    ``k1`` inputs after its last token here (its own rows, and the
    carried ones where it has fewer)."""
    t, c = u_pre.shape
    n_seg = conv0.shape[0]
    # tap d back of token t: the chunk's own row t - d where the segment
    # has one, else the segment's carried input
    s = jnp.maximum(seg, 0)
    start = jnp.argmax(seg[:, None] == seg[None, :], axis=1)
    off = jnp.arange(t) - start
    carried = conv0.reshape(n_seg * k1, c)
    taps = []
    for d in range(k1, 0, -1):
        own = jnp.pad(u_pre, ((d, 0), (0, 0)))[:t]
        old = carried[s * k1 + jnp.clip(k1 - d + off, 0, k1 - 1)]
        taps.append(jnp.where((off >= d)[:, None], own, old))
    seg_ids = jnp.arange(n_seg)
    mine = seg[None, :] == seg_ids[:, None]
    count = mine.sum(axis=1)
    first = jnp.argmax(mine, axis=1)
    rows = []
    for r in range(k1):
        at = count + r - k1  # index among the segment's own rows
        own = u_pre[jnp.clip(first + at, 0, t - 1)]
        old = carried[seg_ids * k1 + jnp.clip(count + r, 0, k1 - 1)]
        rows.append(jnp.where((at >= 0)[:, None], own, old))
    return taps, jnp.concatenate(rows, axis=1)


def row_width(values: int) -> int:
    """The width a page cache stores a row of ``values`` values in: whole
    lane tiles of 128, the values first and zeros behind them. Rows of
    another width the TPU's compiler keeps tokens-minor in HBM, and every
    step program then copies the whole cache to rows-minor and back."""
    return -(-values // 128) * 128


def page_rows(layer_cache, pages):
    """The cache rows of ``pages``, in order: [len(pages) * page, width]
    (None where there are no pages)."""
    if pages is None:
        return None
    return layer_cache[pages].reshape(-1, layer_cache.shape[-1])


def layer_page_rows(cache, layer, ids):
    """The rows of ``layer``'s pages ``ids`` of the WHOLE cache ``[layers,
    pages, page_tokens, width]``, in order: [..., len(ids) * page, width]
    (None where there are no pages). One gather out of the array as it
    lies: ``cache[layer]`` first would copy that layer out every time, a
    step's layers together the whole cache."""
    if ids is None:
        return None
    return cache[layer, ids].reshape(*ids.shape[:-1], -1, cache.shape[-1])


def top_logits(logits):
    """Per row of float32 ``logits`` [rows, vocab] the ``TOP_LOGITS``
    largest, descending, and their ids, among equal logits the lowest id
    first: what ``jax.lax.top_k`` returns, bit for bit, without the sort
    of the whole row that it becomes inside a step program on a TPU
    (3.2 ms for 64 rows of 65 536). Each round takes the row's maximum
    and the lowest id that holds it, and masks that one position; XLA
    folds a round's mask into the next round's two reductions, so the
    logits are read sixteen times and nothing is written back.

    A row with fewer than ``TOP_LOGITS`` logits above -inf gets its -inf
    values right and unspecified ids beside them. A NaN in a row leaves
    the whole row unspecified (``lax.top_k`` would rank the NaN first);
    a NaN logit is the model's fault either way."""
    n = logits.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    top, ids = [], []
    for _ in range(TOP_LOGITS):
        best = jnp.max(logits, -1, keepdims=True)
        first = jnp.min(jnp.where(logits == best, idx, n), -1, keepdims=True)
        top.append(best)
        ids.append(first)
        logits = jnp.where(idx == first, -jnp.inf, logits)
    return jnp.concatenate(top, -1), jnp.concatenate(ids, -1)


def head(x, gain, eps, w, tied: bool = False):
    """Float32 logits, and per row the ``TOP_LOGITS`` largest with their
    ids (the first is the greedy sample). ``w`` [hidden, vocab], or with
    ``tied`` the embedding itself, [vocab, hidden]."""
    with jax.named_scope("head"):
        x = rms_norm(x, gain, eps)
        if tied:
            logits = es("th,vh->tv", x, w)
        else:
            logits = jnp.dot(x, w, preferred_element_type=F32)
        top, ids = top_logits(logits)
    return logits, top, ids
