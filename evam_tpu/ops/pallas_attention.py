"""Pallas TPU kernel: plain key-value attention of a packed prefill chunk's
grouped queries over ONE list of cache rows a key-value head, never
materialising the scores.

The prefill chunk of a family with plain attention layers
(models/lm/attention.py; LFM2-MoE's runs this kernel) asks, for every
token and query head, a softmax over up to ~3 k cached rows: the shared
instruction prefix, the sequence's own earlier rows and the chunk's own
rows. Through
XLA that is a [tokens, heads, rows] float32 tensor a layer (193 MB at
LFM2-8B-A1B's widths), written and read several times: 1.7 ms a layer of a
chunk's 33 ms on a v5e (PERF.md section 6, PR 40). Here a block of query
rows keeps its running maximum, sum and output in VMEM while the key blocks
stream past (the online softmax of flash attention, as ops/pallas_mla.py
does for latent rows), so the scores never leave the chip.

The query heads that read one key-value head are one long list of query
rows (``q`` [kv_heads, R, d], R = tokens x group); keys and values are
``k``, ``v`` [kv_heads, S, d]; the grid's first axis walks the key-value
heads. Which keys a query row may see is ops/pallas_mla.py's three
half-open intervals per row (``bounds`` [R, 4] = a, b1, c0, c1; ``b0``
static; [R, 6] with a first visible row of the two leading intervals, for
a layer under a window: models/lm/laguna.py), the same for every
key-value head. A row whose intervals are
empty (a padded token) comes out 0.

``chunk_attention_xla`` is the same arithmetic through XLA; the CPU tests
check the kernel against it in the interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from evam_tpu.ops.pallas_mla import NEG, _visible

F32 = jnp.float32


def _kernel(bounds_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, block_k, b0):
    from jax.experimental import pallas as pl

    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    v = v_ref[...]
    s = jax.lax.dot_general(q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale
    col = kv * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = _visible(col, bounds_ref[...], b0)
    s = jnp.where(ok, s, NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=F32)
    m_ref[...] = m_new

    @pl.when(kv == pl.num_programs(2) - 1)
    def _():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "b0", "block_q", "block_k", "interpret"))
def chunk_attention(q, k, v, bounds, *, scale, b0, block_q=1024, block_k=512,
                    interpret=False):
    """``q`` [G, R, d], ``k``, ``v`` [G, S, d], ``bounds`` [R, 4 | 6] int32 ->
    [G, R, d] (the attention-weighted ``v``), ``G`` the key-value heads.
    R and S are padded here to whole blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g, r, d = q.shape
    s = k.shape[1]
    block_q = min(block_q, -(-r // 16) * 16)
    block_k = min(block_k, -(-s // 128) * 128)
    rp, sp = -(-r // block_q) * block_q, -(-s // block_k) * block_k

    def pad(x, rows):
        return jnp.pad(x, ((0, 0), (0, rows - x.shape[1]), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k, b0=b0),
        grid=(g, rp // block_q, sp // block_k),
        in_specs=[
            pl.BlockSpec((block_q, bounds.shape[1]), lambda h, i, j: (i, 0)),
            pl.BlockSpec((None, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, rp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 1), F32),
                        pltpu.VMEM((block_q, 1), F32),
                        pltpu.VMEM((block_q, d), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        name="attn_chunk_attention",
        interpret=interpret,
    )(jnp.pad(bounds, ((0, rp - r), (0, 0))), pad(q, rp), pad(k, sp),
      pad(v, sp))
    return out[:, :r]


def chunk_attention_xla(q, k, v, bounds, *, scale, b0):
    """The same through XLA, scores materialised (float32 softmax)."""
    hi = dict(preferred_element_type=F32)
    if jax.default_backend() != "tpu":  # see models/lm/common.py es
        q, k, v = (x.astype(F32) for x in (q, k, v))
        hi = {}
    s = jnp.einsum("grd,gsd->grs", q, k, **hi) * scale
    ok = _visible(jnp.arange(k.shape[1])[None, :], bounds, b0)[None]
    m = jnp.where(ok, s, NEG).max(axis=-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    p = (p / jnp.where(l > 0, l, 1.0)).astype(jnp.bfloat16)
    if not hi:
        p = p.astype(F32)
    return jnp.einsum("grs,gsd->grd", p, v, **hi).astype(jnp.bfloat16)
