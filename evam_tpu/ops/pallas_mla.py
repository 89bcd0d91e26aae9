"""Pallas TPU kernel: latent (MLA, absorbed) attention of many query rows
over ONE shared sequence of cached latent rows, never materialising the
scores.

The prefill chunk of the generate engine (models/lm/deepseek_v2.py) asks,
for every token and head, a softmax over up to ~3 k cached rows: the
shared instruction prefix, the sequence's own earlier rows and the chunk's
own rows. Through XLA that is a [heads, tokens, rows] float32 tensor per
layer (0.8 GB at the published widths), written and read several times:
the step was bound by that traffic, not by its products (PERF.md section
6, PR 28). Here a block of query rows keeps its running maximum, sum and
output in VMEM while the key blocks stream past (the online softmax of
flash attention), so the scores never leave the chip.

Every query row is ``[latent | rope]`` (the query folded through
``W_uk``, and its rope part); every key row is a cache row ``[c_kv |
k_r]``; the value of a key row is its ``c_kv``. The rope parts come as
they lie in a stored row (models/lm/mla.py): the row's last lane tile, the
rope values and zeros behind them, 128 wide at the published widths, so
nothing is padded to lanes here. All heads share the keys,
so the rows of all heads of all tokens are one long list of queries. Which
keys a query row may see is three half-open intervals of key positions,
``[0, a) | [b0, b1) | [c0, c1)``, per row (``bounds`` [rows, 4] = a, b1,
c0, c1; b0 is static): the prefix rows, the sequence's earlier rows, and
the chunk's own rows up to the token itself. A row whose intervals are
empty (a padded token) comes out 0.

``latent_attention_xla`` is the same arithmetic through XLA; the CPU
tests run it, and check the kernel against it in the interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30


def _visible(col, bounds, b0):
    """The one visibility rule: ``bounds`` [rows, 4] = a, b1, c0, c1 for
    ``[0, a) | [b0, b1) | [c0, c1)``, or [rows, 6] with a first visible
    row of the two leading intervals behind them, a_lo and b_lo (a window:
    models/lm/common.py ``chunk_bounds``)."""
    a, b1, c0, c1 = (bounds[:, i:i + 1] for i in range(4))
    if bounds.shape[1] == 4:
        return ((col < a) | ((col >= b0) & (col < b1))
                | ((col >= c0) & (col < c1)))
    a_lo, b_lo = bounds[:, 4:5], bounds[:, 5:6]
    return (((col >= a_lo) & (col < a)) | ((col >= b_lo) & (col < b1))
            | ((col >= c0) & (col < c1)))


def _kernel(bounds_ref, q_lat_ref, q_rope_ref, ckv_ref, kr_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale, block_k, b0):
    from jax.experimental import pallas as pl

    kv = pl.program_id(1)

    @pl.when(kv == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    ckv = ckv_ref[...]
    contract_last = (((1,), (1,)), ((), ()))
    s = (jax.lax.dot_general(q_lat_ref[...], ckv, contract_last,
                             preferred_element_type=F32)
         + jax.lax.dot_general(q_rope_ref[...], kr_ref[...], contract_last,
                               preferred_element_type=F32)) * scale
    col = kv * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = _visible(col, bounds_ref[...], b0)
    s = jnp.where(ok, s, NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p.astype(ckv.dtype), ckv, (((1,), (0,)), ((), ())),
        preferred_element_type=F32)
    m_ref[...] = m_new

    @pl.when(kv == pl.num_programs(1) - 1)
    def _():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "b0", "block_q", "block_k", "interpret"))
def latent_attention(q_lat, q_rope, ckv, kr, bounds, *, scale, b0,
                     block_q=1024, block_k=512, interpret=False):
    """``q_lat`` [R, C], ``q_rope`` [R, P], ``ckv`` [S, C], ``kr`` [S, P],
    ``bounds`` [R, 4 | 6] int32 -> [R, C] (the attention-weighted ``ckv``).
    R and S are padded here to whole blocks; P is taken as it comes (a
    stored row's last lane tile: 128)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, c = q_lat.shape
    s = ckv.shape[0]
    block_q = min(block_q, -(-r // 16) * 16)
    block_k = min(block_k, -(-s // 128) * 128)
    rp, sp = -(-r // block_q) * block_q, -(-s // block_k) * block_k
    p = q_rope.shape[1]

    def pad(x, rows):
        return jnp.pad(x, ((0, rows - x.shape[0]), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k, b0=b0),
        grid=(rp // block_q, sp // block_k),
        in_specs=[
            pl.BlockSpec((block_q, bounds.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, c), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, p), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, c), lambda i, j: (j, 0)),
            pl.BlockSpec((block_k, p), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, c), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, c), q_lat.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 1), F32),
                        pltpu.VMEM((block_q, 1), F32),
                        pltpu.VMEM((block_q, c), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="mla_latent_attention",
        interpret=interpret,
    )(pad(bounds, rp), pad(q_lat, rp), pad(q_rope, rp), pad(ckv, sp),
      pad(kr, sp))
    return out[:r]


def latent_attention_xla(q_lat, q_rope, ckv, kr, bounds, *, scale, b0):
    """The same through XLA, scores materialised (float32 softmax)."""
    hi = dict(preferred_element_type=F32)
    if jax.default_backend() != "tpu":  # see models/lm/common.py es
        q_lat, q_rope, ckv, kr = (x.astype(F32) for x in (
            q_lat, q_rope, ckv, kr))
        hi = {}
    s = (jnp.einsum("rc,sc->rs", q_lat, ckv, **hi)
         + jnp.einsum("rp,sp->rs", q_rope, kr, **hi)) * scale
    ok = _visible(jnp.arange(ckv.shape[0])[None, :], bounds, b0)
    m = jnp.where(ok, s, NEG).max(axis=1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s - m), 0.0)
    l = p.sum(axis=1, keepdims=True)
    p = (p / jnp.where(l > 0, l, 1.0)).astype(jnp.bfloat16)
    if not hi:
        p = p.astype(F32)
    return jnp.einsum("rs,sc->rc", p, ckv, **hi).astype(jnp.bfloat16)
