"""The decode step's own-pages kernel (ops/pallas_attention.py
``decode_pages``) in the interpreter against ``attn_decode``'s XLA path,
which gathers each row's pages: the same softmax sums and the same merged
output, whatever the rows' lengths and wherever their pages lie."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evam_tpu.models.lm import attention, common
from evam_tpu.ops import pallas_attention

PAGE = 128
#: one batch: a dead row, one row, a page less one, a whole page, one row
#: into the second, the benchmark's longest, the whole table, two pages
CTX_LEN = (0, 1, 127, 128, 129, 336, 384, 256)
ROPE = attention.Rope(theta=1e6)

#: kind, head norms, gate, the table's pages, the rows' lengths
CASES = {
    # LFM2-8B-A1B: head norms and rotary positions
    "lfm2": (attention.Kind(256, 32, 8, 64, 1e-5, rope=ROPE), True, False,
             3, CTX_LEN),
    # Laguna-XS.2's full layers: a gate on every head's output
    "laguna_full": (attention.Kind(256, 48, 8, 128, 1e-6, rope=ROPE), True,
                    True, 3, CTX_LEN),
    # its window layers: nothing here lies before a window of 512
    "laguna_window": (attention.Kind(256, 64, 8, 128, 1e-6, rope=ROPE,
                                     window=512), True, True, 3, CTX_LEN),
    # a window under the rows' lengths: of 336 rows the first page lies
    # wholly before it, of 384 the first two
    "small_window": (attention.Kind(256, 64, 8, 128, 1e-6, rope=ROPE,
                                    window=100), True, True, 3, CTX_LEN),
    # a table longer than the pages walked at once (PAGE_GROUP): of 640
    # rows under the window the whole first group is left out
    "long_table": (attention.Kind(256, 16, 2, 128, 1e-6, rope=ROPE,
                                  window=100), True, False, 6,
                   (0, 1, 511, 512, 513, 640, 768, 300)),
    # AI21-Jamba2-3B: one key-value head, no norms, no positions
    "jamba": (attention.Kind(256, 20, 1, 128, 1e-6), False, False, 3,
              CTX_LEN),
}


def _layer(kind, head_norms, gate, rng):
    return {k: jnp.asarray(
        (1.0 if k.endswith("norm") else 0.0) + 0.05 * rng.standard_normal(s),
        jnp.bfloat16)
        for k, s in attention.tensor_shapes(kind, head_norms, gate).items()}


def _needed(n, window):
    """The pages of its table a row of ``n`` own rows needs."""
    first = max(n - window, 0) // PAGE if window else 0
    return range(first, -(-n // PAGE))


@pytest.mark.parametrize("with_prefix", [True, False],
                         ids=["prefix", "own_alone"])
@pytest.mark.parametrize("name", list(CASES))
def test_decode_pages_kernel_matches_the_gathered_rows(monkeypatch, name,
                                                       with_prefix):
    kind, head_norms, gate, n_table, lens = CASES[name]
    assert n_table <= pallas_attention.PAGE_GROUP or name == "long_table"
    rng = np.random.default_rng(len(name))
    lp = _layer(kind, head_norms, gate, rng)
    b, layers, pages, layer = len(lens), 2, 64, 1
    width = attention.kv_width(kind)
    ctx_len = jnp.asarray(lens, jnp.int32)
    h = jnp.asarray(rng.standard_normal((b, kind.hidden)), jnp.bfloat16)
    q, _ = attention.qkv(kind, lp, h, 256 + ctx_len - 1)
    gates = attention.head_gates(lp, h)
    cache = jnp.asarray(rng.standard_normal((layers, pages, PAGE, width)),
                        jnp.bfloat16)
    # scattered, never contiguous, no page twice: every two rows' disjoint
    table = rng.permutation(pages)[:b * n_table].reshape(b, n_table)
    assert not (np.diff(table, axis=1) == 1).all(axis=1).any()
    prefix = (jnp.asarray(rng.standard_normal((2 * PAGE, width)),
                          jnp.bfloat16) if with_prefix else None)
    # what the kernel may not touch holds NaN in ITS cache: the other
    # layer, pages no table names, and the pages of its table a row does
    # not need (behind its rows, or wholly before its window)
    keep = np.zeros((pages,), bool)
    for i, n in enumerate(lens):
        keep[table[i, list(_needed(n, kind.window))]] = True
    assert 0 < (~keep[table]).sum()
    poisoned = jnp.where(keep[None, :, None, None], cache, jnp.nan)
    poisoned = poisoned.at[1 - layer].set(jnp.nan)
    table = jnp.asarray(table, jnp.int32)

    def run(own_sums, decode, cache):
        return (own_sums(kind, q, cache, layer, table, ctx_len),
                decode(kind, lp, q, cache, jnp.int32(layer), table, ctx_len,
                       prefix, 256, gates, 0))

    want_sums, want = run(attention._own_sums, attention.attn_decode, cache)
    monkeypatch.setattr(attention, "_own_pages_kernel", lambda *a: True)
    monkeypatch.setattr(
        pallas_attention, "decode_pages",
        functools.partial(pallas_attention.decode_pages, interpret=True))
    got_sums, got = run(attention._own_sums, attention.attn_decode, poisoned)

    group = kind.heads // kind.kv_heads
    for part, w, g in zip("mla", want_sums, got_sums):
        assert g.shape == w.shape == (
            b, kind.kv_heads, group, kind.head_dim if part == "a" else 1)
        assert g.dtype == jnp.float32
    m, l, acc = (np.asarray(x) for x in got_sums)
    m_w, l_w, acc_w = (np.asarray(x) for x in want_sums)
    # the dead row: nothing seen, exactly what the merge takes for that
    assert (m[0] == pallas_attention.NEG).all()
    assert not l[0].any() and not acc[0].any()
    np.testing.assert_allclose(m, m_w, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(l, l_w, rtol=2e-3, atol=1e-6)
    np.testing.assert_allclose(acc, acc_w, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(
        np.asarray(common.merge_softmax_sums(got_sums, None)),
        np.asarray(common.merge_softmax_sums(want_sums, None)), atol=2e-3)
    # through the merge with the prefix's part, the gates and W_o
    assert got.shape == (b, kind.hidden) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    assert np.abs(np.asarray(want, np.float32)).max() > 0.05


def test_the_kernel_is_taken_for_rows_worth_a_grid_step(monkeypatch):
    """Which path a decode step's own part takes is read off what the code
    sees: the chip, rows of whole tiles, and the bytes of a row's pages
    (LFM2's and Laguna's tables: the kernel; Jamba's 196 KB a row and a
    rehearsal's tiny heads: XLA)."""
    def taken(kind, cache_shape, table_width=3):
        return attention._own_pages_kernel(
            kind, jax.ShapeDtypeStruct(cache_shape, jnp.bfloat16),
            jax.ShapeDtypeStruct((64, table_width), jnp.int32))

    lfm2, laguna, jamba = (CASES[k][0] for k in
                           ("lfm2", "laguna_window", "jamba"))
    assert not taken(laguna, (5, 401, 128, 2048))   # not on the chip
    monkeypatch.setattr(common, "TARGET_TPU", True)
    assert taken(laguna, (5, 401, 128, 2048))
    assert taken(lfm2, (6, 401, 128, 1024))
    assert not taken(jamba, (2, 401, 128, 256))
    tiny = attention.Kind(64, 4, 2, 16, 1e-6)
    assert not taken(tiny, (3, 50, 8, 64))
    # a table whose two rows' pages would not fit the chip's fast memory
    assert not taken(laguna, (5, 4001, 128, 2048), table_width=32)
