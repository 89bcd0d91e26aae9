"""A decode step's rows of the per-slot state, moved in place
(ops/slot_rows.py): each family's kernel in the interpreter
(ops/pallas_kda.py ``decode_rows``, ops/pallas_selective_scan.py
``decode_rows``) against its twin against the recurrence written out,
and what the addressing has to hold: the rows no live row names, the
null row and the snapshot row come back bit for bit, every other layer
whole, rows that are not live come out zero."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evam_tpu.ops import pallas_kda as pk
from evam_tpu.ops import pallas_selective_scan as pss
from evam_tpu.ops import slot_rows

LAYERS = 3
#: (rows of the step, of them live, the layer)
STEPS = [
    pytest.param(16, 16, 0, id="b16-all-live"),
    pytest.param(64, 62, LAYERS - 1, id="b64-the-cells-fill-last-layer"),
    pytest.param(64, 5, 1, id="b64-mostly-dead"),
    pytest.param(16, 11, LAYERS - 1, id="b16-five-dead-name-the-null-row"),
]


def _step(bucket, n_live, seed):
    """A step's ``slot`` and ``live`` over ``bucket + 6`` state rows: the
    live rows a permutation of the slots, in no order; every other row of
    the step names the null row (the last but one; the last is the
    snapshot's)."""
    r = np.random.default_rng(seed)
    rows = bucket + 6
    live = np.zeros(bucket, bool)
    live[r.permutation(bucket)[:n_live]] = True
    slot = np.where(live, r.permutation(rows - 2)[:bucket], rows - 2)
    return rows, jnp.asarray(slot, jnp.int32), jnp.asarray(live)


def _f(r, *shape):
    return jnp.asarray(r.standard_normal(shape), jnp.float32)


def _taps(r, bucket, rows, width=96):
    """The rows a step writes to its slots' convolution taps, and the
    taps' state, each slot's row in tiles."""
    tile = slot_rows.tiled(width)
    return (jnp.asarray(r.standard_normal((bucket, *tile)), jnp.bfloat16),
            jnp.asarray(r.standard_normal((LAYERS, rows, *tile)),
                        jnp.bfloat16))


def _taps_written(conv_new, conv, taps, layer, slot, live):
    """The live rows' taps are in their slots, every other row of the
    taps' state as it was."""
    _untouched(conv_new.astype(jnp.float32), conv.astype(jnp.float32),
               layer, slot, live)
    at = np.flatnonzero(np.asarray(live))
    np.testing.assert_array_equal(
        np.asarray(conv_new.astype(jnp.float32))[layer][np.asarray(slot)[at]],
        np.asarray(taps.astype(jnp.float32))[at])


def _untouched(new, old, layer, slot, live):
    """Bit for bit: every other layer, and of this one every row that no
    live row names, the null row and the snapshot row among them."""
    new, old = np.asarray(new), np.asarray(old)
    named = np.asarray(slot)[np.asarray(live)]
    others = np.setdiff1d(np.arange(old.shape[1]), named)
    assert {old.shape[1] - 2, old.shape[1] - 1} <= set(others.tolist())
    np.testing.assert_array_equal(np.delete(new, layer, axis=0),
                                  np.delete(old, layer, axis=0))
    np.testing.assert_array_equal(new[layer][others], old[layer][others])
    assert np.abs(new[layer][named] - old[layer][named]).max() > 0


@pytest.mark.parametrize("bucket,n_live,layer", STEPS)
def test_kda_decode_kernel_its_twin_and_the_recurrence(bucket, n_live, layer):
    heads, d = 2, 128
    r = np.random.default_rng(bucket + n_live)
    rows, slot, live = _step(bucket, n_live, seed=layer)
    state = _f(r, LAYERS, rows, heads, d, d) * 0.1
    q, k, kb, vb = (_f(r, bucket, heads, d) * s for s in (0.1, 0.1, 0.05, 1.0))
    g = -jnp.exp(_f(r, bucket, heads, d) - 3.0)
    taps, conv = _taps(r, bucket, rows)
    args = (jnp.int32(layer), slot, live, q, k, kb, vb, g, taps, state, conv)
    o0, s0, c0 = pk.decode_rows_xla(*args)
    o1, s1, c1 = pk.decode_rows(*args, interpret=True)
    for b in np.flatnonzero(np.asarray(live)):
        for h in range(heads):
            was = np.asarray(state[layer, slot[b], h], np.float64)
            st = np.exp(np.asarray(g[b, h], np.float64))[:, None] * was
            u = np.asarray(vb[b, h]) - np.asarray(kb[b, h], np.float64) @ st
            st = st + np.outer(np.asarray(k[b, h]), u)
            for o, s in ((o0, s0), (o1, s1)):
                np.testing.assert_allclose(
                    np.asarray(o[b, h]), st.T @ np.asarray(q[b, h]),
                    rtol=2e-4, atol=2e-6)
                np.testing.assert_allclose(
                    np.asarray(s[layer, slot[b], h]), st, rtol=2e-4,
                    atol=2e-6)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o0), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-5,
                               atol=1e-6)
    for o, s, c in ((o0, s0, c0), (o1, s1, c1)):
        _untouched(s, state, layer, slot, live)
        _taps_written(c, conv, taps, layer, slot, live)
        assert not np.asarray(o)[~np.asarray(live)].any()


@pytest.mark.parametrize("bucket,n_live,layer", STEPS)
def test_ssm_decode_kernel_its_twin_and_the_recurrence(bucket, n_live, layer):
    n, ch = 16, 256
    r = np.random.default_rng(bucket + n_live)
    rows, slot, live = _step(bucket, n_live, seed=layer)
    state = _f(r, LAYERS, rows, n, ch)
    dt = jnp.exp(_f(r, bucket, ch) - 4.0)
    u, z, b, c = (_f(r, bucket, w) for w in (ch, ch, n, n))
    a = -jnp.exp(0.1 * _f(r, n, ch) + jnp.log(jnp.arange(1, n + 1.0))[:, None])
    d = 1 + 0.1 * _f(r, ch)
    taps, conv = _taps(r, bucket, rows)
    args = (jnp.int32(layer), slot, live, dt, u, z, b, c, a, d, taps, state,
            conv)
    y0, h0, c0 = pss.decode_rows_xla(*args)
    y1, h1, c1 = pss.decode_rows(*args, block_c=128, interpret=True)
    dt64, u64, z64 = (np.asarray(x, np.float64) for x in (dt, u, z))
    for i in np.flatnonzero(np.asarray(live)):
        h = (np.exp(dt64[i][None] * np.asarray(a))
             * np.asarray(state[layer, slot[i]], np.float64)
             + (dt64[i] * u64[i])[None] * np.asarray(b[i])[:, None])
        want = ((h * np.asarray(c[i])[:, None]).sum(0) + np.asarray(d)
                * u64[i]) * (z64[i] / (1 + np.exp(-z64[i])))
        for y, hh in ((y0, h0), (y1, h1)):
            np.testing.assert_allclose(np.asarray(y[i]), want, rtol=2e-4,
                                       atol=2e-5)
            np.testing.assert_allclose(np.asarray(hh[layer, slot[i]]), h,
                                       rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), rtol=1e-5,
                               atol=1e-6)
    for y, hh, cc in ((y0, h0, c0), (y1, h1, c1)):
        _untouched(hh, state, layer, slot, live)
        _taps_written(cc, conv, taps, layer, slot, live)
        assert not np.asarray(y)[~np.asarray(live)].any()


@pytest.mark.parametrize("form", ["twin", "kernel"])
def test_a_null_row_that_holds_no_number_stays_where_it_is(form):
    """Rows that are not live write back what they read and reach no
    other row: a null row of inf and NaN (what a sum of dead rows once
    left there) comes back as it is and every live row stays finite."""
    rows, slot, live = _step(16, 9, seed=4)
    r = np.random.default_rng(5)
    state = _f(r, 2, rows, 2, 128, 128) * 0.1
    state = state.at[:, rows - 2, :, ::2].set(jnp.inf)
    state = state.at[:, rows - 2, :, 1::2].set(jnp.nan)
    q, k, kb, vb = (_f(r, 16, 2, 128) for _ in range(4))
    g = -jnp.exp(_f(r, 16, 2, 128) - 3.0)
    taps, conv = _taps(r, 16, rows)
    run = (pk.decode_rows_xla if form == "twin"
           else lambda *a: pk.decode_rows(*a, interpret=True))
    o, s, _ = run(jnp.int32(1), slot, live, q, k, kb, vb, g, taps, state,
                  conv[:2])
    np.testing.assert_array_equal(np.asarray(s[:, rows - 2]),
                                  np.asarray(state[:, rows - 2]))
    assert np.isfinite(np.asarray(o)).all()
    assert np.isfinite(np.asarray(np.delete(s, rows - 2, axis=1))).all()


def test_put_writes_the_named_rows_and_drops_the_dead():
    state = jnp.arange(2 * 6 * 4, dtype=jnp.float32).reshape(2, 6, 4)
    slot = jnp.asarray([3, 0, 4, 4], jnp.int32)   # two dead rows name row 4
    live = jnp.asarray([True, True, False, False])
    got = state[1, slot]
    new = slot_rows.put(state, jnp.int32(1), slot, live, -got, check=True)
    want = np.asarray(state).copy()
    want[1, [3, 0]] *= -1
    np.testing.assert_array_equal(np.asarray(new), want)


def test_the_twins_refuse_two_live_rows_that_name_one_slot():
    """The kernels rely on the engine's invariant (a row's block is
    fetched while the row before it computes); the twins say so."""
    state = jnp.zeros((1, 6, 4), jnp.float32)
    rows = jnp.ones((3, 4), jnp.float32)
    put = jax.jit(lambda slot, live: slot_rows.put(
        state, jnp.int32(0), slot, live, rows, check=True))
    dead_twice = put(jnp.asarray([4, 1, 4]), jnp.asarray([False, True, False]))
    assert np.asarray(dead_twice)[0].sum(axis=1).tolist() == [0, 4, 0, 0, 0, 0]
    with pytest.raises(Exception, match="name one slot"):
        jax.block_until_ready(put(jnp.asarray([1, 2, 1]),
                                  jnp.asarray([True, True, True])))
        jax.effects_barrier()
