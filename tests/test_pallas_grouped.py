"""The grouped products of the expert layer (ops/pallas_grouped.py): the
kernel in the interpreter against ``jax.lax.ragged_dot`` over the
routings that break grouped products, the count of (row tile, group)
pairs against a count in numpy, and ``held_experts`` through the kernel
against ``held_experts`` through its twin."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from evam_tpu.models.lm import experts
from evam_tpu.models.lm.presets import PRESETS
from evam_tpu.ops import pallas_grouped as pg

K, N = 128, 256
#: (rows, rows of each group); a tile is 128 rows, or all where fewer
ROUTINGS = [
    pytest.param(512, [0, 50, 200, 30], id="an-empty-group-first"),
    pytest.param(512, [70, 90, 300, 0], id="an-empty-group-last"),
    pytest.param(512, [40, 0, 0, 330], id="empty-groups-in-the-middle"),
    pytest.param(512, [0, 0, 512, 0], id="every-row-in-one-group"),
    pytest.param(512, [100, 290, 2, 1], id="a-group-spans-three-tiles"),
    pytest.param(512, [0, 0, 0, 0], id="no-row-of-any-group"),
    pytest.param(512, [128, 128, 128, 128], id="groups-end-where-tiles-end"),
    pytest.param(256, [3, 130, 0, 7], id="most-rows-of-no-group"),
    pytest.param(48, [1, 0, 1, 1], id="one-tile-of-48-rows"),
]


def _pairs(sizes, tile):
    """(row tile, group) pairs that hold rows, counted in numpy."""
    lo, n = 0, 0
    for size in sizes:
        if size:
            n += (lo + size - 1) // tile - lo // tile + 1
        lo += size
    return n


@pytest.mark.parametrize("m,sizes", ROUTINGS)
def test_kernel_is_ragged_dot_on_the_rows_of_a_group(m, sizes):
    r = np.random.default_rng(sum(sizes) + m)
    tile = pg.row_tile(m)
    groups, n_mine = len(sizes), sum(sizes)
    rows = jnp.asarray(r.standard_normal((m, K)), pg.BF16)
    gate, up = (jnp.asarray(r.standard_normal((groups, K, N)) * 0.1,
                            pg.BF16) for _ in range(2))
    down = jnp.asarray(r.standard_normal((groups, N, K)) * 0.1, pg.BF16)
    size = jnp.asarray(sizes, jnp.int32)

    want_h = pg.swiglu_xla(rows, gate, up, size)
    got_h = pg.swiglu(rows, gate, up, size, interpret=True)
    want = pg.product_xla(want_h, down, size)
    got = pg.product(want_h, down, size, interpret=True)
    assert got_h.shape == (m, N) and got.shape == (m, K)
    assert got_h.dtype == got.dtype == pg.BF16
    for a, b in ((got_h, want_h), (got, want)):
        a, b = (np.asarray(x, np.float32)[:n_mine] for x in (a, b))
        # the twin rounds the logistic too: two bfloat16 steps at most
        assert np.abs(a - b).max(initial=0) <= 2 ** -6 * max(
            1.0, np.abs(b).max(initial=0))

    tile_of, group_of, starts, ends, n = pg.visits(size, m)
    assert int(n) == _pairs(sizes, tile)
    seen = {(int(t), int(g)) for t, g in zip(tile_of[:int(n)],
                                            group_of[:int(n)])}
    assert len(seen) == int(n)
    for t, g in seen:   # the pair's tile does hold rows of its group
        assert sizes[g] and int(starts[g]) < (t + 1) * tile
        assert int(ends[g]) > t * tile
    # in order: a tile's visits follow each other (its output block is
    # kept between them), and no group comes before one below it
    order = [(int(t), int(g)) for t, g in zip(tile_of[:int(n)],
                                             group_of[:int(n)])]
    assert order == sorted(order)


def test_the_count_a_product_reports_is_the_tiles_the_kernel_would_walk():
    sizes = jnp.asarray([0, 130, 3, 0, 200, 1], jnp.int32)
    assert pg.padded(700) == 768 and pg.row_tile(768) == 128
    assert pg.padded(96) == 96 and pg.row_tile(96) == 96
    assert pg.padded(100) == 112
    assert int(pg.n_visits(sizes, 768)) == _pairs(sizes.tolist(), 128) == 6


def test_a_block_is_whole_lane_tiles_within_the_budget():
    # Kimi's three matrices whole; DeepSeek's in blocks under 6 MiB
    assert pg.col_block(2304, 1024) == 1024
    assert pg.col_block(1024, 2304) == 2304
    assert pg.col_block(5120, 1536) == 512
    assert pg.col_block(1536, 5120) == 1280
    assert pg.col_block(64, 96) == 96      # no whole lane tile: all of it


def test_rows_that_are_no_whole_tiles_are_refused():
    with pytest.raises(ValueError, match="whole tiles"):
        pg.product(jnp.zeros((40, K), pg.BF16),
                   jnp.zeros((2, K, N), pg.BF16),
                   jnp.asarray([1, 2], jnp.int32), interpret=True)


@pytest.mark.parametrize("family,preset,tokens", [
    ("deepseek_v2", "deepseek_v2_tiny", 24),   # 72 assignments: 80 rows
    ("kimi_linear", "kimi_linear_tiny", 40),   # padded to tiles of 128
])
def test_held_experts_through_the_kernel_are_those_through_its_twin(
        monkeypatch, family, preset, tokens):
    """Dead rows, assignments of other chips' experts and the padding
    sort last and are left to the mask; what comes back per token is the
    twin's, and so are the three counts."""
    import importlib

    lm = importlib.import_module(f"evam_tpu.models.lm.{family}")
    cfg = lm.Config.from_dict(PRESETS[preset])
    shapes = experts.tensor_shapes(cfg, bias=family == "kimi_linear")
    lp = (lm.make_layer(cfg, 1, shapes,
                        range(cfg.held_lo, cfg.held_lo + cfg.n_held))
          if family == "kimi_linear" else lm.make_layer(cfg, 1))
    r = np.random.default_rng(7)
    x = jnp.asarray(r.standard_normal((tokens, cfg.hidden)), pg.BF16)
    w, ids = experts.route(cfg, x, lp["router"], lp.get("router_bias"))
    live = jnp.asarray(r.random(tokens) < 0.8)

    want = experts.held_experts(cfg, lp, x, w, ids, live)
    monkeypatch.setattr(experts, "on_tpu", lambda: True)
    monkeypatch.setattr(pg, "swiglu",
                        functools.partial(pg.swiglu, interpret=True))
    monkeypatch.setattr(pg, "product",
                        functools.partial(pg.product, interpret=True))
    got = experts.held_experts(cfg, lp, x, w, ids, live)

    assert [int(v) for v in got[1:]] == [int(v) for v in want[1:]]
    assert int(got[1]) > 0 and int(got[3]) >= int(got[2]) > 0
    a, b = (np.asarray(v[0], np.float32) for v in (got, want))
    assert np.isfinite(a).all()
    assert np.abs(a - b).max() <= 0.02 * np.abs(b).max()
    assert not a[~np.asarray(live)].any()
