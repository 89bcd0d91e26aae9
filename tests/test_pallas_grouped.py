"""The grouped products of the expert layer (ops/pallas_grouped.py): the
kernel in the interpreter against ``jax.lax.ragged_dot`` over the
routings that break grouped products, the count of (row tile, group)
pairs against a count in numpy, and ``held_experts`` through the kernel
against ``held_experts`` through its twin."""

import functools

import jax
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


#: (preset, whether its family hands ``moe`` every layer's tensors stacked)
EXPERT_PRESETS = [("deepseek_v2_tiny", False), ("kimi_linear_tiny", False),
                  ("lfm2_moe_tiny", True), ("laguna_tiny", True),
                  ("nemotron_h_tiny", True)]


def _route_before(cfg, x, router, bias=None):
    """``experts.route`` as it was before PR 58: the group mask by a
    scatter, the chosen scores by a gather of single scalars."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if cfg.score_func == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    t = scores.shape[0]
    chosen_by = scores if bias is None else scores + bias.astype(jnp.float32)
    if cfg.n_group > 1:
        per_group = cfg.n_experts // cfg.n_group
        group = chosen_by.reshape(t, cfg.n_group, per_group).max(-1)
        _, keep = jax.lax.top_k(group, cfg.topk_group)
        kept = jnp.zeros((t, cfg.n_group), bool).at[
            jnp.arange(t)[:, None], keep].set(True)
        chosen_by = jnp.where(jnp.repeat(kept, per_group, axis=1), chosen_by,
                              0.0)
    w, ids = jax.lax.top_k(chosen_by, cfg.top_k)
    if bias is not None:
        w = jnp.take_along_axis(scores, ids, axis=1)
    if cfg.norm_topk and cfg.top_k > 1:
        w = w / (w.sum(-1, keepdims=True) + cfg.topk_eps)
    if cfg.scale_routed:
        w = w * cfg.routed_scale
    return w, ids


def _held_experts_before(cfg, lp, x, w, ids, live, layer=None):
    """``experts.held_experts`` as it was before PR 58: the sizes by
    ``bincount``, a token's assignments side by side, every sorted row past
    the last assignment zeroed before the un-sort."""
    t, k = ids.shape
    n_held = lp["expert_down"].shape[-3]
    local = ids - cfg.held_lo
    mine = (local >= 0) & (local < n_held) & live[:, None]
    m = pg.padded(t * k)
    sort_key = jnp.pad(jnp.where(mine, local, n_held).reshape(-1),
                       (0, m - t * k), constant_values=n_held)
    order = jnp.argsort(sort_key, stable=True)
    sizes = jnp.bincount(sort_key, length=n_held + 1)[:n_held].astype(
        jnp.int32)
    n_mine = sizes.sum()
    rows = x[jnp.minimum(order // k, t - 1)]
    if cfg.expert_act == "swiglu":
        hmid = pg.swiglu_xla(rows, lp["expert_gate"], lp["expert_up"], sizes,
                             layer)
    else:
        hmid = pg.relu2_xla(rows, lp["expert_up"], sizes, layer)
    y = pg.product_xla(hmid, lp["expert_down"], sizes, layer)
    y = jnp.where((jnp.arange(m) < n_mine)[:, None], y, 0)
    back = jnp.argsort(order)[:t * k]
    y = y[back].reshape(t, k, -1).astype(jnp.float32)
    out = (y * jnp.where(mine, w, 0.0)[..., None]).sum(1)
    return (out.astype(pg.BF16), n_mine,
            (sizes > 0).sum().astype(jnp.int32), pg.n_visits(sizes, m))


def _expert_layer(preset, stacked, tokens=48):
    """A family's tiny config, seeded tensors of its expert layer (stacked:
    two layers' of which ``layer`` 1 is run), tokens and their ``live``.
    Experts 1 and 2 are one column of the router under one bias, and with
    groups so are the first experts of groups 0 and 1, so scores TIE;
    tokens 0 and 1 are one row; a fifth of the rows are dead."""
    from evam_tpu.models.lm import family

    model = PRESETS[preset]
    cfg = family(model["model_type"]).Config.from_dict(model)
    n_held = getattr(cfg, "n_held", None) or cfg.per_group
    r = np.random.default_rng(58)
    lp = {}
    for name, shape in experts.tensor_shapes(
            cfg, bias=model["model_type"] != "deepseek_v2").items():
        lead = ((2,) if stacked else ()) + (
            (n_held,) if name.startswith("expert_") else ())
        a = r.standard_normal(lead + shape) * (
            0.5 if name.startswith("router") else 0.1)
        if name.startswith("router"):
            a[..., 2] = a[..., 1]
            if cfg.n_group > 1:
                a[..., cfg.n_experts // cfg.n_group] = a[..., 0]
        lp[name] = jnp.asarray(a, jnp.float32 if name.startswith("router")
                               else pg.BF16)
    x = r.standard_normal((tokens, cfg.hidden))
    x[1] = x[0]
    live = r.random(tokens) < 0.8
    live[:2] = True
    return (cfg, lp, jnp.asarray(x, pg.BF16), jnp.asarray(live),
            1 if stacked else None)


def _routed(lp, layer):
    """The router and its selection bias, of ``layer`` where stacked."""
    router, bias = lp["router"], lp.get("router_bias")
    if layer is not None:
        router, bias = router[layer], None if bias is None else bias[layer]
    return router, bias


@pytest.mark.parametrize("preset,stacked", EXPERT_PRESETS)
def test_route_is_the_route_it_replaces_bit_for_bit(preset, stacked):
    """The group mask by comparison and the chosen scores by a one-hot
    maximum are the scatter's and the gather's, tied scores, groups and a
    selection bias among the inputs."""
    cfg, lp, x, _, layer = _expert_layer(preset, stacked)
    router, bias = _routed(lp, layer)
    (w, ids), (w0, ids0) = (f(cfg, x, router, bias)
                            for f in (experts.route, _route_before))
    ids0 = np.asarray(ids0)
    assert (bias is None) == (preset == "deepseek_v2_tiny")
    assert np.array_equal(np.asarray(ids), ids0)
    assert np.array_equal(np.asarray(w), np.asarray(w0))
    # the tie is among the chosen: somewhere 1 and 2 stand side by side
    both = (ids0[:, :-1] == 1) & (ids0[:, 1:] == 2)
    assert both.any() or cfg.n_group > 1


@pytest.mark.parametrize("preset,stacked", EXPERT_PRESETS)
def test_held_experts_are_the_held_experts_they_replace_bit_for_bit(
        monkeypatch, preset, stacked):
    """Sizes by comparison, assignments choice-major and the mask where the
    rows are gathered give the sum and the three counts of ``bincount``, a
    token's assignments side by side and the pass over every sorted row:
    dead rows, tokens with no held expert and (Nemotron-H) a latent among
    the inputs; and so does the whole layer."""
    cfg, lp, x, live, layer = _expert_layer(preset, stacked)
    w, ids = experts.route(cfg, x, *_routed(lp, layer))
    rows = x
    if cfg.moe_latent:
        rows = experts.mm(x, lp["latent_down"][layer])
    got = experts.held_experts(cfg, lp, rows, w, ids, live, layer)
    want = _held_experts_before(cfg, lp, rows, w, ids, live, layer)
    assert [int(v) for v in got[1:]] == [int(v) for v in want[1:]]
    assert np.array_equal(np.asarray(got[0], np.float32),
                          np.asarray(want[0], np.float32))
    n_held = lp["expert_down"].shape[-3]
    local = np.asarray(ids) - cfg.held_lo
    mine = (local >= 0) & (local < n_held) & np.asarray(live)[:, None]
    assert int(got[1]) == mine.sum() > 0
    assert not np.asarray(live).all()
    if n_held < cfg.n_experts:   # Laguna holds them all
        assert (~mine.any(1) & np.asarray(live)).any()
        assert not np.asarray(got[0], np.float32)[~mine.any(1)].any()

    whole = experts.moe(cfg, lp, x, live, layer)
    monkeypatch.setattr(experts, "route", _route_before)
    monkeypatch.setattr(experts, "held_experts", _held_experts_before)
    whole0 = experts.moe(cfg, lp, x, live, layer)
    assert np.array_equal(np.asarray(whole[1]), np.asarray(whole0[1]))
    assert np.array_equal(np.asarray(whole[0], np.float32),
                          np.asarray(whole0[0], np.float32))


@pytest.mark.parametrize("preset,stacked", [EXPERT_PRESETS[1],
                                            EXPERT_PRESETS[4]])
def test_rows_past_the_last_assignment_never_reach_the_sum(
        monkeypatch, preset, stacked):
    """No pass zeroes the sorted rows past the last held assignment any
    more: a product that leaves NaN there (the kernel leaves whatever was
    there) gives the same finite sum, because only an assignment that is
    not this chip's gathers such a row and the select drops it."""
    cfg, lp, x, live, layer = _expert_layer(preset, stacked)
    w, ids = experts.route(cfg, x, *_routed(lp, layer))
    if cfg.moe_latent:
        x = experts.mm(x, lp["latent_down"][layer])
    want = experts.held_experts(cfg, lp, x, w, ids, live, layer)
    product_xla, seen = pg.product_xla, []

    def product_with_nan_rows(hmid, down, sizes, layer=None):
        y = product_xla(hmid, down, sizes, layer)
        seen.append(y.shape[0])
        return jnp.where((jnp.arange(y.shape[0]) < sizes.sum())[:, None], y,
                         jnp.nan)

    monkeypatch.setattr(pg, "product_xla", product_with_nan_rows)
    got = experts.held_experts(cfg, lp, x, w, ids, live, layer)
    assert seen and int(want[1]) < seen[0]   # there were such rows
    a = np.asarray(got[0], np.float32)
    assert np.isfinite(a).all()
    assert np.array_equal(a, np.asarray(want[0], np.float32))
