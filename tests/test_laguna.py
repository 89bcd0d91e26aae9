"""The fifth language-model family: Laguna (models/lm/laguna.py) through the
generate engine with pages alone (engine/generate.py), the attention module
it shares with Jamba and LFM2 taking its data per LAYER KIND
(models/lm/attention.py: two head counts, a window, two rotations of which
one partial and rescaled, a gate on every head's output), the one
visibility rule with a first visible row (models/lm/common.py
``chunk_bounds``, ops/pallas_attention.py ``_visible``, the chunk kernel of
ops/pallas_attention.py), the expert layer with ALL its experts held and a
shared one (models/lm/experts.py), the fifth describe pipeline, and the
comparison that decides the Laguna cell's ``correct``
(benchmark/reference/laguna_child.py), all at a tiny size on the CPU
against the plain reference (benchmark/reference/laguna_plain.py): the same
structure as the published model (dense + full, three window layers, full +
experts, one more window layer; 6 and 8 query heads over 2 key-value heads
of 16; a window of 8 under pages of 4, so that own rows DO fall out of it
and it reaches into the prefix; an untied head)."""

import asyncio
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.opsbytes import laguna as opsbytes
from benchmark.reference import laguna_child, lm_compare
from benchmark.reference import laguna_plain as ref
from benchmark.reference.compare import check_schema
from evam_tpu.config.settings import LMSettings, Settings
from evam_tpu.engine.generate import GenerateEngine, GenerateSizes
from evam_tpu.models.lm import attention, common, experts, family
from evam_tpu.models.lm import laguna as lm
from evam_tpu.models.lm.presets import LAGUNA_XS2_PUBLISHED, PRESETS
from evam_tpu.ops import pallas_attention

REPO = Path(__file__).resolve().parent.parent
TINY = PRESETS["laguna_tiny"]
FULL = PRESETS["laguna_xs2_pp8"]
SIZES = GenerateSizes(slots=8, page_tokens=4, chunk_tokens=64,
                      max_segments=8, private_tokens=120)
NEW = 6
WINDOW = TINY["sliding_window"]


@pytest.fixture(scope="module", autouse=True)
def _yield_the_cores(yield_the_cores):
    """This file's compiles keep to two cores (tests/conftest.py)."""
    yield


def _prefix(n=16):
    return np.random.default_rng(1).integers(1, TINY["vocab_held"], size=n)


def _prompt(seed, n):
    return np.random.default_rng(100 + seed).integers(
        1, TINY["vocab_held"], size=n)


def _engine(prefix, name="generate:laguna", sizes=SIZES):
    eng = GenerateEngine(name, TINY, prefix, sizes=sizes)
    eng.warm_async()
    assert eng.warmed.wait(300) and eng.warm_error is None
    return eng


@pytest.fixture(scope="module")
def engine():
    eng = _engine(_prefix())
    yield eng
    eng.stop()


_compare = laguna_child.compare_logits
_SCALE = laguna_child.limits_scale(TINY)


def _generate(eng, prompt, n=NEW, stream="s"):
    return eng.submit(stream=stream, prompt_ids=prompt,
                      max_new_tokens=n).result(timeout=300)


def _ref_logits(prefix, prompt, result, **kw):
    """The reference's logits rows at the generated positions."""
    full = np.concatenate([prefix, prompt, result["ids"]]).astype(np.int64)
    first = len(prefix) + len(prompt) - 1
    return np.asarray(ref.forward(
        TINY, full, rows=list(range(first, first + len(result["ids"]))),
        **kw))


def _same_tensor(got, want, name=""):
    """The program makes a tensor inside one compiled function, the
    reference op by op: the float32 value before the rounding to bfloat16
    may differ in its last place, and where it lies at a tie a value in
    ten thousand lands one bfloat16 step away."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, name
    off = got != want
    assert off.mean() <= 1e-4, name
    assert np.all(np.abs(got - want)[off] <= np.abs(want[off]) / 64), name


def _idle(eng, timeout=10):
    deadline = time.time() + timeout
    while ((eng.pages_in_use()[0] != eng._prefix_pages
            or len(eng._free_slots) != eng.sizes.slots)
           and time.time() < deadline):
        time.sleep(0.05)


# ------------------------------------------------------------ the model


def test_the_layer_kinds_follow_from_the_published_lists():
    cfg = lm.Config.from_dict(FULL)
    assert cfg.layers == 5 and cfg.full_ids == (0, 4)
    assert cfg.window_ids == (1, 2, 3) and cfg.dense_ids == (0,)
    assert cfg.moe_ids == (1, 2, 3, 4)
    full, win = cfg.full, cfg.windowed
    assert (full.heads, win.heads, full.kv_heads, win.kv_heads,
            full.head_dim, cfg.kv_width) == (48, 64, 8, 8, 128, 2048)
    assert (full.window, win.window, cfg.window) == (None, 512, 512)
    assert full.rope == attention.Rope(
        500000.0, 64, 1.4158883083359672, (64.0, 4096.0, 64.0, 1.0))
    assert win.rope == attention.Rope(10000.0, 128)
    assert (cfg.n_experts, cfg.n_held, cfg.held_lo, cfg.top_k, cfg.n_shared,
            cfg.routed_scale, cfg.topk_eps) == (256, 256, 0, 8, 1, 2.5, 1e-20)
    # (is window, index among its kind, is dense, index among its ffn)
    assert cfg.schedule == ((0, 0, 1, 0), (1, 0, 0, 0), (1, 1, 0, 1),
                            (1, 2, 0, 2), (0, 1, 0, 3))
    tiny = lm.Config.from_dict(TINY)
    assert tiny.full_ids == (0, 4) and tiny.window_ids == (1, 2, 3, 5)
    assert (tiny.full.heads, tiny.windowed.heads) == (6, 8)
    assert tiny.schedule[5] == (1, 3, 0, 4)
    for i in range(40):
        model = dict(FULL, num_hidden_layers=40)
        assert ref.is_window(model, i) == (i % 4 != 0)
        assert ref.is_dense(model, i) == (i == 0)
    assert lm.SEGMENT_ALIGN == 1 and family("laguna") is lm


def test_a_config_of_another_shape_is_refused():
    kinds = TINY["layer_types"]
    for key, value in (
            ("attention_bias", True), ("gating", False),
            ("tie_word_embeddings", True), ("num_key_value_heads", 4),
            ("moe_apply_router_weight_on_input", True),
            ("shared_expert_intermediate_size", 48),
            ("layer_types", ["mamba"] + kinds[1:]),
            ("layer_types", ["full_attention"] * 6),
            # two head counts in one kind of layer
            ("num_attention_heads_per_layer", [6, 8, 8, 6, 6, 8]),
            ("mlp_layer_types", ["dense"] * 2),
            ("rope_parameters", {**TINY["rope_parameters"],
                                 "sliding_attention": {
                                     "rope_type": "llama3",
                                     "rope_theta": 100}})):
        with pytest.raises(ValueError):
            lm.Config.from_dict({**TINY, key: value})


def test_weights_are_the_same_tensors_in_program_and_reference():
    cfg = lm.Config.from_dict(TINY)
    params = lm.make_params(cfg)
    # model layer 3 is the third window layer and the third expert layer
    w = ref.layer_weights(TINY, 3)
    for name, shape in lm.attn_shapes(cfg.windowed).items():
        assert params["window"][name][2].shape == shape
        _same_tensor(params["window"][name][2], w[name], name)
    for name in lm.norm_shapes(cfg):
        _same_tensor(params["norms"][name][3], w[name])
    for name in ("router", "router_bias", "shared_gate", "shared_down"):
        _same_tensor(params["moe"][name][2], w[name])
    # model layer 4 is the second full layer: six heads, its own gate
    w = ref.layer_weights(TINY, 4)
    for name in lm.attn_shapes(cfg.full):
        _same_tensor(params["full"][name][1], w[name])
    assert params["full"]["gate"].shape == (2, 64, 6)
    assert params["window"]["gate"].shape == (4, 64, 8)
    _same_tensor(params["dense"]["mlp_down"][0],
                 ref.layer_weights(TINY, 0)["mlp_down"])
    # all 8 experts, each its own tensor, every layer in ONE stack
    assert params["moe"]["expert_down"].shape == (5, 8, cfg.moe_inter,
                                                  cfg.hidden)
    _same_tensor(params["moe"]["expert_down"][4, 6],
                 ref.tensor(TINY, 5, "expert_down",
                            (cfg.moe_inter, cfg.hidden), 6))
    for name, shape in (("embed", (cfg.vocab, cfg.hidden)),
                        ("head", (cfg.hidden, cfg.vocab))):
        _same_tensor(params[name],
                     ref.tensor(TINY, ref.GLOBAL_LAYER, name, shape))
    # the head norms' gains lie around qk_norm_gain, every other around 1
    gains = np.asarray(params["window"]["q_norm"], np.float32)
    assert abs(gains.mean() - TINY["qk_norm_gain"]) < 0.1
    assert abs(np.asarray(params["norms"]["post_norm"],
                          np.float32).mean() - 1) < 0.05
    assert np.abs(np.asarray(params["moe"]["router_bias"])).max() > 0


def test_parameter_count_matches_the_benchmarks_arithmetic():
    cfg = lm.Config.from_dict(FULL)
    # gains and the selection bias: what opsbytes leaves out
    small = 2048 + 5 * 2 * 2048 + 5 * 2 * 128 + 4 * 256
    model = dict(FULL, engine_prefix_tokens=2048)
    assert lm.param_count(cfg) - small == opsbytes.parameters(model)
    assert 3.86e9 < lm.param_count(cfg) < 3.88e9
    # the issue's arithmetic, term by term
    full = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    window = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64
    expert = 3 * 2048 * 512
    assert opsbytes.parameters(model) == (
        2 * full + 3 * window + 3 * 2048 * 8192
        + 4 * (257 * expert + 2048 * 256) + 2 * 100352 * 2048)
    state = lm.state_shapes(cfg, 401, 128, 128)
    assert set(state) == {"pages"}
    assert state["pages"].shape == (5, 401, 128, 2048)
    assert state["pages"].dtype == jnp.bfloat16


# -------------------------------------------------------- the rotation


def test_lfm2s_rotation_is_the_plain_table_over_the_whole_head():
    """What ``rotate_half`` computed from ``rope_theta`` before there was a
    table, bit for bit."""
    r = np.random.default_rng(2)
    x = jnp.asarray(r.standard_normal((5, 3, 16)), jnp.float32)
    pos = jnp.asarray([0, 1, 7, 100, 2300])
    half = 8
    inv = 1.0 / 1e6 ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    want = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    got = attention.rotate_half(x, pos, attention.Rope(1e6))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_yarn_table_is_the_references_and_static():
    for model in (TINY, FULL):
        cfg = lm.Config.from_dict(model)
        rope = model["rope_parameters"]["full_attention"]
        r = cfg.full.rope.rotated
        want, m = ref.frequencies(rope, r)
        got = np.asarray(attention.rope_table(cfg.full.rope,
                                              cfg.full.head_dim))
        assert got.shape == (r // 2,) and m == cfg.full.rope.scale
        np.testing.assert_allclose(got, want, rtol=2e-6)
        plain, _ = ref.frequencies(rope, r, frozenset(["yarn"]))
        # the fastest frequencies stay, the slowest are divided, a ramp
        # between them that lies inside the table
        ratio = want / plain
        assert ratio[0] == 1.0 and ratio[-1] == pytest.approx(
            1 / rope["factor"])
        assert ((ratio < 0.999) & (ratio > 1.001 / rope["factor"])).any()
        win = model["rope_parameters"]["sliding_attention"]
        f, m = ref.frequencies(win, cfg.full.head_dim)
        assert m == 1.0 and cfg.windowed.rope.scale == 1.0
        np.testing.assert_allclose(
            np.asarray(attention.rope_table(cfg.windowed.rope,
                                            cfg.full.head_dim)), f,
            rtol=2e-6)
    # the published table: 32 frequencies, the ramp from 5 to 16
    cfg = lm.Config.from_dict(FULL)
    want, _ = ref.frequencies(FULL["rope_parameters"]["full_attention"], 64)
    plain = 500000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(want[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(want[16:], plain[16:] / 64, rtol=1e-6)


def test_half_of_a_full_layers_head_passes_unturned():
    cfg = lm.Config.from_dict(TINY)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((4, 2, 16)),
                    jnp.float32)
    pos = jnp.asarray([3, 9, 30, 31])
    got = np.asarray(attention.rotate_half(x, pos, cfg.full.rope))
    np.testing.assert_array_equal(got[..., 8:], np.asarray(x)[..., 8:])
    # the turned part keeps its length times the scale
    np.testing.assert_allclose(
        np.linalg.norm(got[..., :8], axis=-1),
        1.2 * np.linalg.norm(np.asarray(x)[..., :8], axis=-1), rtol=1e-5)
    whole = np.asarray(attention.rotate_half(x, pos, cfg.windowed.rope))
    assert np.abs(whole[..., 8:] - np.asarray(x)[..., 8:]).max() > 0.1


# ------------------------------------------------------ the visibility


def test_chunk_bounds_under_a_window_have_a_first_visible_row():
    """Segment 0 continues a sequence of 5 earlier rows behind a prefix of
    14 whose list starts at position 8; a window of 4."""
    seg = jnp.asarray([0, 0, 0, 1, 1, 1, 1, 1, -1])
    plain, b0 = common.chunk_bounds(seg, 6, 5, 6, 8)
    assert plain.shape == (9, 4) and b0 == 6
    bounds, b0 = common.chunk_bounds(seg, 14, 5, 6, 8, window=4,
                                     prefix_first=8)
    got = np.asarray(bounds)
    assert got.shape == (9, 6) and b0 == 6
    # a, b1 as without a window (the prefix list starts at position 8)
    assert got[:, 0].tolist() == [6] * 8 + [0]
    assert got[:, 1].tolist() == [11, 11, 11, 6, 6, 6, 6, 6, 6]
    # token 0 of segment 0 stands at 14 + 5: it sees positions 16..19,
    # the continued rows 2, 3, 4 and itself
    # (a first row at or behind an interval's end: nothing of that list)
    assert got[0].tolist() == [6, 11, 14, 15, 8, 6 + 2]
    assert got[2].tolist() == [6, 11, 14, 17, 10, 6 + 4]
    # token 0 of segment 1 stands at 14: positions 11..14, the prefix
    # list's rows 3, 4, 5 and itself
    assert got[3].tolist() == [6, 6, 17, 18, 3, 6]
    assert got[5].tolist() == [6, 6, 17, 20, 5, 6]
    # from its 4th token on the prefix is behind it, and own rows too
    assert got[6].tolist() == [6, 6, 17, 21, 6, 6]
    assert got[7].tolist() == [6, 6, 18, 22, 7, 7]
    assert got[8, :4].tolist() == [0, 6, 0, 0]   # three empty intervals
    col = jnp.arange(6 + 8 + 9)[None, :]
    seen = np.asarray(pallas_attention._visible(col, bounds, b0))
    assert seen.sum(1).tolist() == [4, 4, 4, 4, 4, 4, 4, 4, 0]
    assert np.flatnonzero(seen[3]).tolist() == [3, 4, 5, 17]
    assert np.flatnonzero(seen[0]).tolist() == [8, 9, 10, 14]
    # without a window the same call is the three intervals of old
    old = np.asarray(pallas_attention._visible(col, plain, b0))
    assert old.sum(1).tolist() == [12, 13, 14, 7, 8, 9, 10, 11, 0]


@pytest.mark.parametrize("heads,group,tokens,prefix,cont,window", [
    (2, 2, 48, 32, 24, 9), (1, 4, 40, 0, 0, 5), (2, 3, 16, 128, 0, 40)])
def test_chunk_kernel_with_a_lower_bound_matches_its_xla_twin(
        heads, group, tokens, prefix, cont, window):
    """ops/pallas_attention.py in the interpreter against the same
    arithmetic through XLA, under a packed chunk's bounds WITH a window:
    every token sees exactly ``window`` rows or all there are."""
    from evam_tpu.ops import pallas_attention as pa

    r = np.random.default_rng(tokens + prefix)
    d = 16
    seg = np.repeat(np.arange(4), tokens // 4)
    seg[-3:] = -1
    n_prefix, n_cont = max(prefix - 5, 0), max(cont - 2, 0)
    bounds, b0 = common.chunk_bounds(jnp.asarray(seg), n_prefix, n_cont,
                                     prefix, cont, window=window)
    assert bounds.shape == (tokens, 6)
    keys = prefix + cont + tokens
    seen = np.asarray(pallas_attention._visible(
        jnp.arange(keys)[None, :], bounds, b0))
    place = np.arange(tokens) - np.repeat(
        np.arange(4) * (tokens // 4), tokens // 4)
    before = n_prefix + np.where(seg == 0, n_cont, 0) + place + 1
    assert seen.sum(1).tolist() == np.where(
        seg >= 0, np.minimum(before, window), 0).tolist()
    q = jnp.asarray(r.standard_normal((heads, tokens * group, d)), lm.BF16)
    k, v = (jnp.asarray(r.standard_normal((heads, keys, d)), lm.BF16)
            for _ in range(2))
    rows = jnp.repeat(bounds, group, axis=0)
    want = pa.chunk_attention_xla(q, k, v, rows, scale=0.25, b0=b0)
    got = pa.chunk_attention(q, k, v, rows, scale=0.25, b0=b0,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.02)
    dead = np.repeat(seg < 0, group)
    assert not np.asarray(got, np.float32)[:, dead].any()
    # and it is not what the same rows give without the window
    free, _ = common.chunk_bounds(jnp.asarray(seg), n_prefix, n_cont, prefix,
                                  cont)
    other = pa.chunk_attention_xla(q, k, v, jnp.repeat(free, group, axis=0),
                                   scale=0.25, b0=b0)
    assert np.abs(np.asarray(other, np.float32)
                  - np.asarray(want, np.float32)).max() > 0.1


def _window_layer():
    cfg = lm.Config.from_dict(TINY)
    lp = {k: jnp.asarray(v, lm.BF16)
          for k, v in ref.layer_weights(TINY, 1).items()}
    return cfg.windowed, lp


def test_a_chunks_token_sees_the_7th_row_before_it_and_not_the_8th():
    """The window's edge in a chunk, across all three lists: moving the
    value of the row ``window - 1`` positions back moves the token's
    output, moving the one before it moves nothing, bit for bit."""
    kind, lp = _window_layer()
    r = np.random.default_rng(5)
    h = jnp.asarray(r.standard_normal((12, kind.hidden)), lm.BF16)
    q, kv = attention.qkv(kind, lp, h, jnp.arange(12) + 20)
    prefix = jnp.asarray(r.standard_normal((8, kv.shape[1])), lm.BF16)
    cont = jnp.asarray(r.standard_normal((8, kv.shape[1])), lm.BF16)
    seg = jnp.asarray([0] * 5 + [1] * 7)

    def run(prefix, cont, kv):
        # the prefix list starts at position 8 of 16; 3 continued rows
        return np.asarray(attention.attn_prefill(
            kind, lp, q, kv, seg, prefix, 16, cont, 3,
            attention.head_gates(lp, h), 8), np.float32)

    base = run(prefix, cont, kv)
    half = kv.shape[1] // 2

    def moved(rows, at):
        return rows.at[at, half:].add(1.0)

    # segment 1's token 2 (chunk row 7) stands at 16 + 2: it sees 11..18,
    # of the prefix list (from 8) rows 3.. and not row 2
    assert np.abs(run(moved(prefix, 3), cont, kv)[7] - base[7]).max() > 1e-3
    np.testing.assert_array_equal(run(moved(prefix, 2), cont, kv)[7], base[7])
    # segment 0's token 4 (row 4) stands at 16 + 3 + 4 = 23: it sees
    # 16..23: the continued rows 0.. and no prefix row
    assert np.abs(run(prefix, moved(cont, 0), kv)[4] - base[4]).max() > 1e-3
    np.testing.assert_array_equal(run(moved(prefix, 7), cont, kv)[4], base[4])
    # segment 1's last token (row 11, place 6) sees its own rows only from
    # place 0 on and of the prefix the last row alone
    np.testing.assert_array_equal(run(moved(prefix, 6), cont, kv)[11],
                                  base[11])
    assert np.abs(run(moved(prefix, 7), cont, kv)[11] - base[11]).max() > 1e-3
    # nothing of segment 0 or of its continued rows reaches segment 1
    np.testing.assert_array_equal(run(prefix, moved(cont, 2), kv)[5:],
                                  base[5:])


def test_a_decode_row_sees_the_7th_row_before_it_and_not_the_8th():
    kind, lp = _window_layer()
    r = np.random.default_rng(6)
    h = jnp.asarray(r.standard_normal((3, kind.hidden)), lm.BF16)
    ctx_len = jnp.asarray([3, 8, 12])
    q, _ = attention.qkv(kind, lp, h, 16 + ctx_len - 1)
    width = attention.kv_width(kind)
    ctx = jnp.asarray(r.standard_normal((3, 12, width)), lm.BF16)
    prefix = jnp.asarray(r.standard_normal((8, width)), lm.BF16)
    half = width // 2

    # each row's own rows are one page of 12 of a one-layer cache
    table = jnp.arange(3)[:, None]

    def run(ctx, prefix):
        # the prefix list holds positions 8..15 of a prefix of 16
        return np.asarray(attention.attn_decode(
            kind, lp, q, ctx[None], 0, table, ctx_len, prefix, 16,
            attention.head_gates(lp, h), 8), np.float32)

    base = run(ctx, prefix)
    # row 0: 3 own rows, so the prefix's positions 11..15 (list rows 3..)
    assert np.abs(run(ctx, prefix.at[3, half:].add(1.0))[0]
                  - base[0]).max() > 1e-3
    got = run(ctx, prefix.at[2, half:].add(1.0))
    np.testing.assert_array_equal(got[0], base[0])
    # rows 1 and 2 have 8 and 12 own rows: no prefix row is inside
    np.testing.assert_array_equal(
        run(ctx, prefix.at[:, half:].add(1.0))[1:], base[1:])
    # row 2 sees its own rows 4..11
    assert np.abs(run(ctx.at[2, 4, half:].add(1.0), prefix)[2]
                  - base[2]).max() > 1e-3
    np.testing.assert_array_equal(
        run(ctx.at[2, 3, half:].add(1.0), prefix)[2], base[2])
    # the full kind sees them all
    cfg = lm.Config.from_dict(TINY)
    full_lp = {k: jnp.asarray(v, lm.BF16)
               for k, v in ref.layer_weights(TINY, 0).items()}
    fq, _ = attention.qkv(cfg.full, full_lp, h, 16 + ctx_len - 1)

    def full(ctx):
        return np.asarray(attention.attn_decode(
            cfg.full, full_lp, fq, ctx[None], 0, table, ctx_len, prefix, 8),
            np.float32)

    assert np.abs(full(ctx.at[2, 0, half:].add(1.0))[2]
                  - full(ctx)[2]).max() > 1e-3


def test_the_prefix_pages_a_window_layer_reads_are_its_last():
    pages = np.arange(1, 17)
    # a decode step: the whole prefix is there, the slice is static
    seen, first = attention.window_pages(512, pages, 2048, 128)
    assert seen.tolist() == [13, 14, 15, 16] and first == 1536
    seen, first = attention.window_pages(8, pages[:4], 16, 4)
    assert seen.tolist() == [3, 4] and first == 8
    assert attention.window_pages(None, pages, 2048, 128) == (pages, 0)
    assert attention.window_pages(8, None, 0, 4) == (None, 0)
    # a chunk: the prefix's length is the program's argument
    for n_prefix in (0, 3, 8, 9, 16):
        seen, first = jax.jit(
            lambda n: attention.window_pages(8, pages[:4], n, 4))(
                jnp.int32(n_prefix))
        seen, first = np.asarray(seen), int(first)
        assert len(seen) == 3 and first % 4 == 0
        # every position a token behind the prefix can see is among them
        lo = max(n_prefix - 7, 0)
        assert first <= lo and first + 12 >= n_prefix
        assert seen.tolist() == (pages[:4][first // 4:first // 4 + 3]
                                 ).tolist()


# ------------------------------------------------------- the attention


def _attention_against(layer, omit=frozenset(), **kw):
    """The module's chunk attention over one sequence against the
    reference's, largest difference over the largest value."""
    cfg = lm.Config.from_dict(TINY)
    kind = cfg.windowed if ref.is_window(TINY, layer) else cfg.full
    w = ref.layer_weights(TINY, layer)
    lp = {k: jnp.asarray(v, lm.BF16) for k, v in w.items()}
    r = np.random.default_rng(11)
    h = jnp.asarray(r.standard_normal((40, cfg.hidden)), lm.BF16)
    pos = jnp.arange(40)
    q, kv = attention.qkv(kind, lp, h, pos)
    got = attention.attn_prefill(kind, lp, q, kv, jnp.zeros((40,), jnp.int32),
                                 None, 0, None, 0,
                                 attention.head_gates(lp, h))
    want = np.asarray(ref.attention(TINY, layer, w,
                                    jnp.asarray(h, jnp.float32), omit, **kw))
    return np.abs(np.asarray(got, np.float32) - want).max() / np.abs(
        want).max()


@pytest.mark.parametrize("layer", [0, 1])
def test_both_kinds_of_layer_match_the_reference(layer):
    assert _attention_against(layer) < 0.03


@pytest.mark.parametrize("layer,lacks", [
    (1, "window"), (0, "gate"), (1, "gate"), (0, "partial"), (0, "yarn"),
    (0, "rope_scale"), (0, "rotation"), (1, "rotation"), (1, "pairing"),
    (0, "head_norms")])
def test_attention_differs_from_a_reference_that_lacks(layer, lacks):
    kw = {"window": {"window": False}, "gate": {"gated": False},
          "rotation": {"rotated": False}}.get(
              lacks, {"omit": frozenset([lacks])})
    assert _attention_against(layer, **kw) > 0.09, lacks


# ------------------------------------------------ the expert layer's share


def test_all_experts_held_and_the_shared_one_on_every_live_row():
    cfg = lm.Config.from_dict(TINY)
    assert set(lm.moe_shapes(cfg)) == {
        "router", "router_bias", "shared_gate", "shared_up", "shared_down",
        "expert_gate", "expert_up", "expert_down"}
    lp = lm.make_layers(cfg, (1, 2), lm.moe_shapes(cfg), range(8))
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (16, cfg.hidden)), lm.BF16)
    live = jnp.arange(16) < 12
    y, counts = experts.moe(cfg, lp, x, live, jnp.int32(1))
    w, ids = experts.route(cfg, x, lp["router"][1], lp["router_bias"][1])
    # sigmoid scores renormalised over the chosen two, times 2.5
    np.testing.assert_allclose(np.asarray(w).sum(1), 2.5, rtol=1e-3)
    want = np.asarray(common.swiglu(
        x, lp["shared_gate"][1], lp["shared_up"][1], lp["shared_down"][1]),
        np.float32)
    for e in range(8):
        we = np.where(np.asarray(ids) == e, np.asarray(w), 0).sum(-1)
        want += we[:, None] * np.asarray(common.swiglu(
            x, lp["expert_gate"][1, e], lp["expert_up"][1, e],
            lp["expert_down"][1, e]), np.float32)
    got = np.asarray(y, np.float32)
    assert np.abs(got[:12] - want[:12]).max() < 0.05 * np.abs(want).max()
    # every assignment of a live row is held: 2 a token
    assert int(counts[0]) == 2 * 12
    w_ref, ids_ref = ref.route(TINY, np.asarray(jax.nn.sigmoid(
        x.astype(jnp.float32) @ lp["router"][1].astype(jnp.float32))),
        np.asarray(lp["router_bias"][1], np.float32))
    assert (np.sort(ids_ref, 1) == np.sort(np.asarray(ids), 1)).mean() > 0.9


# ---------------------------------------------------------- the engine


@pytest.mark.parametrize("length", [3, 20, 100])
def test_prefill_then_decode_matches_the_reference(engine, length):
    """Through the engine, pages alone: packed prefill over the pinned
    prefix pages (100 tokens cross a chunk boundary: the second chunk
    attends to the first chunk's pages through the continued rows, under
    the window too), then decode steps in a running batch whose own rows
    fall out of the window of 8, against the reference's full forward
    pass."""
    prompt = _prompt(length, length)
    out = _generate(engine, prompt)
    problems, stats = _compare(
        out, _ref_logits(engine.prefix, prompt, out), _SCALE)
    assert not problems, (problems, stats)
    assert out["prefix_tokens"] == 16


def test_rows_that_carry_no_sequence_write_the_null_page_and_no_other(engine):
    """Fifty decode steps of which most rows carry nothing: what they
    write goes to the null page (page 0, which no table names below a
    context's length); the pinned prefix pages and every page the one
    sequence did not hold stay bit for bit, in every layer."""
    _idle(engine)
    before = np.asarray(engine._state["pages"], np.float32)
    prompt = _prompt(77, 9)
    out = _generate(engine, prompt, n=50)
    assert np.isfinite(out["top_logits"]).all()
    after = np.asarray(engine._state["pages"], np.float32)
    shared = list(engine._shared)
    np.testing.assert_array_equal(after[:, shared], before[:, shared])
    moved = np.flatnonzero((after != before).any(axis=(0, 2, 3)))
    # at most the null page and the sequence's own 9 + 49 rows, 4 a page
    assert len(set(moved) - {0}) <= 15 and not set(moved) & set(shared)
    problems, stats = _compare(
        out, _ref_logits(engine.prefix, prompt, out), _SCALE)
    assert not problems, (problems, stats)


def test_compiled_programs_constant_after_warmup(engine):
    before = engine.stats.compiled_programs
    assert before == 1 + len(SIZES.slot_buckets)
    futs = [engine.submit(stream=f"c{i}", prompt_ids=_prompt(i, 5 + 4 * i),
                          max_new_tokens=NEW) for i in range(10)]
    for f in futs:
        assert len(f.result(timeout=300)["ids"]) == NEW
    assert engine.stats.compiled_programs == before


def test_eight_segments_in_one_chunk_do_not_see_each_other(engine):
    lengths = [4, 12, 7, 1, 11, 3, 9, 5]
    prompts = [_prompt(60 + i, n) for i, n in enumerate(lengths)]
    alone = [_generate(engine, p, n=8) for p in prompts]
    _idle(engine)
    chunks, inner = [], engine._prefill

    def spy(params, state, last_ids, heads, mat, aux):
        chunks.append(np.array(mat[1]))
        return inner(params, state, last_ids, heads, mat, aux)

    engine._prefill = spy
    engine._admit = lambda: None  # hold admission until all eight wait
    try:
        futs = [engine.submit(stream=f"p{i}", prompt_ids=p, max_new_tokens=8)
                for i, p in enumerate(prompts)]
        del engine._admit
        packed = [f.result(timeout=300) for f in futs]
    finally:
        engine.__dict__.pop("_admit", None)
        engine._prefill = inner
    assert len(chunks) == 1
    seg = chunks[0]
    assert seg[:sum(lengths)].tolist() == [
        i for i, n in enumerate(lengths) for _ in range(n)]
    for prompt, one, many in zip(prompts, alone, packed):
        np.testing.assert_allclose(many["top_logits"][0],
                                   one["top_logits"][0], atol=1e-5)
        assert many["ids"][0] == one["ids"][0]
        problems, stats = _compare(
            many, _ref_logits(engine.prefix, prompt, many), _SCALE)
        assert not problems, (problems, stats)


def test_prefix_as_pinned_pages_equals_the_prefix_before_the_prompt(engine):
    """The shared prefix as pinned pages of every layer, the window
    layers reading only its last two, against the same tokens run in front
    of the prompt by an engine that shares nothing."""
    import dataclasses

    prompt = _prompt(11, 10)
    shared = _generate(engine, prompt)
    private_engine = _engine(np.zeros((0,), np.int32), "generate:private",
                             dataclasses.replace(SIZES, slots=1))
    try:
        private = _generate(private_engine,
                            np.concatenate([engine.prefix, prompt]))
    finally:
        private_engine.stop()
    # the prefill's token and the first decode steps' (the two engines pad
    # to different rows, and at this size a later near tie goes either way)
    assert private["ids"][:3] == shared["ids"][:3]
    np.testing.assert_allclose(private["top_logits"][:3],
                               shared["top_logits"][:3], atol=0.15)
    for out, pre in ((shared, engine.prefix), (private, engine.prefix)):
        problems, stats = _compare(out, _ref_logits(pre, prompt, out), _SCALE)
        assert not problems, (problems, stats)
    _idle(engine)
    assert engine.pages_in_use() == (4, 4 + 8 * 30)
    assert engine.state_slots()[2] == 0   # pages alone: no slot state


def test_cancel_frees_slots_and_pages(engine):
    futs = [engine.submit(stream="doomed", prompt_ids=_prompt(i, 8),
                          max_new_tokens=40) for i in range(12)]
    keep = engine.submit(stream="kept", prompt_ids=_prompt(3, 8),
                         max_new_tokens=4)
    engine.cancel_stream("doomed")
    assert all(f.result(timeout=60) is None for f in futs)
    assert len(keep.result(timeout=300)["ids"]) == 4
    _idle(engine)
    assert engine.pages_in_use()[0] == 4
    assert len(engine._free_slots) == SIZES.slots
    assert engine.queue_depth() == 0


def test_the_window_counters_count_rows_read_and_rows_skipped(engine):
    """Cache rows a full layer reads, and of them what a WINDOW layer read
    and what lay behind its window; all held assignments."""
    from evam_tpu.obs import metrics

    def counted():
        c = metrics.get_counter
        out = {f"{name}_{kind}": c(f"evam_generate_{series}",
                                   {"kind": kind})
               for kind in ("prefill", "decode")
               for name, series in (("rows", "latent_rows_read"),
                                    ("read", "window_rows_read"),
                                    ("skipped", "window_rows_skipped"),
                                    ("tokens", "tokens"))}
        out["held"] = c("evam_moe_held_assignments")
        out["state"] = c("evam_generate_state_rows", {"kind": "decode"})
        return out

    _idle(engine)
    before = counted()
    _generate(engine, _prompt(5, 100))  # two chunks: 64 tokens, then 36
    deadline = time.time() + 10
    while (counted()["tokens_decode"] - before["tokens_decode"] < NEW - 1
           and time.time() < deadline):
        time.sleep(0.05)
    grew = {k: v - before[k] for k, v in counted().items()}
    assert grew["tokens_prefill"] == 100 and grew["tokens_decode"] == NEW - 1
    assert grew["rows_decode"] == sum(16 + 100 + i + 1
                                      for i in range(NEW - 1))
    # a window layer reads the last 8 positions of each
    assert grew["read_decode"] == 8 * (NEW - 1)
    assert grew["skipped_decode"] == grew["rows_decode"] - 8 * (NEW - 1)
    # chunk 1 over the prefix's 16 rows, chunk 2 over 16 + 64 cached rows:
    # the 7 before the chunk's first token each
    assert grew["rows_prefill"] == 16 + 80
    assert (grew["read_prefill"], grew["skipped_prefill"]) == (14, 82)
    # five expert layers, all 8 experts held, two a token
    assert grew["held"] == 5 * 2 * (100 + NEW - 1)
    assert grew["state"] == 0
    # a family without a window has none of the two series
    assert engine._window == 8
    assert metrics.get_counter("evam_generate_window_rows_read",
                               {"kind": "decode"}) > 0


def test_the_own_pages_counters_add_up_to_rows_times_the_table(engine):
    """Per decode row the pages of its table that hold its own rows, and
    those wholly behind them, which the decode kernel neither fetches nor
    computes (ops/pallas_attention.py ``decode_pages``)."""
    from evam_tpu.obs import metrics

    def counted():
        return {name: metrics.get_counter(f"evam_generate_{series}",
                                          {"kind": "decode"})
                for name, series in (("read", "own_pages_read"),
                                     ("skipped", "own_pages_skipped"),
                                     ("steps", "steps"),
                                     ("tokens", "tokens"))}

    _idle(engine)
    before = counted()
    _generate(engine, _prompt(6, 100))
    deadline = time.time() + 10
    while (counted()["tokens"] - before["tokens"] < NEW - 1
           and time.time() < deadline):
        time.sleep(0.05)
    grew = {k: v - before[k] for k, v in counted().items()}
    assert grew["steps"] == grew["tokens"] == NEW - 1   # one row a step
    table = -(-SIZES.private_tokens // SIZES.page_tokens)
    assert grew["read"] + grew["skipped"] == (NEW - 1) * table == 150
    # step i feeds back own token 100 + i: 101 + i own rows, pages of 4
    assert grew["read"] == sum(-(-(101 + i) // 4) for i in range(NEW - 1))
    # a prefill chunk counts none
    assert not metrics.get_counter("evam_generate_own_pages_read",
                                   {"kind": "prefill"})


@pytest.mark.parametrize("own_rows,read", [
    (1, 1), (128, 1), (129, 2), (130, 2), (257, 3), (384, 3)])
def test_a_decode_row_reads_the_pages_that_hold_its_rows(own_rows, read):
    """At the deployment's sizes (pages of 128, a table of 3): a row of
    130 own rows reads 2 pages of 3. The dispatch alone, on an engine that
    was never built."""
    eng = object.__new__(GenerateEngine)
    eng.sizes = GenerateSizes()
    eng.prefix = np.zeros(2048, np.int32)
    eng._private_pages, eng._window, eng._decode = 3, None, None
    eng._run = lambda *a, **kw: kw
    step = eng._dispatch_decode_raw(
        [(0, own_rows - 1, [17, 18, 19]), (1, 383, [20, 21, 22])], 16, [])
    assert step["own_pages"] == (read + 3, 6 - (read + 3))
    assert step["tokens"] == 2 and step["rows_read"] == (
        2048 + own_rows + 2048 + 384)


# ------------------------------------------------------ the comparator


@pytest.fixture(scope="module")
def published(engine):
    """What a message's description holds, for three prompts."""
    out = []
    for i, n in enumerate((6, 17, 25)):
        prompt = _prompt(40 + i, n)
        out.append((prompt, _generate(engine, prompt, n=12)))
    return out


def _verdict(published, engine, **kw):
    problems = []
    for prompt, out in published:
        p, _ = _compare(out, _ref_logits(engine.prefix, prompt, out, **kw),
                        _SCALE)
        problems += p
    return problems


def test_comparator_passes_the_whole_model(published, engine):
    assert not _verdict(published, engine)


@pytest.mark.parametrize("omit", [
    "partial", "yarn", "rope_scale", "head_norms", "pairing", "router_bias",
    "renormalize", "shared", "expert:1", "control:weights", "control:window",
    "control:rope", "control:gate"])
def test_comparator_fails_when_a_term_or_the_precision_is_taken_away(
        published, engine, omit):
    kw = {"control:weights": {"weight_dtype": jnp.float8_e4m3fn},
          "control:window": {"window": False},
          "control:rope": {"rotated": False},
          "control:gate": {"gated": False}}.get(
              omit, {"omit": frozenset([omit])})
    assert _verdict(published, engine, **kw), omit


def test_a_row_of_another_sequence_is_refused(published, engine):
    (p0, o0), (p1, _), _ = published
    problems, stats = _compare(o0, _ref_logits(engine.prefix, p1, o0),
                               _SCALE)
    assert problems and stats["max"] > laguna_child.LOGIT_TOKEN_TOL


def test_the_child_knows_its_four_controls():
    assert laguna_child.CONTROLS == ("weights", "window", "rope", "gate")
    assert laguna_child.limits_scale(FULL) == 1.0
    assert laguna_child.limits_scale(TINY) == 3.0
    assert laguna_child.READINGS == ("acts",)
    assert "evam_tpu" not in (REPO / "benchmark" / "reference"
                              / "laguna_plain.py").read_text()


# ------------------------------------------------ configuration files


def test_benchmark_config_holds_the_published_widths_and_the_preset():
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "laguna_xs2_pp8.json").read_text())
    path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    catalog = ([json.loads(line) for line in open(path)]
               if path.is_file() else [])
    entry = next((e for e in catalog if e["name"] == "Laguna-XS.2"), None)
    if entry is not None:
        assert entry["config"] == LAGUNA_XS2_PUBLISHED
        assert cfg["source"] == entry["source_url"]
    for key, value in LAGUNA_XS2_PUBLISHED.items():
        assert cfg[key] == (5 if key == "num_hidden_layers" else value), key
    assert cfg["reduced"] == ["num_hidden_layers", "weights"]
    assert cfg["published"]["num_hidden_layers"] == 40
    assert set(cfg["departures"]) == {
        "num_hidden_layers", "layer_mix", "head_on_the_first_stage",
        "weights"}
    assert cfg["deployment"]["chips"] == 8
    assert "layers 0-4" in cfg["deployment"]["this_chip"]
    assert cfg["load_note"].startswith("1x")
    # the four inferences, each with its ground
    for key, number in (("gating", 1), ("router_scores", 2), ("qk_norm", 3),
                        ("rope_pairing", 4)):
        text = cfg["assumed"][key]
        assert text.startswith(f"({number})") and "Ground:" in text, key
    assert {"topk_eps", "qk_norm_gain", "gate_init", "router_bias", "slots",
            "page_tokens", "chunk_tokens", "state", "prefix_tokens",
            "max_new_tokens"} <= set(cfg["assumed"])
    assert cfg["assumed"]["qk_norm_gain"].startswith(
        str(FULL["qk_norm_gain"]))
    assert cfg["fallback"].startswith("not taken")
    model = cfg["shapes"]["model"]
    assert {k: model[k] for k in FULL} == FULL
    assert (model["num_experts"], model["experts_held"], model["held_lo"],
            model["num_experts_per_tok"], model["vocab_held"],
            model["num_hidden_layers"]) == (256, 256, 0, 8, 100352, 5)
    assert model["engine_prefix_tokens"] == \
        cfg["shapes"]["engine"]["prefix_tokens"] == 2048
    assert {k: cfg["rehearsal_shapes"]["model"][k] for k in TINY} == TINY
    assert cfg["request"]["parameters"]["max-new-tokens"] == \
        cfg["shapes"]["engine"]["max_new_tokens"] == 64
    assert cfg["opsbytes"] == "laguna"
    assert cfg["reference"]["child"] == "laguna_child"
    assert set(cfg["server_env"]) == {"EVAM_PRELOAD", "EVAM_MAX_BATCH",
                                      "EVAM_NATIVE"}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"]
                if w["name"] == "describe_laguna_replay")
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        "laguna_xs2_pp8", 1, "replay_1080p_x32")
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == cfg["reduced"] and len(entry["why"]) <= 200
    rate = next(m for m in bench["end_to_end"] if m["name"] == "frames_per_s")
    assert "describe_laguna_replay" in rate["workloads"]
    mine = [m for m in bench["per_layer"]
            if "describe_laguna_replay" in m.get("workloads", [])]
    # the harness admits 128 per-layer metrics: 15 were left
    assert len(mine) == 15 and len(bench["per_layer"]) == 128
    assert bench["per_layer"][-15:] == mine
    for m in mine:
        assert m["workloads"] == ["describe_laguna_replay"]
        assert m["moves"] == "frames_per_s"
        assert m["name"].endswith(".laguna_replay")
        assert (REPO / "benchmark" / "metrics"
                / f"{m['name']}.json").is_file()

    def params(name):
        return json.loads((REPO / "benchmark" / "metrics"
                           / f"{name}.laguna_replay.json").read_text())

    assert params("lm_held_experts_hit_share")["params"]["scale"] == \
        pytest.approx(100 / 1024)
    assert params("lm_held_assignments_per_token")["params"]["scale"] == 0.25
    assert params("lm_step_roofline")["reader"] == "lm_roofline_hit"
    share = params("lm_window_rows_read_share")["params"]
    assert share["num"]["series"] == "evam_generate_window_rows_read_total"
    assert share["den"]["series"] == "evam_generate_latent_rows_read_total"
    pipe = json.loads((REPO / "pipelines" / "scene_description"
                       / "pvb_laguna" / "pipeline.json").read_text())
    assert pipe["parameters"]["properties"]["max-new-tokens"]["default"] == 64
    assert [s.get("model") for s in pipe["stages"] if "model" in s] == [
        "scene_description/pvb_laguna", "scene_description_lm/laguna"]


def test_opsbytes_count_the_windows_rows_and_the_experts_read():
    m = dict(FULL, engine_prefix_tokens=2048)
    ctx = 2048 + 272 + 64
    counts = dict(prefill_steps=0, prefill_tokens=0, prefill_prompts=0,
                  prefill_rows=0, decode_steps=1, decode_tokens=64,
                  decode_rows=64 * ctx, held_assignments=4 * 512,
                  sampled_rows=64)
    one = opsbytes.steps(m, **counts)
    expert = 3 * 2048 * 512
    # every weight once but the embedding (64 rows of it)
    weights = 2.0 * (opsbytes.parameters(m) - 100352 * 2048)
    # a full layer: the prefix once, each row's own 336 rows; a window
    # layer: each row's own 336 and of the prefix what the window still
    # reaches behind them (512 - 336); every layer the 64 new rows
    rows = 2.0 * 2048 * (2 * (2048 + 64 * 336) + 3 * (64 * 336 + 512 - 336)
                         + 5 * 64)
    assert one["bytes"] == pytest.approx(weights + rows + 2.0 * 64 * 2048)
    assert 7.3e9 < weights < 7.4e9
    # attention pairs over VISIBLE rows: 512 a token in a window layer
    pairs = 64 * (2 * 48 * ctx + 3 * 64 * 512) * 4 * 128
    dense = 64 * 2 * (opsbytes.parameters(m) - 2 * 100352 * 2048
                      - 4 * 256 * expert)
    assert one["flops"] == pytest.approx(
        pairs + dense + 2048 * 2 * expert + 64 * 2 * 2048 * 100352)
    # the experts a step HIT, where the reader has them: 222 of 256 a layer
    hit = opsbytes.steps(m, **counts, experts_hit=4 * 222)
    assert one["bytes"] - hit["bytes"] == pytest.approx(
        2.0 * 4 * 34 * expert)
    assert opsbytes.steps(m, **counts, experts_hit=5000)["bytes"] == \
        one["bytes"]
    # a chunk: of its cached rows a window layer reads the 511 before it
    chunk = dict(counts, prefill_steps=1, prefill_tokens=512,
                 prefill_prompts=2, prefill_rows=2048, decode_steps=0,
                 decode_tokens=0, decode_rows=0, held_assignments=4 * 4096,
                 sampled_rows=2)
    got = opsbytes.steps(m, **chunk)
    assert got["bytes"] == pytest.approx(
        2.0 * (opsbytes.parameters(m) - 100352 * 2048) + 2.0 * 512 * 2048
        + 2.0 * 2048 * (2 * 2048 + 3 * 511 + 5 * 512))
    sizing = opsbytes.ops_and_bytes(
        {"model": m, "engine": {"prefix_tokens": 2048, "max_objects": 32,
                                "max_new_tokens": 64}}, 64)
    assert sizing["bytes"] == pytest.approx(one["bytes"])
    scan = opsbytes.scan_ops_and_bytes(m, 512)
    assert scan == {
        "flops": 512.0 * (2 * 48 * 2048 + 3 * 64 * 512) * 512 / 5,
        "bytes": 512.0 * 4 * (2 * 48 + 3 * 64) * 128 / 5}


def test_the_roofline_reader_hands_steps_the_experts_hit():
    from benchmark.readers import lm_roofline, lm_roofline_hit

    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "laguna_xs2_pp8.json").read_text())
    snap = {"metrics": {}, "engines": {}, "t": 0.0}
    ctx = 2048 + 272 + 64

    def after(hit):
        metrics = {
            'evam_generate_steps_total{kind="decode"}': 10.0,
            'evam_generate_tokens_total{kind="decode"}': 640.0,
            'evam_generate_latent_rows_read_total{kind="decode"}':
                640.0 * ctx,
            'evam_moe_held_assignments_total': 4.0 * 8 * 640,
            'evam_generate_steps_total{kind="prefill"}': 0.0,
            'evam_generate_tokens_total{kind="prefill"}': 0.0,
            'evam_generate_latent_rows_read_total{kind="prefill"}': 0.0}
        if hit is not None:
            metrics['evam_moe_held_experts_hit_total{kind="decode"}'] = hit
            metrics['evam_moe_held_experts_hit_total{kind="prefill"}'] = 0.0
        return {"metrics": metrics, "engines": {}, "t": 1.0}

    class Run:
        rehearsal = False
        out_dir = REPO / "benchmark_out" / "_test_laguna"

    Run.out_dir.mkdir(parents=True, exist_ok=True)

    def read(reader, hit):
        return reader.read({
            "device_trace": {"busy_s": 0.2, "devices": 1, "steps": 10,
                             "device_ops": []},
            "trace_before": snap, "trace_after": after(hit), "config": cfg,
            "device": {"kind": "TPU v5e"}, "run": Run,
            "peaks_file": REPO / "benchmark" / "peaks.json"}, {})

    try:
        bound = read(lm_roofline, 8880.0)
        counted = read(lm_roofline_hit, 8880.0)   # 222 of 256 a layer
        assert 0 < counted < bound < 100
        # 34 experts of 3.1 M values a layer and step fewer, at the HBM's
        # 819 GB/s, over 0.2 busy seconds
        assert bound - counted == pytest.approx(
            100 * 10 * 4 * 34 * 2 * 3 * 2048 * 512 / 819e9 / 0.2, rel=0.01)
        # all hit: the bound's own reading; no such series: nothing
        assert read(lm_roofline_hit, 10240.0) == pytest.approx(bound)
        assert read(lm_roofline_hit, None) is None
    finally:
        import shutil

        shutil.rmtree(Run.out_dir, ignore_errors=True)
    import sys

    assert not [m for m in sys.modules if "_steps_with_experts_hit" in m]


def test_the_window_share_reads_the_two_series():
    """``lm_window_rows_read_share`` through the accepted reader: a server
    of this family has the series, one of another family (or of a build
    from before them) has not, and then there is nothing to read."""
    from benchmark.readers import prom_delta_ratio

    params = json.loads((REPO / "benchmark" / "metrics"
                         / "lm_window_rows_read_share.laguna_replay.json"
                         ).read_text())["params"]
    before = {"t": 0.0, "metrics": {
        'evam_generate_latent_rows_read_total{kind="decode"}': 1000.0,
        'evam_generate_window_rows_read_total{kind="decode"}': 300.0}}
    after = {"t": 40.0, "metrics": {
        'evam_generate_latent_rows_read_total{kind="decode"}': 1000.0
        + 64 * 2350.0,
        'evam_generate_latent_rows_read_total{kind="prefill"}': 9e9,
        'evam_generate_window_rows_read_total{kind="decode"}': 300.0
        + 64 * 512.0,
        'evam_generate_window_rows_read_total{kind="prefill"}': 9e9,
        'evam_generate_window_rows_skipped_total{kind="decode"}':
            64 * (2350.0 - 512)}}
    got = prom_delta_ratio.read({"before": before, "after": after}, params)
    assert got == pytest.approx(100 * 512 / 2350)
    for snap in (before, after):
        snap["metrics"] = {k: v for k, v in snap["metrics"].items()
                           if "window" not in k}
    assert prom_delta_ratio.read({"before": before, "after": after},
                                 params) is None
    # the chunk kernel has two names in this family's prefill program and
    # the trace's ten operations hold one: no metric is entered for it
    assert not list((REPO / "benchmark" / "metrics").glob(
        "attn_chunk_*.laguna_replay.json"))


# --------------------------------------------------------- the server


def _registry(tmp_path):
    from evam_tpu.engine import EngineHub
    from evam_tpu.models import ModelRegistry
    from evam_tpu.models.fetch import synthesize_lm, synthesize_omz
    from evam_tpu.parallel import build_mesh
    from evam_tpu.server.registry import PipelineRegistry

    models = tmp_path / "models"
    synthesize_omz(models, alias="scene_description",
                   version="pvb_laguna", input_size=128)
    synthesize_lm(models, "scene_description_lm", "laguna", "laguna_tiny")
    settings = Settings(pipelines_dir=str(REPO / "pipelines"),
                        state_dir=str(tmp_path / "state"))
    hub = EngineHub(
        ModelRegistry(models_dir=models, dtype="float32"), plan=build_mesh(),
        max_batch=4, deadline_ms=4.0,
        lm=LMSettings(slots=4, page_tokens=4, chunk_tokens=64, max_segments=4,
                      private_tokens=288, prefix_tokens=16))
    return PipelineRegistry(settings, hub=hub)


def test_fifth_describe_pipeline_end_to_end_through_rest(eight_devices,
                                                         tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from evam_tpu.server.app import build_app

    reg = _registry(tmp_path)
    out = tmp_path / "out.jsonl"
    path = "/pipelines/scene_description/pvb_laguna"

    async def go():
        async with TestClient(TestServer(build_app(reg))) as c:
            r = await c.post(path, json={
                "source": {"uri": "synthetic://96x96@30?count=6",
                           "type": "uri"},
                "destination": {"metadata": {"type": "file",
                                             "path": str(out)}},
                "parameters": {"threshold": 0.1, "max-new-tokens": 5}})
            assert r.status == 200, await r.text()
            iid = await r.json()
            for _ in range(1500):
                st = await (await c.get(f"{path}/{iid}/status")).json()
                if st["state"] != "RUNNING":
                    break
                await asyncio.sleep(0.2)
            return st, await (await c.get("/engines")).json()

    try:
        st, engines = asyncio.run(go())
    finally:
        reg.stop_all()
    assert st["state"] == "COMPLETED", st
    msgs = [json.loads(line) for line in out.read_text().splitlines()]
    assert len(msgs) == 6
    shapes = {"model": TINY, "engine": {
        "prefix_tokens": 16, "max_new_tokens": 5, "max_objects": 32}}
    for m in msgs:
        assert check_schema(m) is None and m["objects"]
        assert not lm_compare.check_description(m, shapes)
    # one of them through the reference, as the benchmark's child does
    desc = msgs[-1]["description"]
    prefix = lm_compare.instruction_ids(16, TINY["vocab_held"])
    full = prefix + desc["prompt_ids"] + desc["ids"]
    first = len(prefix) + len(desc["prompt_ids"]) - 1
    logits = ref.forward(TINY, full, rows=list(range(first, first + 5)))
    problems, stats = _compare(desc, np.asarray(logits), _SCALE)
    assert not problems, (problems, stats)
    row = engines["generate:scene_description_lm/laguna"]
    assert row["items"] == 6 and row["compiled_programs"] == 5
    assert (row["state_slots_in_use"], row["state_bytes"]) == (0, 0)
    assert row["pages_in_use"] == 4 and row["capacity_fps"] > 0


# ------------------------------------ what the shared modules compute


@pytest.mark.parametrize("program,on_chip,want", [
    ("decode", True, "f2b6f31423d180f4"),
    ("decode", False, "9cfca97248f94a26"),
    ("prefill", True, "80ba93595b73b252"),
    ("prefill", False, "009c2d7306a62be0")])
def test_the_step_programs_compute_what_they_did(monkeypatch, program,
                                                 on_chip, want):
    """The guard of the modules this family shares with the others
    (tests/_step_trace.py): its two step programs at the deployment's
    sizes, traced for the chip (the Pallas kernels' bodies among the
    operations) and for the host (their twins), digest to what they did
    before the newest family came beside it. A PR that changes an
    operation of THIS family's served path moves the digest, and says
    so."""
    from _step_trace import check

    check("laguna_xs2_pp8", program, on_chip, monkeypatch, want)
