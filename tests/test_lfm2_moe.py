"""The fourth language-model family: LFM2-MoE (models/lm/lfm2_moe.py)
through the generate engine with taps-only slot state AND key-value pages
(engine/generate.py), its decode kernel and the kernel's twin
(ops/pallas_short_conv.py), the attention module it shares with Jamba
(models/lm/attention.py), the expert layer with no shared expert over a
STACK of layers (models/lm/experts.py, ops/pallas_grouped.py), the fourth
describe pipeline, and the comparison that decides the LFM2 cell's
``correct`` (benchmark/reference/lfm2_moe_child.py), all at a tiny size on
the CPU against the plain reference
(benchmark/reference/lfm2_moe_plain.py): the same structure as the
published model (both dense layers, an expert layer behind a convolution
and behind an attention layer, attention at 2, 6 and 8: no regular period;
2 key-value heads under 4 query heads; a tied head)."""

import asyncio
import dataclasses
import json
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.opsbytes import lfm2_moe as opsbytes
from benchmark.reference import lfm2_moe_child, lm_compare
from benchmark.reference import lfm2_moe_plain as ref
from benchmark.reference.compare import check_schema
from evam_tpu.config.settings import LMSettings, Settings
from evam_tpu.engine.generate import GenerateEngine, GenerateSizes
from evam_tpu.models.lm import attention, experts, family
from evam_tpu.models.lm import lfm2_moe as lm
from evam_tpu.models.lm.presets import LFM2_8B_A1B_PUBLISHED, PRESETS
from evam_tpu.ops import pallas_grouped as pg
from evam_tpu.ops import pallas_short_conv as psc
from evam_tpu.ops import slot_rows

REPO = Path(__file__).resolve().parent.parent
TINY = PRESETS["lfm2_moe_tiny"]
FULL = PRESETS["lfm2_moe_ep2"]
SIZES = GenerateSizes(slots=8, page_tokens=8, chunk_tokens=128,
                      max_segments=8, private_tokens=160)
NEW = 6


@pytest.fixture(scope="module", autouse=True)
def _yield_the_cores(yield_the_cores):
    """This file's compiles keep to two cores (tests/conftest.py)."""
    yield


def _prefix(n=16):
    return np.random.default_rng(1).integers(1, TINY["vocab_held"], size=n)


def _prompt(seed, n):
    return np.random.default_rng(100 + seed).integers(
        1, TINY["vocab_held"], size=n)


def _engine(prefix, name="generate:lfm2", sizes=SIZES):
    eng = GenerateEngine(name, TINY, prefix, sizes=sizes)
    eng.warm_async()
    assert eng.warmed.wait(300) and eng.warm_error is None
    return eng


@pytest.fixture(scope="module")
def engine():
    eng = _engine(_prefix())
    yield eng
    eng.stop()


_compare = lfm2_moe_child.compare_logits


def _generate(eng, prompt, n=NEW, stream="s"):
    return eng.submit(stream=stream, prompt_ids=prompt,
                      max_new_tokens=n).result(timeout=300)


def _ref_logits(prefix, prompt, result, **kw):
    """The reference's logits rows at the generated positions."""
    full = np.concatenate([prefix, prompt, result["ids"]]).astype(np.int64)
    first = len(prefix) + len(prompt) - 1
    if kw.pop("taps_lost", False):
        kw["taps_lost_from"] = first + 1
    return np.asarray(ref.forward(
        TINY, full, rows=list(range(first, first + len(result["ids"]))),
        **kw))


def _idle(eng, timeout=10):
    deadline = time.time() + timeout
    while ((eng.pages_in_use()[0] != eng._prefix_pages
            or len(eng._free_slots) != eng.sizes.slots)
           and time.time() < deadline):
        time.sleep(0.05)


# ------------------------------------------------------------ the model


def test_the_layer_order_follows_from_layer_types():
    cfg = lm.Config.from_dict(FULL)
    assert cfg.attn_ids == (2, 6, 10, 14, 18, 21)
    assert len(cfg.conv_ids) == 18 and cfg.conv_ids[:4] == (0, 1, 3, 4)
    assert cfg.moe_ids == tuple(range(2, 24)) and cfg.layers == 24
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.kv_width) == (
        32, 8, 64, 1024)
    assert (cfg.n_experts, cfg.n_held, cfg.top_k, cfg.n_shared,
            cfg.topk_eps) == (32, 16, 4, 0, 1e-6)
    sched = cfg.schedule
    # (is attention, index among its kind, is dense, index among its ffn)
    assert sched[:4] == ((0, 0, 1, 0), (0, 1, 1, 1), (1, 0, 0, 0),
                         (0, 2, 0, 1))
    assert sched[21] == (1, 5, 0, 19) and sched[23] == (0, 17, 0, 21)
    tiny = lm.Config.from_dict(TINY)
    assert tiny.attn_ids == (2, 6, 8) and tiny.moe_ids == tuple(range(2, 10))
    # an expert layer behind a convolution and behind an attention layer
    assert {TINY["layer_types"][i] for i in tiny.moe_ids} == {
        "conv", "full_attention"}
    for i in range(24):
        assert ref.is_conv(FULL, i) == (i not in cfg.attn_ids)
    assert lm.SEGMENT_ALIGN == 1 and family("lfm2_moe") is lm


def test_a_config_of_another_shape_is_refused():
    kinds = TINY["layer_types"]
    for key, value in (
            ("conv_bias", True), ("use_expert_bias", False),
            ("num_dense_layers", 0), ("num_dense_layers", 10),
            ("num_key_value_heads", 3),
            # a dense feed-forward behind an attention layer
            ("layer_types", ["full_attention"] + kinds[1:]),
            ("layer_types", ["mamba"] + kinds[1:]),
            ("layer_types", kinds[:5])):
        with pytest.raises(ValueError):
            lm.Config.from_dict({**TINY, key: value})


def test_weights_are_the_same_tensors_in_program_and_reference():
    cfg = lm.Config.from_dict(TINY)
    params = lm.make_params(cfg)
    # model layer 4 is the fourth convolution (0, 1, 3, 4) and the third
    # expert layer (2, 3, 4)
    w = ref.layer_weights(TINY, 4)
    for name, shape in lm.conv_shapes(cfg).items():
        got = np.asarray(params["conv"][name][3], np.float32)
        assert got.shape == shape
        np.testing.assert_array_equal(got, np.asarray(w[name]), name)
    for name in lm.norm_shapes(cfg):
        np.testing.assert_array_equal(
            np.asarray(params["norms"][name][4], np.float32),
            np.asarray(w[name]), name)
    for name in ("router", "router_bias"):
        np.testing.assert_array_equal(
            np.asarray(params["moe"][name][2], np.float32),
            np.asarray(w[name]), name)
    # attention layer 6 is the second of its stack
    w = ref.layer_weights(TINY, 6)
    for name in lm.attn_shapes(cfg):
        np.testing.assert_array_equal(
            np.asarray(params["attn"][name][1], np.float32),
            np.asarray(w[name]), name)
    w = ref.layer_weights(TINY, 1)
    np.testing.assert_array_equal(
        np.asarray(params["dense"]["mlp_down"][1], np.float32),
        np.asarray(w["mlp_down"]))
    # held experts 0-3 of 8, each its own tensor, every layer in ONE stack
    assert params["moe"]["expert_down"].shape == (8, 4, cfg.moe_inter,
                                                  cfg.hidden)
    np.testing.assert_array_equal(
        np.asarray(params["moe"]["expert_down"][5, 2], np.float32),
        np.asarray(ref.tensor(TINY, 7, "expert_down",
                              (cfg.moe_inter, cfg.hidden), 2)))
    assert "shared_gate" not in params["moe"] and "head" not in params
    np.testing.assert_array_equal(
        np.asarray(params["embed"], np.float32),
        np.asarray(ref.tensor(TINY, ref.GLOBAL_LAYER, "embed",
                              (cfg.vocab, cfg.hidden))))
    # the head norms' gains lie around qk_norm_gain, every other around 1
    gains = np.asarray(params["attn"]["q_norm"], np.float32)
    assert abs(gains.mean() - TINY["qk_norm_gain"]) < 0.1
    assert abs(np.asarray(params["norms"]["ffn_norm"],
                          np.float32).mean() - 1) < 0.05
    conv_w = np.asarray(params["conv"]["conv_w"], np.float32)
    assert np.abs(conv_w).max() <= 3 ** -0.5 + 1e-3 and conv_w.std() > 0.2
    assert np.abs(np.asarray(params["moe"]["router_bias"])).max() > 0


def test_parameter_count_matches_the_benchmarks_arithmetic():
    cfg = lm.Config.from_dict(FULL)
    # gains, the convolutions' taps and the selection bias: what opsbytes
    # leaves out
    small = (2048 + 24 * 2 * 2048 + 18 * 3 * 2048 + 6 * 2 * 64 + 22 * 32)
    model = dict(FULL, engine_prefix_tokens=2048)
    assert lm.param_count(cfg) - small == opsbytes.parameters(model)
    assert 4.39e9 < lm.param_count(cfg) < 4.41e9
    # the issue's arithmetic, term by term
    assert opsbytes.parameters(model) == (
        18 * (2048 * 6144 + 2048 * 2048)
        + 6 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 2 * 3 * 2048 * 7168
        + 22 * 16 * 3 * 2048 * 1792 + 22 * 2048 * 32 + 32768 * 2048)
    state = lm.state_shapes(cfg, 401, 128, 128)
    assert state["pages"].shape == (6, 401, 128, 1024)
    # a slot's 2 x 2048 taps as 16 rows: one whole bfloat16 tile a slot
    assert state["conv"].shape == (18, 130, 16, 256)
    assert set(state) == {"pages", "conv"}
    assert all(a.dtype == jnp.bfloat16 for a in state.values())


# ------------------------------------------------ the expert layer's share


def test_the_renormalisation_epsilon_is_the_configs():
    """LFM2 divides by the sum + 1e-6; the other two families keep their
    1e-20, bit for bit."""
    from evam_tpu.models.lm import deepseek_v2, kimi_linear

    cfg = lm.Config.from_dict(TINY)
    scores = np.full((1, 8), 1e-4, np.float32)
    scores[0, [1, 5]] = [2e-3, 1e-3]
    logits = np.log(scores / (1 - scores))
    x = jnp.zeros((1, cfg.hidden), jnp.float32).at[0, 0].set(1.0)
    router = jnp.zeros((cfg.hidden, 8), jnp.float32).at[0].set(logits[0])
    w, ids = experts.route(cfg, x, router, jnp.zeros((8,), jnp.float32))
    assert np.asarray(ids)[0].tolist() == [1, 5]
    got = np.asarray(w)[0]
    np.testing.assert_allclose(got, [2e-3 / (3e-3 + 1e-6),
                                     1e-3 / (3e-3 + 1e-6)], rtol=1e-4)
    assert got.sum() < 1 - 2e-4   # 1e-20 would give 1 to rounding
    w_ref, ids_ref = ref.route(TINY, scores, np.zeros(8, np.float32))
    assert ids_ref[0].tolist() == [1, 5]
    np.testing.assert_allclose(got, w_ref[0], rtol=1e-4)
    assert kimi_linear.Config.topk_eps == deepseek_v2.Config.topk_eps == 1e-20


def test_no_shared_tensors_and_no_shared_term_without_shared_experts():
    cfg = lm.Config.from_dict(TINY)
    assert set(lm.moe_shapes(cfg)) == {
        "router", "router_bias", "expert_gate", "expert_up", "expert_down"}
    lp = lm.make_layers(cfg, (2, 3), lm.moe_shapes(cfg), range(4))
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (16, cfg.hidden)), lm.BF16)
    live = jnp.arange(16) < 12
    y, counts = experts.moe(cfg, lp, x, live, jnp.int32(1))
    # the routed sum alone: the held experts' terms by hand, layer 3's
    w, ids = experts.route(cfg, x, lp["router"][1], lp["router_bias"][1])
    want = np.zeros((16, cfg.hidden), np.float32)
    for e in range(4):
        we = np.where(np.asarray(ids) == e, np.asarray(w), 0).sum(-1)
        want += we[:, None] * np.asarray(lm.common.swiglu(
            x, lp["expert_gate"][1, e], lp["expert_up"][1, e],
            lp["expert_down"][1, e]), np.float32)
    got = np.asarray(y, np.float32)
    assert np.abs(got[:12] - want[:12]).max() < 0.05 * np.abs(want).max()
    assert not got[12:].any()  # dead rows get nothing, no shared term
    assert int(counts[0]) == int(((np.asarray(ids)[:12] < 4)).sum())
    # layer 2's slice of the stack gives another sum
    other, _ = experts.moe(cfg, lp, x, live, jnp.int32(0))
    assert np.abs(np.asarray(other, np.float32) - got).max() > 0.01


@pytest.mark.parametrize("m,sizes", [
    (512, [0, 50, 200, 30]), (512, [100, 290, 2, 1]), (48, [1, 0, 1, 1])])
def test_grouped_kernel_reads_one_layer_of_a_stack(m, sizes):
    r = np.random.default_rng(m + sum(sizes))
    k, n, layers = 128, 256, 3
    rows = jnp.asarray(r.standard_normal((m, k)), pg.BF16)
    gate, up = (jnp.asarray(r.standard_normal((layers, 4, k, n)) * 0.1,
                            pg.BF16) for _ in range(2))
    down = jnp.asarray(r.standard_normal((layers, 4, n, k)) * 0.1, pg.BF16)
    size = jnp.asarray(sizes, jnp.int32)
    mine = sum(sizes)
    for layer in (0, 2):
        l = jnp.int32(layer)
        want_h = pg.swiglu_xla(rows, gate[layer], up[layer], size)
        got_h = pg.swiglu(rows, gate, up, size, l, interpret=True)
        want = pg.product_xla(want_h, down[layer], size)
        got = pg.product(want_h, down, size, l, interpret=True)
        for a, b in ((got_h, want_h), (got, want),
                     (pg.swiglu_xla(rows, gate, up, size, l), want_h)):
            a, b = (np.asarray(x, np.float32)[:mine] for x in (a, b))
            assert np.abs(a - b).max() <= 0.02 * max(1.0, np.abs(b).max())
    with pytest.raises(ValueError, match="addressed by"):
        pg.product(rows, down, size, interpret=True)
    with pytest.raises(ValueError, match="addressed by"):
        pg.product(rows, down[0], size, jnp.int32(0), interpret=True)


# ------------------------------------------- the convolution's two forms


def _rows_inputs(rows, layers=3, slots=10, width=64, seed=0):
    r = np.random.default_rng(seed)
    taps = jnp.asarray(r.standard_normal(
        (layers, slots, *slot_rows.tiled(2 * width))), lm.BF16)
    bx, c = (jnp.asarray(r.standard_normal((rows, width)), lm.BF16)
             for _ in range(2))
    w = jnp.asarray(r.uniform(-0.57, 0.57, (3, width)), lm.BF16)
    return bx, c, w, taps


def test_the_twin_is_the_convolution_written_out():
    bx, c, w, taps = _rows_inputs(3, width=128)
    slot = jnp.asarray([6, 2, 8], jnp.int32)
    live = jnp.ones((3,), bool)
    y, new = psc.decode_rows_xla(jnp.int32(1), slot, live, bx, c, w, taps)
    old = np.asarray(taps, np.float32)[1].reshape(10, 2, 128)
    b32, c32, w32 = (np.asarray(a, np.float32) for a in (bx, c, w))
    for i, s in enumerate((6, 2, 8)):
        z = w32[0] * old[s, 0] + w32[1] * old[s, 1] + w32[2] * b32[i]
        np.testing.assert_allclose(np.asarray(y)[i], c32[i] * z, rtol=1e-5,
                                   atol=1e-6)
        got = np.asarray(new, np.float32)[1, s].reshape(2, 128)
        np.testing.assert_array_equal(got[0], old[s, 1])
        np.testing.assert_array_equal(got[1], b32[i])


@pytest.mark.parametrize("rows,width", [(5, 128), (16, 2048), (8, 64)])
def test_rows_kernel_matches_its_xla_twin(rows, width):
    """The kernel's body in the interpreter against the twin: outputs,
    the rows written, and every row no live row names bit for bit (the
    null row among them, which two dead rows name)."""
    bx, c, w, taps = _rows_inputs(rows, slots=rows + 6, width=width,
                                  seed=rows)
    r = np.random.default_rng(rows)
    live = np.ones(rows, bool)
    live[[1, rows - 1]] = False
    slot = r.permutation(rows + 4)[:rows]
    slot[~live] = rows + 4   # the null row
    slot, live = jnp.asarray(slot, jnp.int32), jnp.asarray(live)
    y0, t0 = psc.decode_rows_xla(jnp.int32(2), slot, live, bx, c, w, taps)
    y1, t1 = psc.decode_rows(jnp.int32(2), slot, live, bx, c, w, taps,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(t1, np.float32),
                                  np.asarray(t0, np.float32))
    named = set(np.asarray(slot)[np.asarray(live)].tolist())
    others = [i for i in range(rows + 6) if i not in named]
    np.testing.assert_array_equal(
        np.asarray(t1, np.float32)[2][others],
        np.asarray(taps, np.float32)[2][others])
    np.testing.assert_array_equal(np.asarray(t1, np.float32)[:2],
                                  np.asarray(taps, np.float32)[:2])
    assert not np.asarray(y1)[~np.asarray(live)].any()
    assert np.abs(np.asarray(t1, np.float32)[2][sorted(named)]
                  - np.asarray(taps, np.float32)[2][sorted(named)]).max() > 0


def test_two_live_rows_that_name_one_slot_are_refused_by_the_twin():
    bx, c, w, taps = _rows_inputs(2)
    with pytest.raises(Exception, match="name one"):
        y, _ = psc.decode_rows_xla(
            jnp.int32(0), jnp.asarray([3, 3], jnp.int32),
            jnp.ones((2,), bool), bx, c, w, taps)
        np.asarray(y)


def test_a_decode_step_moves_the_named_rows_and_no_other():
    """``conv_decode`` updates one layer's taps: the rows its tokens name
    move as the convolution says, every other row comes back bit for bit,
    and a row alone gives what it gives among others."""
    cfg = lm.Config.from_dict(TINY)
    lp = lm._at(lm.make_layers(cfg, (0, 1), lm.conv_shapes(cfg)), 1)
    r = np.random.default_rng(7)
    taps = jnp.asarray(r.standard_normal(
        (10, *slot_rows.tiled(2 * cfg.hidden))), lm.BF16)
    h = jnp.asarray(r.standard_normal((3, cfg.hidden)), lm.BF16)
    h = jnp.concatenate([h, jnp.full((2, cfg.hidden), 1e4, lm.BF16)])
    slot = jnp.asarray([6, 2, 8, 9, 9], jnp.int32)  # two dead rows name 9
    live = jnp.asarray([True, True, True, False, False])
    y, new = lm.conv_decode(cfg, lp, h, slot, live, taps)
    assert not np.asarray(y[3:], np.float32).any()
    others = [i for i in range(10) if i not in (6, 2, 8)]
    np.testing.assert_array_equal(np.asarray(new, np.float32)[others],
                                  np.asarray(taps, np.float32)[others])
    for b, at in enumerate((6, 2, 8)):
        y1, t1 = lm.conv_decode(cfg, lp, h[b:b + 1], slot[b:b + 1],
                                live[b:b + 1], taps)
        np.testing.assert_allclose(np.asarray(y1[0], np.float32),
                                   np.asarray(y[b], np.float32), atol=1e-2)
        np.testing.assert_array_equal(np.asarray(t1[at], np.float32),
                                      np.asarray(new[at], np.float32))
        # the older tap moved up, the newest is B * x
        flat_old = np.asarray(taps[at], np.float32).reshape(2, -1)
        flat_new = np.asarray(new[at], np.float32).reshape(2, -1)
        np.testing.assert_array_equal(flat_new[0], flat_old[1])
        assert np.abs(flat_new[1] - flat_old[1]).max() > 0


def test_a_chunk_continues_the_convolution_where_a_step_left_it():
    """Prefill in two pieces and then a decode step give the sequence's
    one convolution: the taps carried between them are the last two
    values of ``B * x``."""
    cfg = lm.Config.from_dict(TINY)
    lp = lm._at(lm.make_layers(cfg, (0,), lm.conv_shapes(cfg)), 0)
    r = np.random.default_rng(9)
    h = jnp.asarray(r.standard_normal((12, cfg.hidden)), lm.BF16)
    seg = jnp.zeros((12,), jnp.int32)
    zero = jnp.zeros((1, 2 * cfg.hidden), lm.BF16)
    whole, _ = lm.conv_prefill(cfg, lp, h, seg, zero)
    a, carried = lm.conv_prefill(cfg, lp, h[:7], seg[:7], zero)
    b, carried = lm.conv_prefill(cfg, lp, h[7:11], seg[:4], carried)
    taps = jnp.zeros((3, *slot_rows.tiled(2 * cfg.hidden)), lm.BF16)
    taps = taps.at[1].set(carried.reshape(taps.shape[1:]))
    c, _ = lm.conv_decode(cfg, lp, h[11:], jnp.asarray([1], jnp.int32),
                          jnp.ones((1,), bool), taps)
    got = np.concatenate([np.asarray(x, np.float32) for x in (a, b, c)])
    np.testing.assert_allclose(got, np.asarray(whole, np.float32),
                               atol=2e-2 * np.abs(np.asarray(
                                   whole, np.float32)).max())


# ------------------------------------------------------- the attention


def _attention_against(omit=frozenset(), rotated=True):
    """The module's chunk attention over one sequence against the
    reference's, largest difference over the largest value."""
    cfg = lm.Config.from_dict(TINY)
    w = ref.layer_weights(TINY, 2)
    lp = {k: jnp.asarray(v, lm.BF16) for k, v in w.items()}
    r = np.random.default_rng(11)
    h = jnp.asarray(r.standard_normal((40, cfg.hidden)), lm.BF16)
    pos = jnp.arange(40) + 100
    q, kv = attention.qkv(cfg, lp, h, pos)
    got = attention.attn_prefill(cfg, lp, q, kv, jnp.zeros((40,), jnp.int32),
                                 None, 0, None, 0)
    # the reference counts positions from 0: a rotation is relative, so
    # the shift by 100 moves no score
    want = np.asarray(ref.attention(TINY, w, jnp.asarray(h, jnp.float32),
                                    omit, rotated))
    return np.abs(np.asarray(got, np.float32) - want).max() / np.abs(
        want).max()


def test_grouped_queries_head_norms_and_rotation_match_the_reference():
    assert _attention_against() < 0.03


@pytest.mark.parametrize("lacks", ["pairing", "head_norms", "rotation"])
def test_attention_differs_from_a_reference_that_lacks(lacks):
    kw = ({"rotated": False} if lacks == "rotation"
          else {"omit": frozenset([lacks])})
    assert _attention_against(**kw) > 0.15


@pytest.mark.parametrize("heads,group,tokens,prefix,cont", [
    (2, 2, 48, 32, 24), (1, 4, 40, 0, 0), (2, 2, 16, 128, 0)])
def test_chunk_attention_kernel_matches_its_xla_twin(heads, group, tokens,
                                                     prefix, cont):
    """ops/pallas_attention.py in the interpreter against the same
    arithmetic through XLA, under a packed chunk's bounds: prefix rows,
    a continued sequence's rows (to segment 0 only), own rows up to the
    token, a padded tail that sees nothing."""
    from evam_tpu.models.lm import common
    from evam_tpu.ops import pallas_attention as pa

    r = np.random.default_rng(tokens + prefix)
    d = 16
    seg = np.repeat(np.arange(4), tokens // 4)
    seg[-3:] = -1
    bounds, b0 = common.chunk_bounds(jnp.asarray(seg), prefix - 5, cont - 2,
                                     prefix, cont)
    keys = prefix + cont + tokens
    q = jnp.asarray(r.standard_normal((heads, tokens * group, d)), lm.BF16)
    k, v = (jnp.asarray(r.standard_normal((heads, keys, d)), lm.BF16)
            for _ in range(2))
    rows = jnp.repeat(bounds, group, axis=0)
    want = pa.chunk_attention_xla(q, k, v, rows, scale=0.25, b0=b0)
    got = pa.chunk_attention(q, k, v, rows, scale=0.25, b0=b0,
                             interpret=True)
    assert got.shape == want.shape == (heads, tokens * group, d)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.02)
    dead = np.repeat(seg < 0, group)
    assert not np.asarray(got, np.float32)[:, dead].any()
    assert np.abs(np.asarray(got, np.float32)[:, ~dead]).max() > 0.1


def test_the_cache_row_holds_rotated_keys_then_values():
    cfg = lm.Config.from_dict(TINY)
    lp = {k: jnp.asarray(v, lm.BF16)
          for k, v in ref.layer_weights(TINY, 2).items()}
    h = jnp.asarray(np.random.default_rng(3).standard_normal(
        (4, cfg.hidden)), lm.BF16)
    _, at0 = attention.qkv(cfg, lp, h, jnp.zeros((4,), jnp.int32))
    _, at9 = attention.qkv(cfg, lp, h, jnp.full((4,), 9, jnp.int32))
    half = cfg.kv_width // 2
    assert at0.shape == (4, cfg.kv_width) and half == 2 * 16
    # values do not turn, keys do
    np.testing.assert_array_equal(np.asarray(at0[:, half:], np.float32),
                                  np.asarray(at9[:, half:], np.float32))
    assert np.abs(np.asarray(at0[:, :half], np.float32)
                  - np.asarray(at9[:, :half], np.float32)).max() > 0.1
    # Jamba's rows through the same function: no norms, no rotation
    from evam_tpu.models.lm import jamba

    jcfg = jamba.Config.from_dict(PRESETS["jamba_tiny"])
    assert (jcfg.kv_heads, jcfg.rope_theta) == (1, None)
    assert set(jamba.attn_shapes(jcfg)) >= {"q", "k", "v", "o"}
    assert "q_norm" not in jamba.attn_shapes(jcfg)


# ---------------------------------------------------------- the engine


@pytest.mark.parametrize("length", [3, 20, 150])
def test_prefill_then_decode_matches_the_reference(engine, length):
    """Through the engine, slot taps AND key-value pages: packed prefill
    from the prefix snapshot over the pinned prefix pages (150 tokens
    cross a chunk boundary: the second chunk starts from the slot's own
    taps and attends to the first chunk's pages), then decode steps in a
    running batch, against the reference's full forward pass."""
    prompt = _prompt(length, length)
    out = _generate(engine, prompt)
    problems, stats = _compare(
        out, _ref_logits(engine.prefix, prompt, out),
        lfm2_moe_child.limits_scale(TINY))
    assert not problems, (problems, stats)
    assert out["prefix_tokens"] == 16


def test_rows_that_carry_no_sequence_never_reach_the_state(engine):
    """Sixty decode steps of which most rows carry nothing: the null row
    stays what warm-up left there, bit for bit."""
    _idle(engine)
    null = np.asarray(engine._state["conv"][:, SIZES.slots], np.float32)
    prompt = _prompt(77, 9)
    out = _generate(engine, prompt, n=60)
    assert np.isfinite(out["top_logits"]).all()
    np.testing.assert_array_equal(np.asarray(
        engine._state["conv"][:, SIZES.slots], np.float32), null)
    problems, stats = _compare(
        out, _ref_logits(engine.prefix, prompt, out),
        lfm2_moe_child.limits_scale(TINY))
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
    """Eight prompts packed into one chunk, each right behind the other
    (``SEGMENT_ALIGN`` 1): neither the convolution's taps nor the
    attention cross a segment's start (eight tokens each: one flipped
    routing decision in a handful of tokens is no share of them)."""
    lengths = [4, 16, 7, 1, 12, 3, 9, 5]
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
    assert (seg >= 0).sum() == sum(lengths)
    for prompt, one, many in zip(prompts, alone, packed):
        np.testing.assert_allclose(many["top_logits"][0],
                                   one["top_logits"][0], atol=1e-5)
        assert many["ids"][0] == one["ids"][0]
        problems, stats = _compare(
            many, _ref_logits(engine.prefix, prompt, many),
            lfm2_moe_child.limits_scale(TINY))
        assert not problems, (problems, stats)


def test_prefix_as_pages_and_snapshot_equals_the_prefix_before_the_prompt(
        engine):
    """The shared prefix as pinned pages (the attention layers) AND a
    snapshot row of taps (the convolution layers) against the same tokens
    run in front of the prompt by an engine that shares nothing (a prompt
    whose greedy choices are no near ties: the two engines pad to
    different rows)."""
    prompt = _prompt(11, 10)
    shared = _generate(engine, prompt)
    private_engine = _engine(np.zeros((0,), np.int32), "generate:private",
                             dataclasses.replace(SIZES, slots=1))
    try:
        private = _generate(private_engine,
                            np.concatenate([engine.prefix, prompt]))
    finally:
        private_engine.stop()
    assert private["ids"] == shared["ids"]
    np.testing.assert_allclose(private["top_logits"], shared["top_logits"],
                               atol=0.1)
    _idle(engine)
    assert engine.pages_in_use() == (2, 2 + 8 * 20)
    # the snapshot is the prefix's last two inputs, not zeros
    snap = np.asarray(engine._state["conv"][:, SIZES.slots + 1], np.float32)
    assert np.abs(snap).max() > 0


def test_a_released_slot_taken_again_carries_nothing_over(engine):
    _idle(engine)
    prompt = _prompt(31, 12)
    first = _generate(engine, prompt)
    _idle(engine)
    slot = engine._free_slots[-1]  # the next request's slot
    other = _generate(engine, _prompt(32, 25), n=9)
    assert other["ids"] != first["ids"]
    _idle(engine)
    assert engine._free_slots[-1] == slot  # last in, first out
    again = _generate(engine, prompt)
    assert again["ids"] == first["ids"]
    np.testing.assert_array_equal(again["top_logits"], first["top_logits"])
    # and the snapshot row is what warm-up left: nothing writes it
    snap = np.asarray(engine._state["conv"][:, SIZES.slots + 1], np.float32)
    _generate(engine, _prompt(33, 7))
    np.testing.assert_array_equal(snap, np.asarray(
        engine._state["conv"][:, SIZES.slots + 1], np.float32))


def test_cancel_frees_slots_pages_and_state(engine):
    futs = [engine.submit(stream="doomed", prompt_ids=_prompt(i, 8),
                          max_new_tokens=40) for i in range(12)]
    keep = engine.submit(stream="kept", prompt_ids=_prompt(3, 8),
                         max_new_tokens=4)
    engine.cancel_stream("doomed")
    assert all(f.result(timeout=60) is None for f in futs)
    assert len(keep.result(timeout=300)["ids"]) == 4
    _idle(engine)
    assert engine.pages_in_use()[0] == 2
    assert len(engine._free_slots) == SIZES.slots
    assert engine.state_slots()[:2] == (0, SIZES.slots)
    assert engine.queue_depth() == 0


def test_every_series_is_live_and_the_engines_row(engine):
    """Taps rows and prefix restores, cache rows and shared rows, held
    assignments, the held experts hit and the matrices read."""
    from evam_tpu.engine.hub import EngineHub
    from evam_tpu.obs import metrics

    def counted():
        c = metrics.get_counter
        return {
            "state_decode": c("evam_generate_state_rows", {"kind": "decode"}),
            "state_prefill": c("evam_generate_state_rows",
                               {"kind": "prefill"}),
            "restores": c("evam_generate_prefix_restores"),
            "tokens": c("evam_generate_tokens", {"kind": "decode"}),
            "prefill_tokens": c("evam_generate_tokens", {"kind": "prefill"}),
            "rows": c("evam_generate_latent_rows_read", {"kind": "decode"}),
            "shared": c("evam_generate_decode_shared_rows"),
            "held": c("evam_moe_held_assignments"),
            "hit_decode": c("evam_moe_held_experts_hit", {"kind": "decode"}),
            "reads_decode": c("evam_moe_expert_reads", {"kind": "decode"})}

    _idle(engine)
    before = counted()
    _generate(engine, _prompt(5, 150))  # two chunks: one restore, two states
    deadline = time.time() + 10
    while (counted()["tokens"] - before["tokens"] < NEW - 1
           and time.time() < deadline):
        time.sleep(0.05)
    grew = {k: v - before[k] for k, v in counted().items()}
    assert {k: grew[k] for k in ("state_decode", "state_prefill", "restores",
                                 "tokens", "prefill_tokens")} == {
        "state_decode": NEW - 1, "state_prefill": 2, "restores": 1,
        "tokens": NEW - 1, "prefill_tokens": 150}
    assert grew["shared"] == 16 * (NEW - 1)
    assert grew["rows"] == sum(16 + 150 + i + 1 for i in range(NEW - 1))
    # eight expert layers, four of eight experts held, two a token
    assert 0 < grew["held"] <= 8 * 2 * (150 + NEW - 1)
    assert 0 < grew["hit_decode"] <= 8 * 4 * (NEW - 1)
    assert grew["reads_decode"] == grew["hit_decode"]
    cfg = engine.cfg
    per_row = len(cfg.conv_ids) * 2 * 2 * cfg.hidden
    assert engine.state_slots() == (0, 8, 10 * per_row)
    row = EngineHub._stat_row(engine, None, None, engine.name)
    assert (row["state_slots"], row["state_slots_in_use"],
            row["state_bytes"]) == (8, 0, 10 * per_row)
    assert (row["pages"], row["pages_in_use"]) == (2 + 8 * 20, 2)


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
                        lfm2_moe_child.limits_scale(TINY))
        problems += p
    return problems


def test_comparator_passes_the_whole_model(published, engine):
    assert not _verdict(published, engine)


@pytest.mark.parametrize("omit", [
    "taps", "gate_b", "gate_c", "head_norms", "pairing", "router_bias",
    "renormalize", "expert:1", "control:weights", "control:rope",
    "control:taps"])
def test_comparator_fails_when_a_term_or_the_precision_is_taken_away(
        published, engine, omit):
    kw = {"control:weights": {"weight_dtype": jnp.float8_e4m3fn},
          "control:rope": {"rotated": False},
          "control:taps": {"taps_lost": True}}.get(
              omit, {"omit": frozenset([omit])})
    assert _verdict(published, engine, **kw), omit


def test_a_row_of_another_sequence_is_refused(published, engine):
    (p0, o0), (p1, _), _ = published
    problems, stats = _compare(o0, _ref_logits(engine.prefix, p1, o0),
                               lfm2_moe_child.limits_scale(TINY))
    assert problems and stats["max"] > lfm2_moe_child.LOGIT_TOKEN_TOL


def test_the_child_knows_its_three_controls():
    assert lfm2_moe_child.CONTROLS == ("weights", "rope", "taps")
    assert lfm2_moe_child.limits_scale(FULL) == 1.0
    assert lfm2_moe_child.limits_scale(TINY) == pytest.approx(
        (10 / 24) ** 0.5)
    assert lfm2_moe_child.READINGS == ("acts",)
    assert "evam_tpu" not in (REPO / "benchmark" / "reference"
                              / "lfm2_moe_plain.py").read_text().replace(
        "evam_tpu/", "")


# ------------------------------------------------ configuration files


def test_benchmark_config_holds_the_published_widths_and_the_preset():
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "lfm2_moe_ep2.json").read_text())
    path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    catalog = ([json.loads(line) for line in open(path)]
               if path.is_file() else [])
    entry = next((e for e in catalog if e["name"] == "LFM2-8B-A1B"), None)
    if entry is not None:
        assert entry["config"] == LFM2_8B_A1B_PUBLISHED
        assert cfg["source"] == entry["source_url"]
    reduced = {"num_experts": 16, "vocab_size": 32768}
    for key, value in LFM2_8B_A1B_PUBLISHED.items():
        assert cfg[key] == reduced.get(key, value), key
    assert cfg["reduced"] == [*reduced, "weights"]
    assert cfg["num_hidden_layers"] == 24   # the whole depth: no stage cut
    for key in reduced:
        assert cfg["published"][key] == LFM2_8B_A1B_PUBLISHED[key]
    assert {"tie_embedding", "dense_width", "rope_pairing", "conv_init",
            "expert_bias", "topk_eps", "qk_norm_gain", "slots",
            "page_tokens", "chunk_tokens", "slot_state"} <= set(
        cfg["assumed"])
    assert cfg["fallback"].startswith("not taken")
    model = cfg["shapes"]["model"]
    assert {k: model[k] for k in FULL} == FULL
    assert (model["num_experts"], model["experts_held"], model["held_lo"],
            model["num_experts_per_tok"], model["vocab_held"]) == (
        32, 16, 0, 4, 32768)
    assert model["engine_prefix_tokens"] == \
        cfg["shapes"]["engine"]["prefix_tokens"] == 2048
    assert {k: cfg["rehearsal_shapes"]["model"][k] for k in TINY} == TINY
    assert cfg["request"]["parameters"]["max-new-tokens"] == \
        cfg["shapes"]["engine"]["max_new_tokens"] == 64
    assert cfg["opsbytes"] == "lfm2_moe"
    assert cfg["reference"]["child"] == "lfm2_moe_child"
    assert set(cfg["server_env"]) == {"EVAM_PRELOAD", "EVAM_MAX_BATCH",
                                      "EVAM_NATIVE"}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"]
                if w["name"] == "describe_lfm2_replay")
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        "lfm2_moe_ep2", 1, "replay_1080p_x32")
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == cfg["reduced"] and len(entry["why"]) <= 200
    rate = next(m for m in bench["end_to_end"] if m["name"] == "frames_per_s")
    assert "describe_lfm2_replay" in rate["workloads"]
    mine = [m for m in bench["per_layer"]
            if "describe_lfm2_replay" in m.get("workloads", [])]
    assert len(mine) >= 20
    for m in mine:
        assert m["workloads"] == ["describe_lfm2_replay"]
        assert m["moves"] == "frames_per_s"
        assert m["name"].endswith(".lfm2_replay")
        assert (REPO / "benchmark" / "metrics"
                / f"{m['name']}.json").is_file()
    hit = json.loads((REPO / "benchmark" / "metrics"
                      / "lm_held_experts_hit_share.lfm2_replay.json"
                      ).read_text())["params"]["scale"]
    assert hit == pytest.approx(100 / 352)
    pipe = json.loads((REPO / "pipelines" / "scene_description"
                       / "pvb_lfm2_moe" / "pipeline.json").read_text())
    assert pipe["parameters"]["properties"]["max-new-tokens"]["default"] == 64
    assert [s.get("model") for s in pipe["stages"] if "model" in s] == [
        "scene_description/pvb_lfm2_moe", "scene_description_lm/lfm2_moe"]


def test_opsbytes_count_taps_experts_and_the_prefix_once_a_step():
    m = dict(FULL, engine_prefix_tokens=2048)
    ctx = 2048 + 272 + 64
    one = opsbytes.steps(m, prefill_steps=0, prefill_tokens=0,
                         prefill_prompts=0, prefill_rows=0, decode_steps=1,
                         decode_tokens=64, decode_rows=64 * ctx,
                         held_assignments=22 * 128, sampled_rows=64)
    expert = 3 * 2048 * 1792
    # every weight once (the embedding is the head); the 64 embedding rows
    weights = 2.0 * opsbytes.parameters(m)
    taps = 2 * 64 * 18 * (2 * 2 * 2048)
    # the prefix once, each row's own 336 rows, the 64 new rows
    rows = 2.0 * 6 * 1024 * (2048 + 64 * (272 + 64) + 64)
    assert one["bytes"] == pytest.approx(
        weights + taps + rows + 2.0 * 64 * 2048)
    assert 8.7e9 < weights < 8.9e9 and taps == 18_874_368
    few = opsbytes.steps(m, prefill_steps=0, prefill_tokens=0,
                         prefill_prompts=0, prefill_rows=0, decode_steps=1,
                         decode_tokens=2, decode_rows=2 * ctx,
                         held_assignments=22 * 4, sampled_rows=2)
    # 4 assignments a layer can have reached 4 of the 16 held experts
    assert one["bytes"] - few["bytes"] > 2.0 * 22 * 12 * expert
    sizing = opsbytes.ops_and_bytes(
        {"model": m, "engine": {"prefix_tokens": 2048, "max_objects": 32,
                                "max_new_tokens": 64}}, 64)
    assert sizing["bytes"] == pytest.approx(one["bytes"])


def test_attention_metrics_read_the_one_kernel_name():
    from benchmark.readers import trace_op_share

    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "lfm2_moe_ep2.json").read_text())
    snap = {"metrics": {}, "engines": {}}
    after = {"metrics": {
        'evam_generate_tokens_total{kind="prefill"}': 4096.0,
        'evam_generate_steps_total{kind="prefill"}': 8.0,
        'evam_generate_steps_total{kind="decode"}': 12.0}, "engines": {}}

    def ctx(ops):
        return {"device_trace": {"busy_s": 2.0, "devices": 1, "steps": 10,
                                 "device_ops": ops},
                "trace_before": snap, "trace_after": after, "config": cfg,
                "device": {"kind": "TPU v5e"},
                "peaks_file": REPO / "benchmark" / "peaks.json"}

    files = [json.loads((REPO / "benchmark" / "metrics"
                         / f"{m}.lfm2_replay.json").read_text())["params"]
             for m in ("attn_chunk_busy_share", "attn_chunk_roofline")]
    assert [(f["op"], f["names"]) for f in files] == [
        ("attn_chunk_attention", 1)] * 2 and files[1]["layers"] == 6
    ops = [["while.1 s32[]", 1.5],
           ["attn_chunk_attention.2 bf16[8,2048,64]", 0.1]]
    assert trace_op_share.read(ctx(ops), files[0]) == pytest.approx(5.0)
    # a token and head over the prefix's 2048 rows at least: 4 d a pair
    least = 6 * (4096 * 32 * 2048 * 4 * 64 / 197e12) * (10 / 20)
    got = trace_op_share.read(ctx(ops), files[1])
    assert got == pytest.approx(100.0 * least / 0.1) and 0 < got < 100
    scan = opsbytes.scan_ops_and_bytes(
        dict(FULL, engine_prefix_tokens=2048), 512)
    assert scan == {"flops": 512.0 * 32 * 2048 * 256,
                    "bytes": 512.0 * 4 * 2048}
    # a program without the kernel (the parent's): nothing to read
    assert trace_op_share.read(ctx(ops[:1]), files[0]) is None


# --------------------------------------------------------- the server


def _registry(tmp_path):
    from evam_tpu.engine import EngineHub
    from evam_tpu.models import ModelRegistry
    from evam_tpu.models.fetch import synthesize_lm, synthesize_omz
    from evam_tpu.parallel import build_mesh
    from evam_tpu.server.registry import PipelineRegistry

    models = tmp_path / "models"
    synthesize_omz(models, alias="scene_description",
                   version="pvb_lfm2_moe", input_size=128)
    synthesize_lm(models, "scene_description_lm", "lfm2_moe",
                  "lfm2_moe_tiny")
    settings = Settings(pipelines_dir=str(REPO / "pipelines"),
                        state_dir=str(tmp_path / "state"))
    hub = EngineHub(
        ModelRegistry(models_dir=models, dtype="float32"), plan=build_mesh(),
        max_batch=4, deadline_ms=4.0,
        lm=LMSettings(slots=4, page_tokens=8, chunk_tokens=64, max_segments=4,
                      private_tokens=288, prefix_tokens=16))
    return PipelineRegistry(settings, hub=hub)


def test_fourth_describe_pipeline_end_to_end_through_rest(eight_devices,
                                                          tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from evam_tpu.server.app import build_app

    reg = _registry(tmp_path)
    out = tmp_path / "out.jsonl"
    path = "/pipelines/scene_description/pvb_lfm2_moe"

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
    problems, stats = _compare(desc, np.asarray(logits),
                               lfm2_moe_child.limits_scale(TINY))
    assert not problems, (problems, stats)
    row = engines["generate:scene_description_lm/lfm2_moe"]
    assert row["items"] == 6 and row["compiled_programs"] == 5
    assert (row["state_slots"], row["state_slots_in_use"]) == (4, 0)
    assert row["pages_in_use"] == 2 and row["capacity_fps"] > 0
    assert row["state_bytes"] > 0


# ------------------------------------ what the shared modules compute


@pytest.mark.parametrize("program,on_chip,want", [
    ("decode", True, "7843f475c18b948f"),
    ("decode", False, "b2ca0004bdab42f1"),
    ("prefill", True, "912983d448a06902"),
    ("prefill", False, "e086034d64e2e7de")])
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

    check("lfm2_moe_ep2", program, on_chip, monkeypatch, want)
