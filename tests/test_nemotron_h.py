"""The sixth language-model family: Nemotron-H (models/lm/nemotron_h.py)
through the generate engine with a matrix state per slot AND key-value pages
(engine/generate.py), blocks of ONE sublayer by a pattern string, the
Mamba-2 recurrence in its chunkwise dual form and its one-token body
(ops/pallas_ssd.py), the expert layer in a projected latent with experts of
two matrices under relu^2 and 6 chosen of 16 (models/lm/experts.py,
ops/pallas_grouped.py ``relu2``), attention as a ``Kind`` without positions
(models/lm/attention.py), the sixth describe pipeline, and the comparison
that decides the Nemotron cell's ``correct``
(benchmark/reference/nemotron_h_child.py), all at a tiny size on the CPU
against the plain reference (benchmark/reference/nemotron_h_plain.py): the
same structure as the published stage (``MEM*EME``: Mamba-2, experts,
Mamba-2, attention, experts, Mamba-2, experts; an eighth of the experts
held; an untied head)."""

import asyncio
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.opsbytes import nemotron_h as opsbytes
from benchmark.reference import lm_compare, nemotron_h_child
from benchmark.reference import nemotron_h_plain as ref
from benchmark.reference.compare import check_schema
from evam_tpu.config.settings import LMSettings, Settings
from evam_tpu.engine.generate import GenerateEngine, GenerateSizes
from evam_tpu.models.lm import common, experts, family
from evam_tpu.models.lm import nemotron_h as lm
from evam_tpu.models.lm.presets import NEMOTRON3_SUPER_PUBLISHED, PRESETS
from evam_tpu.obs import metrics
from evam_tpu.ops import pallas_grouped, pallas_ssd, slot_rows

REPO = Path(__file__).resolve().parent.parent
TINY = PRESETS["nemotron_h_tiny"]
FULL = PRESETS["nemotron3_super_ep8"]
SIZES = GenerateSizes(slots=4, page_tokens=4, chunk_tokens=64,
                      max_segments=8, private_tokens=120)
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


def _engine(prefix, name="generate:nemotron", sizes=SIZES):
    eng = GenerateEngine(name, TINY, prefix, sizes=sizes)
    eng.warm_async()
    assert eng.warmed.wait(300) and eng.warm_error is None
    return eng


@pytest.fixture(scope="module")
def engine():
    eng = _engine(_prefix())
    yield eng
    eng.stop()


_compare = nemotron_h_child.compare_logits
_SCALE = nemotron_h_child.limits_scale(TINY)


def _generate(eng, prompt, n=NEW, stream="s"):
    return eng.submit(stream=stream, prompt_ids=prompt,
                      max_new_tokens=n).result(timeout=300)


def _ref_logits(prefix, prompt, result, **kw):
    """The reference's logits rows at the generated positions."""
    full = np.concatenate([prefix, prompt, result["ids"]]).astype(np.int64)
    first = len(prefix) + len(prompt) - 1
    if kw.get("carried") is False:
        kw["fresh_at"] = first + 1
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
    assert off.mean() <= 1e-3, name
    assert np.all(np.abs(got - want)[off] <= np.abs(want[off]) / 64), name


def _idle(eng, timeout=10):
    deadline = time.time() + timeout
    while ((eng.pages_in_use()[0] != eng._prefix_pages
            or len(eng._free_slots) != eng.sizes.slots)
           and time.time() < deadline):
        time.sleep(0.05)


# ------------------------------------------------------------ the model


def test_the_blocks_follow_from_the_pattern_string():
    cfg = lm.Config.from_dict(FULL)
    assert cfg.pattern == "MEMEMEM*EME"
    assert (cfg.mamba_ids, cfg.attn_ids, cfg.moe_ids) == (
        (0, 2, 4, 6, 9), (7,), (1, 3, 5, 8, 10))
    assert cfg.attn_after == (-1, -1, -1, 0, -1)
    assert (cfg.top_k, cfg.n_experts, cfg.n_held, cfg.moe_latent,
            cfg.expert_act, cfg.n_shared) == (22, 512, 64, 1024, "relu2", 2)
    assert (cfg.attn.heads, cfg.attn.kv_heads, cfg.attn.head_dim,
            cfg.attn.rope, cfg.attn.window) == (32, 2, 128, None, None)
    assert (cfg.d_inner, cfg.conv_width, cfg.kv_width) == (8192, 10240, 512)
    # the whole published pattern is pairs of a mixer and an expert layer
    whole = lm.Config.from_dict({**FULL, "num_hidden_layers": 88})
    assert (len(whole.mamba_ids), len(whole.moe_ids),
            len(whole.attn_ids)) == (40, 40, 8)
    tiny = lm.Config.from_dict(TINY)
    assert tiny.pattern == "MEM*EME" and tiny.attn_after == (-1, 0, -1)
    assert family("nemotron_h") is lm and lm.SEGMENT_ALIGN == 1


@pytest.mark.parametrize("key,value", [
    ("hybrid_override_pattern", "EMEM*EM"),     # an expert layer first
    ("hybrid_override_pattern", "MME*EME"),     # two mixers in a row
    ("num_hidden_layers", 6),                   # ends in a mixer
    ("mlp_hidden_act", "silu"), ("n_group", 2), ("use_conv_bias", False),
    ("tie_word_embeddings", True), ("moe_latent_size", None),
    ("residual_in_fp32", True), ("n_groups", 4)])
def test_a_config_of_another_shape_is_refused(key, value):
    with pytest.raises(ValueError):
        lm.Config.from_dict({**TINY, key: value})


def test_weights_are_the_same_tensors_in_program_and_reference():
    cfg = lm.Config.from_dict(TINY)
    params = lm.make_params(cfg)
    # block 2 is the second Mamba-2 mixer
    w = ref.block_weights(TINY, 2)
    assert set(w) == set(lm.mamba_shapes(cfg))
    for name, shape in lm.mamba_shapes(cfg).items():
        assert params["mamba"][name][1].shape == shape
        _same_tensor(params["mamba"][name][1], w[name], name)
    # block 3 the attention block, its q and k drawn wider
    w = ref.block_weights(TINY, 3)
    for name in lm.attn_shapes(cfg):
        _same_tensor(params["attn"][name][0], w[name], name)
    # block 4 the second expert layer
    w = ref.block_weights(TINY, 4)
    for name in w:
        _same_tensor(params["moe"][name][1], w[name], name)
    assert set(params["moe"]) == set(w) | {"expert_up", "expert_down"}
    # 2 of 16 experts, each its own tensor, every layer in ONE stack
    assert params["moe"]["expert_up"].shape == (3, 2, 32, 48)
    assert params["moe"]["expert_down"].shape == (3, 2, 48, 32)
    _same_tensor(params["moe"]["expert_down"][2, 1],
                 ref.tensor(TINY, 6, "expert_down", (48, 32), 1))
    for name, shape in (("embed", (cfg.vocab, cfg.hidden)),
                        ("head", (cfg.hidden, cfg.vocab))):
        _same_tensor(params[name],
                     ref.tensor(TINY, ref.GLOBAL_LAYER, name, shape))
    a = np.exp(np.asarray(params["mamba"]["A_log"], np.float32))
    assert 1 <= a.min() and a.max() <= 16.1
    step = jax.nn.softplus(params["mamba"]["dt_bias"].astype(jnp.float32))
    assert 0.0009 < float(step.min()) and float(step.max()) < 0.11
    # the deployment draws both from the low end of those ranges (the
    # configuration's ``assumed`` (6)): a state that remembers
    full = lm.Config.from_dict(FULL)
    for name, lo, hi in (("A_log", 1.0, 1.5), ("dt_bias", 0.001, 0.002)):
        w = lm.make_tensor(full, 2, name, (128,))
        _same_tensor(w, ref.tensor(FULL, 2, name, (128,)), name)
        w = w.astype(jnp.float32)
        drawn = np.asarray(jnp.exp(w) if name == "A_log"
                           else jax.nn.softplus(w))
        assert lo * 0.99 <= drawn.min() and drawn.max() <= hi * 1.01, name
    assert abs(np.asarray(params["mamba"]["D"], np.float32).mean() - 1) < 0.3
    assert np.abs(np.asarray(params["moe"]["router_bias"])).max() > 0
    wide = lm.make_tensor(cfg, 3, "q", (64, 64), True).astype(jnp.float32)
    plain = lm.make_tensor(cfg, 3, "q", (64, 64)).astype(jnp.float32)
    assert float(jnp.std(wide)) == pytest.approx(
        cfg.qk_init_scale * float(jnp.std(plain)), rel=0.05)


def test_parameter_count_matches_the_benchmarks_arithmetic():
    cfg = lm.Config.from_dict(FULL)
    model = dict(FULL, engine_prefix_tokens=2048)
    assert lm.param_count(cfg) == opsbytes.parameters(model) == 2_752_338_304
    # the issue's arithmetic, term by term
    mamba = (4096 * (8192 + 10240 + 128) + 4 * 10240 + 10240 + 3 * 128
             + 8192 + 8192 * 4096 + 4096)
    attn = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    expert = 2 * 1024 * 2688
    rest = (4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096)
    assert (mamba, attn, expert, rest) == (109_640_064, 35_655_680,
                                           5_505_024, 54_530_560)
    assert lm.param_count(cfg) == (5 * mamba + attn + 5 * (rest + 64 * expert)
                                   + 2 * 16384 * 4096 + 4096)
    uncut = {**model, "num_hidden_layers": 88, "experts_held": 512,
             "vocab_held": 131072}
    assert lm.param_count(lm.Config.from_dict(uncut)) == \
        opsbytes.parameters(uncut) == (
            40 * mamba + 8 * attn + 40 * (rest + 512 * expert)
            + 2 * 131072 * 4096 + 4096)
    assert round(opsbytes.parameters(uncut) / 1e9, 2) == 120.67
    state = lm.state_shapes(cfg, 401, 128, 128)
    assert state["pages"].shape == (1, 401, 128, 512)
    assert state["ssm"].shape == (5, 130, 64, 128, 128)
    assert state["ssm"].dtype == jnp.float32
    assert state["conv"].shape == (5, 130, 16, 1920)
    row = 64 * 128 * 128 * 4
    assert row == 4_194_304     # 4 MB a row and layer, 21 MB a row


# ---------------------------------------------------------- the kernels


def _scan_inputs(rng, t, heads, groups, seg, n_seg, p=64, n=128):
    return (rng.normal(size=(t, heads * p)).astype(np.float32),
            0.1 * np.log1p(np.exp(rng.normal(size=(t, heads)))).astype(
                np.float32),
            -rng.uniform(1, 16, size=(heads,)).astype(np.float32),
            rng.normal(size=(t, groups * n)).astype(np.float32),
            rng.normal(size=(t, groups * n)).astype(np.float32),
            jnp.asarray(seg, jnp.int32),
            rng.normal(size=(n_seg, heads // 2, n, 2 * p)).astype(np.float32))


@pytest.mark.parametrize("seg,n_seg", [
    # three segments and a dead tail, two boundaries inside one block
    (np.r_[np.zeros(100), np.ones(90), np.full(50, 2), -np.ones(16)], 4),
    # one segment over both blocks: the state carried in VMEM
    (np.zeros(256), 2),
    # dead rows first, a segment of ONE token, a boundary AT a block's edge
    (np.r_[-np.ones(3), np.zeros(124), np.ones(1), np.full(128, 2)], 3),
    # the segments' ids out of order, and one with no token here
    (np.r_[np.ones(130), np.zeros(10), -np.ones(116)], 3)])
def test_chunk_scan_kernel_matches_its_xla_twin(seg, n_seg):
    args = _scan_inputs(np.random.default_rng(0), 256, 4, 2, seg, n_seg)
    y_ref, h_ref = pallas_ssd.chunk_scan_xla(*args)
    y, h = pallas_ssd.chunk_scan(*args, interpret=True)
    live = (np.asarray(seg) >= 0)[:, None]
    np.testing.assert_allclose(np.where(live, y, 0), y_ref, atol=2e-4)
    np.testing.assert_allclose(h, h_ref, atol=2e-5)
    # a segment with no token here keeps its h0, bit for bit
    absent = sorted(set(range(n_seg)) - set(np.asarray(seg).astype(int)))
    assert absent or n_seg == 3
    np.testing.assert_array_equal(np.asarray(h)[absent], args[6][absent])


def test_the_visits_of_a_packed_chunk_are_its_block_segment_pairs():
    seg = np.r_[np.zeros(100), np.ones(90), np.full(50, 2), -np.ones(16)]
    blk, row, lo, hi, opens, closes, n = (np.asarray(a) for a in (
        pallas_ssd.visits(jnp.asarray(seg, jnp.int32), 4)))
    assert int(n) == 4 and len(blk) == 2 + 4
    assert blk[:4].tolist() == [0, 0, 1, 1]
    assert row[:4].tolist() == [0, 1, 1, 2] and row[4:].tolist() == [4, 4]
    assert lo[:4].tolist() == [0, 100, 0, 62]
    assert hi[:4].tolist() == [100, 128, 62, 112]
    assert opens[:4].tolist() == [1, 1, 0, 1]
    assert closes[:4].tolist() == [1, 0, 1, 1]
    assert (hi[4:] == 0).all() and (blk[4:] == 1).all()


def test_decode_rows_kernel_matches_its_twin_and_moves_only_live_rows():
    rng = np.random.default_rng(3)
    heads, p, n, groups, b = 4, 64, 128, 2, 5
    state = jnp.asarray(rng.normal(size=(2, 7, heads // 2, n, 2 * p)),
                        jnp.float32)
    tile = slot_rows.tiled(3 * (heads * p + 2 * groups * n))
    conv = jnp.asarray(rng.normal(size=(2, 7, *tile)), jnp.bfloat16)
    args = (0.1 * np.abs(rng.normal(size=(b, heads))).astype(np.float32),
            -rng.uniform(1, 16, size=(heads,)).astype(np.float32),
            rng.normal(size=(b, heads * p)).astype(np.float32),
            rng.normal(size=(b, groups * n)).astype(np.float32),
            rng.normal(size=(b, groups * n)).astype(np.float32),
            jnp.asarray(rng.normal(size=(b, *tile)), jnp.bfloat16))
    slot = jnp.asarray([3, 0, 6, 5, 6])
    live = jnp.asarray([True, True, False, True, False])
    want = pallas_ssd.decode_rows_xla(jnp.int32(1), slot, live, *args, state,
                                      conv)
    got = pallas_ssd.decode_rows(jnp.int32(1), slot, live, *args, state,
                                 conv, interpret=True)
    np.testing.assert_allclose(got[0], want[0], atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[2], np.float32),
                                  np.asarray(want[2], np.float32))
    assert not np.asarray(got[0])[[2, 4]].any()
    # layer 0, and the rows no live row names (the null row 6 among them,
    # named twice), are as they were, bit for bit
    for new, old in ((got[1], state), (got[2], conv)):
        new, old = (np.asarray(a, np.float32) for a in (new, old))
        np.testing.assert_array_equal(new[0], old[0])
        np.testing.assert_array_equal(new[1, [1, 2, 4, 6]],
                                      old[1, [1, 2, 4, 6]])
        assert (new[1, [0, 3, 5]] != old[1, [0, 3, 5]]).any()


def test_a_token_through_the_decode_body_is_a_token_through_the_scan():
    """One recurrence, two bodies: a chunk of 128 tokens through the scan
    and the same tokens one by one through the decode body."""
    rng = np.random.default_rng(5)
    x, dt, a, b, c, seg, h0 = _scan_inputs(rng, 128, 4, 2, np.zeros(128), 1)
    y_ref, h_ref = pallas_ssd.chunk_scan_xla(x, dt, a, b, c, seg, h0)
    state = jnp.asarray(h0)[None]
    conv = jnp.zeros((1, 1, *slot_rows.tiled(16)), jnp.bfloat16)
    taps = jnp.zeros((1, *conv.shape[2:]), jnp.bfloat16)
    one = jnp.asarray([True])
    ys = []
    for t in range(128):
        y, state, conv = pallas_ssd.decode_rows_xla(
            jnp.int32(0), jnp.asarray([0]), one, dt[t:t + 1], a, x[t:t + 1],
            b[t:t + 1], c[t:t + 1], taps, state, conv)
        ys.append(y[0])
    np.testing.assert_allclose(np.stack(ys), y_ref, atol=2e-4)
    np.testing.assert_allclose(state[0], h_ref, atol=2e-5)


@pytest.mark.parametrize("stacked", [False, True])
def test_grouped_relu2_kernel_matches_its_xla_twin(stacked):
    rng = np.random.default_rng(7)
    m, k, n, groups = 256, 128, 256, 6
    rows = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    w = jnp.asarray(0.1 * rng.normal(size=(2, groups, k, n)), jnp.bfloat16)
    sizes = jnp.asarray([40, 0, 90, 1, 0, 77], jnp.int32)
    layer = jnp.int32(1) if stacked else None
    w_in = w if stacked else w[1]
    got = pallas_grouped.relu2(rows, w_in, sizes, layer, interpret=True)
    want = pallas_grouped.relu2_xla(rows, w_in, sizes, layer)
    used = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got[:used], np.float32),
                               np.asarray(want[:used], np.float32),
                               rtol=2e-2, atol=1e-3)
    assert (np.asarray(got[:used], np.float32) >= 0).all()
    assert (np.asarray(want[used:], np.float32) == 0).all()


# ----------------------------------------------------------- the layers


def _mamba_block(i=1):
    cfg = lm.Config.from_dict(TINY)
    lp = jax.tree.map(lambda a: a[i], lm.make_params(cfg)["mamba"])
    return cfg, lp, ref.block_weights(TINY, cfg.mamba_ids[i])


def test_a_mamba2_mixer_matches_the_reference_across_chunk_and_decode():
    """A packed chunk of two sequences from given states is not what the
    reference has; ONE sequence is: 35 tokens as a chunk of 29 from zero
    and 6 decode steps from what the chunk left."""
    cfg, lp, w = _mamba_block()
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(35, 64)), jnp.bfloat16)
    want = np.asarray(ref.mamba2(
        TINY, w, ref.rms_norm(x.astype(jnp.float32), w["norm"], 1e-5)))
    seg = jnp.asarray(np.r_[np.zeros(29), -np.ones(3)], jnp.int32)
    shapes = lm.state_shapes(cfg, 4, 4, 1)
    conv0 = jnp.zeros((1, 3 * cfg.conv_width), jnp.bfloat16)
    h0 = jnp.zeros((1, *shapes["ssm"].shape[2:]), jnp.float32)
    y, conv_end, h_end = lm.mamba_prefill(
        cfg, lp, jnp.pad(x[:29], ((0, 3), (0, 0))), seg, conv0, h0)
    np.testing.assert_allclose(np.asarray(y[:29], np.float32), want[:29],
                               atol=0.05)
    ssm = jnp.zeros((1, 3, *h_end.shape[1:]), jnp.float32).at[0, 0].set(
        h_end[0])
    conv = jnp.zeros((1, 3, *shapes["conv"].shape[2:]), jnp.bfloat16)
    conv = conv.at[0, 0].set(conv_end.reshape(conv.shape[2:]))
    for t in range(29, 35):
        y, conv, ssm = lm.mamba_decode(
            cfg, lp, jnp.int32(0), x[t:t + 1], jnp.asarray([0]),
            jnp.asarray([True]), conv, ssm)
        np.testing.assert_allclose(np.asarray(y[0], np.float32), want[t],
                                   atol=0.05)
    assert np.abs(want).mean() > 0.2


@pytest.mark.parametrize("lacks", ["conv_bias", "D", "gate", "group_norm"])
def test_a_mamba2_mixer_differs_from_a_reference_that_lacks(lacks):
    cfg, lp, w = _mamba_block()
    x = jnp.asarray(np.random.default_rng(12).normal(size=(32, 64)),
                    jnp.bfloat16)
    u = ref.rms_norm(x.astype(jnp.float32), w["norm"], 1e-5)
    shapes = lm.state_shapes(cfg, 4, 4, 1)
    y, _, _ = lm.mamba_prefill(
        cfg, lp, x, jnp.zeros((32,), jnp.int32),
        jnp.zeros((1, 3 * cfg.conv_width), jnp.bfloat16),
        jnp.zeros((1, *shapes["ssm"].shape[2:]), jnp.float32))
    y = np.asarray(y, np.float32)
    whole = np.abs(y - np.asarray(ref.mamba2(TINY, w, u))).max()
    without = np.abs(y - np.asarray(
        ref.mamba2(TINY, w, u, omit=frozenset([lacks])))).max()
    assert whole < 0.05 < without / 3, (whole, without)


def _moe_layer(cfg_dict, held=None, i=1):
    cfg = lm.Config.from_dict(cfg_dict)
    lp = lm.make_layers(cfg, cfg.moe_ids, lm.moe_shapes(cfg),
                        held if held is not None else range(
                            cfg.held_lo, cfg.held_lo + cfg.n_held))
    return cfg, lp


def test_the_expert_layer_works_in_the_latent_and_matches_the_reference():
    cfg, lp = _moe_layer(TINY)
    x = jnp.asarray(np.random.default_rng(13).normal(size=(24, 64)),
                    jnp.bfloat16)
    live = jnp.asarray([True] * 20 + [False] * 4)
    y, counts = experts.moe(cfg, lp, x, live, jnp.int32(1))
    w = ref.block_weights(TINY, cfg.moe_ids[1])
    want = np.asarray(ref.moe(TINY, cfg.moe_ids[1], w,
                              x.astype(jnp.float32), range(2)))
    np.testing.assert_allclose(np.asarray(y[:20], np.float32), want[:20],
                               atol=0.08, rtol=0.05)
    rw, ids = ref.route(TINY, np.asarray(jax.nn.sigmoid(
        x.astype(jnp.float32) @ w["router"])), np.asarray(w["router_bias"]))
    assert ids.shape == (24, 6) and rw.sum(-1) == pytest.approx(5.0, rel=1e-4)
    held = int((ids[:20] < 2).sum())
    assert int(counts[0]) == held and 0 < held < 20 * 6
    # a dead row gets the shared expert alone; the latent's tensors exist
    assert set(experts.tensor_shapes(cfg, bias=True)) == {
        "router", "router_bias", "latent_down", "latent_up", "shared_up",
        "shared_down", "expert_up", "expert_down"}
    assert lp["expert_up"].shape == (3, 2, 32, 48)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST: chip ``c`` of eight holds experts ``2c, 2c + 1`` and
    hands on its routed partial sum through ``W_up``; the eight of them,
    with the shared expert counted once, are the reference's layer with
    ALL sixteen experts."""
    x = jnp.asarray(np.random.default_rng(17).normal(size=(16, 64)),
                    jnp.bfloat16)
    live = jnp.ones((16,), bool)
    layer = lm.Config.from_dict(TINY).moe_ids[1]
    w = ref.block_weights(TINY, layer)
    x32 = x.astype(jnp.float32)
    whole = np.asarray(ref.moe(TINY, layer, w, x32, range(16)))
    shared = np.asarray(ref.moe(TINY, layer, w, x32, ()))
    total, assignments = np.zeros_like(whole), 0
    for chip in range(8):
        cfg, lp = _moe_layer({**TINY, "held_lo": 2 * chip})
        y, counts = experts.moe(cfg, lp, x, live, jnp.int32(1))
        total += np.asarray(y, np.float32) - shared
        assignments += int(counts[0])
        part = np.asarray(ref.moe(TINY, layer, w, x32,
                                  range(2 * chip, 2 * chip + 2),
                                  shared=False))
        np.testing.assert_allclose(np.asarray(y, np.float32) - shared, part,
                                   atol=0.08, rtol=0.05)
    assert assignments == 16 * 6    # every routed assignment held once
    np.testing.assert_allclose(total + shared, whole, atol=0.3, rtol=0.05)
    assert np.abs(whole - shared).mean() > 0.05


# ---------------------------------------------------------- the engine


@pytest.mark.parametrize("length", [3, 20, 100])
def test_prefill_then_decode_matches_the_reference(engine, length):
    """Through the engine: packed prefill from the prefix snapshot (100
    tokens cross a chunk boundary: the second chunk continues from the
    slot's own state and convolution inputs, and attends to the first
    chunk's pages), then decode steps in a running batch that move the
    slot state in place, against the reference's full forward pass."""
    prompt = _prompt(length, length)
    out = _generate(engine, prompt)
    problems, stats = _compare(
        out, _ref_logits(engine.prefix, prompt, out), _SCALE)
    assert not problems, (problems, stats)
    assert out["prefix_tokens"] == 16


def test_two_sequences_sharing_a_chunk_do_not_see_each_other(engine):
    lengths = [12, 7, 1, 9]
    prompts = [_prompt(60 + i, n) for i, n in enumerate(lengths)]
    alone = [_generate(engine, p, n=4) for p in prompts]
    _idle(engine)
    chunks, inner = [], engine._prefill

    def spy(params, state, last_ids, heads, mat, aux):
        chunks.append(np.array(mat[1]))
        return inner(params, state, last_ids, heads, mat, aux)

    engine._prefill = spy
    engine._admit = lambda: None  # hold admission until all four wait
    try:
        futs = [engine.submit(stream=f"p{i}", prompt_ids=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        del engine._admit
        packed = [f.result(timeout=300) for f in futs]
    finally:
        engine.__dict__.pop("_admit", None)
        engine._prefill = inner
    assert len(chunks) == 1
    # SEGMENT_ALIGN 1: the segments lie end to end
    assert chunks[0][:sum(lengths)].tolist() == [
        i for i, n in enumerate(lengths) for _ in range(n)]
    for prompt, one, many in zip(prompts, alone, packed):
        np.testing.assert_allclose(many["top_logits"][0],
                                   one["top_logits"][0], atol=1e-4)
        assert many["ids"][0] == one["ids"][0]
    for prompt, many in list(zip(prompts, packed))[1:3]:
        problems, stats = _compare(
            many, _ref_logits(engine.prefix, prompt, many), _SCALE)
        assert not problems, (problems, stats)


def test_rows_that_carry_no_sequence_leave_every_other_slot_as_it_was(engine):
    """Thirty decode steps of which most rows carry nothing: the snapshot
    row, the null row and every slot the one sequence does not hold stay
    bit for bit, in every Mamba-2 layer."""
    _idle(engine)
    before = {k: np.asarray(engine._state[k], np.float32)
              for k in ("ssm", "conv")}
    prompt = _prompt(77, 9)
    out = _generate(engine, prompt, n=30)
    assert np.isfinite(out["top_logits"]).all()
    for k, was in before.items():
        now = np.asarray(engine._state[k], np.float32)
        moved = np.flatnonzero((now != was).any(
            axis=tuple(i for i in range(now.ndim) if i != 1)))
        assert len(moved) == 1 and moved[0] < SIZES.slots, (k, moved)
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


def test_prefix_snapshot_equals_the_prefix_before_the_prompt(engine):
    """The shared prefix as pinned pages and a snapshot row of the slot
    state, against the same tokens run in front of the prompt by an engine
    that shares nothing."""
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
    assert private["ids"][:3] == shared["ids"][:3]
    np.testing.assert_allclose(private["top_logits"][:3],
                               shared["top_logits"][:3], atol=0.15)
    for out in (shared, private):
        problems, stats = _compare(
            out, _ref_logits(engine.prefix, prompt, out), _SCALE)
        assert not problems, (problems, stats)
    _idle(engine)
    assert engine.pages_in_use() == (4, 4 + 4 * 30)
    slots_in_use, slots, state_bytes = engine.state_slots()
    assert (slots_in_use, slots) == (0, 4)
    cfg = engine.cfg
    assert state_bytes == 3 * 6 * (
        4 * cfg.m_heads * cfg.m_dim * cfg.d_state + 2 * 3 * cfg.conv_width)


def test_the_series_of_the_family_go_live_and_routed_counts_every_choice(
        engine):
    _idle(engine)

    def read():
        return {
            "routed": metrics.get_counter("evam_moe_routed_assignments"),
            "held": metrics.get_counter("evam_moe_held_assignments"),
            "hit": metrics.get_counter("evam_moe_held_experts_hit",
                               {"kind": "decode"}),
            "reads": metrics.get_counter("evam_moe_expert_reads", {"kind": "decode"}),
            "tokens": sum(metrics.get_counter("evam_generate_tokens", {"kind": k})
                          for k in ("prefill", "decode")),
            "state_rows": metrics.get_counter("evam_generate_state_rows",
                                      {"kind": "decode"}),
            "restores": metrics.get_counter("evam_generate_prefix_restores"),
            "rows_read": metrics.get_counter("evam_generate_latent_rows_read",
                                     {"kind": "decode"}),
            "blocks": metrics.get_counter("evam_generate_chunk_key_blocks",
                                  {"layers": "attn", "class": "mixed"}),
        }

    before = read()
    _generate(engine, _prompt(5, 9), n=NEW)
    _idle(engine)
    d = {k: v - before[k] for k, v in read().items()}
    assert d["tokens"] == 9 + NEW - 1
    # 6 of 16 a token in each of the 3 expert layers, whoever holds them
    assert d["routed"] == d["tokens"] * 6 * 3
    assert 0 < d["held"] < d["routed"]
    assert 0 < d["hit"] <= (NEW - 1) * 3 * 2 and d["reads"] >= d["hit"]
    assert d["state_rows"] == NEW - 1 and d["restores"] == 1
    assert d["rows_read"] == sum(16 + 9 + k + 1 for k in range(NEW - 1))
    assert d["blocks"] > 0
    assert "evam_moe_routed_assignments_total" in metrics.render()


# ------------------------------------------------------ the comparator


@pytest.fixture(scope="module")
def published(engine):
    """What a message's description holds, for two prompts."""
    out = []
    for i, n in enumerate((6, 17)):
        prompt = _prompt(40 + i, n)
        out.append((prompt, _generate(engine, prompt, n=8)))
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
    "conv_bias", "gate", "group_norm", "renormalize", "shared",
    "control:weights", "control:carry", "control:latent", "control:act",
    "control:topk", "control:rope"])
def test_comparator_fails_when_a_term_or_the_precision_is_taken_away(
        published, engine, omit):
    kw = {"control:weights": {"weight_dtype": jnp.float8_e4m3fn},
          "control:carry": {"carried": False},
          "control:latent": {"latent": False},
          "control:act": {"act": "silu"},
          "control:topk": {"top_k": 2},
          "control:rope": {"rotated": True}}.get(
              omit, {"omit": frozenset([omit])})
    assert _verdict(published, engine, **kw), omit


def test_a_row_of_another_sequence_is_refused(published, engine):
    (p0, o0), (p1, _) = published
    problems, stats = _compare(o0, _ref_logits(engine.prefix, p1, o0),
                               _SCALE)
    assert problems and stats["max"] > nemotron_h_child.LOGIT_TOKEN_TOL


def test_the_child_knows_its_seven_controls():
    assert nemotron_h_child.CONTROLS == (
        "weights", "carry", "state", "latent", "act", "topk", "rope")
    assert (nemotron_h_child.LOGIT_MEDIAN_TOL,
            nemotron_h_child.LOGIT_ABS_TOL) == (0.03, 0.4)
    assert nemotron_h_child.limits_scale(FULL) == 1.0
    assert nemotron_h_child.limits_scale(TINY) == 4.0
    assert nemotron_h_child.READINGS == ("acts",)
    assert nemotron_h_child.CONTROL_TOP_K == 8
    text = (REPO / "benchmark" / "reference"
            / "nemotron_h_plain.py").read_text()
    assert "evam_tpu" not in text and "pallas" not in text
    assert 'default_matmul_precision("highest")' in text


# ------------------------------------------------ configuration files


def test_benchmark_config_holds_the_published_widths_and_the_preset():
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "nemotron3_super_ep8.json").read_text())
    path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    catalog = ([json.loads(line) for line in open(path)]
               if path.is_file() else [])
    entry = next((e for e in catalog if e["name"]
                  == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"), None)
    if entry is not None:
        assert entry["config"] == NEMOTRON3_SUPER_PUBLISHED
        assert cfg["source"] == entry["source_url"]
    for key, value in NEMOTRON3_SUPER_PUBLISHED.items():
        assert cfg[key] == (11 if key == "num_hidden_layers" else value), key
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "weights"]
    assert cfg["published"]["num_hidden_layers"] == 88
    assert set(cfg["departures"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "head_on_the_first_stage", "mtp_not_held", "weights"}
    assert cfg["deployment"]["chips"] == 64
    assert "blocks 0-10" in cfg["deployment"]["this_chip"]
    assert cfg["load_note"].startswith("8x")
    for key, number in (("no_positions", 1), ("router_and_latent", 2),
                        ("mamba2_order", 3), ("seeding", 4),
                        ("attn_qk_init_scale", 5)):
        assert cfg["assumed"][key].startswith(f"({number})"), key
    assert {"topk_eps", "slots", "page_tokens", "chunk_tokens", "state",
            "prefix_tokens", "max_new_tokens", "precision"} <= set(
                cfg["assumed"])
    assert cfg["assumed"]["attn_qk_init_scale"].startswith(
        f"(5) {FULL['attn_qk_init_scale']}")
    model = cfg["shapes"]["model"]
    assert {k: model[k] for k in FULL} == FULL
    assert (model["n_routed_experts"], model["experts_held"],
            model["held_lo"], model["num_experts_per_tok"],
            model["vocab_held"], model["num_hidden_layers"]) == (
                512, 64, 0, 22, 16384, 11)
    assert model["engine_prefix_tokens"] == \
        cfg["shapes"]["engine"]["prefix_tokens"] == 2048
    assert {k: cfg["rehearsal_shapes"]["model"][k] for k in TINY} == TINY
    assert cfg["request"]["parameters"]["max-new-tokens"] == \
        cfg["shapes"]["engine"]["max_new_tokens"] == 64
    assert cfg["opsbytes"] == "nemotron_h"
    assert cfg["reference"]["child"] == "nemotron_h_child"
    assert set(cfg["server_env"]) == {"EVAM_PRELOAD", "EVAM_MAX_BATCH",
                                      "EVAM_NATIVE"}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"]
                if w["name"] == "describe_nemotron_replay")
    assert (cell["name"], cell["config"], cell["chips"], cell["traffic"]) == (
        "describe_nemotron_replay", "nemotron3_super_ep8", 1,
        "replay_1080p_x32")
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["name"] == cell["config"]
    assert entry["reduced"] == cfg["reduced"] and len(entry["why"]) <= 200
    rate = next(m for m in bench["end_to_end"] if m["name"] == "frames_per_s")
    assert rate["workloads"][-2:] == ["describe_nemotron_replay",
                                      "describe_brumby_replay"]
    # the harness admits 128 per-layer metrics and holds 128, so none is
    # entered; a cell has to report one, so the cell's name is appended to
    # the accepted DeepSeek entries whose file is this cell's own file,
    # reader and parameters, and to no other
    assert len(bench["per_layer"]) == 128
    metrics = REPO / "benchmark" / "metrics"
    mine = [m for m in bench["per_layer"]
            if "describe_nemotron_replay" in m["workloads"]]
    assert [m["name"] for m in mine] == [
        "lm_prefill_ms_per_step.replay", "lm_decode_ms_per_step.replay",
        "lm_decode_fill.replay", "lm_prefill_share.replay",
        "lm_queue_wait_ms.replay", "device_idle_share.describe_replay",
        "admit_capacity_fps.describe_replay"]
    for m in mine:
        assert m["workloads"] == ["describe_replay",
                                  "describe_nemotron_replay",
                                  "describe_brumby_replay"]
        assert m["moves"] == "frames_per_s"
        own = metrics / f"{m['name'].split('.')[0]}.nemotron_replay.json"
        assert json.loads(own.read_text()) == json.loads(
            (metrics / f"{m['name']}.json").read_text())
    # the files of the fifteen, for the benchmark PR that makes room
    files = sorted(p.name.split(".")[0] for p in (
        REPO / "benchmark" / "metrics").glob("*.nemotron_replay.json"))
    assert len(files) == 15 and "ssd_roofline" in files

    def params(name):
        return json.loads((REPO / "benchmark" / "metrics"
                           / f"{name}.nemotron_replay.json").read_text())

    assert params("lm_held_experts_hit_share")["params"]["scale"] == \
        pytest.approx(100 / (64 * 5))
    assert params("lm_held_assignments_per_token")["params"]["scale"] == 0.2
    assert params("lm_step_roofline")["reader"] == "lm_roofline_hit"
    roof = params("ssd_roofline")["params"]
    assert (roof["op"], roof["layers"], roof["roofline"]) == (
        "ssd_chunk_scan", 5, True)
    pipe = json.loads((REPO / "pipelines" / "scene_description"
                       / "pvb_nemotron" / "pipeline.json").read_text())
    assert pipe["parameters"]["properties"]["max-new-tokens"]["default"] == 64
    assert [s.get("model") for s in pipe["stages"] if "model" in s] == [
        "scene_description/pvb_nemotron", "scene_description_lm/nemotron_h"]


def test_opsbytes_count_the_state_the_experts_hit_and_the_scan():
    m = dict(FULL, engine_prefix_tokens=2048)
    ctx = 2048 + 272 + 64
    none = dict(prefill_steps=0, prefill_tokens=0, prefill_prompts=0,
                prefill_rows=0, decode_steps=0, decode_tokens=0,
                decode_rows=0, held_assignments=0, sampled_rows=0)
    step = dict(none, decode_steps=1, decode_tokens=128,
                decode_rows=128 * ctx, held_assignments=5 * 128 * 22 // 8,
                sampled_rows=128)
    full = opsbytes.steps(m, **step)
    assert full == opsbytes.ops_and_bytes(
        {"model": m, "engine": {"prefix_tokens": 2048, "max_objects": 32,
                                "max_new_tokens": 64}}, 128)
    # each live row's state read and written: 5.4 GB of a 128-row step,
    # half of its bytes beside 5.4 GB of weights
    row = 4 * 128 * 64 * 128 + 2 * 3 * 10240
    state = 128 * 5 * 2 * row
    assert 5.3e9 < state < 5.5e9
    without = opsbytes.steps(m, **dict(step, decode_tokens=0, decode_rows=0,
                                       sampled_rows=0, held_assignments=0))
    assert full["bytes"] - without["bytes"] > state
    assert 0.45 < state / full["bytes"] < 0.55
    # the experts HIT bound the experts read
    few = opsbytes.steps(m, **step, experts_hit=100)
    assert full["bytes"] - few["bytes"] == 2.0 * (
        5 * 64 - 100) * 2 * 1024 * 2688
    # the scan: the issue's multiply-adds a token and head, at least
    scan = opsbytes.scan_ops_and_bytes(m, 512)
    macs = 2 * 128 * 64 + 128 * 64 // 2 + 128 * 128 // 32
    assert scan["flops"] == 2.0 * 512 * 128 * macs
    assert scan["flops"] <= 2.0 * 512 * 128 * (
        128 * 64 + 2 * 128 * 64 + 128 * 128 // 16)
    assert scan["bytes"] == 512 * (2 * 10240 + 4 * 128 + 2 * 8192)
    chunk = opsbytes.steps(m, **dict(
        none, prefill_steps=1, prefill_tokens=512, prefill_prompts=2,
        prefill_rows=2048, held_assignments=5 * 512 * 22 // 8,
        sampled_rows=2))
    assert chunk["flops"] > 5 * scan["flops"]


# --------------------------------------------------------- the server


def _registry(tmp_path):
    from evam_tpu.engine import EngineHub
    from evam_tpu.models import ModelRegistry
    from evam_tpu.models.fetch import synthesize_lm, synthesize_omz
    from evam_tpu.parallel import build_mesh
    from evam_tpu.server.registry import PipelineRegistry

    models = tmp_path / "models"
    synthesize_omz(models, alias="scene_description",
                   version="pvb_nemotron", input_size=128)
    synthesize_lm(models, "scene_description_lm", "nemotron_h",
                  "nemotron_h_tiny")
    settings = Settings(pipelines_dir=str(REPO / "pipelines"),
                        state_dir=str(tmp_path / "state"))
    hub = EngineHub(
        ModelRegistry(models_dir=models, dtype="float32"), plan=build_mesh(),
        max_batch=4, deadline_ms=4.0,
        lm=LMSettings(slots=4, page_tokens=4, chunk_tokens=64, max_segments=4,
                      private_tokens=288, prefix_tokens=16))
    return PipelineRegistry(settings, hub=hub)


def test_sixth_describe_pipeline_end_to_end_through_rest(eight_devices,
                                                         tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from evam_tpu.server.app import build_app

    reg = _registry(tmp_path)
    out = tmp_path / "out.jsonl"
    path = "/pipelines/scene_description/pvb_nemotron"

    async def go():
        async with TestClient(TestServer(build_app(reg))) as c:
            r = await c.post(path, json={
                "source": {"uri": "synthetic://96x96@30?count=4",
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
    assert len(msgs) == 4
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
    row = engines["generate:scene_description_lm/nemotron_h"]
    assert row["items"] == 4 and row["compiled_programs"] == 5
    assert row["state_slots_in_use"] == 0 and row["state_bytes"] > 0
    assert row["pages_in_use"] == 4 and row["capacity_fps"] > 0


# ------------------------------------ what the shared modules compute


@pytest.mark.parametrize("program,on_chip,want", [
    ("decode", True, "ca49b03d143dc641"),
    ("decode", False, "d409d8a62c4191a3"),
    ("prefill", True, "96e4226a98936d65"),
    ("prefill", False, "163cb3dfc25e1c25")])
def test_the_step_programs_compute_what_they_did(monkeypatch, program,
                                                 on_chip, want):
    """The guard of the modules this family shares with the others
    (tests/_step_trace.py): its two step programs at the deployment's
    sizes, traced for the chip (the Pallas kernels' bodies among the
    operations) and for the host (their twins), digest to what they did
    when PR 58 entered them in the record. A PR that changes an operation
    of THIS family's served path moves the digest, and says so."""
    from _step_trace import check

    check("nemotron3_super_ep8", program, on_chip, monkeypatch, want)
