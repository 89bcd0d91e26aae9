"""The third language-model family: Kimi-Linear (models/lm/kimi_linear.py)
through the generate engine with a matrix state per slot AND latent pages
(engine/generate.py), its delta-rule kernel and the kernel's twin
(ops/pallas_kda.py), the expert layer told a held range under sigmoid
scores (models/lm/experts.py), the third describe pipeline, and the
comparison that decides the Kimi cell's ``correct``
(benchmark/reference/kimi_linear_child.py), all at a tiny size on the CPU
against the plain reference (benchmark/reference/kimi_linear_plain.py): the
same structure as the published model (KDA, KDA, MLA, KDA; the first layer
dense, the others over experts; an untied head)."""

import asyncio
import dataclasses
import json
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.opsbytes import kimi_linear as opsbytes
from benchmark.reference import kimi_linear_child, lm_compare
from benchmark.reference import kimi_linear_plain as ref
from benchmark.reference.compare import check_schema
from evam_tpu.config.settings import LMSettings, Settings
from evam_tpu.engine.generate import GenerateEngine, GenerateSizes
from evam_tpu.models.lm import common, experts, family, mla
from evam_tpu.models.lm import kimi_linear as lm
from evam_tpu.models.lm.presets import KIMI_LINEAR_PUBLISHED, PRESETS
from evam_tpu.ops import pallas_kda as pk

REPO = Path(__file__).resolve().parent.parent
TINY = PRESETS["kimi_linear_tiny"]
FULL = PRESETS["kimi_linear_ep4"]
SIZES = GenerateSizes(slots=8, page_tokens=8, chunk_tokens=128,
                      max_segments=8, private_tokens=160)
NEW = 6
#: the same at six layers: KDA, KDA, MLA, KDA, MLA, KDA, so that the walk
#: over the KDA mixers has trips with no MLA layer, with the first and with
#: the second (as the deployment's: MLA behind its third and sixth mixer)
TWO_MLA = {**TINY, "num_hidden_layers": 6, "linear_attn_config": {
    **TINY["linear_attn_config"], "kda_layers": [1, 2, 4, 6],
    "full_attn_layers": [3, 5]}}


@pytest.fixture(scope="module", autouse=True)
def _yield_the_cores(yield_the_cores):
    """This file's compiles keep to two cores (tests/conftest.py)."""
    yield


def _prefix(n=16):
    return np.random.default_rng(1).integers(1, TINY["vocab_held"], size=n)


def _prompt(seed, n):
    return np.random.default_rng(100 + seed).integers(
        1, TINY["vocab_held"], size=n)


def _engine(prefix, name="generate:kimi", sizes=SIZES, model=TINY):
    eng = GenerateEngine(name, model, prefix, sizes=sizes)
    eng.warm_async()
    assert eng.warmed.wait(300) and eng.warm_error is None
    return eng


@pytest.fixture(scope="module")
def engine():
    eng = _engine(_prefix())
    yield eng
    eng.stop()


_compare = kimi_linear_child.compare_logits


def _generate(eng, prompt, n=NEW, stream="s"):
    return eng.submit(stream=stream, prompt_ids=prompt,
                      max_new_tokens=n).result(timeout=300)


def _ref_logits(prefix, prompt, result, model=TINY, **kw):
    """The reference's logits rows at the generated positions."""
    full = np.concatenate([prefix, prompt, result["ids"]]).astype(np.int64)
    first = len(prefix) + len(prompt) - 1
    return np.asarray(ref.forward(
        model, full, rows=list(range(first, first + len(result["ids"]))),
        **kw))


def _idle(eng, timeout=10):
    deadline = time.time() + timeout
    while ((eng.pages_in_use()[0] != eng._prefix_pages
            or len(eng._free_slots) != eng.sizes.slots)
           and time.time() < deadline):
        time.sleep(0.05)


# ------------------------------------------------------------ the model


def test_the_layer_order_follows_from_the_two_lists():
    cfg = lm.Config.from_dict(FULL)
    assert cfg.kda_ids == (0, 1, 2, 4, 5, 6) and cfg.mla_ids == (3, 7)
    assert cfg.mla_after == (-1, -1, 0, -1, -1, 1)
    assert cfg.moe_ids == tuple(range(1, 8))
    assert (cfg.kda_width, cfg.latent, cfg.n_experts, cfg.n_held,
            cfg.top_k) == (4096, 576, 256, 64, 8)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5)
    tiny = lm.Config.from_dict(TINY)
    assert tiny.kda_ids == (0, 1, 3) and tiny.mla_ids == (2,)
    assert tiny.mla_after == (-1, 0, -1)
    for i in range(8):
        assert ref.is_kda(FULL, i) == (i not in (3, 7))
    assert lm.SEGMENT_ALIGN == pk.BLOCK == 16


def test_a_config_of_another_shape_is_refused():
    la = TINY["linear_attn_config"]
    for key, value in (
            ("q_lora_rank", 24), ("mla_use_nope", False),
            ("num_expert_group", 2), ("tie_word_embeddings", True),
            ("moe_router_activation_func", "softmax"),
            ("first_k_dense_replace", 0),
            # a full-attention layer with no KDA layer before it
            ("linear_attn_config", {**la, "kda_layers": [1, 4],
                                    "full_attn_layers": [2, 3]}),
            # a layer that is in neither list
            ("linear_attn_config", {**la, "kda_layers": [1, 2]})):
        with pytest.raises(ValueError):
            lm.Config.from_dict({**TINY, key: value})
    assert family("kimi_linear") is lm


def test_weights_are_the_same_tensors_in_program_and_reference():
    cfg = lm.Config.from_dict(TINY)
    params = lm.make_params(cfg)
    # KDA layer 4 (index 3) is the third of the stack
    w = ref.layer_weights(TINY, 3)
    for name, shape in lm.kda_shapes(cfg).items():
        got = np.asarray(params["kda"][name][2], np.float32)
        assert got.shape == shape
        np.testing.assert_array_equal(got, np.asarray(w[name]), name)
    w = ref.layer_weights(TINY, 2)
    # the MLA layer's tensors as the loader lays the reference's values
    # (``mla.store``: the up-projections per head, ``q`` with no ``q_a``)
    laid = mla.store(cfg, {name: jnp.asarray(w[name]).astype(jnp.bfloat16)
                           for name in lm.mla_shapes(cfg)})
    assert set(laid) == set(params["mla"][0]) and "q" not in laid
    for name in laid:
        np.testing.assert_array_equal(
            np.asarray(params["mla"][0][name], np.float32),
            np.asarray(laid[name], np.float32), name)
    for name in ("router", "router_bias", "shared_up"):
        np.testing.assert_array_equal(
            np.asarray(params["ffn"][2][name], np.float32),
            np.asarray(w[name]), name)
    assert "mlp_gate" in params["ffn"][0] and "router" in params["ffn"][1]
    # held experts 0-3 of 16, each its own tensor
    np.testing.assert_array_equal(
        np.asarray(params["ffn"][3]["expert_down"][2], np.float32),
        np.asarray(ref.tensor(TINY, 3, "expert_down",
                              (cfg.moe_inter, cfg.hidden), 2)))
    np.testing.assert_array_equal(
        np.asarray(params["head"], np.float32),
        np.asarray(ref.tensor(TINY, ref.GLOBAL_LAYER, "head",
                              (cfg.hidden, cfg.vocab))))
    # the family's own initialisation: steps in [0.001, 0.1], A in [1, 16]
    dt = np.log1p(np.exp(np.asarray(
        ref.tensor(TINY, 0, "dt_bias", (cfg.kda_width,)))))
    assert 0.0009 < dt.min() and dt.max() < 0.11
    a = np.exp(np.asarray(ref.tensor(TINY, 0, "A_log", (cfg.kda_heads,))))
    assert 0.99 <= a.min() and a.max() <= 16.1
    assert np.abs(np.asarray(w["router_bias"])).max() > 0


def test_only_the_latent_attentions_q_and_kv_a_are_seeded_wider():
    wide = {**TINY, "mla_qk_init_scale": 2.5}
    assert FULL["mla_qk_init_scale"] == 2.5
    cfg = lm.Config.from_dict(wide)
    got = lm.make_layer(cfg, 2, lm.mla_shapes(cfg), wider=("q", "kv_a"))
    plain, w = ref.layer_weights(TINY, 2), ref.layer_weights(wide, 2)
    for name in lm.mla_shapes(cfg):
        np.testing.assert_array_equal(
            np.asarray(got[name], np.float32), np.asarray(w[name]), name)
        ratio = float(np.std(w[name]) / np.std(plain[name]))
        assert ratio == pytest.approx(
            2.5 if name in ("q", "kv_a") else 1.0, rel=0.01), name
    # a KDA layer has a ``q`` too: as wide as it was
    np.testing.assert_array_equal(np.asarray(ref.layer_weights(wide, 3)["q"]),
                                  np.asarray(ref.layer_weights(TINY, 3)["q"]))
    np.testing.assert_array_equal(
        np.asarray(lm.make_params(cfg)["kda"]["q"][2], np.float32),
        np.asarray(ref.layer_weights(TINY, 3)["q"]))


def test_parameter_count_matches_the_benchmarks_arithmetic():
    cfg = lm.Config.from_dict(FULL)
    # gains, convolutions, A_log, dt_bias and the selection bias: what
    # opsbytes leaves out
    small = (cfg.hidden + 6 * (2 * cfg.hidden + 3 * 4 * 4096 + 4096 + 32
                               + 128) + 2 * (2 * cfg.hidden + 512) + 7 * 256)
    shapes = {"model": dict(FULL, engine_prefix_tokens=2048)}
    assert lm.param_count(cfg) - small == opsbytes.parameters(
        shapes["model"])
    assert 3.76e9 < lm.param_count(cfg) < 3.78e9
    state = lm.state_shapes(cfg, 401, 128, 128)
    # the 576 values of a latent row stored in whole lane tiles
    assert state["pages"].shape == (2, 401, 128, 640)
    assert state["kda"].shape == (6, 130, 32, 128, 128)
    assert state["kda"].dtype == jnp.float32
    # a slot's 3 * 12288 taps as 16 rows: whole bfloat16 tiles a slot
    assert state["conv"].shape == (6, 130, 16, 3 * 12288 // 16)
    assert state["kda"].shape[-1] % 128 == 0
    assert state["conv"].shape[-1] % 128 == 0
    per_slot = 6 * (4 * 32 * 128 * 128 + 2 * 3 * 12288)
    assert 13.0e6 < per_slot < 13.1e6


# ------------------------------------------------ the expert layer's share


@pytest.mark.parametrize("case", ["bias_moves_the_choice", "no_bias"])
def test_selection_is_by_score_plus_bias_and_weights_by_score(case):
    """Expert 5 has the fifth score but the largest bias: it is chosen,
    and weighted by its score alone."""
    cfg = lm.Config.from_dict(TINY)
    scores = np.full((1, 16), 0.05, np.float32)
    scores[0, [0, 1, 2, 3, 5]] = [0.9, 0.8, 0.7, 0.6, 0.5]
    bias = np.zeros(16, np.float32)
    if case == "bias_moves_the_choice":
        bias[5] = 0.45   # 0.95: first; expert 3 (0.6) falls out of 4
        bias[3] = -0.2
        want = [5, 0, 1, 2]
    else:
        want = [0, 1, 2, 3]
    w_ref, ids_ref = ref.route(TINY, scores, bias)
    assert ids_ref[0].tolist() == want
    chosen = scores[0][want]
    np.testing.assert_allclose(
        w_ref[0], chosen / chosen.sum() * TINY["routed_scaling_factor"],
        rtol=1e-6)
    # the program's router, given logits whose sigmoid is these scores
    logits = np.log(scores / (1 - scores))
    x = jnp.zeros((1, cfg.hidden), jnp.float32).at[0, 0].set(1.0)
    router = jnp.zeros((cfg.hidden, 16), jnp.float32).at[0].set(logits[0])
    w, ids = experts.route(cfg, x, router, jnp.asarray(bias))
    assert np.asarray(ids)[0].tolist() == want
    np.testing.assert_allclose(np.asarray(w)[0], w_ref[0], rtol=1e-5)


def test_held_experts_are_a_range_and_the_hit_count_follows_the_routing():
    cfg = lm.Config.from_dict({**TINY, "held_lo": 8})
    lp = lm.make_layer(cfg, 1, lm.moe_shapes(cfg), range(8, 12))
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (16, cfg.hidden)), lm.BF16)
    # every token to experts 8 and 10 (held) and 0, 1 (another chip's)
    ids = jnp.tile(jnp.asarray([[8, 10, 0, 1]]), (16, 1))
    w = jnp.ones((16, 4), jnp.float32)
    live = jnp.arange(16) < 12
    y, n, hit, reads = experts.held_experts(cfg, lp, x, w, ids, live)
    assert (int(n), int(hit), int(reads)) == (24, 2, 2)
    want = sum(np.asarray(lm.common.swiglu(
        x, lp["expert_gate"][e], lp["expert_up"][e], lp["expert_down"][e]),
        np.float32) for e in (0, 2))
    got = np.asarray(y, np.float32)
    assert np.abs(got[:12] - want[:12]).max() < 0.05 * np.abs(want).max()
    assert not got[12:].any()  # dead rows get nothing


# ---------------------------------------------- the delta rule's two forms


def _rule_inputs(t, heads, d, lengths, seed=0, dead_garbage=False):
    """A packed chunk: segments of ``lengths`` tokens, each started at a
    multiple of 16, rows of no segment between and after."""
    r = np.random.default_rng(seed)
    seg = np.full(t, -1, np.int32)
    lo = 0
    for i, n in enumerate(lengths):
        lo = -(-lo // pk.BLOCK) * pk.BLOCK
        seg[lo:lo + n] = i
        lo += n
    live = (seg >= 0)[:, None, None]

    def f(*shape):
        return r.standard_normal(shape).astype(np.float32)

    q, k, v = f(t, heads, d), f(t, heads, d), f(t, heads, d)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * d ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = 1 / (1 + np.exp(-f(t, heads, 1)))
    g = (-np.exp(r.uniform(0, np.log(16), (1, heads, 1)))
         * np.exp(r.uniform(np.log(1e-3), np.log(0.1), (t, heads, d)))
         ).astype(np.float32)
    if dead_garbage:   # what a dead row's q and k hold is never read
        q = np.where(live, q, 1e3)
        k = np.where(live, k, -1e3)

    def flat(x):
        return jnp.asarray(x.reshape(t, heads * d))

    return (flat(q), flat(k), flat(beta * k * live), flat(beta * v * live),
            flat(g * live), jnp.asarray(seg),
            jnp.asarray(f(len(lengths), heads, d, d) * 0.1))


def test_rule_twin_is_the_plain_recurrence_per_segment():
    """The packed twin against the recurrence written out in numpy, each
    segment alone from its own initial state."""
    heads, d, lengths = 2, 16, [5, 1, 18, 3]
    q, k, kb, vb, g, seg, h0 = (np.asarray(x) for x in _rule_inputs(
        80, heads, d, lengths, seed=3))
    o, h_end = pk.delta_rule_xla(q, k, kb, vb, g, seg, h0)
    o = np.asarray(o).reshape(80, heads, d)
    rs = lambda x: x.reshape(80, heads, d).astype(np.float64)  # noqa: E731
    q, k, kb, vb, g = map(rs, (q, k, kb, vb, g))
    for s in range(len(lengths)):
        state = h0[s].astype(np.float64)
        for t in np.flatnonzero(seg == s):
            for h in range(heads):
                st = np.exp(g[t, h])[:, None] * state[h]
                st = st + np.outer(k[t, h], vb[t, h] - kb[t, h] @ st)
                state[h] = st
                np.testing.assert_allclose(o[t, h], st.T @ q[t, h],
                                           rtol=2e-4, atol=2e-6)
        np.testing.assert_allclose(np.asarray(h_end[s]), state, rtol=2e-4,
                                   atol=2e-6)


@pytest.mark.parametrize("lengths,tokens", [
    ([20, 16, 5, 30], 96),             # tails of 4, 0, 5 and 14 rows
    ([3, 16, 1, 2, 5, 1, 6, 2], 128),  # eight segments, one of 16 tokens
    ([64], 64),                        # one segment over four blocks
])
def test_rule_kernel_matches_its_xla_twin(lengths, tokens):
    args = _rule_inputs(tokens, 2, 128, lengths, seed=len(lengths))
    o0, h0 = pk.delta_rule_xla(*args)
    o1, h1 = pk.delta_rule(*args, interpret=True)
    live = np.asarray(args[5]) >= 0
    np.testing.assert_allclose(np.asarray(o1)[live], np.asarray(o0)[live],
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), rtol=1e-4,
                               atol=1e-6)


@pytest.mark.parametrize("form", ["twin", "kernel"])
def test_dead_rows_and_absent_segments_move_no_state(form):
    """Rows of no segment (between segments and after the last) leave
    every state as it is whatever they hold, and a segment with no token
    here keeps its initial state."""
    run = (pk.delta_rule_xla if form == "twin"
           else lambda *a: pk.delta_rule(*a, interpret=True))
    clean = _rule_inputs(64, 2, 128, [5, 20], seed=9)
    dirty = _rule_inputs(64, 2, 128, [5, 20], seed=9, dead_garbage=True)
    o0, h0 = run(*clean)
    o1, h1 = run(*dirty)
    live = np.asarray(clean[5]) >= 0
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h0))
    np.testing.assert_array_equal(np.asarray(o1)[live], np.asarray(o0)[live])
    # a third segment with no token: its row comes back untouched
    args = list(clean)
    args[6] = jnp.concatenate([args[6], 7.0 + args[6][:1]], axis=0)
    _, h_end = run(*args)
    np.testing.assert_array_equal(np.asarray(h_end[2]),
                                  np.asarray(args[6][2]))
    np.testing.assert_array_equal(np.asarray(h_end[:2]), np.asarray(h0))


def test_a_decode_step_moves_the_named_rows_and_no_other():
    """``kda_decode`` updates the layer's slot state whole: the rows its
    tokens name move as the recurrence says, every other row comes back
    bit for bit."""
    cfg = lm.Config.from_dict(TINY)
    lp = lm.make_layer(cfg, 0, lm.kda_shapes(cfg))
    r = np.random.default_rng(7)
    rows, width = 10, 3 * 3 * cfg.kda_width
    s_all = jnp.asarray(r.standard_normal(
        (rows, cfg.kda_heads, cfg.kda_dim, cfg.kda_dim)), jnp.float32)
    conv_all = jnp.asarray(r.standard_normal((rows, width)), lm.BF16)
    x = jnp.asarray(r.standard_normal((3, cfg.hidden)), lm.BF16)
    x = jnp.concatenate([x, jnp.full((2, cfg.hidden), 1e4, lm.BF16)])
    slot = jnp.asarray([6, 2, 8, 9, 9], jnp.int32)  # two dead rows name 9
    live = jnp.asarray([True, True, True, False, False])
    y, conv_new, s_new = lm.kda_decode(cfg, lp, x, slot, live, conv_all,
                                       s_all)
    assert not np.asarray(y[3:], np.float32).any()
    others = [i for i in range(rows) if i not in (6, 2, 8)]
    np.testing.assert_array_equal(np.asarray(s_new)[others],
                                  np.asarray(s_all)[others])
    np.testing.assert_array_equal(
        np.asarray(conv_new, np.float32)[others],
        np.asarray(conv_all, np.float32)[others])
    # each named row alone gives the same output and the same new row
    for b, at in enumerate((6, 2, 8)):
        y1, c1, s1 = lm.kda_decode(cfg, lp, x[b:b + 1], slot[b:b + 1],
                                   live[b:b + 1], conv_all, s_all)
        np.testing.assert_allclose(np.asarray(y1[0], np.float32),
                                   np.asarray(y[b], np.float32), atol=1e-2)
        np.testing.assert_allclose(np.asarray(s1[at]), np.asarray(s_new[at]),
                                   rtol=1e-5, atol=1e-6)
        assert np.abs(np.asarray(s1[at]) - np.asarray(s_all[at])).max() > 0
        np.testing.assert_array_equal(
            np.asarray(c1[at], np.float32)[-3 * cfg.kda_width:],
            np.asarray(conv_new[at], np.float32)[-3 * cfg.kda_width:])


def test_rows_that_carry_no_sequence_never_reach_the_state(engine):
    """Sixty decode steps of which most rows carry nothing: the null row
    stays what warm-up's loaded chunk left there (a sum of dead rows
    there once overflowed and, as 0 x inf in a one-hot product, made every
    row NaN), and the live sequence's logits stay finite and agree with
    the reference."""
    _idle(engine)
    null = {key: np.asarray(engine._state[key][:, SIZES.slots], np.float32)
            for key in ("kda", "conv")}
    prompt = _prompt(77, 9)
    out = _generate(engine, prompt, n=60)
    assert np.isfinite(out["top_logits"]).all()
    for key, was in null.items():
        np.testing.assert_array_equal(np.asarray(
            engine._state[key][:, SIZES.slots], np.float32), was, key)
    problems, stats = _compare(out, _ref_logits(engine.prefix, prompt, out))
    assert not problems, (problems, stats)


# ------------------------------------- the page cache through the walk


def _walk_case(program):
    """One step of ``program`` at the six-layer size over a state of
    noise: ``(cfg, pages before, pages after, the rows' dest_page,
    dest_off, which of them carry a token)``."""
    cfg = lm.Config.from_dict(TWO_MLA)
    assert cfg.mla_after == (-1, 0, 1, -1)
    params = lm.make_params(cfg)
    slots, i32 = 4, jnp.int32
    r = np.random.default_rng(11)
    state = {k: jnp.asarray(r.standard_normal(v.shape) * 0.1, v.dtype)
             for k, v in lm.state_shapes(cfg, 12, 8, slots).items()}
    prefix_pages = jnp.asarray([1, 2], i32)
    if program == "decode":
        live = np.array([True, True, False])
        page, off = np.array([5, 7, 0]), np.array([2, 3, 0])
        out = lm.decode_tokens(
            cfg, params, state, jnp.asarray([3, 9, 0], i32),
            jnp.asarray([18, 27, 0], i32),
            jnp.asarray([[5, 6], [6, 7], [0, 0]], i32),
            jnp.asarray([3, 12, 0], i32), jnp.asarray(page, i32),
            jnp.asarray(off, i32), jnp.asarray(live), prefix_pages, 16,
            jnp.asarray([1, 3, slots], i32))
    else:
        # two segments of 32 and 16 tokens and a tail of no segment
        seg = np.repeat([0, 1, -1], [32, 16, 16])
        live = seg >= 0
        at = np.where(live, np.arange(64), 0)
        page, off = np.where(live, 5 + at // 8, 0), at % 8
        out = lm.prefill_chunk(
            cfg, params, state, jnp.asarray(r.integers(1, 128, 64), i32),
            jnp.asarray(seg, i32), jnp.asarray(16 + at, i32),
            jnp.asarray(page, i32), jnp.asarray(off, i32), prefix_pages, 16,
            jnp.zeros((2,), i32), 0, jnp.asarray([31, 47], i32),
            jnp.full((2,), slots + 1, i32), jnp.asarray([0, 2], i32))
    assert np.isfinite(np.asarray(out[1])).all()
    return (cfg, np.asarray(state["pages"], np.float32),
            np.asarray(out[0]["pages"], np.float32), page, off, live)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_walk_writes_the_named_rows_and_the_null_page_alone(program):
    """The loop body writes the cache, not the branches that hold the
    layers (``_layers``): the MLA layer's new rows where the trip over
    the KDA mixers has one, nothing where it has none (the zeros such a
    trip's branch returns go nowhere). After a decode step and after a
    chunk every row of both MLA layers other than those ``dest_page`` /
    ``dest_off`` name and page 0, where rows of no sequence write, is
    what it was, bit for bit; the named rows hold ``[c_kv | k_r | zeros]``
    in BOTH layers, each layer its own."""
    cfg, before, after, page, off, live = _walk_case(program)
    assert before.shape[0] == 2
    named = np.zeros(before.shape[:3], bool)
    named[:, 0] = True
    named[:, page, off] = True
    np.testing.assert_array_equal(after[~named], before[~named])
    rows = after[:, page[live], off[live]]
    assert (rows != before[:, page[live], off[live]]).any(axis=-1).all()
    assert np.abs(rows[..., :cfg.latent]).sum(axis=-1).all()
    assert not rows[..., cfg.latent:].any()
    assert (rows[0] != rows[1]).any(axis=-1).all()


@pytest.fixture(scope="module")
def two_mla_engine():
    # one slot: one decode program to compile
    eng = _engine(_prefix(), name="generate:kimi_two_mla", model=TWO_MLA,
                  sizes=GenerateSizes(slots=1, page_tokens=8, chunk_tokens=64,
                                      max_segments=4, private_tokens=96))
    yield eng
    eng.stop()


@pytest.mark.parametrize("length", [5, 70])
def test_two_latent_layers_through_the_walk_match_the_reference(
        two_mla_engine, length):
    """Six layers, two of them MLA, through the engine against the
    reference's full forward pass: tokens and logits of a prompt in one
    chunk and of one that crosses a chunk boundary (the second chunk
    attends to the first's pages of both layers), then decode steps
    whose rows read their own new latent rows through the cache; the
    slot state carries what the later tokens' logits rest on."""
    eng = two_mla_engine
    prompt = _prompt(200 + length, length)
    before = np.asarray(eng._state["pages"], np.float32)
    out = _generate(eng, prompt)
    problems, stats = _compare(
        out, _ref_logits(eng.prefix, prompt, out, model=TWO_MLA))
    assert not problems, (problems, stats)
    _idle(eng)
    # the prefix's pinned pages, which every row attends to, are as they
    # were in both layers; the sequence's rows went to pages of its own
    after = np.asarray(eng._state["pages"], np.float32)
    pinned = slice(1, 1 + eng._prefix_pages)
    np.testing.assert_array_equal(after[:, pinned], before[:, pinned])
    moved = (after != before).any(axis=(0, 2, 3))
    moved[0] = False
    assert 1 <= moved.sum() <= -(-(length + NEW) // 8)


# ---------------------------------------------------------- the engine


@pytest.mark.parametrize("length", [3, 20, 150])
def test_prefill_then_decode_matches_the_reference(engine, length):
    """Through the engine, slot state AND latent pages: packed prefill
    from the prefix snapshot over the pinned prefix pages (150 tokens
    cross a chunk boundary: the second chunk starts from the slot's own
    state and attends to the first chunk's pages), then decode steps in a
    running batch, against the reference's full forward pass."""
    prompt = _prompt(length, length)
    out = _generate(engine, prompt)
    problems, stats = _compare(
        out, _ref_logits(engine.prefix, prompt, out))
    assert not problems, (problems, stats)
    assert out["prefix_tokens"] == 16


def test_stored_rows_end_in_zeros_and_the_logits_are_the_references(engine):
    """A prefill chunk and three decode steps through the engine: the MLA
    layer's cache holds ``[c_kv | k_r | zeros]`` (every written row's
    columns past the model's ``latent`` values exactly zero), and the
    wider rows and queries change no logit."""
    cfg = engine.cfg
    prompt = _prompt(31, 11)
    out = _generate(engine, prompt, n=4)
    problems, stats = _compare(out, _ref_logits(engine.prefix, prompt, out))
    assert not problems, (problems, stats)
    pages = np.asarray(engine._state["pages"].astype(jnp.float32))
    assert pages.shape[-1] == common.row_width(cfg.latent) > cfg.latent
    written = np.abs(pages[..., :cfg.latent]).sum(axis=-1) > 0
    # the prefix's 16 rows and the generation's 11 + 3, in every MLA layer
    assert (written.sum(axis=(1, 2)) >= 16 + 14).all()
    assert not pages[..., cfg.latent:].any()


def test_compiled_programs_constant_after_warmup(engine):
    before = engine.stats.compiled_programs
    assert before == 1 + len(SIZES.slot_buckets)
    futs = [engine.submit(stream=f"c{i}", prompt_ids=_prompt(i, 5 + 4 * i),
                          max_new_tokens=NEW) for i in range(10)]
    for f in futs:
        assert len(f.result(timeout=300)["ids"]) == NEW
    assert engine.stats.compiled_programs == before


def test_eight_segments_in_one_chunk_do_not_see_each_other(engine):
    """Eight prompts packed into one chunk, each started at a multiple of
    16 tokens (one of them fills its block exactly), the rows between
    rows of no segment."""
    lengths = [4, 16, 7, 1, 12, 3, 9, 5]
    prompts = [_prompt(60 + i, n) for i, n in enumerate(lengths)]
    alone = [_generate(engine, p, n=3) for p in prompts]
    _idle(engine)
    chunks, inner = [], engine._prefill

    def spy(params, state, last_ids, heads, mat, aux):
        chunks.append(np.array(mat[1]))
        return inner(params, state, last_ids, heads, mat, aux)

    engine._prefill = spy
    engine._admit = lambda: None  # hold admission until all eight wait
    try:
        futs = [engine.submit(stream=f"p{i}", prompt_ids=p, max_new_tokens=3)
                for i, p in enumerate(prompts)]
        del engine._admit
        packed = [f.result(timeout=300) for f in futs]
    finally:
        engine.__dict__.pop("_admit", None)
        engine._prefill = inner
    assert len(chunks) == 1
    seg = chunks[0]
    for i, n in enumerate(lengths):
        at = np.flatnonzero(seg == i)
        assert len(at) == n and at[0] == 16 * i and at[-1] == 16 * i + n - 1
    assert (seg >= 0).sum() == sum(lengths)
    for prompt, one, many in zip(prompts, alone, packed):
        # the prefill is the same program either way, so a segment alone
        # and among seven others samples from the same logits
        np.testing.assert_allclose(many["top_logits"][0],
                                   one["top_logits"][0], atol=1e-5)
        assert many["ids"][0] == one["ids"][0]
        problems, stats = _compare(
            many, _ref_logits(engine.prefix, prompt, many))
        assert not problems, (problems, stats)


def test_prefix_as_pages_and_snapshot_equals_the_prefix_before_the_prompt(
        engine):
    """The shared prefix as pinned latent pages (the MLA layer) AND a
    snapshot row (the KDA layers) against the same tokens run in front of
    the prompt by an engine that shares nothing (a prompt whose greedy
    choices are no near ties: the two engines pad to different rows)."""
    prompt = _prompt(10, 10)
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
                               atol=0.05)
    _idle(engine)
    assert engine.pages_in_use() == (2, 2 + 8 * 20)


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
    snap = np.asarray(engine._state["kda"][:, SIZES.slots + 1])
    _generate(engine, _prompt(33, 7))
    np.testing.assert_array_equal(
        snap, np.asarray(engine._state["kda"][:, SIZES.slots + 1]))
    assert np.abs(snap).max() > 0


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
    """Both kinds of sequence state count at once: slot-state rows and
    prefix restores, latent rows and shared rows, held assignments and the
    held experts hit."""
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
            "hit_prefill": c("evam_moe_held_experts_hit",
                             {"kind": "prefill"}),
            "reads_decode": c("evam_moe_expert_reads", {"kind": "decode"}),
            "reads_prefill": c("evam_moe_expert_reads",
                               {"kind": "prefill"}),
            "read_prefill": c("evam_generate_latent_rows_read",
                              {"kind": "prefill"})}

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
    # three expert layers, four of sixteen experts held, four a token
    assert 0 < grew["held"] <= 3 * 4 * (150 + NEW - 1)
    assert 0 < grew["hit_decode"] <= 3 * 4 * (NEW - 1)
    assert 0 < grew["hit_prefill"] <= 3 * 4 * 2
    # a decode step's 16 rows are one row tile: a hit expert is read once
    # a product; a chunk's 512 are four, and a group may span two
    assert grew["reads_decode"] == grew["hit_decode"]
    assert grew["hit_prefill"] <= grew["reads_prefill"] <= 3 * (4 + 3) * 2
    cfg = engine.cfg
    per_row = len(cfg.kda_ids) * (
        4 * cfg.kda_heads * cfg.kda_dim ** 2 + 2 * 3 * 3 * cfg.kda_width)
    assert engine.state_slots() == (0, 8, 10 * per_row)
    row = EngineHub._stat_row(engine, None, None, engine.name)
    assert (row["state_slots"], row["state_slots_in_use"],
            row["state_bytes"]) == (8, 0, 10 * per_row)
    assert (row["pages"], row["pages_in_use"]) == (2 + 8 * 20, 2)
    # the prefix's heads of the ONE MLA layer, held beside the weights:
    # W_kvb of its cached rows, [heads, 16, 16] keys and as many values
    (k, v), = engine._prefix_heads
    made = mla.expand(cfg, engine._params["mla"][0], common.layer_page_rows(
        engine._state["pages"], 0, np.asarray(engine._shared, np.int32)))
    assert k.shape == v.shape == (cfg.heads, 16, 16)
    assert (np.asarray(k) == np.asarray(made[0])).all() and np.asarray(k).any()
    assert (np.asarray(v) == np.asarray(made[1])).all()
    assert row["prefix_heads_bytes"] == 2 * cfg.heads * 16 * 16 * 2
    # two chunks of one latent layer read the prefix's 16 rows, then the
    # prefix's and the 128 continued: the counter means what it meant
    assert grew["read_prefill"] == 16 + (16 + 128)
    text = metrics.render()
    for series in ('evam_moe_held_experts_hit_total{kind="decode"}',
                   'evam_moe_expert_reads_total{kind="decode"}',
                   'evam_generate_state_rows_total{kind="decode"}',
                   "evam_generate_prefix_restores_total",
                   "evam_generate_state_bytes",
                   "evam_generate_decode_shared_rows_total",
                   "evam_moe_held_assignments_total"):
        assert series in text, series


@pytest.mark.parametrize("preset", ["deepseek_v2_tiny", "jamba_tiny"])
def test_the_other_families_pack_as_before(preset):
    """Their packer block is 1: a second segment starts at the token
    after the first, and a chunk counts the tokens it carries."""
    mod = family(PRESETS[preset]["model_type"])
    assert mod.SEGMENT_ALIGN == 1
    eng = GenerateEngine("generate:packing", PRESETS[preset], _prefix(),
                         sizes=dataclasses.replace(SIZES, chunk_tokens=64))
    try:
        assert eng._align == 1
        seen = []
        eng._dispatch_prefill_raw = lambda *a: seen.append(a)
        for i, n in enumerate((5, 3)):
            seq = type("S", (), {})()
            seq.prompt, seq.n_prefilled, seq.slot = _prompt(i, n), 0, i
            seq.pages, seq.t_first, seq.t_submit = [3 + i], 0.0, 0.0
            seq.max_new, seq.n_gen = 4, 0
            eng._prefilling.append(seq)
        eng._dispatch_prefill()
        tokens, seg = seen[0][0], seen[0][1]
        assert seg == [0] * 5 + [1] * 3 and len(tokens) == 8
    finally:
        eng._prefilling.clear()
        eng._decoding.clear()
        eng.stop()


def test_a_chunk_that_is_not_whole_blocks_is_refused():
    with pytest.raises(ValueError, match="whole blocks"):
        GenerateEngine("generate:odd", TINY, _prefix(),
                       sizes=dataclasses.replace(SIZES, chunk_tokens=72))


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
        p, _ = _compare(out, _ref_logits(engine.prefix, prompt, out, **kw))
        problems += p
    return problems


def test_comparator_passes_the_whole_model(published, engine):
    assert not _verdict(published, engine)


@pytest.mark.parametrize("omit", [
    "conv", "decay", "beta", "delta", "out_gate", "k_rope", "router_bias",
    "renormalize", "shared", "expert:1", "float8_weights", "rotate"])
def test_comparator_fails_when_a_term_or_the_precision_is_taken_away(
        published, engine, omit):
    kw = {"float8_weights": {"weight_dtype": jnp.float8_e4m3fn},
          "rotate": {"rotate": True}}.get(omit, {"omit": frozenset([omit])})
    assert _verdict(published, engine, **kw), omit


def test_a_row_of_another_sequence_is_refused(published, engine):
    (p0, o0), (p1, _), _ = published
    problems, stats = _compare(o0, _ref_logits(engine.prefix, p1, o0))
    assert problems and stats["max"] > kimi_linear_child.LOGIT_TOKEN_TOL


def test_flipped_tokens_are_counted_and_nothing_is_excused():
    """A token over the token limit is a fault of its own only above the
    largest limit; FLIP_SHARE bounds how many there may be."""
    n = 10
    desc = {"top_logits": [[1.0] * 8] * n, "top_ids": [list(range(8))] * n}
    logits = np.zeros((n, 16))
    logits[:, :8] = 1.0
    two = logits.copy()
    two[:2, 0] += 2 * kimi_linear_child.LOGIT_TOKEN_TOL    # 20 %: the limit
    three = two.copy()
    three[2, 0] += 2 * kimi_linear_child.LOGIT_TOKEN_TOL
    ok, stats = kimi_linear_child.compare_logits(desc, two)
    assert not ok and stats["flipped"] == 2
    bad, stats = kimi_linear_child.compare_logits(desc, three)
    assert stats["flipped"] == 3 and len(bad) == 1 and "3 of 10" in bad[0]
    one = logits.copy()
    one[0, 0] += 1.01 * kimi_linear_child.LOGIT_ABS_TOL
    bad, _ = kimi_linear_child.compare_logits(desc, one)
    assert len(bad) == 1 and "a logit differs" in bad[0]


@pytest.mark.parametrize("seeded,scale", [(0.02, 1.0), (0.01, 1.0),
                                          (0.08, 2.0)])
def test_limits_widen_only_for_a_model_seeded_wider(seeded, scale):
    assert kimi_linear_child.limits_scale(
        {"initializer_range": seeded}) == pytest.approx(scale)
    assert kimi_linear_child.limits_scale(FULL) == 1.0


# ------------------------------------------------ configuration files


def test_benchmark_config_holds_the_published_widths_and_the_preset():
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "kimi_linear_ep4.json").read_text())
    path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    catalog = ([json.loads(line) for line in open(path)]
               if path.is_file() else [])
    entry = next((e for e in catalog
                  if e["name"] == "Kimi-Linear-48B-A3B-Instruct"), None)
    if entry is not None:
        assert entry["config"] == KIMI_LINEAR_PUBLISHED
        assert cfg["source"] == entry["source_url"]
    reduced = {"num_hidden_layers": 8, "num_experts": 64,
               "vocab_size": 40960}
    for key, value in KIMI_LINEAR_PUBLISHED.items():
        assert cfg[key] == reduced.get(key, value), key
    assert cfg["reduced"] == [*reduced, "weights"]
    for key in reduced:
        assert cfg["published"][key] == KIMI_LINEAR_PUBLISHED[key]
    assert {"kda_gate_rank", "conv_bias", "kda_init", "router_bias",
            "rope_dim_kept", "unused_keys", "mla_qk_init_scale", "slots",
            "page_tokens",
            "chunk_tokens", "segment_align"} <= set(cfg["assumed"])
    model = cfg["shapes"]["model"]
    assert {k: model[k] for k in FULL} == FULL
    assert (model["num_experts"], model["experts_held"], model["held_lo"],
            model["num_experts_per_token"]) == (256, 64, 0, 8)
    assert model["engine_prefix_tokens"] == \
        cfg["shapes"]["engine"]["prefix_tokens"] == 2048
    assert {k: cfg["rehearsal_shapes"]["model"][k] for k in TINY} == TINY
    assert cfg["request"]["parameters"]["max-new-tokens"] == \
        cfg["shapes"]["engine"]["max_new_tokens"] == 64
    assert cfg["opsbytes"] == "kimi_linear"
    assert cfg["reference"]["child"] == "kimi_linear_child"
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"]
                if w["name"] == "describe_kimi_replay")
    assert (cell["config"], cell["chips"]) == ("kimi_linear_ep4", 1)
    assert cell["traffic"] == "replay_1080p_x32"
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == cfg["reduced"]
    rate = next(m for m in bench["end_to_end"] if m["name"] == "frames_per_s")
    assert "describe_kimi_replay" in rate["workloads"]
    mine = [m for m in bench["per_layer"]
            if "describe_kimi_replay" in m.get("workloads", [])]
    assert len(mine) == 22   # PR 34's 21 and lm_expert_reads_per_hit
    assert mine[-1]["name"] == "lm_expert_reads_per_hit.kimi_replay"
    for m in mine:
        assert m["workloads"] == ["describe_kimi_replay"]
        assert m["moves"] == "frames_per_s"
        assert m["name"].endswith(".kimi_replay")
        assert (REPO / "benchmark" / "metrics"
                / f"{m['name']}.json").is_file()
    pipe = json.loads((REPO / "pipelines" / "scene_description"
                       / "pvb_kimi_linear" / "pipeline.json").read_text())
    assert pipe["parameters"]["properties"]["max-new-tokens"]["default"] == 64


def test_opsbytes_count_state_experts_and_the_prefix_once_a_step():
    m = dict(FULL, engine_prefix_tokens=2048)
    ctx = 2048 + 272 + 64
    one = opsbytes.steps(m, prefill_steps=0, prefill_tokens=0,
                         prefill_prompts=0, prefill_rows=0, decode_steps=1,
                         decode_tokens=64, decode_rows=64 * ctx,
                         held_assignments=7 * 128, sampled_rows=64)
    expert = 3 * 2304 * 1024
    weights = 2.0 * (opsbytes.parameters(m) - 7 * 64 * expert
                     - 40960 * 2304)  # the embedding is read a row a token
    state = 2 * 64 * 6 * (4 * 32 * 128 * 128 + 2 * 3 * 12288)
    # the prefix once, each row's own 336 rows, the 64 new rows
    rows = 2.0 * 2 * 576 * (2048 + 64 * (272 + 64) + 64)
    assert one["bytes"] == pytest.approx(
        weights + 2.0 * 7 * 64 * expert + state + rows + 2.0 * 64 * 2304)
    assert 1.6e9 < state < 1.7e9
    few = opsbytes.steps(m, prefill_steps=0, prefill_tokens=0,
                         prefill_prompts=0, prefill_rows=0, decode_steps=1,
                         decode_tokens=16, decode_rows=16 * ctx,
                         held_assignments=7 * 32, sampled_rows=16)
    # 32 assignments a layer can have reached 32 of the 64 held experts
    assert one["bytes"] - few["bytes"] > 2.0 * 7 * 32 * expert
    scan = opsbytes.scan_ops_and_bytes(m, 512)
    assert scan["flops"] == 7.0 * 512 * 32 * 128 * 128
    assert scan["bytes"] == 512 * (4096 * 12 + 128)
    sizing = opsbytes.ops_and_bytes(
        {"model": m, "engine": {"prefix_tokens": 2048, "max_objects": 32,
                                "max_new_tokens": 64}}, 64)
    assert sizing["bytes"] == pytest.approx(one["bytes"])


def test_kda_metrics_read_the_one_kernel_name():
    from benchmark.readers import trace_op_share

    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "kimi_linear_ep4.json").read_text())
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
                         / f"{m}.kimi_replay.json").read_text())["params"]
             for m in ("kda_busy_share", "kda_roofline")]
    assert [(f["op"], f["names"]) for f in files] == [
        ("kda_delta_rule", 1)] * 2 and files[1]["layers"] == 6
    ops = [["while.30 s32[]", 1.5], ["kda_delta_rule.12 f32[512,4096]", 0.2]]
    assert trace_op_share.read(ctx(ops), files[0]) == pytest.approx(10.0)
    least = 6 * max(7.0 * 4096 * 32 * 128 * 128 / 197e12,
                    4096 * (4096 * 12 + 128) / 819e9) * (10 / 20)
    got = trace_op_share.read(ctx(ops), files[1])
    assert got == pytest.approx(100.0 * least / 0.2) and 0 < got < 100
    # the decode step's kernel beside it is another name: still ONE
    both = ops + [["kda_decode_rows.7 f32[64,32,128]", 0.4]]
    assert trace_op_share.read(ctx(both), files[0]) == pytest.approx(10.0)
    assert trace_op_share.read(ctx(both), files[1]) == pytest.approx(got)
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
                   version="pvb_kimi_linear", input_size=128)
    synthesize_lm(models, "scene_description_lm", "kimi_linear",
                  "kimi_linear_tiny")
    settings = Settings(pipelines_dir=str(REPO / "pipelines"),
                        state_dir=str(tmp_path / "state"))
    hub = EngineHub(
        ModelRegistry(models_dir=models, dtype="float32"), plan=build_mesh(),
        max_batch=4, deadline_ms=4.0,
        lm=LMSettings(slots=4, page_tokens=8, chunk_tokens=64, max_segments=4,
                      private_tokens=288, prefix_tokens=16))
    return PipelineRegistry(settings, hub=hub)


def test_third_describe_pipeline_end_to_end_through_rest(eight_devices,
                                                         tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from evam_tpu.server.app import build_app

    reg = _registry(tmp_path)
    out = tmp_path / "out.jsonl"
    path = "/pipelines/scene_description/pvb_kimi_linear"

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
    problems, stats = _compare(desc, np.asarray(logits))
    assert not problems, (problems, stats)
    row = engines["generate:scene_description_lm/kimi_linear"]
    assert row["items"] == 6 and row["compiled_programs"] == 5
    assert (row["state_slots"], row["state_slots_in_use"]) == (4, 0)
    assert row["pages_in_use"] == 2 and row["capacity_fps"] > 0
    assert row["state_bytes"] > 0


# ------------------------------------ what the shared modules compute


@pytest.mark.parametrize("program,on_chip,want", [
    ("decode", True, "c1a749fbd429b090"),
    ("decode", False, "2600070ff9f97575"),
    ("prefill", True, "a0c17e83f8d98ff4"),
    ("prefill", False, "d27caf8b8626abd6")])
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

    check("kimi_linear_ep4", program, on_chip, monkeypatch, want)
