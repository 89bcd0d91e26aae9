"""The language-model path: DeepSeek-V2 (models/lm), the generate engine
and its page cache (engine/generate.py, engine/pages.py), the describe
stage and pipeline, and the comparison that decides the describe cell's
``correct`` (benchmark/reference/lm_compare.py), all at a tiny size on the
CPU against the plain reference (benchmark/reference/deepseek_v2_plain.py):
the same structure as the published model (low-rank query and key-value
projections, a rope part, 4 groups of experts, top-2 groups, shared
experts, a leading dense layer, YaRN on)."""

import asyncio
import dataclasses
import json
import math
import os
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.opsbytes import deepseek_v2 as opsbytes
from benchmark.reference import deepseek_v2_plain as ref
from benchmark.reference import lm_compare
from benchmark.reference.compare import check_schema
from evam_tpu.config.settings import LMSettings, Settings
from evam_tpu.engine.generate import (
    MAX_PREFILL_RUN,
    PART_CHUNK_PATIENCE,
    GenerateEngine,
    GenerateSizes,
    PrefillPace,
    next_step_kind,
)
from evam_tpu.engine.pages import PagePool
from evam_tpu.models.lm import common, family, mla
from evam_tpu.models.lm import deepseek_v2 as lm
from evam_tpu.models.lm.presets import DEEPSEEK_V2_PUBLISHED, PRESETS
from evam_tpu.ops import pallas_attention

REPO = Path(__file__).resolve().parent.parent
TINY = PRESETS["deepseek_v2_tiny"]
SIZES = GenerateSizes(slots=8, page_tokens=8, chunk_tokens=32, max_segments=4,
                      private_tokens=48)
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


def _engine(prefix, name="generate:test", sizes=SIZES):
    eng = GenerateEngine(name, TINY, prefix, sizes=sizes)
    eng.warm_async()
    assert eng.warmed.wait(300) and eng.warm_error is None
    return eng


@pytest.fixture(scope="module")
def engine():
    eng = _engine(_prefix())
    yield eng
    eng.stop()


def _generate(eng, prompt, n=NEW, stream="s"):
    return eng.submit(stream=stream, prompt_ids=prompt,
                      max_new_tokens=n).result(timeout=300)


def _idle(eng, timeout=10):
    """Wait until every sequence has left the engine."""
    deadline = time.time() + timeout
    while ((eng.pages_in_use()[0] != eng._prefix_pages
            or len(eng._free_slots) != eng.sizes.slots)
           and time.time() < deadline):
        time.sleep(0.05)


def _ref_logits(prefix, prompt, result, **kw):
    """The reference's logits rows at the generated positions (with
    ``margins=True`` also its routing margins there)."""
    full = np.concatenate([prefix, prompt, result["ids"]]).astype(np.int64)
    first = len(prefix) + len(prompt) - 1
    out = ref.forward(
        TINY, full, rows=list(range(first, first + len(result["ids"]))),
        **kw)
    return (np.asarray(out[0]), out[1]) if kw.get("margins") else np.asarray(
        out)


# ------------------------------------------------------------ the model


def _laid(cfg, **made):
    """A latent mixer's tensors as the loader lays them (``mla.store``),
    from arrays ``made`` in their PUBLISHED names and shapes
    (``mla.tensor_shapes``); zeros for those a test has no use for."""
    shapes = mla.tensor_shapes(cfg)
    full = {name: jnp.zeros(shapes[name], jnp.bfloat16)
            for name in mla.LAID if name in shapes}
    return mla.store(cfg, {**full, **{
        k: jnp.asarray(v).astype(jnp.bfloat16) for k, v in made.items()}})


def test_yarn_frequencies_against_closed_form():
    cfg = lm.Config.from_dict(DEEPSEEK_V2_PUBLISHED | {
        "vocab_held": 8, "held_group": 0, "weights_seed": 0,
        "initializer_range": 0.02})
    got = lm.yarn_inv_freq(cfg)
    base, dim, factor, orig = 10000.0, 64, 40.0, 4096

    def corr(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    for i in range(dim // 2):
        plain = base ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = plain / factor * ramp + plain * (1 - ramp)
        assert got[i] == pytest.approx(want, rel=1e-6)
    # fast dimensions keep their frequency, slow ones are interpolated
    assert got[0] == pytest.approx(1.0)
    assert got[31] == pytest.approx(base ** (-62 / 64) / factor, rel=1e-6)
    np.testing.assert_allclose(got, ref.yarn_inv_freq(DEEPSEEK_V2_PUBLISHED),
                               rtol=1e-6)


def test_yarn_softmax_scale_against_closed_form():
    cfg = lm.Config.from_dict(PRESETS["deepseek_v2_ep8"])
    m = 0.1 * 0.707 * math.log(40) + 1
    assert lm.yarn_mscale(40, 0.707) == pytest.approx(m)
    assert lm.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert lm.yarn_mscale(1.0, 0.707) == 1.0


def _scores(rows):
    s = np.asarray(rows, np.float32)
    return s / s.sum(-1, keepdims=True)


ROUTING_CASES = {
    # 16 experts, 4 groups of 4, keep 2 groups, 3 experts
    "plain": ([0, 0, 9, 1, 5, 6, 0, 0, 0, 0, 7, 0, 8, 0, 0, 0],
              [2, 12, 3]),  # groups 0 (9) and 3 (8): 9, 8, 1
    "group_tie_goes_low": ([2, 5, 0, 0, 0, 0, 5, 0, 0, 4, 0, 0, 5, 1, 0, 0],
                           [1, 6, 0]),  # groups 0, 1 (5 each), not 3
    "expert_tie_goes_low": ([3, 3, 3, 3, 0, 0, 0, 0, 1, 0, 0, 0, 0, 0, 0, 2],
                            [0, 1, 2]),  # group 0 and 3; three of the 3s
    "best_expert_outside_kept_groups_is_lost": (
        [9, 9, 0, 0, 8, 8, 0, 0, 7.9, 7.9, 7.9, 7.9, 0, 0, 0, 0],
        [0, 1, 4]),  # group 2's four 7.9s lose to groups 0 and 1
}


@pytest.mark.parametrize("case", sorted(ROUTING_CASES))
def test_group_limited_routing_on_crafted_scores(case):
    raw, want = ROUTING_CASES[case]
    scores = _scores([raw])
    w_ref, ids_ref = ref.route(TINY, scores)
    assert ids_ref[0].tolist() == want
    np.testing.assert_allclose(w_ref[0], scores[0][want])
    # the program's router, given logits whose softmax is these scores
    cfg = lm.Config.from_dict(TINY)
    logits = np.log(np.maximum(scores, 1e-30))
    x = jnp.zeros((1, cfg.hidden), jnp.float32).at[0, 0].set(1.0)
    router = jnp.zeros((cfg.hidden, 16), jnp.float32).at[0].set(logits[0])
    w, ids = lm.route(cfg, x, router)
    assert np.asarray(ids)[0].tolist() == want
    np.testing.assert_allclose(
        np.asarray(w)[0], scores[0][want] * TINY["routed_scaling_factor"],
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("model", ["deepseek_v2", "kimi_linear", "lfm2_moe",
                                   "laguna"])
def test_the_shares_add_up_to_the_uncut_layer(model):
    """All the shares' routed sums, the shared experts counted once, are
    the uncut reference layer (every routed expert + the shared), for
    every expert family through the one expert layer
    (models/lm/experts.py): DeepSeek-V2's four routing groups,
    Kimi-Linear's four ranges of four, LFM2-MoE's two halves of eight
    (NO shared expert to count once; the layer one of a stack), Laguna's
    two halves of eight (its configuration holds ALL of them; the halves
    show that a held range still means what it says with one shared
    expert, the factor 2.5 and the layer one of a stack)."""
    from benchmark.reference import (kimi_linear_plain, laguna_plain,
                                     lfm2_moe_plain)
    from evam_tpu.models.lm import kimi_linear, laguna, lfm2_moe

    layer, stacked = 1, None
    if model == "deepseek_v2":
        tiny, plain = TINY, ref
        cfg = lm.Config.from_dict(tiny)
        shares = [(share := lm.Config.from_dict({**tiny, "held_group": g}),
                   lm.make_layer(share, layer)) for g in range(cfg.n_group)]
    elif model == "kimi_linear":
        tiny, plain = PRESETS["kimi_linear_tiny"], kimi_linear_plain
        cfg = kimi_linear.Config.from_dict(tiny)
        shares = [(share := kimi_linear.Config.from_dict(
            {**tiny, "held_lo": lo}), kimi_linear.make_layer(
                share, layer, kimi_linear.moe_shapes(share),
                range(lo, lo + 4))) for lo in range(0, 16, 4)]
    elif model == "laguna":
        tiny, plain = PRESETS["laguna_tiny"], laguna_plain
        cfg = laguna.Config.from_dict(tiny)
        layer, stacked = 2, 1   # model layers 1 and 2 in a stack of two
        shares = [(share := laguna.Config.from_dict(
            {**tiny, "held_lo": lo, "experts_held": 4}), laguna.make_layers(
                share, (1, 2), laguna.moe_shapes(share),
                range(lo, lo + 4))) for lo in range(0, 8, 4)]
    else:
        tiny, plain = PRESETS["lfm2_moe_tiny"], lfm2_moe_plain
        cfg = lfm2_moe.Config.from_dict(tiny)
        layer, stacked = 3, 1   # model layers 2 and 3 in a stack of two
        shares = [(share := lfm2_moe.Config.from_dict(
            {**tiny, "held_lo": lo}), lfm2_moe.make_layers(
                share, (2, 3), lfm2_moe.moe_shapes(share),
                range(lo, lo + 4))) for lo in range(0, 8, 4)]
    x = (np.random.default_rng(3).standard_normal((24, cfg.hidden))
         .astype(np.float32))
    xb = jnp.asarray(x, lm.BF16)
    w = plain.layer_weights(tiny, layer)
    x32 = jnp.asarray(xb, jnp.float32)
    whole = np.asarray(plain.moe(tiny, layer, w, x32, range(cfg.n_experts)))
    shared = np.asarray(plain.moe(tiny, layer, w, x32, []))
    assert bool(shared.any()) == bool(cfg.n_shared)
    total = np.zeros_like(whole)
    held = 0
    live = jnp.ones((24,), bool)
    for share, lp in shares:
        y, n = lm.moe(share, lp, xb, live, stacked)
        total += np.asarray(y, np.float32)
        held += int(n[0])
    total -= (len(shares) - 1) * shared
    # every assignment went to exactly one share
    assert len(shares) == cfg.n_experts // 4 and held == 24 * cfg.top_k
    assert np.abs(total - whole).max() < 0.05 * np.abs(whole).max()
    assert np.median(np.abs(total - whole)) < 0.01 * np.abs(whole).max()


def test_no_assignment_is_dropped_when_all_route_here():
    """Every token to the held group (three times the even share): none
    is dropped."""
    cfg = lm.Config.from_dict(TINY)
    lp = lm.make_layer(cfg, 1)
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (16, cfg.hidden)), lm.BF16)
    ids = jnp.tile(jnp.asarray([[0, 1, 2]]), (16, 1))
    w = jnp.ones((16, 3), jnp.float32)
    y, n, hit, reads = lm.held_experts(cfg, lp, x, w, ids,
                                       jnp.ones((16,), bool))
    # 48 rows are one row tile: each hit expert's matrix is read once
    assert int(n) == 48 and int(hit) == 3 and int(reads) == 3
    want = sum(np.asarray(lm.swiglu(x, lp["expert_gate"][e],
                                    lp["expert_up"][e],
                                    lp["expert_down"][e]), np.float32)
               for e in range(3))
    assert np.abs(np.asarray(y, np.float32) - want).max() < 0.05 * np.abs(
        want).max()


def test_weights_are_the_same_tensors_in_program_and_reference():
    cfg = lm.Config.from_dict(TINY)
    lp = lm.make_layer(cfg, 2)
    w = ref.layer_weights(TINY, 2)
    for name in ("kv_a_norm", "router", "shared_down"):
        np.testing.assert_array_equal(np.asarray(lp[name], np.float32),
                                      np.asarray(w[name]))
    # the latent mixer's up-projections: the reference's values, laid
    laid = _laid(cfg, **{name: w[name] for name in ("q_b", "kv_b", "o")})
    for name in ("w_qn", "w_qr", "w_uk", "w_uv", "o"):
        np.testing.assert_array_equal(np.asarray(lp[name], np.float32),
                                      np.asarray(laid[name], np.float32))
    e = cfg.held_lo + 3
    np.testing.assert_array_equal(
        np.asarray(lp["expert_up"][3], np.float32),
        np.asarray(ref.tensor(TINY, 2, "expert_up",
                              (cfg.hidden, cfg.moe_inter), e)))


@pytest.mark.parametrize("preset", ["deepseek_v2_ep8", "kimi_linear_ep4",
                                    "deepseek_v2_tiny", "kimi_linear_tiny"])
def test_the_stored_up_projections_are_the_published_ones_turned_and_split(
        preset):
    """``mla.store``: what the step programs hold of ``kv_b``, of the
    query's projection and of ``o`` is the tensor made under its published
    name, key and shape, per head with the contraction last and split into
    its two parts, value for value; the published arrays are not kept."""
    cfg = family(PRESETS[preset]["model_type"]).Config.from_dict(
        PRESETS[preset])
    shapes = mla.tensor_shapes(cfg)
    made = {name: common.make_one(common.tensor_key(cfg.seed, 1, name),
                                  shapes[name], cfg.init_range, False)
            for name in mla.LAID if name in shapes}
    gain = jnp.ones((cfg.kv_rank,), jnp.bfloat16)
    lp = mla.store(cfg, {**made, "kv_a_norm": gain})
    assert set(lp) == {"w_qn", "w_qr", "w_uk", "w_uv", "o", "kv_a_norm"}
    assert lp["kv_a_norm"] is gain
    assert sum(a.size for a in lp.values()) - gain.size == sum(
        a.size for a in made.values())
    hd, nope = cfg.heads, cfg.nope
    kv = np.asarray(made["kv_b"]).reshape(cfg.kv_rank, hd, nope + cfg.v_dim)
    assert lp["w_uk"].shape == (hd, nope, cfg.kv_rank)
    assert lp["w_uv"].shape == (hd, cfg.v_dim, cfg.kv_rank)
    assert (np.asarray(lp["w_uk"]) == kv[..., :nope].transpose(1, 2, 0)).all()
    assert (np.asarray(lp["w_uv"]) == kv[..., nope:].transpose(1, 2, 0)).all()
    q = np.asarray(made["q_b" if cfg.q_rank else "q"])
    q = q.reshape(q.shape[0], hd, nope + cfg.rope)
    assert (np.asarray(lp["w_qn"]) == q[..., :nope].transpose(1, 2, 0)).all()
    # the rope part: the smaller of its two leading axes outermost
    turned = (2, 1, 0) if cfg.rope < hd else (1, 2, 0)
    assert (np.asarray(lp["w_qr"]) == q[..., nope:].transpose(turned)).all()
    assert lp["w_qr"].shape == {
        "deepseek_v2_ep8": (64, 128, 1536), "kimi_linear_ep4": (32, 64, 2304),
    }.get(preset, lp["w_qr"].shape)
    assert (np.asarray(lp["o"]) == np.asarray(made["o"]).reshape(
        hd, cfg.v_dim, cfg.hidden)).all()


def test_parameter_count_matches_the_benchmarks_arithmetic():
    full = PRESETS["deepseek_v2_ep8"]
    cfg = lm.Config.from_dict(full)
    gains = cfg.hidden + cfg.layers * (2 * cfg.hidden + cfg.q_rank
                                       + cfg.kv_rank)
    assert lm.param_count(cfg) - gains == opsbytes.parameters(full)
    assert 3.80e9 < lm.param_count(cfg) < 3.83e9


# ----------------------------------------------------------- page pool


def test_page_pool_hands_out_frees_and_pins():
    pool = PagePool(8, 4)
    assert pool.capacity == 7 and pool.in_use == 0
    shared = pool.pin(2)
    mine = pool.alloc(3)
    assert 0 not in shared + mine and len(set(shared + mine)) == 5
    assert pool.alloc(3) is None and pool.in_use == 5
    pool.free(mine)
    assert pool.in_use == 2
    with pytest.raises(ValueError):
        pool.free(shared[:1])
    assert pool.pages_for(9) == 3


# ---------------------------------------------------------- the engine


@pytest.mark.parametrize("waiting,decoding,run,passed_over,want", [
    (0, False, 0, False, None),          # nothing to do
    (0, True, 0, False, "decode"),
    (5, False, MAX_PREFILL_RUN, False, "prefill"),  # nothing decodes
    (31, True, 0, False, "decode"),      # a lone prompt waits one step,
    (31, True, 0, True, "prefill"),      # and only one
    (32, True, 0, False, "prefill"),     # a full chunk runs at once,
    (640, True, MAX_PREFILL_RUN - 1, False, "prefill"),
    (640, True, MAX_PREFILL_RUN, True, "decode"),   # but decoding is held
])                                       # up for MAX_PREFILL_RUN chunks
def test_next_step_kind(waiting, decoding, run, passed_over, want):
    assert next_step_kind(waiting, decoding, run, passed_over, 32) == want


@pytest.mark.parametrize("waiting,passed_over,credit,owed,want", [
    (640, 0, 31.0, 9.0, "decode"),       # a full chunk waits for its credit
    (640, 0, 32.0, 9.0, "prefill"),
    (16, 1, 0.0, 2.0, "decode"),         # a part-full one for its fill,
    (16, 1, 0.0, 1.0, "prefill"),        # if that is on its way,
    (16, PART_CHUNK_PATIENCE, 0.0, 2.0, "prefill"),  # and not for ever
    (16, 0, 64.0, 0.0, "decode"),        # nor before a decode step passed
])
def test_next_step_kind_paces_prefill(waiting, passed_over, credit, owed,
                                      want):
    assert next_step_kind(waiting, True, 0, passed_over, 32, credit,
                          owed) == want


def _closed_loop(n_seqs, n_steps, prompt=272, new=64, chunk=512,
                 segments=8):
    """``n_seqs`` equal generations submitted TOGETHER, each submitted
    again when it ends, through the engine's own pace and packing rules:
    per step its kind, a chunk's tokens and the generations a decode step
    ended."""
    pace = PrefillPace(chunk)
    waiting = [0] * n_seqs               # tokens prefilled of each prompt
    decoding: list[int] = []             # tokens generated of each row
    steps = []
    for _ in range(n_steps):
        owed = (len(waiting) + len(decoding)) * prompt / (new - 1)
        kind = pace.next(sum(prompt - w for w in waiting), bool(decoding),
                         owed)
        tokens = ended = 0
        if kind == "prefill":
            for seg in range(segments):
                if not waiting or tokens == chunk or (waiting[0] and seg):
                    break                # a continued prompt opens a chunk
                take = min(prompt - waiting[0], chunk - tokens)
                tokens += take
                waiting[0] += take
                if waiting[0] == prompt:
                    waiting.pop(0)
                    decoding.append(1)
        else:
            decoding = [g + 1 for g in decoding]
            ended = sum(g == new for g in decoding)
            decoding = [g for g in decoding if g < new]
        pace.ran(kind, tokens, bool(waiting),
                 (len(waiting) + len(decoding)) * prompt / (new - 1))
        waiting += [0] * ended           # submitted again at once
        steps.append((kind, tokens, ended))
    return steps


def test_generations_submitted_together_do_not_stay_together():
    """The describe cells' closed loop: 64 equal generations that start at
    one moment. After a few rounds the engine ends about one a decode
    step, never a burst, every chunk runs full and a chunk follows every
    other decode step: what it completes a second does not depend on the
    phases the streams started in (PERF.md section 6, PR 42)."""
    steps = _closed_loop(64, 3000)
    late = steps[1500:]
    chunks = [t for k, t, _ in late if k == "prefill"]
    ends = [e for k, _, e in late if k == "decode"]
    assert set(chunks) == {512}
    assert 0.5 < len(chunks) / len(ends) < 0.56      # 272 / 512 a frame
    assert max(sum(ends[i:i + 8]) for i in range(len(ends) - 8)) <= 12
    assert min(sum(ends[i:i + 8]) for i in range(len(ends) - 8)) >= 4
    runs = "".join(k[0] for k, _, _ in late)
    assert "pp" not in runs and "ddddd" not in runs


@pytest.mark.parametrize("n_seqs", [1, 2, 4])
def test_a_few_generations_are_not_held_back(n_seqs):
    """With few sequences in the engine a chunk cannot fill in time, and
    a prompt waits ONE decode step, as before the pace."""
    steps = _closed_loop(n_seqs, 600)
    for i, (k, _, ended) in enumerate(steps[:-3]):
        if k == "decode" and ended:
            assert "prefill" in (steps[i + 1][0], steps[i + 2][0])


#: (prefix pages or None, n_prefix, own rows of each row of the bucket;
#: a dead row holds the null page and a context of 1, as the engine
#: pads). Pages hold 8 rows.
DECODE_CASES = {
    "no_prefix": (None, 0, [5, 9, 17]),
    "prefix_and_an_own_length_of_1": ([1, 2], 16, [1, 1, 1]),
    "own_rows_crossing_a_page": ([1, 2], 16, [8, 9, 16, 17]),
    "a_bucket_with_dead_rows": ([1, 2], 16, [11, None, 3, None]),
    "n_prefix_0_with_prefix_pages_given": ([1, 2], 0, [4, 12]),
    "a_prefix_begun_only": ([1, 2], 5, [7, 24]),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_two_part_decode_attention_is_the_one_softmax(case):
    """``mla_decode`` (shared prefix rows in one pass for all rows, own
    rows through the page table, merged by softmax sums) against ONE
    softmax over prefix and own rows, per row, written out in float32
    with materialised heads' arithmetic left absorbed."""
    prefix_pages, n_prefix, own = DECODE_CASES[case]
    cfg = lm.Config.from_dict(TINY)
    made = ref.layer_weights(TINY, 1)
    lp = _laid(cfg, kv_b=made["kv_b"], o=made["o"])
    page, own_pages = 8, 3
    rng = np.random.default_rng(sorted(DECODE_CASES).index(case))
    b = len(own)

    def bf16(a):
        return jnp.asarray(a, lm.F32).astype(lm.BF16)

    def f32(a):
        return np.asarray(a.astype(lm.F32), np.float64)

    def rows_of(ids):
        return f32(cache)[ids].reshape(-1, width)[:, :cfg.latent]

    # rows as the cache holds them: the latent values, then zeros
    width = common.row_width(cfg.latent)
    cache = bf16(np.pad(
        rng.normal(size=(3 + b * own_pages, page, cfg.latent)),
        ((0, 0), (0, 0), (0, width - cfg.latent))))
    q_nope = bf16(rng.normal(size=(b, cfg.heads, cfg.nope)))
    q_rope = bf16(rng.normal(size=(b, cfg.heads, cfg.rope)))
    table = np.zeros((b, own_pages), np.int32)
    ctx_len = np.ones(b, np.int32)
    for i, n in enumerate(own):
        if n is not None:
            table[i] = 3 + i * own_pages + np.arange(own_pages)
            ctx_len[i] = n
    # as decode_tokens hands them over
    pages = None if prefix_pages is None else np.asarray(prefix_pages)
    got = lm.mla_decode(
        cfg, lp, q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
        common.layer_page_rows(cache[None], 0, jnp.asarray(table)),
        jnp.asarray(ctx_len),
        common.layer_page_rows(cache[None], 0, pages), n_prefix)
    assert got.shape == (b, cfg.hidden)

    w = np.asarray(made["kv_b"], np.float64).reshape(
        cfg.kv_rank, cfg.heads, cfg.nope + cfg.v_dim)
    w_uk, w_uv = w[..., :cfg.nope], w[..., cfg.nope:]
    w_o = np.asarray(made["o"], np.float64)
    want, own_only = [], []
    for i in range(b):
        q_lat = np.einsum("hd,chd->hc", f32(q_nope)[i], w_uk)
        q = np.concatenate([q_lat, f32(q_rope)[i]], axis=1)

        def attend(keys):
            s = q @ keys.T * lm.softmax_scale(cfg)
            pr = np.exp(s - s.max(axis=1, keepdims=True))
            pr /= pr.sum(axis=1, keepdims=True)
            o = np.einsum("hc,chv->hv", pr @ keys[:, :cfg.kv_rank], w_uv)
            return o.reshape(-1) @ w_o

        mine = rows_of(table[i])[:ctx_len[i]]
        seen = (rows_of(pages)[:n_prefix] if pages is not None
                else mine[:0])
        want.append(attend(np.concatenate([seen, mine])))
        own_only.append(attend(mine))
    want, own_only = np.asarray(want), np.asarray(own_only)
    # bfloat16 roundings of the folded query, the softmax weights, the
    # latent output and the result: 0.003 to 0.006 seen on values to 2
    np.testing.assert_allclose(f32(got), want, atol=0.025, rtol=0)
    if n_prefix:
        # the prefix part is no rounding: without it 0.75 to 2.8 off
        assert np.abs(own_only - want).max() > 0.25


@pytest.mark.parametrize("length", [3, 20, 40])
def test_prefill_then_decode_matches_the_reference(engine, length):
    """Through the engine and the page cache: packed prefill (40 tokens
    continue into a second chunk), then decode steps in a running batch,
    against the reference's full forward pass without a cache."""
    prompt = _prompt(length, length)
    out = _generate(engine, prompt)
    problems, stats = lm_compare.compare_logits(
        out, *_ref_logits(engine.prefix, prompt, out, margins=True))
    assert not problems, (problems, stats)
    assert stats["flipped"] == 0 and stats["max"] < 0.2
    assert out["prefix_tokens"] == 16


@pytest.mark.parametrize("preset,width", [
    ("deepseek_v2_ep8", 640), ("jamba2_3b", 256), ("kimi_linear_ep4", 640),
    ("lfm2_moe_ep2", 1024)])
def test_page_cache_rows_are_whole_lane_tiles(preset, width):
    """Every family's page cache at its deployment's widths: a row the
    compiler keeps rows-minor on the chip (``common.row_width``; the two
    latent families' 576 values are stored 640 wide)."""
    fam = family(PRESETS[preset]["model_type"])
    cfg = fam.Config.from_dict(PRESETS[preset])
    pages = fam.state_shapes(cfg, 401, 128, 128)["pages"]
    assert pages.shape[1:3] == (401, 128)
    assert pages.shape[-1] == width == common.row_width(width)
    if hasattr(cfg, "latent"):
        assert cfg.latent == 576 and width == common.row_width(cfg.latent)


def test_stored_rows_end_in_zeros_and_the_logits_are_the_references(engine):
    """A prefill chunk and three decode steps through the engine: the
    cache holds ``[c_kv | k_r | zeros]`` (every written row's columns
    past the model's ``latent`` values exactly zero), and the wider rows
    and queries change no logit."""
    cfg = engine.cfg
    prompt = _prompt(31, 11)
    out = _generate(engine, prompt, n=4)
    problems, stats = lm_compare.compare_logits(
        out, *_ref_logits(engine.prefix, prompt, out, margins=True))
    assert not problems, (problems, stats)
    assert stats["flipped"] == 0 and stats["max"] < 0.2
    pages = np.asarray(engine._state["pages"].astype(jnp.float32))
    assert pages.shape[-1] == common.row_width(cfg.latent) > cfg.latent
    written = np.abs(pages[..., :cfg.latent]).sum(axis=-1) > 0
    # the prefix's 16 rows and the generation's 11 + 3, in every layer
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
    assert set(engine.stats.bucket_batches) <= set(SIZES.slot_buckets)


def test_the_freeze_recorder_watches_the_generate_engine(engine):
    """A stand-still of the engine's thread (its queue a second old while
    the heartbeat wakes on time) leaves a ``stall`` dump with every
    thread's stack, as a batch engine's does: the recorder reads the
    engines that ``trace.watch_engine`` was handed."""
    from evam_tpu.obs import trace

    assert engine in trace._watched
    assert engine.queue_age_s() == 0.0
    assert set(engine.thread_states()) == {"generate"}


def test_joining_and_leaving_leave_the_others_logits_unchanged(engine):
    prompt = _prompt(7, 12)
    alone = _generate(engine, prompt, n=10)
    # the same request again, while others join (short prompts) and
    # leave (short generations) around it
    first = engine.submit(stream="a", prompt_ids=prompt, max_new_tokens=10)
    others = [engine.submit(stream="b", prompt_ids=_prompt(20 + i, 4 + i),
                            max_new_tokens=2 + i % 3) for i in range(6)]
    crowded = first.result(timeout=300)
    for f in others:
        f.result(timeout=300)
    assert crowded["ids"] == alone["ids"]
    # other rows of a step change no row's arithmetic (the grouped product
    # sorts by expert, so a row's neighbours differ; its sums do not). The
    # two runs pad to different buckets, though, which are different
    # programs: a bfloat16 rounding of the hidden state apart (0.024 seen
    # on logits of 2 to 5; which buckets serve turns on timing)
    np.testing.assert_allclose(crowded["top_logits"], alone["top_logits"],
                               atol=0.05)
    assert crowded["top_ids"][0] == alone["top_ids"][0]


def test_shared_prefix_pages_equal_a_private_copy_and_stay(engine):
    prompt = _prompt(9, 10)
    shared = _generate(engine, prompt)
    pinned = engine._pool.pinned
    assert len(pinned) == 2
    # the same tokens with the instruction as part of a private prompt
    # (one slot: one decode program to compile, not eight)
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
    # after every sequence left, exactly the prefix's pages are held
    deadline = time.time() + 10
    while engine.pages_in_use()[0] != 2 and time.time() < deadline:
        time.sleep(0.05)
    assert engine.pages_in_use() == (2, 2 + 8 * 6)
    assert engine._pool.pinned == pinned


def test_cancel_frees_slots_and_pages(engine):
    futs = [engine.submit(stream="doomed", prompt_ids=_prompt(i, 8),
                          max_new_tokens=40) for i in range(12)]
    keep = engine.submit(stream="kept", prompt_ids=_prompt(3, 8),
                         max_new_tokens=4)
    engine.cancel_stream("doomed")
    assert all(f.result(timeout=60) is None for f in futs)
    assert len(keep.result(timeout=300)["ids"]) == 4
    deadline = time.time() + 10
    while engine.pages_in_use()[0] != 2 and time.time() < deadline:
        time.sleep(0.05)
    assert engine.pages_in_use()[0] == 2
    assert len(engine._free_slots) == SIZES.slots
    assert engine.queue_depth() == 0


def test_a_request_that_cannot_fit_is_refused(engine):
    with pytest.raises(ValueError):
        engine.submit(prompt_ids=_prompt(0, 44), max_new_tokens=NEW)
    with pytest.raises(ValueError):
        engine.submit(prompt_ids=[TINY["vocab_held"]], max_new_tokens=1)


def test_capacity_model_and_counters(engine):
    from evam_tpu.obs import metrics

    tables, inner = [], engine._decode

    def spy(params, cache, last_ids, mat, page_table):
        tables.append((np.array(mat), np.array(page_table)))
        return inner(params, cache, last_ids, mat, page_table)

    def counted():
        return {
            "shared": metrics.get_counter(
                "evam_generate_decode_shared_rows"),
            "rows": metrics.get_counter(
                "evam_generate_latent_rows_read", {"kind": "decode"}),
            "tokens": metrics.get_counter(
                "evam_generate_tokens", {"kind": "decode"})}

    before = counted()
    engine._decode = spy
    try:
        _generate(engine, _prompt(5, 9))
    finally:
        engine._decode = inner
    # the last step is harvested one step late: wait for its counts
    deadline = time.time() + 10
    while (counted()["tokens"] - before["tokens"] < NEW - 1
           and time.time() < deadline):
        time.sleep(0.05)
    grew = {k: v - before[k] for k, v in counted().items()}
    # a decode row's table: the sequence's OWN pages and no pinned one;
    # its context length counts own rows, its position all of them
    assert len(tables) == NEW - 1
    for step, (mat, table) in enumerate(tables):
        assert table.shape == (mat.shape[1], engine._private_pages) == (
            SIZES.slot_buckets[0], 6)
        assert not set(table.ravel()) & set(engine._pool.pinned)
        assert mat[5].sum() == 1 and mat[5, 0] == 1
        assert mat[2, 0] == 9 + step + 1 and mat[1, 0] == 16 + 9 + step
        assert (table[0, :2] > 0).all() and (table[1:] == 0).all()
    # the one pass over the prefix served prefix rows x decode tokens;
    # the rows read are what they were: every row's whole context
    assert grew["tokens"] == NEW - 1
    assert grew["shared"] == 16 * (NEW - 1)
    assert grew["rows"] == sum(16 + 9 + step + 1 for step in range(NEW - 1))
    # every program has a LOADED step's time from warm-up on
    assert set(engine._program_s) == {"prefill"} | {
        f"decode:{b}" for b in SIZES.slot_buckets}
    assert engine.capacity_fps() > 0
    text = metrics.render()
    for series in ('evam_generate_steps_total{kind="decode"}',
                   'evam_generate_tokens_total{kind="prefill"}',
                   'evam_generate_latent_rows_read_total{kind="decode"}',
                   "evam_generate_decode_shared_rows_total",
                   "evam_moe_held_assignments_total",
                   "evam_generate_queue_wait_seconds_count",
                   "evam_generate_slots_active",
                   "evam_generate_pages_in_use"):
        assert series in text, series


def test_a_dispatch_onto_a_dry_device_is_counted(engine):
    """``evam_generate_dry_dispatches_total{kind}``: a step dispatched
    while the loop still holds the step before in flight, which has
    already ended. With every decode step waited for where it is issued,
    each decode dispatch behind another finds the device dry; however the
    steps fall, no more dispatches are dry than steps are counted."""
    import jax

    from evam_tpu.obs import metrics

    def counted():
        return {name: sum(metrics.get_counter(f"evam_generate_{name}",
                                              {"kind": kind})
                          for kind in ("prefill", "decode"))
                for name in ("dry_dispatches", "steps")}

    text = metrics.render()
    for kind in ("prefill", "decode"):  # there from the loop's start, at 0
        assert f'evam_generate_dry_dispatches_total{{kind="{kind}"}}' in text
    inner = engine._decode

    def waited_for(*args):
        out = inner(*args)
        jax.block_until_ready(out)
        return out

    _idle(engine)
    before = counted()
    engine._decode = waited_for
    try:
        _generate(engine, _prompt(6, 9))
    finally:
        engine._decode = inner
    _idle(engine)
    deadline = time.time() + 10
    while (counted()["steps"] - before["steps"] < NEW
           and time.time() < deadline):
        time.sleep(0.05)
    grew = {k: v - before[k] for k, v in counted().items()}
    # one chunk and NEW - 1 decode steps; every decode step but the first
    # was dispatched behind a decode step that had been waited for
    assert grew["steps"] == NEW
    assert NEW - 2 <= grew["dry_dispatches"] <= grew["steps"]
    futs = [engine.submit(stream=f"d{i}", prompt_ids=_prompt(i, 5 + 4 * i),
                          max_new_tokens=NEW) for i in range(6)]
    for f in futs:
        f.result(timeout=300)
    _idle(engine)
    total = counted()
    assert total["dry_dispatches"] <= total["steps"]


# ------------------------------------------------------ the comparator


@pytest.fixture(scope="module")
def published(engine):
    """What a message's description holds, for three prompts."""
    out = []
    for i, n in enumerate((6, 17, 25)):
        prompt = _prompt(40 + i, n)
        out.append((prompt, _generate(engine, prompt, n=12)))
    return out


#: the tiny model's busiest held expert (group 0 holds experts 0-3)
BUSY_HELD_EXPERT = 2


@pytest.fixture
def tiny_limits(monkeypatch):
    """``lm_compare``'s limits are the published size's. At 64 hidden
    values rounding moves a logit twice as far (median 0.03-0.05 against
    0.016-0.025; 0.22 at most where no routing decision was near, against
    0.10), so the tiny model is held to twice LOGIT_TOKEN_TOL."""
    monkeypatch.setattr(lm_compare, "LOGIT_TOKEN_TOL",
                        2 * lm_compare.LOGIT_TOKEN_TOL)


def _verdict(published, engine, **kw):
    problems = []
    for prompt, out in published:
        p, _ = lm_compare.compare_logits(
            out, *_ref_logits(engine.prefix, prompt, out, margins=True, **kw))
        problems += p
    return problems


def test_comparator_passes_the_whole_model(published, engine, tiny_limits):
    assert _verdict(published, engine) == []


def test_a_difference_is_excused_only_next_to_a_routing_decision():
    """One token of 12 differs by 0.5: a flip where the reference saw a
    decision within ROUTE_MARGIN, a fault where it saw none."""
    rng = np.random.default_rng(3)
    logits = rng.normal(0.0, 1.4, (12, 64))
    ids = np.argsort(-logits, axis=1)[:, :8]
    top = np.take_along_axis(logits, ids, axis=1)
    top[5] += 0.5
    desc = {"top_ids": ids.tolist(), "top_logits": top.tolist()}
    near = np.full(12, 0.5)
    near[5] = lm_compare.ROUTE_MARGIN / 2
    problems, stats = lm_compare.compare_logits(desc, logits, near)
    assert not problems and stats["flipped"] == 1
    problems, _ = lm_compare.compare_logits(desc, logits, np.full(12, 0.5))
    assert len(problems) == 1 and "no routing decision" in problems[0]
    # a row of another sequence is no flip, however near a decision was
    top[5] = logits[6, ids[5]] + 5.0
    desc["top_logits"] = top.tolist()
    problems, _ = lm_compare.compare_logits(desc, logits, near)
    assert any("limit 4.0" in p for p in problems)


@pytest.mark.parametrize("case", ["kept_by_a_hair", "cut_by_a_hair",
                                  "expert_in_by_a_hair", "other_group",
                                  "far"])
def test_route_margin_is_the_nearest_decision_that_moves_a_held_expert(case):
    """4 groups of 4, the 2 best groups kept, 3 experts a token, group 0
    held (the tiny preset's routing)."""
    cfg = dict(TINY, held_group=0)
    s = 1e-3 * 0.5 ** np.arange(16.0)[None]  # no two scores near
    if case == "kept_by_a_hair":      # groups 1, 0 kept; group 2 just cut
        s[0, [4, 0, 8]] = 0.30, 0.20, 0.20 * math.exp(-0.01)
        want = 0.01
    elif case == "cut_by_a_hair":     # groups 1, 2 kept; group 0 just cut
        s[0, [4, 8, 0]] = 0.30, 0.20, 0.20 * math.exp(-0.02)
        want = 0.02
    elif case == "expert_in_by_a_hair":  # held expert 1 is third, just
        s[0, [0, 4, 1, 5, 8]] = 0.30, 0.25, 0.10, 0.10 * math.exp(-0.03), 0.02
        want = 0.03
    elif case == "other_group":       # groups 1, 2 kept, 3 just cut: the
        s[0, [4, 8, 12, 0]] = 0.30, 0.20, 0.20 * math.exp(-0.01), 0.05
        want = math.log(0.20 / 0.05)  # held group is far from both
    else:
        s[0, [0, 1, 2, 4, 8]] = 0.30, 0.20, 0.15, 0.10, 0.02
        want = math.log(0.15 / 0.10)  # third held expert against the fourth
    assert ref.route_margin(cfg, s)[0] == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("omit", ["shared", "rope", "routed_scale",
                                  "held_expert", "bf16"])
def test_comparator_fails_when_a_term_or_the_precision_is_taken_away(
        published, engine, omit, tiny_limits):
    if omit == "bf16":  # weights rounded to 8 bits
        kw = {"weight_dtype": jnp.float8_e4m3fn}
    elif omit == "held_expert":
        kw = {"omit": frozenset({f"expert:{BUSY_HELD_EXPERT}"})}
    else:
        kw = {"omit": frozenset({omit})}
    assert _verdict(published, engine, **kw)


def test_tokenizer_of_stage_and_reference_agree():
    from evam_tpu.stages import describe

    msg = {"source": "synthetic://1920x1080@30?seed=5",
           "timestamp": 166666665,
           "objects": [{"detection": {
               "bounding_box": {"x_min": 0.1 * i, "y_min": 0.05,
                                "x_max": 0.1 * i + 0.3, "y_max": 1.2},
               "confidence": 0.11 + 0.01 * i, "label_id": i % 5}}
               for i in range(40)]}
    objs = [(o["detection"]["label_id"],
             *o["detection"]["bounding_box"].values(),
             o["detection"]["confidence"]) for o in msg["objects"]]
    for vocab in (128, 12800):
        got = describe.render_prompt(msg["source"], msg["timestamp"], objs,
                                     vocab, 32)
        assert got == lm_compare.render_prompt(msg, vocab, 32)
        assert len(got) == 16 + 8 * 32 and max(got) < vocab
        assert describe.instruction_ids(64, vocab) == \
            lm_compare.instruction_ids(64, vocab)


# ------------------------------------------------ configuration files


def test_benchmark_config_holds_the_published_widths_and_the_preset():
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "deepseek_v2_ep8.json").read_text())
    for key, value in DEEPSEEK_V2_PUBLISHED.items():
        if key in cfg["reduced"]:
            continue
        assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (6, 20, 12800)
    assert cfg["published"] == {"num_hidden_layers": 60,
                                "n_routed_experts": 160,
                                "vocab_size": 102400}
    assert cfg["shapes"]["model"] == PRESETS["deepseek_v2_ep8"]
    assert cfg["rehearsal_shapes"]["model"] == TINY
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"]
                if w["name"] == "describe_replay")
    assert (cell["config"], cell["chips"]) == ("deepseek_v2_ep8", 1)
    for m in bench["per_layer"]:
        if "describe_replay" in m.get("workloads", []):
            assert (REPO / "benchmark" / "metrics"
                    / f"{m['name']}.json").is_file()


def test_lm_shapes_come_from_one_variable(monkeypatch):
    monkeypatch.setenv("EVAM_LM_SHAPES", "slots=8,page_tokens=8")
    s = Settings.from_env()
    assert (s.lm.slots, s.lm.page_tokens, s.lm.chunk_tokens) == (8, 8, 512)
    # no shape of the ladder is settable: it follows from the slots
    for unknown in ("rows=3", "slot_buckets=4:8"):
        monkeypatch.setenv("EVAM_LM_SHAPES", unknown)
        with pytest.raises(ValueError):
            Settings.from_env()


@pytest.mark.parametrize("slots,ladder", [
    (128, (16, 32, 48, 64, 80, 96, 112, 128)),
    (8, (1, 2, 3, 4, 5, 6, 7, 8)),
    (20, (2, 4, 6, 8, 10, 12, 14, 16, 18, 20)),
    (4, (1, 2, 3, 4)),
])
def test_decode_ladder_follows_from_the_slots(slots, ladder):
    assert GenerateSizes.from_settings(
        LMSettings(slots=slots)).slot_buckets == ladder


# --------------------------------------------------------- the server


def _registry(tmp_path, lm_models=True):
    from evam_tpu.engine import EngineHub
    from evam_tpu.models import ModelRegistry
    from evam_tpu.models.fetch import synthesize_lm, synthesize_omz
    from evam_tpu.parallel import build_mesh
    from evam_tpu.server.registry import PipelineRegistry

    models = tmp_path / "models"
    synthesize_omz(models, alias="scene_description",
                   version="pvb_deepseek_v2", input_size=128)
    if lm_models:
        synthesize_lm(models, "scene_description_lm", "deepseek_v2",
                      "deepseek_v2_tiny")
    settings = Settings(pipelines_dir=str(REPO / "pipelines"),
                        state_dir=str(tmp_path / "state"))
    hub = EngineHub(
        ModelRegistry(models_dir=models, dtype="float32"), plan=build_mesh(),
        max_batch=4, deadline_ms=4.0,
        lm=LMSettings(slots=4, page_tokens=8, chunk_tokens=64, max_segments=4,
                      private_tokens=288, prefix_tokens=16))
    return PipelineRegistry(settings, hub=hub)


def test_pipeline_end_to_end_through_rest(eight_devices, tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from evam_tpu.server.app import build_app

    reg = _registry(tmp_path)
    out = tmp_path / "out.jsonl"
    path = "/pipelines/scene_description/pvb_deepseek_v2"

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
            engines = await (await c.get("/engines")).json()
            traces = await (await c.get("/traces")).json()
            return st, engines, traces

    try:
        st, engines, traces = asyncio.run(go())
    finally:
        reg.stop_all()
    assert st["state"] == "COMPLETED", st
    msgs = [json.loads(line) for line in out.read_text().splitlines()]
    assert [m["timestamp"] for m in msgs] == sorted(
        m["timestamp"] for m in msgs) and len(msgs) == 6
    shapes = {"model": TINY, "engine": {
        "prefix_tokens": 16, "max_new_tokens": 5, "max_objects": 32}}
    for m in msgs:
        assert check_schema(m) is None and m["objects"]
        assert not lm_compare.check_description(m, shapes)
    row = engines["generate:scene_description_lm/deepseek_v2"]
    assert row["items"] == 6 and row["compiled_programs"] == 5
    assert row["buckets"] == [1, 2, 3, 4]
    assert row["capacity_fps"] > 0
    assert row["pages_in_use"] == 2
    names = {e["name"] for e in traces["traceEvents"]}
    assert {"stage.describe.submit", "generate.queue_wait",
            "generate.prefill", "generate.decode"} <= names


def test_delete_of_a_stream_frees_its_sequences(eight_devices, tmp_path):
    reg = _registry(tmp_path)
    try:
        inst = reg.start_instance("scene_description", "pvb_deepseek_v2", {
            "source": {"uri": "synthetic://96x96@30", "type": "uri"},
            "destination": {"metadata": {
                "type": "file", "path": str(tmp_path / "o.jsonl")}},
            "parameters": {"threshold": 0.1, "max-new-tokens": 16}})
        eng = reg.hub.generate_engine("scene_description_lm/deepseek_v2",
                                      prefix_ids=None)
        deadline = time.time() + 240
        while eng.stats.items < 1 and time.time() < deadline:
            time.sleep(0.1)
        assert eng.stats.items >= 1
        reg.stop_instance(inst.id)
        inst.wait(30)
        assert inst.state.value == "ABORTED"
        deadline = time.time() + 10
        while eng.pages_in_use()[0] != 2 and time.time() < deadline:
            time.sleep(0.05)
        assert eng.pages_in_use()[0] == 2 and eng.queue_depth() == 0
    finally:
        reg.stop_all()


def test_a_detect_only_server_builds_no_generate_engine(tmp_path):
    """Preloading only the detect pipeline builds no generate engine and
    imports nothing of the language-model path (a fresh interpreter: this
    one has imported it already)."""
    import subprocess

    code = """
import sys
from evam_tpu.config import Settings
from evam_tpu.engine import EngineHub
from evam_tpu.models import ModelRegistry, ZOO_SPECS
from evam_tpu.parallel import build_mesh
from evam_tpu.server.registry import PipelineRegistry
# the detector at 64x64 and an eighth of its widths: its size is not
# what is tested, and the full one costs two CPU-minutes to compile
hub = EngineHub(ModelRegistry(dtype="float32",
                              input_overrides={k: (64, 64) for k in ZOO_SPECS},
                              width_overrides={k: 8 for k in ZOO_SPECS}),
                plan=build_mesh(), max_batch=1)
reg = PipelineRegistry(Settings(pipelines_dir=%r, state_dir=%r), hub=hub)
reg.preload("object_detection/person_vehicle_bike")
keys = list(reg.hub.stats())
assert keys == ["detect:object_detection/person_vehicle_bike"], keys
bad = [m for m in sys.modules if m.startswith("evam_tpu.models.lm")
       or m in ("evam_tpu.engine.generate", "evam_tpu.engine.pages",
                "evam_tpu.stages.describe")]
assert not bad, bad
reg.stop_all()
print("ok")
""" % (str(REPO / "pipelines"), str(tmp_path))
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=str(REPO), capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "EVAM_ALLOW_RANDOM_WEIGHTS": "1", "EVAM_WARMUP": "0"})
    assert r.returncode == 0 and "ok" in r.stdout, r.stderr[-2000:]


def test_admission_reads_an_engines_own_capacity():
    from evam_tpu.sched.admission import AdmissionController
    from evam_tpu.sched.classes import SchedConfig

    class Hub:
        max_batch = 8

        def stats(self):
            return {
                "detect:x": {"batches": 10, "items": 80, "group": "detect:x",
                             "stage_ms": {"launch": 10.0}},
                "generate:y": {"batches": 500, "items": 9,
                               "group": "generate:y", "capacity_fps": 40.0,
                               "stage_ms": {"launch": 25.0}},
            }

    ctrl = AdmissionController(Hub(), SchedConfig())
    # the detector alone would read 800 fps; the pipeline's slowest
    # engine serves tens of frames a second
    assert ctrl.capacity_fps() == 40.0


def _modelled_capacity(prefill_s, decode_s, prompt=272, new=48):
    """``GenerateEngine.capacity_fps`` on given step times, at the
    deployment's sizes."""
    eng = GenerateEngine.__new__(GenerateEngine)
    eng.sizes = GenerateSizes()
    eng._program_s = {"prefill": prefill_s, "decode:16": 0.5,
                      "decode:128": decode_s}
    eng._mean_prompt, eng._mean_new, eng._done = prompt, new, 1
    return eng.capacity_fps()


def test_capacity_is_a_chunks_share_and_a_row_of_the_full_decode_step():
    # the chip's step times (PERF.md section 5): a chunk 46.3 ms, a decode
    # step over 128 rows 34.7 ms; a stale time of another bucket is not read
    want = 1.0 / (272 / 512 * 0.0463 + 47 * 0.0347 / 128)
    assert _modelled_capacity(0.0463, 0.0347) == pytest.approx(want)
    assert 26.0 < want < 27.0
    eng = GenerateEngine.__new__(GenerateEngine)
    eng.sizes, eng._program_s, eng._done = GenerateSizes(), {}, 0
    assert eng.capacity_fps() == 0.0  # cold: admission admits


@pytest.mark.parametrize("prefill_s,refused_at", [
    (0.0463, None),  # the Pallas attention: all 32 streams are admitted
    (0.0899, 24),    # the XLA attention of the first chip run: stream 24
])
def test_the_describe_cells_streams_against_the_modelled_capacity(
        prefill_s, refused_at):
    """The cell's 32 streams declare what ISSUE.md fixed (0.5 frames/s
    each); admission reads the generate engine's own capacity."""
    from evam_tpu.sched.admission import AdmissionController, AdmissionError
    from evam_tpu.sched.classes import SchedConfig

    traffic = json.loads((REPO / "benchmark" / "traffic"
                          / "replay_1080p_x32.json").read_text())
    assert (traffic["streams"], traffic["declared_fps"]) == (32, 0.5)
    cap = _modelled_capacity(prefill_s, 0.0325)

    class Hub:
        max_batch = 8

        def stats(self):
            return {"generate:y": {"group": "generate:y",
                                   "capacity_fps": cap}}

    ctrl = AdmissionController(Hub(), SchedConfig())
    tickets, stopped = [], None
    for i in range(traffic["streams"]):
        try:
            tickets.append(ctrl.admit("standard", traffic["declared_fps"]))
        except AdmissionError:
            stopped = i
            break
    assert stopped == refused_at


# ------------------------------------------- prefill over materialised heads


def _heads_of_the_cache(eng):
    """Every latent layer's ``(k_nope, v)`` of the prefix's cached rows as
    they lie now: through ``mla.expand`` as the engine's program, and in
    float64 from the rows and ``W_kvb``."""
    import jax

    cfg, pinned = eng.cfg, np.asarray(eng._shared, np.int32)
    made = jax.jit(lambda params, state: lm.prefix_heads(
        cfg, params, state, pinned))(eng._params, eng._state)
    exact = []
    for i in range(cfg.layers):
        rows = np.asarray(common.layer_page_rows(
            eng._state["pages"], i, pinned).astype(jnp.float32), np.float64)
        w = np.asarray(ref.layer_weights(TINY, i)["kv_b"], np.float64).reshape(
            cfg.kv_rank, cfg.heads, cfg.nope + cfg.v_dim)
        both = np.einsum("sc,chd->hsd", rows[:, :cfg.kv_rank], w)
        exact.append((both[..., :cfg.nope], both[..., cfg.nope:]))
    return made, exact


def _bits(tree):
    import jax

    return [np.asarray(a) for a in jax.tree.leaves(tree)]


def test_the_prefixs_heads_are_made_in_warm_up_and_never_touched(engine):
    """After warm-up the engine holds, per layer, ``W_kvb`` of the prefix's
    cached rows (``k_nope`` and ``v`` [heads, 16, 16], heads-major), beside
    the weights; fifty and more prefill chunks and decode steps later they
    are the same arrays, bit for bit: the prefill program reads them and
    neither writes nor donates them, the decode program never sees them."""
    import jax

    cfg = engine.cfg
    held = engine._prefix_heads
    assert len(held) == cfg.layers == 3
    made, exact = _heads_of_the_cache(engine)
    for (k, v), (k_made, v_made), (k_64, v_64) in zip(held, made, exact):
        assert k.shape == (cfg.heads, 16, cfg.nope) and k.dtype == jnp.bfloat16
        assert v.shape == (cfg.heads, 16, cfg.v_dim)
        assert (np.asarray(k) == np.asarray(k_made)).all()
        assert (np.asarray(v) == np.asarray(v_made)).all()
        for got, want in ((k, k_64), (v, v_64)):
            got = np.asarray(got.astype(jnp.float32))
            assert np.abs(want).max() > 0.1
            np.testing.assert_allclose(got, want, atol=0.01, rtol=0.01)
    assert engine.prefix_heads_bytes() == sum(a.nbytes for a in _bits(held))
    leaves, before = jax.tree.leaves(held), _bits(held)
    _idle(engine)
    steps = engine.stats.batches
    futs = [engine.submit(stream=f"h{i}", prompt_ids=_prompt(300 + i, 40),
                          max_new_tokens=NEW) for i in range(24)]
    for f in futs:
        assert len(f.result(timeout=300)["ids"]) == NEW
    _idle(engine)
    assert engine.stats.batches - steps >= 50
    after = jax.tree.leaves(engine._prefix_heads)
    assert all(a is b and not a.is_deleted() for a, b in zip(after, leaves))
    assert all((a == b).all() for a, b in zip(_bits(after), before))


def test_a_rebuilt_engine_holds_the_same_heads_and_a_long_prefix_fills_them(
        engine):
    """An engine built again (the supervisor's rebuild is a new object
    that warms up anew) over the same prefix holds, bit for bit, the heads
    the first one holds. And over a prefix of three chunks (80 tokens of
    32 a chunk: chunk ``lo`` attends to the rows ``[0, lo)`` through the
    heads the chunks before it left) the held heads are those of the whole
    prefix's cached rows, and a generation behind it agrees with the
    reference."""
    again = _engine(_prefix(), "generate:again",
                    dataclasses.replace(SIZES, slots=1))
    try:
        assert all((a == b).all() for a, b in zip(
            _bits(again._prefix_heads), _bits(engine._prefix_heads)))
        assert again.prefix_heads_bytes() == engine.prefix_heads_bytes()
    finally:
        again.stop()
    long = _engine(_prefix(80), "generate:long",
                   dataclasses.replace(SIZES, slots=1))
    try:
        made, _ = _heads_of_the_cache(long)
        assert all((a == b).all() and a.any() for a, b in zip(
            _bits(long._prefix_heads), _bits(made)))
        assert long._prefix_heads[0][0].shape == (4, 80, 16)
        prompt = _prompt(77, 20)
        out = _generate(long, prompt)
        problems, stats = lm_compare.compare_logits(
            out, *_ref_logits(long.prefix, prompt, out, margins=True))
        assert not problems, (problems, stats)
        assert stats["flipped"] == 0 and stats["max"] < 0.2
    finally:
        long.stop()


def test_the_engines_row_and_a_gauge_name_the_held_heads(engine):
    """``/engines`` and a gauge give the held heads' bytes where
    ``memory_peak_bytes`` is read, and the cached rows a chunk READ (the
    prefix's and the continued sequence's, once a layer) are counted as
    they were: benchmark/opsbytes/deepseek_v2.py reads them so."""
    from evam_tpu.engine.hub import EngineHub
    from evam_tpu.obs import metrics

    def counted():
        c = metrics.get_counter
        return {"read": c("evam_generate_latent_rows_read",
                          {"kind": "prefill"}),
                "tokens": c("evam_generate_tokens", {"kind": "decode"})}

    _idle(engine)
    before = counted()
    _generate(engine, _prompt(41, 40))   # chunks of 32 and 8 tokens
    deadline = time.time() + 10
    while (counted()["tokens"] - before["tokens"] < NEW - 1
           and time.time() < deadline):
        time.sleep(0.05)
    # the first chunk beside the prefix's 16 rows; the second beside them
    # and the 32 continued
    assert counted()["read"] - before["read"] == 16 + (16 + 32)
    cfg = engine.cfg
    want = cfg.layers * cfg.heads * 16 * (cfg.nope + cfg.v_dim) * 2
    row = EngineHub._stat_row(engine, None, None, engine.name)
    assert row["prefix_heads_bytes"] == engine.prefix_heads_bytes() == want
    assert "evam_generate_prefix_heads_bytes" in metrics.render()
    assert metrics.get_gauge("evam_generate_prefix_heads_bytes") == want
    # at the deployment's sizes, from the shapes alone
    for preset, n_bytes in (("deepseek_v2_ep8", 805_306_368),
                            ("kimi_linear_ep4", 67_108_864)):
        fam = family(PRESETS[preset]["model_type"])
        shapes = fam.prefix_heads_shapes(
            fam.Config.from_dict(PRESETS[preset]), 2048)
        assert sum(a.size * a.dtype.itemsize for pair in shapes
                   for a in pair) == n_bytes
    for preset in ("jamba2_3b", "lfm2_moe_ep2", "laguna_xs2_pp8"):
        assert not hasattr(family(PRESETS[preset]["model_type"]),
                           "prefix_heads_shapes")


def _absorbed_prefill(cfg, lp, q_nope, q_rope, lat, seg, prefix, n_prefix,
                      cont, n_cont, prefix_heads=None):
    """``mla.mla_prefill`` in the ABSORBED form it had until PR 45, through
    XLA: every (token, head) one query folded through ``W_uk`` over ONE
    list of stored rows, a row's value its ``c_kv``, the output through
    ``W_uv``. Held heads are of no use to it."""
    q = jnp.concatenate(mla.absorb_q(cfg, lp["w_uk"], q_nope, q_rope),
                        axis=-1)
    keys = jnp.concatenate(
        [rows for rows in (prefix, cont, lat) if rows is not None], axis=0)
    bounds, b0 = common.chunk_bounds(
        seg, n_prefix, n_cont, 0 if prefix is None else prefix.shape[0],
        0 if cont is None else cont.shape[0])
    seen = pallas_attention._visible(
        jnp.arange(keys.shape[0])[None, :], bounds, b0)[None]
    o_lat = common.merge_softmax_sums(common.softmax_sums(
        cfg.softmax_scale, "htc,sc->hts", "hts,sc->htc", q, keys,
        keys[:, :cfg.kv_rank], seen), None).astype(jnp.bfloat16)
    o = common.es("htc,hvc->htv", o_lat, lp["w_uv"]).astype(jnp.bfloat16)
    return common.es("htv,hvo->to", o, lp["o"]).astype(jnp.bfloat16)


def test_generations_are_token_for_token_what_the_absorbed_form_gave(
        engine, monkeypatch):
    """An engine whose chunks attend in the absorbed form (the program as
    it stood, decode untouched) and the engine over materialised heads
    generate the same ids, one chunk or two (a prompt of 40 continues in a
    second chunk), with top logits that agree to bfloat16's roundings. The
    tiny model's logits lie close: where the absorbed form's two best stood
    within those roundings of each other the other may be sampled, and the
    generations part there."""
    monkeypatch.setattr(lm, "mla_prefill", _absorbed_prefill)
    old = _engine(_prefix(), "generate:absorbed",
                  dataclasses.replace(SIZES, slots=2))
    monkeypatch.undo()
    same = 0
    try:
        for seed, n in ((51, 33), (52, 3), (53, 20), (58, 40)):
            prompt = _prompt(seed, n)
            was, now = _generate(old, prompt), _generate(engine, prompt)
            assert len(now["ids"]) == len(was["ids"]) == NEW
            for i in range(NEW):
                a, b = (np.asarray(r["top_logits"][i]) for r in (was, now))
                if now["ids"][i] != was["ids"][i]:
                    assert a[0] - a[1] < 0.1 and i > 0
                    break
                np.testing.assert_allclose(b, a, atol=0.1, rtol=0)
                same += 1
    finally:
        old.stop()
    assert same >= 3 * NEW


@pytest.mark.parametrize("case", ["plain", "continued", "no_prefix",
                                  "warm_up"])
@pytest.mark.parametrize("preset", ["deepseek_v2_tiny", "kimi_linear_tiny",
                                    "deepseek_v2_ep8", "kimi_linear_ep4"])
def test_prefill_over_materialised_heads_is_the_absorbed_arithmetic(
        preset, case):
    """``mla.mla_prefill`` (heads materialised: the prefix's handed as the
    engine holds them, the continued and own rows' expanded in the call,
    the rope part one list for all heads) against the ABSORBED arithmetic
    it replaced: in bfloat16 as it ran (``_absorbed_prefill``) and written
    out in float64, per token and head a query folded through ``W_uk`` over
    latent rows, the output through ``W_uv``. At both families' tiny
    presets and at their published attention widths (128 and 32 heads,
    rank 512, 128 + 64 | 128) over a hidden size cut to 64; a packed chunk
    of three segments and dead rows, the prefix part-visible; ``case``:
    with a sequence that continues; with no prefix at all; and warm-up's
    own, the held heads and the prefix's rows valid only below ``n_prefix``
    (what lies behind is whatever the pages held: it must not be read)."""
    fam = family(PRESETS[preset]["model_type"])
    cfg = dataclasses.replace(fam.Config.from_dict(PRESETS[preset]),
                              hidden=64)
    rng = np.random.default_rng(cfg.heads + len(case))
    width = common.row_width(cfg.latent)
    h, c = cfg.heads, cfg.kv_rank

    def bf16(a):
        return jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)

    def f64(a):
        return np.asarray(a.astype(jnp.float32), np.float64)

    def rows(n):
        return bf16(np.pad(rng.normal(size=(n, cfg.latent)),
                           ((0, 0), (0, width - cfg.latent))))

    made = {"kv_b": bf16(rng.normal(size=(c, h * (cfg.nope + cfg.v_dim)))
                         * c ** -0.5),
            "o": bf16(rng.normal(size=(h * cfg.v_dim, cfg.hidden))
                      * (h * cfg.v_dim) ** -0.5)}
    lp = _laid(cfg, **made)
    seg = np.array([0] * 5 + [1] * 3 + [-1] * 2 + [2] * 4 + [-1] * 2)
    continued = case == "continued"
    t, n_cont = len(seg), 5 if continued else 0
    n_rows, n_prefix = (0, 0) if case == "no_prefix" else (16, 11)
    prefix = rows(n_rows) if n_rows else None
    cont, lat = rows(8) if continued else None, rows(t)
    q_nope = bf16(rng.normal(size=(t, h, cfg.nope)))
    q_rope = bf16(rng.normal(size=(t, h, cfg.rope)))
    held = None
    if n_rows:
        held = mla.expand(cfg, lp, prefix)
        assert [a.shape for a in held] == [
            a.shape for a in mla.prefix_heads_shapes(cfg, 16, 1)[0]]
    # heads-major, as ``mla.qkv`` writes the query
    args = (cfg, lp, q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
            lat, jnp.asarray(seg))
    got = f64(mla.mla_prefill(*args, prefix, n_prefix, cont, n_cont, held))
    if n_rows:
        # a caller that holds nothing gets the same: the prefix expanded
        # in the call
        unheld = f64(mla.mla_prefill(*args, prefix, n_prefix, cont, n_cont))
        assert (unheld == got).all()
    if case == "warm_up":
        # chunk ``lo`` of the prefix's own prefill: rows and heads at and
        # behind ``n_prefix`` are not the prefix's yet
        stale = jnp.arange(n_rows)[:, None] >= n_prefix
        early = f64(mla.mla_prefill(
            *args, jnp.where(stale, 7.0, prefix).astype(jnp.bfloat16),
            n_prefix, cont, n_cont,
            tuple(jnp.where(stale[None], -5.0, a).astype(jnp.bfloat16)
                  for a in held)))
        assert (early == got).all()
    was = f64(_absorbed_prefill(*args, prefix, n_prefix, cont, n_cont))
    np.testing.assert_allclose(got, was, atol=0.03, rtol=0)

    w = f64(made["kv_b"]).reshape(c, h, cfg.nope + cfg.v_dim)
    w_uk, w_uv = w[..., :cfg.nope], w[..., cfg.nope:]
    keys = np.concatenate([f64(r)[:, :cfg.latent] for r in (
        prefix, cont, lat) if r is not None])
    base = n_rows + (8 if continued else 0)
    want = np.zeros((t, cfg.hidden))
    for i in range(t):
        if seg[i] < 0:
            continue
        start = int(np.argmax(seg == seg[i]))
        seen = list(range(n_prefix)) + list(range(base + start, base + i + 1))
        if seg[i] == 0:
            seen += list(range(n_rows, n_rows + n_cont))
        q = np.concatenate([np.einsum("hd,chd->hc", f64(q_nope)[i], w_uk),
                            f64(q_rope)[i]], axis=1)
        sc = q @ keys[seen].T * cfg.softmax_scale
        pr = np.exp(sc - sc.max(axis=1, keepdims=True))
        pr /= pr.sum(axis=1, keepdims=True)
        o = np.einsum("hc,chv->hv", pr @ keys[seen][:, :c], w_uv)
        want[i] = o.reshape(-1) @ f64(made["o"])
    assert np.abs(want).max() > 0.5
    # bfloat16 roundings of the expanded keys and values, the weights of
    # the softmax, the heads' outputs and the result
    np.testing.assert_allclose(got, want, atol=0.03, rtol=0)
    assert not got[seg < 0].any()
    if continued:
        # the continued rows are in segment 0's softmax and in no other's
        alone = f64(mla.mla_prefill(*args, prefix, n_prefix, cont, 0, held))
        assert np.abs(alone - got)[seg == 0].max() > 0.05
        assert (alone == got)[seg != 0].all()


# ------------------------------------------------------ the Pallas kernel


def _three_intervals(rng, r):
    """Bounds of ``r`` rows over 100 prefix rows, 50 continued and 50 own:
    three visible intervals a row, a row that sees nothing, one whose
    intervals are all empty, one that sees the whole prefix and one own
    row."""
    b = np.zeros((r, 4), np.int32)
    b[:, 0] = rng.integers(0, 100, r)
    b[:, 1] = 100 + rng.integers(0, 40, r)
    b[:, 2] = 150
    b[:, 3] = 150 + rng.integers(0, 50, r)
    b[5] = 0                       # a padded token: sees nothing
    b[6] = [0, 100, 150, 150]      # three empty intervals
    b[7] = [100, 100, 199, 200]    # the whole prefix and one own row
    return b


@pytest.mark.parametrize("lists", [1, 2])
@pytest.mark.parametrize("zeros", [0, 64])
@pytest.mark.parametrize("blocks", [(32, 128), (96, 256)])
def test_chunk_kernel_with_a_shared_score_term_matches_its_xla_twin(
        blocks, zeros, lists):
    """ops/pallas_attention.py as the latent families call it, in the
    interpreter against the same arithmetic through XLA: every head a
    key-value head of group 1, a second score term over a part all heads
    share; three visible intervals per row, rows that see nothing, rows
    and keys that do not fill whole blocks. ``zeros``: the kernel is handed
    the rope parts as they lie in a stored row, that many zero columns
    behind them (128 wide), the twin the unpadded ones. ``lists``: the keys
    as one list, or as the prefix's 100 rows and the rest's 100 that the
    key axis walks one after the other (neither a whole block: the prefix's
    padded tail must stay hidden from the intervals behind it)."""
    from evam_tpu.ops.pallas_attention import (
        chunk_attention,
        chunk_attention_xla,
    )

    rng = np.random.default_rng(0)
    g, r, d, p, s = 3, 96, 32, 64, 200
    q, q_r, k, v, k_r = (
        jnp.asarray(rng.standard_normal(sh), jnp.bfloat16)
        for sh in ((g, r, d), (g, r, p), (g, s, d), (g, s, d), (s, p)))
    b = jnp.asarray(_three_intervals(rng, r))
    want = chunk_attention_xla(q, k, v, b, q_r, k_r, scale=0.1, b0=100)

    def stored(a):
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, zeros)])

    def split(a, axis):
        return a if lists == 1 else tuple(jnp.split(a, [100], axis=axis))

    got = chunk_attention(
        q, split(k, 1), split(v, 1), b, stored(q_r), split(stored(k_r), 0),
        scale=0.1, b0=100, block_q=blocks[0], block_k=blocks[1],
        interpret=True)
    if lists == 2:
        # the twin takes the two lists too, one behind the other
        again = chunk_attention_xla(
            q, split(k, 1), split(v, 1), b, q_r, split(k_r, 0), scale=0.1,
            b0=100)
        assert (np.asarray(again) == np.asarray(want)).all()
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() < 0.02
    assert not got[:, 5].any() and not got[:, 6].any() and got[:, 7].any()
    # the shared term is in the scores: without it the answer is another
    bare = np.asarray(chunk_attention_xla(q, k, v, b, scale=0.1, b0=100),
                      np.float32)
    assert np.abs(bare - want).max() > 0.1


@pytest.mark.parametrize("columns", [4, 6])
@pytest.mark.parametrize("lists", [1, 2])
def test_chunk_kernel_with_a_value_width_of_its_own_matches_its_xla_twin(
        lists, columns):
    """Keys of 64 and values of 32: the output, and the running sums in
    VMEM, are as wide as the VALUES. In the interpreter against the twin,
    under bounds of 4 columns and of 6 (a first visible row of the two
    leading intervals), the keys as one list and as two."""
    from evam_tpu.ops.pallas_attention import (
        chunk_attention,
        chunk_attention_xla,
    )

    rng = np.random.default_rng(columns)
    g, r, d, dv, s = 2, 96, 64, 32, 200
    q, k, v = (jnp.asarray(rng.standard_normal(sh), jnp.bfloat16)
               for sh in ((g, r, d), (g, s, d), (g, s, dv)))
    b = _three_intervals(rng, r)
    if columns == 6:
        b = np.concatenate([b, np.maximum(b[:, :1] - 30, 0),
                            np.maximum(b[:, 1:2] - 10, 100)], axis=1)
    b = jnp.asarray(b)
    want = chunk_attention_xla(q, k, v, b, scale=0.1, b0=100)

    def split(a):
        return a if lists == 1 else tuple(jnp.split(a, [100], axis=1))

    got = chunk_attention(q, split(k), split(v), b, scale=0.1, b0=100,
                          block_q=32, block_k=128, interpret=True)
    assert got.shape == want.shape == (g, r, dv)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() < 0.02
    assert not got[:, 5].any() and not got[:, 6].any() and got[:, 7].any()
    if columns == 6:
        # the lower bounds hide rows: without them the answer is another
        bare = np.asarray(chunk_attention_xla(q, k, v, b[:, :4], scale=0.1,
                                              b0=100), np.float32)
        assert np.abs(bare - want).max() > 0.05


def _chunk_attention_of_pr_42(q, k, v, bounds, *, scale, b0, block_q,
                              block_k):
    """The chunk kernel as it stood before it took a shared score term and
    two key lists (PR 40's, with PR 42's bounds), in the interpreter: what
    LFM2's and Laguna's chunks ran, kept here to be compared with."""
    import functools

    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32, neg = jnp.float32, -1e30

    def visible(col, bounds):
        a, b1, c0, c1 = (bounds[:, i:i + 1] for i in range(4))
        if bounds.shape[1] == 4:
            return ((col < a) | ((col >= b0) & (col < b1))
                    | ((col >= c0) & (col < c1)))
        a_lo, b_lo = bounds[:, 4:5], bounds[:, 5:6]
        return (((col >= a_lo) & (col < a)) | ((col >= b_lo) & (col < b1))
                | ((col >= c0) & (col < c1)))

    def kernel(bounds_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
               acc_ref):
        kv = pl.program_id(2)

        @pl.when(kv == 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, neg, f32)
            l_ref[...] = jnp.zeros(l_ref.shape, f32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

        v = v_ref[...]
        s = jax.lax.dot_general(q_ref[...], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
        col = kv * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = visible(col, bounds_ref[...])
        s = jnp.where(ok, s, neg)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        m_ref[...] = m_new

        @pl.when(kv == pl.num_programs(2) - 1)
        def _():
            l = l_ref[...]
            o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
                o_ref.dtype)

    g, r, d = q.shape
    s = k.shape[1]
    block_q = min(block_q, -(-r // 16) * 16)
    block_k = min(block_k, -(-s // 128) * 128)
    rp, sp = -(-r // block_q) * block_q, -(-s // block_k) * block_k

    def pad(x, rows):
        return jnp.pad(x, ((0, 0), (0, rows - x.shape[1]), (0, 0)))

    out = pl.pallas_call(
        kernel, grid=(g, rp // block_q, sp // block_k),
        in_specs=[
            pl.BlockSpec((block_q, bounds.shape[1]), lambda h, i, j: (i, 0)),
            pl.BlockSpec((None, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, rp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 1), f32),
                        pltpu.VMEM((block_q, 1), f32),
                        pltpu.VMEM((block_q, d), f32)],
        interpret=True,
    )(jnp.pad(bounds, ((0, rp - r), (0, 0))), pad(q, rp), pad(k, sp),
      pad(v, sp))
    return out[:, :r]


@pytest.mark.parametrize("family_shape,group,dim,window", [
    ("lfm2", 4, 64, None),     # 8 key-value heads of 64 under groups of 4
    ("laguna", 8, 128, 40),    # a window layer: bounds of six columns
])
def test_chunk_kernel_without_a_shared_term_is_bit_for_bit_what_it_was(
        family_shape, group, dim, window):
    """A family without latent layers hands no shared part and one list of
    keys: the kernel then computes, bit for bit, what it computed before
    it learned either or gave its key blocks a class (the kernel of PR 42
    kept above), and its call has no shared operand and no second list."""
    import jax

    from evam_tpu.ops.pallas_attention import chunk_attention

    rng = np.random.default_rng(3)
    g, t, s = 2, 24, 200
    q, k, v = (jnp.asarray(rng.standard_normal(sh), jnp.bfloat16)
               for sh in ((g, t * group, dim), (g, s, dim), (g, s, dim)))
    b = _three_intervals(rng, t)
    if window is not None:
        lo = np.maximum(b[:, 3] - window, 0)
        b = np.concatenate([b, np.maximum(b[:, :1] - 30, 0),
                            np.maximum(lo[:, None], 100)], axis=1)
        b[:, 2] = np.maximum(b[:, 2], lo)
    b = jnp.asarray(np.repeat(b, group, axis=0))
    kw = dict(scale=dim ** -0.5, b0=100, block_q=32, block_k=128)
    got = chunk_attention(q, k, v, b, interpret=True, **kw)
    was = _chunk_attention_of_pr_42(q, k, v, b, **kw)
    assert got.dtype == was.dtype and (np.asarray(got) == np.asarray(
        was)).all()
    assert np.asarray(got, np.float32).any()

    def kernel_call(fn):
        """The kernel's call, as traced."""
        def find(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    return eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    if (found := find(sub)) is not None:
                        return found
        return find(jax.make_jaxpr(fn)(q, k, v, b).jaxpr)

    # no shared operand and no second list: the classes, the bounds, the
    # queries, ONE list of keys and one of values, and nothing else
    call = kernel_call(lambda *a: chunk_attention(*a, interpret=True, **kw))
    rp = -(-t * group // 32) * 32
    assert [tuple(x.aval.shape) for x in call.invars] == [
        (rp // 32 * 2,), (rp, b.shape[1]), (g, rp, dim), (g, 256, dim),
        (g, 256, dim)]
    assert [tuple(x.aval.shape) for x in call.outvars] == [(g, rp, dim)]


def _chunk_of(case):
    """A small chunk's operands and bounds for the cases of the classes:
    ``(q, k, v, bounds, q_shared, k_shared, b0, blocks, group)``, keys as
    lists where the case has two."""
    from evam_tpu.models.lm import common

    rng = np.random.default_rng(11)
    seg, n_prefix, n_cont, prefix, cont, window, group, two = {
        # a latent family's full chunk: the held prefix, then the rest
        "full": ([0] * 40 + [1] * 24, 256, 20, 256, 32, None, 1, True),
        # the same chunk part-full: dead rows between and behind
        "part": ([0] * 30 + [-1] * 2 + [1] * 20 + [-1] * 12, 256, 20, 256,
                 32, None, 1, True),
        # a window layer, two heads a group: no row's window reaches the
        # prefix's first block, every row's cuts its second
        "window": ([0] * 64, 256, 0, 256, 0, 40, 2, False),
        # no segment continues: the continued rows' block, in the middle
        # of the key axis, is seen by no row
        "middle": ([0] * 40 + [1] * 24, 128, 0, 128, 128, None, 1, False),
    }[case]
    seg = np.asarray(seg, np.int32)
    bounds, b0 = common.chunk_bounds(jnp.asarray(seg), n_prefix, n_cont,
                                     prefix, cont, window)
    bounds = jnp.repeat(bounds, group, axis=0)
    g, d, p, s = 2, 32, 64, prefix + cont + len(seg)
    q, qs, k, v, ks = (
        jnp.asarray(rng.standard_normal(sh), jnp.bfloat16)
        for sh in ((g, len(seg) * group, d), (g, len(seg) * group, p),
                   (g, s, d), (g, s, d), (s, p)))
    if two:
        k, v = (tuple(jnp.split(a, [prefix], axis=1)) for a in (k, v))
        ks = tuple(jnp.split(ks, [prefix], axis=0))
    else:
        qs = ks = None
    return q, k, v, bounds, qs, ks, b0, seg, group


@pytest.mark.parametrize("case,classes", [
    ("full", [[1, 1, 0]]),
    ("part", [[1, 1, 0]]),
    ("window", [[2, 0, 0], [2, 0, 0]]),
    ("middle", [[1, 2, 0]]),
])
def test_chunk_kernel_asks_its_rule_only_where_a_blocks_answer_differs(
        monkeypatch, case, classes):
    """Every (query block, key block) pair has a class (``block_classes``:
    1 WHOLE, every live row sees every key and no mask is computed; 0
    MIXED, the masked visit; 2 NONE, no row sees any, not visited). In the
    interpreter, two heads a grid step:
    the classes are what the chunk's segments say; the outputs are BIT FOR
    BIT those of the same kernel with every pair MIXED (the masked visit
    everywhere, what the kernel did before it had classes) for every live
    row, a dead row comes out 0 though it ran through WHOLE blocks, and
    both agree with the twin through XLA. Query blocks of 64 rows, key
    blocks of 128."""
    from evam_tpu.ops import pallas_attention as pa

    q, k, v, bounds, qs, ks, b0, seg, group = _chunk_of(case)
    list_rows = [x.shape[1] for x in pa._lists(k)]
    kw = dict(scale=0.1, b0=b0, block_q=64, block_k=128)
    handed = pa.block_classes(bounds, b0, list_rows, 64, 128)
    assert np.asarray(handed).tolist() == classes
    got = np.asarray(pa.chunk_attention(q, k, v, bounds, qs, ks,
                                        interpret=True, **kw))
    monkeypatch.setattr(pa, "block_classes",
                        lambda *a, **kw: jnp.zeros_like(handed))
    masked = np.asarray(pa.chunk_attention.__wrapped__(
        q, k, v, bounds, qs, ks, interpret=True, **kw))
    live = np.repeat(seg >= 0, group)
    assert got.dtype == masked.dtype and (got == masked)[:, live].all()
    assert not got[:, ~live].any() and got[:, live].any(axis=-1).all()
    want = np.asarray(pa.chunk_attention_xla(q, k, v, bounds, qs, ks,
                                             scale=0.1, b0=b0), np.float32)
    assert np.abs(got.astype(np.float32) - want).max() < 0.02
    if case in ("window", "middle"):
        # one list and no shared term: PR 42's kernel, kept above
        was = _chunk_attention_of_pr_42(q, k, v, bounds, **kw)
        assert (got == np.asarray(was)).all()


@pytest.mark.parametrize("preset,kind_name,seg,n_prefix,n_cont", [
    ("deepseek_v2_tiny", "mla", [0] * 272 + [1] * 240, 2048, 0),
    ("deepseek_v2_tiny", "mla", [0] * 100 + [-1] * 412, 2000, 300),
    ("lfm2_moe_tiny", "attn", [0] * 32 + [1] * 272 + [2] * 208, 2048, 240),
    ("lfm2_moe_tiny", "attn", [0] * 272 + [-1] * 240, 2048, 0),
    ("laguna_tiny", "attn_full", [0] * 272 + [1] * 240, 2048, 0),
    ("laguna_tiny", "attn_window", [0] * 272 + [1] * 240, 2048, 0),
    ("laguna_tiny", "attn_window", [0] * 512, 1990, 300),
])
def test_the_engines_count_of_key_blocks_is_of_the_array_the_kernel_is_handed(
        monkeypatch, preset, kind_name, seg, n_prefix, n_cont):
    """``evam_generate_chunk_key_blocks``: the host counts a chunk's key
    blocks by class from its segments (the family's ``chunk_key_blocks``,
    numpy, a token's bounds once); the program computes the classes from
    the bounds it hands the kernel (``block_classes`` in
    ``chunk_attention``, every query row's). For the same chunk the two
    are the same array: recorded here from the call that ``mla_prefill``
    or ``attn_prefill`` makes as the family's chunk makes it, at the
    deployment's rows (a prefix of 16 pages of 128, 3 continued pages, a
    chunk of 512) and a tiny model's widths, with the kernel's path taken
    (its twin stands in for the kernel, which the CPU cannot run) and the
    prefix's length an argument, as a chunk's program has it."""
    from evam_tpu.models.lm import attention

    fam = family(PRESETS[preset]["model_type"])
    cfg = fam.Config.from_dict(PRESETS[preset])
    page_tokens, prefix_pages, cont_pages = 128, 16, 3
    seg = np.asarray(seg, np.int32)
    t = len(seg)
    handed = []

    def kernel(q, k, v, bounds, q_shared=None, k_shared=None, *, scale, b0):
        handed.append(np.asarray(pallas_attention.block_classes(
            bounds, b0, [x.shape[1] for x in pallas_attention._lists(k)])))
        return jnp.zeros(
            (*q.shape[:2], pallas_attention._lists(v)[0].shape[2]), q.dtype)

    monkeypatch.setattr(pallas_attention, "chunk_attention", kernel)
    monkeypatch.setattr(common, "TARGET_TPU", True)

    def zeros(*shape):
        return jnp.zeros(shape, jnp.bfloat16)

    traced = (jnp.asarray(n_prefix, jnp.int32), jnp.asarray(n_cont, jnp.int32))
    if kind_name == "mla":
        width = common.row_width(cfg.latent)
        mla.mla_prefill(
            cfg, _laid(cfg), zeros(cfg.heads, t, cfg.nope),
            zeros(cfg.heads, t, cfg.rope), zeros(t, width), jnp.asarray(seg),
            zeros(prefix_pages * page_tokens, width), traced[0],
            zeros(cont_pages * page_tokens, width), traced[1])
    else:
        kind = {"attn": cfg, "attn_full": getattr(cfg, "full", None),
                "attn_window": getattr(cfg, "windowed", None)}[kind_name]
        seen, first = attention.window_pages(
            kind.window, np.arange(1, 1 + prefix_pages, dtype=np.int32),
            traced[0], page_tokens)
        width = attention.kv_width(kind)
        attention.attn_prefill(
            kind, {"o": zeros(kind.heads * kind.head_dim, kind.hidden)},
            zeros(t, kind.heads, kind.head_dim), zeros(t, width),
            jnp.asarray(seg), zeros(len(seen) * page_tokens, width),
            traced[0], zeros(cont_pages * page_tokens, width), traced[1],
            None, first)
    counted = {name: (layers, classes) for name, layers, classes
               in fam.chunk_key_blocks(cfg, seg, n_prefix, n_cont,
                                       prefix_pages, cont_pages, page_tokens)}
    (program,), (layers, host) = handed, counted[kind_name]
    assert host.dtype == program.dtype and (host == program).all()
    assert layers > 0 and sum(pallas_attention.count_classes(host)) == (
        host.size)
    # the kernel's own blocks cut these rows into several, not all MIXED
    assert program.shape[1] > 2 and (program != 0).any()


@pytest.mark.parametrize("preset,seg,n_cont,want", [
    # describe_replay's full chunk: the prefix's two blocks of 1024 WHOLE,
    # the 896 new rows' one MIXED, a layer
    ("deepseek_v2_ep8", [0] * 272 + [1] * 240, 0, [("mla", (6, 12, 0))]),
    # part-full: the dead rows behind do not demote the prefix's blocks
    ("deepseek_v2_ep8", [0] * 272 + [-1] * 240, 0, [("mla", (6, 12, 0))]),
    ("kimi_linear_ep4", [0] * 32 + [1] * 272 + [2] * 208, 240,
     [("mla", (2, 4, 0))]),
    # 2 query blocks of 1024 rows over 4 + 2 key blocks of 512
    ("lfm2_moe_ep2", [0] * 272 + [1] * 240, 0, [("attn", (24, 48, 0))]),
    # a full layer's last query block does not reach the continued rows'
    # block; a window layer's every block is asked
    ("laguna_xs2_pp8", [0] * 272 + [1] * 240, 0,
     [("attn_full", (10, 24, 2)), ("attn_window", (36, 0, 0))]),
    # Jamba's chunks do not run the kernel: nothing to count
    ("jamba2_3b", [0] * 272 + [1] * 240, 0, []),
])
def test_a_chunk_counts_its_key_blocks_by_class(preset, seg, n_cont, want):
    """``evam_generate_chunk_key_blocks{layers, class}``: per kind of layer
    whose chunks run the chunk kernel ``(mixed, whole, not visited)``, the
    (query block, key block) pairs of one key-value head's grid over the
    kind's layers, at the deployment's sizes (a prefix of 2 048, a table
    of 3 pages of 128, a chunk of 512). The dispatch alone, on an engine
    that was never built."""
    eng = object.__new__(GenerateEngine)
    eng.sizes = sz = GenerateSizes()
    eng._lm = family(PRESETS[preset]["model_type"])
    eng.cfg = eng._lm.Config.from_dict(PRESETS[preset])
    eng.prefix = np.zeros(2048, np.int32)
    eng._prefix_pages, eng._private_pages = 16, 3
    eng._window = getattr(eng.cfg, "window", None)
    eng._prefill = eng._prefix_heads = None
    eng._run = lambda *a, **kw: kw
    assert len(seg) == sz.chunk_tokens and sz.page_tokens == 128
    step = eng._dispatch_prefill_raw(
        [1] * len(seg), seg, [0] * len(seg), [0] * len(seg), 2048,
        [17, 18, 19] if n_cont else None, n_cont, [], [])
    assert step["key_blocks"] == want


def test_the_key_block_classes_are_on_metrics(engine):
    """A generation's chunks count their key blocks by class, and the
    series is rendered for ``/metrics``."""
    from evam_tpu.obs import metrics

    def counted():
        return [metrics.get_counter("evam_generate_chunk_key_blocks",
                                    {"layers": "mla", "class": cls})
                for cls in pallas_attention.CLASSES]

    _idle(engine)
    before, chunks = counted(), metrics.get_counter(
        "evam_generate_steps", {"kind": "prefill"})
    _generate(engine, _prompt(61, 40))   # continues in a second chunk
    _idle(engine)
    chunks = metrics.get_counter("evam_generate_steps",
                                 {"kind": "prefill"}) - chunks
    grew = [b - a for a, b in zip(before, counted())]
    # the tiny shapes are one query block over the prefix's one key block
    # and the rest's one, in each of the three latent layers
    assert chunks == 2 and sum(grew) == chunks * 2 * engine.cfg.layers
    assert 'evam_generate_chunk_key_blocks_total{class="mixed",' in (
        metrics.render())


# ------------------------------------ what the shared modules compute


@pytest.mark.parametrize("program,on_chip,want", [
    ("decode", True, "69e183c79370fdc9"),
    ("decode", False, "4af32f910955f26a"),
    ("prefill", True, "48dadf58afc1c338"),
    ("prefill", False, "a9fc55dfbebef9d0")])
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

    check("deepseek_v2_ep8", program, on_chip, monkeypatch, want)


# ------------------------------- the slots follow from the family's state

#: what a v5e's ``memory_stats()["bytes_limit"]`` reads (my chip run, PR 57)
V5E_LIMIT = 16_909_336_064
BRUMBY_TINY = PRESETS["brumby_tiny"]
BRUMBY_SIZES = GenerateSizes(slots=16, page_tokens=8, chunk_tokens=128,
                             max_segments=2, private_tokens=160)


def _brumby(limit=None, sizes=BRUMBY_SIZES, name="generate:rows"):
    return GenerateEngine(name, BRUMBY_TINY, _prefix(), sizes=sizes,
                          memory_limit=limit)


def test_a_family_of_large_rows_gets_the_slots_its_state_leaves_room_for():
    """With a limit handed in: a multiple of ``DECODE_LADDER`` under the
    ceiling, and the decode ladder over THOSE slots; with none (a CPU
    reports none), the ceiling."""
    from evam_tpu.engine.generate import (DECODE_LADDER, RESERVE_BYTES,
                                          fit_slots)
    from evam_tpu.models.lm import brumby

    cfg = brumby.Config.from_dict(BRUMBY_TINY)
    row = 3 * 2 * (136 + 9) * 16 * 4          # a slot's row, three layers
    fixed = 2 * brumby.param_count(cfg) + 2 * row
    for room, want in ((19.5, 16), (15.9, 8), (8.0, 8), (200.0, 16)):
        eng = _brumby(int(RESERVE_BYTES + fixed + room * row))
        try:
            assert eng.slots() == (want, 16), room
            assert eng.sizes.slots == want and want % DECODE_LADDER == 0
            assert eng.buckets == list(range(want // 8, want + 1, want // 8))
            assert len(eng._free_slots) == want
            shapes = eng._state_shapes
            assert "pages" not in shapes
            assert shapes["pow"].shape[:2] == (3, want + 2)
        finally:
            eng.stop()
    eng = _brumby(None)
    try:
        assert eng.slots() == (16, 16)
    finally:
        eng.stop()
    with pytest.raises(ValueError, match="leave no room"):
        _brumby(int(RESERVE_BYTES + fixed + 7.9 * row))
    assert fit_slots(128, None, 1, 1) == 128
    assert fit_slots(128, 10 ** 12, 10 ** 9, 0) == 128
    # the reserve stands in ONE place, with its reason
    assert RESERVE_BYTES == 4_300_000_000


@pytest.mark.parametrize("preset,want", [
    ("deepseek_v2_ep8", 128), ("jamba2_3b", 128), ("kimi_linear_ep4", 128),
    ("lfm2_moe_ep2", 128), ("laguna_xs2_pp8", 128),
    ("nemotron3_super_ep8", 128), ("brumby_14b_pp8", 32)])
def test_every_configuration_derives_its_slots_under_the_chips_limit(
        preset, want):
    """The six that were there come out at the ceiling, with the programs
    they had (their ladder); Brumby at what 170 MB a row leave."""
    model = PRESETS[preset]
    eng = GenerateEngine(f"generate:{preset}", model,
                         np.arange(2048) % model["vocab_held"],
                         sizes=GenerateSizes(), memory_limit=V5E_LIMIT)
    try:
        assert eng.slots() == (want, 128)
        assert eng.buckets == list(range(want // 8, want + 1, want // 8))
        assert len(eng._free_slots) == want
        assert eng._pool.n_pages == (
            1 + 16 + want * 3 if "pages" in eng._state_shapes else 1)
    finally:
        eng.stop()


def test_an_engine_without_pages_serves_with_generations_waiting_for_slots():
    """A family with NO cache rows through the engine's whole life: more
    generations than slots are submitted, so every one that ends hands its
    slot to one that waits (``_pending``); a stream is cancelled while some
    of its generations hold slots and some wait; no page is ever pinned,
    allocated or counted."""
    from evam_tpu.obs import metrics

    eng = _brumby(sizes=dataclasses.replace(BRUMBY_SIZES, slots=2),
                  name="generate:waits")
    eng.warm_async()
    assert eng.warmed.wait(300) and eng.warm_error is None
    try:
        waited = metrics.render().count("evam_generate_slot_wait_seconds")
        assert waited
        rows = metrics.get_counter("evam_generate_latent_rows_read",
                                   {"kind": "decode"})
        futs = [eng.submit(stream=f"s{i % 3}", prompt_ids=_prompt(i, 5 + 9 * i),
                           max_new_tokens=5) for i in range(7)]
        doomed = [eng.submit(stream="doomed", prompt_ids=_prompt(i, 8),
                             max_new_tokens=40) for i in range(5)]
        assert eng.queue_depth() > 0          # they wait for slots
        eng.cancel_stream("doomed")
        assert all(f.result(timeout=120) is None for f in doomed)
        for f in futs:
            out = f.result(timeout=300)
            assert len(out["ids"]) == 5 and np.isfinite(
                out["top_logits"]).all()
        deadline = time.time() + 10
        while len(eng._free_slots) != 2 and time.time() < deadline:
            time.sleep(0.05)
        assert len(eng._free_slots) == 2 and eng.queue_depth() == 0
        assert eng.pages_in_use() == (0, 0) and not eng._pool.pinned
        assert eng.state_slots()[:2] == (0, 2)
        assert metrics.get_counter("evam_generate_latent_rows_read",
                                   {"kind": "decode"}) == rows
    finally:
        eng.stop()
