"""The seventh language-model family: Brumby (models/lm/brumby.py) through
the generate engine with a slot state of 34 MB a row and layer at the
published widths and NO cache rows (engine/generate.py), power retention of
degree 2 in its chunkwise form and its one-token body
(ops/pallas_power.py), the projections, head norms and rotation as a
``Kind`` (models/lm/attention.py), the seventh describe pipeline, and the
comparison that decides the Brumby cell's ``correct``
(benchmark/reference/brumby_child.py), all at a tiny size on the CPU
against the plain reference (benchmark/reference/brumby_plain.py: the
ATTENTION form): the same structure as the published stage (every layer the
one kind, 4 query heads over 2 key-value heads, an untied head)."""

import asyncio
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.opsbytes import brumby as opsbytes
from benchmark.reference import brumby_child, lm_compare
from benchmark.reference import brumby_plain as ref
from benchmark.reference.compare import check_schema
from evam_tpu.config.settings import LMSettings, Settings
from evam_tpu.engine.generate import GenerateEngine, GenerateSizes
from evam_tpu.models.lm import brumby as lm
from evam_tpu.models.lm import common, family
from evam_tpu.models.lm.presets import BRUMBY_14B_PUBLISHED, PRESETS
from evam_tpu.obs import metrics
from evam_tpu.ops import pallas_power as power

REPO = Path(__file__).resolve().parent.parent
TINY = PRESETS["brumby_tiny"]
FULL = PRESETS["brumby_14b_pp8"]
#: chunks of two segments of whole blocks of 64 (``SEGMENT_ALIGN``)
SIZES = GenerateSizes(slots=4, page_tokens=8, chunk_tokens=128,
                      max_segments=2, private_tokens=160)
NEW = 6
#: what a v5e's ``memory_stats()["bytes_limit"]`` reads (my chip run, PR 57)
V5E_LIMIT = 16_909_336_064


@pytest.fixture(scope="module", autouse=True)
def _yield_the_cores(yield_the_cores):
    """This file's compiles keep to two cores (tests/conftest.py)."""
    yield


def _prefix(n=16):
    return np.random.default_rng(1).integers(1, TINY["vocab_held"], size=n)


def _prompt(seed, n):
    return np.random.default_rng(100 + seed).integers(
        1, TINY["vocab_held"], size=n)


def _engine(prefix, name="generate:brumby", sizes=SIZES, **kw):
    eng = GenerateEngine(name, TINY, prefix, sizes=sizes, **kw)
    eng.warm_async()
    assert eng.warmed.wait(300) and eng.warm_error is None
    return eng


@pytest.fixture(scope="module")
def engine():
    eng = _engine(_prefix())
    yield eng
    eng.stop()


_compare = brumby_child.compare_logits


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


def _idle(eng, timeout=10):
    deadline = time.time() + timeout
    while (len(eng._free_slots) != eng.sizes.slots
           and time.time() < deadline):
        time.sleep(0.05)


# ------------------------------------------------------------ the model


def test_the_family_is_every_layer_the_one_kind_and_keeps_no_pages():
    assert family("brumby") is lm
    cfg = lm.Config.from_dict(FULL)
    assert (cfg.layers, cfg.attn.heads, cfg.attn.kv_heads, cfg.group,
            cfg.attn.head_dim, cfg.attn.rope.theta) == (5, 40, 8, 5, 128,
                                                        1e6)
    assert lm.SEGMENT_ALIGN == power.BLOCK == 64
    shapes = lm.state_shapes(cfg, 1, 128, 32)
    assert set(shapes) == {"pow", "pow_z"}
    assert shapes["pow"].shape == (5, 34, 8, 8256, 128)
    assert shapes["pow_z"].shape == (5, 34, 8, 65, 128)
    assert {a.dtype for a in shapes.values()} == {jnp.dtype(jnp.float32)}
    # 33.8 MB a row and layer, 170 MB a row
    row = sum(a.size * 4 for a in shapes.values()) // 34
    assert row == 5 * 8 * (8256 + 65) * 128 * 4 == 170_414_080


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("attention_bias", True),
    ("tie_word_embeddings", True), ("num_key_value_heads", 3),
    ("rope_scaling", {"type": "yarn"})])
def test_a_config_of_another_shape_is_refused(key, value):
    with pytest.raises(ValueError, match="brumby family"):
        lm.Config.from_dict({**TINY, key: value})


def test_weights_are_the_same_tensors_in_program_and_reference():
    cfg = lm.Config.from_dict(TINY)
    params = lm.make_params(cfg)
    for layer in range(cfg.layers):
        want = ref.layer_weights(TINY, layer)
        assert set(want) == set(params["layers"])
        for name, w in want.items():
            got = np.asarray(params["layers"][name][layer], np.float32)
            want_w = np.asarray(w, np.float32)
            off = got != want_w
            assert off.mean() <= 1e-3, name
            assert np.all(np.abs(got - want_w)[off]
                          <= np.abs(want_w[off]) / 64), name
    # the seeded decays remember 8-40 tokens at the tiny size
    tau = 1.0 / (1.0 - jax.nn.sigmoid(
        np.asarray(params["layers"]["gate_b"], np.float32)))
    assert 7.5 < float(tau.min()) and float(tau.max()) < 42


def test_parameter_count_matches_the_benchmarks_arithmetic():
    cfg = lm.Config.from_dict(FULL)
    h, i, d, a, g = 5120, 17408, 128, 40, 8
    terms = {"q": h * a * d, "k": h * g * d, "v": h * g * d, "o": a * d * h,
             "gate_w": h * g, "gate_b": g, "q_norm": d, "k_norm": d,
             "input_norm": h, "post_norm": h, "mlp_gate": h * i,
             "mlp_up": h * i, "mlp_down": i * h}
    assert {k: int(np.prod(s)) for k, s in lm.layer_shapes(cfg).items()} \
        == terms
    assert sum(terms.values()) == 330_352_896 + 8
    # 3 207 594 240 and the gates' 5 x 8 biases
    total = 2 * 151936 * h + h + 5 * sum(terms.values())
    assert total == 3_207_594_240 + 40
    assert lm.param_count(cfg) == total
    assert opsbytes.parameters({**FULL, "engine_chunk_tokens": 512}) == total
    params = jax.eval_shape(lambda: lm.make_params(cfg))
    assert sum(a.size for a in jax.tree.leaves(params)) == total


# --------------------------------------------------- the two kernels


def test_phi_of_a_query_times_phi_of_a_key_is_their_product_squared():
    rng = np.random.default_rng(0)
    for d in (16, 128):
        q, k = (rng.standard_normal((9, d)).astype(np.float32)
                for _ in range(2))
        got = np.asarray((power.phi(q) * power.phi(k)).sum(-1), np.float64)
        want = (q.astype(np.float64) * k).sum(-1) ** 2
        assert power.phi(q).shape == (9, power.expanded(d))
        # float32 sums of 136 / 8256 signed terms
        np.testing.assert_allclose(got, want, rtol=2e-5,
                                   atol=2e-6 * want.max())
        # the lanes' layout holds the same entries, the half block's lower
        # lanes zero
        lanes = np.asarray(power.phi_lanes(q))
        assert lanes.shape == (9, d // 2 + 1, d)
        assert not lanes[:, -1, :d // 2].any()
        np.testing.assert_array_equal(
            np.concatenate([lanes[:, :-1].reshape(9, -1),
                            lanes[:, -1, d // 2:]], axis=1),
            np.asarray(power.phi(q)))
    assert power.expanded(128) == 8256 == 1032 * 8


def _operands(rng, t, kvh, group, d, scale=0.3):
    def bf(shape, s=1.0):
        return jnp.asarray(rng.standard_normal(shape) * s, jnp.bfloat16)

    lg = jnp.asarray(np.log(rng.uniform(0.9, 0.999, size=(t, kvh))),
                     jnp.float32)
    return (bf((t, kvh, group, d), scale), bf((t, kvh, d), scale),
            bf((t, kvh, d)), lg)


def _states(rng, layers, rows, kvh, d):
    state = jnp.asarray(rng.standard_normal(
        (layers, rows, kvh, power.expanded(d), d)) * 0.1, jnp.float32)
    zsum = jnp.abs(jnp.asarray(rng.standard_normal(
        (layers, rows, kvh, d // 2 + 1, d)), jnp.float32))
    return state, zsum.at[..., -1, :d // 2].set(0.0)


#: segments start at multiples of 64; dead rows behind them
_PACKED = np.full(256, -1, np.int32)
_PACKED[:100] = 0
_PACKED[128:202] = 1


@pytest.mark.parametrize("seg,seg_from,seg_to", [
    (_PACKED, [5, 3, 4], [1, 3, 4]),     # new | continues | absent
    (np.repeat(np.arange(4, dtype=np.int32), 64), [5] * 4, [4] * 4),
    (np.zeros(128, np.int32), [5], [5]),     # the prefix: snapshot on
])
def test_chunk_kernel_matches_its_twin_and_the_attention_form(
        seg, seg_from, seg_to):
    """The chunkwise form in the interpreter, the recurrence token by token
    and, for a segment begun from an EMPTY state, the plain reference's
    attention form: one layer's mixer three ways."""
    rng = np.random.default_rng(3)
    kvh, group, d = 2, 2, 128
    t = len(seg)
    q, k, v, lg = _operands(rng, t, kvh, group, d)
    state, zsum = _states(rng, 2, 6, kvh, d)
    # row 5 (where new segments start) is the state before any token
    state, zsum = state.at[:, 5].set(0.0), zsum.at[:, 5].set(0.0)
    args = (jnp.asarray(1), q, k, v, lg, jnp.asarray(seg),
            jnp.asarray(seg_from, jnp.int32), jnp.asarray(seg_to, jnp.int32),
            state, zsum)
    y, s, z = power.chunk_scan(*args, interpret=True)
    y2, s2, z2 = power.chunk_scan_xla(*args)
    live = seg >= 0
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(y2)[live],
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s2), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z2), rtol=1e-4,
                               atol=1e-4)
    # layer 0 and every row no segment ends in: bit for bit
    assert (np.asarray(s)[0] == np.asarray(state)[0]).all()
    kept = sorted(set(range(6)) - set(seg_to))
    assert (np.asarray(s)[1, kept] == np.asarray(state)[1, kept]).all()
    for i in np.unique(seg[live]):
        if seg_from[i] != 5:
            continue
        rows = seg == i
        f32 = [np.asarray(a, np.float32)[rows] for a in (q, k, v)]
        want = ref.attention_form(
            jnp.asarray(f32[0].reshape(rows.sum(), kvh * group, d)),
            *(jnp.repeat(jnp.asarray(a), group, axis=1) for a in f32[1:]),
            jnp.repeat(jnp.cumsum(lg[rows], axis=0), group, axis=1))
        np.testing.assert_allclose(
            np.asarray(y)[rows].reshape(rows.sum(), kvh * group, d),
            np.asarray(want), rtol=3e-3, atol=3e-3)


def test_decode_kernel_matches_its_twin_and_moves_only_live_rows():
    rng = np.random.default_rng(4)
    kvh, group, d = 2, 5, 128
    q, k, v, lg = _operands(rng, 4, kvh, group, d, 1.0)
    state, zsum = _states(rng, 2, 6, kvh, d)
    slot = jnp.asarray([2, 4, 0, 5], jnp.int32)   # the last names the null
    live = jnp.asarray([True, True, True, False])
    args = (jnp.asarray(1), slot, live, q, k, v, lg, state, zsum)
    y, s, z = power.decode_rows(*args, interpret=True)
    y2, s2, z2 = power.decode_rows_xla(*args)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=2e-3,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s2), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z2), rtol=1e-5,
                               atol=1e-5)
    assert not np.asarray(y)[3].any()
    for got, was in ((s, state), (z, zsum), (s2, state), (z2, zsum)):
        got, was = np.asarray(got), np.asarray(was)
        assert (got[0] == was[0]).all()
        assert (got[1, [1, 3, 5]] == was[1, [1, 3, 5]]).all()
        assert (got[1, [0, 2, 4]] != was[1, [0, 2, 4]]).any()


def test_the_twin_leaves_a_dead_row_bit_for_bit_and_refuses_a_slot_twice():
    rng = np.random.default_rng(5)
    kvh, group, d = 2, 2, 16
    q, k, v, lg = _operands(rng, 3, kvh, group, d, 1.0)
    state, zsum = _states(rng, 1, 4, kvh, d)
    live = jnp.asarray([True, False, False])
    _, s, z = power.decode_rows_xla(
        jnp.asarray(0), jnp.asarray([1, 3, 3]), live, q, k, v, lg, state,
        zsum)
    assert (np.asarray(s)[0, [0, 2, 3]] == np.asarray(state)[0, [0, 2, 3]]
            ).all()
    assert (np.asarray(z)[0, [0, 2, 3]] == np.asarray(zsum)[0, [0, 2, 3]]
            ).all()
    with pytest.raises(Exception, match="name one slot"):
        jax.block_until_ready(power.decode_rows_xla(
            jnp.asarray(0), jnp.asarray([1, 1, 3]),
            jnp.asarray([True, True, False]), q, k, v, lg, state, zsum))


def test_a_token_through_the_decode_body_is_a_token_through_the_scan():
    rng = np.random.default_rng(6)
    kvh, group, d = 2, 2, 16
    q, k, v, lg = _operands(rng, 1, kvh, group, d, 1.0)
    state, zsum = _states(rng, 1, 3, kvh, d)
    one = jnp.asarray([1], jnp.int32)
    y, s, z = power.decode_rows_xla(jnp.asarray(0), one,
                                    jnp.asarray([True]), q, k, v, lg, state,
                                    zsum)
    y2, s2, z2 = power.chunk_scan_xla(
        jnp.asarray(0), q, k, v, lg, jnp.zeros((1,), jnp.int32), one, one,
        state, zsum)
    for a, b in ((y, y2), (s, s2), (z, z2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)


# ------------------------------------------------- through the engine


@pytest.mark.parametrize("length", [3, 20, 100, 150])
def test_prefill_then_decode_matches_the_reference(engine, length):
    """Through the engine: packed prefill from the prefix snapshot (150
    tokens cross a chunk boundary: the second chunk continues from the
    slot's own state), then decode steps in a running batch that move the
    slot state in place, against the reference's full forward pass in the
    ATTENTION form. Logits are compared."""
    prompt = _prompt(length, length)
    out = _generate(engine, prompt)
    problems, stats = _compare(out, _ref_logits(engine.prefix, prompt, out))
    assert not problems, (problems, stats)
    assert out["prefix_tokens"] == 16


def test_two_sequences_sharing_a_chunk_do_not_see_each_other(engine):
    lengths = [12, 50]
    prompts = [_prompt(60 + i, n) for i, n in enumerate(lengths)]
    alone = [_generate(engine, p, n=4) for p in prompts]
    _idle(engine)
    chunks, inner = [], engine._prefill

    def spy(params, state, last_ids, heads, mat, aux):
        chunks.append(np.array(mat[1]))
        return inner(params, state, last_ids, heads, mat, aux)

    engine._prefill = spy
    engine._admit = lambda: None  # hold admission until both wait
    try:
        futs = [engine.submit(stream=f"p{i}", prompt_ids=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        del engine._admit
        packed = [f.result(timeout=300) for f in futs]
    finally:
        engine.__dict__.pop("_admit", None)
        engine._prefill = inner
    assert len(chunks) == 1
    # SEGMENT_ALIGN 64: the second segment starts at the next block
    assert chunks[0].tolist() == ([0] * 12 + [-1] * 52 + [1] * 50
                                  + [-1] * 14)
    for prompt, one, many in zip(prompts, alone, packed):
        np.testing.assert_allclose(many["top_logits"][0],
                                   one["top_logits"][0], atol=1e-4)
        problems, stats = _compare(
            many, _ref_logits(engine.prefix, prompt, many))
        assert not problems, (problems, stats)


def test_rows_that_carry_no_sequence_leave_every_other_slot_as_it_was(engine):
    _idle(engine)
    before = {k: np.asarray(engine._state[k]) for k in ("pow", "pow_z")}
    prompt = _prompt(77, 9)
    out = _generate(engine, prompt, n=30)
    assert np.isfinite(out["top_logits"]).all()
    for k, was in before.items():
        now = np.asarray(engine._state[k])
        moved = np.flatnonzero((now != was).any(
            axis=tuple(i for i in range(now.ndim) if i != 1)))
        assert len(moved) == 1 and moved[0] < SIZES.slots, (k, moved)
    problems, stats = _compare(out, _ref_logits(engine.prefix, prompt, out))
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
    """The shared prefix as a snapshot row of the slot state and NOTHING
    else, against the same tokens run in front of the prompt by an engine
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
    _idle(engine)
    # no page but the null page exists; nothing is pinned
    assert engine.pages_in_use() == (0, 0)
    assert engine._pool.n_pages == 1 and not engine._pool.pinned
    slots_in_use, slots, state_bytes = engine.state_slots()
    assert (slots_in_use, slots) == (0, 4)
    assert state_bytes == 3 * 6 * 2 * (136 + 9) * 16 * 4
    assert engine.slots() == (4, 4)


def test_the_series_of_the_family_go_live_and_no_cache_row_is_counted(
        engine):
    _idle(engine)

    def read():
        return {
            "tokens": sum(metrics.get_counter("evam_generate_tokens",
                                              {"kind": k})
                          for k in ("prefill", "decode")),
            "state_rows": metrics.get_counter("evam_generate_state_rows",
                                              {"kind": "decode"}),
            "moved": metrics.get_counter("evam_generate_state_bytes",
                                         {"kind": "decode"}),
            "moved_prefill": metrics.get_counter(
                "evam_generate_state_bytes", {"kind": "prefill"}),
            "restores": metrics.get_counter("evam_generate_prefix_restores"),
            "rows_read": sum(metrics.get_counter(
                "evam_generate_latent_rows_read", {"kind": k})
                for k in ("prefill", "decode")),
            "shared": metrics.get_counter("evam_generate_decode_shared_rows"),
            "own_pages": sum(metrics.get_counter(
                f"evam_generate_own_pages_{w}", {"kind": "decode"})
                for w in ("read", "skipped")),
        }

    before = read()
    _generate(engine, _prompt(5, 9), n=NEW)
    _idle(engine)
    d = {k: v - before[k] for k, v in read().items()}
    row = engine.state_slots()[2] // 6
    assert d["tokens"] == 9 + NEW - 1
    assert d["state_rows"] == NEW - 1 and d["restores"] == 1
    # each decoded token's row read and written; the prompt's once
    assert d["moved"] == 2 * (NEW - 1) * row
    assert d["moved_prefill"] == 2 * row
    assert d["rows_read"] == d["shared"] == d["own_pages"] == 0
    text = metrics.render()
    assert 'evam_generate_state_bytes_total{kind="decode"}' in text
    assert 'evam_generate_slots{engine="generate:brumby"} 4' in text
    assert "evam_generate_slot_wait_seconds_count" in text
    assert "evam_generate_state_bytes " in text     # the gauge beside it


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
        p, _ = _compare(out, _ref_logits(engine.prefix, prompt, out, **kw))
        problems += p
    return problems


def test_comparator_passes_the_whole_model_in_both_forms(published, engine):
    assert not _verdict(published, engine)
    assert not _verdict(published, engine, state_dtype=jnp.float32)
    assert not _verdict(published, engine, act_dtype=jnp.bfloat16)


@pytest.mark.parametrize("control", ["degree", "gate", "norm", "rope",
                                     "weights"])
def test_comparator_fails_when_the_model_is_another(published, engine,
                                                    control):
    """``state`` is read at the published size alone: a state of 136 x 16
    over 40 tokens holds nothing that a rounding could lose."""
    kw = {"degree": {"degree": 4}, "gate": {"gated": False},
          "norm": {"normalised": False}, "rope": {"rotated": False},
          "weights": {"weight_dtype": jnp.float8_e4m3fn}}[control]
    assert _verdict(published, engine, **kw), control


def test_a_row_of_another_sequence_is_refused(published, engine):
    (p0, o0), (p1, _) = published
    problems, stats = _compare(o0, _ref_logits(engine.prefix, p1, o0))
    assert problems and stats["max"] > brumby_child.LOGIT_TOKEN_TOL


def test_the_child_knows_its_controls_and_the_reference_is_plain():
    assert brumby_child.CONTROLS == ("state", "degree", "gate", "norm",
                                     "rope", "weights")
    assert brumby_child.READINGS == ("acts", "recurrent")
    assert brumby_child.CONTROL_DEGREE == 4
    assert (brumby_child.LOGIT_MEDIAN_TOL, brumby_child.LOGIT_TOKEN_TOL,
            brumby_child.LOGIT_ABS_TOL) == (0.035, 0.15, 0.25)
    text = (REPO / "benchmark" / "reference" / "brumby_plain.py").read_text()
    assert "evam_tpu" not in text and "pallas" not in text
    assert 'default_matmul_precision("highest")' in text
    for n in range(1, 7):     # the six inferences restated at its head
        assert f"\n{n}. " in text
    child = (REPO / "benchmark" / "reference" / "brumby_child.py").read_text()
    assert "die_with_parent" in child and "COMPILE_CACHE_DIR" in child


@pytest.mark.parametrize("program,on_chip,want", [
    ("decode", True, "a8aa6ee1fe7bb551"), ("decode", False, "8a0324bc2d8884ca"),
    ("prefill", True, "40402ef21074dcb5"), ("prefill", False, "1dc95482dcec500e")])
def test_the_step_programs_compute_what_they_did(monkeypatch, program,
                                                 on_chip, want):
    """The guard of the modules this family shares with the others
    (tests/_step_trace.py): its two step programs at the published widths,
    traced for the chip (the two kernels' bodies among the operations) and
    for the host (their twins). A PR that changes an operation of THIS
    family's served path moves the digest, and says so."""
    from _step_trace import check

    check("brumby_14b_pp8", program, on_chip, monkeypatch, want)


# ------------------------------------------------ configuration files


def _cell(bench, name):
    return next(w for w in bench["workloads"] if w["name"] == name)


def test_benchmark_config_holds_the_published_widths_and_the_preset():
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "brumby_14b_pp8.json").read_text())
    path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    catalog = ([json.loads(line) for line in open(path)]
               if path.is_file() else [])
    entry = next((e for e in catalog if e["name"] == "Brumby-14B-Base"), None)
    if entry is not None:
        assert entry["config"] == BRUMBY_14B_PUBLISHED
        assert cfg["source"] == entry["source_url"]
    for key, value in BRUMBY_14B_PUBLISHED.items():
        assert cfg[key] == (5 if key == "num_hidden_layers" else value), key
    assert cfg["reduced"] == ["num_hidden_layers", "weights"]
    assert cfg["published"]["num_hidden_layers"] == 40
    assert set(cfg["departures"]) == {
        "num_hidden_layers", "head_on_the_first_stage",
        "recurrent_form_at_every_length", "weights"}
    assert cfg["deployment"]["chips"] == 8
    assert "layers 0-4" in cfg["deployment"]["this_chip"]
    assert cfg["load_note"].startswith("1x")
    for key, number in (("degree", 1), ("gate", 2), ("normaliser", 3),
                        ("qwen3_conventions", 4), ("state_float32", 5),
                        ("seeding", 6)):
        assert cfg["assumed"][key].startswith(f"({number})"), key
    assert (cfg["assumed"]["page_tokens"], cfg["assumed"]["chunk_tokens"],
            cfg["assumed"]["prefix_tokens"], cfg["assumed"]["max_new_tokens"],
            cfg["assumed"]["precision"]) == (128, 512, 2048, 64, "bfloat16")
    model = cfg["shapes"]["model"]
    assert {k: model[k] for k in FULL} == FULL
    assert model["engine_chunk_tokens"] == \
        cfg["shapes"]["engine"]["chunk_tokens"] == 512
    assert {k: cfg["rehearsal_shapes"]["model"][k] for k in TINY} == TINY
    assert cfg["request"]["parameters"]["max-new-tokens"] == \
        cfg["shapes"]["engine"]["max_new_tokens"] == 64
    assert cfg["opsbytes"] == "brumby"
    assert cfg["reference"]["child"] == "brumby_child"
    assert set(cfg["server_env"]) == {"EVAM_PRELOAD", "EVAM_MAX_BATCH",
                                      "EVAM_NATIVE"}
    # the slots the file states are the slots the engine derives under a
    # v5e's limit, and the ceiling is the setting's
    from evam_tpu.engine.generate import fit_slots

    row = 170_414_080
    assert cfg["assumed"]["slots"] == cfg["shapes"]["engine"]["slots"] == \
        fit_slots(LMSettings().slots, V5E_LIMIT,
                  2 * lm.param_count(lm.Config.from_dict(FULL)) + 2 * row,
                  row) == 32
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = _cell(bench, "describe_brumby_replay")
    assert cell == bench["workloads"][-1]
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        "brumby_14b_pp8", 1, "replay_1080p_x16")
    assert len(cell["why"]) <= 200
    entry = bench["configs"][-1]
    assert entry["name"] == cell["config"]
    assert entry["reduced"] == cfg["reduced"] and len(entry["why"]) <= 200
    rate = next(m for m in bench["end_to_end"] if m["name"] == "frames_per_s")
    assert rate["workloads"][-1] == "describe_brumby_replay"
    # the harness admits 128 per-layer metrics and holds 128, so none is
    # entered: the cell's name is appended to the accepted entries whose
    # file is this cell's own file, reader and parameters, and to no other
    assert len(bench["per_layer"]) == 128
    mdir = REPO / "benchmark" / "metrics"
    mine = [m for m in bench["per_layer"]
            if "describe_brumby_replay" in m.get("workloads", ())]
    assert [m["name"] for m in mine] == [
        "lm_prefill_ms_per_step.replay", "lm_decode_ms_per_step.replay",
        "lm_decode_fill.replay", "lm_prefill_share.replay",
        "lm_queue_wait_ms.replay", "device_idle_share.describe_replay",
        "admit_capacity_fps.describe_replay",
        "lm_state_slots_in_use_share.jamba_replay",
        "lm_prefix_restores_per_prompt.jamba_replay"]
    for m in mine:
        assert m["workloads"][-1] == "describe_brumby_replay"
        assert m["moves"] == "frames_per_s"
        own = mdir / f"{m['name'].split('.')[0]}.brumby_replay.json"
        assert json.loads(own.read_text()) == json.loads(
            (mdir / f"{m['name']}.json").read_text())
    # the files of the seventeen, for the benchmark PR that makes room
    files = sorted(p.name.split(".")[0]
                   for p in mdir.glob("*.brumby_replay.json"))
    assert len(files) == 17 and {
        "pow_chunk_roofline", "pow_chunk_busy_share", "pow_decode_roofline",
        "pow_decode_busy_share", "lm_step_roofline",
        "lm_state_bytes_per_decode_row", "lm_slot_wait_ms"} <= set(files)

    def params(name):
        return json.loads((mdir / f"{name}.brumby_replay.json").read_text())

    roof = params("pow_chunk_roofline")
    assert (roof["reader"], roof["params"]["op"], roof["params"]["layers"],
            roof["params"]["roofline"]) == (
                "trace_op_share", "pow_chunk_scan", 5, True)
    roof = params("pow_decode_roofline")
    assert (roof["reader"], roof["params"]["op"], roof["params"]["kind"]) == (
        "trace_kernel_share", "pow_decode_rows", "decode")
    assert params("lm_step_roofline")["reader"] == "lm_roofline"
    # the traffic file is the siblings' but for its streams and its texts
    x32, x16 = (json.loads((REPO / "benchmark" / "traffic"
                            / f"replay_1080p_x{n}.json").read_text())
                for n in (32, 16))
    texts = ("streams", "what", "declared_fps_why")
    assert x16["streams"] == 16 and list(x16) == list(x32)
    assert {k: v for k, v in x16.items() if k not in texts} == \
        {k: v for k, v in x32.items() if k not in texts}
    pipe = json.loads((REPO / "pipelines" / "scene_description"
                       / "pvb_brumby" / "pipeline.json").read_text())
    assert pipe["parameters"]["properties"]["max-new-tokens"]["default"] == 64
    assert [s.get("model") for s in pipe["stages"] if "model" in s] == [
        "scene_description/pvb_brumby", "scene_description_lm/brumby"]


def test_opsbytes_count_the_state_the_weights_and_each_kernel():
    m = {**FULL, "engine_chunk_tokens": 512}
    none = dict(prefill_steps=0, prefill_tokens=0, prefill_prompts=0,
                prefill_rows=0, decode_steps=0, decode_tokens=0,
                decode_rows=0, held_assignments=0, sampled_rows=0)
    step = dict(none, decode_steps=1, decode_tokens=32, sampled_rows=32)
    full = opsbytes.steps(m, **step)
    assert full == opsbytes.ops_and_bytes(
        {"model": m, "engine": {"prefix_tokens": 2048, "max_objects": 32,
                                "max_new_tokens": 64}}, 32)
    row = 170_414_080
    layer_w = 330_352_896 - 2 * 5120 - 2 * 128
    weights = 2 * (5 * layer_w + 5120 * 151936 + 32 * 5120)
    # each live row's state read and written: 10.9 GB of a 32-row step,
    # beside 4.86 GB of weights and head
    assert full["bytes"] == weights + 2 * 32 * row
    assert 10.9e9 < 2 * 32 * row < 10.95e9 and 4.85e9 < weights < 4.87e9
    dec = opsbytes.scan_ops_and_bytes(m, 32, "pow_decode_rows")
    assert dec["bytes"] == 2 * 32 * row / 5
    assert dec["flops"] == 2 * 32 * 48 * 8256 * 128
    chunk = opsbytes.scan_ops_and_bytes(m, 512)
    assert chunk["flops"] == 2 * 512 * (48 * 8256 * 128 + 40 * 32 * 256)
    assert chunk["bytes"] == 512 * (2 * 96 * 128 + 32) + 2 * row / 5
    with pytest.raises(ValueError, match="no kernel"):
        opsbytes.scan_ops_and_bytes(m, 1, "ssd_chunk_scan")
    # a prompt moves its state once
    one = opsbytes.steps(m, **dict(none, prefill_steps=1, prefill_tokens=272,
                                   prefill_prompts=1, sampled_rows=1))
    assert one["bytes"] == 2 * (5 * layer_w + 5120 * 151936 + 272 * 5120) \
        + 2 * row


# --------------------------------------------------------- the server


def _registry(tmp_path):
    from evam_tpu.engine import EngineHub
    from evam_tpu.models import ModelRegistry
    from evam_tpu.models.fetch import synthesize_lm, synthesize_omz
    from evam_tpu.parallel import build_mesh
    from evam_tpu.server.registry import PipelineRegistry

    models = tmp_path / "models"
    synthesize_omz(models, alias="scene_description",
                   version="pvb_brumby", input_size=128)
    synthesize_lm(models, "scene_description_lm", "brumby", "brumby_tiny")
    settings = Settings(pipelines_dir=str(REPO / "pipelines"),
                        state_dir=str(tmp_path / "state"))
    hub = EngineHub(
        ModelRegistry(models_dir=models, dtype="float32"), plan=build_mesh(),
        max_batch=4, deadline_ms=4.0,
        lm=LMSettings(slots=4, page_tokens=4, chunk_tokens=128,
                      max_segments=2, private_tokens=288, prefix_tokens=16))
    return PipelineRegistry(settings, hub=hub)


def test_seventh_describe_pipeline_end_to_end_through_rest(eight_devices,
                                                           tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from evam_tpu.server.app import build_app

    reg = _registry(tmp_path)
    out = tmp_path / "out.jsonl"
    path = "/pipelines/scene_description/pvb_brumby"

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
            return (st, await (await c.get("/engines")).json(),
                    await (await c.get("/traces")).json())

    try:
        st, engines, traces = asyncio.run(go())
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
    problems, stats = _compare(desc, np.asarray(logits))
    assert not problems, (problems, stats)
    row = engines["generate:scene_description_lm/brumby"]
    assert row["items"] == 4 and row["compiled_programs"] == 5
    assert row["state_slots_in_use"] == 0 and row["state_bytes"] > 0
    assert (row["pages_in_use"], row["pages"]) == (0, 0)
    assert (row["slots"], row["slots_ceiling"]) == (4, 4)
    assert row["capacity_fps"] > 0
    assert "generate.slot_wait" in json.dumps(traces)
