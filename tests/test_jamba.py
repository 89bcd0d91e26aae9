"""The second language-model family: Jamba (models/lm/jamba.py) through the
generate engine with per-slot recurrent state beside the page cache
(engine/generate.py), its selective-scan kernel's twin
(ops/pallas_selective_scan.py), the second describe pipeline, and the
comparison that decides the Jamba cell's ``correct``
(benchmark/reference/jamba_child.py), all at a tiny size on the CPU
against the plain reference (benchmark/reference/jamba_plain.py): the
same structure as the published model (Mamba runs before, between and
after two attention layers, one key-value head, the three inner norms, a
tied head)."""

import asyncio
import dataclasses
import json
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.opsbytes import jamba as opsbytes
from benchmark.reference import jamba_child, lm_compare
from benchmark.reference import jamba_plain as ref
from benchmark.reference.compare import check_schema
from evam_tpu.config.settings import LMSettings, Settings
from evam_tpu.engine.generate import GenerateEngine, GenerateSizes
from evam_tpu.models.lm import common, family
from evam_tpu.models.lm import jamba as lm
from evam_tpu.models.lm.presets import JAMBA2_3B_PUBLISHED, PRESETS
from evam_tpu.ops import pallas_selective_scan as pss

REPO = Path(__file__).resolve().parent.parent
TINY = PRESETS["jamba_tiny"]
SIZES = GenerateSizes(slots=8, page_tokens=8, chunk_tokens=32, max_segments=8,
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


def _engine(prefix, name="generate:jamba", sizes=SIZES):
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


def _ref_logits(prefix, prompt, result, **kw):
    """The reference's logits rows at the generated positions."""
    full = np.concatenate([prefix, prompt, result["ids"]]).astype(np.int64)
    first = len(prefix) + len(prompt) - 1
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


def test_the_layer_order_follows_from_period_and_offset():
    cfg = lm.Config.from_dict(PRESETS["jamba2_3b"])
    assert cfg.attn_layers == (7, 21) and cfg.mamba_layers == 26
    assert cfg.mamba_ids == tuple(i for i in range(28) if i not in (7, 21))
    # the attention layers run in the trips of Mamba layers 8 and 22
    assert [i for i, j in enumerate(cfg.attn_before) if j >= 0] == [7, 20]
    assert [j for j in cfg.attn_before if j >= 0] == [0, 1]
    assert (cfg.d_inner, cfg.head_dim, cfg.kv_width) == (5120, 128, 256)
    tiny = lm.Config.from_dict(TINY)
    assert tiny.attn_layers == (1, 4) and tiny.mamba_ids == (0, 2, 3, 5)
    assert tiny.attn_before == (-1, 0, -1, 1)
    for i in range(28):
        assert ref.is_attention(PRESETS["jamba2_3b"], i) == (i in (7, 21))


def test_a_config_of_another_shape_is_refused():
    for key, value in (("num_experts", 2), ("num_key_value_heads", 2),
                       ("tie_word_embeddings", False),
                       ("num_hidden_layers", 5)):  # ends in attention
        with pytest.raises(ValueError):
            lm.Config.from_dict({**TINY, key: value})
    with pytest.raises(ValueError):
        family("no_such_model")
    assert family("jamba") is lm


def test_weights_are_the_same_tensors_in_program_and_reference():
    cfg = lm.Config.from_dict(TINY)
    params = lm.make_params(cfg)
    # Mamba layer 3 is the third of the stack; attention layer 4 the second
    w = ref.layer_weights(TINY, 3)
    for name, shape in lm.mamba_shapes(cfg).items():
        got = np.asarray(params["mamba"][name][2], np.float32)
        assert got.shape == shape
        np.testing.assert_array_equal(got, np.asarray(w[name]), name)
    w = ref.layer_weights(TINY, 4)
    for name in lm.attn_shapes(cfg):
        np.testing.assert_array_equal(
            np.asarray(params["attn"][name][1], np.float32),
            np.asarray(w[name]), name)
    np.testing.assert_array_equal(
        np.asarray(params["embed"], np.float32),
        np.asarray(ref.tensor(TINY, ref.GLOBAL_LAYER, "embed",
                              (cfg.vocab, cfg.hidden))))
    # Mamba's own initialisation: step sizes in [0.001, 0.1], A = -(1..N)
    dt = np.log1p(np.exp(np.asarray(w_dt := ref.tensor(
        TINY, 0, "dt_bias", (cfg.d_inner,)))))
    assert 0.0009 < dt.min() and dt.max() < 0.11, (w_dt.min(), w_dt.max())
    a = -np.exp(np.asarray(ref.tensor(TINY, 0, "A_log",
                                      (cfg.d_state, cfg.d_inner))))
    assert np.all(np.diff(a.mean(axis=1)) < 0) and -1.3 < a[0].mean() < -0.8


def test_parameter_count_matches_the_benchmarks_arithmetic():
    full = PRESETS["jamba2_3b"]
    cfg = lm.Config.from_dict(full)
    # gains, biases and D: what opsbytes leaves out
    small = (cfg.hidden + cfg.layers * 2 * cfg.hidden + cfg.mamba_layers * (
        3 * cfg.d_inner + cfg.dt_rank + 2 * cfg.d_state))
    assert lm.param_count(cfg) - small == opsbytes.parameters(full)
    assert 3.02e9 < lm.param_count(cfg) < 3.04e9
    state = lm.state_shapes(cfg, 401, 128, 128)
    assert state["ssm"].shape == (26, 130, 16, 5120)
    # a slot's 3 * 5120 taps as 16 rows (a bfloat16 tile's), so that a
    # kernel's block can be one slot: 960 lanes, 7.5 tiles
    assert state["conv"].shape == (26, 130, 16, 3 * 5120 // 16)
    assert state["pages"].shape == (2, 401, 128, 256)
    # the lanes hold d_inner: no minor dimension that is not whole tiles
    assert all(state[k].shape[-1] % 128 == 0 for k in ("ssm", "pages"))


def test_chunk_bounds_are_the_three_intervals():
    seg = jnp.asarray([0, 0, 1, 1, 1, -1], jnp.int32)
    bounds, b0 = common.chunk_bounds(seg, 5, 3, 8, 4)
    assert b0 == 8
    assert np.asarray(bounds).tolist() == [
        [5, 11, 12, 13], [5, 11, 12, 14],   # segment 0 continues: 3 rows
        [5, 8, 14, 15], [5, 8, 14, 16], [5, 8, 14, 17],
        [0, 8, 0, 0]]                        # padding sees nothing
    bounds, b0 = common.chunk_bounds(seg, 5, 3, 0, 0)
    assert b0 == 0 and np.asarray(bounds)[:, :2].tolist() == [[0, 0]] * 6


# ---------------------------------------------------- the scan's two forms


def _scan_inputs(t, ch, n, lengths, seed=0):
    """A packed chunk: segments of ``lengths`` tokens, padding after."""
    r = np.random.default_rng(seed)
    seg = np.full(t, -1, np.int32)
    lo = 0
    for i, k in enumerate(lengths):
        seg[lo:lo + k] = i
        lo += k

    def f(*shape):
        return jnp.asarray(r.standard_normal(shape), jnp.float32)

    dt = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(0.1), (t, ch))),
                     jnp.float32)
    a = -jnp.exp(0.1 * f(n, ch) + jnp.log(jnp.arange(1, n + 1.0))[:, None])
    return (f(t, ch), dt, f(t, ch), f(t, n), f(t, n), a, 1 + 0.1 * f(ch),
            jnp.asarray(seg), f(len(lengths), n, ch))


def test_scan_twin_is_the_plain_recurrence_per_segment():
    """The packed twin against the recurrence written out in numpy, each
    segment alone from its own initial state."""
    lengths = [5, 1, 9, 3]
    u, dt, z, b, c, a, d, seg, h0 = (
        np.asarray(x) for x in _scan_inputs(24, 128, 16, lengths, seed=3))
    y, h_end = pss.selective_scan_xla(u, dt, z, b, c, a, d, seg, h0)
    lo = 0
    for s, k in enumerate(lengths):
        h = h0[s].astype(np.float64)
        for t in range(lo, lo + k):
            h = np.exp(dt[t][None] * a) * h + (dt[t] * u[t])[None] * b[t][:, None]
            want = ((h * c[t][:, None]).sum(0) + d * u[t]) * (
                z[t] / (1 + np.exp(-z[t])))
            np.testing.assert_allclose(np.asarray(y[t]), want, rtol=2e-4,
                                       atol=2e-5)
        np.testing.assert_allclose(np.asarray(h_end[s]), h, rtol=2e-4,
                                   atol=2e-5)
        lo += k


@pytest.mark.parametrize("lengths,block_c", [
    ([7, 13, 4, 8], 128),        # whole chunk used, four segments
    ([3, 1, 1, 2, 5, 1, 6, 2], 256),  # eight short segments, padding after
    ([40], 128),                 # one segment alone
])
def test_scan_kernel_matches_its_xla_twin(lengths, block_c):
    t = 32 if sum(lengths) <= 32 else 40
    args = _scan_inputs(t, 256, 16, lengths, seed=len(lengths))
    y0, h0 = pss.selective_scan_xla(*args)
    y1, h1 = pss.selective_scan(*args, block_c=block_c, interpret=True)
    live = np.asarray(args[7]) >= 0
    np.testing.assert_allclose(np.asarray(y1)[live], np.asarray(y0)[live],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), rtol=1e-5,
                               atol=1e-6)


def test_a_segment_with_no_token_keeps_its_initial_state():
    args = list(_scan_inputs(16, 128, 16, [4, 6], seed=9))
    h0 = jnp.concatenate([args[8], 7.0 + args[8][:1]], axis=0)  # segment 2
    args[8] = h0
    _, h_end = pss.selective_scan_xla(*args)
    np.testing.assert_array_equal(np.asarray(h_end[2]), np.asarray(h0[2]))
    _, h_end = pss.selective_scan(*args, interpret=True)
    np.testing.assert_array_equal(np.asarray(h_end[2]), np.asarray(h0[2]))


# ---------------------------------------------------------- the engine


@pytest.mark.parametrize("length", [3, 20, 40])
def test_prefill_then_decode_matches_the_reference(engine, length):
    """Through the engine, slot state and pages: packed prefill from the
    prefix snapshot (40 tokens cross a chunk boundary: the second chunk
    starts from the slot's own state), then decode steps in a running
    batch, against the reference's full forward pass."""
    prompt = _prompt(length, length)
    out = _generate(engine, prompt)
    problems, stats = jamba_child.compare_logits(
        out, _ref_logits(engine.prefix, prompt, out))
    assert not problems, (problems, stats)
    assert stats["max"] < 0.2 and out["prefix_tokens"] == 16


def test_compiled_programs_constant_after_warmup(engine):
    before = engine.stats.compiled_programs
    assert before == 1 + len(SIZES.slot_buckets)
    futs = [engine.submit(stream=f"c{i}", prompt_ids=_prompt(i, 5 + 4 * i),
                          max_new_tokens=NEW) for i in range(10)]
    for f in futs:
        assert len(f.result(timeout=300)["ids"]) == NEW
    assert engine.stats.compiled_programs == before


def test_eight_segments_in_one_chunk_do_not_see_each_other(engine):
    prompts = [_prompt(60 + i, 4) for i in range(8)]
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
    assert len(chunks) == 1 and sorted(set(chunks[0])) == list(range(8))
    for prompt, one, many in zip(prompts, alone, packed):
        # the prefill is the same program either way, so a segment alone
        # and among seven others samples from the same logits; the decode
        # steps pad to different buckets (a bfloat16 rounding apart) and
        # are held to the reference
        np.testing.assert_allclose(many["top_logits"][0],
                                   one["top_logits"][0], atol=1e-5)
        assert many["ids"][0] == one["ids"][0]
        problems, stats = jamba_child.compare_logits(
            many, _ref_logits(engine.prefix, prompt, many))
        assert not problems, (problems, stats)


def test_prefix_snapshot_equals_the_prefix_in_front_of_the_prompt(engine):
    prompt = _prompt(9, 10)
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
    assert engine.pages_in_use() == (2, 2 + 8 * 6)


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
    snap = np.asarray(engine._state["ssm"][:, SIZES.slots + 1])
    _generate(engine, _prompt(33, 7))
    np.testing.assert_array_equal(
        snap, np.asarray(engine._state["ssm"][:, SIZES.slots + 1]))
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


def test_state_counters_and_the_engines_row(engine):
    from evam_tpu.engine.hub import EngineHub
    from evam_tpu.obs import metrics

    def counted():
        return {
            "decode": metrics.get_counter("evam_generate_state_rows",
                                          {"kind": "decode"}),
            "prefill": metrics.get_counter("evam_generate_state_rows",
                                           {"kind": "prefill"}),
            "restores": metrics.get_counter("evam_generate_prefix_restores"),
            "tokens": metrics.get_counter("evam_generate_tokens",
                                          {"kind": "decode"})}

    _idle(engine)
    before = counted()
    _generate(engine, _prompt(5, 40))  # two chunks: one restore, two states
    deadline = time.time() + 10
    while (counted()["tokens"] - before["tokens"] < NEW - 1
           and time.time() < deadline):
        time.sleep(0.05)
    grew = {k: v - before[k] for k, v in counted().items()}
    assert grew == {"decode": NEW - 1, "prefill": 2, "restores": 1,
                    "tokens": NEW - 1}
    # no latent layer: no prefix's heads are held
    assert engine._prefix_heads == () and engine.prefix_heads_bytes() == 0
    cfg = engine.cfg
    per_row = cfg.mamba_layers * (4 * cfg.d_state * cfg.d_inner
                                  + 2 * 3 * cfg.d_inner)
    assert engine.state_slots() == (0, 8, 10 * per_row)
    row = EngineHub._stat_row(engine, None, None, engine.name)
    assert (row["state_slots"], row["state_slots_in_use"],
            row["state_bytes"]) == (8, 0, 10 * per_row)
    assert row["pages"] == 2 + 8 * 6
    assert row["prefix_heads_bytes"] == 0
    assert metrics.get_gauge("evam_generate_prefix_heads_bytes") == 0
    text = metrics.render()
    for series in ('evam_generate_state_rows_total{kind="decode"}',
                   "evam_generate_prefix_restores_total",
                   "evam_generate_state_bytes",
                   "evam_moe_held_assignments_total"):
        assert series in text, series


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
        p, _ = jamba_child.compare_logits(
            out, _ref_logits(engine.prefix, prompt, out, **kw))
        problems += p
    return problems


def test_comparator_passes_the_whole_model(published, engine):
    assert not _verdict(published, engine)


@pytest.mark.parametrize("omit", ["conv", "state", "gate", "inner_norms",
                                  "prefix_kv", "float8_weights"])
def test_comparator_fails_when_a_term_or_the_precision_is_taken_away(
        published, engine, omit):
    kw = ({"weight_dtype": jnp.float8_e4m3fn} if omit == "float8_weights"
          else {"omit": frozenset([omit])})
    assert _verdict(published, engine, **kw), omit


def test_a_row_of_another_sequence_is_refused(published, engine):
    (p0, o0), (p1, _), _ = published
    problems, stats = jamba_child.compare_logits(
        o0, _ref_logits(engine.prefix, p1, o0))
    assert problems and stats["max"] > jamba_child.LOGIT_ABS_TOL


# ------------------------------------------------ configuration files


def test_benchmark_config_holds_the_published_widths_and_the_preset():
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "jamba2_3b.json").read_text())
    catalog = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    ] if Path("/opt/skills/guides/model-configs/architectures.jsonl"
              ).is_file() else []
    entry = next((e for e in catalog if e["name"] == "AI21-Jamba2-3B"), None)
    if entry is not None:
        assert entry["config"] == JAMBA2_3B_PUBLISHED
        assert cfg["source"] == entry["source_url"]
    for key, value in JAMBA2_3B_PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["weights"]
    assert cfg["published"]["num_hidden_layers"] == cfg["num_hidden_layers"]
    assert {"layer_order", "initializer_range", "mamba_init", "slots",
            "page_tokens", "chunk_tokens"} <= set(cfg["assumed"])
    assert cfg["shapes"]["model"] == PRESETS["jamba2_3b"]
    assert cfg["shapes"]["model"]["vocab_held"] == 65536
    assert cfg["rehearsal_shapes"]["model"] == TINY
    assert cfg["request"]["parameters"]["max-new-tokens"] == \
        cfg["shapes"]["engine"]["max_new_tokens"] == 64
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"]
                if w["name"] == "describe_jamba_replay")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "jamba2_3b", "replay_1080p_x32", 1)
    mine = [m for m in bench["per_layer"]
            if "describe_jamba_replay" in m.get("workloads", [])]
    assert len(mine) == 17
    for m in mine:
        # two of them are also the Brumby cell's own file, reader and
        # parameters: its name stands behind this cell's (PR 57)
        shared = m["name"] in ("lm_state_slots_in_use_share.jamba_replay",
                               "lm_prefix_restores_per_prompt.jamba_replay")
        assert m["workloads"] == ["describe_jamba_replay"] + shared * [
            "describe_brumby_replay"]
        assert m["moves"] == "frames_per_s"
        assert (REPO / "benchmark" / "metrics"
                / f"{m['name']}.json").is_file()
    pipe = json.loads((REPO / "pipelines" / "scene_description"
                       / "pvb_jamba2" / "pipeline.json").read_text())
    assert pipe["parameters"]["properties"]["max-new-tokens"]["default"] == 64


def test_opsbytes_count_state_weights_and_rows():
    m = PRESETS["jamba2_3b"]
    one = opsbytes.steps(m, prefill_steps=0, prefill_tokens=0,
                         prefill_prompts=0, prefill_rows=0, decode_steps=1,
                         decode_tokens=64, decode_rows=64 * 2384,
                         held_assignments=0, sampled_rows=64)
    weights = 2.0 * opsbytes.parameters(m)
    state = 2 * 64 * 26 * (4 * 16 * 5120 + 2 * 3 * 5120)
    rows = 2.0 * 2 * 256 * (64 * 2384 + 64)
    assert one["bytes"] == pytest.approx(
        weights + state + rows + 2.0 * 64 * 2560)
    assert 5.9e9 < weights < 6.1e9 and 1.1e9 < state < 1.3e9
    scan = opsbytes.scan_ops_and_bytes(m, 512)
    assert scan["flops"] == 6.0 * 512 * 16 * 5120
    assert scan["bytes"] == 512 * (5120 * 10 + 64)
    sizing = opsbytes.ops_and_bytes(
        {"model": m, "engine": {"prefix_tokens": 2048, "max_objects": 32,
                                "max_new_tokens": 64}}, 64)
    assert sizing["bytes"] == pytest.approx(one["bytes"])


def _trace_ctx(ops):
    snap = {"metrics": {}, "engines": {}}
    after = {"metrics": {
        'evam_generate_tokens_total{kind="prefill"}': 4096.0,
        'evam_generate_steps_total{kind="prefill"}': 8.0,
        'evam_generate_steps_total{kind="decode"}': 12.0}, "engines": {}}
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "jamba2_3b.json").read_text())
    return {"device_trace": {"busy_s": 2.0, "devices": 1, "steps": 10,
                             "device_ops": ops},
            "trace_before": snap, "trace_after": after, "config": cfg,
            "device": {"kind": "TPU v5e"},
            "peaks_file": REPO / "benchmark" / "peaks.json"}


def test_trace_op_share_reader():
    from benchmark.readers import trace_op_share

    ops = [["fusion.1 bf16[512,8192]", 0.5],
           ["ssm_selective_scan.15 f32[512,5120]", 0.2],
           ["ssm_selective_scan.16 f32[512,5120]", 0.1],
           ["ssm_selective_scan.17 f32[512,5120]", 0.1]]
    params = {"op": "ssm_selective_scan", "names": 3}
    files = [json.loads((REPO / "benchmark" / "metrics" / f"{m}.jamba_replay"
                         ".json").read_text())
             for m in ("ssm_scan_busy_share", "ssm_scan_roofline")]
    assert [f["params"]["names"] for f in files] == [1, 1]
    assert files[1]["params"]["layers"] == 26
    assert trace_op_share.read(_trace_ctx(ops), params) == pytest.approx(20.0)
    least = 26 * max(6.0 * 4096 * 16 * 5120 / 197e12,
                     4096 * (5120 * 10 + 64) / 819e9) * (10 / 20)
    got = trace_op_share.read(_trace_ctx(ops), dict(
        params, roofline=True, layers=26))
    assert got == pytest.approx(100.0 * least / 0.4) and got < 100
    # the decode step's kernel beside them is another name: the same
    both = ops + [["ssm_decode_rows.9 f32[64,5120]", 0.3]]
    assert trace_op_share.read(_trace_ctx(both), params) == pytest.approx(20.0)
    one = [ops[0], ["ssm_selective_scan.15 f32[512,5120]", 0.4], both[-1]]
    assert trace_op_share.read(_trace_ctx(one), files[0]["params"]) == (
        pytest.approx(20.0))
    assert trace_op_share.read(_trace_ctx(one), files[1]["params"]) == (
        pytest.approx(got))
    # a name that fell off the list, or a program without the kernel
    assert trace_op_share.read(_trace_ctx(ops[:3]), params) is None
    assert trace_op_share.read(_trace_ctx(ops[:1]), params) is None
    assert trace_op_share.read({"device_trace": None}, params) is None


# --------------------------------------------------------- the server


def _registry(tmp_path):
    from evam_tpu.engine import EngineHub
    from evam_tpu.models import ModelRegistry
    from evam_tpu.models.fetch import synthesize_lm, synthesize_omz
    from evam_tpu.parallel import build_mesh
    from evam_tpu.server.registry import PipelineRegistry

    models = tmp_path / "models"
    synthesize_omz(models, alias="scene_description", version="pvb_jamba2",
                   input_size=128)
    synthesize_lm(models, "scene_description_lm", "jamba2", "jamba_tiny")
    settings = Settings(pipelines_dir=str(REPO / "pipelines"),
                        state_dir=str(tmp_path / "state"))
    hub = EngineHub(
        ModelRegistry(models_dir=models, dtype="float32"), plan=build_mesh(),
        max_batch=4, deadline_ms=4.0,
        lm=LMSettings(slots=4, page_tokens=8, chunk_tokens=64, max_segments=4,
                      private_tokens=288, prefix_tokens=16))
    return PipelineRegistry(settings, hub=hub)


def test_second_describe_pipeline_end_to_end_through_rest(eight_devices,
                                                          tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from evam_tpu.server.app import build_app

    reg = _registry(tmp_path)
    out = tmp_path / "out.jsonl"
    path = "/pipelines/scene_description/pvb_jamba2"

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
    problems, stats = jamba_child.compare_logits(desc, np.asarray(ref.forward(
        TINY, full, rows=list(range(first, first + 5)))))
    assert not problems, (problems, stats)
    row = engines["generate:scene_description_lm/jamba2"]
    assert row["items"] == 6 and row["compiled_programs"] == 5
    assert (row["state_slots"], row["state_slots_in_use"]) == (4, 0)
    assert row["pages_in_use"] == 2 and row["capacity_fps"] > 0


# ------------------------------------ what the shared modules compute


@pytest.mark.parametrize("program,on_chip,want", [
    ("decode", True, "baeb5ec35611c3d0"),
    ("decode", False, "205a22891147f132"),
    ("prefill", True, "0fcdc45a698985e9"),
    ("prefill", False, "cea5795356c8d049")])
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

    check("jamba2_3b", program, on_chip, monkeypatch, want)
