"""Compiles for a DESCRIBED v5e (no chip attached): what Mosaic refuses
at the published widths, it refuses here, at no chip time. Keep every
such test in THIS file: one worker loads the TPU's library, inside the
fixture, never while a module is imported."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("tokens,segments", [
    (512, 8),    # a prefill chunk of the deployment
    (64, 4),     # a short chunk
])
def test_selective_scan_kernel_compiles_at_published_widths(
        one_chip, tokens, segments):
    from evam_tpu.ops.pallas_selective_scan import selective_scan

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ch, n = 5120, 16
    compiled = jax.jit(selective_scan).lower(
        s((tokens, ch), jnp.bfloat16), s((tokens, ch), jnp.float32),
        s((tokens, ch), jnp.bfloat16), s((tokens, n), jnp.bfloat16),
        s((tokens, n), jnp.bfloat16), s((n, ch), jnp.float32),
        s((ch,), jnp.bfloat16), s((tokens,), jnp.int32),
        s((segments, n, ch), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssm_selective_scan" in text


@pytest.mark.parametrize("tokens,segments", [
    (512, 8),    # a prefill chunk of the deployment
    (64, 4),     # a short chunk
])
def test_delta_rule_kernel_compiles_at_published_widths(
        one_chip, tokens, segments):
    from evam_tpu.ops.pallas_kda import delta_rule

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    heads, d = 32, 128
    compiled = jax.jit(delta_rule).lower(
        *[s((tokens, heads * d))] * 5, s((tokens,), jnp.int32),
        s((segments, heads, d, d))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kda_delta_rule" in text


@pytest.mark.parametrize("rows", [64, 128])   # the cell's step, the widest
def test_kda_decode_kernel_compiles_at_published_widths(one_chip, rows):
    """The whole slot state goes in and comes out in place: the program
    keeps no second copy of its 1.64 GB."""
    from evam_tpu.ops.pallas_kda import decode_rows

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    heads, d = 32, 128
    compiled = jax.jit(decode_rows, donate_argnums=(9, 10)).lower(
        s((), jnp.int32), s((rows,), jnp.int32), s((rows,), jnp.bool_),
        *[s((rows, heads, d))] * 5, s((rows, 16, 2304), jnp.bfloat16),
        s((6, 130, heads, d, d)),
        s((6, 130, 16, 2304), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kda_decode_rows" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("rows", [64, 128])
def test_ssm_decode_kernel_compiles_at_published_widths(one_chip, rows):
    from evam_tpu.ops.pallas_selective_scan import decode_rows

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ch, n = 5120, 16
    compiled = jax.jit(decode_rows, donate_argnums=(11, 12)).lower(
        s((), jnp.int32), s((rows,), jnp.int32), s((rows,), jnp.bool_),
        s((rows, ch)), s((rows, ch), jnp.bfloat16),
        s((rows, ch), jnp.bfloat16), s((rows, n), jnp.bfloat16),
        s((rows, n), jnp.bfloat16), s((n, ch)), s((ch,), jnp.bfloat16),
        s((rows, 16, 960), jnp.bfloat16), s((26, 130, n, ch)),
        s((26, 130, 16, 960), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssm_decode_rows" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("m,groups,hidden,inter", [
    (4096, 64, 2304, 1024),    # Kimi: a chunk's 512 tokens x 8
    (512, 64, 2304, 1024),     # Kimi: a 64-row decode step
    (3072, 20, 5120, 1536),    # DeepSeek: a chunk's 512 tokens x 6
    (384, 20, 5120, 1536),     # DeepSeek: a 64-row decode step
])
def test_grouped_product_kernel_compiles_at_published_widths(
        one_chip, m, groups, hidden, inter):
    """Gate and up [groups, hidden, inter] in one call, down [groups,
    inter, hidden] in the next, the tensors as they are: no temporary of
    a tensor's size (302 and 315 MB)."""
    from evam_tpu.ops import pallas_grouped as pg

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(rows, gate, up, down, sizes):
        return pg.product(pg.swiglu(rows, gate, up, sizes), down, sizes)

    compiled = jax.jit(layer).lower(
        s((m, hidden)), s((groups, hidden, inter)),
        s((groups, hidden, inter)), s((groups, inter, hidden)),
        s((groups,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "expert_gate_up" in text and "expert_down" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_expert_layer_runs_the_grouped_kernel_at_published_widths(
        one_chip, monkeypatch):
    """The program around the kernel, a 64-row decode step of Kimi's
    expert layer: router, sort, gather, the two calls, un-sort, the
    shared expert; the held experts' 0.9 GB go in as arguments and are
    not copied."""
    from evam_tpu.models.lm import experts, kimi_linear
    from evam_tpu.models.lm.presets import PRESETS

    cfg = kimi_linear.Config.from_dict(PRESETS["kimi_linear_ep4"])
    monkeypatch.setattr(experts, "on_tpu", lambda: True)

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lp = {name: s(((cfg.n_held,) if name.startswith("expert_") else ())
                  + shape,
                  jnp.float32 if name.startswith("router") else jnp.bfloat16)
          for name, shape in kimi_linear.moe_shapes(cfg).items()}
    compiled = jax.jit(lambda lp, x, live: experts.moe(cfg, lp, x, live)
                       ).lower(lp, s((64, cfg.hidden)),
                               s((64,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "expert_gate_up" in text and "expert_down" in text
    assert "ragged" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("rows", [64, 512])   # a decode step, a chunk
@pytest.mark.parametrize("preset,stacked", [
    ("deepseek_v2_ep8", False), ("kimi_linear_ep4", False),
    ("lfm2_moe_ep2", True), ("laguna_xs2_pp8", True),
    ("nemotron3_super_ep8", True)])
def test_no_operation_of_the_expert_layer_walks_its_assignments(
        one_chip, monkeypatch, preset, stacked, rows):
    """Around the grouped kernels, in all five expert families at the
    published widths: no scatter (the sizes and the group mask are dense
    comparisons), no gather of single scalars under ``router`` (the chosen
    scores are a one-hot maximum), no stand-alone select over the sorted
    rows ``[m, w]`` (the mask is where the rows are gathered), and the
    assignments lie choice-major: no ``[rows, top_k, w]`` with ``top_k``
    padded to a sublane tile. Temporaries under the bound of the test
    above."""
    import re

    from evam_tpu.models.lm import experts, family
    from evam_tpu.models.lm.presets import PRESETS
    from evam_tpu.ops.pallas_grouped import padded

    model = PRESETS[preset]
    cfg = family(model["model_type"]).Config.from_dict(model)
    monkeypatch.setattr(experts, "on_tpu", lambda: True)
    n_held = getattr(cfg, "n_held", None) or cfg.per_group

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lp = {name: s(((4,) if stacked else ())
                  + ((n_held,) if name.startswith("expert_") else ())
                  + shape,
                  jnp.float32 if name.startswith("router") else jnp.bfloat16)
          for name, shape in experts.tensor_shapes(
              cfg, bias=preset != "deepseek_v2_ep8").items()}
    layer = (s((), jnp.int32),) if stacked else ()
    compiled = jax.jit(
        lambda lp, x, live, *layer: experts.moe(cfg, lp, x, live, *layer)
    ).lower(lp, s((rows, cfg.hidden)), s((rows,), jnp.bool_),
            *layer).compile()
    text = compiled.as_text()
    assert "expert_down" in text and "ragged" not in text
    assert "scatter" not in text
    m, w = padded(rows * cfg.top_k), cfg.moe_latent or cfg.hidden
    for line in text.splitlines():
        sizes = re.search(r" gather\(.*slice_sizes=\{([\d,]*)\}", line)
        if sizes and "/router/" in line:
            assert set(sizes.group(1).split(",")) != {"1"}, line
        assert not re.search(
            rf'= bf16\[{m},{w}\]\S* fusion\(.*op_name="[^"]*select_n"',
            line), line
    assert f"[{rows},{cfg.top_k},{w}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("rows", [64, 128])
def test_conv_decode_kernel_compiles_at_published_widths(one_chip, rows):
    """A layer's taps [130, 16, 256] go in and come out in place, a slot's
    row one whole bfloat16 tile."""
    from evam_tpu.ops.pallas_short_conv import decode_rows

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(decode_rows, donate_argnums=(6,)).lower(
        s((), jnp.int32), s((rows,), jnp.int32), s((rows,), jnp.bool_),
        s((rows, 2048)), s((rows, 2048)), s((3, 2048)),
        s((1, 130, 16, 256))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "conv_decode_rows" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


def _own_pages_stay(text, cache, gathered, halves):
    """A decode program's text holds no gather of the rows' own pages
    (``gathered``: rows x table x page), neither half of them split off or
    turned (``halves``), and no copy of the whole ``cache``."""
    import re

    for shape in (gathered, halves):
        assert f"bf16[{shape}]" not in text, shape
    assert not re.search(rf"= bf16\[{cache}\]\S* copy\(", text)


def _chunk_kernel_operands(text):
    """Per call of the chunk kernel in a program's text, its operands'
    shapes in order: the classes of its key blocks (ops/pallas_attention.py
    ``block_classes``, the one operand the classes brought), the bounds,
    the queries, and per key list its keys and values (a latent family: a
    shared part behind the queries and behind each list)."""
    import re

    return [re.findall(r"(\w+\[[\d,]*\])\{", re.search(
        r"operand_layout_constraints=\{(.*?\})\}", line).group(1))
        for line in text.splitlines()
        if re.search(r"%attn_chunk_attention[.\d]* = ", line)]


def _compile_step(lm, cfg, params, one_chip, program, traced_prefix=False):
    """A family's ``decode`` step at 64 rows or its ``prefill`` chunk of
    512 tokens at the deployment's sizes (401 pages of 128, 128 slots, a
    prefix of 16 pages), the state donated, for the described chip:
    ``(compiled, the state's shapes)``. ``traced_prefix``: the chunk takes
    the prefix's length as an argument, as the engine's program does. A
    latent family's chunk takes the prefix's held heads beside the weights,
    as the engine's does."""
    import numpy as np

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = {k: s(v.shape, v.dtype)
             for k, v in lm.state_shapes(cfg, 401, 128, 128).items()}
    shared = np.arange(1, 17, dtype=np.int32)
    i32 = jnp.int32
    if program == "decode":
        b = 64

        def step(params, state, tokens, pos, table, ctx_len, page, off, live,
                 slot):
            return lm.decode_tokens(cfg, params, state, tokens, pos, table,
                                    ctx_len, page, off, live, shared, 2048,
                                    slot)

        args = (s((b,), i32), s((b,), i32), s((b, 3), i32), s((b,), i32),
                s((b,), i32), s((b,), i32), s((b,), jnp.bool_), s((b,), i32))
    else:
        t, n_seg = 512, 8

        heads = (jax.tree.map(lambda a: s(a.shape, a.dtype),
                              lm.prefix_heads_shapes(cfg, 2048))
                 if hasattr(lm, "prefix_heads_shapes") else ())

        def step(params, state, heads, tokens, seg, pos, page, off, cont,
                 n_cont, last_idx, seg_from, seg_to, n_prefix=2048):
            return lm.prefill_chunk(
                cfg, params, state, tokens, seg, pos, page, off, shared,
                n_prefix, cont, n_cont, last_idx, seg_from, seg_to,
                **({"prefix_heads": heads} if heads else {}))

        args = (heads, *[s((t,), i32)] * 5, s((3,), i32), s((), i32),
                *[s((n_seg,), i32)] * 3,
                *([s((), i32)] if traced_prefix else []))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, state, *args).compile()
    return compiled, state


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_lfm2_step_programs_compile_at_published_widths(one_chip,
                                                        monkeypatch, program):
    """Both step programs of the LFM2-MoE family at the deployment's
    sizes: all 24 layers in one loop body, so each kernel is in the
    program ONCE; the 7.75 GB of stacked expert tensors are read where
    they lie (temporaries stay small); and a decode step holds no scatter
    and no whole-array copy of the slot state: the taps move through
    ``conv_decode_rows``."""
    import re

    from evam_tpu.models.lm import common, lfm2_moe as lm
    from evam_tpu.models.lm.presets import PRESETS

    monkeypatch.setattr(common, "TARGET_TPU", True)
    cfg = lm.Config.from_dict(PRESETS["lfm2_moe_ep2"])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def stack(n, shapes, held=()):
        return {k: s((n, *(held if k.startswith("expert_") else ()), *v))
                for k, v in shapes.items()}

    params = {
        "embed": s((cfg.vocab, cfg.hidden)), "final_norm": s((cfg.hidden,)),
        "norms": stack(cfg.layers, lm.norm_shapes(cfg)),
        "conv": stack(len(cfg.conv_ids), lm.conv_shapes(cfg)),
        "attn": stack(len(cfg.attn_ids), lm.attn_shapes(cfg)),
        "dense": stack(cfg.n_dense, lm.dense_shapes(cfg)),
        "moe": stack(len(cfg.moe_ids), lm.moe_shapes(cfg), (cfg.n_held,)),
    }
    compiled, _ = _compile_step(lm, cfg, params, one_chip, program)
    text = compiled.as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call"', text)
    for name, n in (("expert_gate_up", 1), ("expert_down", 1),
                    ("conv_decode_rows", int(program == "decode")),
                    ("attn_decode_pages", int(program == "decode")),
                    ("attn_chunk_attention", int(program == "prefill"))):
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == n, name
    assert len(calls) == 3 + int(program == "decode")
    assert "ragged" not in text
    if program == "prefill":
        # 2 query blocks of 1024 rows over 6 key blocks of 512: ONE list,
        # padded to whole blocks, and no operand beyond the classes
        assert _chunk_kernel_operands(text) == [[
            "s32[12]", "s32[2048,4]", "bf16[8,2048,64]", "bf16[8,3072,64]",
            "bf16[8,3072,64]"]]
    slot_state = "bf16[18,130,16,256]"
    moved = [line for line in text.splitlines()
             if re.search(rf"= {re.escape(slot_state)}\S* (scatter|copy)\(",
                          line)]
    if program == "decode":
        assert not moved, moved[:2]
        assert not re.search(r"= bf16\[(1,)?130,16,256\]\S* scatter\(", text)
        # a row's own pages are read where they lie: none gathered, split
        # into keys and values or turned, and the cache nowhere copied
        _own_pages_stay(text, "6,401,128,1024", "64,3,128,1024",
                        "64,384,512")
        # nor does XLA fetch the dense layers' weights (59 MB) on chip
        # behind every layer's mixer, as it did in the room the gathered
        # rows left until the kernel asked for that room itself
        assert "slice-start" not in text
    # no layer's pages sliced out (105 MB), no scores materialised
    assert compiled.memory_analysis().temp_size_in_bytes < {
        "decode": 16, "prefill": 64}[program] << 20


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_laguna_step_programs_compile_at_published_widths(one_chip,
                                                          monkeypatch,
                                                          program):
    """Both step programs of the Laguna family at the deployment's sizes:
    the five layers in one loop body whose two mixers are the branches of
    a ``lax.cond``, so the chunk kernel is in the prefill program TWICE
    (once a kind of layer, under bounds of 4 and of 6 columns) and each
    grouped product once; the 6.4 GB of stacked expert tensors are read
    where they lie and no layer's pages are taken out of the cache (210
    MB): the temporaries stay small. A window layer gathers 4 (decode) or
    5 (a chunk, whose prefix length is an argument) of the prefix's 16
    pages."""
    import re

    from evam_tpu.models.lm import common, laguna as lm
    from evam_tpu.models.lm.presets import PRESETS

    monkeypatch.setattr(common, "TARGET_TPU", True)
    cfg = lm.Config.from_dict(PRESETS["laguna_xs2_pp8"])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: s(a.shape, a.dtype),
                          jax.eval_shape(lambda: lm.make_params(cfg)))
    assert params["moe"]["expert_gate"].shape == (4, 256, 2048, 512)
    compiled, state = _compile_step(lm, cfg, params, one_chip, program,
                                    traced_prefix=True)
    text = compiled.as_text()
    for name, n in (("expert_gate_up", 1), ("expert_down", 1),
                    ("attn_decode_pages", 2 * int(program == "decode")),
                    ("attn_chunk_attention", 2 * int(program == "prefill"))):
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == n, name
    assert "ragged" not in text
    # no expert tensor copied, whole or a layer of it
    for shape in ("4,256,2048,512", "256,2048,512", "4,256,512,2048",
                  "256,512,2048"):
        assert not re.search(rf"= bf16\[{shape}\]\S* copy\(", text), shape
    # nor a layer taken out of the cache before its pages are gathered
    assert state["pages"].shape == (5, 401, 128, 2048)
    assert not re.search(r"= bf16\[401,128,2048\]", text)
    if program == "decode":
        # once a kind of layer the kernel that reads a row's own pages
        # where they lie (101 MiB of temporaries while they were gathered)
        _own_pages_stay(text, "5,401,128,2048", "64,3,128,2048",
                        "64,384,1024")
    assert compiled.memory_analysis().temp_size_in_bytes < {
        "decode": 16, "prefill": 400}[program] << 20


#: (preset, program) -> what ``_latent_program`` compiled
_LATENT_PROGRAMS: dict = {}


def _latent_program(one_chip, monkeypatch, preset, program):
    """A latent family's step program at the deployment's sizes, compiled
    once for the tests below: ``(the family, its config, its parameters'
    shapes, the compiled program, the state's shapes, its text)``."""
    from evam_tpu.models.lm import common, family
    from evam_tpu.models.lm.presets import PRESETS

    if (preset, program) not in _LATENT_PROGRAMS:
        monkeypatch.setattr(common, "TARGET_TPU", True)
        lm = family(PRESETS[preset]["model_type"])
        cfg = lm.Config.from_dict(PRESETS[preset])
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(lambda: lm.make_params(cfg)))
        compiled, state = _compile_step(lm, cfg, params, one_chip, program)
        _LATENT_PROGRAMS[preset, program] = (lm, cfg, params, compiled,
                                             state, compiled.as_text())
    return _LATENT_PROGRAMS[preset, program]


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("preset", ["deepseek_v2_ep8", "kimi_linear_ep4"])
def test_no_step_program_turns_a_latent_layers_weights(one_chip, monkeypatch,
                                                       preset, program):
    """The latent mixer's up-projections are held as their products read
    them (``mla.store``: ``w_qn``, ``w_qr``, ``w_uk``, ``w_uv`` per head
    with the contraction last, ``o`` [heads, v_dim, hidden]), so neither
    step program of either family copies one: no ``copy`` whose operand or
    ``op_name`` is such a parameter, none that writes an array of such a
    tensor's size in any axis order (inside Kimi's loop a parameter is an
    element of a tuple, its name gone), and none of the published shapes
    either. While ``kv_b`` was held [kv_rank, heads * 256] DeepSeek's
    decode program turned it twice a layer, ``bf16[512,32768]`` and
    ``bf16[512,128,256]``, 805 MB read and written a step, and a chunk
    once; a chunk also turned the query behind ``q_b``
    (``bf16[512,24576]``), its two parts heads-major for the chunk kernel
    (``bf16[512,128,64]``) and the kernel's output back to rows
    (``bf16[512,128,128]``): the products write and read those heads-major
    now."""
    import re

    from evam_tpu.models.lm import mla

    _, cfg, params, _, _, text = _latent_program(one_chip, monkeypatch,
                                                 preset, program)
    layer = (params["layers"] if "layers" in params else params["mla"])[0]
    held = {name: layer[name].shape
            for name in ("w_qn", "w_qr", "w_uk", "w_uv", "o")}
    # no published array kept beside them
    assert not (set(mla.LAID) - {"o"}) & set(layer)
    assert held["o"] == (cfg.heads, cfg.v_dim, cfg.hidden)
    sizes = {tuple(sorted(shape)) for shape in held.values()}
    q_out, kv_out = cfg.nope + cfg.rope, cfg.nope + cfg.v_dim
    published = [(cfg.kv_rank, cfg.heads * kv_out),
                 (cfg.kv_rank, cfg.heads, kv_out),
                 (512, cfg.heads * q_out), (512, cfg.heads, cfg.rope),
                 (512, cfg.heads, cfg.v_dim)]
    copies = [line for line in text.splitlines()
              if re.search(r"= \w+\[[\d,]+\]\S* copy\(", line)]
    assert copies   # the pattern still finds this compiler's copies
    for line in copies:
        shape = tuple(map(int, re.search(
            r"= \w+\[([\d,]+)\]", line).group(1).split(",")))
        assert tuple(sorted(shape)) not in sizes, line[:200]
        assert shape not in published, line[:200]
        assert not re.search(
            r"copy\(%params\w*_(w_qn|w_qr|w_uk|w_uv|o|q|q_b|kv_b)__",
            line), line[:200]
        name = re.search(r'op_name="([^"]*)"', line)
        assert not (name and re.search(
            r"\['(w_qn|w_qr|w_uk|w_uv|o|q|q_b|kv_b)\\?'\]", name.group(1))
        ), line[:200]
    if program == "prefill":
        # the query's parts leave their products heads-major: nothing of
        # the rows-major shapes is left under the mixer's scope at all
        for shape in published[2:4]:
            assert "bf16[%s]" % ",".join(map(str, shape)) not in text


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("preset", ["deepseek_v2_ep8", "kimi_linear_ep4"])
def test_latent_page_cache_stays_where_it_lies(one_chip, monkeypatch, preset,
                                               program):
    """Both step programs of both latent families at the deployment's
    sizes, the state donated: the page cache, its rows stored in whole
    lane tiles (``common.row_width``: 640 for the model's 576), enters
    and leaves rows-minor and no copy turns it tokens-minor (at 576 the
    compiler kept it tokens-minor and each program copied all of it to
    rows-minor and back, every step: 355 MB each way in DeepSeek's), and
    every layer gathers its pages out of the whole array. The prefix's
    heads, which a chunk attends to and the engine holds (``[heads, 2048,
    128]`` keys and as many values a latent layer: 134 MB in DeepSeek's),
    are parameters of the PREFILL program that nothing copies and nothing
    is concatenated behind (the chunk kernel walks them and the chunk's new
    rows' as two lists), and the decode program does not take them.
    Neither family's programs hold a copy of the whole cache at all:
    DeepSeek's six layers are unrolled, and Kimi's two MLA layers sit in
    branches of a ``lax.switch`` inside the ONE ``lax.scan`` over its KDA
    mixers (each KDA kernel once a program, which the benchmark's readers
    of ``kda_delta_rule`` rest on) that read the cache and never return
    it: the loop body makes the write, under a ``lax.cond`` that holds
    nothing else (the ``switch``, while it returned the cache, had it
    copied whole, twice a decode step and three times a chunk, 131 MB
    each)."""
    import re

    lm, cfg, _, compiled, state, text = _latent_program(
        one_chip, monkeypatch, preset, program)
    pages = state["pages"].shape
    assert pages[-1] == 640 and cfg.latent == 576
    cache = re.escape("bf16[%s]" % ",".join(map(str, pages)))
    entry = re.findall(rf"= {cache}(\S*) parameter\(", text)
    assert entry and all(e.startswith("{3,2,1,0") for e in entry), entry
    copies = re.findall(rf"= {cache}(\S*) copy\(", text)
    assert not [c for c in copies if c.startswith("{2,3,1,0")], copies
    # nor is a layer taken out of it before its pages are gathered
    # (``common.layer_page_rows``): six of those are the whole cache too
    layer = re.escape("bf16[%s]" % ",".join(map(str, pages[1:])))
    assert not re.search(rf"= {layer}", text)
    held = re.escape(f"bf16[{cfg.heads},2048,128]")
    handed = re.findall(rf"= {held}\S* parameter\(", text)
    # read-only data: neither program returns them (nothing to donate)
    assert (cfg.heads, 2048, 128) not in [
        tuple(o.shape) for o in jax.tree.leaves(compiled.out_info)]
    if program == "prefill":
        n_latent = len(lm.prefix_heads_shapes(cfg, 2048))
        assert n_latent == {"deepseek_v2_ep8": 6, "kimi_linear_ep4": 2}[preset]
        assert len(handed) >= 2 * n_latent   # at the entry; in branches too
        assert not re.search(rf"= {held}\S* (copy|concatenate)\(", text)
        # nor the prefix's and the new rows' heads as one list
        assert f"bf16[{cfg.heads},{2048 + 384 + 512},128]" not in text
        assert len(re.findall(r"%attn_chunk_attention[.\d]* = ", text)) == (
            n_latent)
        # the held heads and the chunk's rows' as two lists, the rope part
        # once a list, and no operand beyond the classes of 3 key blocks
        h = cfg.heads
        assert _chunk_kernel_operands(text) == [[
            "s32[3]", "s32[512,4]", f"bf16[{h},512,128]", f"bf16[{h},512,128]",
            f"bf16[{h},2048,128]", f"bf16[{h},2048,128]", "bf16[2048,128]",
            f"bf16[{h},896,128]", f"bf16[{h},896,128]", "bf16[896,128]"]
        ] * n_latent
        assert "mla_latent_attention" not in text
    else:
        assert not handed, handed[:2]
    assert not copies, copies
    if preset == "deepseek_v2_ep8":
        # 74 and 228 MiB before the chunk ran over materialised heads;
        # with the 576-value row's two copies 434 and 626
        assert compiled.memory_analysis().temp_size_in_bytes < {
            "decode": 256, "prefill": 400}[program] << 20
    else:
        for name, where in (("kda_decode_rows", "decode"),
                            ("kda_delta_rule", "prefill")):
            assert len(re.findall(rf"%{name}[.\d]* = ", text)) == int(
                program == where), name


@pytest.mark.parametrize("heads,rows,keys,dim,bounds", [
    (8, 512 * 4, 2048 + 384 + 512, 64, 4),  # LFM2: a chunk, 4 heads a group
    (8, 64 * 4, 512 + 64, 64, 4),           # a short chunk, ragged key count
    (8, 512 * 6, 2048 + 384 + 512, 128, 4),  # Laguna: a full layer's chunk
    (8, 512 * 8, 640 + 384 + 512, 128, 6),   # a window layer's, lower bounds
])
def test_chunk_attention_kernel_compiles_at_published_widths(
        one_chip, heads, rows, keys, dim, bounds):
    from evam_tpu.ops.pallas_attention import chunk_attention

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda *a: chunk_attention(*a, scale=dim ** -0.5, b0=2048)).lower(
        s((heads, rows, dim)), s((heads, keys, dim)), s((heads, keys, dim)),
        s((rows, bounds), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "attn_chunk_attention" in text


@pytest.mark.parametrize("heads", [128, 32])   # DeepSeek-V2's, Kimi-Linear's
def test_chunk_attention_kernel_compiles_for_latent_heads(one_chip, heads):
    """A latent family's chunk: every head a key-value head of group 1,
    the prefix's held heads and the 384 + 512 new rows' as two lists, the
    rope part one list all heads share, 128 wide as it lies in a row."""
    from evam_tpu.ops.pallas_attention import chunk_attention

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def lists(*lead):
        return tuple(s((*lead, n, 128)) for n in (2048, 896))

    compiled = jax.jit(
        lambda *a: chunk_attention(*a, scale=0.1147, b0=2048)).lower(
        s((heads, 512, 128)), lists(heads), lists(heads),
        s((512, 4), jnp.int32), s((heads, 512, 128)), lists()).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "attn_chunk_attention" in text
    # neither list copied behind the other: the new rows' 896 padded to
    # whole blocks (2 x 34 MB at 128 heads) and no more
    assert compiled.memory_analysis().temp_size_in_bytes < 80 << 20


@pytest.mark.parametrize("rows", [16, 64, 128])
@pytest.mark.parametrize("heads,kv_heads,dim,window,layers", [
    (48, 8, 128, None, 5),   # Laguna: a full layer
    (64, 8, 128, 512, 5),    # a window layer
    (32, 8, 64, None, 6),    # LFM2: two heads a lane tile
    (20, 1, 128, None, 2),   # Jamba (served through XLA: rows too small)
])
def test_decode_pages_kernel_compiles_at_published_widths(
        one_chip, rows, heads, kv_heads, dim, window, layers):
    """The whole cache goes in as it lies and nothing of its size comes
    out or is kept beside it."""
    from evam_tpu.ops.pallas_attention import decode_pages

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda *a: decode_pages(
        *a, kv_heads=kv_heads, scale=dim ** -0.5, window=window)).lower(
        s((rows, heads, dim)), s((layers, 401, 128, 2 * kv_heads * dim)),
        s((), jnp.int32), s((rows, 3), jnp.int32),
        s((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "attn_decode_pages" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


# ------------------------------------------------------- Nemotron-H's


@pytest.mark.parametrize("tokens,segments", [
    (512, 8),     # a chunk of the deployment
    (128, 1),     # one block, one segment
])
def test_ssd_chunk_scan_kernel_compiles_at_published_widths(
        one_chip, tokens, segments):
    """Nemotron-3-Super's Mamba-2 recurrence in its chunkwise form: 128
    heads of 64 over a state of 128 in 8 groups, a group's 8 pairs of heads
    a grid step, the visits of a packed chunk its prefetched scalars; the
    states of 8 segments (32 MB) go in and out and nothing of that size is
    made beside them."""
    from evam_tpu.ops import pallas_ssd

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32 = jnp.float32
    compiled = jax.jit(pallas_ssd.chunk_scan).lower(
        s((tokens, 8192)), s((tokens, 128), f32), s((128,), f32),
        s((tokens, 1024)), s((tokens, 1024)), s((tokens,), jnp.int32),
        s((segments, 64, 128, 128), f32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssd_chunk_scan" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 128 << 20


@pytest.mark.parametrize("rows", [64, 128])   # the cell's step, the widest
def test_ssd_decode_kernel_compiles_at_published_widths(one_chip, rows):
    """A decode step's one token a row over the WHOLE slot state of the
    deployment (5 layers x 130 rows x 4 MB = 2.7 GB), donated: the state
    is aliased through the kernel and no copy of it, whole or a layer of
    it, is made around it."""
    import re

    from evam_tpu.ops import pallas_ssd, slot_rows

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32 = jnp.float32
    tile = slot_rows.tiled(3 * 10240)
    compiled = jax.jit(pallas_ssd.decode_rows, donate_argnums=(9, 10)).lower(
        s((), jnp.int32), s((rows,), jnp.int32), s((rows,), jnp.bool_),
        s((rows, 128), f32), s((128,), f32), s((rows, 8192)),
        s((rows, 1024)), s((rows, 1024)), s((rows, *tile)),
        s((5, 130, 64, 128, 128), f32), s((5, 130, *tile))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssd_decode_rows" in text
    assert not re.search(r"= f32\[(5,)?130,64,128,128\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("m", [11264, 1408])   # a chunk's 512 x 22, a step's
def test_grouped_relu2_kernel_compiles_at_published_widths(one_chip, m):
    """An expert of TWO matrices in the latent: up [64, 1024, 2688] under
    ``relu^2`` in one call, down [64, 2688, 1024] in the next, out of the
    five layers' stacks (1.76 GB each) by a prefetched layer id, over all
    ``T x 22`` sorted assignments of which an eighth is held."""
    from evam_tpu.ops import pallas_grouped as pg

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(rows, up, down, sizes, l):
        return pg.product(pg.relu2(rows, up, sizes, l), down, sizes, l)

    compiled = jax.jit(layer).lower(
        s((m, 1024)), s((5, 64, 1024, 2688)), s((5, 64, 2688, 1024)),
        s((64,), jnp.int32), s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "expert_up" in text and "expert_down" in text
    assert "expert_gate_up" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_nemotron_step_programs_compile_at_published_widths(one_chip,
                                                            monkeypatch,
                                                            program):
    """Both step programs of the Nemotron-H family at the deployment's
    sizes: the eleven blocks in one loop body of five trips (a Mamba-2
    mixer, the attention block under a ``cond`` in one of them, an expert
    layer), so each kernel is in its program ONCE; the 2.7 GB of slot state
    pass the loop in place (no copy of the state, whole or a layer of it,
    around ``ssd_decode_rows`` or the chunk's row writes), the 3.5 GB of
    stacked expert tensors are read where they lie, and a decode row's own
    pages (393 KB) go through XLA, as Jamba's."""
    import re

    from evam_tpu.models.lm import common, nemotron_h as lm
    from evam_tpu.models.lm.presets import PRESETS

    monkeypatch.setattr(common, "TARGET_TPU", True)
    cfg = lm.Config.from_dict(PRESETS["nemotron3_super_ep8"])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: s(a.shape, a.dtype),
                          jax.eval_shape(lambda: lm.make_params(cfg)))
    assert params["moe"]["expert_up"].shape == (5, 64, 1024, 2688)
    compiled, state = _compile_step(lm, cfg, params, one_chip, program,
                                    traced_prefix=True)
    text = compiled.as_text()
    for name, n in (("expert_up", 1), ("expert_down", 1),
                    ("ssd_decode_rows", int(program == "decode")),
                    ("ssd_chunk_scan", int(program == "prefill")),
                    ("attn_chunk_attention", int(program == "prefill")),
                    ("attn_decode_pages", 0), ("expert_gate_up", 0)):
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == n, name
    assert "ragged" not in text
    assert state["ssm"].shape == (5, 130, 64, 128, 128)
    for shape in ("5,130,64,128,128", "130,64,128,128", "5,64,1024,2688",
                  "64,1024,2688", "5,64,2688,1024", "64,2688,1024"):
        assert not re.search(rf"= \w+\[{shape}\]\S* copy\(", text), shape
    assert compiled.memory_analysis().temp_size_in_bytes < {
        "decode": 32, "prefill": 256}[program] << 20


def _power_state(s, rows, layers=5, heads=8, d=128):
    from evam_tpu.ops.pallas_power import expanded

    return (s((layers, rows, heads, expanded(d), d), jnp.float32),
            s((layers, rows, heads, d // 2 + 1, d), jnp.float32))


@pytest.mark.parametrize("tokens,segments", [
    (512, 8),    # a prefill chunk of the deployment
    (128, 2),    # a short chunk
])
def test_pow_chunk_scan_kernel_compiles_at_published_widths(
        one_chip, tokens, segments):
    """Brumby's chunk kernel at the published widths: 8 key-value heads of
    5 query heads of 128, a state of 8256 x 128 a head read from and
    written to its slot row by prefetched scalars, the whole 5.8 GB of slot
    state aliased in and out."""
    from evam_tpu.ops.pallas_power import chunk_scan

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    heads, group, d = 8, 5, 128
    compiled = jax.jit(chunk_scan, donate_argnums=(8, 9)).lower(
        s((), jnp.int32), s((tokens, heads, group, d)),
        s((tokens, heads, d)), s((tokens, heads, d)),
        s((tokens, heads), jnp.float32), s((tokens,), jnp.int32),
        s((segments,), jnp.int32), s((segments,), jnp.int32),
        *_power_state(s, 34)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "pow_chunk_scan" in text
    # no second copy of the state beside the donated one
    assert compiled.memory_analysis().temp_size_in_bytes < 128 << 20


@pytest.mark.parametrize("rows", [4, 32])   # the least bucket, the slots
def test_pow_decode_kernel_compiles_at_published_widths(one_chip, rows):
    """The fifth body on ops/slot_rows.py's addressing, a grid of (step
    row, key-value head): the whole slot state goes in and comes out in
    place."""
    from evam_tpu.ops.pallas_power import decode_rows

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    heads, group, d = 8, 5, 128
    compiled = jax.jit(decode_rows, donate_argnums=(7, 8)).lower(
        s((), jnp.int32), s((rows,), jnp.int32), s((rows,), jnp.bool_),
        s((rows, heads, group, d)), s((rows, heads, d)),
        s((rows, heads, d)), s((rows, heads), jnp.float32),
        *_power_state(s, 34)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "pow_decode_rows" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_brumby_step_programs_compile_at_published_widths(one_chip,
                                                          monkeypatch,
                                                          program):
    """Both step programs of the Brumby family at the published widths
    (here over 128 slots, 22 GB of state that no chip holds: the compiler
    lays out what it is described): the five layers in ONE loop, so each
    kernel is in its program once; the slot state passes the loop in place
    (no copy of it, whole or a layer of it), and no page cache exists."""
    import re

    from evam_tpu.models.lm import brumby as lm, common
    from evam_tpu.models.lm.presets import PRESETS

    monkeypatch.setattr(common, "TARGET_TPU", True)
    cfg = lm.Config.from_dict(PRESETS["brumby_14b_pp8"])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: s(a.shape, a.dtype),
                          jax.eval_shape(lambda: lm.make_params(cfg)))
    assert params["layers"]["mlp_gate"].shape == (5, 5120, 17408)
    # the engine's rows at the slots it derives: 32 + the null + the snapshot
    monkeypatch.setattr(
        lm, "state_shapes", lambda cfg, n, p, slots, real=lm.state_shapes:
        real(cfg, n, p, 32))
    compiled, state = _compile_step(lm, cfg, params, one_chip, program,
                                    traced_prefix=True)
    text = compiled.as_text()
    for name, n in (("pow_decode_rows", int(program == "decode")),
                    ("pow_chunk_scan", int(program == "prefill"))):
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == n, name
    assert set(state) == {"pow", "pow_z"}
    assert state["pow"].shape == (5, 34, 8, 8256, 128)
    for shape in ("5,34,8,8256,128", "34,8,8256,128", "5,5120,17408",
                  "5,17408,5120"):
        assert not re.search(rf"= \w+\[{shape}\]\S* copy\(", text), shape
    assert compiled.memory_analysis().temp_size_in_bytes < {
        "decode": 64, "prefill": 512}[program] << 20
