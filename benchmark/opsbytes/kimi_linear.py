"""Operations and compulsory bytes of the generate engine's device steps for
the Kimi-Linear configuration, from shapes alone (``shapes.model``) and from
what the engine counted (steps, tokens, prompts, cache rows read,
assignments to held experts).

Everything is a FLOOR, so that a roofline share built on it can read low
and never over 100 %: 2 operations per multiply-accumulate of every matrix
product the mathematics needs; norms, the convolutions' silu, softplus, exp,
sigmoid, softmax, routing, sorting and sampling are left out. Bytes are
what no schedule avoids: the weights a step touches, once per step (an
expert's only where an assignment can have reached it); each live row's
slot state, read and written; the cache rows read, once each, THE SHARED
PREFIX'S ONCE A STEP (the decode program reads them in one pass for all its
rows); the new cache rows written. Activations are not counted.

Per token and layer (h hidden, W = KDA heads x head dim, R the gates' rank,
K the convolutions' taps; MLA: a heads, r = kv_lora_rank, p = rope dim):
  KDA     q, k, v 3 x 2 h W, o 2 W h, the two gates 2 x 2 (h R + R W), the
          write strength 2 h heads, three convolutions 2 K 3 W, and the
          recurrence (``scan_ops_and_bytes``)
  MLA     q, kv_a, o; W_uk into the query and W_uv out of the latent (2 a
          128 r each); per cached row read 2 a (2 r + p); in prefill W_kvb
          for the chunk's own tokens and, within a prompt of L tokens, on
          average L/2 materialised pairs of 2 a (192 + 128)
  ffn     layer 1: 3 x 2 h 9216; later layers: router, the shared expert on
          every token, one expert (3 x 2 h 1024) per ASSIGNMENT routed to a
          held expert
and once per sampled row the head over the held vocabulary.

``lm_roofline.py`` hands ``steps`` the model's shapes and the engine's
counts, and the counts give a decode row's WHOLE context; the length of the
shared prefix, which the floor needs to count those rows once a step, is
``shapes.model["engine_prefix_tokens"]`` (the configuration file restates
it from ``shapes.engine`` for this reader). The held experts actually hit
cannot reach ``steps`` through those keywords: the experts are counted as
``min(assignments, steps x expert layers x held)``, up to a tenth high at
two assignments a token and 64 rows (``lm_held_experts_hit_share`` says by
how much).
"""

from __future__ import annotations


def _model(m: dict) -> dict:
    la = m["linear_attn_config"]
    h = m["hidden_size"]
    layers, dense = m["num_hidden_layers"], m["first_k_dense_replace"]
    kda = sum(1 for i in la["kda_layers"] if i <= layers)
    mla = sum(1 for i in la["full_attn_layers"] if i <= layers)
    kh, kd, taps = la["num_heads"], la["head_dim"], la[
        "short_conv_kernel_size"]
    w, rk = kh * kd, m["kda_gate_rank"]
    kda_w = 4 * h * w + 2 * (h * rk + rk * w) + h * kh
    a, r, p = m["num_attention_heads"], m["kv_lora_rank"], m[
        "qk_rope_head_dim"]
    nope, vd = m["qk_nope_head_dim"], m["v_head_dim"]
    proj = h * a * (nope + p) + h * (r + p) + a * vd * h
    kvb = r * a * (nope + vd)
    return dict(
        h=h, layers=layers, dense=dense, moe=layers - dense, kda=kda,
        mla=mla, kh=kh, kd=kd, taps=taps, w=w, kda_w=kda_w, a=a, r=r, p=p,
        nope=nope, vd=vd, proj=proj, kvb=kvb, mla_w=proj + kvb,
        held=m["experts_held"],
        shared_w=3 * h * m["num_shared_experts"] * m["moe_intermediate_size"],
        expert_w=3 * h * m["moe_intermediate_size"],
        dense_w=3 * h * m["intermediate_size"],
        router_w=h * m["num_experts"], vocab=m["vocab_held"], latent=r + p,
        prefix=m["engine_prefix_tokens"],
        # a slot's state in one KDA layer: the float32 matrices and the
        # three convolutions' K - 1 bfloat16 inputs
        slot_bytes=4 * kh * kd * kd + 2 * (taps - 1) * 3 * w)


def parameters(m: dict) -> int:
    """Matrix parameters held on this chip (gains, the convolutions,
    ``A_log``, ``dt_bias`` and the selection bias left out)."""
    g = _model(m)
    return (2 * g["vocab"] * g["h"] + g["kda"] * g["kda_w"]
            + g["mla"] * g["mla_w"] + g["dense"] * g["dense_w"]
            + g["moe"] * (g["shared_w"] + g["router_w"]
                          + g["held"] * g["expert_w"]))


def scan_ops_and_bytes(m: dict, tokens: int) -> dict:
    """The recurrence of ONE KDA layer over ``tokens`` tokens (the kernel
    ``kda_delta_rule``). Per token and head over the [d, d] state: the
    decay, ``k^T S`` (2), the rank-one update (2) and ``S^T q`` (2): 7 d d
    operations (exp left out). Bytes: ``q``, ``k``, ``v`` (bfloat16), the
    log decay (float32) and the write strength in, ``o`` (bfloat16) out;
    the state costs no HBM bytes inside a chunk."""
    g = _model(m)
    return {"flops": 7.0 * tokens * g["kh"] * g["kd"] * g["kd"],
            "bytes": float(tokens * (g["w"] * (2 + 2 + 2 + 4 + 2)
                                     + 4 * g["kh"]))}


def steps(m: dict, *, prefill_steps: int, prefill_tokens: int,
          prefill_prompts: int, prefill_rows: int, decode_steps: int,
          decode_tokens: int, decode_rows: int, held_assignments: int,
          sampled_rows: int) -> dict:
    """Operations and bytes of the counted steps together (the keyword
    names are ``readers/lm_roofline.py``'s). The ``rows`` are per layer
    THAT HAS a cache (the MLA layers), as the engine counts them
    (``evam_generate_latent_rows_read_total{kind}``): a decode row's whole
    context, the prefix among it; a chunk's cached rows once a chunk.
    ``held_assignments`` is summed over the expert layers. Slot state:
    every decode token's, and every prompt's once (a prompt that continues
    in a second chunk moves its state twice: a floor)."""
    g = _model(m)
    tokens = prefill_tokens + decode_tokens
    flops = tokens * g["kda"] * (2 * g["kda_w"] + 2 * g["taps"] * 3 * g["w"])
    flops += g["kda"] * scan_ops_and_bytes(m, tokens)["flops"]
    flops += tokens * g["mla"] * 2 * (g["proj"]
                                      + 2 * g["a"] * g["nope"] * g["r"])
    pair = 2 * g["a"] * (2 * g["r"] + g["p"])  # one query, one cached row
    flops += g["mla"] * pair * decode_rows
    if prefill_steps:
        flops += (g["mla"] * pair * (prefill_rows / prefill_steps)
                  * prefill_tokens)
    flops += prefill_tokens * g["mla"] * 2 * g["kvb"]
    if prefill_prompts:
        mean_len = prefill_tokens / prefill_prompts
        flops += (prefill_tokens * g["mla"] * (mean_len / 2)
                  * 2 * g["a"] * (g["nope"] + g["p"] + g["vd"]))
    flops += tokens * g["dense"] * 2 * g["dense_w"]
    flops += tokens * g["moe"] * 2 * (g["shared_w"] + g["router_w"])
    flops += held_assignments * 2 * g["expert_w"]
    flops += sampled_rows * 2 * g["h"] * g["vocab"]

    n_steps = prefill_steps + decode_steps
    # an expert's weights are read by a step only if an assignment
    # reaches it: at most one expert per assignment, at most all held
    expert_reads = min(held_assignments, n_steps * g["moe"] * g["held"])
    weight_values = (n_steps * (g["kda"] * g["kda_w"] + g["mla"] * g["mla_w"]
                                + g["dense"] * g["dense_w"]
                                + g["moe"] * (g["shared_w"] + g["router_w"])
                                + g["h"] * g["vocab"])
                     + expert_reads * g["expert_w"] + tokens * g["h"])
    # a decode step reads the prefix once for all its rows
    own_rows = max(0, decode_rows - decode_tokens * g["prefix"])
    shared_rows = decode_steps * g["prefix"] if decode_tokens else 0
    cache_values = g["mla"] * g["latent"] * (
        own_rows + shared_rows + prefill_rows + tokens)
    state_bytes = (2 * g["kda"] * g["slot_bytes"]
                   * (decode_tokens + prefill_prompts))
    return {"flops": float(flops),
            "bytes": 2.0 * (weight_values + cache_values) + state_bytes}


def ops_and_bytes(shapes: dict, batch: int) -> dict:
    """One decode step over ``batch`` rows at the longest context a
    sequence reaches, every held expert touched: the figure a reader of
    the configuration wants for sizing."""
    m, e = shapes["model"], shapes["engine"]
    ctx = e["prefix_tokens"] + 16 + 8 * e["max_objects"] + e["max_new_tokens"]
    g = _model(m)
    return steps(m, prefill_steps=0, prefill_tokens=0, prefill_prompts=0,
                 prefill_rows=0, decode_steps=1, decode_tokens=batch,
                 decode_rows=batch * ctx,
                 held_assignments=g["moe"] * max(
                     g["held"], batch * m["num_experts_per_token"]
                     * g["held"] // m["num_experts"]),
                 sampled_rows=batch)
