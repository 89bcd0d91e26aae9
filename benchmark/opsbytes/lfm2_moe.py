"""Operations and compulsory bytes of the generate engine's device steps for
the LFM2-MoE configuration, from shapes alone (``shapes.model``) and from
what the engine counted (steps, tokens, prompts, cache rows read,
assignments to held experts).

Everything is a FLOOR, so that a roofline share built on it can read low
and never over 100 %: 2 operations per multiply-accumulate of every matrix
product the mathematics needs; norms, the convolution's three taps and two
gates, the rotation, softmax, routing, sorting and sampling are left out.
Bytes are what no schedule avoids: the weights a step touches, once per
step (an expert's only where an assignment can have reached it); each live
row's taps, read and written; the cache rows read, once each, THE SHARED
PREFIX'S ONCE A STEP (the decode program reads them in one pass for all its
rows); the new cache rows written. Activations are not counted.

Per token and layer (h hidden, a query heads of d over g key-value heads):
  conv    W_in 2 h 3h, W_out 2 h h
  attn    q and o 2 x 2 h h, k and v 2 x 2 h g d; per cached row read 2 x 2
          a d; within a prompt of L tokens on average L/2 own rows
  ffn     layers 0, 1: 3 x 2 h 7168; later layers: the router, one expert
          (3 x 2 h 1792) per ASSIGNMENT routed to a held expert
and once per sampled row the head over the held vocabulary (the embedding,
tied).

``lm_roofline.py`` hands ``steps`` the model's shapes and the engine's
counts, and the counts give a decode row's WHOLE context; the length of the
shared prefix, which the floor needs to count those rows once a step, is
``shapes.model["engine_prefix_tokens"]`` (the configuration file restates
it from ``shapes.engine`` for this reader). The held experts actually hit
cannot reach ``steps`` through those keywords: the experts are counted as
``min(assignments, steps x expert layers x held)``, which is what a 64-row
step reads here (``lm_held_experts_hit_share`` near 100 %).
"""

from __future__ import annotations


def _model(m: dict) -> dict:
    h = m["hidden_size"]
    kinds = m["layer_types"][:m["num_hidden_layers"]]
    layers, dense = len(kinds), m["num_dense_layers"]
    a, g = m["num_attention_heads"], m["num_key_value_heads"]
    d = h // a
    return dict(
        h=h, layers=layers, dense=dense, moe=layers - dense,
        conv=kinds.count("conv"), attn=kinds.count("full_attention"),
        taps=m["conv_L_cache"], a=a, d=d, kv=2 * g * d,
        conv_w=h * 3 * h + h * h, attn_w=2 * h * a * d + 2 * h * g * d,
        held=m["experts_held"],
        expert_w=3 * h * m["moe_intermediate_size"],
        dense_w=3 * h * m["intermediate_size"],
        router_w=h * m["num_experts"], vocab=m["vocab_held"],
        prefix=m["engine_prefix_tokens"],
        # a slot's state in one convolution layer: the taps - 1 carried
        # inputs, bfloat16
        slot_bytes=2 * (m["conv_L_cache"] - 1) * h)


def parameters(m: dict) -> int:
    """Matrix parameters held on this chip (gains, the convolutions' taps
    and the selection bias left out; the embedding once: it is the head)."""
    g = _model(m)
    return (g["vocab"] * g["h"] + g["conv"] * g["conv_w"]
            + g["attn"] * g["attn_w"] + g["dense"] * g["dense_w"]
            + g["moe"] * (g["router_w"] + g["held"] * g["expert_w"]))


def scan_ops_and_bytes(m: dict, tokens: int) -> dict:
    """ONE attention layer's chunk attention over ``tokens`` prefill
    tokens (the kernel ``attn_chunk_attention``; the name is what
    ``readers/trace_op_share.py`` asks a configuration for). Every token
    and query head scores and weighs at least the shared prefix's rows (2
    x 2 d operations a pair); its own and continued rows are left out: a
    floor. Bytes: the queries in and the weighted values out."""
    g = _model(m)
    return {"flops": float(tokens) * g["a"] * g["prefix"] * 4 * g["d"],
            "bytes": float(tokens * 2 * 2 * g["a"] * g["d"])}


def steps(m: dict, *, prefill_steps: int, prefill_tokens: int,
          prefill_prompts: int, prefill_rows: int, decode_steps: int,
          decode_tokens: int, decode_rows: int, held_assignments: int,
          sampled_rows: int) -> dict:
    """Operations and bytes of the counted steps together (the keyword
    names are ``readers/lm_roofline.py``'s). The ``rows`` are per layer
    THAT HAS a cache (the attention layers), as the engine counts them
    (``evam_generate_latent_rows_read_total{kind}``): a decode row's whole
    context, the prefix among it; a chunk's cached rows once a chunk.
    ``held_assignments`` is summed over the expert layers. Slot state:
    every decode token's, and every prompt's once (a prompt that continues
    in a second chunk moves its taps twice: a floor)."""
    g = _model(m)
    tokens = prefill_tokens + decode_tokens
    flops = tokens * 2 * (g["conv"] * g["conv_w"] + g["attn"] * g["attn_w"])
    pair = 2 * 2 * g["a"] * g["d"]  # one query, one cached row
    flops += g["attn"] * pair * decode_rows
    if prefill_steps:
        flops += (g["attn"] * pair * (prefill_rows / prefill_steps)
                  * prefill_tokens)
    if prefill_prompts:
        mean_len = prefill_tokens / prefill_prompts
        flops += prefill_tokens * g["attn"] * (mean_len / 2) * pair
    flops += tokens * g["dense"] * 2 * g["dense_w"]
    flops += tokens * g["moe"] * 2 * g["router_w"]
    flops += held_assignments * 2 * g["expert_w"]
    flops += sampled_rows * 2 * g["h"] * g["vocab"]

    n_steps = prefill_steps + decode_steps
    # an expert's weights are read by a step only if an assignment
    # reaches it: at most one expert per assignment, at most all held
    expert_reads = min(held_assignments, n_steps * g["moe"] * g["held"])
    weight_values = (n_steps * (g["conv"] * g["conv_w"]
                                + g["attn"] * g["attn_w"]
                                + g["dense"] * g["dense_w"]
                                + g["moe"] * g["router_w"]
                                + g["h"] * g["vocab"])
                     + expert_reads * g["expert_w"] + tokens * g["h"])
    # a decode step reads the prefix once for all its rows
    own_rows = max(0, decode_rows - decode_tokens * g["prefix"])
    shared_rows = decode_steps * g["prefix"] if decode_tokens else 0
    cache_values = g["attn"] * g["kv"] * (
        own_rows + shared_rows + prefill_rows + tokens)
    state_bytes = (2 * g["conv"] * g["slot_bytes"]
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
                     g["held"], batch * m["num_experts_per_tok"]
                     * g["held"] // m["num_experts"]),
                 sampled_rows=batch)
