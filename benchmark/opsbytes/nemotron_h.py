"""Operations and compulsory bytes of the generate engine's device steps for
the Nemotron-H configuration, from shapes alone (``shapes.model``) and from
what the engine counted (steps, tokens, prompts, cache rows read,
assignments to held experts and, where the reader has it, the held experts
hit).

Everything is a FLOOR, so that a roofline share built on it can read low
and never over 100 %: 2 operations per multiply-accumulate of every matrix
product the mathematics needs; norms, the convolution's silu, softplus, the
decay's exponentials, softmax, routing, sorting and sampling are left out.
Bytes are what no schedule avoids: the weights a step touches, once per
step (an expert's only where an assignment reached it); each live
sequence's slot state, read and written once per decode token and once per
prompt; the cache rows read, once each, THE SHARED PREFIX'S ONCE A STEP; the
new cache rows written. Activations are not counted.

Per token (h hidden; H heads of P over a state N in G groups, c = H P, w =
c + 2 G N the convolution's channels; a query heads of d over g key-value
heads; a latent l, experts of i, a shared expert of s):
  M  (x 5)   in_proj and dt 2 h (c + w + H), the convolution 2 k w, out_proj
             2 c h; the recurrence: a decode token 2 x 2 H P N (the state
             updated and read out), a prefill token ``scan_ops_and_bytes``
  *  (x 1)   q and o 2 x 2 h a d, k and v 2 x 2 h g d; per visible cached
             row 2 x 2 a d
  E  (x 5)   the router 2 h 512, the two latent projections 2 x 2 h l, the
             shared expert 2 x 2 h s, and one expert (2 x 2 l i) per HELD
             assignment
and once per sampled row the head over the held vocabulary (untied: the
embedding is read by the row, the head whole).

``readers/lm_roofline_hit.py`` hands ``experts_hit``
(``evam_moe_held_experts_hit_total``: per step and layer the held experts
that received an assignment); without it the experts read are bounded by
``min(assignments, steps x expert layers x held)``.
"""

from __future__ import annotations


def _model(m: dict) -> dict:
    n = m["num_hidden_layers"]
    pattern = m["hybrid_override_pattern"][:n]
    h = m["hidden_size"]
    heads, p, s = m["mamba_num_heads"], m["mamba_head_dim"], m["ssm_state_size"]
    c = heads * p
    w = c + 2 * m["n_groups"] * s
    a, g, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    lat, inter = m["moe_latent_size"], m["moe_intermediate_size"]
    return dict(
        h=h, mamba=pattern.count("M"), attn=pattern.count("*"),
        moe=pattern.count("E"), heads=heads, p=p, n=s, c=c, w=w,
        per=heads // m["n_groups"], block=m["chunk_size"],
        k=m["conv_kernel"],
        mamba_w=h * (c + w + heads) + m["conv_kernel"] * w + c * h,
        a=a, d=d, kv=2 * g * d, attn_w=2 * h * a * d + 2 * h * g * d,
        held=m["experts_held"], expert_w=2 * lat * inter,
        moe_w=(h * m["n_routed_experts"] + 2 * h * lat
               + 2 * h * m["moe_shared_expert_intermediate_size"]),
        vocab=m["vocab_held"], prefix=m["engine_prefix_tokens"])


def parameters(m: dict) -> int:
    """Every parameter held on this chip: the matrices, the convolution,
    the per-head vectors (``dt_bias``, ``A_log``, ``D``), the selection
    bias and every gain; the embedding and the untied head both. It is the
    family's ``param_count``."""
    g = _model(m)
    h = g["h"]
    mamba = g["mamba_w"] + g["w"] + 3 * g["heads"] + g["c"] + h
    moe = (g["moe_w"] + m["n_routed_experts"] + h
           + g["held"] * g["expert_w"])
    return (2 * g["vocab"] * h + h + g["mamba"] * mamba
            + g["attn"] * (g["attn_w"] + h) + g["moe"] * moe)


def scan_ops_and_bytes(m: dict, tokens: int) -> dict:
    """The recurrence of ONE Mamba-2 layer over ``tokens`` prefill tokens
    (the kernel ``ssd_chunk_scan``; the name is what
    ``readers/trace_op_share.py`` asks a configuration for), in its
    chunkwise form over blocks of ``chunk_size``: per token and head the
    carried state read out (N P multiply-adds), the block's causal scores
    times ``x`` (chunk_size P / 2 at least: a token sees half a block in
    the mean), the state's update (N P), and a 16th of its group's ``C
    B^T`` (chunk_size N / 2 over the group's heads). Bytes: ``x``, ``B``,
    ``C`` (bfloat16) and ``dt`` (float32) in and ``y`` (bfloat16 at least)
    out; ``z`` gates ``y`` behind the kernel and is not its operand; the
    state costs no HBM bytes inside a chunk."""
    g = _model(m)
    macs = (2 * g["n"] * g["p"] + g["block"] * g["p"] // 2
            + g["block"] * g["n"] // (2 * g["per"]))
    return {"flops": 2.0 * tokens * g["heads"] * macs,
            "bytes": float(tokens * (2 * g["w"] + 4 * g["heads"]
                                     + 2 * g["c"]))}


def steps(m: dict, *, prefill_steps: int, prefill_tokens: int,
          prefill_prompts: int, prefill_rows: int, decode_steps: int,
          decode_tokens: int, decode_rows: int, held_assignments: int,
          sampled_rows: int, experts_hit: int | None = None) -> dict:
    """Operations and bytes of the counted steps together (the keyword
    names are ``readers/lm_roofline.py``'s). The ``rows`` are per layer
    THAT HAS a cache, as the engine counts them
    (``evam_generate_latent_rows_read_total{kind}``): a decode row's whole
    context, the prefix among it; a chunk's cached rows once a chunk. Slot
    state: every decode token's, read and written, and every prompt's once
    (a prompt that continues in a second chunk moves its state twice: a
    floor). ``held_assignments`` is summed over the expert layers;
    ``experts_hit`` (None: not known) likewise."""
    g = _model(m)
    tokens = prefill_tokens + decode_tokens
    flops = tokens * g["mamba"] * 2 * g["mamba_w"]
    flops += g["mamba"] * scan_ops_and_bytes(m, prefill_tokens)["flops"]
    flops += decode_tokens * g["mamba"] * 4 * g["heads"] * g["p"] * g["n"]
    flops += tokens * g["attn"] * 2 * g["attn_w"]
    pair = 2 * 2 * g["a"] * g["d"]
    flops += g["attn"] * pair * decode_rows
    if prefill_steps:
        flops += (g["attn"] * pair * (prefill_rows / prefill_steps)
                  * prefill_tokens)
    if prefill_prompts:
        mean_len = prefill_tokens / prefill_prompts
        flops += prefill_tokens * g["attn"] * (mean_len / 2) * pair
    flops += tokens * g["moe"] * 2 * g["moe_w"]
    flops += held_assignments * 2 * g["expert_w"]
    flops += sampled_rows * 2 * g["h"] * g["vocab"]

    n_steps = prefill_steps + decode_steps
    # an expert's weights are read by a step only if an assignment
    # reaches it: at most one expert per assignment, at most all held
    expert_reads = min(held_assignments, n_steps * g["moe"] * g["held"])
    if experts_hit is not None:
        expert_reads = min(expert_reads, experts_hit)
    weight_values = (n_steps * (g["mamba"] * g["mamba_w"]
                                + g["attn"] * g["attn_w"]
                                + g["moe"] * g["moe_w"]
                                + g["h"] * g["vocab"])
                     + expert_reads * g["expert_w"] + tokens * g["h"])
    # a decode step reads the prefix once for all its rows
    own_rows = max(0, decode_rows - decode_tokens * g["prefix"])
    shared_rows = decode_steps * g["prefix"] if decode_tokens else 0
    cache_values = g["attn"] * g["kv"] * (own_rows + shared_rows
                                          + prefill_rows + tokens)
    # the state in float32, the convolution's inputs in bfloat16: in and
    # out, a decode token and a prompt
    moves = decode_tokens + prefill_prompts
    state_bytes = moves * g["mamba"] * 2 * (
        4 * g["heads"] * g["p"] * g["n"] + 2 * (g["k"] - 1) * g["w"])
    return {"flops": float(flops),
            "bytes": 2.0 * (weight_values + cache_values) + state_bytes}


def ops_and_bytes(shapes: dict, batch: int) -> dict:
    """One decode step over ``batch`` rows at the longest context a
    sequence reaches, its share of the assignments held under even routing
    and every expert they can reach touched: the figure a reader of the
    configuration wants for sizing."""
    m, e = shapes["model"], shapes["engine"]
    ctx = e["prefix_tokens"] + 16 + 8 * e["max_objects"] + e["max_new_tokens"]
    g = _model(m)
    return steps(m, prefill_steps=0, prefill_tokens=0, prefill_prompts=0,
                 prefill_rows=0, decode_steps=1, decode_tokens=batch,
                 decode_rows=batch * ctx,
                 held_assignments=g["moe"] * batch * m["num_experts_per_tok"]
                 * g["held"] // m["n_routed_experts"],
                 sampled_rows=batch)
