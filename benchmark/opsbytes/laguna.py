"""Operations and compulsory bytes of the generate engine's device steps for
the Laguna configuration, from shapes alone (``shapes.model``) and from what
the engine counted (steps, tokens, prompts, cache rows read, assignments to
held experts and, where the reader has it, the held experts hit).

Everything is a FLOOR, so that a roofline share built on it can read low
and never over 100 %: 2 operations per multiply-accumulate of every matrix
product the mathematics needs; norms, the rotation, softmax, the gates'
sigmoid, routing, sorting and sampling are left out. Bytes are what no
schedule avoids: the weights a step touches, once per step (an expert's
only where an assignment reached it); the cache rows read, once each, THE
SHARED PREFIX'S ONCE A STEP and IN A WINDOW LAYER ONLY THE WINDOW'S; the
new cache rows written. Activations are not counted.

Per token and layer (h hidden, a_i query heads of d over g key-value heads;
a_i = 48 in a full layer, 64 in a window layer):
  attn    q and o 2 x 2 h a_i d, k and v 2 x 2 h g d, the gate 2 h a_i;
          per VISIBLE cached row 2 x 2 a_i d: a full layer's token sees
          every earlier row, a window layer's the last ``sliding_window``
          positions (every context here is longer than the window, so
          that is ``sliding_window`` rows a token, its own among them)
  ffn     layer 0: 3 x 2 h 8192; later layers: the router, the shared
          expert (3 x 2 h 512) and one expert (3 x 2 h 512) per ASSIGNMENT
          (all of them are held: 8 a token)
and once per sampled row the head over the whole vocabulary (untied: the
embedding is read by the row, the head whole).

``lm_roofline.py`` hands ``steps`` the model's shapes and the engine's
counts, and the counts give a decode row's WHOLE context (what a full
layer reads); the length of the shared prefix is
``shapes.model["engine_prefix_tokens"]`` (the configuration file restates
it from ``shapes.engine`` for this reader). Through those keywords the
experts read are counted as ``min(assignments, steps x expert layers x
held)``: a 64-row step's 512 assignments a layer reach about 222 of the
256, so that bound reads 13 % of the expert bytes too many in decode.
``readers/lm_roofline_hit.py`` hands ``experts_hit``
(``evam_moe_held_experts_hit_total``: per step and layer the experts that
received an assignment) and the count is then what is read.
"""

from __future__ import annotations

FULL, WINDOW = "full_attention", "sliding_attention"


def _model(m: dict) -> dict:
    h, n = m["hidden_size"], m["num_hidden_layers"]
    kinds = m["layer_types"][:n]
    heads = m["num_attention_heads_per_layer"][:n]
    mlps = m["mlp_layer_types"][:n]
    g, d = m["num_key_value_heads"], m["head_dim"]
    a = {k: next(x for x, kind in zip(heads, kinds) if kind == k)
         for k in (FULL, WINDOW)}

    def attn_w(a_i):
        return 2 * h * a_i * d + 2 * h * g * d + h * a_i

    return dict(
        h=h, layers=n, full=kinds.count(FULL), win=kinds.count(WINDOW),
        dense=mlps.count("dense"), moe=mlps.count("sparse"),
        a_full=a[FULL], a_win=a[WINDOW], d=d, kv=2 * g * d,
        attn_w=kinds.count(FULL) * attn_w(a[FULL])
        + kinds.count(WINDOW) * attn_w(a[WINDOW]),
        held=m["experts_held"], expert_w=3 * h * m["moe_intermediate_size"],
        shared_w=3 * h * m["shared_expert_intermediate_size"],
        dense_w=3 * h * m["intermediate_size"],
        router_w=h * m["num_experts"], vocab=m["vocab_held"],
        prefix=m["engine_prefix_tokens"], window=m["sliding_window"])


def parameters(m: dict) -> int:
    """Matrix parameters held on this chip (gains and the selection bias
    left out; the embedding and the untied head both)."""
    g = _model(m)
    return (2 * g["vocab"] * g["h"] + g["attn_w"] + g["dense"] * g["dense_w"]
            + g["moe"] * (g["router_w"] + g["shared_w"]
                          + g["held"] * g["expert_w"]))


def scan_ops_and_bytes(m: dict, tokens: int) -> dict:
    """The chunk attention (the kernel ``attn_chunk_attention``; the name
    is what ``readers/trace_op_share.py`` asks a configuration for) over
    ``tokens`` prefill tokens in the MEAN layer of the stage: the reader
    multiplies by the number of layers, and the two kinds differ. Every
    token and query head scores and weighs at least the rows of the shared
    prefix it can see (2 x 2 d operations a pair): all of them in a full
    layer, the window's (its own row among them: a floor of ``window - 1``
    of the prefix's is ``window`` in all) in a window layer; a full
    layer's own and continued rows are left out: a floor. Bytes: the
    queries in and the weighted values out."""
    g = _model(m)
    seen = min(g["window"], g["prefix"])
    pairs = (g["full"] * g["a_full"] * g["prefix"]
             + g["win"] * g["a_win"] * seen)
    heads = g["full"] * g["a_full"] + g["win"] * g["a_win"]
    return {"flops": float(tokens) * pairs * 4 * g["d"] / g["layers"],
            "bytes": float(tokens * 2 * 2 * heads * g["d"]) / g["layers"]}


def steps(m: dict, *, prefill_steps: int, prefill_tokens: int,
          prefill_prompts: int, prefill_rows: int, decode_steps: int,
          decode_tokens: int, decode_rows: int, held_assignments: int,
          sampled_rows: int, experts_hit: int | None = None) -> dict:
    """Operations and bytes of the counted steps together (the keyword
    names are ``readers/lm_roofline.py``'s). The ``rows`` are what a FULL
    layer reads, as the engine counts them
    (``evam_generate_latent_rows_read_total{kind}``): a decode row's whole
    context, the prefix among it; a chunk's cached rows once a chunk. A
    window layer's are derived: ``sliding_window`` visible rows a token
    (every context is longer), of a chunk's cached rows the ``window - 1``
    before its first token, of a decode row's own rows at most the
    window's. ``held_assignments`` is summed over the expert layers;
    ``experts_hit`` (None: not known) likewise."""
    g = _model(m)
    tokens = prefill_tokens + decode_tokens
    seen = min(g["window"], g["prefix"] + 1)
    pair_full, pair_win = (2 * 2 * a * g["d"]
                           for a in (g["a_full"], g["a_win"]))
    flops = tokens * 2 * g["attn_w"]
    flops += g["full"] * pair_full * decode_rows
    flops += g["win"] * pair_win * seen * tokens
    if prefill_steps:
        flops += (g["full"] * pair_full * (prefill_rows / prefill_steps)
                  * prefill_tokens)
    if prefill_prompts:
        mean_len = prefill_tokens / prefill_prompts
        flops += prefill_tokens * g["full"] * (mean_len / 2) * pair_full
    flops += tokens * g["dense"] * 2 * g["dense_w"]
    flops += tokens * g["moe"] * 2 * (g["router_w"] + g["shared_w"])
    flops += held_assignments * 2 * g["expert_w"]
    flops += sampled_rows * 2 * g["h"] * g["vocab"]

    n_steps = prefill_steps + decode_steps
    # an expert's weights are read by a step only if an assignment
    # reaches it: at most one expert per assignment, at most all held
    expert_reads = min(held_assignments, n_steps * g["moe"] * g["held"])
    if experts_hit is not None:
        expert_reads = min(expert_reads, experts_hit)
    weight_values = (n_steps * (g["attn_w"] + g["dense"] * g["dense_w"]
                                + g["moe"] * (g["router_w"] + g["shared_w"])
                                + g["h"] * g["vocab"])
                     + expert_reads * g["expert_w"] + tokens * g["h"])
    # a decode step reads the prefix once for all its rows; a window layer
    # of it what the row with the fewest own rows still sees (the mean's,
    # here: a floor where the rows' lengths differ)
    own_rows = max(0, decode_rows - decode_tokens * g["prefix"])
    shared_rows = decode_steps * g["prefix"] if decode_tokens else 0
    full_rows = own_rows + shared_rows + prefill_rows
    own_seen = min(own_rows, decode_tokens * g["window"])
    shared_seen = (decode_steps * max(
        0, min(g["prefix"], g["window"] - own_rows // decode_tokens))
        if decode_tokens else 0)
    win_rows = own_seen + shared_seen + min(
        prefill_rows, prefill_steps * (g["window"] - 1))
    cache_values = g["kv"] * (g["full"] * full_rows + g["win"] * win_rows
                              + g["layers"] * tokens)
    return {"flops": float(flops),
            "bytes": 2.0 * (weight_values + cache_values)}


def ops_and_bytes(shapes: dict, batch: int) -> dict:
    """One decode step over ``batch`` rows at the longest context a
    sequence reaches, every expert an assignment can reach touched: the
    figure a reader of the configuration wants for sizing."""
    m, e = shapes["model"], shapes["engine"]
    ctx = e["prefix_tokens"] + 16 + 8 * e["max_objects"] + e["max_new_tokens"]
    g = _model(m)
    return steps(m, prefill_steps=0, prefill_tokens=0, prefill_prompts=0,
                 prefill_rows=0, decode_steps=1, decode_tokens=batch,
                 decode_rows=batch * ctx,
                 held_assignments=g["moe"] * batch * m["num_experts_per_tok"]
                 * g["held"] // m["num_experts"],
                 sampled_rows=batch)
