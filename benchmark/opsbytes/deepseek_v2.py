"""Operations and compulsory bytes of the generate engine's device steps,
from shapes alone (the configuration's ``shapes.model``) and from what the
engine counted (steps, tokens, cache rows read, assignments to held
experts).

Everything is a FLOOR, so that a roofline share built on it can read low
and never over 100 %: 2 operations per multiply-accumulate of every matrix
product the mathematics needs; norms, rope, softmax, routing, sorting and
sampling are left out. Bytes are what no schedule avoids: the weights a
step touches, once per step; the cache rows read, once each; the new cache
rows written. Activations are not counted.

Per token and layer (h heads, r = kv_lora_rank, p = rope dim):
  projections  q_a, q_b, kv_a, o                      (both kinds of step)
  absorbed     W_uk into the query and W_uv out of the latent: 2 h 128 r
               each, for attention over cached rows
  cached rows  scores over r + p and values over r: 2 h (2 r + p) a row
  prefill      W_kvb for the chunk's own tokens (2 r h 256) and, within a
               prompt of L tokens, on average L/2 materialised pairs of
               2 h (192 + 128)
  mlp          layer 0: 3 x 2 x hidden x 12288; later layers: router,
               shared experts on every token, one expert (3 x 2 x hidden x
               1536) per ASSIGNMENT routed to a held expert
and once per sampled row the head over the held vocabulary.
"""

from __future__ import annotations


def _model(m: dict) -> dict:
    h, hd = m["hidden_size"], m["num_attention_heads"]
    r, p = m["kv_lora_rank"], m["qk_rope_head_dim"]
    nope, vd, qr = m["qk_nope_head_dim"], m["v_head_dim"], m["q_lora_rank"]
    layers = m["num_hidden_layers"]
    dense = m["first_k_dense_replace"]
    moe = layers - dense
    held = m["n_routed_experts"] // m["n_group"]
    proj = h * qr + qr * hd * (nope + p) + h * (r + p) + hd * vd * h
    kvb = r * hd * (nope + vd)
    attn_w = proj + kvb
    shared_w = 3 * h * m["n_shared_experts"] * m["moe_intermediate_size"]
    expert_w = 3 * h * m["moe_intermediate_size"]
    dense_w = 3 * h * m["intermediate_size"]
    router_w = h * m["n_routed_experts"]
    return dict(h=h, hd=hd, r=r, p=p, nope=nope, vd=vd, layers=layers,
                dense=dense, moe=moe, held=held, proj=proj, kvb=kvb,
                attn_w=attn_w, shared_w=shared_w, expert_w=expert_w,
                dense_w=dense_w, router_w=router_w,
                vocab=m["vocab_held"], latent=r + p)


def parameters(m: dict) -> int:
    """Matrix parameters held on this chip (norm gains left out)."""
    g = _model(m)
    return (2 * g["vocab"] * g["h"] + g["layers"] * g["attn_w"]
            + g["dense"] * g["dense_w"]
            + g["moe"] * (g["shared_w"] + g["router_w"]
                          + g["held"] * g["expert_w"]))


def steps(m: dict, *, prefill_steps: int, prefill_tokens: int,
          prefill_prompts: int, prefill_rows: int, decode_steps: int,
          decode_tokens: int, decode_rows: int, held_assignments: int,
          sampled_rows: int) -> dict:
    """Operations and bytes of the counted steps together. The ``rows``
    are per layer, as the engine counts them
    (``evam_generate_latent_rows_read_total{kind}``): a decode row's
    whole context; a chunk's cached rows once a chunk (all its tokens
    attend to them). ``held_assignments`` is summed over the expert
    layers."""
    g = _model(m)
    tokens = prefill_tokens + decode_tokens
    per_token_layer = 2 * (g["proj"]
                           + 2 * g["hd"] * g["nope"] * g["r"])  # absorb in/out
    flops = tokens * g["layers"] * per_token_layer
    pair = 2 * g["hd"] * (2 * g["r"] + g["p"])  # one query, one cached row
    flops += g["layers"] * pair * decode_rows
    if prefill_steps:
        flops += (g["layers"] * pair * (prefill_rows / prefill_steps)
                  * prefill_tokens)
    flops += prefill_tokens * g["layers"] * 2 * g["kvb"]
    if prefill_prompts:
        mean_len = prefill_tokens / prefill_prompts
        flops += (prefill_tokens * g["layers"] * (mean_len / 2)
                  * 2 * g["hd"] * (g["nope"] + g["p"] + g["vd"]))
    flops += tokens * g["dense"] * 2 * g["dense_w"]
    flops += tokens * g["moe"] * 2 * (g["shared_w"] + g["router_w"])
    flops += held_assignments * 2 * g["expert_w"]
    flops += sampled_rows * 2 * g["h"] * g["vocab"]

    n_steps = prefill_steps + decode_steps
    # an expert's weights are read by a step only if an assignment
    # reaches it: at most one expert per assignment, at most all held
    expert_reads = min(held_assignments, n_steps * g["moe"] * g["held"])
    weight_values = (n_steps * (g["layers"] * g["attn_w"]
                                + g["dense"] * g["dense_w"]
                                + g["moe"] * (g["shared_w"] + g["router_w"])
                                + g["h"] * g["vocab"])
                     + expert_reads * g["expert_w"] + tokens * g["h"])
    cache_values = g["layers"] * g["latent"] * (
        decode_rows + prefill_rows + tokens)
    return {"flops": float(flops), "bytes": 2.0 * (weight_values
                                                   + cache_values)}


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
                     // m["n_group"]),
                 sampled_rows=batch)
