"""Operations and compulsory bytes of the generate engine's device steps for
the Jamba configuration, from shapes alone (``shapes.model``) and from what
the engine counted (steps, tokens, prompts, cache rows read).

Everything is a FLOOR, so that a roofline share built on it can read low
and never over 100 %: 2 operations per multiply-accumulate of every matrix
product the mathematics needs; norms, the convolution's silu, softplus,
exp, softmax and sampling are left out. Bytes are what no schedule avoids:
the weights a step touches, once per step; each live row's slot state,
read and written; the cache rows read, once each; the new cache rows
written. Activations are not counted.

Per token and layer (H hidden, C = d_inner, N = d_state, R = dt_rank,
K = d_conv, I = intermediate):
  mamba      in_proj 2 H 2C, x_proj 2 C (R + 2N), dt_proj 2 R C, out_proj
             2 C H, the convolution 2 K C, and the recurrence
             (``scan_ops_and_bytes``)
  attention  q 2 H H, k and v 2 H 2 hd, o 2 H H; per cached row read 2
             heads hd for the score and as much for the value
  every      the feed-forward 3 x 2 H I
and once per sampled row the head over the vocabulary (the embedding,
tied).
"""

from __future__ import annotations


def _model(m: dict) -> dict:
    h, inter = m["hidden_size"], m["intermediate_size"]
    c = m["mamba_expand"] * h
    n, r, k = m["mamba_d_state"], m["mamba_dt_rank"], m["mamba_d_conv"]
    heads = m["num_attention_heads"]
    hd = h // heads
    layers = m["num_hidden_layers"]
    attn = sum(1 for i in range(layers)
               if i % m["attn_layer_period"] == m["attn_layer_offset"])
    mamba_w = h * 2 * c + k * c + c * (r + 2 * n) + r * c + n * c + c * h
    attn_w = 2 * h * heads * hd + 2 * h * hd * m["num_key_value_heads"]
    ff_w = 3 * h * inter
    return dict(h=h, c=c, n=n, r=r, k=k, heads=heads, hd=hd, layers=layers,
                attn=attn, mamba=layers - attn, mamba_w=mamba_w,
                attn_w=attn_w, ff_w=ff_w, vocab=m["vocab_held"],
                kv_width=2 * hd * m["num_key_value_heads"],
                # a slot's state in one Mamba layer: float32 state and
                # the convolution's K - 1 bfloat16 inputs
                slot_bytes=4 * n * c + 2 * (k - 1) * c)


def parameters(m: dict) -> int:
    """Matrix parameters held (gains, biases and ``D`` left out)."""
    g = _model(m)
    return (g["vocab"] * g["h"] + g["mamba"] * g["mamba_w"]
            + g["attn"] * g["attn_w"] + g["layers"] * g["ff_w"])


def scan_ops_and_bytes(m: dict, tokens: int) -> dict:
    """The recurrence of ONE Mamba layer over ``tokens`` tokens (the
    kernel ``ssm_selective_scan``). Per token and state element: the
    decay's argument ``dt * A``, the input term ``(dt u) * B``, ``s =
    decay * s + input`` and ``y += s * C``: 6 operations (exp left out).
    Bytes: ``u``, ``z`` (bfloat16), ``dt`` (float32), ``B``, ``C`` in and
    ``y`` (bfloat16) out; the state costs no HBM bytes inside a chunk."""
    g = _model(m)
    return {"flops": 6.0 * tokens * g["n"] * g["c"],
            "bytes": float(tokens * (g["c"] * (2 + 2 + 4 + 2)
                                     + 2 * g["n"] * 2))}


def steps(m: dict, *, prefill_steps: int, prefill_tokens: int,
          prefill_prompts: int, prefill_rows: int, decode_steps: int,
          decode_tokens: int, decode_rows: int, held_assignments: int,
          sampled_rows: int) -> dict:
    """Operations and bytes of the counted steps together (the keyword
    names are ``readers/lm_roofline.py``'s). The ``rows`` are per layer
    THAT HAS a cache, as the engine counts them
    (``evam_generate_latent_rows_read_total{kind}``): a decode row's
    whole context; a chunk's cached rows once a chunk. Slot state: every
    decode token's, and every prompt's once (a prompt that continues in
    a second chunk moves its state twice: a floor). ``held_assignments``
    is 0 here."""
    g = _model(m)
    tokens = prefill_tokens + decode_tokens
    flops = tokens * g["mamba"] * (2 * g["mamba_w"] - 2 * g["n"] * g["c"])
    flops += g["mamba"] * scan_ops_and_bytes(m, tokens)["flops"]
    flops += tokens * g["attn"] * 2 * g["attn_w"]
    flops += tokens * g["layers"] * 2 * g["ff_w"]
    pair = 4 * g["heads"] * g["hd"]  # one query token, one cached row
    flops += g["attn"] * pair * decode_rows
    if prefill_steps:
        flops += (g["attn"] * pair * (prefill_rows / prefill_steps)
                  * prefill_tokens)
    if prefill_prompts:
        mean_len = prefill_tokens / prefill_prompts
        flops += prefill_tokens * g["attn"] * (mean_len / 2) * pair
    flops += sampled_rows * 2 * g["h"] * g["vocab"]

    n_steps = prefill_steps + decode_steps
    weight_values = (n_steps * (g["mamba"] * g["mamba_w"]
                                + g["attn"] * g["attn_w"]
                                + g["layers"] * g["ff_w"]
                                + g["h"] * g["vocab"])
                     + tokens * g["h"])
    cache_values = g["attn"] * g["kv_width"] * (
        decode_rows + prefill_rows + tokens)
    state_bytes = (2 * g["mamba"] * g["slot_bytes"]
                   * (decode_tokens + prefill_prompts))
    return {"flops": float(flops),
            "bytes": 2.0 * (weight_values + cache_values) + state_bytes}


def ops_and_bytes(shapes: dict, batch: int) -> dict:
    """One decode step over ``batch`` rows at the longest context a
    sequence reaches: the figure a reader of the configuration wants for
    sizing."""
    m, e = shapes["model"], shapes["engine"]
    ctx = e["prefix_tokens"] + 16 + 8 * e["max_objects"] + e["max_new_tokens"]
    return steps(m, prefill_steps=0, prefill_tokens=0, prefill_prompts=0,
                 prefill_rows=0, decode_steps=1, decode_tokens=batch,
                 decode_rows=batch * ctx, held_assignments=0,
                 sampled_rows=batch)
