"""Operations and compulsory bytes of the generate engine's device steps for
the Brumby configuration, from shapes alone (``shapes.model``) and from what
the engine counted (steps, tokens, prompts).

Everything is a FLOOR, so that a roofline share built on it can read low and
never over 100 %: 2 operations per multiply-accumulate of every matrix
product the mathematics needs; norms, the rotation, the gate's sigmoid, the
decay's exponentials, the division and sampling are left out. Bytes are what
no schedule avoids: the weights a step touches, once per step; each live
sequence's slot state, read and written once per decode token and once per
prompt; the embedding's rows. The family keeps NO cache rows, so the
``rows`` the engine hands are 0 and are not read. Activations are not
counted.

Per token and layer (h hidden; a query heads over g key-value heads of d; D
= d (d + 1) / 2 the expanded keys; i the feed-forward's width):
  the projections 2 x (2 h a d + 2 h g d + h g), SwiGLU 2 x 3 h i;
  the mixer: a decode token 2 x (g + a) D d (``phi(k) v^T`` into the state
  of each key-value head, ``phi(q)^T S`` out of it for each query head), a
  prefill token ``scan_ops_and_bytes``;
and once per sampled row the head over the vocabulary (untied: the embedding
is read by the row, the head whole).
"""

from __future__ import annotations

#: ops/pallas_power.py's block: a token sees half of one in the mean
BLOCK = 64


def _model(m: dict) -> dict:
    h, i = m["hidden_size"], m["intermediate_size"]
    a, g, d = (m["num_attention_heads"], m["num_key_value_heads"],
               m["head_dim"])
    wide = d * (d + 1) // 2
    return dict(
        h=h, n=m["num_hidden_layers"], a=a, g=g, d=d, wide=wide,
        layer_w=2 * h * a * d + 2 * h * g * d + h * g + 3 * h * i,
        # a row's state and sums in one layer, float32
        row_bytes=4 * g * (wide * d + (d // 2 + 1) * d),
        vocab=m["vocab_held"], chunk=m["engine_chunk_tokens"])


def parameters(m: dict) -> int:
    """Every parameter held on this chip: the matrices, the gate's biases
    and every gain; the embedding and the untied head both. It is the
    family's ``param_count``."""
    g = _model(m)
    return (2 * g["vocab"] * g["h"] + g["h"]
            + g["n"] * (g["layer_w"] + g["g"] + 2 * g["h"] + 2 * g["d"]))


def scan_ops_and_bytes(m: dict, tokens: int,
                       kernel: str = "pow_chunk_scan") -> dict:
    """The mixer's recurrence of ONE layer over ``tokens`` tokens, by the
    kernel that runs it (the names are what the trace readers ask a
    configuration for).

    ``pow_chunk_scan`` (prefill tokens, the chunkwise form over blocks of
    64): per token ``D d`` multiply-adds for each of the ``a`` query heads
    (the carried state read out) and each of the ``g`` key-value heads (the
    state's update), and the block's scores and their product with ``v``
    over half a block (2 d a pair). Bytes: ``q``, ``k``, ``v`` (bfloat16)
    and the log gates (float32) in, ``y`` (bfloat16 at least) out, and a
    segment's state in and out once, one segment a chunk at least.

    ``pow_decode_rows`` (decode tokens): bound by its bytes, a row's state
    and sums read and written (2 x 34.1 MB a row and layer); the same
    multiply-adds a token without the block's scores."""
    g = _model(m)
    per_head = g["wide"] * g["d"]
    if kernel == "pow_decode_rows":
        return {"flops": 2.0 * tokens * (g["a"] + g["g"]) * per_head,
                "bytes": 2.0 * tokens * g["row_bytes"]}
    if kernel != "pow_chunk_scan":
        raise ValueError(f"the brumby family runs no kernel {kernel!r}")
    macs = (g["a"] + g["g"]) * per_head + g["a"] * (BLOCK // 2) * 2 * g["d"]
    moved = 2 * (2 * g["a"] + 2 * g["g"]) * g["d"] + 4 * g["g"]
    return {"flops": 2.0 * tokens * macs,
            "bytes": float(tokens * moved
                           + 2 * g["row_bytes"] * (tokens // g["chunk"]))}


def steps(m: dict, *, prefill_steps: int, prefill_tokens: int,
          prefill_prompts: int, prefill_rows: int, decode_steps: int,
          decode_tokens: int, decode_rows: int, held_assignments: int,
          sampled_rows: int, experts_hit: int | None = None) -> dict:
    """Operations and bytes of the counted steps together (the keyword names
    are ``readers/lm_roofline.py``'s; the rows, the assignments and the
    experts hit are those of families with a cache or experts and are not
    read). Slot state: every decode token's, read and written, and every
    prompt's once (a prompt that continues in a second chunk moves its
    state twice: a floor)."""
    g = _model(m)
    tokens = prefill_tokens + decode_tokens
    flops = tokens * g["n"] * 2 * g["layer_w"]
    flops += g["n"] * (
        scan_ops_and_bytes(m, prefill_tokens)["flops"]
        + scan_ops_and_bytes(m, decode_tokens, "pow_decode_rows")["flops"])
    flops += sampled_rows * 2 * g["h"] * g["vocab"]
    n_steps = prefill_steps + decode_steps
    weight_values = (n_steps * (g["n"] * g["layer_w"] + g["h"] * g["vocab"])
                     + tokens * g["h"])
    state_bytes = (decode_tokens + prefill_prompts) * g["n"] * 2 \
        * g["row_bytes"]
    return {"flops": float(flops),
            "bytes": 2.0 * weight_values + state_bytes}


def ops_and_bytes(shapes: dict, batch: int) -> dict:
    """One decode step over ``batch`` rows: the figure a reader of the
    configuration wants for sizing."""
    return steps(shapes["model"], prefill_steps=0, prefill_tokens=0,
                 prefill_prompts=0, prefill_rows=0, decode_steps=1,
                 decode_tokens=batch, decode_rows=0, held_assignments=0,
                 sampled_rows=batch)
