"""The comparison that decides ``correct`` for the describe configuration.

A published message carries the detector's objects and a ``description``:
the prompt ids the stage rendered from them, the 48 generated ids and, per
generated token, the 8 largest logits with their ids, all of the timed
path. The reference (``deepseek_v2_plain``: float32, no cache) is
teacher-forced over instruction + prompt + generated ids and gives every
position's full logits row. Compared are LOGITS, not tokens: under random
weights the two largest logits lie a quarter of a unit apart in the median
and which is larger turns on rounding.

What is checked, and why the limits are what they are:

* the prompt is the stand-in tokenizer's rendering of THIS message's
  source, timestamp and objects, restated here (the program's is
  ``evam_tpu/stages/describe.py``); the generated ids are the published
  argmax ids; each row's 8 logits are ordered. Exact.
* LOGIT_MEDIAN_TOL: the median over a frame's 384 published logits of
  |published - reference|. bfloat16 products, a bfloat16 residual stream
  and the absorbed attention move a logit (spread 1.43 over the
  vocabulary) by 0.016-0.025 in the median (chip, PR 28: 76 frames of 19
  runs); weights rounded to 8 bits (float8_e4m3: the nearest precision
  below) move it by 0.395-0.451 (two frames of one run). The limit lies
  between, 2.4 times the first reading and a seventh of the second.
* routing is DISCRETE: a token's experts are the 6 best scores of the 3
  best groups, and where two scores lie within rounding of each other the
  bfloat16 path and the float32 reference pick different experts; this
  chip holds one group, so a flipped decision adds or removes local terms
  of that token, and its logits differ by 0.2-2.5 with no defect. A token
  is FLIPPED where any of its 8 logits differs by more than
  LOGIT_TOKEN_TOL (rounding alone moved a token by 0.09 at most where no
  decision was nearer than 0.05, by 0.07 beyond ROUTE_MARGIN). It is excused only where the reference itself says
  a decision was near: ``route_margin`` (deepseek_v2_plain), the log
  ratio of router scores by which the nearest decision that changes the
  token's HELD experts was made, least over the expert layers, is under
  ROUTE_MARGIN. In 192 tokens of four chip frames all 22 flipped tokens
  had margins of 0.0002-0.0305, the median token 0.042; the limit is five
  times the largest, and leaves 15 % of the tokens with no excuse at all.
  Of the others at most FLIP_SHARE may be flipped: 0-10 of 48 were
  (21 %; 76 frames), against 48 of 48 with 8-bit weights. No logit may
  differ by more than LOGIT_ABS_TOL: the largest a flip made was 2.45,
  while a row of ANOTHER sequence or position (a page-table or slot
  mix-up) reads 8.0-10.3 as a frame's largest and 3.0-5.2 as a row's
  (four frames, each against the others' rows and its own shifted by
  one). With ONE typical held expert of 20 taken away, each of the four
  frames has 1-2 flipped tokens that nothing excuses (0.29-4.4), 12-22
  flipped and a largest difference of 3.6-4.6: refused by the margin in
  every frame, by the share in one, by the largest in three (PERF.md
  section 6).
* the greedy choice: on unflipped tokens the published id's reference
  logit is within 2 x LOGIT_TOKEN_TOL of the reference's own best (each
  of the two may be off by one LOGIT_TOKEN_TOL).
"""

from __future__ import annotations

import hashlib

import numpy as np

LOGIT_MEDIAN_TOL = 0.06
LOGIT_TOKEN_TOL = 0.2
ROUTE_MARGIN = 0.15
FLIP_SHARE = 0.3
LOGIT_ABS_TOL = 4.0

FRAME, OBJ, END_OBJ = 1, 2, 3
LABEL0, NUM0 = 8, 24


def instruction_ids(n: int, vocab: int) -> list[int]:
    return [LABEL0 + ((i + 1) * 2654435761 % 2**32) % (vocab - LABEL0)
            for i in range(n)]


def render_prompt(msg: dict, vocab: int, max_objects: int) -> list[int]:
    """The ids a frame's message stands for: 16 of header, 8 an object."""
    bins = min(1000, vocab - NUM0)

    def number(v):
        return NUM0 + min(bins - 1, max(0, int(v * bins)))

    digest = hashlib.sha256(msg["source"].encode()).digest()
    ids = [FRAME]
    ids += [NUM0 + (digest[2 * i] * 256 + digest[2 * i + 1]) % bins
            for i in range(7)]
    ids += [NUM0 + (msg["timestamp"] // bins ** i) % bins for i in range(8)]
    for obj in msg["objects"][:max_objects]:
        det = obj["detection"]
        bb = det["bounding_box"]
        ids += [OBJ, LABEL0 + det["label_id"] % 16, number(bb["x_min"]),
                number(bb["y_min"]), number(bb["x_max"]), number(bb["y_max"]),
                number(det["confidence"]), END_OBJ]
    return ids


def check_description(msg: dict, shapes: dict) -> list[str]:
    """What can be said without the reference."""
    desc = msg.get("description")
    if not isinstance(desc, dict):
        return ["the message carries no description"]
    model, engine = shapes["model"], shapes["engine"]
    problems = []
    want = render_prompt(msg, model["vocab_held"], engine["max_objects"])
    if desc.get("prompt_ids") != want:
        problems.append("prompt_ids are not the rendering of this message's "
                        "source, timestamp and objects")
    n = engine["max_new_tokens"]
    ids, top_ids, top = (desc.get(k) for k in ("ids", "top_ids",
                                               "top_logits"))
    if not (isinstance(ids, list) and len(ids) == n):
        return problems + [f"ids are not {n} generated tokens"]
    if np.asarray(top_ids).shape != (n, 8) or np.asarray(top).shape != (n, 8):
        return problems + ["top_ids/top_logits are not 8 per generated token"]
    if desc.get("prefix_tokens") != engine["prefix_tokens"]:
        problems.append(f"prefix_tokens {desc.get('prefix_tokens')}, the "
                        f"configuration says {engine['prefix_tokens']}")
    if [row[0] for row in top_ids] != ids:
        problems.append("the generated ids are not the published best ids")
    if np.any(np.diff(np.asarray(top), axis=1) > 0):
        problems.append("a token's logits are not ordered best first")
    vocab = model["vocab_held"]
    if not all(0 <= t < vocab for row in top_ids for t in row):
        problems.append("an id lies outside the held vocabulary")
    return problems


def compare_logits(desc: dict, ref_logits: np.ndarray,
                   margins: np.ndarray) -> tuple[list, dict]:
    """``ref_logits`` [generated tokens, vocab]: the reference's row for
    each generated position; ``margins`` [generated tokens]: its
    ``route_margin`` there."""
    top = np.asarray(desc["top_logits"], np.float64)
    top_ids = np.asarray(desc["top_ids"])
    want = np.take_along_axis(ref_logits.astype(np.float64), top_ids, axis=1)
    diff = np.abs(top - want)
    per_token = diff.max(axis=1)
    flipped = per_token > LOGIT_TOKEN_TOL
    near = np.asarray(margins) < ROUTE_MARGIN
    stats = {"median": float(np.median(diff)), "max": float(diff.max()),
             "flipped": int(flipped.sum()), "tokens": int(len(per_token)),
             "largest_unflipped": float(per_token[~flipped].max())
             if (~flipped).any() else None,
             "near_a_decision": int(near.sum()),
             "largest_flipped_margin": float(margins[flipped].max())
             if flipped.any() else None}
    problems = []
    if stats["median"] > LOGIT_MEDIAN_TOL:
        problems.append(
            f"logits differ from the reference's by {stats['median']:.4f} "
            f"in the median (limit {LOGIT_MEDIAN_TOL})")
    if (flipped & ~near).any():
        problems.append(
            f"{int((flipped & ~near).sum())} tokens differ by up to "
            f"{float(per_token[flipped & ~near].max()):.3f} (limit "
            f"{LOGIT_TOKEN_TOL}) where no routing decision was nearer than "
            f"{float(margins[flipped & ~near].min()):.3f} (an excuse needs "
            f"one within {ROUTE_MARGIN})")
    if flipped.mean() > FLIP_SHARE:
        problems.append(
            f"{stats['flipped']} of {stats['tokens']} tokens differ by more "
            f"than {LOGIT_TOKEN_TOL} (limit {FLIP_SHARE:.0%} of them)")
    if stats["max"] > LOGIT_ABS_TOL:
        problems.append(f"a logit differs by {stats['max']:.3f} "
                        f"(limit {LOGIT_ABS_TOL})")
    chosen = want[:, 0]
    short = ref_logits.max(axis=1) - chosen
    bad = (short > 2 * LOGIT_TOKEN_TOL) & ~flipped
    if bad.any():
        problems.append(
            f"{int(bad.sum())} greedy choices fall short of the reference's "
            f"best logit by up to {float(short[bad].max()):.3f}")
    return problems, stats
