"""Child process of the correctness check of the Laguna describe
configuration.

  python benchmark/reference/laguna_child.py job.json result.json [control]

For each sampled message: the stand-in tokenizer's rendering of its
objects must be its published ``prompt_ids``; then the plain reference
(``laguna_plain``) is teacher-forced over instruction + prompt + generated
ids, one sample at a time, one layer's weights alive at a time and the
experts one by one, and its logits at the generated positions are compared
with the published top-8 of every generated token. The tokenizer
restatement and ``check_description`` are ``lm_compare``'s; the LIMITS, and
what becomes of a token that a routing decision flipped, are this model's
own (below).

By the time this runs the harness has stopped the server, so the chip is
free: where the configuration's ``shapes.reference_platform`` says ``tpu``
the reference runs THERE (float32 at ``highest`` precision) and fails if it
finds none; a rehearsal says ``cpu``. Nothing of the program is imported.
The harness hands its children no compile cache, and op by op on the chip
a reference compiles for minutes; so it keeps one of its own, at a fixed
path under ``benchmark_out/`` beside the installed models, cold in a
checkout's first run only. It holds the chip, so it asks the kernel to end
it with its parent, and leaves without the runtime's teardown once the
result is written: it never outlives a run. ``control`` (``weights`` |
``window`` | ``rope`` | ``gate``; the harness gives none) computes the
reference as a model the configuration is NOT: weights rounded to
float8_e4m3fn (the nearest precision below the configuration's), the
window layers seeing everything earlier, both rotations left out, every
head's gate 1. A reading by hand over a run's saved
``reference_job.json``; all four must come out NOT ok.

What is compared, and why the limits are what they are. Readings on the
chip at the published size (PR 42, PERF.md section 6: the 28 frames
of the cell's runs of calls c1 and s1; the controls and ``acts`` over the 4
frames of the first of them): OURS is the served path (weights and
activations bfloat16; the softmax, the gates and the router's scores
float32; pages, packed chunks, the window as a lower bound, the prefix's
last pages); WEIGHTS the reference with weights rounded to float8_e4m3fn,
WINDOW with the window layers seeing everything earlier, ROPE without
either rotation, GATE with every gate 1. Every limit lies between ours and
the controls' with room on both sides. The reference at bfloat16
ACTIVATIONS alone (``acts``: no cache, no kernel, float32 products)
differs from the plain one by 0.085-0.101 in the median and 0.52-0.60 at
most, and reads against the published logits as ours does (0.103-0.121,
0.61-0.76): the precision, not the path.

* LOGIT_MEDIAN_TOL: the median over a frame's 512 published logits (64
  tokens x 8) of |published - reference|. Ours 0.106-0.126; WEIGHTS
  0.946-1.022; WINDOW 1.000-1.088; GATE 1.932-1.997; ROPE 3.39-3.51. The
  limit is 2.8 times ours and under 0.4 of the nearest control's.
* Routing is DISCRETE: a token's experts are the 8 best of ``score +
  bias`` over 256, and where the 8th and 9th lie within rounding the
  bfloat16 path and the float32 reference pick different experts. All 256
  are held, so a flipped decision swaps one of eight routed terms for
  another and moves the token's logits with no defect, by less than in
  the other expert cells (an expert carries an eighth of 2.5 times a
  renormalised sum, in 4 layers). A token is FLIPPED where any of its 8
  logits differs by more than LOGIT_TOKEN_TOL (0.8), and of a frame's
  tokens at most FLIP_SHARE may be: ours 0-2 of 64 (a frame's 90th
  percentile 0.40-0.60); every control all 64 (their least token 1.005).
  Nothing is excused.
* LOGIT_ABS_TOL: no logit may differ by more, flipped or not. Ours
  0.49-0.94; WEIGHTS 2.80-2.95; WINDOW 2.76-3.69; GATE 4.24-4.53; ROPE
  6.07-6.49. The limit is 1.9 times ours and 0.65 of the least control's.
* The greedy choice: on unflipped tokens the published id's reference
  logit is within 2 x LOGIT_TOKEN_TOL of the reference's own best (ours
  0.37-0.83).

WINDOW and ROPE are refused because the configuration seeds the query and
key head norms' gains around ``qk_norm_gain`` (under ``assumed``): at a
gain of 1 a seeded softmax over 2.4 k rows is flat, and the mean of 512
values reads like the mean of 2384 (float32 on a CPU at the published
widths, PERF.md section 4: the reference without the window differs from
the plain one by 0.25 in the median at a gain of 1 and by 1.02 at 1.75).
A window off by one row, a wrong slice of the prefix's pages, a wrong page
table or a wrong merge of the prefix with a row's own pages moves a logit
as WINDOW and ROPE do: by which rows a query weighs.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

LOGIT_MEDIAN_TOL = 0.35
LOGIT_TOKEN_TOL = 0.8
FLIP_SHARE = 0.25
LOGIT_ABS_TOL = 1.8
READ_AT_TOP_K = 8
CONTROLS = ("weights", "window", "rope", "gate")
#: a reading, not a control: the reference at the served path's own
#: activation precision (it has to come out ok)
READINGS = ("acts",)
#: part of every entry's key, so one fixed path
COMPILE_CACHE_DIR = REPO / "benchmark_out" / "reference_cache" / "laguna"


def compare_logits(desc: dict, ref_logits: np.ndarray,
                   scale: float = 1.0) -> tuple[list, dict]:
    """``ref_logits`` [generated tokens, vocab]: the reference's row for
    each generated position. ``scale`` moves the three limits that are in
    units of a logit for a model of another depth than the one they were
    read on (``limits_scale``)."""
    median_tol, token_tol, abs_tol = (
        scale * v for v in (LOGIT_MEDIAN_TOL, LOGIT_TOKEN_TOL, LOGIT_ABS_TOL))
    top = np.asarray(desc["top_logits"], np.float64)
    want = np.take_along_axis(ref_logits.astype(np.float64),
                              np.asarray(desc["top_ids"]), axis=1)
    diff = np.abs(top - want)
    per_token = diff.max(axis=1)
    flipped = per_token > token_tol
    short = ref_logits.max(axis=1) - want[:, 0]
    stats = {"median": float(np.median(diff)), "max": float(diff.max()),
             "flipped": int(flipped.sum()), "tokens": int(len(per_token)),
             "largest_unflipped": float(per_token[~flipped].max())
             if (~flipped).any() else None,
             "token_p90": float(np.quantile(per_token, 0.9)),
             "greedy_short": float(short[~flipped].max())
             if (~flipped).any() else None,
             # per generated token, for whoever sets the limits anew
             "per_token": [round(float(v), 4) for v in per_token]}
    problems = []
    if stats["median"] > median_tol:
        problems.append(
            f"logits differ from the reference's by {stats['median']:.4f} "
            f"in the median (limit {median_tol})")
    if flipped.mean() > FLIP_SHARE:
        problems.append(
            f"{stats['flipped']} of {stats['tokens']} tokens differ by more "
            f"than {token_tol} (limit {FLIP_SHARE:.0%} of them)")
    if stats["max"] > abs_tol:
        problems.append(f"a logit differs by {stats['max']:.3f} "
                        f"(limit {abs_tol})")
    bad = (short > 2 * token_tol) & ~flipped
    if bad.any():
        problems.append(
            f"{int(bad.sum())} greedy choices fall short of the reference's "
            f"best logit by up to {float(short[bad].max()):.3f}")
    return problems, stats


def limits_scale(model: dict) -> float:
    """The limits were read on the published stage, where a token's routed
    sum is 8 experts' and one of them carries an eighth of it. A model
    that chooses fewer (the rehearsal's tiny one: 2 of 8, under the same
    factor 2.5) puts four times the weight on one decision, and a flipped
    one moves a token's logits by as much more: the three limits in units
    of a logit are 3 times as wide there. Read over the tiny model on a
    CPU (4 prompts, 12 tokens each): ours 0.06-0.27 in the median and
    0.81-5.1 at most (the reference at bfloat16 activations against
    itself: 0.04-0.17 and 0.34-2.3), one token of 12 over 2.4 in two of
    them, for limits of 1.2 and 6.0; every control 1.75-2.8 in the median
    with all 12 tokens over."""
    return 1.0 if model["num_experts_per_tok"] >= READ_AT_TOP_K else 3.0


def main() -> int:
    from benchmark.reference.kimi_linear_child import die_with_parent

    parent = os.getppid()
    die_with_parent()
    if os.getppid() != parent:  # it ended before the request was made
        return 1
    job = json.loads(Path(sys.argv[1]).read_text())
    shapes = job["shapes"]
    platform = shapes["reference_platform"]
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        os.environ.pop("JAX_PLATFORMS", None)
    import jax
    import jax.numpy as jnp

    if platform != "cpu":  # a rehearsal's seconds of compiling need none
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from benchmark.reference import laguna_plain as ref
    from benchmark.reference import lm_compare
    from benchmark.reference.compare import check_schema

    found = jax.devices()[0].platform
    if found != platform:
        print(f"the reference asks for {platform!r}, JAX came up on "
              f"{found!r}", file=sys.stderr)
        return 1
    model, engine = shapes["model"], shapes["engine"]
    prefix = lm_compare.instruction_ids(engine["prefix_tokens"],
                                        model["vocab_held"])
    control = sys.argv[3] if len(sys.argv) > 3 else None
    if control is not None and control not in CONTROLS + READINGS:
        print(f"no control {control!r} ({'|'.join(CONTROLS)})",
              file=sys.stderr)
        return 1
    out = []
    for f in job["frames"]:
        t0 = time.time()
        msg = f["message"]
        row = {"stream": f["stream"], "seq": f["seq"]}
        bad = check_schema(msg)
        problems = ([f"schema: {bad}"] if bad
                    else lm_compare.check_description(msg, shapes))
        if not problems:
            desc = msg["description"]
            tokens = prefix + desc["prompt_ids"] + desc["ids"]
            first = len(prefix) + len(desc["prompt_ids"]) - 1
            rows = list(range(first, first + len(desc["ids"])))
            how = {None: {},
                   "weights": {"weight_dtype": jnp.float8_e4m3fn},
                   "window": {"window": False},
                   "rope": {"rotated": False},
                   "gate": {"gated": False},
                   "acts": {"act_dtype": jnp.bfloat16}}[control]
            logits = ref.forward(model, tokens, rows=rows, **how)
            problems, row["logits"] = compare_logits(
                desc, np.asarray(logits), limits_scale(model))
            if control in READINGS:
                # the reading against the plain reference itself, at the
                # published ids: what that precision alone moves
                plain = {**desc, "top_logits": np.take_along_axis(
                    np.asarray(ref.forward(model, tokens, rows=rows)),
                    np.asarray(desc["top_ids"]), axis=1).tolist()}
                _, row["against_plain"] = compare_logits(
                    plain, np.asarray(logits), limits_scale(model))
        row["problems"] = problems
        row["seconds"] = round(time.time() - t0, 1)
        out.append(row)
    Path(sys.argv[2]).write_text(json.dumps({
        "ok": all(not r["problems"] for r in out),
        "frames": out,
        "platform": found,
        "control": control,
        "tolerances": {
            "logit_median": LOGIT_MEDIAN_TOL, "logit_token": LOGIT_TOKEN_TOL,
            "flip_share": FLIP_SHARE,
            "logit_abs": LOGIT_ABS_TOL},
    }, indent=1))
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
