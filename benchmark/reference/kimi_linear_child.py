"""Child process of the correctness check of the Kimi-Linear describe
configuration.

  python benchmark/reference/kimi_linear_child.py job.json result.json [control]

For each sampled message: the stand-in tokenizer's rendering of its
objects must be its published ``prompt_ids``; then the plain reference
(``kimi_linear_plain``) is teacher-forced over instruction + prompt +
generated ids, one sample at a time, one layer's weights alive at a time
and the held experts one by one, and its logits at the generated positions
are compared with the published top-8 of every generated token. The
tokenizer restatement and ``check_description`` are ``lm_compare``'s; the
LIMITS, and what becomes of a token that a routing decision flipped, are
this model's own (below).

By the time this runs the harness has stopped the server, so the chip is
free: where the configuration's ``shapes.reference_platform`` says ``tpu``
the reference runs THERE (float32 at ``highest`` precision) and fails if it
finds none; a rehearsal says ``cpu``. Nothing of the program is imported.
The harness hands its children no compile cache, and op by op on the chip
this reference compiles for two minutes, which made a run of the cell the
longest of the benchmark's by a third (PERF.md section 6, PR 34); so it
keeps one of its own, at a fixed path under ``benchmark_out/`` beside the
installed models, cold in a checkout's first run only. It holds the chip,
so it asks the kernel to end it with its parent, and leaves without the
runtime's teardown once the result is written: it never outlives a run.
``control`` (``weights`` | ``state`` | ``rotate``; the harness gives none)
computes the reference as a model the configuration is NOT: weights rounded
to float8_e4m3fn, the KDA recurrence held in bfloat16 (the nearest
precisions below the configuration's), ``q_r`` and ``k_r`` rotated as
DeepSeek's latent attention rotates them. A reading by hand over a run's
saved ``reference_job.json``; all three must come out NOT ok.

What is compared, and why the limits are what they are. Readings on the
chip at the published size (PR 34, PERF.md section 6, call r1: 24 frames
of 6 runs, 1536 tokens; the controls over the 8 frames of two of them):
OURS is the served path (weights and activations bfloat16; the KDA state,
decay and delta update float32); STATE the reference with the KDA
recurrence held in bfloat16, WEIGHTS with weights rounded to float8_e4m3fn,
ROTATE with ``q_r`` / ``k_r`` rotated. Every limit lies between ours and
the controls' with room on both sides.

* LOGIT_MEDIAN_TOL: the median over a frame's 512 published logits (64
  tokens x 8) of |published - reference|. Ours 0.031-0.051; STATE
  0.147-0.188; ROTATE 0.236-0.266; WEIGHTS 0.414-0.464. The limit is 1.6
  times ours and half of STATE's.
* Routing is DISCRETE: a token's experts are the 8 best of ``score +
  bias`` over 256, and where the 8th and 9th lie within rounding the
  bfloat16 path and the float32 reference pick different experts; this
  chip holds 64 of them in 7 layers, so a flipped decision adds or removes
  a local term and the token's logits differ with no defect. A token is
  FLIPPED where any of its 8 logits differs by more than LOGIT_TOKEN_TOL
  (ours moves a token by 0.15-0.27 at a frame's 90th percentile), and of a
  frame's tokens at most FLIP_SHARE may be: ours 0-6.25 % (4 of 64 in the
  worst frame); STATE 80-92 %, ROTATE 97-100 %, WEIGHTS all.
  ``lm_compare``'s excuse (a flipped token passes where the reference saw
  a routing decision within a margin) is NOT taken over: with 64 held
  experts in each of 7 layers 58-62 of a frame's 64 tokens have a decision
  within the 0.006 that covered ours (call 14; the median token's is
  0.0014), so it would excuse nearly every token and limit nothing.
  Nothing is excused.
* LOGIT_ABS_TOL: no logit may differ by more, flipped or not. Ours
  0.24-0.47; STATE 0.70-0.89 (NOT refused by this limit: by the median and
  the share); ROTATE 1.05-1.26; WEIGHTS 1.54-1.97.
* The greedy choice: on unflipped tokens the published id's reference
  logit is within 2 x LOGIT_TOKEN_TOL of the reference's own best (ours
  0.06-0.27).

ROTATE is refused because the configuration seeds the MLA layers' ``q`` and
``kv_a`` 2.5 times as wide (``mla_qk_init_scale``, under ``assumed``): at
``initializer_range`` alone a softmax over 2.4 k rows of seeded scores is
flat, the layer adds a hundredth of what a KDA mixer adds, and this
comparison read ROTATE as ok (0.035-0.044 beside ours 0.025-0.043: call
14). A wrong page table or a wrong merge of the prefix with a row's own
pages moves a logit as ROTATE does: by which rows a query weighs.
"""

from __future__ import annotations

import ctypes
import json
import os
import signal
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

LOGIT_MEDIAN_TOL = 0.08
LOGIT_TOKEN_TOL = 0.3
FLIP_SHARE = 0.2
LOGIT_ABS_TOL = 1.0
READ_AT_RANGE = 0.02
#: part of every entry's key, so one fixed path
COMPILE_CACHE_DIR = REPO / "benchmark_out" / "reference_cache" / "kimi_linear"
PR_SET_PDEATHSIG = 1


def compare_logits(desc: dict, ref_logits: np.ndarray,
                   scale: float = 1.0) -> tuple[list, dict]:
    """``ref_logits`` [generated tokens, vocab]: the reference's row for
    each generated position. ``scale`` widens the three limits that are in
    units of a logit for a model seeded wider than the one they were read
    on (``limits_scale``)."""
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
    """The limits were read on weights of ``READ_AT_RANGE``; a model seeded
    wider (a rehearsal's tiny one: a width of 64 needs 0.15 for scores that
    are not flat) moves a logit further for the same rounding, by the
    square root of the ratio as read over a rehearsal's 64 tokens."""
    return max(1.0, model["initializer_range"] / READ_AT_RANGE) ** 0.5


def die_with_parent() -> None:
    """SIGKILL from the kernel when the process that started this one
    ends, however it ends (Linux; elsewhere nothing)."""
    try:
        ctypes.CDLL(None, use_errno=True).prctl(
            PR_SET_PDEATHSIG, signal.SIGKILL, 0, 0, 0)
    except (OSError, AttributeError):
        pass


def main() -> int:
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

    from benchmark.reference import kimi_linear_plain as ref
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
    control = {"weights": {"weight_dtype": jnp.float8_e4m3fn},
               "state": {"state_dtype": jnp.bfloat16},
               "rotate": {"rotate": True}}[sys.argv[3]] \
        if len(sys.argv) > 3 else {}
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
            logits = ref.forward(model, tokens, rows=rows, **control)
            problems, row["logits"] = compare_logits(
                desc, np.asarray(logits), limits_scale(model))
        row["problems"] = problems
        row["seconds"] = round(time.time() - t0, 1)
        out.append(row)
    Path(sys.argv[2]).write_text(json.dumps({
        "ok": all(not r["problems"] for r in out),
        "frames": out,
        "platform": found,
        "control": sys.argv[3] if len(sys.argv) > 3 else None,
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
