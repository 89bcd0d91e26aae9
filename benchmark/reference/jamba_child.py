"""Child process of the correctness check of the Jamba describe
configuration.

  python benchmark/reference/jamba_child.py job.json result.json [control]

For each sampled message: the stand-in tokenizer's rendering of its
objects must be its published ``prompt_ids``; then the plain reference
(``jamba_plain``) is teacher-forced over instruction + prompt + generated
ids, one sample at a time, and its logits at the generated positions are
compared with the published top-8 of every generated token. The tokenizer
restatement and ``check_description`` are ``lm_compare``'s; the LIMITS are
this model's own (below).

By the time this runs the harness has stopped the server, so the chip is
free: where the configuration's ``shapes.reference_platform`` says
``tpu`` the reference runs THERE (float32 at ``highest`` precision) and
fails if it finds none; a rehearsal says ``cpu``. Nothing of the program
is imported. ``control`` (``weights`` | ``scan``; the harness gives none)
computes the reference in the nearest precision below the configuration's
(weights rounded to float8_e4m3fn; the recurrence in bfloat16): a reading
by hand over a run's saved ``reference_job.json``, which must come out NOT
ok.

What is compared, and why the limits are what they are. Readings on the
chip at the published size (PR 32: 36 frames of 9 runs, and the two
controls over the 8 frames of 2 runs; PERF.md section 6): OURS is the served
path (weights and activations bfloat16, the recurrence float32); SCAN is
the reference with the recurrence in bfloat16, the nearest precision below;
WEIGHTS the reference with weights rounded to float8_e4m3fn. Each limit
lies between ours and SCAN's; there is no routing here, so no token is
excused and every token is held to the same limits.

* LOGIT_MEDIAN_TOL: the median over a frame's 512 published logits (64
  tokens x 8) of |published - reference| (spread 1.01 over the
  vocabulary). Ours 0.0262-0.0322; SCAN 0.088-0.102; WEIGHTS 0.70-0.78.
* LOGIT_TOKEN_P90_TOL: the 90th percentile over the frame's tokens of a
  token's largest difference among its 8 logits. Ours 0.096-0.119; SCAN
  0.43-0.52; WEIGHTS 1.9-2.2.
* LOGIT_ABS_TOL: no logit may differ by more. Ours 0.124-0.186; SCAN
  1.41-1.55; WEIGHTS 2.3-2.6; a row of ANOTHER sequence (a slot or page
  mix-up) 5.9.
* GREEDY_TOL: the published id's reference logit is within it of the
  reference's own best. Each of the two may be off by as much as a logit
  differs at most (0.19 read), so ours can reach twice that; ours
  0.026-0.157; SCAN 0.36-0.70; WEIGHTS 2.0-2.6.
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

LOGIT_MEDIAN_TOL = 0.05
LOGIT_TOKEN_P90_TOL = 0.2
LOGIT_ABS_TOL = 0.6
GREEDY_TOL = 0.3


def compare_logits(desc: dict, ref_logits: np.ndarray) -> tuple[list, dict]:
    """``ref_logits`` [generated tokens, vocab]: the reference's row for
    each generated position."""
    top = np.asarray(desc["top_logits"], np.float64)
    want = np.take_along_axis(ref_logits.astype(np.float64),
                              np.asarray(desc["top_ids"]), axis=1)
    diff = np.abs(top - want)
    per_token = diff.max(axis=1)
    short = ref_logits.max(axis=1) - want[:, 0]
    stats = {"median": float(np.median(diff)),
             "token_p90": float(np.quantile(per_token, 0.9)),
             "max": float(diff.max()), "greedy_short": float(short.max()),
             "tokens": int(len(per_token))}
    problems = []
    for key, limit, what in (
            ("median", LOGIT_MEDIAN_TOL, "in the median"),
            ("token_p90", LOGIT_TOKEN_P90_TOL,
             "at the 90th percentile of the tokens' largest"),
            ("max", LOGIT_ABS_TOL, "at most")):
        if stats[key] > limit:
            problems.append(
                f"logits differ from the reference's by {stats[key]:.4f} "
                f"{what} (limit {limit})")
    if stats["greedy_short"] > GREEDY_TOL:
        problems.append(
            f"a greedy choice falls short of the reference's best logit by "
            f"{stats['greedy_short']:.3f} (limit {GREEDY_TOL})")
    return problems, stats


def main() -> int:
    job = json.loads(Path(sys.argv[1]).read_text())
    shapes = job["shapes"]
    platform = shapes["reference_platform"]
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        os.environ.pop("JAX_PLATFORMS", None)
    import jax

    from benchmark.reference import jamba_plain as ref
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
    import jax.numpy as jnp

    control = {"weights": {"weight_dtype": jnp.float8_e4m3fn},
               "scan": {"scan_dtype": jnp.bfloat16}}[sys.argv[3]] \
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
            problems, row["logits"] = compare_logits(desc, np.asarray(logits))
        row["problems"] = problems
        row["seconds"] = round(time.time() - t0, 1)
        out.append(row)
    Path(sys.argv[2]).write_text(json.dumps({
        "ok": all(not r["problems"] for r in out),
        "frames": out,
        "platform": found,
        "control": sys.argv[3] if len(sys.argv) > 3 else None,
        "tolerances": {
            "logit_median": LOGIT_MEDIAN_TOL,
            "logit_token_p90": LOGIT_TOKEN_P90_TOL,
            "logit_abs": LOGIT_ABS_TOL, "greedy": GREEDY_TOL},
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
