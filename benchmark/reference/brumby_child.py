"""Child process of the correctness check of the Brumby describe
configuration.

  python benchmark/reference/brumby_child.py job.json result.json [control]

For each sampled message: the stand-in tokenizer's rendering of its
objects must be its published ``prompt_ids``; then the plain reference
(``brumby_plain``: the mixer in its ATTENTION form, float32 at ``highest``,
no state) is teacher-forced over instruction + prompt + generated ids, one
sample at a time, one layer's weights alive at a time, and its logits at the
generated positions are compared with the published top-8 of every generated
token. LOGITS are compared, not tokens. The tokenizer restatement and
``check_description`` are ``lm_compare``'s; the LIMITS are this model's own
(below).

By the time this runs the harness has stopped the server, so the chip is
free: where the configuration's ``shapes.reference_platform`` says ``tpu``
the reference runs THERE and fails if it finds none; a rehearsal says
``cpu``. Nothing of the program is imported. The harness hands its children
no compile cache, and op by op on the chip a reference compiles for minutes;
so it keeps one of its own, at a fixed path under ``benchmark_out/`` beside
the installed models, cold in a checkout's first run only. It holds the
chip, so it asks the kernel to end it with its parent, and leaves without
the runtime's teardown once the result is written: it never outlives a run.
``control`` (``state`` | ``degree`` | ``gate`` | ``norm`` | ``rope`` |
``weights``; the harness gives none) computes the reference as a model the
configuration is NOT: the mixer in its RECURRENT form, the served path's,
with the state and its sum rounded to bfloat16 after every token (the
nearest precision below the float32 the configuration states); degree 4 for
2; every decay 1; no division by the weights' sum; no rotation; weights
rounded to float8_e4m3fn. A reading by hand over a run's saved
``reference_job.json``; all six come out NOT ok. ``acts`` (a READING, not a
control: the reference at bfloat16 activations, which has to come out ok)
and ``recurrent`` (the recurrent form in float32: the two forms agree) are
read the same way.

What is compared, and why the limits are what they are. OURS is the served
path (weights and activations bfloat16; the gate, the scores, the state and
its sum float32; slot state, packed chunks, no cache rows); each control the
reference as the model above, under the configuration's seeding
(``assumed`` (6): decays that remember 600-1800 tokens). Every limit lies
between ours and the controls' with room on both sides.

Readings (my chip runs, PR 57, calls "call2" and "final": the published
size, the configuration's seeding), as median | largest | the greedy
choice's shortfall. OURS over the 28 frames of seven runs, each run a seed
of its own (three of them from the tree as committed): 0.0136-0.0163 |
0.059-0.087 | 0.0001-0.066. Over the first 2 frames
of the first of them: the reference at bfloat16 ACTIVATIONS alone (``acts``:
no state, no kernel, float32 products) 0.0149-0.0164 | 0.074-0.075 against
the published logits, as ours (0.0125-0.0132 | 0.058 against the plain
reference itself): the precision, not the path; the RECURRENT form in
float32 0.0135-0.0143 | 0.075-0.077 (0.0004-0.0005 | 0.0024 against the
attention form: the two forms are one function). STATE 0.0857-0.0924 |
0.42-0.47 | 0.36-0.51; WEIGHTS 0.394-0.406 | 1.68-2.15 | 1.36-1.78; DEGREE
2.09-2.14 | 6.1-6.5; GATE 3.59-3.61 | 7.3-7.9; ROPE 4.03-4.07 | 7.7-9.0;
NORM 5.75-5.90 | 9.7-10.3 (all 64 tokens of both frames over
LOGIT_TOKEN_TOL in each of the last five, 55 in STATE).

Under the FIRST seeding (decays that remember 150-450 tokens, ISSUE.md's;
call "call1") ours read 0.0132-0.0142 | 0.064-0.073 and the state's
rounding, read against the plain reference itself (call "explore2"), moved
the median by 0.0283 where bfloat16 activations move it by 0.0141: a limit
between the two would have had a third of room each way. The seeding is
``assumed`` and not published, and it moved (``gate_memory_min`` /
``gate_memory_max``: the configuration file's ``assumed.seeding`` has the
four ranges read).

* LOGIT_MEDIAN_TOL 0.035: the median over a frame's 512 published logits (64
  tokens x 8) of |published - reference|: 2.15 times ours' largest and 0.41
  of the nearest control's least (STATE). It is the limit that is sure to
  refuse a state kept in bfloat16 (rounded after EVERY token: the nearest
  precision below the float32 the configuration states).
* LOGIT_ABS_TOL 0.25: no logit may differ by more: 2.9 times ours' largest
  and 0.6 of the least of the controls' (STATE's 0.42, which it refuses too;
  WEIGHTS' 1.68).
* The greedy choice: the published id's reference logit is within 2 x
  LOGIT_TOKEN_TOL (0.3) of the reference's own best (each of the two may be
  off by one LOGIT_TOKEN_TOL, 0.15: twice the most that rounding moved one
  of ours' logits): ours 0.066 at most, STATE 0.36 at least.
  Nothing in this model is discrete: no token is excused.
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

LOGIT_MEDIAN_TOL = 0.035
LOGIT_TOKEN_TOL = 0.15
LOGIT_ABS_TOL = 0.25
CONTROLS = ("state", "degree", "gate", "norm", "rope", "weights")
#: readings, not controls: the reference at the served path's own
#: activation precision, and in the served path's form (both come out ok)
READINGS = ("acts", "recurrent")
#: what the ``degree`` control raises the scores to in place of 2
CONTROL_DEGREE = 4
#: part of every entry's key, so one fixed path
COMPILE_CACHE_DIR = REPO / "benchmark_out" / "reference_cache" / "brumby"


def compare_logits(desc: dict, ref_logits: np.ndarray) -> tuple[list, dict]:
    """``ref_logits`` [generated tokens, vocab]: the reference's row for
    each generated position."""
    top = np.asarray(desc["top_logits"], np.float64)
    want = np.take_along_axis(ref_logits.astype(np.float64),
                              np.asarray(desc["top_ids"]), axis=1)
    diff = np.abs(top - want)
    per_token = diff.max(axis=1)
    short = ref_logits.max(axis=1) - want[:, 0]
    stats = {"median": float(np.median(diff)), "max": float(diff.max()),
             "tokens": int(len(per_token)),
             "over_token_tol": int((per_token > LOGIT_TOKEN_TOL).sum()),
             "token_p90": float(np.quantile(per_token, 0.9)),
             "greedy_short": float(short.max()),
             # per generated token, for whoever sets the limits anew
             "per_token": [round(float(v), 4) for v in per_token]}
    problems = []
    if not np.isfinite(top).all():
        problems.append("a published logit is not finite")
    if stats["median"] > LOGIT_MEDIAN_TOL:
        problems.append(
            f"logits differ from the reference's by {stats['median']:.4f} "
            f"in the median (limit {LOGIT_MEDIAN_TOL})")
    if stats["max"] > LOGIT_ABS_TOL:
        problems.append(f"a logit differs by {stats['max']:.3f} "
                        f"(limit {LOGIT_ABS_TOL})")
    bad = short > 2 * LOGIT_TOKEN_TOL
    if bad.any():
        problems.append(
            f"{int(bad.sum())} greedy choices fall short of the reference's "
            f"best logit by up to {float(short[bad].max()):.3f}")
    return problems, stats


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

    from benchmark.reference import brumby_plain as ref
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
    how = {None: {},
           "state": {"state_dtype": jnp.bfloat16},
           "degree": {"degree": CONTROL_DEGREE},
           "gate": {"gated": False},
           "norm": {"normalised": False},
           "rope": {"rotated": False},
           "weights": {"weight_dtype": jnp.float8_e4m3fn},
           "acts": {"act_dtype": jnp.bfloat16},
           "recurrent": {"state_dtype": jnp.float32}}[control]
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
            logits = ref.forward(model, tokens, rows=rows, **how)
            problems, row["logits"] = compare_logits(desc, np.asarray(logits))
            if control in READINGS:
                # the reading against the plain reference itself, at the
                # published ids: what that precision or form alone moves
                plain = {**desc, "top_logits": np.take_along_axis(
                    np.asarray(ref.forward(model, tokens, rows=rows)),
                    np.asarray(desc["top_ids"]), axis=1).tolist()}
                _, row["against_plain"] = compare_logits(
                    plain, np.asarray(logits))
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
            "logit_abs": LOGIT_ABS_TOL},
    }, indent=1))
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
