"""Child process of the correctness check of the Nemotron-H describe
configuration.

  python benchmark/reference/nemotron_h_child.py job.json result.json [control]

For each sampled message: the stand-in tokenizer's rendering of its
objects must be its published ``prompt_ids``; then the plain reference
(``nemotron_h_plain``) is teacher-forced over instruction + prompt +
generated ids, one sample at a time, one block's weights alive at a time,
the recurrence token by token and the experts one by one, and its logits at
the generated positions are compared with the published top-8 of every
generated token. The tokenizer restatement and ``check_description`` are
``lm_compare``'s; the LIMITS, and what becomes of a token that a routing
decision flipped, are this model's own (below).

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
``carry`` | ``state`` | ``latent`` | ``act`` | ``topk`` | ``rope``; the
harness gives none) computes the reference as a model the configuration is
NOT: weights rounded to float8_e4m3fn (the nearest precision below the
configuration's); the recurrence's state and the convolution's inputs begun
anew at the first decode step (a state not carried from the chunk to the
decode steps); the state rounded to bfloat16 after every token; both latent
projections dropped (the experts fed the hidden's first 1024 values);
``silu`` for ``relu^2``; 8 experts a token for 22; ``q`` and ``k`` turned
by the config's ``rope_theta``. A reading by hand over a run's saved
``reference_job.json``; all seven come out NOT ok.

What is compared, and why the limits are what they are. Readings on the
chip at the published size (PR 53, PERF.md section 6), under the
configuration's seeding (``assumed`` (6): the decays drawn from [1, 1.5] and
the steps from [0.001, 0.002], a state that remembers for 150-450 tokens):
OURS is the served path (weights and activations bfloat16; scores, delta,
the decay and the state float32; pages, slot state, packed chunks); each
control the reference as the model above. Every limit lies between ours and
the controls' with room on both sides.

Readings (my chip runs, PR 53, call "proofB": the tree as committed), as
median | largest | tokens of 64 over LOGIT_TOKEN_TOL. OURS over the 28
frames of seven runs, each run a seed of its own: 0.0116-0.0145 |
0.091-0.176 | 0. Over the 4 frames of the first of them: the reference at
bfloat16 ACTIVATIONS alone (``acts``: no cache, no kernel, float32
products) 0.0139-0.0155 | 0.135-0.151 | 0 against the published logits, as
ours: the precision, not the path. STATE 0.0451-0.0493 | 0.195-0.282 | 0;
LATENT 0.111-0.119 | 0.59-0.72 | 30-36; ROPE 0.117-0.127 | 0.54-0.61 |
36-44; TOPK 0.122-0.137 | 0.65-0.80 | 36-44; WEIGHTS 0.169-0.184 |
0.78-1.19 | 59-60; CARRY 1.11-1.22 | 4.28-4.34 | 63; ACT 1.57-1.69 |
4.79-5.32 | 64.

* LOGIT_MEDIAN_TOL 0.03: the median over a frame's 512 published logits
  (64 tokens x 8) of |published - reference|: 2.07 times ours' largest and
  0.67 of the nearest control's least (STATE). It is the limit that
  refuses a state kept in bfloat16 (the state rounded after EVERY token:
  the nearest precision below the float32 the configuration states), and
  the only one that does: that rounding moves no single token by 0.3. It
  was 0.045 under the first seeding (``A`` to 16, steps to 0.1: a state
  that forgot in some 25 tokens), where STATE read 0.016-0.019, as ours,
  and passed: the review sent that back, and the seeding, which is
  ``assumed`` and not published, moved (``mamba_a_init_max``,
  ``mamba_dt_init_max``).
* LOGIT_ABS_TOL 0.4: no logit may differ by more, flipped or not: 2.3
  times ours and 0.74 of the least of the controls it refuses (ROPE's
  0.54; STATE's largest, 0.20-0.28, it does not refuse).

* Routing is DISCRETE: a token's experts are the 22 best of ``score +
  bias`` over 512, and where the 22nd and 23rd lie within rounding the
  bfloat16 path and the float32 reference pick different experts; this chip
  holds an eighth of them, so a flipped decision adds or removes one held
  term of about 2.75 of that token, in 5 layers, with no defect (all the
  routed terms changed at once, LATENT, move a token by 0.28 in the
  median). A token is FLIPPED where any of its 8 logits differs by more
  than LOGIT_TOKEN_TOL (0.3: ours 0.16 at most), and of a frame's tokens
  at most FLIP_SHARE (a quarter) may be: ours none, the controls 39-100 %.
  Nothing is excused.
* The greedy choice: on unflipped tokens the published id's reference
  logit is within 2 x LOGIT_TOKEN_TOL of the reference's own best.
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

LOGIT_MEDIAN_TOL = 0.03
LOGIT_TOKEN_TOL = 0.3
FLIP_SHARE = 0.25
LOGIT_ABS_TOL = 0.4
READ_AT_TOP_K = 22
#: what the ``topk`` control chooses in place of ``num_experts_per_tok``
CONTROL_TOP_K = 8
CONTROLS = ("weights", "carry", "state", "latent", "act", "topk", "rope")
#: a reading, not a control: the reference at the served path's own
#: activation precision (it has to come out ok)
READINGS = ("acts",)
#: part of every entry's key, so one fixed path
COMPILE_CACHE_DIR = REPO / "benchmark_out" / "reference_cache" / "nemotron_h"


def compare_logits(desc: dict, ref_logits: np.ndarray,
                   scale: float = 1.0) -> tuple[list, dict]:
    """``ref_logits`` [generated tokens, vocab]: the reference's row for
    each generated position. ``scale`` widens the two limits that a
    flipped routing decision moves, a token's and the largest
    (``limits_scale``); the median's holds for every model."""
    median_tol, token_tol, abs_tol = (
        LOGIT_MEDIAN_TOL, scale * LOGIT_TOKEN_TOL, scale * LOGIT_ABS_TOL)
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
    sum is 22 experts' and one of them carries a 22nd of 5. A model that
    chooses fewer (the rehearsal's tiny one: 6 of 16, 2 held, under the
    same factor 5) puts four times the weight on one decision, and a
    flipped one moves a token's logits by as much more: a token's limit and
    the largest are 4 times as wide there, the median's stays. Read over
    the tiny model on a CPU (my readings, PR 53: 4 prompts of 12 tokens and
    the tests' 30-token runs): ours 0.007-0.014 in the median, 0.08 at most
    where no decision flipped and 0.64 and 1.10 in the two tokens where one
    did; every control but ``state`` 0.064-0.73 in the median (a state of
    16 over 60 tokens holds nothing that a rounding could lose: ``state``
    is read at the published size alone)."""
    return 1.0 if model["num_experts_per_tok"] >= READ_AT_TOP_K else 4.0


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

    from benchmark.reference import nemotron_h_plain as ref
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
                   # the first decode step feeds the token the chunk sampled
                   "carry": {"carried": False, "fresh_at": first + 1},
                   "state": {"state_dtype": jnp.bfloat16},
                   "latent": {"latent": False},
                   "act": {"act": "silu"},
                   "topk": {"top_k": CONTROL_TOP_K},
                   "rope": {"rotated": True},
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
