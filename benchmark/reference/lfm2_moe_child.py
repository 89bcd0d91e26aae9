"""Child process of the correctness check of the LFM2-MoE describe
configuration.

  python benchmark/reference/lfm2_moe_child.py job.json result.json [control]

For each sampled message: the stand-in tokenizer's rendering of its
objects must be its published ``prompt_ids``; then the plain reference
(``lfm2_moe_plain``) is teacher-forced over instruction + prompt +
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
a reference compiles for two minutes (PERF.md section 6, PR 34); so it
keeps one of its own, at a fixed path under ``benchmark_out/`` beside the
installed models, cold in a checkout's first run only. It holds the chip,
so it asks the kernel to end it with its parent, and leaves without the
runtime's teardown once the result is written: it never outlives a run.
``control`` (``weights`` | ``rope`` | ``taps``; the harness gives none)
computes the reference as a model the configuration is NOT: weights rounded
to float8_e4m3fn (the nearest precision below the configuration's), the
rotary positions left out, every convolution given zeros in place of the
two inputs before each generated token (what a lost or wrong slot row does
to a decode step). A reading by hand over a run's saved
``reference_job.json``; all three must come out NOT ok.

What is compared, and why the limits are what they are. Readings on the
chip at the published size (PR 40, PERF.md section 6: 20 frames of the
5 runs of calls c1 and c2, 1280 tokens; the controls over the 4 frames of
one of them): OURS is the served path (weights and activations bfloat16;
the convolution's sums, the softmax and the router's scores float32);
WEIGHTS the reference with weights rounded to float8_e4m3fn, ROPE without
the rotary positions, TAPS with the convolutions' two earlier inputs lost
before every generated token. Every limit lies between ours and the
controls' with room on both sides. Ours reads higher than the other
language models' (Kimi's median 0.03-0.05): 24 layers deep, 22 of them
routed, and the reference at bfloat16 ACTIVATIONS alone (``acts``, no
cache, no kernel, float32 products) differs from the plain one by
0.111-0.124 in the median, 0.70-0.79 at most and 0.45-0.49 at a frame's
90th percentile (call c3, the 4 frames of one run): the precision, not
the path.

* LOGIT_MEDIAN_TOL: the median over a frame's 512 published logits (64
  tokens x 8) of |published - reference|. Ours 0.113-0.164; WEIGHTS
  0.855-0.948; ROPE 0.980-1.031; TAPS 2.97-3.02. The limit is 2.4 times
  ours and under half of WEIGHTS'.
* Routing is DISCRETE: a token's experts are the 4 best of ``score +
  bias`` over 32, and where the 4th and 5th lie within rounding the
  bfloat16 path and the float32 reference pick different experts; this
  chip holds 16 of them in 22 layers and an expert carries a quarter of a
  layer's routed sum, so a flipped decision adds or removes a local term
  and the token's logits differ with no defect: over 0.3 in 28-49 of a
  frame's 64 tokens, at a frame's 90th percentile by 0.50-0.63. A token is
  FLIPPED where any of its 8 logits differs by more than LOGIT_TOKEN_TOL
  (0.8), and of a frame's tokens at most FLIP_SHARE may be: ours 0-4 of 64
  (6.25 % in the worst frame); WEIGHTS, ROPE all 64; TAPS 63 (the first
  token is the prefill's, which keeps its taps). Nothing is excused
  (``kimi_linear_child``'s reasons hold here: with 16 held experts in each
  of 22 layers nearly every token has a decision within rounding
  somewhere).
* LOGIT_ABS_TOL: no logit may differ by more, flipped or not. Ours
  0.64-1.35; WEIGHTS 2.38-2.80; ROPE 2.85-3.16; TAPS 5.4-6.1.
* The greedy choice: on unflipped tokens the published id's reference
  logit is within 2 x LOGIT_TOKEN_TOL of the reference's own best (ours
  0.06-0.37).

ROPE is refused because the configuration seeds the query and key head
norms' gains around ``qk_norm_gain`` (under ``assumed``): at a gain of 1 a
seeded score has a deviation near 1, a softmax over 2.4 k rows is nearly
flat and a reference WITHOUT rotation reads like the model (Kimi's lesson,
PERF.md section 6, PR 34). A wrong page table, a wrong position or a wrong
merge of the prefix with a row's own pages moves a logit as ROPE does: by
which rows a query weighs. TAPS is what a wrong slot, a slot row not
written back, or a snapshot not restored does.
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

LOGIT_MEDIAN_TOL = 0.4
LOGIT_TOKEN_TOL = 0.8
FLIP_SHARE = 0.25
LOGIT_ABS_TOL = 2.0
READ_AT_LAYERS = 24
CONTROLS = ("weights", "rope", "taps")
#: a reading, not a control: the reference at the served path's own
#: activation precision (it has to come out ok)
READINGS = ("acts",)
#: part of every entry's key, so one fixed path
COMPILE_CACHE_DIR = REPO / "benchmark_out" / "reference_cache" / "lfm2_moe"


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
    """The limits were read on the published model's ``READ_AT_LAYERS``
    layers, where the roundings and flipped routing decisions of 24 layers
    add up; a shallower model (a rehearsal's tiny one: 10 layers)
    accumulates fewer, as a random walk does: the three limits in units of
    a logit shrink by the square root of the depth's ratio. Read over the
    tiny model on a CPU (7 prompts, 12 tokens each): ours 0.03-0.06 in the
    median and 0.12-0.47 at most for limits of 0.26 and 1.29; the nearest
    term taken away (the renormalisation, one held expert) 0.15-0.26 in
    the median with a quarter of its tokens over 0.58-0.97."""
    return min(1.0, model["num_hidden_layers"] / READ_AT_LAYERS) ** 0.5


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

    from benchmark.reference import lfm2_moe_plain as ref
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
            # a decode step's positions: the prompt's last token is
            # the prefill's, every generated token fed back a step's
            how = {None: {},
                   "weights": {"weight_dtype": jnp.float8_e4m3fn},
                   "rope": {"rotated": False},
                   "acts": {"act_dtype": jnp.bfloat16},
                   "taps": {"taps_lost_from": first + 1}}[control]
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
