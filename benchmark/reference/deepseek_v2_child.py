"""Child process of the correctness check of the describe configuration.

  python benchmark/reference/deepseek_v2_child.py job.json result.json

For each sampled message: the stand-in tokenizer's rendering of its
objects must be its published ``prompt_ids``; then the plain reference
(``deepseek_v2_plain``) is teacher-forced over instruction + prompt +
generated ids, one sample at a time, attention in blocks of queries, and
its logits at the generated positions are compared with the published ones
(``lm_compare``).

By the time this runs the harness has stopped the server, so the chip is
free: where the configuration's ``shapes.reference_platform`` says
``tpu`` the reference runs THERE (float32 at ``highest`` precision: a CPU
pass over 3.8 G float32 parameters and 2.4 k tokens a sample does not fit
the harness's 600 s) and fails if it finds none; a rehearsal says ``cpu``.
Nothing of the program is imported.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def main() -> int:
    job = json.loads(Path(sys.argv[1]).read_text())
    shapes = job["shapes"]
    platform = shapes["reference_platform"]
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        os.environ.pop("JAX_PLATFORMS", None)
    import jax
    import numpy as np

    from benchmark.reference import deepseek_v2_plain as ref
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
            logits, margins = ref.forward(model, tokens, rows=rows,
                                          margins=True)
            problems, row["logits"] = lm_compare.compare_logits(
                desc, np.asarray(logits), margins)
        row["problems"] = problems
        row["seconds"] = round(time.time() - t0, 1)
        out.append(row)
    Path(sys.argv[2]).write_text(json.dumps({
        "ok": all(not r["problems"] for r in out),
        "frames": out,
        "platform": found,
        "tolerances": {
            "logit_median": lm_compare.LOGIT_MEDIAN_TOL,
            "logit_token": lm_compare.LOGIT_TOKEN_TOL,
            "route_margin": lm_compare.ROUTE_MARGIN,
            "flip_share": lm_compare.FLIP_SHARE,
            "logit_abs": lm_compare.LOGIT_ABS_TOL},
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
