"""The share of its roofline that the device reached while it served
generations, over the traced stretch.

What ran is what the engine COUNTED between the two snapshots around the
profiler trace (``trace_before`` / ``trace_after``): prefill steps, tokens
and prompts, decode steps and rows, cache rows read, assignments routed to
held experts, and the detector's batches by bucket. Their least time is
max(operations / peak FLOP/s, bytes / peak bytes/s), operations and bytes
from ``opsbytes/<config's>.py`` (``steps``) and, for the detector,
``opsbytes/mobilenet_ssd.py``. Prefill steps, decode steps and detector
batches are separate programs, bounded each by its own roof and added (a
compute-bound chunk and a memory-bound decode step bounded together would
read low). The snapshots bracket the profiler's own start and stop, so
they cover more steps than the trace holds: the least time is scaled by
the programs the trace counted over the programs the counters counted,
and the share is that over the trace's device-busy seconds. A trace that
holds MORE programs than were counted is a fault of the counting: it is
reported, not capped.

A server with no generate engine has none of the series: None.
"""

import importlib
import json

from benchmark.harness import BenchFailure
from benchmark.readers.common import peaks_for, prom_delta
from benchmark.readers.prom_delta_ratio import delta


def read(ctx: dict, params: dict):
    tr = ctx.get("device_trace")
    before, after = ctx.get("trace_before"), ctx.get("trace_after")
    if not tr or tr["busy_s"] <= 0 or not before or not after:
        return None

    def d(series, **labels):
        return delta(before, after, {"series": series, "labels": labels})

    if d("evam_generate_steps_total") is None:
        return None
    cfg = ctx["config"]
    shapes = cfg["shapes"]
    peaks = peaks_for(ctx)
    flops_peak = peaks["flops_per_s"][cfg["assumed"]["precision"]]
    bw = peaks["hbm_bytes_per_s"]
    counts = {
        "prefill_steps": d("evam_generate_steps_total", kind="prefill"),
        "prefill_tokens": d("evam_generate_tokens_total", kind="prefill"),
        "prefill_rows": d("evam_generate_latent_rows_read_total",
                          kind="prefill"),
        "decode_steps": d("evam_generate_steps_total", kind="decode"),
        "decode_tokens": d("evam_generate_tokens_total", kind="decode"),
        "decode_rows": d("evam_generate_latent_rows_read_total",
                         kind="decode"),
        "held_assignments": d("evam_moe_held_assignments_total"),
    }
    counts = {k: int(v or 0) for k, v in counts.items()}
    # a prompt ends in the chunk that samples its first token
    prompts = int(prom_delta(before, after,
                             "evam_generate_queue_wait_seconds_count"))
    lm = importlib.import_module(f"benchmark.opsbytes.{cfg['opsbytes']}")
    none = dict.fromkeys(counts, 0)
    # an assignment belongs to the kind of step that routed its token
    tokens = counts["prefill_tokens"] + counts["decode_tokens"]
    held_prefill = (counts["held_assignments"] * counts["prefill_tokens"]
                    // tokens if tokens else 0)
    kinds = {
        "prefill": dict(
            none, prefill_prompts=prompts, sampled_rows=prompts,
            held_assignments=held_prefill,
            **{k: v for k, v in counts.items() if k.startswith("prefill")}),
        "decode": dict(
            none, prefill_prompts=0, sampled_rows=counts["decode_tokens"],
            held_assignments=counts["held_assignments"] - held_prefill,
            **{k: v for k, v in counts.items() if k.startswith("decode")}),
    }
    least_kind, bound = {}, {}
    for kind, counted_kind in kinds.items():
        ob = lm.steps(shapes["model"], **counted_kind)
        t_flops, t_bytes = ob["flops"] / flops_peak, ob["bytes"] / bw
        least_kind[kind] = max(t_flops, t_bytes)
        bound[kind] = "compute" if t_flops >= t_bytes else "memory"
    least_lm = sum(least_kind.values())

    det = importlib.import_module("benchmark.opsbytes.mobilenet_ssd")
    least_det, det_batches = 0.0, 0
    for key, row in after["engines"].items():
        if not key.startswith("detect"):
            continue
        prev = before["engines"].get(key, {}).get("bucket_batches", {})
        for bucket, n in row["bucket_batches"].items():
            n -= prev.get(bucket, 0)
            if n <= 0:
                continue
            o = det.ops_and_bytes(shapes["detector"], int(bucket))
            least_det += n * max(o["flops"] / flops_peak, o["bytes"] / bw)
            det_batches += n
    counted = counts["prefill_steps"] + counts["decode_steps"] + det_batches
    if counted <= 0:
        return None
    # (a rehearsal reduces a RECORDED trace of another run: no relation)
    if tr["steps"] > counted and not ctx["run"].rehearsal:
        raise BenchFailure(
            f"the trace holds {tr['steps']} programs, the counters around "
            f"it counted {counted}: a step ran that no counter saw")
    scale = tr["steps"] / counted
    least = (least_lm + least_det) * scale
    (ctx["run"].out_dir / "lm_roofline.json").write_text(json.dumps({
        "counts": counts, "prompts": prompts, "detector_batches": det_batches,
        "programs_in_trace": tr["steps"], "programs_counted": counted,
        "least_s": least_kind, "least_detector_s": least_det,
        "bound": bound,
        "scale": scale, "busy_s": tr["busy_s"]}, indent=1))
    return 100.0 * least / (tr["busy_s"] * tr["devices"])
