"""One kernel's share of the traced stretch, from the reduced trace's
``device_ops`` (operation -> summed device seconds, the ten largest).

``params.op`` is the kernel's name: every operation whose name starts with
it is the kernel (one name per loop body that holds it). The value is

* 100 x their summed seconds over the trace's device-busy seconds, or
* with ``"roofline": true``, 100 x the kernel's least time over their
  summed seconds. The least time is ``opsbytes/<config's>.py``'s
  ``scan_ops_and_bytes`` for the prefill tokens the engine counted between
  the snapshots around the trace, in every layer that runs the kernel,
  bounded by max(operations / peak FLOP/s, bytes / peak bytes/s) and
  scaled, as ``lm_roofline.py`` scales, by the programs the trace holds
  over the programs the counters counted (the snapshots bracket the
  profiler's start and stop).

``device_ops`` keeps ten operations. ``params.names`` says how many names
the kernel's executions carry in this configuration; where the list holds
another number (a name fell off the list, or the program has no such
kernel, as a build from before it has not), the sum would be short:
None, with a note, and the metric is left out of the line.
"""

import importlib

from benchmark.harness import note
from benchmark.readers.common import peaks_for
from benchmark.readers.prom_delta_ratio import delta


def _counted_programs(before: dict, after: dict) -> int:
    """Programs the counters saw between the snapshots: generate steps and
    the detector's batches (as ``lm_roofline.py`` counts them)."""
    n = int(delta(before, after, {"series": "evam_generate_steps_total",
                                  "labels": {}}) or 0)
    for key, row in after["engines"].items():
        if not key.startswith("detect"):
            continue
        prev = before["engines"].get(key, {}).get("bucket_batches", {})
        n += sum(max(0, c - prev.get(b, 0))
                 for b, c in row["bucket_batches"].items())
    return n


def read(ctx: dict, params: dict):
    tr = ctx.get("device_trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    mine = [s for name, s in tr["device_ops"]
            if name.startswith(params["op"])]
    if len(mine) != int(params.get("names", 1)):
        note(f"{params['op']}: {len(mine)} of its {params.get('names', 1)} "
             f"names are among the trace's {len(tr['device_ops'])} largest "
             "operations; no share is reported")
        return None
    seconds = sum(mine)
    if not params.get("roofline"):
        return 100.0 * seconds / (tr["busy_s"] * tr["devices"])
    before, after = ctx.get("trace_before"), ctx.get("trace_after")
    if not before or not after or seconds <= 0:
        return None
    tokens = delta(before, after, {"series": "evam_generate_tokens_total",
                                   "labels": {"kind": "prefill"}})
    counted = _counted_programs(before, after)
    if not tokens or counted <= 0:
        return None
    cfg = ctx["config"]
    model = cfg["shapes"]["model"]
    ob = importlib.import_module(f"benchmark.opsbytes.{cfg['opsbytes']}")
    peaks = peaks_for(ctx)
    one = ob.scan_ops_and_bytes(model, int(tokens))
    least = int(params["layers"]) * max(
        one["flops"] / peaks["flops_per_s"][cfg["assumed"]["precision"]],
        one["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * (tr["steps"] / counted) / seconds
