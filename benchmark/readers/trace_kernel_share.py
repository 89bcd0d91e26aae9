"""A kernel's share of the traced stretch where ``trace_op_share.py``
cannot read it: a kernel of the DECODE steps, whose executions carry one
name a decode bucket (the bucket's rows are in the operand's shape), and
whose least time is over the decode tokens.

``params.op`` is the kernel's name: every operation among the reduced
trace's ``device_ops`` whose name starts with it is the kernel, however many
names that is (at least one: the steady state runs one or two buckets, and a
name that fell off the ten would be a bucket that hardly ran). The value is

* 100 x their summed seconds over the trace's device-busy seconds, or
* with ``"roofline": true``, 100 x the kernel's least time over their
  summed seconds: ``opsbytes/<config's>.py``'s ``scan_ops_and_bytes(model,
  tokens, kernel=params.op)`` for the tokens of ``params.kind`` (``decode``
  | ``prefill``) that the engine counted between the snapshots around the
  trace, in each of ``params.layers`` layers, bounded by max(operations /
  peak FLOP/s, bytes / peak bytes/s) and scaled as ``trace_op_share.py``
  scales.

A program without the kernel (a build from before it) has no such
operation: None, and the metric is left out of the line.
"""

import importlib

from benchmark.readers.common import peaks_for
from benchmark.readers.prom_delta_ratio import delta
from benchmark.readers.trace_op_share import _counted_programs


def read(ctx: dict, params: dict):
    tr = ctx.get("device_trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    seconds = sum(s for name, s in tr["device_ops"]
                  if name.startswith(params["op"]))
    if seconds <= 0:
        return None
    if not params.get("roofline"):
        return 100.0 * seconds / (tr["busy_s"] * tr["devices"])
    before, after = ctx.get("trace_before"), ctx.get("trace_after")
    if not before or not after:
        return None
    tokens = delta(before, after, {"series": "evam_generate_tokens_total",
                                   "labels": {"kind": params["kind"]}})
    counted = _counted_programs(before, after)
    if not tokens or counted <= 0:
        return None
    cfg = ctx["config"]
    ob = importlib.import_module(f"benchmark.opsbytes.{cfg['opsbytes']}")
    peaks = peaks_for(ctx)
    one = ob.scan_ops_and_bytes(cfg["shapes"]["model"], int(tokens),
                                kernel=params["op"])
    least = int(params["layers"]) * max(
        one["flops"] / peaks["flops_per_s"][cfg["assumed"]["precision"]],
        one["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * (tr["steps"] / counted) / seconds
