"""The device step's share of its roofline over the traced stretch.

Numerator and denominator come from the same trace. Every execution of the
step program is one event on the device's "XLA Modules" line; the batch it
ran is the leading dimension most of its own operations' outputs carry
(``trace_reduce.py``: ``steps_by_batch``). A step over b frames needs at
least t_b = max(operations / peak FLOP/s, compulsory bytes / peak bytes/s),
with operations and bytes from the configuration's ``opsbytes`` function
and the peaks from ``peaks.json`` by ``device_kind``. The share is
100 x sum(n_b t_b) / device-busy seconds of the trace. ``bound`` in the
side file says which of the two limits is the larger.
"""

import importlib
import json

from benchmark.readers.common import peaks_for


def read(ctx: dict, params: dict):
    tr = ctx.get("device_trace")
    if not tr or tr["busy_s"] <= 0 or not tr.get("steps_by_batch"):
        return None
    peaks = peaks_for(ctx)
    cfg = ctx["config"]
    fn = importlib.import_module(
        f"benchmark.opsbytes.{cfg['opsbytes']}").ops_and_bytes
    flops_peak = peaks["flops_per_s"][cfg["assumed"]["precision"]]
    least = 0.0
    bound = {}
    for batch, n in tr["steps_by_batch"].items():
        ob = fn(cfg["shapes"], int(batch))
        t_c = ob["flops"] / flops_peak
        t_m = ob["bytes"] / peaks["hbm_bytes_per_s"]
        least += n * max(t_c, t_m)
        bound[batch] = "compute" if t_c >= t_m else "memory"
    (ctx["run"].out_dir / "step_roofline.json").write_text(json.dumps(
        {"steps_by_batch": tr["steps_by_batch"], "bound": bound,
         "least_s": least, "busy_s": tr["busy_s"]}, indent=1))
    return 100.0 * least / (tr["busy_s"] * tr["devices"])
