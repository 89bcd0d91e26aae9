"""``lm_roofline.py``'s share of the roofline with the experts a step READ
counted, not bounded: its arithmetic, unchanged, over a configuration's
``opsbytes`` module whose ``steps`` also takes ``experts_hit``.

``lm_roofline.py`` hands ``steps`` the assignments to held experts, and a
module can bound the experts read only by ``min(assignments, steps x
expert layers x held)``. Where a step's assignments outnumber the held
experts and still miss some (Laguna: 512 assignments a layer over 256
experts reach about 222), that bound counts expert bytes nothing read. The
engine counts, per step and expert layer, the held experts that received
an assignment (``evam_moe_held_experts_hit_total{kind}``): this reader
takes that counter's delta between the snapshots around the trace, per
kind of step, and hands it to ``steps`` as ``experts_hit`` beside what
``lm_roofline.py`` hands it. A server without the series (a build from
before it): None, as ``lm_roofline.py``."""

import importlib
import sys
import types

from benchmark.readers import lm_roofline
from benchmark.readers.prom_delta_ratio import delta

_SHIM = "benchmark.opsbytes._steps_with_experts_hit"


def read(ctx: dict, params: dict):
    before, after = ctx.get("trace_before"), ctx.get("trace_after")
    if not before or not after:
        return None
    hit = {kind: delta(before, after, {
        "series": "evam_moe_held_experts_hit_total",
        "labels": {"kind": kind}}) for kind in ("prefill", "decode")}
    if None in hit.values():
        return None
    cfg = ctx["config"]
    base = importlib.import_module(f"benchmark.opsbytes.{cfg['opsbytes']}")
    shim = types.ModuleType(_SHIM)

    def steps(model, **counted):
        # ``lm_roofline.py`` bounds each kind of step by itself
        kind = "prefill" if counted["prefill_steps"] else "decode"
        return base.steps(model, **counted, experts_hit=int(hit[kind]))

    shim.steps = steps
    sys.modules[_SHIM] = shim
    try:
        return lm_roofline.read(
            {**ctx, "config": {**cfg, "opsbytes": _SHIM.rsplit(".", 1)[1]}},
            params)
    finally:
        del sys.modules[_SHIM]
