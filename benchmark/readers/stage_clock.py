"""Mean milliseconds per clocked batch of the named stages of the
steady-state stage clock (``/engines`` ``stage_ms`` over
``stage_batches``), between the two snapshots. ``params.stages`` lists the
stages summed; ``"all"`` is every stage."""

from benchmark.readers.common import engine_delta, window_snapshots


def read(ctx: dict, params: dict):
    d = engine_delta(*window_snapshots(ctx, params))
    if d["stage_batches"] <= 0:
        return None
    stages = params["stages"]
    sums = d["stage_ms_sum"]
    if stages == "all":
        stages = list(sums)
    return sum(sums.get(s, 0.0) for s in stages) / d["stage_batches"]
