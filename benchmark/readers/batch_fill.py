"""Items per batch between the two snapshots (``/engines`` ``items`` over
``batches``)."""

from benchmark.readers.common import engine_delta, window_snapshots


def read(ctx: dict, params: dict):
    d = engine_delta(*window_snapshots(ctx, params))
    if d["batches"] <= 0:
        return None
    return d["items"] / d["batches"]
