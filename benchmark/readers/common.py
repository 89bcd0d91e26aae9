"""Arithmetic the readers share: counter deltas between two snapshots."""

from __future__ import annotations

from benchmark.harness import BenchFailure


def window_snapshots(ctx: dict, params: dict) -> tuple[dict, dict]:
    """The pair of snapshots a reader works between: the measured window's
    by default, the traced stretch's where the metric file says so."""
    if params.get("over") == "trace":
        return ctx["trace_before"], ctx["trace_after"]
    return ctx["before"], ctx["after"]


def engine_delta(before: dict, after: dict) -> dict:
    """Summed over the engine rows (one configuration preloads one
    engine): batches, items, clocked batches, per-bucket batches and the
    stage clock's seconds (``stage_ms`` is a mean over ``stage_batches``
    since boot, so mean x batches is the sum)."""
    out = {"batches": 0, "items": 0, "stage_batches": 0,
           "bucket_batches": {}, "stage_ms_sum": {}}
    for key, row in after["engines"].items():
        prev = before["engines"].get(key, {})
        out["batches"] += row["batches"] - prev.get("batches", 0)
        out["items"] += row["items"] - prev.get("items", 0)
        b1, b0 = row["stage_batches"], prev.get("stage_batches", 0)
        out["stage_batches"] += b1 - b0
        for bucket, n in row["bucket_batches"].items():
            d = n - prev.get("bucket_batches", {}).get(bucket, 0)
            if d:
                out["bucket_batches"][bucket] = (
                    out["bucket_batches"].get(bucket, 0) + d)
        ms0 = prev.get("stage_ms") or {}
        for stage, ms in (row.get("stage_ms") or {}).items():
            out["stage_ms_sum"][stage] = (
                out["stage_ms_sum"].get(stage, 0.0)
                + ms * b1 - ms0.get(stage, 0.0) * b0)
    return out


def prom_delta(before: dict, after: dict, series: str) -> float:
    return after["metrics"].get(series, 0.0) - before["metrics"].get(
        series, 0.0)


def peaks_for(ctx: dict) -> dict:
    import json

    table = json.loads(ctx["peaks_file"].read_text())["device_kinds"]
    kind = ctx["device"]["kind"]
    if kind not in table:
        raise BenchFailure(
            f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]
