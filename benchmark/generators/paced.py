"""Open-loop paced cameras: N ``realtime`` streams at rates a fixed step
apart around a mean, so that inside one window every pair of streams
passes through every relative phase.

Frame k of stream i is due at ``start_time_i + k / f_i``: ``start_time``
is read from the instance's status payload (``time.time()`` of the server,
same machine, unrounded) and k is the message's ``timestamp`` over the
stream's period. Latency is arrival at the sink minus that due time, pooled
over every frame due in the window; a frame that never arrives counts as
later than every frame that did.
"""

from __future__ import annotations

import json
import math
import statistics
import time

from benchmark.generators import common
from benchmark.harness import Run, note


def rates_for(traffic: dict) -> list[float]:
    n = int(traffic["streams"])
    return [round(traffic["mean_fps"] + (i - (n - 1) / 2)
                  * traffic["fps_step"], 6) for i in range(n)]


def drive(run: Run) -> list[dict]:
    tr = run.traffic
    streams = common.start_streams(run, rates_for(tr), realtime=True)
    common.settle_and_open(run, streams)
    run.run_window()
    overdue_s = float(tr.get("overdue_s", 2.0))
    time.sleep(max(0.0, run.window[1] + overdue_s - time.time()))
    return streams


def reduce(run: Run, streams: list[dict]) -> dict:
    """After the streams are stopped: latencies, misses, side files."""
    tr = run.traffic
    t_open, t_close = run.window
    overdue_ms = float(tr.get("overdue_s", 2.0)) * 1e3
    rows_by_topic, faults = common.parse_messages(run, streams)
    lat: list[float] = []
    per_stream = []
    per_second: dict[int, list[float]] = {}
    in_window: dict[str, list] = {}
    attempted = missed = 0
    for s in streams:
        f, a = s["fps"], s["start_time"]
        k_lo = math.ceil((t_open - a) * f)
        k_hi = math.ceil((t_close - a) * f) - 1
        got = {}
        for t, k, msg in rows_by_topic[s["topic"]]:
            if k_lo <= k <= k_hi:
                got[k] = (t - (a + k / f)) * 1e3
                in_window.setdefault(s["topic"], []).append((t, k, msg))
        n_due = k_hi - k_lo + 1
        attempted += n_due
        missed += n_due - len(got)
        mine = list(got.values())
        lat += mine
        for k, v in got.items():
            per_second.setdefault(int(a + k / f - t_open), []).append(v)
        per_stream.append({
            "stream": s["index"], "fps": f, "due": n_due,
            "arrived": len(got),
            "median_ms": statistics.median(mine) if mine else None})
    worst = max(lat) if lat else overdue_ms
    pooled = sorted(lat) + [max(worst, overdue_ms)] * missed
    client = {
        "latency_p50_ms": common.percentile(pooled, 0.50),
        "latency_p95_ms": common.percentile(pooled, 0.95),
        "latency_p99_ms": common.percentile(pooled, 0.99),
        "latency_worst_ms": pooled[-1],
        "on_time_40ms_share": 100.0 * sum(1 for v in lat if v <= 40.0)
        / max(attempted, 1),
        "objects_per_frame": statistics.fmean(
            len(m["objects"]) for rows in in_window.values()
            for _, _, m in rows) if in_window else 0.0,
    }
    (run.out_dir / "latency_breakdown.json").write_text(json.dumps({
        "workload": run.cell["name"], "seed": run.seed,
        "window_s": run.seconds, "pooled": client,
        "posts_asked_again": run.refused_posts,
        "attempted": attempted, "missed": missed,
        "per_stream": per_stream,
        "per_second_median_ms": [
            statistics.median(per_second[sec]) if sec in per_second else None
            for sec in range(int(run.seconds))],
    }, indent=1))
    note(f"paced: {attempted} frames due, {missed} missed, p50 "
         f"{client['latency_p50_ms']:.2f} ms p95 "
         f"{client['latency_p95_ms']:.2f} ms")
    return {
        "attempted": attempted, "failed": missed, "faults": faults,
        "end_to_end": {"latency_p50_ms": client["latency_p50_ms"],
                       "latency_p95_ms": client["latency_p95_ms"]},
        "client": client,
        "sample": common.pick_sample(run, in_window),
    }
