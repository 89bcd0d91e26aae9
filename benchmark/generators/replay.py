"""Closed-loop replay: N free-running streams (``realtime`` off), endless
for the window. Each stream keeps at most the program's own 4 frames in
flight, so the served rate is what the box sustains with N clients that
each wait for a reply. ``frames_per_s`` is the schema-valid messages, one
per frame, that arrive at the sink inside the window, over its length.
"""

from __future__ import annotations

import json
import statistics

from benchmark.generators import common
from benchmark.harness import Run, note


def drive(run: Run) -> list[dict]:
    tr = run.traffic
    rates = [float(tr["nominal_fps"])] * int(tr["streams"])
    streams = common.start_streams(run, rates, realtime=False)
    common.settle_and_open(run, streams)
    run.run_window()
    return streams


def reduce(run: Run, streams: list[dict]) -> dict:
    t_open, t_close = run.window
    rows_by_topic, faults = common.parse_messages(run, streams)
    in_window: dict[str, list] = {}
    per_stream = []
    per_second: dict[int, int] = {}
    total = lost = 0
    for s in streams:
        rows = [r for r in rows_by_topic[s["topic"]]
                if t_open <= r[0] < t_close]
        # a frame missing between two published ones was shed or errored
        # by the server (a stall past the class's staleness budget): a
        # miss, counted in ``failed``, not a wrong answer
        lost += sum(b[1] - a[1] - 1 for a, b in zip(rows, rows[1:]))
        in_window[s["topic"]] = rows
        total += len(rows)
        per_stream.append({"stream": s["index"], "frames": len(rows),
                           "frames_per_s": len(rows) / run.seconds})
        for t, _, _ in rows:
            sec = int(t - t_open)
            per_second[sec] = per_second.get(sec, 0) + 1
    client = {
        "frames_per_s": total / run.seconds,
        "objects_per_frame": statistics.fmean(
            len(m["objects"]) for rows in in_window.values()
            for _, _, m in rows) if total else 0.0,
    }
    (run.out_dir / "throughput_breakdown.json").write_text(json.dumps({
        "workload": run.cell["name"], "seed": run.seed,
        "window_s": run.seconds, "pooled": client,
        "posts_asked_again": run.refused_posts, "lost": lost,
        "per_stream": per_stream,
        "per_second_frames": [per_second.get(sec, 0)
                              for sec in range(int(run.seconds))],
    }, indent=1))
    note(f"replay: {total} frames in {run.seconds} s, {lost} lost")
    return {
        "attempted": total + lost, "failed": lost, "faults": faults,
        "end_to_end": {"frames_per_s": client["frames_per_s"]},
        "client": client,
        "sample": common.pick_sample(run, in_window),
    }
