"""How far the due-time anchor is off, measured and not assumed.

The client computes frame k of stream i as due at
``start_time_i + k / f_i``. The server's frame traces (``/traces``, the
1-in-16 sample the ring retains) carry ``perf_counter()`` stamps, and on
Linux that clock is one for every process of the machine. The earliest
stamp of a frame's tree is its engine submit, a wire encode after its
ingest. So per stream, the median over its retained frames of
(first stamp - computed due) is how EARLY the anchor reads (``start_time``
is taken before the source thread has made its first frame) plus the
source's frame build and the wire encode. ``params.stat`` chooses:

  offset  the median of that over the streams, ms: the constant that the
          generator's anchor adds to every latency it reports. It is in
          the level of ``latency_p50_ms``; a PR that moves it (faster
          thread start, a cheaper source) moves the end-to-end number by
          as much without a camera's operator feeling anything.
  spread  largest minus smallest of it across streams, ms: how unevenly
          the anchor is off, which is what adds run-to-run noise to a
          pooled latency.

The side file gives each stream's median and smallest value.
"""

import json
import statistics


def read(ctx: dict, params: dict):
    traces = ctx.get("traces")
    if not traces or not traces.get("enabled"):
        return None
    by_id = {s["id"]: s for s in ctx["streams"]}
    first: dict[tuple[str, int], float] = {}
    for ev in traces["traceEvents"]:
        if ev.get("cat") != "frame" or ev["tid"] not in by_id:
            continue
        key = (ev["tid"], ev["args"]["seq"])
        t = ev["ts"] / 1e6
        if key not in first or t < first[key]:
            first[key] = t
    offset = ctx["clock_offset"]  # time.time() - perf_counter()
    per_stream: dict[int, list[float]] = {}
    for (sid, k), t in first.items():
        s = by_id[sid]
        due_perf = s["start_time"] - offset + k / s["fps"]
        per_stream.setdefault(s["index"], []).append((t - due_perf) * 1e3)
    if len(per_stream) < 2:
        return None
    med = {i: statistics.median(v) for i, v in per_stream.items()}
    (ctx["run"].out_dir / "due_anchor.json").write_text(json.dumps({
        "per_stream_median_ms": med,
        "per_stream_min_ms": {i: min(v) for i, v in per_stream.items()},
        "frames": {i: len(v) for i, v in per_stream.items()},
    }, indent=1))
    if params["stat"] == "offset":
        return statistics.median(med.values())
    if params["stat"] == "spread":
        return max(med.values()) - min(med.values())
    raise KeyError(params["stat"])
