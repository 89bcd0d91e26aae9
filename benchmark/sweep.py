#!/usr/bin/env python3
"""Find the paced knee once: one server on the chip, N stepped upward.

  python3 benchmark/sweep.py --config pvb_detect --traffic paced_1080p30 \\
      --steps 4 8 12 16 20 --seconds 10 [--out chiprun_out/sweep.json]

For each N it adds realtime streams up to N (serial POSTs, rates as the
traffic file spreads them around the mean for the largest N), lets them
settle, and measures a window of ``--seconds``: pooled p50/p95 of
due-to-arrival, the p95 of the window's two halves (a p95 that climbs
through the step is a growing queue), frames shed, and whether a POST was
refused. The knee is the largest N with no shed, no refusal and a p95 that
does not climb; the cell runs at four fifths of it, written into the
traffic file as data. A sweep is made once, by hand; a run never searches.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402
from benchmark.generators import common, paced  # noqa: E402
from benchmark.mqtt_sink import MqttSink  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--steps", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--settle", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    config = harness.load_json(HERE / "configs" / f"{args.config}.json")
    traffic = harness.load_json(HERE / "traffic" / f"{args.traffic}.json")
    if args.rehearse_cpu:
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    out_dir = REPO / "benchmark_out" / f"sweep_{args.config}"
    out_dir.mkdir(parents=True, exist_ok=True)
    harness.build_native()
    models_dir = harness.prepare_models(config, args.rehearse_cpu)
    sink = MqttSink()
    server = harness.Server(
        out_dir, harness.server_env(config, args.rehearse_cpu, models_dir))
    run = harness.Run(cell={"name": "sweep"}, config=config, traffic=traffic,
                      seed=args.seed, seconds=args.seconds, trace=False,
                      out_dir=out_dir, server=server, sink=sink,
                      rehearsal=args.rehearse_cpu)
    rates = paced.rates_for({**traffic, "streams": max(args.steps)})
    rows = []
    streams: list[dict] = []
    try:
        server.wait_ready(1100)
        device = harness.device_of(server)
        for n in sorted(args.steps):
            refused = None
            while len(streams) < n:
                try:
                    streams.append(common.start_stream(
                        run, len(streams), rates[len(streams)], True))
                except harness.BenchFailure as exc:
                    refused = str(exc)
                    break
            if refused:
                rows.append({"streams": n, "refused": refused})
                break
            time.sleep(args.settle)
            before = server.snapshot()
            t_open = time.time()
            time.sleep(args.seconds)
            t_close = time.time()
            time.sleep(1.0)
            after = server.snapshot()
            lat = []
            due = 0
            by_topic = {s["topic"]: s for s in streams}
            for t, topic, payload in list(sink.messages):
                s = by_topic[topic]
                k = json.loads(payload)["timestamp"] // s["period_ns"]
                d = s["start_time"] + k / s["fps"]
                if t_open <= d < t_close:
                    lat.append((d, (t - d) * 1e3))
            for s in streams:
                due += (math.ceil((t_close - s["start_time"]) * s["fps"])
                        - math.ceil((t_open - s["start_time"]) * s["fps"]))
            mid = (t_open + t_close) / 2
            first = sorted(v for d, v in lat if d < mid)
            second = sorted(v for d, v in lat if d >= mid)
            pooled = sorted(v for _, v in lat)
            shed = (sum(after["healthz"]["scheduler"]["shed"].values())
                    - sum(before["healthz"]["scheduler"]["shed"].values()))
            rows.append({
                "streams": n, "due": due, "arrived": len(lat), "shed": shed,
                "p50_ms": common.percentile(pooled, 0.5),
                "p95_ms": common.percentile(pooled, 0.95),
                "p95_first_half_ms": common.percentile(first, 0.95),
                "p95_second_half_ms": common.percentile(second, 0.95),
                "capacity_fps": after["scheduler"]["capacity_fps"],
                "host_stages_ms": after["healthz"]["host_stages_ms"],
            })
            harness.note(f"sweep: {rows[-1]}")
        common.stop_streams(run, streams)
    finally:
        server.stop()
        sink.close()
    result = {"config": args.config, "traffic": args.traffic,
              "device": device, "seconds": args.seconds, "rows": rows}
    text = json.dumps(result, indent=1)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
