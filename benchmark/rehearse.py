#!/usr/bin/env python3
"""Rehearsal without the chip: every cell end to end on the CPU at a tiny
size, the data-driven lookup, and the trace reduction on a small recorded
chip trace. Not part of tests/; prints no device metric (a CPU time is no
evidence): only which keys each result line holds and what was found.

  python3 benchmark/rehearse.py                 # everything (~10 min)
  python3 benchmark/rehearse.py --cells detect_paced --skip-lookup
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(REPO))

from benchmark import harness, run as bench_run, trace_reduce  # noqa: E402

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def fail(msg: str) -> None:
    raise SystemExit(f"rehearsal FAILED: {msg}")


def check_line(line: dict, bench: dict, cell: str, trace: int) -> None:
    if not LINE_KEYS <= set(line):
        fail(f"{cell}: result line lacks {LINE_KEYS - set(line)}")
    if not DEVICE_KEYS <= set(line["device"]):
        fail(f"{cell}: device lacks {DEVICE_KEYS - set(line['device'])}")
    section = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in harness.metric_files(bench, section, cell)}
    got = set(line["metrics"])
    if trace:
        if not got <= want or not got:
            fail(f"{cell}: traced metrics {sorted(got)} are not among the "
                 f"cell's per-layer metrics")
        if not {"busy_s", "window_s"} <= set(line["device"]):
            fail(f"{cell}: traced device block lacks busy_s/window_s")
        if "breakdown" not in line:
            fail(f"{cell}: traced line has no breakdown")
    elif got != want:
        fail(f"{cell}: end-to-end metrics {sorted(got)}, want {sorted(want)}")
    for name, m in line["metrics"].items():
        if set(m) != {"value", "unit"} or not isinstance(m["value"], float):
            fail(f"{cell}: metric {name} is not {{value, unit}}")
    if not line["correct"]:
        fail(f"{cell}: the answers did not agree with the reference")
    if line["failed"]:
        fail(f"{cell}: {line['failed']} of {line['attempted']} failed")


def rehearse_cells(cells: list[str], bench: dict, bench_path=None) -> None:
    for cell in cells:
        for trace in (0, 1):
            args = SimpleNamespace(workload=cell, seed=2_345_678_901 + trace,
                                   seconds=3.0, trace=trace)
            line = bench_run.run_cell(args, rehearsal=True,
                                      bench_path=bench_path)
            check_line(line, bench, cell, trace)
            print(f"ok  {cell} --trace {trace}: keys {sorted(line)}; "
                  f"metrics {sorted(line['metrics'])}; attempted "
                  f"{line['attempted']} failed {line['failed']}", flush=True)


def rehearse_lookup(bench: dict) -> None:
    """A configuration, a traffic mix and a per-layer metric added as new
    files plus one new entry each are found by name."""
    added = []
    try:
        cfg = json.loads((HERE / "configs" / "pvb_detect.json").read_text())
        cfg["name"] = "rehearsal_config"
        added.append(HERE / "configs" / "rehearsal_config.json")
        added[-1].write_text(json.dumps(cfg))
        tr = json.loads((HERE / "traffic" / "paced_1080p30.json").read_text())
        tr["rehearsal"]["streams"] = 1
        added.append(HERE / "traffic" / "rehearsal_traffic.json")
        added[-1].write_text(json.dumps(tr))
        added.append(HERE / "metrics" / "rehearsal_fill.json")
        added[-1].write_text(
            (HERE / "metrics" / "batch_fill.paced.json").read_text())
        b = json.loads(json.dumps(bench))
        b["configs"].append({**b["configs"][0], "name": "rehearsal_config",
                             "file": "benchmark/configs/rehearsal_config.json"})
        b["workloads"].append({"name": "rehearsal_cell",
                               "config": "rehearsal_config",
                               "traffic": "rehearsal_traffic", "chips": 1,
                               "why": "rehearsal of the lookup"})
        for m in b["end_to_end"]:
            if m["name"].startswith("latency_"):
                m["workloads"] = m["workloads"] + ["rehearsal_cell"]
        fill = next(m for m in b["per_layer"]
                    if m["name"] == "batch_fill.paced")
        b["per_layer"].append({**fill, "name": "rehearsal_fill",
                               "workloads": ["rehearsal_cell"]})
        out = REPO / "benchmark_out" / "rehearsal"
        out.mkdir(parents=True, exist_ok=True)
        added.append(out / "BENCHMARK.json")
        added[-1].write_text(json.dumps(b))
        args = SimpleNamespace(workload="rehearsal_cell", seed=7,
                               seconds=3.0, trace=1)
        line = bench_run.run_cell(args, rehearsal=True, bench_path=added[-1])
        if set(line["metrics"]) != {"rehearsal_fill"}:
            fail(f"lookup: metrics {sorted(line['metrics'])}, want the one "
                 "new metric")
        print("ok  lookup: a new configuration, traffic mix and metric, "
              "each a new file plus one entry, were found by name",
              flush=True)
    finally:
        for p in added:
            p.unlink(missing_ok=True)


def rehearse_trace() -> None:
    rec = HERE / "rehearsal" / "recorded_trace.json"
    want = json.loads(
        (HERE / "rehearsal" / "recorded_trace.expected.json").read_text())
    got = trace_reduce.reduce_events(json.loads(rec.read_text())["events"])
    for key in ("busy_s", "window_s", "steps"):
        if abs(got[key] - want[key]) > 1e-9 * max(1.0, abs(want[key])):
            fail(f"trace reduction: {key} {got[key]} != {want[key]}")
    if got["device_ops"][:3] != want["device_ops"][:3]:
        fail("trace reduction: the per-op times differ from the record")
    print(f"ok  trace reduction: {len(got['device_ops'])} operations, "
          f"{got['steps']} steps, busy share and per-op times as recorded",
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", nargs="*", default=None)
    ap.add_argument("--skip-lookup", action="store_true")
    args = ap.parse_args()
    bench = harness.load_json(REPO / "BENCHMARK.json")
    rehearse_trace()
    cells = args.cells if args.cells is not None else [
        w["name"] for w in bench["workloads"]]
    try:
        rehearse_cells(cells, bench)
        if not args.skip_lookup:
            rehearse_lookup(bench)
    except harness.BenchFailure as exc:
        fail(str(exc))
    print("rehearsal passed (CPU: no number above is a device metric)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
