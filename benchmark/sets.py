#!/usr/bin/env python3
"""Run one cell several times and say how widely its runs spread.

  python3 benchmark/sets.py --workload detect_paced --runs 6 --sets 2 \\
      [--seconds 40] [--first-seed 1000000007] [--out chiprun_out/x.json]

Each set uses the same seeds (first-seed + i). For every end-to-end metric
it prints the values, each set's median and its spread: the distance
between the first and third quartile (``statistics.quantiles(v, n=4)``) as
a share of the median. Side files of every run (latency or throughput
breakdown) are kept beside the output. A bound is about five times the
widest spread, never under 1 %.
"""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


def spread(values: list[float]) -> float | None:
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--first-seed", type=int, default=1_000_000_007)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    out_path = Path(args.out) if args.out else (
        REPO / "benchmark_out" / f"sets_{args.workload}.json")
    out_path.parent.mkdir(parents=True, exist_ok=True)
    side_dir = out_path.with_suffix("")
    side_dir.mkdir(parents=True, exist_ok=True)
    sets = []
    for si in range(args.sets):
        lines = []
        for ri in range(args.runs):
            seed = args.first_seed + ri
            cmd = [sys.executable, str(HERE / "run.py"), "--workload",
                   args.workload, "--seed", str(seed), "--trace",
                   str(args.trace)]
            if args.seconds is not None:
                cmd += ["--seconds", str(args.seconds)]
            r = subprocess.run(cmd, cwd=str(REPO), capture_output=True,
                               text=True)
            sys.stderr.write(r.stderr[-1500:])
            if r.returncode != 0:
                print(f"run {si}.{ri} exited {r.returncode}", flush=True)
                lines.append(None)
                continue
            line = json.loads(r.stdout.strip().splitlines()[-1])
            lines.append(line)
            print(f"set {si} run {ri} seed {seed}: " + " ".join(
                f"{k}={v['value']:.4f}" for k, v in line["metrics"].items())
                + f" correct={line['correct']} failed={line['failed']}/"
                  f"{line['attempted']} mem={line['device']['memory_peak_bytes']}",
                flush=True)
            for name in ("latency_breakdown.json",
                         "throughput_breakdown.json", "due_anchor.json",
                         "device_trace.json", "step_roofline.json",
                         "reference_result.json"):
                src = REPO / "benchmark_out" / args.workload / name
                if src.exists():
                    shutil.copy(src, side_dir / f"s{si}r{ri}_{name}")
        sets.append(lines)
    summary = {}
    names = sorted({k for s in sets for ln in s if ln for k in ln["metrics"]})
    for name in names:
        per_set = []
        for s in sets:
            vals = [ln["metrics"][name]["value"] for ln in s
                    if ln and name in ln["metrics"]]
            per_set.append({"values": vals,
                            "median": statistics.median(vals) if vals else None,
                            "spread": spread(vals),
                            "spread_without_first": spread(vals[1:])})
        summary[name] = per_set
        print(name, json.dumps(per_set), flush=True)
    out_path.write_text(json.dumps(
        {"workload": args.workload, "sets": sets, "summary": summary},
        indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
