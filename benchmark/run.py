#!/usr/bin/env python3
"""One cell, one run: ``python3 benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``, from the root of a checkout.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and,
traced, ``breakdown``. Everything else goes to standard error and to files
under ``benchmark_out/<workload>/``. A run that finds no TPU, or no program
beside the benchmark, exits non-zero and prints no line.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402
from benchmark.generators.common import stop_streams  # noqa: E402
from benchmark.harness import BenchFailure, note  # noqa: E402
from benchmark.mqtt_sink import MqttSink  # noqa: E402


#: the first run of a cell in a checkout compiles its ladder (~85 s in all
#: on a v5e); the contract allows that run 1200 s
READY_TIMEOUT_S = 1100.0


def child_env() -> dict[str, str]:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def check_answers(run: harness.Run, streams, sample) -> tuple[bool, dict]:
    """The seeded sample against the plain reference, in a CPU child."""
    by_topic = {s["topic"]: s for s in streams}
    tr = run.traffic
    job = {
        "reference": run.config["reference"],
        "shapes": run.config["shapes"],
        "model_xml": str(harness.model_file(run.config, run.rehearsal,
                                            ".xml")),
        "model_bin": str(harness.model_file(run.config, run.rehearsal,
                                            ".bin")),
        "frames": [{"stream": by_topic[t]["index"],
                    "seed": by_topic[t]["seed"], "seq": k,
                    "width": tr["width"], "height": tr["height"],
                    "message": msg} for t, k, msg in sample],
    }
    job_path = run.out_dir / "reference_job.json"
    res_path = run.out_dir / "reference_result.json"
    job_path.write_text(json.dumps(job))
    res_path.unlink(missing_ok=True)
    child = HERE / "reference" / f"{run.config['reference']['child']}.py"
    r = subprocess.run(
        [sys.executable, str(child), str(job_path), str(res_path)],
        cwd=str(REPO), env=child_env(), capture_output=True, text=True,
        timeout=600)
    if r.returncode != 0 or not res_path.exists():
        raise BenchFailure(
            f"the reference child failed ({r.returncode}):\n"
            + r.stderr[-3000:])
    res = json.loads(res_path.read_text())
    return bool(res["ok"]) and bool(job["frames"]), res


def reduce_trace(run: harness.Run) -> dict | None:
    """xplane.pb -> busy, window, per-op and idle gaps, in a CPU child."""
    if run.trace_dir is None:
        return None
    if run.rehearsal:
        # a CPU has no device plane: the rehearsal reduces the small
        # recorded chip trace instead of the one it just took
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        run.trace_dir = HERE / "rehearsal" / "recorded_trace.json"
    out = run.out_dir / "device_trace.json"
    out.unlink(missing_ok=True)
    cmd = [sys.executable, str(HERE / "trace_reduce.py"),
           str(run.trace_dir), str(out)]
    if not run.rehearsal:  # the recorded trace is of another ladder
        cmd.append(",".join(str(b) for b in sorted(
            {int(b) for row in run.after["engines"].values()
             for b in row["buckets"]})))
    r = subprocess.run(cmd, cwd=str(REPO), env=child_env(),
                       capture_output=True, text=True, timeout=600)
    if r.returncode != 0 or not out.exists():
        raise BenchFailure(
            f"the trace reduction failed ({r.returncode}):\n"
            + r.stderr[-3000:])
    if not run.rehearsal:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    return json.loads(out.read_text())


def run_cell(args, rehearsal: bool = False, bench_path=None) -> dict:
    bench, cell, config, traffic = harness.load_cell(args.workload, bench_path)
    if rehearsal:
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    if not (REPO / "evam_tpu" / "cli" / "main.py").is_file():
        raise BenchFailure("no evam_tpu package beside benchmark/: "
                           "nothing to measure")
    out_dir = REPO / "benchmark_out" / cell["name"]
    out_dir.mkdir(parents=True, exist_ok=True)
    harness.build_native()
    models_dir = harness.prepare_models(config, rehearsal)
    if rehearsal and "rehearsal_shapes" in config:
        config = {**config, "shapes": {**config["shapes"],
                                       **config["rehearsal_shapes"]}}
    sink = MqttSink()
    server = harness.Server(
        out_dir, harness.server_env(config, rehearsal, models_dir))
    run = harness.Run(cell=cell, config=config, traffic=traffic,
                      seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), out_dir=out_dir,
                      server=server, sink=sink, rehearsal=rehearsal)
    try:
        server.wait_ready(READY_TIMEOUT_S)
        note(f"server ready {time.time() - harness.T_PROCESS_START:.1f} s "
             "after process start")
        device = harness.device_of(server)
        want = "cpu" if rehearsal else "tpu"
        if device["platform"] != want:
            raise BenchFailure(
                f"the server came up on {device['platform']!r}, not {want!r}")
        if device["count"] < cell["chips"] and not rehearsal:
            raise BenchFailure(
                f"{device['count']} chip(s), the cell asks for "
                f"{cell['chips']}")
        gen = harness.generator_for(traffic)
        streams = gen.drive(run)
        clock_offset = statistics.median(
            time.time() - time.perf_counter() for _ in range(51))
        stop_streams(run, streams)
        device = harness.device_of(server)  # the peak, after the traffic
        rc = server.stop()
        if rc not in (0, None):
            note(f"the server exited {rc} on SIGTERM")
    except BaseException:
        note("---- tail of the server's log ----\n" + server.log_tail())
        raise
    finally:
        server.stop()
        sink.close()

    result = gen.reduce(run, streams)
    compiled = [sum(r["compiled_programs"] for r in snap["engines"].values())
                for snap in (run.before, run.after)]
    if compiled[0] != compiled[1]:
        result["faults"].append(
            f"the server compiled inside the window ({compiled[0]} -> "
            f"{compiled[1]} programs)")
    answers_ok, ref = check_answers(run, streams, result["sample"])
    for fault in result["faults"]:
        note(f"fault: {fault}")
    for frame in ref["frames"]:
        for p in frame["problems"]:
            note(f"stream {frame['stream']} frame {frame['seq']}: {p}")
    correct = answers_ok and not result["faults"]

    metrics = {}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": device}
    if run.trace:
        dev_trace = reduce_trace(run)
        ctx = {
            "run": run, "config": config, "client": result["client"],
            "attempted": result["attempted"], "streams": streams,
            "before": run.before, "after": run.after,
            "trace_before": run.trace_before,
            "trace_after": run.trace_after, "traces": run.traces,
            "clock_offset": clock_offset, "device": device,
            "device_trace": dev_trace, "peaks_file": HERE / "peaks.json",
        }
        if rehearsal:  # peaks are looked up for the recorded trace's chip
            ctx["device"] = dict(device, kind=dev_trace.get(
                "device_kind", device["kind"]))
        metrics.update(harness.read_per_layer(bench, cell["name"], ctx))
        device["busy_s"] = dev_trace["busy_s"]
        device["window_s"] = dev_trace["window_s"]
        line["breakdown"] = {"device_ops": dev_trace["device_ops"][:10],
                             "idle_gaps": dev_trace["idle_gaps"][:10]}
    else:
        values = dict(result["end_to_end"], setup_s=run.setup_s)
        for entry in harness.metric_files(bench, "end_to_end", cell["name"]):
            if entry["name"] not in values:
                raise BenchFailure(
                    f"the {traffic['kind']} generator does not measure "
                    f"{entry['name']}")
            metrics[entry["name"]] = {"value": float(values[entry["name"]]),
                                      "unit": entry["unit"]}
    (out_dir / f"result_seed{args.seed}_trace{args.trace}.json").write_text(
        json.dumps(line, indent=1))
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        if args.seconds is None:
            args.seconds = float(harness.load_json(
                REPO / "BENCHMARK.json")["run_seconds"])
        line = run_cell(args)
    except BenchFailure as exc:
        note(f"FAILED: {exc}")
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
