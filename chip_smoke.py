#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

Drives ``python -m evam_tpu.cli.main serve`` (REST mode) the way a user
would: the registry's default models at their default sizes, random
weights from the registry's seed, real 1080p pixels through the host
resize + I420 wire encode, over HTTP.

  cold server   wave 1: POSTs land while the bucket ladder is still
                compiling on the background warmup threads
                wave 2: the same mix again, warm — exact frame counts,
                zero errors/sheds/rejects/restarts, no compile
                wave 3: the same mix once more, now ADMITTED BY THE
                LIVE CAPACITY MODEL (waves 1 and 2 are POSTed while it
                is still cold and admits everything) — same contract
                SIGTERM, clean exit
  warm restart  EVAM_PRELOAD of both pipelines against the persistent
                compile cache (compile_s must fall), a short wave 4,
                SIGTERM

This process never imports jax (a chip belongs to one process): the
server is the only JAX process, and the device identity printed here
is what the server reported. Any failed check, a phase that raised, or
a server that died exits non-zero and prints no result line. The last
stdout line of a passing run is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--rehearse-cpu`` is the explicit hardware-free rehearsal: it sets
JAX_PLATFORMS=cpu for the server, cuts the bucket ladder, and ends with
a ``rehearsal_ok`` line instead — it can never print the result above.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from importlib import metadata
from pathlib import Path

REPO = Path(__file__).resolve().parent

#: (pipeline, version, request parameters). The second runs with its
#: default parameters, so the FUSED detect+classify program serves it.
PIPELINES = (
    ("object_detection", "person_vehicle_bike"),
    ("object_classification", "vehicle_attributes"),
)
TERMINAL = ("COMPLETED", "ERROR", "ABORTED")
#: the device-path stages of the per-batch clock that must have run
DEVICE_STAGES = ("h2d_issue", "launch", "readback")
#: a stream keeps at most this many frames in flight (StreamRunner's
#: window), so ONE stall of a fresh process — however long — sheds at
#: most this many stale frames per stream; more is a repeated stall
FRAMES_IN_FLIGHT = 4
#: rehearsal only: a CPU cannot carry 30 fps of a 512x512 SSD, so the
#: ladder is cut and the admission/staleness budgets (which would
#: rightly refuse and shed that traffic) are lifted
REHEARSAL_ENV = {
    "JAX_PLATFORMS": "cpu",
    "EVAM_MAX_BATCH": "2",
    "EVAM_SCHED_CAPACITY_FPS": "1000000",
    "EVAM_SCHED_STALENESS_MS_STANDARD": "0",
}
MESH_RE = re.compile(
    r"mesh: (\{.*?\}) over (\d+) devices \(([^,)]+), ([^)]+)\)")


class SmokeFailure(Exception):
    """A check that did not hold; the run exits non-zero."""


def say(msg: str) -> None:
    print(msg, flush=True)


def note(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def version_of(dist: str) -> str:
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return "absent"


class Deadline:
    def __init__(self, seconds: float):
        self.end = time.monotonic() + seconds

    def left(self, what: str) -> float:
        left = self.end - time.monotonic()
        if left <= 0:
            raise SmokeFailure(f"out of time while {what}")
        return left


class Server:
    """One ``serve`` child: the only process that touches JAX."""

    def __init__(self, workdir: Path, tag: str, env: dict[str, str]):
        self.tag = tag
        self.log_path = workdir / f"server_{tag}.log"
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        env = dict(env, REST_PORT=str(self.port))
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "evam_tpu.cli.main", "serve"],
            cwd=str(REPO), env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)
        self.t_start = time.monotonic()

    # ---------------------------------------------------------- process

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise SmokeFailure(
                f"server {self.tag} died (exit code {rc})")

    def log_text(self) -> str:
        return self.log_path.read_text(errors="replace")

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        self._log.close()

    def terminate(self, deadline: Deadline) -> None:
        """SIGTERM → clean exit (code 0) with no leaked stream."""
        self.check_alive()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(
                timeout=min(90.0, deadline.left("stopping the server")))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"server {self.tag} ignored SIGTERM for 90 s") from None
        if rc != 0:
            raise SmokeFailure(
                f"server {self.tag} exited {rc} on SIGTERM")
        if "shutdown drain abandoned" in self.log_text():
            raise SmokeFailure(
                f"server {self.tag} leaked streams at shutdown")

    # ------------------------------------------------------------- HTTP

    def request(self, method: str, path: str, body=None, timeout=60.0):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", data=data,
            method=method, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            raw = resp.read()
        ctype = resp.headers.get("Content-Type", "")
        return json.loads(raw) if "json" in ctype else raw.decode()

    def healthz(self) -> dict:
        """/healthz answers 503 with the same payload while an engine
        is restarting/degraded/stalled — read it either way."""
        try:
            return self.request("GET", "/healthz")
        except urllib.error.HTTPError as exc:
            return json.loads(exc.read())

    def wait_port(self, deadline: Deadline) -> None:
        while True:
            self.check_alive()
            try:
                self.request("GET", "/healthz", timeout=5.0)
                return
            except urllib.error.HTTPError:
                return
            except (urllib.error.URLError, OSError):
                deadline.left(f"waiting for server {self.tag}'s port")
                time.sleep(0.5)

    def wait_warm(self, deadline: Deadline) -> dict:
        while True:
            self.check_alive()
            h = self.healthz()
            if h["status"] == "ok" and h["warming"] == 0:
                return h
            if h["status"] not in ("ok", "warming"):
                raise SmokeFailure(f"/healthz says {h['status']}: {h}")
            deadline.left(f"waiting for warmup ({h['warming']} engines "
                          "still compiling)")
            time.sleep(1.0)

    def frame_errors(self) -> int:
        total = 0.0
        for line in self.request("GET", "/metrics").splitlines():
            if line.startswith("evam_frame_errors"):
                total += float(line.rsplit(" ", 1)[1])
        return int(total)

    def dump_state(self, workdir: Path) -> None:
        """Post-mortem for a failed run: what the server still says
        about itself (retained span trees include every shed/errored
        frame), beside its log."""
        if self.proc.poll() is not None:
            return
        for route in ("traces", "engines", "healthz", "scheduler"):
            try:
                body = (self.healthz() if route == "healthz"
                        else self.request("GET", f"/{route}"))
            except (urllib.error.URLError, OSError, ValueError):
                continue
            (workdir / f"failed_{self.tag}_{route}.json").write_text(
                json.dumps(body))

    def device(self) -> dict:
        m = MESH_RE.search(self.log_text())
        if m is None:
            raise SmokeFailure(
                f"server {self.tag} logged no 'mesh: ... over N devices' "
                "line")
        return {"platform": m.group(3).strip(), "kind": m.group(4).strip(),
                "count": int(m.group(2)), "mesh": m.group(1)}


def snapshot(server: Server) -> dict:
    h = server.healthz()
    sched = h["scheduler"]
    return {
        # engine keys always hold a ':'; with EVAM_CKPT on the route
        # also carries a "checkpoint" summary, which is not a row
        "engines": {k: r for k, r in
                    server.request("GET", "/engines").items() if ":" in k},
        "frame_errors": server.frame_errors(),
        "shed": sum(sched["shed"].values()),
        "rejected": sum(sched["rejected"].values()),
        "capacity_fps": server.request("GET", "/scheduler")["capacity_fps"],
        "healthz": h,
    }


def run_wave(server: Server, name: str, workdir: Path, streams: int,
             frames: int, deadline: Deadline) -> tuple[list[dict], int]:
    """POST ``streams`` instances (split over PIPELINES) at once and
    wait for every one to reach a terminal state. Returns one record
    per stream (pipeline, state, metadata path, line count) and how
    many engines were still compiling when the traffic started."""
    records = []
    for i in range(streams):
        pname, pver = PIPELINES[i % len(PIPELINES)]
        records.append({
            "pipeline": f"{pname}/{pver}",
            "path": workdir / f"{name}_{i:02d}.jsonl",
            "seed": i,
        })

    def post(rec: dict) -> None:
        try:
            rec["id"] = server.request(
                "POST", f"/pipelines/{rec['pipeline']}", {
                    "source": {
                        "uri": f"synthetic://1920x1080@30?count={frames}"
                               f"&seed={rec['seed']}",
                        "type": "uri"},
                    "destination": {"metadata": {
                        "type": "file", "path": str(rec["path"]),
                        "format": "json-lines"}},
                }, timeout=600.0)
        except urllib.error.HTTPError as exc:
            rec["post_error"] = f"HTTP {exc.code}: {exc.read().decode()}"
        except (urllib.error.URLError, OSError) as exc:
            rec["post_error"] = f"{type(exc).__name__}: {exc}"

    threads = [threading.Thread(target=post, args=(r,)) for r in records]
    for t in threads:
        t.start()
    for t in threads:
        t.join(deadline.left(f"posting {name}"))
    refused = [r for r in records if "id" not in r]
    if refused:
        server.check_alive()
        raise SmokeFailure(
            f"{name}: {len(refused)} of {streams} POSTs failed, first: "
            f"{refused[0].get('post_error', 'no answer')}")

    warming = server.healthz()["warming"]
    mine = {r["id"]: r for r in records}
    while True:
        server.check_alive()
        for st in server.request("GET", "/pipelines/status"):
            if st["id"] in mine:
                mine[st["id"]]["state"] = st["state"]
                mine[st["id"]]["status"] = st
        if all(r.get("state") in TERMINAL for r in records):
            break
        deadline.left(f"waiting for {name} to finish")
        time.sleep(0.5)
    for r in records:
        r["lines"] = (len(r["path"].read_bytes().splitlines())
                      if r["path"].exists() else 0)
    return records, warming


def stage_ms_between(before: dict, after: dict) -> dict[str, float]:
    """Mean per-batch stage clock of the batches clocked between two
    /engines snapshots (the route reports means since boot, over
    ``stage_batches`` — cold-start batches are not clocked)."""
    b0, b1 = before.get("stage_batches", 0), after["stage_batches"]
    if b1 <= b0:
        return {}
    ms0 = before.get("stage_ms") or {}
    return {
        s: round((ms * b1 - ms0.get(s, 0.0) * b0) / (b1 - b0), 3)
        for s, ms in (after.get("stage_ms") or {}).items()
    }


def check_engines_healthy(engines: dict, where: str) -> list[str]:
    bad = []
    for key, row in engines.items():
        if row["restarts"] > 0 or row["state"] != "running":
            bad.append(f"{where}: engine {key} state={row['state']} "
                       f"restarts={row['restarts']}")
    return bad


def check_warmup(engines: dict, where: str) -> list[str]:
    """After warmup every engine holds one program per rung of its
    ladder and reports no warmup failure (a failed background warmup
    is only a warning in the server's log; traffic that never reaches
    the failed bucket would not notice)."""
    bad = []
    for key, row in engines.items():
        if row["warm_error"]:
            bad.append(f"{where}: engine {key} warmup failed: "
                       f"{row['warm_error']}")
        if row["compiled_programs"] < len(row["buckets"]):
            bad.append(f"{where}: engine {key} holds "
                       f"{row['compiled_programs']} programs for the "
                       f"{len(row['buckets'])}-bucket ladder "
                       f"{row['buckets']}")
    return bad


def check_first_wave(records: list[dict], before: dict, after: dict,
                     name: str, shed_cap: int | None = None) -> list[str]:
    """A process's FIRST traffic (racing the compile, or just its own
    first-use costs) may shed a stale frame; what it may not do is
    lose a stream, restart an engine, leave /healthz unhealthy, end
    with a failed or partial warmup — or shed more than ``shed_cap``
    frames where one is given."""
    bad = [f"{name}: stream {r['id'][:8]} ended {r['state']}"
           for r in records if r["state"] != "COMPLETED"]
    bad += check_engines_healthy(after["engines"], name)
    bad += check_warmup(after["engines"], name)
    h = after["healthz"]
    if h["status"] != "ok" or h["warming"]:
        bad.append(f"{name}: /healthz ended {h['status']} "
                   f"warming={h['warming']}")
    shed = after["shed"] - before["shed"]
    if shed_cap is not None and shed > shed_cap:
        bad.append(f"{name}: shed {shed} frames, more than the "
                   f"{shed_cap} one stall can cost")
    return bad


def check_no_compile(before: dict, after: dict, name: str) -> list[str]:
    bad = []
    for key, row in after["engines"].items():
        prev = before["engines"].get(key, {}).get("compiled_programs")
        if row["compiled_programs"] != prev:
            bad.append(
                f"{name}: engine {key} compiled in steady state "
                f"({prev} → {row['compiled_programs']} programs)")
    return bad


def check_placement(engines: dict, device: dict) -> list[str]:
    """Every device JAX reported must carry traffic: a mesh engine's
    row names them all; fleet shard rows name one each, all distinct,
    all with batches."""
    want = device["platform"].lower()
    bad = []
    for key, row in engines.items():
        names = (row["device"] or "").split()
        if not all(want in n.lower() for n in names):
            bad.append(f"engine {key} runs on {row['device']!r}, not "
                       f"on {want}")
    groups: dict[str, list[dict]] = {}
    for row in engines.values():
        groups.setdefault(row["group"], []).append(row)
    for group, members in groups.items():
        shards = [r for r in members if r["shard"] not in (None, "mesh")]
        if shards:
            devs = [r["device"] for r in shards]
            if (len(set(devs)) != len(devs)
                    or len(devs) != device["count"]):
                bad.append(f"{group}: {len(devs)} shard rows on "
                           f"{sorted(set(devs))}, want one per each of "
                           f"{device['count']} devices")
            idle = [r["shard"] for r in shards if r["batches"] == 0]
            if idle:
                bad.append(f"{group}: shards {idle} served no batch")
        else:
            for r in members:
                n = len(set((r["device"] or "").split()))
                if n != device["count"]:
                    bad.append(f"{group}: engine row names {n} "
                               f"device(s), JAX reported "
                               f"{device['count']}")
    return bad


def check_warm_wave(records: list[dict], before: dict, after: dict,
                    frames: int, name: str) -> list[str]:
    """The steady-state contract: exact frame counts, no error, shed,
    reject, restart or compile, batching happened, and the device-path
    stages of the batch clock all ran."""
    bad = [f"{name}: {r['path'].name} has {r['lines']} metadata lines, "
           f"want {frames}" for r in records if r["lines"] != frames]
    for what in ("frame_errors", "shed", "rejected"):
        delta = after[what] - before[what]
        if delta:
            bad.append(f"{name}: {what} rose by {delta}")
    bad += check_first_wave(records, before, after, name)
    bad += check_no_compile(before, after, name)
    for key, row in after["engines"].items():
        prev = before["engines"].get(key, {})
        if row["batches"] == prev.get("batches", 0):
            continue  # a fleet shard no stream of this wave hashed to
        ms = stage_ms_between(prev, row)
        zero = [s for s in DEVICE_STAGES if not ms.get(s, 0.0) > 0.0]
        if zero:
            bad.append(f"{name}: engine {key} stage clock has no "
                       f"{zero} time: {ms}")
    big = 0
    for key, row in after["engines"].items():
        prev = before["engines"].get(key, {}).get("bucket_batches", {})
        big += sum(c - prev.get(b, 0)
                   for b, c in row["bucket_batches"].items() if int(b) > 1)
    if big <= 0:
        bad.append(f"{name}: no batch landed in a bucket larger than 1")
    return bad


def report_wave(name: str, records: list[dict], before: dict,
                after: dict, frames: int, seconds: float) -> None:
    states: dict[str, int] = {}
    for r in records:
        states[r["state"]] = states.get(r["state"], 0) + 1
    say(f"{name}: {len(records)} streams x {frames} frames in "
        f"{seconds:.1f} s wall; states {states}; metadata lines "
        f"{sum(r['lines'] for r in records)}/{len(records) * frames}; "
        f"frame_errors +{after['frame_errors'] - before['frame_errors']} "
        f"shed +{after['shed'] - before['shed']} "
        f"rejected +{after['rejected'] - before['rejected']}; admission "
        f"now models {after['capacity_fps']} fps of capacity")
    for key, row in after["engines"].items():
        prev = before["engines"].get(key, {})
        pb = prev.get("bucket_batches", {})
        buckets = {b: c - pb.get(b, 0)
                   for b, c in row["bucket_batches"].items()
                   if c - pb.get(b, 0)}
        say(f"  {key}: batches +{row['batches'] - prev.get('batches', 0)}"
            f" buckets {buckets} programs {row['compiled_programs']}/"
            f"{len(row['buckets'])} compile_s {row['compile_s']} "
            f"restarts {row['restarts']} "
            f"device {row['device']}")
        say(f"    stage_ms over "
            f"{row['stage_batches'] - prev.get('stage_batches', 0)} "
            f"steady batches {stage_ms_between(prev, row)}")


def weights_of(records: list[dict]) -> set[str]:
    out = set()
    for r in records:
        for stage in (r["status"].get("weights") or {}).values():
            out.update(stage["weights"].values())
    return out


def build_native() -> str:
    """Build the host wire-encode library from source — never trust a
    leftover binary riding along in the tree."""
    r = subprocess.run(
        ["make", "-B", "-C", str(REPO / "native")],
        capture_output=True, text=True)
    if r.returncode != 0:
        raise SmokeFailure(
            f"native/evam_media.cpp did not build:\n{r.stderr[-2000:]}")
    return str(REPO / "native" / "libevam_media.so")


def cache_dir_in_use() -> Path:
    """Mirrors obs/trace.configure_compilation_cache (cross-checked
    against the server's own log line)."""
    return Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or REPO / ".jax_cache")


def count_entries(path: Path) -> int:
    return sum(1 for p in path.rglob("*") if p.is_file()) \
        if path.is_dir() else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--streams", type=int, default=8,
                    help="concurrent streams per wave, split over the "
                         "two pipelines")
    ap.add_argument("--frames", type=int, default=120,
                    help="1080p frames per stream per wave")
    ap.add_argument("--timeout", type=float, default=1140.0,
                    help="seconds for the whole run, compiles included")
    ap.add_argument("--workdir", default=None,
                    help="where server logs and metadata files go "
                         "(default: a fresh temp dir)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="explicit CPU rehearsal at a cut size; never "
                         "prints the chip result line")
    args = ap.parse_args()

    if not (REPO / "evam_tpu" / "cli" / "main.py").is_file():
        note(f"no evam_tpu package beside {Path(__file__).name} — "
             "nothing to smoke")
        return 2
    if args.streams < 2 or args.frames < 1:
        ap.error("--streams must be >= 2 and --frames >= 1")

    deadline = Deadline(args.timeout)
    workdir = Path(args.workdir or tempfile.mkdtemp(prefix="evam_smoke_"))
    workdir.mkdir(parents=True, exist_ok=True)

    env = dict(os.environ)
    env.pop("EVAM_PRELOAD", None)
    env.pop("EVAM_NO_NATIVE", None)
    env.update({
        # the registry refuses missing weights unless told otherwise;
        # the smoke serves the seeded random init on purpose
        "EVAM_ALLOW_RANDOM_WEIGHTS": "1",
        "EVAM_NATIVE": "1",  # not the core-count heuristic
        "PYTHONUNBUFFERED": "1",
    })
    if args.rehearse_cpu:
        env.update(REHEARSAL_ENV)
    want_platform = "cpu" if args.rehearse_cpu else "tpu"

    native_lib = build_native()
    cache_dir = cache_dir_in_use()
    cache_before = count_entries(cache_dir)

    servers: list[Server] = []
    try:
        # ------------------------------------------------ cold server
        cold = Server(workdir, "cold", env)
        servers.append(cold)
        note(f"server up on :{cold.port}, log {cold.log_path}")
        cold.wait_port(deadline)
        device = cold.device()
        say(f"platform: {device['platform']} device_kind: "
            f"{device['kind']} count: {device['count']} mesh: "
            f"{device['mesh']} jax {version_of('jax')} jaxlib "
            f"{version_of('jaxlib')} libtpu {version_of('libtpu')}"
            + (" [CPU REHEARSAL — not a chip run]"
               if args.rehearse_cpu else ""))
        if device["platform"] != want_platform:
            raise SmokeFailure(
                f"the server came up on {device['platform']!r}, not "
                f"{want_platform!r}")
        if f"XLA compilation cache at {cache_dir}" not in cold.log_text():
            raise SmokeFailure(
                f"server did not report the compile cache at {cache_dir}")

        s0 = snapshot(cold)
        t0 = time.monotonic()
        w1, warming = run_wave(cold, "wave1", workdir, args.streams,
                               args.frames, deadline)
        t_w1 = time.monotonic() - t0
        cold.wait_warm(deadline)
        s1 = snapshot(cold)
        report_wave("wave1 (cold, compile racing dispatch)", w1, s0, s1,
                    args.frames, t_w1)
        say(f"  engines still compiling when wave1's traffic started: "
            f"{warming}; all warm {time.monotonic() - cold.t_start:.0f} s "
            "after server start")
        bad = check_first_wave(w1, s0, s1, "wave1")
        if bad:
            raise SmokeFailure("; ".join(bad))
        cold_compile = {k: r["compile_s"]
                        for k, r in s1["engines"].items()}

        t0 = time.monotonic()
        w2, _ = run_wave(cold, "wave2", workdir, args.streams,
                         args.frames, deadline)
        t_w2 = time.monotonic() - t0
        s2 = snapshot(cold)
        report_wave("wave2 (warm)", w2, s1, s2, args.frames, t_w2)
        bad = check_warm_wave(w2, s1, s2, args.frames, "wave2")
        if bad:
            raise SmokeFailure("; ".join(bad))
        # waves 1 and 2 were POSTed against a cold capacity model
        # (no steady-state batch clocked yet: it admits everything).
        # Now it has a reading, and it must carry the same streams.
        if not s2["capacity_fps"] > 0:
            raise SmokeFailure(
                "admission's capacity model is still cold after a warm "
                f"wave: capacity_fps {s2['capacity_fps']}")
        t0 = time.monotonic()
        w3, _ = run_wave(cold, "wave3", workdir, args.streams,
                         args.frames, deadline)
        t_w3 = time.monotonic() - t0
        s3 = snapshot(cold)
        report_wave(f"wave3 (warm, admitted by the live model at "
                    f"{s2['capacity_fps']} fps)", w3, s2, s3,
                    args.frames, t_w3)
        bad = check_warm_wave(w3, s2, s3, args.frames, "wave3")
        bad += check_placement(s3["engines"], device)
        if bad:
            raise SmokeFailure("; ".join(bad))
        weights = weights_of(w2) | weights_of(w3)
        if weights != {"random"}:
            raise SmokeFailure(
                f"expected seeded random weights, served {weights}")
        log = cold.log_text()
        if f"native media kernels loaded ({native_lib}" not in log:
            raise SmokeFailure(
                "the server did not load the freshly built native "
                "wire-encode library")
        say(f"wire-encode: native, built this run from "
            f"native/evam_media.cpp ({native_lib}); weights: random "
            "(EVAM_ALLOW_RANDOM_WEIGHTS=1)")
        cold.terminate(deadline)
        say("cold server: SIGTERM → exit 0, no leaked stream")
        cache_cold = count_entries(cache_dir)

        # ----------------------------------------------- warm restart
        warm = Server(workdir, "warm", dict(
            env, EVAM_PRELOAD=",".join("/".join(p) for p in PIPELINES)))
        servers.append(warm)
        warm.wait_port(deadline)  # EVAM_PRELOAD: opens only when warm
        t_ready = time.monotonic() - warm.t_start
        s4 = snapshot(warm)
        if s4["healthz"]["warming"] or not s4["engines"]:
            raise SmokeFailure(
                "EVAM_PRELOAD opened the port before the engines were "
                f"warm: {s4['healthz']}")
        warm_compile = {k: r["compile_s"]
                        for k, r in s4["engines"].items()}
        say(f"warm restart: port open and engines warm {t_ready:.0f} s "
            "after start")
        say(f"  compile_s cold {cold_compile}")
        say(f"  compile_s warm {warm_compile}")
        if args.rehearse_cpu:
            say("  CPU rehearsal: a CPU time is no evidence, not compared")
        elif cache_before:
            # the cache directory came already filled: the first
            # server was not cold either, so there is no fall to see
            say(f"  the compile cache held {cache_before} entries before "
                "this run — no cold compile to compare against")
        elif sum(warm_compile.values()) >= sum(cold_compile.values()):
            raise SmokeFailure(
                "the persistent compile cache did not cut compile_s on "
                f"restart: cold {cold_compile} warm {warm_compile}")
        t0 = time.monotonic()
        w4, _ = run_wave(warm, "wave4", workdir, 2, args.frames, deadline)
        t_w4 = time.monotonic() - t0
        s5 = snapshot(warm)
        report_wave("wave4 (warm restart)", w4, s4, s5, args.frames, t_w4)
        # this process's first traffic, served from cached programs:
        # one first-use stall may cost each stream its frames in flight
        bad = check_first_wave(w4, s4, s5, "wave4",
                               shed_cap=FRAMES_IN_FLIGHT * len(w4))
        bad += check_no_compile(s4, s5, "wave4")
        if bad:
            raise SmokeFailure("; ".join(bad))
        warm.terminate(deadline)
        say("warm server: SIGTERM → exit 0, no leaked stream")
    except BaseException:
        for s in servers:
            s.dump_state(workdir)
            tail = s.log_text()[-6000:] if s.log_path.exists() else ""
            note(f"---- tail of {s.log_path} ----\n{tail}")
        note(f"workdir (server logs, failed_* state dumps): {workdir}")
        raise
    finally:
        for s in servers:
            s.kill()

    say(f"compile cache: {cache_dir} — {count_entries(cache_dir)} entries "
        f"({cache_before} before the run, {cache_cold} after the cold "
        "server)")
    say(f"workdir: {workdir}")
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"]}
    if args.rehearse_cpu:
        say(json.dumps({"rehearsal_ok": True, "device": dev}))
    else:
        say(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as exc:
        note(f"FAILED: {exc}")
        sys.exit(1)
