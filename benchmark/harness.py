"""The harness: one cell, one run, through the path a user takes.

The benchmark process never imports JAX. It starts its MQTT sink, spawns
the server (``serve_child.py``: the program's ``serve`` plus one idle
control thread) as the one process that holds the chip, and hands a
``Run`` to the traffic generator named by the cell's traffic file. What
belongs to one configuration, one traffic mix or one per-layer metric
lives in a file of its own, found by the name in ``BENCHMARK.json``:

  configs/<config>.json      sizes, models, server environment, request,
                             reference
  traffic/<traffic>.json     parameters; ``kind`` names generators/<kind>.py
  metrics/<metric>.json      ``reader`` names readers/<reader>.py, ``params``
                             are handed to it (unit, layer, moves and cells
                             stand in ``BENCHMARK.json`` alone)
"""

from __future__ import annotations

import importlib
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
T_PROCESS_START = time.time()


class BenchFailure(Exception):
    """The run cannot produce a result; exit non-zero, print no line."""


def note(msg: str) -> None:
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchFailure(f"{path.relative_to(REPO)} is missing") from None


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ------------------------------------------------------------------ server


class Server:
    """The ``serve`` child and the three ways the harness talks to it:
    REST (what a user has), the control port (device, profiler) and its
    log (the mesh line)."""

    def __init__(self, out_dir: Path, env: dict[str, str]):
        self.port = free_port()
        self.ctl_port = free_port()
        self.log_path = out_dir / "server.log"
        self._log = open(self.log_path, "wb")
        env = dict(env, REST_PORT=str(self.port),
                   BENCH_CTL_PORT=str(self.ctl_port), PYTHONUNBUFFERED="1")
        self.proc = subprocess.Popen(
            [sys.executable, str(HERE / "serve_child.py")], cwd=str(REPO),
            env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True)

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise BenchFailure(
                f"the server died (exit code {rc}); see {self.log_path}:\n"
                + self.log_tail())

    def log_tail(self, n: int = 3000) -> str:
        try:
            return self.log_path.read_text(errors="replace")[-n:]
        except OSError:
            return ""

    def request(self, method: str, path: str, body=None, timeout=30.0):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", data=data, method=method,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            raw = resp.read()
            ctype = resp.headers.get("Content-Type", "")
        return json.loads(raw) if "json" in ctype else raw.decode()

    def healthz(self) -> dict:
        try:
            return self.request("GET", "/healthz")
        except urllib.error.HTTPError as exc:
            return json.loads(exc.read())

    def wait_ready(self, timeout_s: float) -> None:
        """With EVAM_PRELOAD the port opens only when the engine is warm."""
        end = time.monotonic() + timeout_s
        while True:
            self.check_alive()
            try:
                h = self.healthz()
                if h.get("status") == "ok" and not h.get("warming"):
                    return
                if h.get("status") not in ("ok", "warming"):
                    raise BenchFailure(f"/healthz says {h.get('status')}")
            except (urllib.error.URLError, OSError):
                pass
            if time.monotonic() > end:
                raise BenchFailure(
                    f"the server was not ready in {timeout_s:.0f} s:\n"
                    + self.log_tail())
            time.sleep(0.25)

    def control(self, req: dict, timeout: float = 120.0) -> dict:
        with socket.create_connection(("127.0.0.1", self.ctl_port),
                                      timeout=timeout) as s:
            fh = s.makefile("rwb")
            fh.write(json.dumps(req).encode() + b"\n")
            fh.flush()
            out = json.loads(fh.readline())
        if "error" in out:
            raise BenchFailure(f"control {req.get('op')}: {out['error']}")
        return out

    def snapshot(self) -> dict:
        """Every counter a reader may want, at one moment."""
        return {
            "t": time.time(),
            "engines": {k: v for k, v in
                        self.request("GET", "/engines").items() if ":" in k},
            "healthz": self.healthz(),
            "scheduler": self.request("GET", "/scheduler"),
            "metrics": parse_prometheus(self.request("GET", "/metrics")),
        }

    def stop(self) -> int | None:
        """SIGTERM, wait, and make sure nothing of the group is left."""
        rc = None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                rc = self.proc.wait(timeout=60.0)
            except subprocess.TimeoutExpired:
                note("the server ignored SIGTERM for 60 s; killing it")
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()
        self._log.close()
        return rc


_PROM = re.compile(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})?\s+(\S+)")


def parse_prometheus(text: str) -> dict[str, float]:
    """``name{labels}`` -> value, for the ``_sum``/``_count``/``_total``
    series (quantile lines carry exemplars and are not needed)."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#" or "quantile=" in line:
            continue
        m = _PROM.match(line)
        if m:
            try:
                out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
            except ValueError:
                pass
    return out


MESH_RE = re.compile(
    r"mesh: (\{.*?\}) over (\d+) devices \(([^,)]+), ([^)]+)\)")


# --------------------------------------------------------------------- run


class Run:
    """What a traffic generator gets: the server, the sink, the cell's
    data files, and the clock rules of the window."""

    def __init__(self, *, cell, config, traffic, seed, seconds, trace,
                 out_dir, server, sink, rehearsal):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.out_dir, self.server, self.sink = out_dir, server, sink
        self.rehearsal = rehearsal
        self.before: dict | None = None
        self.after: dict | None = None
        self.trace_before: dict | None = None
        self.trace_after: dict | None = None
        self.trace_dir: Path | None = None
        self.traces: dict | None = None
        self.setup_s: float | None = None
        self.window: tuple[float, float] | None = None
        #: POSTs answered 503 during the start and asked again
        self.refused_posts = 0

    # -- requests ---------------------------------------------------------

    def post_stream(self, index: int, uri: str, realtime: bool) -> dict:
        """POST one pipeline instance with an MQTT destination of its own
        topic. Returns {id, topic, uri}."""
        req = self.config["request"]
        topic = f"bench/{index:03d}"
        body = {
            "source": {"uri": uri, "type": "uri", "realtime": realtime},
            "destination": {"metadata": {
                "type": "mqtt", "host": f"127.0.0.1:{self.sink.port}",
                "topic": topic}},
        }
        if req.get("parameters"):
            body["parameters"] = req["parameters"]
        if self.traffic.get("declared_fps"):
            # what the stream claims of the box at admission (the request's
            # ``fps``; the server assumes 30 where none is given)
            body["fps"] = self.traffic["declared_fps"]
        # A 503 with Retry-After is the server's honest answer while its
        # capacity model still reads a fresh process's first, slow batches
        # (one stall of the first second reads as 55 fps of capacity): a
        # client waits and asks again. This is set-up; the window opens only
        # after every stream has run for the settle time.
        give_up = time.monotonic() + 60.0
        while True:
            try:
                iid = self.server.request(
                    "POST", f"/pipelines/{req['pipeline']}", body,
                    timeout=120.0)
                break
            except urllib.error.HTTPError as exc:
                text = exc.read().decode(errors="replace")[:300]
                if exc.code == 503 and time.monotonic() < give_up:
                    try:
                        wait = float(json.loads(text)["retry_after_s"])
                    except (ValueError, KeyError):
                        wait = 3.0
                    note(f"POST of stream {index} answered 503, asking "
                         f"again in {wait:.0f} s: {text}")
                    self.refused_posts += 1
                    time.sleep(wait)
                    continue
                raise BenchFailure(
                    f"POST of stream {index} refused: HTTP {exc.code} "
                    f"{text}") from None
        return {"index": index, "id": iid, "topic": topic, "uri": uri}

    def status(self, stream: dict) -> dict:
        return self.server.request(
            "GET", f"/pipelines/{self.config['request']['pipeline']}/"
                   f"{stream['id']}/status")

    def delete_stream(self, stream: dict) -> None:
        try:
            self.server.request(
                "DELETE", f"/pipelines/{self.config['request']['pipeline']}/"
                          f"{stream['id']}", timeout=60.0)
        except (urllib.error.URLError, OSError) as exc:
            note(f"DELETE of stream {stream['index']} failed: {exc}")

    def wait_first_message(self, stream: dict, timeout_s: float = 120.0):
        end = time.monotonic() + timeout_s
        while stream["topic"] not in self.sink.first_seen:
            self.server.check_alive()
            if time.monotonic() > end:
                raise BenchFailure(
                    f"stream {stream['index']} published nothing in "
                    f"{timeout_s:.0f} s")
            time.sleep(0.01)

    # -- the window -------------------------------------------------------

    def open_window(self, t_open: float) -> None:
        """Snapshot the counters just before ``t_open``, sleep up to it,
        and stop the set-up clock."""
        time.sleep(max(0.0, t_open - 0.6 - time.time()))
        self.before = self.server.snapshot()
        time.sleep(max(0.0, t_open - time.time()))
        self.setup_s = t_open - T_PROCESS_START
        self.window = (t_open, t_open + self.seconds)

    def run_window(self) -> None:
        """Sleep through the window and snapshot the counters at its end.
        A traced run then takes a profiler trace of ``trace.seconds`` from
        the same, still running traffic, AFTER the window: starting and
        stopping the profiler stalls the server's threads for 6-30 s
        (measured), which inside the window would be read as the
        program's own delay."""
        _, t_close = self.window
        time.sleep(max(0.0, t_close - time.time()))
        self.after = self.server.snapshot()
        if not self.trace:
            return
        # the frame traces the ring holds now are the window's own
        self.traces = self.server.request("GET", "/traces", timeout=120.0)
        spec = self.traffic.get("trace", {})
        time.sleep(max(0.0, t_close + float(spec.get("lead_s", 2.0))
                       - time.time()))
        self.trace_dir = self.out_dir / "profile"
        self.trace_before = self.server.snapshot()
        t0 = time.time()
        self.server.control({"op": "trace_start", "dir": str(self.trace_dir)})
        time.sleep(float(spec.get("seconds", 3.0)))
        self.server.control({"op": "trace_stop"}, timeout=300.0)
        self.trace_after = self.server.snapshot()
        note(f"profiler trace of {spec.get('seconds', 3.0)} s taken in "
             f"{time.time() - t0:.1f} s, after the window")


def device_of(server: Server) -> dict:
    dev = server.control({"op": "device"})
    m = MESH_RE.search(server.log_path.read_text(errors="replace"))
    if m is None:
        raise BenchFailure("the server logged no 'mesh: ... over N devices'")
    if (m.group(3).strip() != dev["platform"]
            or int(m.group(2)) != dev["count"]):
        raise BenchFailure(
            f"the server's mesh ({m.group(0)}) is not what JAX reports "
            f"({dev})")
    return dev


def build_native() -> None:
    """The host resize + I420 encode library is built in the checkout
    (it is git-ignored); a run builds it if it is missing or stale."""
    r = subprocess.run(["make", "-C", str(REPO / "native")],
                       capture_output=True, text=True)
    if r.returncode != 0:
        raise BenchFailure(
            f"native/evam_media.cpp did not build:\n{r.stderr[-2000:]}")


def prepare_models(config: dict, rehearsal: bool) -> Path | None:
    """The configuration's models, installed the way an operator without
    network installs them: the program's own ``fetch-models`` writes the
    IR (.xml/.bin, weights from the builder's fixed seed) into a models
    directory at a fixed path inside the checkout, once; later runs find
    it. A CPU child does it BEFORE the server takes the chip."""
    jobs = config.get("rehearsal_models" if rehearsal else "models")
    if not jobs:
        return None
    root = REPO / "benchmark_out" / "models" / (
        config["name"] + ("_rehearsal" if rehearsal else ""))
    stamp = root / "installed.json"
    want = json.dumps(jobs, sort_keys=True)
    if stamp.is_file() and stamp.read_text() == want:
        return root
    shutil.rmtree(root, ignore_errors=True)
    root.mkdir(parents=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    for job in jobs:
        cmd = [sys.executable, "-m", "evam_tpu.cli.main", *job["argv"],
               "--output", str(root)]
        r = subprocess.run(cmd, cwd=str(REPO), env=env,
                           capture_output=True, text=True, timeout=600)
        if r.returncode != 0:
            raise BenchFailure(
                f"{' '.join(job['argv'])} failed ({r.returncode}):\n"
                + r.stderr[-2000:])
    stamp.write_text(want)
    return root


def model_file(config: dict, rehearsal: bool, suffix: str) -> Path:
    """The installed IR file (``.xml`` or ``.bin``) of the configuration's
    detector: what the server loads is what the reference reads."""
    root = REPO / "benchmark_out" / "models" / (
        config["name"] + ("_rehearsal" if rehearsal else ""))
    hits = sorted((root / config["request"]["pipeline"]).glob(f"*/*{suffix}"))
    if not hits:
        raise BenchFailure(f"no {suffix} under {root}")
    return hits[0]


def server_env(config: dict, rehearsal: bool,
               models_dir: Path | None = None) -> dict[str, str]:
    env = dict(os.environ)
    for key in ("EVAM_PRELOAD", "EVAM_SERIALIZE_COMPILE", "EVAM_NO_NATIVE",
                "EVAM_FLEET", "PROFILING_MODE", "MODELS_DIR"):
        env.pop(key, None)
    env.update({k: str(v) for k, v in config["server_env"].items()})
    if models_dir is not None:
        env["MODELS_DIR"] = str(models_dir)
    if rehearsal:
        env.update({k: str(v) for k, v in
                    config.get("rehearsal_env", {}).items()})
        env["JAX_PLATFORMS"] = "cpu"
    else:
        # the program fails at start-up where it finds no accelerator;
        # a CPU forced from outside is a rehearsal, not a run
        env.pop("JAX_PLATFORMS", None)
    return env


def load_cell(workload: str, bench_path: Path | None = None
              ) -> tuple[dict, dict, dict, dict]:
    bench = load_json(bench_path or REPO / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(
            f"BENCHMARK.json has no workload {workload!r} "
            f"(it has {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(REPO / cfg_entry["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, config, traffic


def metric_files(bench: dict, section: str, workload: str) -> list[dict]:
    """The metric entries of ``section`` that this cell reports."""
    out = []
    for m in bench[section]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        out.append(m)
    return out


def generator_for(traffic: dict):
    kind = traffic["kind"]
    if not re.fullmatch(r"[a-z0-9_]+", kind):
        raise BenchFailure(f"traffic kind {kind!r} is not a module name")
    try:
        return importlib.import_module(f"benchmark.generators.{kind}")
    except ModuleNotFoundError:
        raise BenchFailure(
            f"no generator benchmark/generators/{kind}.py") from None


def read_per_layer(bench: dict, workload: str, ctx: dict) -> dict:
    """Each per-layer metric through the reader its own file names. A
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    out = {}
    for entry in metric_files(bench, "per_layer", workload):
        spec = load_json(HERE / "metrics" / f"{entry['name']}.json")
        reader = spec["reader"]
        if not re.fullmatch(r"[a-z0-9_]+", reader):
            raise BenchFailure(f"reader {reader!r} is not a module name")
        mod = importlib.import_module(f"benchmark.readers.{reader}")
        value = mod.read(ctx, spec.get("params", {}))
        if value is None:
            continue
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out
