"""The one process that holds the chip: ``evam_tpu.cli.main serve`` run
in-process, plus one idle control thread of the benchmark's own.

The program's REST surface names neither the device's memory nor a way to
take a profiler trace of a window, and only the process that holds the chip
can read either. So the benchmark starts the server through this launcher:
it calls the program's own ``main(["serve"])`` on the main thread, exactly
what ``python -m evam_tpu.cli.main serve`` does, and beside it a daemon
thread blocks in ``accept()`` on ``BENCH_CTL_PORT`` (127.0.0.1). It wakes
only when the harness asks, outside the measured window or in the traced
run:

  {"op": "device"}                 -> platform, kind, count, memory peak
  {"op": "trace_start", "dir": d}  -> jax.profiler.start_trace(d)
  {"op": "trace_stop"}             -> jax.profiler.stop_trace()

One JSON object per line in each direction.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _device() -> dict:
    import jax

    # On this runtime a program's temporaries do not show in
    # ``bytes_in_use``: they live in a pool the runtime reserves when the
    # program first runs and keeps (``bytes_reserved``; measured on a v5e,
    # PERF.md section 6: a step with 3.2 GB of temporaries left
    # peak_bytes_in_use at 0.2 GB, peak_bytes_reserved at 3.2 GB and the
    # largest free block smaller by both). The peak on the chip is the sum.
    devs = jax.local_devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _handle(req: dict) -> dict:
    op = req.get("op")
    if op == "device":
        return _device()
    if op == "trace_start":
        import jax

        # device and runtime (TraceMe) events only: the Python tracer
        # would record every call of every thread and slow the host
        # path that the traced window is there to show
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(req["dir"], profiler_options=opts)
        return {"ok": True}
    if op == "trace_stop":
        import jax

        jax.profiler.stop_trace()
        return {"ok": True}
    return {"error": f"unknown op {op!r}"}


def _control(srv: socket.socket) -> None:
    while True:
        try:
            conn, _ = srv.accept()
        except OSError:
            return
        with conn:
            fh = conn.makefile("rwb")
            for line in fh:
                try:
                    out = _handle(json.loads(line))
                except Exception as exc:  # noqa: BLE001 - reported to the harness
                    out = {"error": f"{type(exc).__name__}: {exc}"}
                fh.write(json.dumps(out).encode() + b"\n")
                fh.flush()


def main() -> int:
    sys.path.insert(0, str(REPO))
    port = int(os.environ["BENCH_CTL_PORT"])
    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", port))
    srv.listen(4)
    threading.Thread(target=_control, args=(srv,), name="bench-ctl",
                     daemon=True).start()
    from evam_tpu.cli.main import main as cli_main

    return cli_main(["serve"])


if __name__ == "__main__":
    sys.exit(main())
