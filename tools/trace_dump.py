#!/usr/bin/env python
"""Render a /traces capture (or a flight-recorder JSONL) to Chrome
trace-event JSON.

The serving side exposes the tail-sampled trace ring three ways
(evam_tpu/obs/trace.py); this tool is the consumer: pull ``GET
/traces`` from a running service (or read a saved payload / flight
JSONL), write a ``chrome://tracing`` / Perfetto-loadable file, and
assert the linkage property the tracing layer exists for — batch spans
that name >= 2 member frame trace ids and carry the full
h2d_issue/h2d_wait/launch/readback stage clock.

    python tools/trace_dump.py --url http://localhost:8080/traces \
        --out /tmp/evam_traces.json --require-linked 1

Stdlib only (urllib), importable by tests: ``convert``,
``events_from_flight``, ``linked_batches``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from evam_tpu.obs.trace import batch_events  # noqa: E402

#: the transfer/compute stages a linked batch span must clock for the
#: acceptance check (readback rides completion, so it proves the batch
#: made the full round trip)
LINK_STAGES = ("h2d_issue", "h2d_wait", "launch", "readback")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def convert(payload: dict) -> dict:
    """A /traces payload -> a Chrome trace-event file body. The route
    already serves ready-made events; this validates the shape and
    wraps them with the displayTimeUnit header."""
    events = payload.get("traceEvents", [])
    if not isinstance(events, list):
        raise ValueError("payload.traceEvents must be a list")
    return {"displayTimeUnit": "ms", "traceEvents": events}


def events_from_flight(rows: list[dict]) -> list[dict]:
    """Flight-recorder JSONL rows -> Chrome trace events (same layout
    as the live route: frame spans per stream track, one batch span
    per record plus a slice per stage and per named wait at its real
    start)."""
    events: list[dict] = []
    for row in rows:
        kind = row.get("type")
        if kind == "frame":
            for span in row.get("spans", ()):
                args = {"trace_id": row.get("trace_id"),
                        "seq": row.get("seq"), "class": row.get("class"),
                        "status": row.get("status")}
                args.update(span.get("attrs", {}))
                events.append({
                    "name": span["name"], "ph": "X", "cat": "frame",
                    "ts": round(span["t0"] * 1e6, 1),
                    "dur": round(span["dur_s"] * 1e6, 1),
                    "pid": "frames", "tid": row.get("stream", ""),
                    "args": args,
                })
        elif kind == "batch":
            batch = batch_events(row)
            batch[0]["args"]["pending"] = row.get("pending", False)
            events.extend(batch)
    return events


def linked_batches(events: list[dict]) -> int:
    """How many batch spans link >= 2 member frame spans AND carry the
    full transfer/compute stage clock — the acceptance property."""
    count = 0
    for ev in events:
        if ev.get("cat") != "batch":
            continue
        args = ev.get("args", {})
        if len(args.get("frames", ())) >= 2 \
                and all(s in args.get("stages", {}) for s in LINK_STAGES):
            count += 1
    return count


def _fetch(url: str) -> dict:
    from urllib.request import urlopen

    with urlopen(url, timeout=10) as resp:
        return json.loads(resp.read().decode("utf-8"))


def main() -> int:
    p = argparse.ArgumentParser()
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--url", help="GET this /traces endpoint")
    src.add_argument("--input", help="saved /traces JSON payload file")
    src.add_argument("--flight", help="flight-recorder JSONL artifact")
    p.add_argument("--out", default="/tmp/evam_traces.json",
                   help="Chrome trace-event output path")
    p.add_argument("--require-linked", type=int, default=0,
                   help="exit 1 unless >= N batch spans link >= 2 "
                        "frame spans with the full h2d/launch/readback "
                        "stage clock")
    args = p.parse_args()

    if args.flight:
        rows = [json.loads(line) for line in
                Path(args.flight).read_text(encoding="utf-8").splitlines()
                if line.strip()]
        header = next((r for r in rows if r.get("type") == "flight"), {})
        if header:
            log(f"flight dump: engine={header.get('engine')} "
                f"reason={header.get('reason')!r} "
                f"profiler_running={header.get('profiler_running')}")
        body = {"displayTimeUnit": "ms",
                "traceEvents": events_from_flight(rows)}
    else:
        payload = _fetch(args.url) if args.url else json.loads(
            Path(args.input).read_text(encoding="utf-8"))
        log(f"payload: enabled={payload.get('enabled')} "
            f"retained={payload.get('retained')} "
            f"frames={payload.get('frames')} "
            f"batches={payload.get('batches')} "
            f"pending={payload.get('pending')}")
        body = convert(payload)

    linked = linked_batches(body["traceEvents"])
    Path(args.out).write_text(json.dumps(body), encoding="utf-8")
    print(json.dumps({
        "out": args.out,
        "events": len(body["traceEvents"]),
        "linked_batches": linked,
        "ok": linked >= args.require_linked,
    }))
    if linked < args.require_linked:
        log(f"FAIL: {linked} linked batch span(s) < "
            f"required {args.require_linked}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
