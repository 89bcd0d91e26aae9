"""What every traffic generator shares: stream URIs from the seed, the
serial start, message parsing, percentiles and the seeded sample that the
correctness check reads."""

from __future__ import annotations

import json
import math
import random
import time

from benchmark.harness import BenchFailure, Run, note
from benchmark.reference.compare import check_schema

NS = 1_000_000_000


def fps_text(fps: float) -> str:
    """The rate as it is written into the URI; ``float()`` of this text is
    the rate both sides compute with."""
    return f"{fps:.6f}".rstrip("0").rstrip(".")


def stream_seed(seed: int, index: int) -> int:
    """Where stream ``index``'s square starts (the URI's ``seed=``)."""
    return (seed + 7919 * index) % 1_000_003


def start_stream(run: Run, index: int, fps: float, realtime: bool) -> dict:
    """POST stream ``index`` and wait for its first message; the returned
    record holds its id, topic, URI, rate, period, seed and the
    ``start_time`` of its status payload."""
    tr = run.traffic
    text = fps_text(fps)
    seed = stream_seed(run.seed, index)
    uri = f"synthetic://{tr['width']}x{tr['height']}@{text}?seed={seed}"
    s = run.post_stream(index, uri, realtime)
    s.update(fps=float(text), seed=seed, period_ns=int(NS / float(text)))
    run.wait_first_message(s)
    st = run.status(s)
    if st["state"] != "RUNNING":
        raise BenchFailure(
            f"stream {index} is {st['state']} after its start: "
            f"{st.get('message')}")
    s["start_time"] = float(st["start_time"])
    return s


def start_streams(run: Run, rates: list[float], realtime: bool) -> list[dict]:
    """POST the streams one at a time, each after the one before has
    delivered its first message, so that no start races another."""
    return [start_stream(run, i, fps, realtime)
            for i, fps in enumerate(rates)]


def stop_streams(run: Run, streams: list[dict]) -> None:
    for s in streams:
        run.delete_stream(s)


def parse_messages(run: Run, streams: list[dict]) -> tuple[dict, list[str]]:
    """topic -> [(arrival, k, message)] in arrival order, and every fault
    of schema, order or multiplicity found on the way."""
    by_topic = {s["topic"]: s for s in streams}
    out: dict[str, list] = {s["topic"]: [] for s in streams}
    faults: list[str] = []
    for t, topic, payload in list(run.sink.messages):
        s = by_topic.get(topic)
        if s is None:
            faults.append(f"message on unknown topic {topic!r}")
            continue
        try:
            msg = json.loads(payload)
        except ValueError:
            faults.append(f"{topic}: payload is not JSON")
            continue
        bad = check_schema(msg)
        if bad:
            faults.append(f"{topic}: {bad}")
            continue
        k, rem = divmod(msg["timestamp"], s["period_ns"])
        if rem or msg["source"] != s["uri"]:
            faults.append(f"{topic}: timestamp {msg['timestamp']} or source "
                          "is not this stream's")
            continue
        rows = out[topic]
        # a gap is a frame the server shed or lost (the generator counts
        # it as a miss); a step backwards is disorder
        if rows and k <= rows[-1][1]:
            faults.append(f"{topic}: frame {k} after frame {rows[-1][1]} "
                          "(duplicate or out of order)")
            continue
        rows.append((t, k, msg))
    return out, faults[:20]


def percentile(sorted_values: list[float], p: float) -> float:
    """Nearest rank."""
    if not sorted_values:
        raise BenchFailure("no samples in the window")
    idx = max(0, math.ceil(p * len(sorted_values)) - 1)
    return sorted_values[idx]


def pick_sample(run: Run, candidates: dict[str, list]) -> list[tuple]:
    """A seeded sample of published frames, spread over the streams:
    ``(topic, k, message)``. ``candidates`` maps topic -> rows."""
    want = int(run.traffic.get("correct_frames", 12))
    rng = random.Random(run.seed)
    topics = sorted(t for t, rows in candidates.items() if rows)
    picked = []
    for j in range(want):
        if not topics:
            break
        rows = candidates[topics[j % len(topics)]]
        t, k, msg = rows[rng.randrange(len(rows))]
        picked.append((topics[j % len(topics)], k, msg))
    return picked


def settle_and_open(run: Run, streams: list[dict]) -> None:
    """Every stream has run for ``settle_s`` before the window opens: a
    fresh process's first-traffic stall falls into set-up."""
    settle = float(run.traffic.get("settle_s", 5.0))
    last_first = max(run.sink.first_seen[s["topic"]] for s in streams)
    run.open_window(max(time.time() + 0.7, last_first + settle))
    note(f"window open after {run.setup_s:.1f} s of set-up")
