"""Reduction of a profiler trace to what the result line needs.

  python benchmark/trace_reduce.py <profile dir | events.json> <out.json> \\
      [<ladder: 1,2,4,...>]

From the ``.xplane.pb`` under ``<dir>/plugins/profile/*/`` (read with
``jax.profiler.ProfileData``; this is a CPU child, the server holds the
chip) or from a recorded list of events (``events.json``, the rehearsal's
small trace), it computes per device plane:

  busy_s      union of the intervals in which an operation ran
  window_s    first operation's start to last operation's end
  steps       executions of a whole program (the "XLA Modules" line)
  steps_by_batch  those executions by the batch their program runs
              (``batch_by_program``)
  device_ops  operation -> summed device seconds, largest first
  idle_gaps   the longest gaps between operations, each named by the
              runtime (TraceMe) span on a host thread that overlaps it
              most, or "unattributed" where the host recorded none

and averages busy_s over the device planes. An event is
``[plane, line, name, start_ns, duration_ns]``.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from pathlib import Path

#: lines of a device plane that hold single operations / whole programs
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
#: host events that span whole threads or sessions name nothing
HOST_SKIP = ("ThreadpoolListener", "$")


_HLO = re.compile(r"%(\S+) = (\(?[a-z0-9]+\[[0-9,]*\])")


def short_name(name: str) -> str:
    """``%fusion.253 = bf16[32,256,256,32]{...} fusion(...)`` ->
    ``fusion.253 bf16[32,256,256,32]``: the operation and the shape it
    makes, which tells the programs of different buckets apart."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2).lstrip('(')}" if m else name[:64]


def load_events(src: Path) -> tuple[list[list], str | None]:
    """The events, and for a recorded trace the chip it was taken on."""
    if src.is_file():
        rec = json.loads(src.read_text())
        return rec["events"], rec.get("device_kind")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    paths = sorted(glob.glob(str(src / "plugins" / "profile" / "*" /
                                 "*.xplane.pb")))
    if not paths:
        raise SystemExit(f"no .xplane.pb under {src}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:")
        is_host = plane.name.startswith("/host:CPU")
        if not (is_dev or is_host):
            continue
        for line in plane.lines:
            if is_dev and line.name not in OP_LINES + MODULE_LINES:
                continue
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                name = ev.name
                if is_host and name.startswith(HOST_SKIP):
                    continue
                if is_dev:
                    name = short_name(name)
                events.append([plane.name, line.name, name,
                               float(ev.start_ns), float(ev.duration_ns)])
    return events, None


def union_ns(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """Total covered time and the gaps between covered stretches."""
    total = 0.0
    gaps = []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def name_gap(gap: tuple[float, float], host: list[list]) -> str:
    a, b = gap
    best, best_overlap = "unattributed", 0.0
    for _, line, name, s, d in host:
        overlap = min(b, s + d) - max(a, s)
        # a span many times longer than the gap is a thread's life,
        # not what the host was doing in it
        if overlap > best_overlap and d < 20 * (b - a):
            best, best_overlap = f"{name} [{line.split('/')[0]}]", overlap
    return best


_LEAD = re.compile(r"\[(\d+)[,\]]")


def batch_by_program(mod_ev: list[list], op_ev: list[list],
                     ladder: set[str] | None) -> dict[str, str]:
    """program name -> the batch it runs. Each bucket of the ladder is a
    program of its own (``jit_step(<fingerprint>)``); its batch is the
    leading output dimension that most operations inside its executions
    carry, counted over ALL its executions in the trace and, where the
    ladder is known, only among the ladder's sizes (the compiler's tiled
    layouts lead with 512 or 1024)."""
    op_ev = sorted(op_ev, key=lambda e: e[3])
    starts = [e[3] for e in op_ev]
    votes: dict[str, dict[str, int]] = {}
    for m in mod_ev:
        s, e = m[3], m[3] + m[4]
        tally = votes.setdefault(m[2], {})
        for ev in op_ev[bisect.bisect_left(starts, s):
                        bisect.bisect_right(starts, e)]:
            lead = _LEAD.search(ev[2])
            if lead and ev[3] + ev[4] <= e and (
                    ladder is None or lead.group(1) in ladder):
                tally[lead.group(1)] = tally.get(lead.group(1), 0) + 1
    return {name: max(t, key=t.get) for name, t in votes.items() if t}


def reduce_events(events: list[list],
                  ladder: set[str] | None = None) -> dict:
    dev_planes = sorted({e[0] for e in events if e[0].startswith("/device:")})
    host = [e for e in events if not e[0].startswith("/device:")]
    if not dev_planes:
        raise SystemExit("the trace holds no device plane: no operation "
                         "ran on a device in the traced window")
    busy = []
    window = []
    steps = 0
    by_batch: dict[str, int] = {}
    ops: dict[str, float] = {}
    gaps_all: list[tuple[float, float]] = []
    for plane in dev_planes:
        op_ev = [e for e in events if e[0] == plane and e[1] in OP_LINES]
        mod_ev = [e for e in events if e[0] == plane and e[1] in MODULE_LINES]
        if not op_ev:
            continue
        total, gaps = union_ns([(e[3], e[3] + e[4]) for e in op_ev])
        busy.append(total / 1e9)
        window.append((max(e[3] + e[4] for e in op_ev)
                       - min(e[3] for e in op_ev)) / 1e9)
        steps += len(mod_ev)
        batch = batch_by_program(mod_ev, op_ev, ladder)
        for m in mod_ev:
            if m[2] in batch:
                by_batch[batch[m[2]]] = by_batch.get(batch[m[2]], 0) + 1
        for e in op_ev:
            ops[e[2]] = ops.get(e[2], 0.0) + e[4] / 1e9
        gaps_all += gaps
    if not busy:
        raise SystemExit("no operation ran on a device in the traced window")
    gaps_all.sort(key=lambda g: g[0] - g[1])
    return {
        "devices": len(busy),
        "busy_s": sum(busy) / len(busy),
        "window_s": max(window),
        "steps": steps,
        "steps_by_batch": by_batch,
        "device_ops": [[n, s] for n, s in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[name_gap(g, host), (g[1] - g[0]) / 1e9]
                      for g in gaps_all[:10]],
    }


def main() -> int:
    src, out = Path(sys.argv[1]), Path(sys.argv[2])
    events, kind = load_events(src)
    ladder = set(sys.argv[3].split(",")) if len(sys.argv) > 3 else None
    reduced = reduce_events(events, ladder)
    if kind:
        reduced["device_kind"] = kind
    out.write_text(json.dumps(reduced, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
