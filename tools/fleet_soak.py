#!/usr/bin/env python
"""Chip-loss + elastic-ramp soaks: reshape the fleet, fail zero streams.

Default mode — chip loss (ISSUE 11): a multi-chip fleet is serving
realtime + standard streams when one chip wedges hard
(``EVAM_FAULT_INJECT wedge``, the PR-4 fault hook, armed mid-run with
a zero restart budget so the supervisor takes the shard to terminal
``degraded`` — a lost chip, not a recoverable stall). The contract
under that loss:

* the shard's streams MIGRATE (consistent-hash drain-and-rebalance,
  counted on ``evam_fleet_rebalance_total`` via
  ``fleet_summary()["rebalances"]``);
* in-flight work on the dead shard resolves or sheds PER CLASS
  POLICY (``evam_sched_shed_total`` / ``hub.shed_totals()``) — it
  does not hang;
* every realtime stream keeps completing frames after the loss:
  chip loss degrades fleet capacity, never a stream's liveness.

``--ramp`` mode — elastic scaling (ISSUE 18): an elastic fleet grows
2→8→2 one shard at a time under live realtime tracking streams: one
``scale_up()`` or ``scale_down()`` of every fleet per step. A seed
phase first warms a full-peak fleet against a fresh EVAM_AOT_DIR, so
every grow during the ramp is a CACHE-HIT spin-up (deserialize, not
compile). The contract under the ramp:

* every grow joins warm-before-join with spin-up-to-first-batch under
  the acceptance bound (full mode; CI runners share cores);
* streams moved by ring growth/shrink are checkpointed through the
  PR-17 path (``evam_stream_migrations_total{reason="scale_up"|
  "scale_down"}``, pre_rebalance barrier, blobs decode) — identity
  continuity, not cold starts;
* zero realtime streams fail or stop progressing at any fleet size.

Exit 0 iff the mode's contract holds. Prints ONE JSON line on stdout;
diagnostics on stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("EVAM_ALLOW_RANDOM_WEIGHTS", "1")
os.environ.setdefault("EVAM_LOG_LEVEL", "warning")
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

MODEL = "object_detection/person_vehicle_bike"
#: ramp mode serves the tracking pipeline: gate + IouTracker +
#: coaster state live per stream, so a migrated stream has identity
#: to keep (the checkpoint-path continuity the ramp asserts)
PIPELINE = ("object_tracking", "person_vehicle_bike")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _build_hub(shards: int):
    import jax

    from evam_tpu.engine.hub import EngineHub
    from evam_tpu.models import ModelRegistry, ZOO_SPECS
    from evam_tpu.parallel.mesh import build_mesh

    overrides = {k: (64, 64) for k in ZOO_SPECS}
    overrides["audio_detection/environment"] = (1, 1600)
    registry = ModelRegistry(
        dtype="float32", input_overrides=overrides,
        width_overrides={k: 8 for k in ZOO_SPECS})
    plan = build_mesh(devices=list(jax.devices())[:shards])
    return EngineHub(
        registry, plan=plan, max_batch=16, deadline_ms=2.0,
        supervise=True, max_restarts=0, stall_timeout_s=1.0,
        first_batch_grace=15.0, fleet="sharded")


def _build_ramp_registry(shards: int, initial: int = 0,
                         max_shards: int = 0):
    """A PipelineRegistry over a sharded hub, warmed. ``initial`` > 0
    starts the fleet smaller than the mesh (the elastic shape);
    0 builds every shard (the seed shape)."""
    import jax

    from evam_tpu.config import Settings
    from evam_tpu.engine.hub import EngineHub
    from evam_tpu.models import ModelRegistry, ZOO_SPECS
    from evam_tpu.parallel.mesh import build_mesh
    from evam_tpu.server.registry import PipelineRegistry

    overrides = {k: (64, 64) for k in ZOO_SPECS}
    overrides["audio_detection/environment"] = (1, 1600)
    registry = ModelRegistry(
        dtype="float32", input_overrides=overrides,
        width_overrides={k: 8 for k in ZOO_SPECS})
    plan = build_mesh(devices=list(jax.devices())[:shards])
    hub = EngineHub(
        registry, plan=plan, max_batch=16, deadline_ms=4.0,
        warmup=True, supervise=True, max_restarts=3,
        restart_backoff_s=0.1, fleet="sharded",
        fleet_max_shards=max_shards, fleet_initial_shards=initial)
    settings = Settings(pipelines_dir=str(REPO / "pipelines"),
                       state_dir="")
    reg = PipelineRegistry(settings, hub=hub)
    reg.preload(f"{PIPELINE[0]}/{PIPELINE[1]}")
    deadline = time.time() + 300
    while time.time() < deadline:
        ready = hub.readiness()
        if ready["engines"] and not ready["warming"]:
            return reg
        time.sleep(0.1)
    reg.stop_all()
    raise RuntimeError("engines never warmed")


def _ramp_streams(reg, n: int):
    """Long-lived synthetic realtime tracking streams: they must
    outlast the whole ramp, so liveness (frame progress at every
    fleet size) is the assertion, not completion."""
    return [
        reg.start_instance(
            *PIPELINE,
            {
                "source": {
                    "uri": f"synthetic://96x96@30?count=1000000&seed={i}",
                    "type": "uri",
                    "realtime": True,
                },
                "destination": {"metadata": {"type": "null"}},
                "priority": "realtime",
            },
        )
        for i in range(n)
    ]


def _progress(insts) -> dict:
    return {i.id: (i._runner.frames_out if i._runner else 0)
            for i in insts}


def ramp(args) -> int:
    """Elastic 2→peak→2 ramp under traffic (ISSUE 18 acceptance)."""
    import tempfile

    # elastic env: persistent AOT cache (fresh dir) + checkpointed
    # migration, resolved before any hub exists
    os.environ["EVAM_AOT"] = "1"
    os.environ["EVAM_AOT_DIR"] = tempfile.mkdtemp(prefix="evam-ramp-aot-")
    os.environ["EVAM_CKPT"] = "1"

    from evam_tpu import aot
    from evam_tpu import state as stream_state
    from evam_tpu.config.settings import reset_settings
    from evam_tpu.state import decode

    reset_settings()
    aot.reset_cache()
    stream_state.reset_cache()

    peak = 4 if args.smoke else args.peak
    base = args.base
    if not base < peak:
        raise SystemExit(f"--base {base} must be < peak {peak}")

    # ---- seed: a full-peak fleet warms once against the empty cache,
    # so an executable exists for every device the ramp grows onto —
    # every scale_up below is then a cache-hit (deserialize) spin-up
    t0 = time.perf_counter()
    reg = _build_ramp_registry(peak)
    reg.stop_all()
    seeded = aot.summary() or {}
    log(f"seed: warmed {peak} shards in {time.perf_counter() - t0:.1f}s "
        f"({seeded.get('entries', 0)} cache entries, "
        f"{seeded.get('misses', {}).get('absent', 0)} cold compiles)")

    # ---- ramp: the elastic fleet starts at base with ckpt on
    reg = _build_ramp_registry(peak, initial=base, max_shards=peak)
    hub = reg.hub
    store = stream_state.active()
    fleets = [e for e in list(hub._engines.values())
              if hasattr(e, "scale_up")]
    spinups: list[float] = []
    stuck = None
    try:
        insts = _ramp_streams(reg, args.streams)
        time.sleep(1.5)  # gate/tracker state accumulates pre-move
        pre = _progress(insts)

        targets = (list(range(base + 1, peak + 1))
                   + list(range(peak - 1, base - 1, -1)))
        prev = base
        for n in targets:
            # one shard per step: scale_up returns once the new shard
            # is warm and in the ring, scale_down once its streams have
            # moved — poll until the summary shows it
            for f in fleets:
                if n > prev:
                    f.scale_up()
                else:
                    f.scale_down()
            deadline = time.monotonic() + 120.0
            while hub.fleet_summary()["shards"] != n:
                if time.monotonic() >= deadline:
                    stuck = n
                    break
                time.sleep(0.1)
            if stuck is not None:
                log(f"ramp STUCK: fleet never reached {n} shards")
                break
            if n > prev:
                spinups.append(max(f._last_spinup_s for f in fleets))
                log(f"fleet at {n} shard(s) — spin-up "
                    f"{spinups[-1]:.2f}s (warm-before-join)")
            else:
                log(f"fleet at {n} shard(s) — drained one")
            prev = n
            time.sleep(args.dwell_s)

        post = _progress(insts)
        states = [i.state.value for i in insts]
        summary = hub.fleet_summary()
        aot_sum = aot.summary() or {}
        mig = store.summary()["migrations"] if store else {}
        blobs = ([store.export(i.id) for i in insts]
                 if store else [])
    finally:
        reg.stop_all()

    # migrated-identity continuity: every held blob decodes (CRC +
    # schema). The pre-move barrier itself is proven by the
    # migrations counters — only pre_rebalance/retire captures carry
    # a reason — not by blob barriers: a stream's held blob is its
    # LATEST capture, and the steady-state post_resolve refresh can
    # overwrite the pre-move one before export.
    decoded, barriers = 0, set()
    for blob in blobs:
        if blob is None:
            continue
        ck = decode(blob)  # raises on CRC/version damage
        decoded += 1
        barriers.add(ck.barrier)

    stalled = [i.id[:8] for i in insts if post[i.id] <= pre[i.id]]
    errored = [s for s in states if s == "ERROR"]
    hits = aot_sum.get("hits", 0) - seeded.get("hits", 0)
    max_spinup = max(spinups) if spinups else -1.0

    ok = bool(
        stuck is None
        and spinups
        and summary["shards"] == base
        and summary["scale_ups"] >= peak - base
        and summary["scale_downs"] >= peak - base
        and not errored and not stalled
        and mig.get("scale_up", 0) >= 1
        and mig.get("scale_down", 0) >= 1
        and decoded >= 1
        and hits > 0)
    if not args.smoke and spinups:
        # the acceptance wall-clock gate rides only the full shape:
        # CI runners share cores
        ok = ok and max_spinup < args.gate_s

    log(f"ramp {base}->{peak}->{base}: spin-ups "
        f"{[round(s, 2) for s in spinups]}, migrations {mig}, "
        f"stalled {stalled}, errored {len(errored)}, "
        f"aot hits during ramp {hits}, blob barriers "
        f"{sorted(barriers)}")

    print(json.dumps({
        "metric": "fleet_ramp_max_spinup_s",
        "value": round(max_spinup, 3),
        "unit": "s",
        "vs_baseline": args.gate_s,
        "ok": ok,
        "ramp": f"{base}->{peak}->{base}",
        "scale_ups": summary["scale_ups"],
        "scale_downs": summary["scale_downs"],
        "rebalances": summary["rebalances"],
        "failed_realtime_streams": len(errored) + len(stalled),
        "migrations": mig,
        "checkpoints_decoded": decoded,
        "aot_hits": hits,
        "smoke": args.smoke,
    }))
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--realtime", type=int, default=8)
    ap.add_argument("--standard", type=int, default=4)
    ap.add_argument("--pre-s", type=float, default=3.0,
                    help="healthy traffic before the chip loss")
    ap.add_argument("--post-s", type=float, default=4.0,
                    help="observation window after the loss")
    ap.add_argument("--wedge-s", type=float, default=60.0)
    ap.add_argument("--ramp", action="store_true",
                    help="elastic 2→peak→2 scaling soak (ISSUE 18) "
                         "instead of the chip-loss drill")
    ap.add_argument("--smoke", action="store_true",
                    help="ramp CI shape: peak 4, no wall-clock gate "
                         "(runners share cores)")
    ap.add_argument("--peak", type=int, default=8,
                    help="ramp ceiling (full mode; smoke uses 4)")
    ap.add_argument("--base", type=int, default=2,
                    help="ramp floor / initial fleet size")
    ap.add_argument("--streams", type=int, default=6,
                    help="realtime tracking streams during the ramp")
    ap.add_argument("--dwell-s", type=float, default=1.0,
                    help="traffic window at each fleet size")
    ap.add_argument("--gate-s", type=float, default=5.0,
                    help="cache-hit spin-up-to-first-batch bound "
                         "(full mode; the ISSUE-18 acceptance number)")
    args = ap.parse_args()
    if args.ramp:
        return ramp(args)

    import numpy as np

    from evam_tpu.obs import faults
    from evam_tpu.ops.color import wire_shape

    hub = _build_hub(args.shards)
    eng = hub.engine("detect", MODEL)
    frame = np.zeros(tuple(wire_shape("i420", 64, 64)), np.uint8)

    streams = ([(f"rt{i}", "realtime") for i in range(args.realtime)]
               + [(f"std{i}", "standard") for i in range(args.standard)])

    # warm every shard's hot bucket before arming the fault: the wedge
    # must hit a mid-traffic batch, not a first-compile one
    for sid, prio in streams:
        eng.submit(priority=prio, stream=sid, frames=frame).result(
            timeout=120)
    log(f"warmed {len(streams)} streams over {args.shards} shards")

    stop = threading.Event()
    post_loss = threading.Event()
    done_pre = {sid: 0 for sid, _ in streams}
    done_post = {sid: 0 for sid, _ in streams}
    errors = {sid: 0 for sid, _ in streams}

    def pump(sid, prio):
        while not stop.is_set():
            try:
                fut = eng.submit(priority=prio, stream=sid,
                                 frames=frame)
                fut.result(timeout=10)
            except Exception:
                # shed / restarting / degraded-shard window: the
                # stream retries — liveness is the assertion, not
                # per-frame success during the loss transient
                errors[sid] += 1
                time.sleep(0.05)
                continue
            (done_post if post_loss.is_set() else done_pre)[sid] += 1
            time.sleep(0.02)

    threads = [threading.Thread(target=pump, args=s, daemon=True)
               for s in streams]
    for t in threads:
        t.start()
    time.sleep(args.pre_s)

    # chip loss: wedge exactly one batch for longer than the stall
    # timeout, with a zero restart budget -> terminal degraded shard
    os.environ["EVAM_FAULT_INJECT"] = (
        f"wedge=1,wedge_s={args.wedge_s},wedge_n=1")
    faults.reset_cache()
    log("fault armed: wedge=1 (one batch, terminal)")

    deadline = time.monotonic() + 45.0
    degraded = 0
    while time.monotonic() < deadline:
        degraded = hub.fleet_summary()["degraded_shards"]
        if degraded >= 1:
            break
        time.sleep(0.2)
    log(f"degraded shards: {degraded}")
    post_loss.set()
    time.sleep(args.post_s)
    stop.set()
    for t in threads:
        t.join(timeout=5)

    summary = hub.fleet_summary()
    sheds = hub.shed_totals()
    failed_rt = [sid for sid, prio in streams
                 if prio == "realtime" and done_post[sid] == 0]
    ok = bool(degraded >= 1 and not failed_rt)

    log(f"pre-loss completions: {sum(done_pre.values())}, post-loss: "
        f"{sum(done_post.values())}, transient errors: "
        f"{sum(errors.values())}")
    log(f"fleet: {summary}, sheds: {sheds}, failed realtime streams: "
        f"{failed_rt}")

    print(json.dumps({
        "metric": "fleet_soak_failed_realtime_streams",
        "value": len(failed_rt),
        "unit": "streams",
        "vs_baseline": 0.0,
        "ok": ok,
        "degraded_shards": summary["degraded_shards"],
        "rebalances": summary["rebalances"],
        "sheds": sheds,
        "post_loss_completions": sum(done_post.values()),
        "transient_errors": sum(errors.values()),
    }))
    hub.stop()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
