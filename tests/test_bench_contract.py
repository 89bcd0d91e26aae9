"""The driver contract on bench.py: exactly ONE JSON line on stdout
with metric/value/unit/vs_baseline — and a NON-ZERO exit whenever the
bench could not run (the JSON error line still prints)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _run_bench(args, env_extra, timeout=420):
    env = dict(os.environ, **env_extra)
    r = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=str(REPO),
    )
    return r


def _assert_contract(r):
    lines = [l for l in r.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"stdout must be ONE JSON line, got: {r.stdout!r}"
    data = json.loads(lines[0])
    assert {"metric", "value", "unit", "vs_baseline"} <= set(data)
    assert isinstance(data["value"], (int, float))
    return data


def test_bench_healthy_cpu_run_emits_contract_line():
    r = _run_bench(
        ["--config", "audio", "--seconds", "2", "--batch", "4",
         "--depth", "2", "--ingest", "host"],
        {"JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stderr[-1500:]
    data = _assert_contract(r)
    # audio streams normalize by window rate (5/s at the reference's
    # 0.2 s sliding-window stride), not 30 fps — bench._metric_for
    assert data["metric"] == "audio_streams_per_chip"
    assert data["value"] > 0
    assert {"batch", "depth", "p50_ms", "p99_ms"} <= set(data)
    # host-latency attribution rides the contract line: the raw
    # --ingest host loop reports the transfer-honest split (h2d_issue
    # = device_put enqueue, h2d_wait = the copy's blocking residual)
    # next to launch dispatch + readback wait, matching the engine
    # stage clock (ringbuf.STAGES)
    assert {"h2d_issue", "h2d_wait", "launch", "readback"} \
        <= set(data["host_stage_p50_ms"])


def test_bench_serve_emits_contract_line():
    """The SERVE path (bench.run_serve_bench: a PipelineRegistry's
    free-running synthetic streams into the hub's engines) completes
    and prints the one contract line."""
    r = _run_bench(
        ["--config", "serve", "--streams", "2", "--seconds", "4",
         "--batch", "4", "--stall-timeout", "120"],
        {"JAX_PLATFORMS": "cpu"},
        timeout=900,
    )
    assert r.returncode == 0, r.stderr[-1500:]
    data = _assert_contract(r)
    assert data["metric"] == "serve_streams_30fps_per_chip"
    assert data["errors"] == 0
    assert data["dead_streams"] == 0
    # the serve line attributes host latency by engine stage
    # (ringbuf.STAGES) next to the throughput number, including the
    # transfer split (h2d_issue on the dispatcher, h2d_wait on the
    # launcher)
    assert {"slot_write", "h2d_issue", "h2d_wait", "launch",
            "readback"} <= set(data["host_stage_p50_ms"])
    # QoS-layer outcome rides the line per class (evam_tpu/sched/):
    # both bench streams admit as `standard`, nothing rejected/shed
    for key in ("sched_admitted", "sched_rejected", "sched_shed"):
        assert set(data[key]) == {"realtime", "standard", "batch"}, key
    assert data["sched_admitted"]["standard"] == 2
    assert sum(data["sched_rejected"].values()) == 0
    # compile-cache accounting rides the line (engine/ragged.py):
    # every bucket program this run compiled, the number bucket
    # consolidation (EVAM_RAGGED=packed) is measured against
    assert data["compiled_programs"] >= 1
    # content-adaptive gating outcome rides the line too
    # (stages/gate.py): this run is ungated — the A/B baseline shape
    # is all-zero counts, fixed keys
    assert {"streams", "ran", "skipped", "skip_rate",
            "skipped_fps"} == set(data["gate"])
    assert data["gate"]["skipped"] == 0
    # fleet operating point rides the line with fixed keys whether
    # EVAM_FLEET is off (this run: mode=off, zero shards) or sharded
    # (evam_tpu/fleet/, hub.fleet_summary())
    assert {"mode", "shards", "degraded_shards", "rebalances",
            "streams", "max_shards", "scale_ups",
            "scale_downs"} == set(data["fleet"])
    assert data["fleet"]["mode"] == "off"
    assert data["fleet"]["shards"] == 0


def test_bench_fleet_smoke_scales_and_stays_bit_identical():
    """The fleet-scaling gate (tools/bench_fleet.py --smoke): 1 vs 2
    host-platform shards must scale >= 1.5x through the consistent-
    hash placement + per-shard dispatch fabric, with per-stream
    outputs bit-identical across fleet sizes."""
    r = subprocess.run(
        [sys.executable, str(REPO / "tools" / "bench_fleet.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=300, cwd=str(REPO),
    )
    assert r.returncode == 0, r.stderr[-1500:]
    data = json.loads(r.stdout.strip().splitlines()[-1])
    assert data["metric"] == "streams_1080p_30fps_per_fleet"
    assert {"metric", "value", "unit", "vs_baseline", "ok",
            "identical"} <= set(data)
    assert data["ok"] is True
    assert data["identical"] is True
    assert data["vs_baseline"] >= 1.5


def test_bench_unreachable_device_exits_nonzero_with_error_line():
    """A backend that cannot initialize is a FAILURE: non-zero exit,
    with the parseable error line still on stdout (bench.py
    fail_line) — never a measured-looking 0.0 under exit 0."""
    r = _run_bench(
        ["--seconds", "1"],
        {"JAX_PLATFORMS": "nonexistent-backend"},
        timeout=180,
    )
    assert r.returncode != 0, r.stdout
    data = _assert_contract(r)
    assert data["value"] == 0.0
    assert "error" in data


def test_bench_refuses_a_cpu_backend_it_was_not_asked_for():
    """JAX falls back to the CPU when it finds no accelerator; unless
    JAX_PLATFORMS=cpu asked for that, the bench must fail instead of
    timing the CPU under a chip metric's name."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    r = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--seconds", "1"],
        capture_output=True, text=True, timeout=180, env=env,
        cwd=str(REPO))
    assert r.returncode != 0, r.stdout
    assert "did not ask for it" in r.stderr
