"""chip_smoke.py and the no-hidden-fallback guards it leans on.

The checks run on doctored REST payloads (an engine that restarted, a
frame that errored, a short metadata file, a device that is not the
one asked for) — each must FAIL the smoke — plus one real end-to-end
CPU rehearsal of the script at a cut size, which can never print the
chip result line."""

from __future__ import annotations

import copy
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", REPO / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


# ------------------------------------------------ the smoke's own checks

def _row(**over) -> dict:
    row = {
        "batches": 10, "stage_batches": 10, "compiled_programs": 8,
        "buckets": [1, 2, 4, 8, 16, 32, 64, 128], "warm_error": None,
        "compile_s": 60.0, "restarts": 0, "state": "running",
        "bucket_batches": {"1": 2, "8": 8}, "shard": None,
        "device": "TPU_0(process=0,(0,0,0,0))", "group": "detect:m",
        "stage_ms": {"h2d_issue": 1.0, "h2d_wait": 0.5, "launch": 1.0,
                     "readback": 2.0},
    }
    row.update(over)
    return row


def _snap(row: dict, **over) -> dict:
    snap = {"engines": {"detect:m": row}, "frame_errors": 0, "shed": 0,
            "rejected": 0, "healthz": {"status": "ok", "warming": 0}}
    snap.update(over)
    return snap


def _records(tmp_path, lines: int = 4, state: str = "COMPLETED"):
    path = tmp_path / "w.jsonl"
    path.write_text("{}\n" * lines)
    return [{"id": "abcdef0123", "pipeline": "p/v", "state": state,
             "path": path, "lines": lines}]


@pytest.fixture
def warm_wave(tmp_path):
    """A passing warm wave: 4 frames asked, 4 lines written, 10 more
    steady batches, nothing else moved."""
    before = _snap(_row())
    after = _snap(_row(
        batches=20, stage_batches=20,
        bucket_batches={"1": 3, "8": 17}))
    return _records(tmp_path), before, after


def test_clean_warm_wave_passes(warm_wave):
    records, before, after = warm_wave
    assert chip_smoke.check_warm_wave(records, before, after, 4, "w") == []


@pytest.mark.parametrize("mutate,needle", [
    (lambda r, b, a: a["engines"]["detect:m"].update(restarts=1),
     "restarts=1"),
    (lambda r, b, a: a["engines"]["detect:m"].update(state="degraded"),
     "state=degraded"),
    (lambda r, b, a: a.update(frame_errors=1), "frame_errors rose by 1"),
    (lambda r, b, a: a.update(shed=2), "shed rose by 2"),
    (lambda r, b, a: a.update(rejected=1), "rejected rose by 1"),
    (lambda r, b, a: r[0].update(lines=3), "3 metadata lines, want 4"),
    (lambda r, b, a: r[0].update(state="ERROR"), "ended ERROR"),
    (lambda r, b, a: a["healthz"].update(status="restarting"),
     "/healthz ended restarting"),
    (lambda r, b, a: a["engines"]["detect:m"].update(compiled_programs=9),
     "compiled in steady state"),
    (lambda r, b, a: a["engines"]["detect:m"].update(
        bucket_batches={"1": 12, "8": 8}), "bucket larger than 1"),
    (lambda r, b, a: a["engines"]["detect:m"]["stage_ms"].update(
        readback=1.0), "['readback']"),
    (lambda r, b, a: a["engines"]["detect:m"].update(
        warm_error="XlaRuntimeError: RESOURCE_EXHAUSTED"),
     "warmup failed: XlaRuntimeError"),
    (lambda r, b, a: [e["detect:m"].update(compiled_programs=7)
                      for e in (b["engines"], a["engines"])],
     "holds 7 programs for the 8-bucket ladder"),
])
def test_each_broken_contract_fails_the_warm_wave(warm_wave, mutate,
                                                  needle):
    records, before, after = copy.deepcopy(warm_wave[0]), \
        copy.deepcopy(warm_wave[1]), copy.deepcopy(warm_wave[2])
    mutate(records, before, after)
    bad = chip_smoke.check_warm_wave(records, before, after, 4, "w")
    assert any(needle in b for b in bad), bad


def test_first_wave_tolerates_a_shed_frame_but_not_a_lost_stream(
        warm_wave):
    """A process's first traffic may shed a stale frame (compile
    stalls, first-use costs); it may not lose a stream or restart an
    engine — and a restarted server's first wave may not compile, nor
    shed more than one stall's worth of frames in flight."""
    records, before, after = copy.deepcopy(warm_wave)
    after.update(shed=3, frame_errors=3)
    records[0]["lines"] = 1
    assert chip_smoke.check_first_wave(records, before, after, "w") == []
    assert chip_smoke.check_first_wave(
        records, before, after, "w", shed_cap=4) == []
    bad = chip_smoke.check_first_wave(
        records, before, after, "w", shed_cap=2)
    assert any("shed 3 frames, more than the 2" in b for b in bad), bad
    assert chip_smoke.check_no_compile(before, after, "w") == []
    records[0]["state"] = "ABORTED"
    after["engines"]["detect:m"]["restarts"] = 2
    after["engines"]["detect:m"]["compiled_programs"] = 9
    bad = chip_smoke.check_first_wave(records, before, after, "w")
    assert any("ended ABORTED" in b for b in bad), bad
    assert any("restarts=2" in b for b in bad), bad
    assert chip_smoke.check_no_compile(before, after, "w")


def test_stage_clock_between_snapshots_is_the_waves_own_mean():
    before = _row(stage_batches=10, stage_ms={"launch": 4.0})
    after = _row(stage_batches=30, stage_ms={"launch": 2.0})
    # (2.0 * 30 - 4.0 * 10) / 20 new steady batches
    assert chip_smoke.stage_ms_between(before, after) == {"launch": 1.0}


TPU1 = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
TPU4 = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def _tpu(i: int) -> str:
    return f"TPU_{i}(process=0,({i},0,0,0))"


def test_placement_one_chip_and_the_wrong_backend():
    assert chip_smoke.check_placement({"detect:m": _row()}, TPU1) == []
    bad = chip_smoke.check_placement(
        {"detect:m": _row(device="TFRT_CPU_0")}, TPU1)
    assert any("not on tpu" in b for b in bad), bad


def test_placement_mesh_engine_must_name_every_device():
    full = " ".join(_tpu(i) for i in range(4))
    assert chip_smoke.check_placement(
        {"detect:m": _row(device=full)}, TPU4) == []
    bad = chip_smoke.check_placement(
        {"detect:m": _row(device=_tpu(0))}, TPU4)
    assert any("names 1 device(s), JAX reported 4" in b for b in bad), bad


def test_placement_fleet_needs_one_busy_shard_per_device():
    def shards(devices, idle=()):
        return {f"detect:m@s{i}": _row(
            shard=f"s{i}", device=d, batches=0 if i in idle else 5)
            for i, d in enumerate(devices)}

    assert chip_smoke.check_placement(
        shards([_tpu(i) for i in range(4)]), TPU4) == []
    bad = chip_smoke.check_placement(
        shards([_tpu(0)] * 4), TPU4)  # everything on chip 0
    assert any("want one per each of 4 devices" in b for b in bad), bad
    bad = chip_smoke.check_placement(
        shards([_tpu(i) for i in range(4)], idle=(2,)), TPU4)
    assert any("['s2'] served no batch" in b for b in bad), bad


def test_mesh_line_parses_platform_kind_and_count():
    m = chip_smoke.MESH_RE.search(
        "INFO [evam_tpu.parallel.mesh] mesh: {'data': 4} over 4 "
        "devices (tpu, TPU v5 lite)")
    assert m.groups() == ("{'data': 4}", "4", "tpu", "TPU v5 lite")


def test_smoke_source_never_imports_jax():
    """A parent that touched JAX would hold the chip the server needs."""
    import ast

    tree = ast.parse((REPO / "chip_smoke.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            imported.add(node.module.split(".")[0])
    assert not imported & {"jax", "jaxlib", "evam_tpu", "numpy", "flax"}


# -------------------------------------------------- the script, end to end

def _smoke(args, env, cwd=REPO, timeout=600):
    return subprocess.run(
        [sys.executable, str(Path(cwd) / "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=str(cwd))


def _env(**over) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "EVAM_FAULT_INJECT")}
    env.update(over)
    return env


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text())
    r = _smoke([], _env(), cwd=tmp_path, timeout=60)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_on_a_cpu_backend_it_fails_without_a_result(tmp_path):
    """The sandbox case: JAX_PLATFORMS=cpu in the environment is not
    the rehearsal flag — the server comes up, says cpu, and the smoke
    refuses before any wave."""
    r = _smoke(["--workdir", str(tmp_path)], _env(JAX_PLATFORMS="cpu"),
               timeout=300)
    assert r.returncode != 0
    assert "not 'tpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_cpu_rehearsal_runs_every_phase(tmp_path):
    # the cache placed from outside (JAX reads its own variable)
    r = _smoke(["--rehearse-cpu", "--streams", "4", "--frames", "6",
                "--workdir", str(tmp_path / "work")],
               _env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
               timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert lines[0].startswith("platform: cpu ")
    assert "CPU REHEARSAL" in lines[0]
    last = json.loads(lines[-1])
    assert last == {"rehearsal_ok": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    assert '"ok"' not in r.stdout  # never the chip result line
    for phase in ("wave1", "wave2", "wave3 (warm, admitted by the live",
                  "wave4 (warm restart)", "cold server: SIGTERM",
                  "warm server: SIGTERM", "wire-encode: native",
                  "compile cache:"):
        assert any(phase in l for l in lines), phase
