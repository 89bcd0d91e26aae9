"""REST layer tests: full request→stream→destination flow against the
reference API surface (charts/templates/NOTES.txt:7-21) using synthetic
sources and small models on the CPU mesh."""

import asyncio
import json
import threading
import time
from pathlib import Path

import pytest
from aiohttp.test_utils import TestClient, TestServer

from evam_tpu.config import Settings
from evam_tpu.engine import EngineHub
from evam_tpu.models import ModelRegistry, ZOO_SPECS
from evam_tpu.parallel import build_mesh
from evam_tpu.server.app import build_app
from evam_tpu.server.registry import PipelineRegistry

REPO = Path(__file__).resolve().parent.parent
SMALL = {k: (64, 64) for k in ZOO_SPECS}
SMALL["audio_detection/environment"] = (1, 1600)
NARROW = {k: 8 for k in ZOO_SPECS}


def _registry(state_dir, **hub_kw):
    settings = Settings(
        pipelines_dir=str(REPO / "pipelines"), state_dir=str(state_dir))
    model_registry = ModelRegistry(dtype="float32", input_overrides=SMALL,
                                   width_overrides=NARROW)
    hub = EngineHub(model_registry, plan=build_mesh(), max_batch=16,
                    deadline_ms=4.0, **hub_kw)
    return PipelineRegistry(settings, hub=hub)


@pytest.fixture(scope="module")
def registry(eight_devices, tmp_path_factory):
    reg = _registry(tmp_path_factory.mktemp("state"))
    yield reg
    reg.stop_all()


@pytest.fixture
def warming_registry(eight_devices, tmp_path):
    """A fresh hub that warms its engines in the background, as
    ``serve`` does (the shared one above compiles on first batch)."""
    reg = _registry(tmp_path, warmup=True)
    yield reg
    reg.stop_all()


def _request(registry, method, path, body=None):
    async def go():
        app = build_app(registry)
        async with TestClient(TestServer(app)) as client:
            resp = await client.request(method, path, json=body)
            try:
                data = await resp.json()
            except Exception:
                data = await resp.text()
            return resp.status, data

    return asyncio.run(go())


def _wait_state(registry, iid, states=("COMPLETED",), timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        inst = registry.get_instance(iid)
        if inst is not None and inst.state.value in states:
            return inst
        time.sleep(0.2)
    raise AssertionError(
        f"instance {iid} did not reach {states}; "
        f"now {registry.get_instance(iid).state}"
    )


class TestRoutes:
    def test_list_pipelines(self, registry):
        status, data = _request(registry, "GET", "/pipelines")
        assert status == 200
        names = {(p["name"], p["version"]) for p in data}
        assert ("object_detection", "person_vehicle_bike") in names
        assert len(names) >= 11

    def test_describe(self, registry):
        status, data = _request(
            registry, "GET", "/pipelines/object_detection/person")
        assert status == 200
        assert "parameters" in data

    def test_describe_missing_404(self, registry):
        status, data = _request(registry, "GET", "/pipelines/nope/v1")
        assert status == 404

    def test_models(self, registry):
        status, data = _request(registry, "GET", "/models")
        assert status == 200
        rows = {f"{d['name']}/{d['version']}": d["weights"] for d in data}
        assert "object_detection/person_vehicle_bike" in rows
        # hermetic test env: provenance must say so, not pretend
        assert rows["object_detection/person_vehicle_bike"] == "random"
        # the gate rides every row (VERDICT r4 item 7): "random" is
        # only servable because EVAM_ALLOW_RANDOM_WEIGHTS permits it
        assert all(d["allow_random_weights"] is True for d in data)

    def test_healthz_and_metrics(self, registry):
        status, data = _request(registry, "GET", "/healthz")
        assert status == 200
        assert data["status"] in ("ok", "warming")
        assert {"engines", "warmed", "warming"} <= set(data)
        status, text = _request(registry, "GET", "/metrics")
        assert status == 200

    def test_healthz_reports_host_stage_clock(self, registry):
        """Host-overhead attribution (VERDICT r5 weak #5): /healthz
        carries the batch-weighted mean per-batch stage clock
        (slot_write / device_put / launch / readback) with fixed keys
        so an operator can see WHERE a batch's time goes."""
        from evam_tpu.engine.ringbuf import STAGES

        # enough frames that the bucket serves a second, warm batch:
        # a cold bucket's launch is its compile and stays out of the
        # service-time clock (BatchEngine._record_batch)
        body = {
            "source": {"uri": "synthetic://96x96@30?count=12",
                       "type": "uri"},
            "destination": {"metadata": {"type": "null"}},
        }
        status, iid = _request(
            registry, "POST",
            "/pipelines/object_detection/person_vehicle_bike", body)
        assert status == 200
        _wait_state(registry, iid)
        status, data = _request(registry, "GET", "/healthz")
        assert status == 200
        stages = data.get("host_stages_ms")
        assert stages is not None, data
        assert set(stages) == set(STAGES)
        # batches have dispatched by now: the launch span is real time
        assert stages["launch"] > 0.0, stages

    def test_preload_builds_engines_before_traffic(self, registry):
        """Serve-time preload (VERDICT item 7): engines for the named
        pipeline exist (and their buckets warm) before the first POST,
        and the instance start path reuses them (cache hit — no
        compile in the request hot path)."""
        before = set(registry.hub.stats())
        n = registry.preload("object_detection/person")
        assert n == 1
        created = set(registry.hub.stats()) - before
        assert any(k.startswith("detect:") for k in created)
        # a started instance reuses the preloaded engine, not a new one
        body = {
            "source": {"uri": "synthetic://96x96@30?count=2", "type": "uri"},
            "destination": {"metadata": {"type": "null"}},
        }
        status, iid = _request(
            registry, "POST", "/pipelines/object_detection/person", body)
        assert status == 200
        _wait_state(registry, iid)
        assert set(registry.hub.stats()) == before | created

    def test_preload_failure_stops_startup(self, registry, monkeypatch):
        """A pipeline named in EVAM_PRELOAD that is unknown, fails to
        build, or fails to compile must RAISE (run_server then never
        opens the port) — not log a warning and serve without it."""
        with pytest.raises(KeyError, match="no_such_pipeline"):
            registry.preload("no_such_pipeline")

        from evam_tpu.server import registry as registry_mod

        def broken_build(*args, **kwargs):
            raise RuntimeError("model build exploded")

        monkeypatch.setattr(registry_mod, "build_stages", broken_build)
        with pytest.raises(RuntimeError, match="model build exploded"):
            registry.preload("object_detection/person")

    def test_preload_of_a_failing_warmup_stops_startup(
            self, warming_registry, monkeypatch):
        from evam_tpu.engine.batcher import BatchEngine

        def broken_warmup(self):
            raise RuntimeError("bucket compile exploded")

        monkeypatch.setattr(BatchEngine, "warmup", broken_warmup)
        with pytest.raises(RuntimeError, match="bucket compile exploded"):
            warming_registry.preload("object_detection/person")
        # the reason is on the REST surface too, not only in the log
        status, rows = _request(warming_registry, "GET", "/engines")
        assert status == 200
        assert any("bucket compile exploded" in (r["warm_error"] or "")
                   for r in rows.values())

    def test_preload_of_a_hanging_warmup_times_out(
            self, warming_registry, monkeypatch):
        """Warmup compiles are not under the stall watchdog; a compile
        that hangs must fail start-up at a deadline instead of holding
        the port shut forever (and `bench.py --config serve` with
        it)."""
        from evam_tpu.engine.batcher import BatchEngine

        release = threading.Event()
        monkeypatch.setattr(warming_registry.hub, "stall_timeout_s", 0.05)
        monkeypatch.setattr(
            BatchEngine, "warmup", lambda self: release.wait(60))
        try:
            with pytest.raises(TimeoutError, match="not finished"):
                warming_registry.preload("object_detection/person")
        finally:
            release.set()

    def test_engines_device_column_names_every_mesh_device(
            self, registry, eight_devices):
        """A mesh engine runs on every device of its plan; /engines
        must say so (one name was `flat[0]` only) — a multi-chip
        placement is then checkable from the REST surface."""
        registry.preload("object_detection/person")
        status, data = _request(registry, "GET", "/engines")
        assert status == 200
        rows = [r for k, r in data.items() if k.startswith("detect:")]
        assert rows
        for row in rows:
            assert row["device"].split() == [
                str(d) for d in eight_devices]


class TestInstanceLifecycle:
    def test_full_flow(self, registry, tmp_path):
        out_file = tmp_path / "results.jsonl"
        body = {
            "source": {"uri": "synthetic://96x96@30?count=6", "type": "uri"},
            "destination": {
                "metadata": {"type": "file", "path": str(out_file)}
            },
            "parameters": {"detection-properties": {"threshold": 0.0}},
        }
        status, iid = _request(
            registry, "POST", "/pipelines/object_detection/person", body)
        assert status == 200, iid
        inst = _wait_state(registry, iid)

        status, data = _request(
            registry, "GET",
            f"/pipelines/object_detection/person/{iid}/status")
        assert status == 200
        assert data["state"] == "COMPLETED"
        assert data["id"] == iid
        # per-engine weight provenance in the status payload (VERDICT
        # r4 item 7): the hermetic env serves random-init weights and
        # the consumer must be able to see that
        assert "weights" in data
        stage_rows = list(data["weights"].values())
        assert stage_rows, "no inference stage reported provenance"
        assert all(
            src == "random"
            for row in stage_rows for src in row["weights"].values()
        )

        lines = [json.loads(l) for l in out_file.read_text().splitlines()]
        assert len(lines) == 6
        meta = lines[0]
        # §6-schema metadata (reference charts/README.md:117)
        assert set(meta) >= {"objects", "resolution", "source", "timestamp"}
        assert meta["resolution"] == {"height": 96, "width": 96}

    def test_bad_body_400(self, registry):
        status, data = _request(
            registry, "POST", "/pipelines/object_detection/person", {})
        assert status == 400

    def test_unknown_pipeline_404(self, registry):
        status, data = _request(
            registry, "POST", "/pipelines/nope/v1",
            {"source": {"uri": "synthetic://64x64@30?count=1"}})
        assert status == 404

    def test_delete_aborts(self, registry):
        body = {
            "source": {"uri": "synthetic://96x96@30?count=100000",
                       "realtime": True},
            "destination": {"metadata": {"type": "null"}},
        }
        status, iid = _request(
            registry, "POST", "/pipelines/video_decode/app_dst", body)
        assert status == 200
        status, data = _request(
            registry, "DELETE", f"/pipelines/video_decode/app_dst/{iid}")
        assert status == 200
        inst = _wait_state(registry, iid, states=("ABORTED", "COMPLETED"))
        assert inst.state.value in ("ABORTED", "COMPLETED")

    def test_statuses_listing(self, registry):
        status, data = _request(registry, "GET", "/pipelines/status")
        assert status == 200
        assert isinstance(data, list) and data


class TestPersistence:
    def test_state_file_roundtrip(self, registry):
        body = {
            "source": {"uri": "synthetic://96x96@30?count=100000",
                       "realtime": True},
            "destination": {"metadata": {"type": "null"}},
        }
        status, iid = _request(
            registry, "POST", "/pipelines/video_decode/app_dst", body)
        assert status == 200
        state_file = Path(registry.settings.state_dir) / "streams.json"
        entries = json.loads(state_file.read_text())
        assert any(e["pipeline"] == "video_decode" for e in entries)
        _request(registry, "DELETE", f"/pipelines/video_decode/app_dst/{iid}")

    def test_completed_streams_not_resumed(self, registry):
        # A finished stream must leave the state file (no duplicate
        # replay on restart); a drain (stop_all) rewrites the file but
        # only with still-active, non-deleted streams.
        body = {
            "source": {"uri": "synthetic://96x96@30?count=2", "type": "uri"},
            "destination": {"metadata": {"type": "null"}},
        }
        status, iid = _request(
            registry, "POST", "/pipelines/video_decode/app_dst", body)
        assert status == 200
        _wait_state(registry, iid)
        time.sleep(0.3)  # on_finish persist
        state_file = Path(registry.settings.state_dir) / "streams.json"
        entries = json.loads(state_file.read_text())
        assert not any(
            e["request"]["source"]["uri"].endswith("count=2") for e in entries
        )


class TestStageStatePersistence:
    def test_tracker_ids_survive_restart(self, tmp_path_factory):
        """Tracker id monotonicity across a server restart: the
        resumed stream must not re-issue object_ids a consumer already
        saw (SURVEY §7 'tracking statefulness' + §5.4 resume)."""
        from evam_tpu.stages.track import TrackStage

        state_dir = tmp_path_factory.mktemp("trackstate")
        settings = Settings(
            pipelines_dir=str(REPO / "pipelines"),
            state_dir=str(state_dir),
        )
        model_registry = ModelRegistry(
            dtype="float32", input_overrides=SMALL, width_overrides=NARROW)
        hub = EngineHub(model_registry, plan=build_mesh(), max_batch=16,
                        deadline_ms=4.0)
        reg = PipelineRegistry(settings, hub=hub)
        body = {
            # realtime + huge count pins the stream open so it cannot
            # COMPLETE (and self-remove from streams.json) between the
            # id poll and stop_all
            "source": {"uri": "synthetic://96x96@30?count=100000",
                       "realtime": True, "type": "uri"},
            "destination": {"metadata": {"type": "null"}},
            "parameters": {"detection-threshold": 0.0},
        }
        inst = reg.start_instance(
            "object_tracking", "person_vehicle_bike", body)
        track = next(s for s in inst.stages if isinstance(s, TrackStage))
        deadline = time.time() + 120
        while track.tracker._next_id <= 1 and time.time() < deadline:
            time.sleep(0.1)
        assert track.tracker._next_id > 1, "tracker never assigned ids"
        reg.stop_all()  # persists current stage state, keeps the file
        high_water = track.tracker._next_id

        reg2 = PipelineRegistry(settings, hub=hub)
        assert reg2.resume() == 1
        inst2 = next(iter(reg2.instances.values()))
        track2 = next(s for s in inst2.stages if isinstance(s, TrackStage))
        # restored BEFORE the stream started: first new id >= high water
        assert track2.tracker._next_id >= high_water
        reg2.stop_all()


class TestDemuxResume:
    @pytest.mark.slow
    def test_live_rtsp_stream_resumes_through_demux(
            self, tmp_path_factory):
        """Crash-resume (SURVEY §5.4) for a live demux-routed stream:
        a persisted rtsp:// instance re-attaches through the shared
        demux on the next boot and keeps producing frames. Slow: two
        full pipeline boots over live RTSP — the fast suite's <90 s
        budget excludes it."""
        from tests._rtsp_helpers import start_camera_server

        srv, stop_feed = start_camera_server(1, fps=15.0,
                                             size=(96, 96))

        state_dir = tmp_path_factory.mktemp("demuxstate")
        settings = Settings(
            pipelines_dir=str(REPO / "pipelines"),
            state_dir=str(state_dir),
            rtsp_demux_workers=1,
        )
        model_registry = ModelRegistry(
            dtype="float32", input_overrides=SMALL,
            width_overrides=NARROW)
        hub = EngineHub(model_registry, plan=build_mesh(),
                        max_batch=16, deadline_ms=4.0)
        reg = PipelineRegistry(settings, hub=hub)
        body = {
            "source": {"uri": f"rtsp://127.0.0.1:{srv.port}/cam0",
                       "type": "uri"},
            "destination": {"metadata": {"type": "null"}},
            "parameters": {"detection-properties": {"threshold": 0.0}},
        }
        try:
            inst = reg.start_instance(
                "object_detection", "person_vehicle_bike", body)
            deadline = time.time() + 120
            while time.time() < deadline and (
                    inst._runner is None or not inst._runner.frames_out):
                time.sleep(0.1)
            assert inst._runner and inst._runner.frames_out > 0
            reg.stop_all()       # persists; keeps streams.json

            reg2 = PipelineRegistry(settings, hub=hub)
            assert reg2.resume() == 1
            inst2 = next(iter(reg2.instances.values()))
            deadline = time.time() + 120
            while time.time() < deadline and (
                    inst2._runner is None
                    or not inst2._runner.frames_out):
                time.sleep(0.1)
            assert inst2._runner and inst2._runner.frames_out > 0, \
                "resumed stream produced no frames through the demux"
            assert inst2.state.value == "RUNNING"
            # it really is on the demux: the shared selector serves it
            assert reg2.rtsp_demux is not None
            assert reg2.rtsp_demux.stats()["streams"] == 1
            reg2.stop_all()
        finally:
            stop_feed.set()
            srv.stop()
