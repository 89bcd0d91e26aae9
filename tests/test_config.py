import os

from evam_tpu.config import Settings, interpolate_env, interpolate_tree


def test_settings_defaults():
    s = Settings()
    assert s.rest_port == 8080
    assert s.rtsp_port == 8554
    assert s.run_mode == "EVA"
    # 128: the latency-leaning serving default (TPUSettings.max_batch)
    assert s.tpu.max_batch == 128


def test_settings_from_env(monkeypatch):
    monkeypatch.setenv("RUN_MODE", "EII")
    monkeypatch.setenv("DETECTION_DEVICE", "cpu")
    monkeypatch.setenv("ENABLE_RTSP", "true")
    monkeypatch.setenv("EVAM_MAX_BATCH", "16")
    monkeypatch.setenv("EVAM_PRELOAD", "all")
    monkeypatch.setenv("EVAM_STALL_TIMEOUT_S", "45.5")
    monkeypatch.setenv("EVAM_PRECISION", "int8")
    monkeypatch.setenv("EVAM_RAGGED", "packed")
    monkeypatch.setenv("EVAM_RAGGED_UNIT_BUDGET", "3")
    s = Settings.from_env()
    assert s.run_mode == "EII"
    assert s.detection_device == "cpu"
    assert s.enable_rtsp is True
    assert s.tpu.max_batch == 16
    assert s.preload == "all"
    assert s.tpu.stall_timeout_s == 45.5
    assert s.tpu.precision == "int8"
    assert s.tpu.ragged == "packed"
    assert s.tpu.ragged_unit_budget == 3


def test_settings_ragged_default_off():
    # EVAM_RAGGED=off stays the serving default until a TPU window
    # banks packed-vs-bucketed numbers (ROADMAP)
    assert Settings().tpu.ragged == "off"


def test_settings_file_then_env_override(tmp_path, monkeypatch):
    cfg = tmp_path / "cfg.json"
    cfg.write_text('{"rest_port": 9090, "run_mode": "EII"}')
    monkeypatch.setenv("RUN_MODE", "EVA")
    s = Settings.from_env(cfg)
    assert s.rest_port == 9090
    assert s.run_mode == "EVA"  # env wins over file


def test_interpolate_env(monkeypatch):
    monkeypatch.setenv("DETECTION_DEVICE", "tpu")
    assert interpolate_env("{env[DETECTION_DEVICE]}") == "tpu"
    assert interpolate_env("{env[NOT_SET_ANYWHERE_42]}") == ""
    tree = {"a": ["{env[DETECTION_DEVICE]}", 3], "b": {"c": "x"}}
    assert interpolate_tree(tree) == {"a": ["tpu", 3], "b": {"c": "x"}}


# ---------------------------------------------------------- no fallbacks

class TestRequestedBackend:
    """``serve`` (and bench.py) refuse a CPU backend nobody asked for:
    JAX falls back to the CPU when it finds no chip, and a server that
    carried on would answer at CPU speed under a TPU name."""

    @staticmethod
    def _guard(monkeypatch, backend, env):
        import jax

        from evam_tpu.parallel.mesh import require_requested_backend

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        if env is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", env)
        require_requested_backend()

    def test_unrequested_cpu_is_refused(self, monkeypatch):
        import pytest

        for env in (None, "", "tpu", "tpu,cpu"):
            with pytest.raises(SystemExit, match="did not ask for it"):
                self._guard(monkeypatch, "cpu", env)

    def test_explicit_cpu_and_any_accelerator_pass(self, monkeypatch):
        self._guard(monkeypatch, "cpu", "cpu")
        self._guard(monkeypatch, "cpu", " CPU ")
        self._guard(monkeypatch, "tpu", None)
        self._guard(monkeypatch, "tpu", "tpu,cpu")

    def test_serve_checks_before_it_builds_anything(self, monkeypatch):
        import pytest

        from evam_tpu.cli import main as cli
        from evam_tpu.parallel import mesh

        def refuse():
            raise SystemExit("refused")

        monkeypatch.setattr(mesh, "require_requested_backend", refuse)
        monkeypatch.setattr(
            cli, "get_settings",
            lambda: pytest.fail("settings resolved before the guard"))
        with pytest.raises(SystemExit, match="refused"):
            cli.main(["serve"])


class TestCompilationCachePlacement:
    """The compile cache is placed from outside: where JAX's own
    variable is set the code sets NO directory; otherwise it is one
    fixed path inside the checkout (the path is part of every entry's
    key, so it never derives from tempdir/uid/pid/time)."""

    @staticmethod
    def _configure(monkeypatch):
        import jax

        from evam_tpu.obs import trace

        writes = {}
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: writes.__setitem__(k, v))
        return trace.configure_compilation_cache(), writes

    def test_env_set_means_no_config_write(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        used, writes = self._configure(monkeypatch)
        assert used == str(tmp_path)
        assert "jax_compilation_cache_dir" not in writes

    def test_env_unset_means_the_fixed_in_checkout_path(self, monkeypatch):
        from pathlib import Path

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        used, writes = self._configure(monkeypatch)
        repo = Path(__file__).resolve().parent.parent
        assert used == str(repo / ".jax_cache")
        assert writes["jax_compilation_cache_dir"] == used
        # the same answer every time: nothing per-process in the path
        assert self._configure(monkeypatch)[0] == used

    def test_the_old_knob_is_gone(self, monkeypatch):
        monkeypatch.setenv("EVAM_COMPILE_CACHE_DIR", "/somewhere/else")
        assert not hasattr(Settings.from_env().tpu, "compile_cache_dir")
