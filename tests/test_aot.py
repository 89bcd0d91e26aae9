"""Persistent AOT executable cache (evam_tpu/aot/, EVAM_AOT).

Tier-1 coverage for the elastic-fleet tentpole's cache half: the
content-addressed key is stable across process restarts and sensitive
to everything that changes the compiled program; every rung of the
fallback ladder (absent / version / crc / deserialize / execute)
falls back to jit loudly with the right ``reason`` counter and never
a crash; the size-capped store evicts oldest-first; a second engine
spin-up is served from the cache (aot_hits == buckets, zero compile
seconds) with bit-identical outputs; and EVAM_AOT=off (the default)
resolves to None once and stays byte-identical to the plain path.
"""

from __future__ import annotations

import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

from evam_tpu import aot
from evam_tpu.aot.cache import (
    MISS_REASONS,
    AotCache,
    _EntryError,
    _pack_entry,
    _unpack_entry,
    cache_key,
    env_fingerprint,
)
from evam_tpu.config.settings import reset_settings
from evam_tpu.engine.batcher import BatchEngine

pytestmark = pytest.mark.aot

_KEY_ARGS = dict(
    program="detect:m|wire=i420|synth=0|ragged=off|ub=0|sched=0",
    bucket=8,
    inputs=[("frames", (8, 64, 64, 3), "uint8")],
    params_sig=[((4, 4), "float32")],
    devices=["TFRT_CPU_0"],
    backend="cpu",
)


def _fresh(monkeypatch, tmp_path=None, **env: str) -> None:
    """Reset the memoized cache under a controlled env (the autouse
    conftest fixture restores the memo on teardown)."""
    monkeypatch.delenv("EVAM_AOT", raising=False)
    monkeypatch.delenv("EVAM_AOT_DIR", raising=False)
    monkeypatch.delenv("EVAM_AOT_MAX_BYTES", raising=False)
    if tmp_path is not None:
        monkeypatch.setenv("EVAM_AOT", "1")
        monkeypatch.setenv("EVAM_AOT_DIR", str(tmp_path))
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    reset_settings()
    aot.reset_cache()


@pytest.fixture(autouse=True)
def _restore_settings():
    yield
    reset_settings()


def _toy_engine(name: str, **kw) -> BatchEngine:
    kwargs = dict(
        step_fn=lambda params, x: x * 2.0 + 1.0,
        params=np.ones((2,), np.float32),
        plan=None,
        max_batch=4,
        deadline_ms=4.0,
        input_names=("x",),
        stall_timeout_s=0,
        aot_key="aot-test|toy",
    )
    kwargs.update(kw)
    return BatchEngine(name, **kwargs)


def _warmed(name: str, **kw) -> BatchEngine:
    eng = _toy_engine(name, **kw)
    eng.set_example(x=np.zeros((2,), np.float32))
    eng.warmup()
    return eng


def _x(v: float) -> np.ndarray:
    return np.full((2,), v, np.float32)


def _run_values(eng: BatchEngine, values) -> list[np.ndarray]:
    futs = [eng.submit(x=_x(v)) for v in values]
    return [f.result(timeout=30) for f in futs]


# ------------------------------------------------------------- the key


class TestCacheKey:
    def test_stable_across_process_restarts(self):
        """The content address must not depend on process state
        (hash seeds, dict order, id()s): a restarted server has to
        find the executables the previous life stored."""
        code = (
            "from evam_tpu.aot.cache import cache_key\n"
            f"print(cache_key(**{_KEY_ARGS!r}))\n"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            text=True, timeout=120, env=env, check=True)
        assert out.stdout.strip() == cache_key(**_KEY_ARGS)

    @pytest.mark.parametrize("field,value", [
        ("program", "other-program"),
        ("bucket", 16),
        ("inputs", [("frames", (8, 64, 64, 3), "float32")]),
        ("params_sig", [((8, 4), "float32")]),
        ("devices", ["TFRT_CPU_1"]),
        ("backend", "tpu"),
    ])
    def test_every_field_addresses_a_different_entry(self, field, value):
        changed = dict(_KEY_ARGS, **{field: value})
        assert cache_key(**changed) != cache_key(**_KEY_ARGS)

    def test_engine_key_stable_across_engine_instances(self):
        a, b = _toy_engine("aot-k1"), _toy_engine("aot-k2")
        try:
            a.set_example(x=np.zeros((2,), np.float32))
            batch = a._warm_batch(a._example_item(), a.buckets[0])
            assert (a._aot_bucket_key(a.buckets[0], batch)
                    == b._aot_bucket_key(b.buckets[0], batch))
        finally:
            a.stop()
            b.stop()


# ----------------------------------------------------- the entry format


class TestEntryFormat:
    def test_pack_unpack_roundtrip(self):
        header = env_fingerprint()
        payload = b"x" * 257
        got_header, got_payload = _unpack_entry(
            _pack_entry(header, payload))
        assert got_header == header and got_payload == payload

    @pytest.mark.parametrize("mangle", [
        lambda blob: b"NOTMAGIC" + blob[8:],      # wrong magic
        lambda blob: blob[:20],                   # truncated header
        lambda blob: blob[:-3],                   # truncated payload
        lambda blob: blob[:-1] + b"\x00",         # payload bit rot
    ])
    def test_structural_damage_reads_as_crc(self, mangle):
        blob = _pack_entry({"jax": "x"}, b"payload-bytes")
        with pytest.raises(_EntryError) as exc:
            _unpack_entry(mangle(blob))
        assert exc.value.reason == "crc"


# ------------------------------------------------- the fallback ladder


class TestFallbackLadder:
    """Every rung degrades to a working (recompiled) engine with the
    right ``reason`` counter — the cache can cost disk, never serving."""

    def _populate(self, monkeypatch, tmp_path) -> BatchEngine:
        _fresh(monkeypatch, tmp_path)
        eng = _warmed("aot-seed")
        eng.stop()
        assert aot.active().summary()["entries"] == len(eng.buckets)
        return eng

    def _entries(self, tmp_path):
        return sorted(tmp_path.glob("*.aotx"))

    def test_absent_miss_populates_the_store(self, monkeypatch,
                                             tmp_path):
        seed = self._populate(monkeypatch, tmp_path)
        s = aot.active().summary()
        assert s["misses"]["absent"] == len(seed.buckets)
        assert s["hits"] == 0
        assert seed.stats.aot_hits == 0
        assert seed.stats.compiled_programs == len(seed.buckets)

    def test_crc_damage_falls_back_and_discards(self, monkeypatch,
                                                tmp_path):
        self._populate(monkeypatch, tmp_path)
        for p in self._entries(tmp_path):
            blob = p.read_bytes()
            p.write_bytes(blob[:-1] + bytes([blob[-1] ^ 0xFF]))
        aot.reset_cache()
        eng = _warmed("aot-crc")
        try:
            s = aot.active().summary()
            assert s["misses"]["crc"] == len(eng.buckets)
            assert eng.stats.aot_hits == 0
            # damaged entries were discarded and repopulated
            assert s["entries"] == len(eng.buckets)
            assert _run_values(eng, [1.0])[0] == pytest.approx(
                np.full((2,), 3.0))
        finally:
            eng.stop()

    def test_version_skew_is_a_distinguishable_miss(self, monkeypatch,
                                                    tmp_path):
        self._populate(monkeypatch, tmp_path)
        for p in self._entries(tmp_path):
            header, payload = _unpack_entry(p.read_bytes())
            header["jax"] = "0.0.0-from-another-life"
            p.write_bytes(_pack_entry(header, payload))
        aot.reset_cache()
        eng = _warmed("aot-ver")
        try:
            s = aot.active().summary()
            assert s["misses"]["version"] == len(eng.buckets)
            assert s["misses"]["crc"] == 0
            assert eng.stats.aot_hits == 0
        finally:
            eng.stop()

    def test_pickle_rot_is_a_deserialize_miss(self, monkeypatch,
                                              tmp_path):
        self._populate(monkeypatch, tmp_path)
        for p in self._entries(tmp_path):
            # valid frame, valid CRC — the payload itself is garbage
            p.write_bytes(_pack_entry(
                env_fingerprint(), pickle.dumps(("not", "an", "exe"))))
        aot.reset_cache()
        eng = _warmed("aot-deser")
        try:
            s = aot.active().summary()
            assert s["misses"]["deserialize"] == len(eng.buckets)
            assert eng.stats.aot_hits == 0
        finally:
            eng.stop()

    def test_unexecutable_entry_is_an_execute_miss(self, monkeypatch,
                                                   tmp_path):
        self._populate(monkeypatch, tmp_path)
        aot.reset_cache()

        def bad_load(self, key, devices, engine=""):
            def boom(*args, **kwargs):
                raise RuntimeError("bound to a device that is gone")
            return boom

        monkeypatch.setattr(AotCache, "load", bad_load)
        eng = _warmed("aot-exec")
        monkeypatch.undo()
        try:
            s = aot.active().summary()
            assert s["misses"]["execute"] == len(eng.buckets)
            assert eng.stats.aot_hits == 0
            # the engine recompiled and serves
            assert _run_values(eng, [2.0])[0] == pytest.approx(
                np.full((2,), 5.0))
        finally:
            eng.stop()


# ---------------------------------------------------------- LRU store


class TestEviction:
    def _fake_entry(self, root, name: str, size: int, mtime: float):
        p = root / f"{name}.aotx"
        p.write_bytes(b"z" * size)
        os.utime(p, (mtime, mtime))
        return p

    def test_oldest_evicted_first_newest_survives(self, tmp_path):
        cache = AotCache(tmp_path, max_bytes=250)
        old = self._fake_entry(tmp_path, "a" * 8, 100, 1_000.0)
        mid = self._fake_entry(tmp_path, "b" * 8, 100, 2_000.0)
        new = self._fake_entry(tmp_path, "c" * 8, 100, 3_000.0)
        cache._evict()
        assert not old.exists()
        assert mid.exists() and new.exists()
        assert cache.summary()["evictions"] == 1

    def test_single_over_cap_entry_never_thrashes(self, tmp_path):
        cache = AotCache(tmp_path, max_bytes=10)
        only = self._fake_entry(tmp_path, "d" * 8, 100, 1_000.0)
        cache._evict()
        assert only.exists()  # the newest entry always survives
        assert cache.summary()["evictions"] == 0

    def test_engine_store_respects_the_cap(self, monkeypatch,
                                           tmp_path):
        # each toy-engine entry is a few KB; a 1-byte cap forces every
        # store to evict down to the one newest entry
        _fresh(monkeypatch, tmp_path, EVAM_AOT_MAX_BYTES="1")
        eng = _warmed("aot-cap")
        eng.stop()
        s = aot.active().summary()
        assert s["entries"] == 1
        assert s["evictions"] == len(eng.buckets) - 1


# -------------------------------------------------- warm spin-up path


class TestWarmSpinUp:
    def test_second_engine_serves_from_the_cache(self, monkeypatch,
                                                 tmp_path):
        _fresh(monkeypatch, tmp_path)
        values = [float(i) for i in range(8)]
        cold = _warmed("aot-cold")
        try:
            cold_out = _run_values(cold, values)
            assert cold.stats.aot_hits == 0
            assert cold.stats.compile_seconds > 0
        finally:
            cold.stop()
        warm = _warmed("aot-warm")
        try:
            # every rung deserialized: the cold-vs-warm attribution
            # /engines shows — aot_hits == buckets, zero compile time
            assert warm.stats.aot_hits == len(warm.buckets)
            assert warm.stats.compile_seconds == 0.0
            assert warm.stats.aot_load_seconds > 0.0
            assert warm.stats.compiled_programs == len(warm.buckets)
            warm_out = _run_values(warm, values)
        finally:
            warm.stop()
        for a, b in zip(cold_out, warm_out):
            np.testing.assert_array_equal(a, b)
        s = aot.active().summary()
        assert s["hits"] == len(warm.buckets)

    def test_summary_shape_is_the_golden_contract(self, monkeypatch,
                                                  tmp_path):
        _fresh(monkeypatch, tmp_path)
        live = aot.summary()
        off = aot.cache.disabled_summary()
        assert set(live) == set(off)
        assert set(live["misses"]) == set(MISS_REASONS)
        assert live["enabled"] is True and off["enabled"] is False


# ----------------------------------------------------------- off path


class TestOffPath:
    def test_off_resolves_to_none_and_memoizes(self, monkeypatch):
        _fresh(monkeypatch)
        assert aot.active() is None
        assert aot.summary()["enabled"] is False
        # memoized: later consults are one global load + None check
        assert aot.cache._resolved == (None,)

    def test_off_vs_on_byte_identity(self, monkeypatch, tmp_path):
        """EVAM_AOT=off (default) must be byte-identical to both the
        cold (populate) and warm (deserialize) on paths — the cache
        may change where an executable comes from, never a number."""
        values = [float(i) for i in range(16)]

        def run(name: str) -> list[np.ndarray]:
            eng = _warmed(name)
            try:
                return _run_values(eng, values)
            finally:
                eng.stop()

        _fresh(monkeypatch)  # off (default)
        off = run("aot-ab-off")
        _fresh(monkeypatch, tmp_path)  # on, cold
        on_cold = run("aot-ab-cold")
        aot.reset_cache()
        on_warm = run("aot-ab-warm")  # on, cache hits
        for a, b, c in zip(off, on_cold, on_warm):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)

    def test_engine_without_key_never_consults_the_cache(
            self, monkeypatch, tmp_path):
        _fresh(monkeypatch, tmp_path)
        eng = _warmed("aot-nokey", aot_key=None)
        try:
            assert eng.stats.aot_hits == 0
            assert not eng._aot_exec
            assert aot.active().summary()["entries"] == 0
        finally:
            eng.stop()
