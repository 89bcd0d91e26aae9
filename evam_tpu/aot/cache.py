"""Content-addressed on-disk cache of serialized compiled executables.

Why: "millions of users" (ROADMAP) means the fleet grows and shrinks
with load, and today every shard cold-start — supervisor rebuild,
fleet scale-up, process restart — pays a full jit trace + XLA compile
per bucket rung. Compiled executables serialize and reload
(``jax.experimental.serialize_executable``, the Julia→TPU AOT
compilation observation from PAPERS.md), so the second cold start can
be a load measured in milliseconds instead of a compile measured in
seconds.

Contract:

- **Keyed on everything that changes the program.** The cache key
  (:func:`cache_key`) hashes the hub's program fingerprint (engine
  key + wire/synth/ragged/sched config), the bucket rung, every step
  input's shape+dtype, the params aval signature, the device set the
  executable is bound to and the backend. The
  jax / jaxlib / PJRT platform versions deliberately live in the
  entry HEADER, not the key — a version upgrade then reads as a
  distinguishable ``version`` miss instead of a silent absent one.
- **Never a crash, always a counter.** Every rung of the fallback
  ladder — ``absent``, ``version``, ``crc``, ``deserialize``,
  ``execute`` — lands on
  ``evam_aot_cache_misses_total{engine,reason}`` and falls back to
  the plain jit path loudly. A cache can only ever cost disk.
- **CRC-guarded, size-capped LRU.** Entries are MAGIC + header JSON +
  CRC32 + pickled ``(payload, in_tree, out_tree)``; writes are atomic
  (tmp + rename); hits touch mtime and eviction removes
  oldest-by-mtime entries past ``EVAM_AOT_MAX_BYTES``.

No environment reads here (evamlint knobs pass): configuration
arrives through ``config/settings.py`` (EVAM_AOT / EVAM_AOT_DIR /
EVAM_AOT_MAX_BYTES) only.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import struct
import tempfile
import threading
import zlib
from pathlib import Path

from evam_tpu.obs import get_logger, metrics

log = get_logger("aot.cache")

#: entry-format magic; bump when the on-disk layout changes (an old
#: layout then reads as a ``crc``-class miss, never a crash)
MAGIC = b"EVAOT001"

#: the fallback ladder, in the order the load path walks it — fixed
#: vocabulary so the /healthz ``aot`` block keeps a stable shape
MISS_REASONS = ("absent", "version", "crc", "deserialize", "execute")

_EXT = ".aotx"

from jax.experimental.serialize_executable import (
    deserialize_and_load,
    serialize,
)


class _EntryError(ValueError):
    """A structurally-bad cache entry, tagged with its miss reason."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(detail or reason)
        self.reason = reason


def cache_key(program: str, bucket: int, inputs, params_sig,
              devices, backend: str) -> str:
    """Content address for one (program, rung, placement) executable.

    Everything that changes the compiled artifact is in here;
    environment versions are in the entry header instead (see module
    docstring). JSON with sorted keys → sha256, so the key is stable
    across processes and hosts."""
    doc = {
        "program": str(program),
        "bucket": int(bucket),
        "inputs": [[str(n), [int(d) for d in shape], str(dt)]
                   for n, shape, dt in inputs],
        "params": [[[int(d) for d in shape], str(dt)]
                   for shape, dt in params_sig],
        "devices": [str(d) for d in devices],
        "backend": str(backend),
    }
    blob = json.dumps(doc, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def env_fingerprint() -> dict:
    """The versions an executable is only valid under — compared
    against the entry header at load, never hashed into the key."""
    import jax

    fp = {"jax": getattr(jax, "__version__", ""), "jaxlib": "",
          "backend": "", "platform_version": ""}
    try:
        import jaxlib.version

        fp["jaxlib"] = jaxlib.version.__version__
    except Exception:  # noqa: BLE001 — vendored/renamed jaxlib
        pass
    try:
        fp["backend"] = jax.default_backend()
        fp["platform_version"] = str(
            jax.devices()[0].client.platform_version)
    except Exception:  # noqa: BLE001 — backend not initialized yet
        pass
    return fp


def _pack_entry(header: dict, payload: bytes) -> bytes:
    hdr = json.dumps(header, sort_keys=True).encode()
    return b"".join([
        MAGIC,
        struct.pack("<I", len(hdr)),
        hdr,
        struct.pack("<I", zlib.crc32(payload) & 0xFFFFFFFF),
        struct.pack("<Q", len(payload)),
        payload,
    ])


def _unpack_entry(blob: bytes) -> tuple[dict, bytes]:
    """Inverse of :func:`_pack_entry`; raises :class:`_EntryError`
    tagged ``crc`` for any structural damage (truncation, bad magic,
    bad checksum, unparseable header)."""
    if len(blob) < len(MAGIC) + 4 or blob[:len(MAGIC)] != MAGIC:
        raise _EntryError("crc", "bad magic")
    off = len(MAGIC)
    (hdr_len,) = struct.unpack_from("<I", blob, off)
    off += 4
    if len(blob) < off + hdr_len + 12:
        raise _EntryError("crc", "truncated header")
    try:
        header = json.loads(blob[off:off + hdr_len].decode())
    except Exception as exc:  # noqa: BLE001
        raise _EntryError("crc", f"header unparseable: {exc}") from exc
    off += hdr_len
    (crc,) = struct.unpack_from("<I", blob, off)
    off += 4
    (n,) = struct.unpack_from("<Q", blob, off)
    off += 8
    payload = blob[off:off + n]
    if len(payload) != n:
        raise _EntryError("crc", "truncated payload")
    if zlib.crc32(payload) & 0xFFFFFFFF != crc:
        raise _EntryError("crc", "checksum mismatch")
    return header, payload


class AotCache:
    """One directory of ``.aotx`` entries + the hit/miss bookkeeping.

    The metrics registry can be reset by tests mid-flight, so the
    cache keeps its own counters for the fixed-shape /healthz
    ``aot`` summary and mirrors every event onto the evam_aot_cache_*
    series."""

    #: counters are bumped from every warming engine thread
    SHARED_UNDER = {
        "_hits": "_lock",
        "_misses": "_lock",
        "_evictions": "_lock",
    }

    def __init__(self, root: str | os.PathLike, max_bytes: int):
        self.root = Path(root)
        self.max_bytes = max(0, int(max_bytes))
        self.root.mkdir(parents=True, exist_ok=True)
        self._fingerprint = env_fingerprint()
        self._lock = threading.Lock()
        self._hits = 0
        self._misses = {r: 0 for r in MISS_REASONS}
        self._evictions = 0

    # ------------------------------------------------------------- API

    def _path(self, key: str) -> Path:
        return self.root / f"{key}{_EXT}"

    def load(self, key: str, devices, engine: str = ""):
        """The executable for ``key`` loaded onto ``devices`` (the
        engine's own device list, in mesh order — without it jax binds
        the executable to every local device), or None after counting
        the miss reason (``absent``/``version``/``crc``/
        ``deserialize``). The caller validates with one execute and
        then confirms via :meth:`hit` (or :meth:`execute_miss`) — a
        deserialized executable is device-bound and the only honest
        validation is running it."""
        path = self._path(key)
        try:
            blob = path.read_bytes()
        except (FileNotFoundError, OSError):
            self._miss("absent", engine)
            return None
        try:
            header, payload = _unpack_entry(blob)
        except _EntryError as exc:
            log.warning("aot entry %s unreadable (%s) — falling back "
                        "to jit", path.name, exc)
            self._miss(exc.reason, engine)
            self._discard(path)
            return None
        if {k: header.get(k) for k in self._fingerprint} \
                != self._fingerprint:
            log.warning(
                "aot entry %s built under %s, running %s — version "
                "miss, falling back to jit", path.name, header,
                self._fingerprint)
            self._miss("version", engine)
            return None
        try:
            unloaded, in_tree, out_tree = pickle.loads(payload)
            loaded = deserialize_and_load(
                unloaded, in_tree, out_tree,
                execution_devices=list(devices))
        except Exception as exc:  # noqa: BLE001 — any pjrt/pickle rot
            log.warning("aot entry %s failed to deserialize (%s) — "
                        "falling back to jit", path.name, exc)
            self._miss("deserialize", engine)
            self._discard(path)
            return None
        try:
            os.utime(path)  # LRU touch
        except OSError:
            pass
        return loaded

    def hit(self, engine: str = "") -> None:
        """Confirm one load as served (post validation-execute)."""
        with self._lock:
            self._hits += 1
        metrics.inc("evam_aot_cache_hits", labels={"engine": engine})

    def execute_miss(self, key: str, engine: str = "") -> None:
        """A deserialized entry that would not execute (wrong device,
        stale placement) — counted and removed so it can't churn."""
        self._miss("execute", engine)
        self._discard(self._path(key))

    def store(self, key: str, compiled, engine: str = "") -> bool:
        """Serialize one compiled executable under ``key`` (atomic
        tmp + rename), then evict past the size cap. Failures are a
        warning, never an error — the executable still serves."""
        try:
            unloaded, in_tree, out_tree = serialize(compiled)
            payload = pickle.dumps(
                (bytes(unloaded), in_tree, out_tree))
        except Exception as exc:  # noqa: BLE001 — backend won't serialize
            log.warning("aot serialize failed for %s (%s) — entry "
                        "skipped", engine or key[:12], exc)
            return False
        blob = _pack_entry(self._fingerprint, payload)
        path = self._path(key)
        try:
            fd, tmp = tempfile.mkstemp(dir=str(self.root),
                                       suffix=".tmp")
            with os.fdopen(fd, "wb") as fh:
                fh.write(blob)
            os.replace(tmp, path)
        except OSError as exc:
            log.warning("aot store failed for %s (%s)", path.name, exc)
            return False
        self._evict()
        return True

    # -------------------------------------------------------- internals

    def _miss(self, reason: str, engine: str) -> None:
        with self._lock:
            self._misses[reason] = self._misses.get(reason, 0) + 1
        metrics.inc("evam_aot_cache_misses",
                    labels={"engine": engine, "reason": reason})

    @staticmethod
    def _discard(path: Path) -> None:
        try:
            path.unlink()
        except OSError:
            pass

    def _entries(self) -> list[tuple[Path, float, int]]:
        out = []
        try:
            for p in self.root.iterdir():
                if p.suffix != _EXT:
                    continue
                try:
                    st = p.stat()
                except OSError:
                    continue
                out.append((p, st.st_mtime, st.st_size))
        except OSError:
            pass
        return out

    def _evict(self) -> None:
        """Oldest-mtime-first eviction past ``max_bytes``. The newest
        entry always survives — a single over-cap executable must not
        thrash store/evict forever."""
        entries = sorted(self._entries(), key=lambda e: e[1])
        total = sum(sz for _, _, sz in entries)
        if self.max_bytes:
            while total > self.max_bytes and len(entries) > 1:
                path, _, sz = entries.pop(0)
                self._discard(path)
                total -= sz
                with self._lock:
                    self._evictions += 1
                log.info("aot cache evicted %s (%d B over cap)",
                         path.name, sz)
        metrics.set("evam_aot_cache_bytes", float(total))

    def summary(self) -> dict:
        """Fixed-shape /healthz block (golden contract — keys stable
        whether the cache is on or off, see :func:`disabled_summary`)."""
        entries = self._entries()
        with self._lock:
            hits = self._hits
            misses = {r: self._misses.get(r, 0) for r in MISS_REASONS}
            evictions = self._evictions
        return {
            "enabled": True,
            "entries": len(entries),
            "bytes": sum(sz for _, _, sz in entries),
            "max_bytes": self.max_bytes,
            "hits": hits,
            "misses": misses,
            "evictions": evictions,
        }


def disabled_summary() -> dict:
    """The same /healthz shape with EVAM_AOT=off."""
    return {
        "enabled": False,
        "entries": 0,
        "bytes": 0,
        "max_bytes": 0,
        "hits": 0,
        "misses": {r: 0 for r in MISS_REASONS},
        "evictions": 0,
    }


#: memoized EVAM_AOT decision — (cache,) once resolved, None before.
#: Same shape as control/state.py and obs/trace.py: the tuple wrapper
#: distinguishes "resolved to disabled" from "not yet resolved".
_resolved: tuple[AotCache | None] | None = None


def active() -> AotCache | None:
    """The process AotCache, or None with EVAM_AOT=off (default).
    Memoized: the off path costs one global load per consult."""
    if _resolved is not None:
        return _resolved[0]
    return _resolve()


def _resolve() -> AotCache | None:
    global _resolved
    from evam_tpu.config.settings import get_settings

    cfg = get_settings().aot
    cache: AotCache | None = None
    if cfg.enabled:
        root = cfg.dir or os.path.join(tempfile.gettempdir(), "evam_aot")
        try:
            cache = AotCache(root, cfg.max_bytes)
        except OSError as exc:
            log.warning("EVAM_AOT dir %s unusable (%s) — AOT "
                        "cache disabled", root, exc)
    _resolved = (cache,)
    return cache


def summary() -> dict:
    """The /healthz ``aot`` block: live cache summary or the disabled
    same-shape zeros."""
    cache = active()
    return disabled_summary() if cache is None else cache.summary()


def reset_cache() -> None:
    """Drop the memo (tests / bench A-B flips)."""
    global _resolved
    _resolved = None
