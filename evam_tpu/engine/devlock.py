"""Global device-interaction serialization.

With ``EVAM_SERIALIZE_COMPILE=1`` every device interaction in the
engine (program launch, bucket-warmup compile, result readback) runs
under ONE process-wide lock, so a compile can never race an execute
RPC no matter what threads exist. The cost is double-buffering (batch
N+1 can no longer be enqueued while batch N computes), and the
pipelined transfer degrades to inline — a diagnostic mode, not the
serving default. Unset, ``device_call`` only counts.

The module also keeps an always-on concurrency gauge
(``max_concurrent()``): tests use it to demonstrate the client-side
overlap of compile and dispatch that the serve path creates and that
the lock removes (the reference has no analogue — its inference
runtime is an external C++ process; SURVEY.md §2b).
"""

from __future__ import annotations

import contextlib
import os
import threading

_lock = threading.RLock()
_stats_lock = threading.Lock()
_active = 0
_max_concurrent = 0
_depth = threading.local()  # nested spans on one thread count once


def enabled() -> bool:
    """``EVAM_SERIALIZE_COMPILE=1``: serialize every engine device
    call process-wide. Read per-call so a bench/test can flip it."""
    return os.environ.get("EVAM_SERIALIZE_COMPILE", "0").lower() in (
        "1", "true", "yes")


def reset_stats() -> None:
    global _max_concurrent
    with _stats_lock:
        _max_concurrent = 0


def max_concurrent() -> int:
    """High-water mark of concurrent device calls since the last
    ``reset_stats()`` — 1 proves serialization held."""
    with _stats_lock:
        return _max_concurrent


@contextlib.contextmanager
def _track():
    global _active, _max_concurrent
    depth = getattr(_depth, "n", 0)
    _depth.n = depth + 1
    if depth == 0:
        with _stats_lock:
            _active += 1
            _max_concurrent = max(_max_concurrent, _active)
    try:
        yield
    finally:
        _depth.n = depth
        if depth == 0:
            with _stats_lock:
                _active -= 1


@contextlib.contextmanager
def device_call(tag: str = ""):
    """Wrap one device interaction (launch / compile / readback).

    No-op (tracking only) unless serialization is enabled.
    """
    if enabled():
        with _lock, _track():
            yield
    else:
        with _track():
            yield
