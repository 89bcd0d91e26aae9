"""Persistent AOT executable cache (EVAM_AOT).

A content-addressed on-disk store of serialized compiled XLA
executables, shared by supervisor rebuilds, fleet shard spin-up and
every warmup path: a cache hit turns a bucket's cold start from a
jit trace + XLA compile into a millisecond deserialize. Off (the
default) the layer is one memoized ``active()`` None-check —
byte-identical, the same A/B discipline as EVAM_GATE / EVAM_TRACE /
EVAM_CKPT.
"""

from evam_tpu.aot.cache import (  # noqa: F401
    AotCache,
    MISS_REASONS,
    active,
    cache_key,
    disabled_summary,
    reset_cache,
    summary,
)
