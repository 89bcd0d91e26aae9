"""Slot-based host staging ring for zero-copy batch assembly.

Stacking a batch per dispatch (``np.stack(rows)`` plus a zero-pad
``np.concatenate``) costs two fresh multi-megabyte arrays per batch,
page-faulted on every first touch (a 256×1080p I420 batch is
~760 MB/s of pure assembly traffic at the north-star fan-in). This
module is the tf.data-style staging discipline instead (PAPERS.md): a
small ring of pre-allocated host blocks, one block per input name,
each sized to the engine's LARGEST bucket and a few deep so staging
batch N+1 overlaps the device round-trip of batch N.

Zero-copy here means *zero per-batch allocation and zero re-stacking*:

* ``stage()`` runs on the engine's dispatcher: it takes a free block
  (waiting for one while every block is in flight — the host-side
  backpressure), copies each picked item's arrays straight into its
  row — the one unavoidable host copy — and seals: pick the bucket,
  zero only the dirty tail rows (the pad is "already zeroed" by
  invariant, not a fresh concat), and hand a contiguous
  ``block[:bucket]`` view to ``device_put``;
* ``release()`` returns the block to the free list after the batch's
  readback, so a block is never overwritten while its transfer may
  still be in flight.

Concurrency contract: a block is owned by exactly one party at a time
— the free list, the dispatcher while it stages, then the sealed
batch until ``release()``. Only the free list and the closed flag are
shared, under the ring's condition variable. Items resolve in row
order, so per-batch future fan-out stays positionally correct.

**Ragged packing** (``engine/ragged.py``, ``EVAM_RAGGED=packed``): a
ring built with a ``RaggedSpec`` additionally packs ONE declared
input's variable-length unit rows (a frame's real region boxes, shape
``(k, unit_shape)``) end to end into a fixed unit block, maintaining a
segment-id vector (``seg[j]`` = owning batch row, −1 on the pad tail)
and per-item ``row_len``/``row_offset`` vectors the completer uses to
scatter results back. An item takes 1 batch row + k unit rows; a
batch seals when either runs out, so a packed batch never overflows
its fixed device shape. Everything else — block reuse, dirty-tail
zeroing — is the same discipline extended to the unit block.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any

import numpy as np

from evam_tpu.engine.ragged import RaggedSpec
from evam_tpu.obs.trace import StageClock

#: stage names of the per-batch host clock, in pipeline order.
#: submit_wait is the head item's wait in its class queue (the
#: deadline-batching formation wait included); slot_write is the
#: batch's row copies on the dispatcher, the wait for a free block
#: before them left out. The device boundary is split
#: transfer-honestly: h2d_issue is the time for device_put to ENQUEUE
#: the host→device copy, h2d_wait the residual wait for that copy at
#: launch (≈0 when the upload overlapped the previous launch), and
#: readback the device→host residual the completer still has to
#: block on after the async D2H copy was put in flight.
#: Each batch's ``StageClock`` (obs/trace.py) keeps these durations
#: and, beside them, every stage's start: its ``spans`` are the
#: batch's timeline.
STAGES = (
    "submit_wait", "slot_write", "seal",
    "h2d_issue", "h2d_wait", "launch", "readback", "resolve",
)


class _Slot:
    """One staging block set: per-input pre-allocated (capacity, …)
    arrays plus what a seal needs to know of the previous uses."""

    __slots__ = ("arrays", "high", "unit_high", "row_len", "seg")

    def __init__(self, arrays: dict[str, np.ndarray],
                 capacity: int = 0, unit_capacity: int = 0):
        self.arrays = arrays
        #: exclusive upper bound of possibly-nonzero rows left behind
        #: by previous uses — the only rows a seal must memset
        self.high = 0
        #: the same bound over the packed unit block, and the ragged
        #: descriptor's backing vectors (unused on dense rings)
        self.unit_high = 0
        self.row_len = (np.zeros(capacity, np.int32)
                        if unit_capacity else None)
        self.seg = (np.full(unit_capacity, -1, np.int32)
                    if unit_capacity else None)


class SealedBatch:
    """A sealed slot ready for dispatch: contiguous ``[:bucket]``
    views over the staging blocks, the items in row order, and the
    batch's stage clock.

    On a ragged ring the batch additionally carries the packed-unit
    descriptor: ``row_len[i]``/``row_offset[i]`` locate item i's unit
    rows in the packed block (COPIES — the slot recycles before the
    completer resolves), ``units`` is the real packed-unit count and
    ``unit_rows`` the computed unit rows of the device shape (the
    honest-occupancy denominator)."""

    __slots__ = ("slot", "arrays", "items", "n", "bucket", "clock",
                 "row_len", "row_offset", "units", "unit_rows")

    def __init__(self, slot: _Slot, arrays: dict[str, np.ndarray],
                 items: list, n: int, bucket: int,
                 clock: StageClock,
                 row_len: np.ndarray | None = None,
                 row_offset: np.ndarray | None = None,
                 units: int = 0, unit_rows: int = 0):
        self.slot = slot
        self.arrays = arrays
        self.items = items
        self.n = n
        self.bucket = bucket
        self.clock = clock
        self.row_len = row_len
        self.row_offset = row_offset
        self.units = units
        self.unit_rows = unit_rows


class SlotRing:
    """Ring of ``depth`` pre-allocated staging slots for one engine.

    Blocks are allocated lazily on the first ``stage()`` (item shapes
    are not known at engine construction) and NEVER reallocated —
    ``blocks_allocated`` is the test hook pinning that invariant.

    ``ragged`` (a RaggedSpec) switches the declared input to packed
    unit-row staging; its bucket callbacks then take ``(n, units)``
    instead of ``(n)``.
    """

    def __init__(self, capacity: int, depth: int = 4,
                 ragged: RaggedSpec | None = None):
        if capacity < 1 or depth < 2:
            raise ValueError("capacity >= 1 and depth >= 2 required")
        self.capacity = capacity
        self.depth = depth
        self.ragged = ragged
        #: fixed unit rows of the packed block (0 on dense rings)
        self.unit_capacity = ragged.unit_rows(capacity) if ragged else 0
        self._cv = threading.Condition()
        self._free: deque[_Slot] = deque()
        self._closed = False
        self._shapes: dict[str, tuple[tuple[int, ...], np.dtype]] | None = None
        #: total staging-block allocations ever performed (one per
        #: input name per slot; constant after the first stage)
        self.blocks_allocated = 0

    # ------------------------------------------------------ dispatcher side

    def stage(self, staged: list[tuple[dict, Any]], bucket_fn,
              clock: StageClock, spans=None,
              ) -> tuple[SealedBatch | None, list]:
        """Stage a dispatcher-picked batch into a free slot: the row
        copies happen HERE, on the dispatcher thread (items arrive
        from per-class queues and stay mobile until they are picked),
        with zero per-batch allocation.

        ``staged`` is ``[(inputs, item), ...]`` in dispatch order. A
        row whose arrays mismatch the ring shapes fails only ITS
        item's future; survivors compact into contiguous rows. Items
        past the slot's capacity — batch rows, or packed unit rows on
        a ragged ring — are NOT silently clamped: they come back as
        the second element for the caller to stage as another batch
        (the oversize-split contract). Blocks while every slot is in
        flight (host-side backpressure); raises RuntimeError once the
        ring is closed; the sealed batch is None when no row
        survived. ``spans`` is the dispatcher's ``ThreadSpans``: the
        wait for a free slot, the row copies and the seal are marked
        on it."""
        spec = self.ragged
        with self._cv:
            if self._shapes is None:
                self._allocate({k: np.asarray(v)
                                for k, v in staged[0][0].items()})
            if spans is not None and not self._free and not self._closed:
                spans.to("wait_staging")
            while not self._free and not self._closed:
                self._cv.wait(0.1)
            if self._closed:
                raise RuntimeError("staging ring is closed")
            slot = self._free.popleft()
        t0 = time.perf_counter()
        if spans is not None:
            spans.to("slot_write", t0)
        ok_items: list = []
        remaining: list = []
        row = 0
        off = 0
        for idx, (inputs, item) in enumerate(staged):
            if row >= self.capacity:
                remaining = list(staged[idx:])
                break
            try:
                arrays = {k: np.asarray(v) for k, v in inputs.items()}
                self._check_shapes(arrays)
            except Exception as exc:  # noqa: BLE001 — fail only this item
                try:
                    item.future.set_exception(exc)
                except Exception:  # noqa: BLE001 — already resolved
                    pass
                continue
            if spec is not None:
                k = int(arrays[spec.input].shape[0])
                if off + k > self.unit_capacity:
                    remaining = list(staged[idx:])
                    break
                for name, a in arrays.items():
                    if name == spec.input:
                        if k:
                            slot.arrays[name][off:off + k] = a
                            slot.seg[off:off + k] = row
                    else:
                        slot.arrays[name][row] = a
                slot.row_len[row] = k
                off += k
            else:
                for name, a in arrays.items():
                    slot.arrays[name][row] = a
            ok_items.append(item)
            row += 1
        t1 = time.perf_counter()
        clock.mark("slot_write", t0, t1 - t0)
        if not ok_items:
            # nothing was written: the block goes back as it came
            with self._cv:
                self._free.append(slot)
                self._cv.notify_all()
            return None, remaining
        if spans is not None:
            spans.to("seal", t1)
        sealed = self._seal(slot, ok_items, row, off, bucket_fn, clock)
        clock.mark("seal", t1, time.perf_counter() - t1)
        return sealed, remaining

    def _seal(self, slot: _Slot, items: list, n: int, units: int,
              bucket_fn, clock: StageClock) -> SealedBatch:
        """Pick the bucket, zero the dirty pad tails (dense rows AND,
        on a ragged ring, the packed unit block + seg vector), and
        build the contiguous views + ragged descriptor."""
        spec = self.ragged
        if spec is not None:
            bucket = bucket_fn(n, units)
            u = min(spec.unit_rows(bucket), self.unit_capacity)
            dirty = min(slot.high, bucket)
            views: dict[str, np.ndarray] = {}
            for name, arr in slot.arrays.items():
                if name == spec.input:
                    udirty = min(slot.unit_high, u)
                    if udirty > units:
                        arr[units:udirty] = 0
                    views[name] = arr[:u]
                else:
                    if dirty > n:
                        arr[n:dirty] = 0
                    views[name] = arr[:bucket]
            # the seg pad tail is ALWAYS −1 (the masked-compute
            # sentinel), whatever an earlier batch left behind
            slot.seg[units:u] = -1
            views["seg"] = slot.seg[:u]
            row_len = slot.row_len[:n].copy()
            row_offset = np.zeros(n, np.int32)
            np.cumsum(row_len[:-1], out=row_offset[1:])
            return SealedBatch(slot, views, items, n, bucket, clock,
                               row_len=row_len, row_offset=row_offset,
                               units=units, unit_rows=u)
        bucket = bucket_fn(n)
        dirty = min(slot.high, bucket)
        for arr in slot.arrays.values():
            if dirty > n:
                arr[n:dirty] = 0
        views = {k: a[:bucket] for k, a in slot.arrays.items()}
        return SealedBatch(slot, views, items, n, bucket, clock)

    # ------------------------------------------------------- completion

    def release(self, sealed: SealedBatch) -> None:
        """Return a dispatched slot to the free list (call after the
        batch's readback — the staging block may back an in-flight
        transfer until then)."""
        slot = sealed.slot
        with self._cv:
            # rows [n, bucket) were zeroed at seal; rows beyond the
            # bucket may still hold older data
            if slot.high <= sealed.bucket:
                slot.high = sealed.n
            if self.ragged is not None and slot.unit_high <= sealed.unit_rows:
                slot.unit_high = sealed.units
            self._free.append(slot)
            self._cv.notify_all()

    # -------------------------------------------------------- lifecycle

    def close(self) -> None:
        """Wake the dispatcher if it waits for a block; every later
        ``stage()`` raises."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    # -------------------------------------------------------- internals

    def _allocate(self, example: dict[str, np.ndarray]) -> None:
        spec = self.ragged
        self._shapes = {}
        for k, a in example.items():
            if spec is not None and k == spec.input:
                # ragged input: the leading dim is per-item variable;
                # pin only the unit shape + dtype
                self._shapes[k] = (tuple(spec.unit_shape),
                                   np.dtype(spec.dtype))
            else:
                self._shapes[k] = (tuple(a.shape), a.dtype)
        for _ in range(self.depth):
            arrays = {}
            for k, (shape, dtype) in self._shapes.items():
                rows = (self.unit_capacity
                        if spec is not None and k == spec.input
                        else self.capacity)
                arrays[k] = np.zeros((rows,) + shape, dtype)
            self.blocks_allocated += len(arrays)
            self._free.append(
                _Slot(arrays, capacity=self.capacity,
                      unit_capacity=self.unit_capacity))

    def _check_shapes(self, arrays: dict[str, np.ndarray]) -> None:
        spec = self.ragged
        for k, a in arrays.items():
            want = self._shapes.get(k)
            if spec is not None and k == spec.input:
                if (want is None
                        or (tuple(a.shape[1:]), a.dtype) != want
                        or a.shape[0] > spec.max_units):
                    raise ValueError(
                        f"ragged input {k}: want (<= {spec.max_units}, "
                        f"{want[0] if want else '?'}) "
                        f"{want[1] if want else '?'}, got shape "
                        f"{tuple(a.shape)} dtype {a.dtype}")
                continue
            if want is None or (tuple(a.shape), a.dtype) != want:
                raise ValueError(
                    f"staging ring configured for {self._shapes}, got "
                    f"{k}: shape {tuple(a.shape)} dtype {a.dtype} — "
                    "engines batch fixed ingest shapes; use a distinct "
                    "model-instance-id for a different resolution"
                )
