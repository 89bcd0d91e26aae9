"""Slot-based host staging ring for zero-copy batch assembly.

The legacy dispatch path allocated per batch: ``np.stack(rows)`` plus
a zero-pad ``np.concatenate`` — two fresh multi-megabyte arrays per
dispatched batch, built on the single dispatcher thread, page-faulted
on every first touch (a 256×1080p I420 batch is ~760 MB/s of pure
assembly traffic at the north-star fan-in). This module replaces that
with the tf.data-style staging discipline (PAPERS.md): a small ring of
pre-allocated host blocks, one block per input name, each sized to the
engine's LARGEST bucket and 2–3 deep so assembly of batch N+1 overlaps
the device round-trip of batch N.

Zero-copy here means *zero per-batch allocation and zero re-stacking*:

* ``write()`` runs on the SUBMITTING stream thread and copies each
  item's arrays straight into its reserved row of the open slot — the
  one unavoidable host copy, moved off the dispatcher's critical path
  and parallelized across stream threads (numpy row copies release
  the GIL);
* the dispatcher ``seal()``s a slot — pick the bucket, zero only the
  dirty tail rows (the pad is "already zeroed" by invariant, not a
  fresh concat) — and hands a contiguous ``block[:bucket]`` view to
  ``device_put``;
* ``release()`` returns the slot to the free list after the batch's
  readback, so a block is never overwritten while its transfer may
  still be in flight.

Concurrency contract: row indices are reserved under the ring lock,
row copies happen OUTSIDE the lock (each row has exactly one writer),
and a seal waits for all in-flight writers of that slot. Items resolve
in row order, so per-batch future fan-out stays positionally correct.

**Ragged packing** (``engine/ragged.py``, ``EVAM_RAGGED=packed``): a
ring built with a ``RaggedSpec`` additionally packs ONE declared
input's variable-length unit rows (a frame's real region boxes, shape
``(k, unit_shape)``) end to end into a fixed unit block, maintaining a
segment-id vector (``seg[j]`` = owning batch row, −1 on the pad tail)
and per-item ``row_len``/``row_offset`` vectors the completer uses to
scatter results back. An item reserves 1 batch row + k unit rows; a
slot seals when either runs out, so a packed batch never overflows its
fixed device shape. Everything else — slot reuse, dirty-tail zeroing,
writer accounting — is the same discipline extended to the unit block.

Measured on this box (``tools/bench_hostpath.py``, serving-default
bucket 128 at the 432×768 I420 wire shape): 3.1× cheaper than
stack+concat at full occupancy, 7.5× with a padded tail (legacy pays
stack + pad + a second full copy through concatenate). The win comes
from (a) no per-batch allocation — blocks > glibc's 32 MB mmap cap
are freshly mapped and page-faulted on EVERY legacy batch, and
(b) pad rows being pre-zeroed instead of re-concatenated. Below
~32 MB the allocator recycles legacy's buffer and the two paths are
comparable; the serving shapes (batch 128–256) sit well above it.
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
#: submit_wait covers slot backpressure AND the deadline-batching
#: formation wait; slot_write is the summed per-item row copies
#: (spent on stream threads, overlapped across submitters). The
#: device boundary is split transfer-honestly (EVAM_TRANSFER):
#: h2d_issue is the time for device_put to ENQUEUE the host→device
#: copy, h2d_wait the residual wait for that copy at launch (≈0 when
#: the pipelined uploader overlapped it with the previous launch; 0
#: by definition on the inline path, where the launch itself absorbs
#: it), and readback the device→host residual the completer still
#: has to block on after the async D2H copy was put in flight.
#: Each batch's ``StageClock`` (obs/trace.py) keeps these durations
#: and, beside them, every stage's start: its ``spans`` are the
#: batch's timeline.
STAGES = (
    "submit_wait", "slot_write", "seal",
    "h2d_issue", "h2d_wait", "launch", "readback", "resolve",
)


class _Slot:
    """One staging block set: per-input pre-allocated (capacity, …)
    arrays plus fill bookkeeping. All mutable fields are guarded by
    the owning ring's condition variable except the row contents
    themselves (single writer per reserved row, written unlocked)."""

    __slots__ = ("arrays", "items", "count", "high", "writers",
                 "t_first", "closed", "wait_sum", "write_sum", "gen",
                 "unit_count", "unit_high", "row_len", "seg")

    def __init__(self, arrays: dict[str, np.ndarray],
                 capacity: int = 0, unit_capacity: int = 0):
        self.arrays = arrays
        self.items: list[Any] = []
        self.count = 0
        #: exclusive upper bound of possibly-nonzero rows left behind
        #: by previous uses — the only rows a seal must memset
        self.high = 0
        self.writers = 0
        self.t_first = 0.0
        self.closed = False
        self.wait_sum = 0.0   # summed per-item slot-acquire waits
        self.write_sum = 0.0  # summed per-item row-copy times
        #: bumped on every recycle (release/drain) so a dispatcher
        #: that slept through a watchdog drain can detect its claim
        #: went stale instead of double-dispatching the slot
        self.gen = 0
        #: ragged packing bookkeeping (unused on dense rings)
        self.unit_count = 0
        self.unit_high = 0
        self.row_len = (np.zeros(capacity, np.int32)
                        if unit_capacity else None)
        self.seg = (np.full(unit_capacity, -1, np.int32)
                    if unit_capacity else None)


class SealedBatch:
    """A sealed slot ready for dispatch: contiguous ``[:bucket]``
    views over the staging blocks, the items in row order, and the
    host-clock readings accumulated so far.

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

    Blocks are allocated lazily on the first ``write()`` (item shapes
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
        self._full: deque[_Slot] = deque()
        self._open: _Slot | None = None
        self._closed = False
        self._shapes: dict[str, tuple[tuple[int, ...], np.dtype]] | None = None
        #: total staging-block allocations ever performed (one per
        #: input name per slot; constant after first write)
        self.blocks_allocated = 0

    # ------------------------------------------------------- submit side

    def write(self, inputs: dict[str, np.ndarray], item) -> None:
        """Reserve the next row of the open slot and copy ``inputs``
        into it (copy happens outside the ring lock). Blocks while
        every slot is in flight — natural backpressure. On a ragged
        ring the item also reserves its ``k`` unit rows; an item that
        would overflow the open slot's unit block seals that slot and
        takes the next one. Raises RuntimeError once the ring is
        closed."""
        arrays = {k: np.asarray(v) for k, v in inputs.items()}
        spec = self.ragged
        k = int(arrays[spec.input].shape[0]) if spec is not None else 0
        t0 = time.perf_counter()
        with self._cv:
            if self._shapes is None:
                self._allocate(arrays)
            else:
                self._check_shapes(arrays)
            while True:
                if self._closed:
                    raise RuntimeError("staging ring is closed")
                if self._open is not None:
                    slot = self._open
                    if (spec is None
                            or slot.unit_count + k <= self.unit_capacity):
                        break
                    # packed units would overflow the fixed block:
                    # seal what's staged and take a fresh slot
                    slot.closed = True
                    self._full.append(slot)
                    self._open = None
                    self._cv.notify_all()
                    continue
                if self._free:
                    slot = self._free.popleft()
                    slot.t_first = time.perf_counter()
                    self._open = slot
                    break
                self._cv.wait(0.1)
            waited = time.perf_counter() - t0
            row = slot.count
            off = slot.unit_count
            slot.count += 1
            slot.unit_count += k
            if spec is not None:
                slot.row_len[row] = k
            slot.writers += 1
            slot.items.append(item)
            slot.wait_sum += waited
            filled = (slot.count >= self.capacity
                      or (spec is not None
                          and slot.unit_count >= self.unit_capacity))
            if filled:
                slot.closed = True
                self._full.append(slot)
                self._open = None
            if row == 0 or filled:
                # wake the dispatcher only on the edges it waits for
                # (first work / slot full) — a notify per row is pure
                # overhead at high fan-in
                self._cv.notify_all()
        t1 = time.perf_counter()
        try:
            for name, a in arrays.items():
                if spec is not None and name == spec.input:
                    if k:  # packed span exclusively owned
                        slot.arrays[name][off:off + k] = a
                        slot.seg[off:off + k] = row
                else:
                    slot.arrays[name][row] = a  # row exclusively owned
        finally:
            with self._cv:
                slot.write_sum += time.perf_counter() - t1
                slot.writers -= 1
                if slot.writers == 0 and slot.closed:
                    self._cv.notify_all()

    # --------------------------------------------------- dispatcher side

    def next_batch(self, deadline_s: float, bucket_fn,
                   spans=None) -> SealedBatch | None:
        """Wait for rows, honor the batch-fill deadline (measured from
        the open slot's FIRST write), then seal: close the slot, wait
        out in-flight row writers, zero the dirty pad tail, and return
        contiguous ``[:bucket]`` views. On a ragged ring ``bucket_fn``
        is called with ``(n, units)`` and the packed block/seg tail is
        masked too. Returns None once the ring is closed and
        drained. ``spans`` is the dispatcher's ``ThreadSpans``: the
        seal is marked on it. ``slot_write`` stays a duration here: the
        rows were copied by the submitting threads while the batch
        formed, before its timeline starts."""
        with self._cv:
            while True:
                if self._full:
                    slot = self._full.popleft()
                elif self._open is not None and self._open.count > 0:
                    slot = self._open
                    gen = slot.gen
                    deadline = slot.t_first + deadline_s
                    while (not slot.closed and slot.gen == gen
                           and not self._closed):
                        remaining = deadline - time.perf_counter()
                        if remaining <= 0:
                            break
                        self._cv.wait(remaining)
                    if slot.gen != gen:
                        continue  # drained (stall/stop) mid-wait
                    if slot.closed:
                        # filled while we waited — it is in _full now;
                        # claim that entry
                        try:
                            self._full.remove(slot)
                        except ValueError:
                            continue
                    else:
                        slot.closed = True
                        if self._open is slot:
                            self._open = None
                elif self._closed:
                    return None
                else:
                    self._cv.wait(0.1)
                    continue
                # slot is now exclusively claimed (in neither _open
                # nor _full — drain/release can no longer touch it)
                while slot.writers:
                    self._cv.wait(0.05)
                if slot.count == 0:
                    # lost a race with a drain that emptied it just
                    # before we claimed — recycle and keep waiting
                    slot.closed = False
                    self._free.append(slot)
                    continue
                n = slot.count
                items = list(slot.items)
                submit_wait = (time.perf_counter() - slot.t_first
                               + slot.wait_sum)
                write_sum = slot.write_sum
                break
        t0 = time.perf_counter()
        if spans is not None:
            spans.to("seal", t0)
        sealed = self._seal(slot, items, n, bucket_fn)
        sealed.clock.update({
            "submit_wait": submit_wait,
            "slot_write": write_sum,
        })
        sealed.clock.mark("seal", t0, time.perf_counter() - t0)
        return sealed

    def _seal(self, slot: _Slot, items: list, n: int,
              bucket_fn) -> SealedBatch:
        """Common seal tail (deadline path + stage_direct): pick the
        bucket, zero the dirty pad tails (dense rows AND, on a ragged
        ring, the packed unit block + seg vector), and build the
        contiguous views + ragged descriptor."""
        spec = self.ragged
        if spec is not None:
            units = slot.unit_count
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
            return SealedBatch(slot, views, items, n, bucket, StageClock(),
                               row_len=row_len, row_offset=row_offset,
                               units=units, unit_rows=u)
        bucket = bucket_fn(n)
        dirty = min(slot.high, bucket)
        for arr in slot.arrays.values():
            if dirty > n:
                arr[n:dirty] = 0
        views = {k: a[:bucket] for k, a in slot.arrays.items()}
        return SealedBatch(slot, views, items, n, bucket, StageClock())

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
            if self.ragged is not None:
                if slot.unit_high <= sealed.unit_rows:
                    slot.unit_high = sealed.units
                slot.unit_count = 0
            slot.count = 0
            slot.items = []
            slot.closed = False
            slot.wait_sum = 0.0
            slot.write_sum = 0.0
            slot.gen += 1
            self._free.append(slot)
            self._cv.notify_all()

    # -------------------------------------------------------- lifecycle

    def close(self) -> None:
        """Reject new writes and wake every waiter (submitters raise,
        the dispatcher drains and exits)."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def drain_items(self) -> list:
        """Remove and return every written-but-undispatched item (open
        + full slots) so the engine can fail their futures on stop or
        stall. Slots return to the free list."""
        out: list = []
        with self._cv:
            slots = list(self._full)
            self._full.clear()
            if self._open is not None:
                slots.append(self._open)
                self._open = None
            for slot in slots:
                while slot.writers:
                    self._cv.wait(0.05)
                out.extend(slot.items)
                slot.high = max(slot.high, slot.count)
                slot.count = 0
                if self.ragged is not None:
                    slot.unit_high = max(slot.unit_high, slot.unit_count)
                    slot.unit_count = 0
                slot.items = []
                slot.closed = False
                slot.wait_sum = 0.0
                slot.write_sum = 0.0
                slot.gen += 1
                self._free.append(slot)
            self._cv.notify_all()
        return out

    def pending_items(self) -> int:
        """Rows written but not yet sealed (the slot-path analogue of
        the legacy queue depth gauge)."""
        with self._cv:
            n = sum(s.count for s in self._full)
            if self._open is not None:
                n += self._open.count
            return n

    def oldest_age_s(self, now: float | None = None) -> float:
        """Age of the oldest staged-but-undispatched work (seconds):
        the earliest first-write time across full + open slots. The
        queue-age gauge's slot-path source — an invisible backlog
        shows up here long before the stall watchdog would trip."""
        now = time.perf_counter() if now is None else now
        with self._cv:
            firsts = [s.t_first for s in self._full if s.count]
            if self._open is not None and self._open.count:
                firsts.append(self._open.t_first)
        return max(0.0, now - min(firsts)) if firsts else 0.0

    # ------------------------------------------- dispatcher-side staging

    def stage_direct(self, staged: list[tuple[dict, Any]], bucket_fn,
                     clock: StageClock, spans=None,
                     ) -> tuple[SealedBatch | None, list]:
        """Stage a dispatcher-assembled batch into a free slot (the
        sched path: items arrive from per-class queues, so the row
        copies happen HERE on the dispatcher thread instead of on the
        submitting stream threads — the trade the QoS layer makes for
        class-ordered dispatch, still zero per-batch allocation).

        ``staged`` is ``[(inputs, item), ...]`` in dispatch order. A
        row whose arrays mismatch the ring shapes fails only ITS
        item's future; survivors compact into contiguous rows. Items
        past the slot's capacity — batch rows, or packed unit rows on
        a ragged ring — are NOT silently clamped: they come back as
        the second element for the caller to stage as another batch
        (the oversize-split contract). Blocks while every slot is in
        flight (the same host-side backpressure as the submit path);
        raises RuntimeError once the ring is closed; the sealed batch
        is None when no row survived. ``spans`` is the dispatcher's
        ``ThreadSpans``: the wait for a free slot, the row copies and
        the seal are marked on it."""
        first = {k: np.asarray(v) for k, v in staged[0][0].items()}
        spec = self.ragged
        with self._cv:
            if self._shapes is None:
                self._allocate(first)
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
            with self._cv:
                slot.count = 0
                slot.unit_count = 0
                slot.items = []
                slot.closed = False
                slot.gen += 1
                self._free.append(slot)
                self._cv.notify_all()
            return None, remaining
        if spans is not None:
            spans.to("seal", t1)
        slot.count = row
        slot.unit_count = off
        sealed = self._seal(slot, ok_items, row, bucket_fn)
        clock.mark("seal", t1, time.perf_counter() - t1)
        sealed.clock = clock
        return sealed, remaining

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
