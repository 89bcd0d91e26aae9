"""Stage interfaces.

Sync stages transform a FrameContext inline; async stages submit work
to a shared engine and park the frame in their own in-order queue. The
StreamRunner resumes a queue's head when its future resolves, so each
stage sees its stream's frames in seq order, one call at a time, always
on the stream's chain thread and never on the thread that resolved the
future. The async split is what lets one stream keep multiple frames
in flight (overlapping decode, batching and TPU steps — the role
GStreamer queues play between elements in the reference, SURVEY.md
§2d-5), a later frame at an earlier stage while an earlier one is
still parked further down the chain; of a chain's several async
stages each holds at most its even share of the stream's window.
"""

from __future__ import annotations

from concurrent.futures import Future

import numpy as np

from evam_tpu.stages.context import FrameContext


class Stage:
    """Synchronous stage: ctx in → list of ctx out (0..n)."""

    name: str = "stage"
    is_async = False

    def process(self, ctx: FrameContext) -> list[FrameContext]:
        raise NotImplementedError

    def flush(self) -> list[FrameContext]:
        """Emit any buffered contexts at end-of-stream."""
        return []

    def close(self) -> None:
        pass

    def cancel(self) -> None:
        """The stream is stopping: give up work still in flight for it
        (``StreamRunner.stop``). Its parked futures then resolve at
        once."""

    # ---- stream-state checkpointing (SURVEY §5.4 + §7 "tracking
    # statefulness"): stages with cross-frame state can round-trip a
    # JSON-serializable snapshot through the stream registry's
    # streams.json so a restarted server resumes without breaking
    # downstream invariants (e.g. tracker id monotonicity).

    def snapshot(self) -> dict | None:
        """JSON-serializable cross-frame state, or None (stateless)."""
        return None

    def restore(self, state: dict) -> None:
        """Re-apply a snapshot() on a freshly built stage."""


class AsyncStage(Stage):
    """Engine-backed stage: submit() returns a Future (or None to skip
    inference for this frame), complete() folds the packed result back
    into the context."""

    is_async = True

    def submit(self, ctx: FrameContext) -> Future | None:
        raise NotImplementedError

    def complete(self, ctx: FrameContext, result: np.ndarray | None) -> list[FrameContext]:
        raise NotImplementedError

    def process(self, ctx: FrameContext) -> list[FrameContext]:
        fut = self.submit(ctx)
        return self.complete(ctx, fut.result() if fut is not None else None)
