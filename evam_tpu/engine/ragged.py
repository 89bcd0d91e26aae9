"""Ragged batching: masked packing + bucket consolidation.

Engines bucket by batch size and zero-pad every block's tail, and the
classify-family engines additionally pad every ITEM to a fixed ROI
budget (`stages/infer.py` fills a [ROI_BUDGET, 4] box block whatever
the frame's real region count is). On a heterogeneous fleet — mixed
resolutions, mixed models, ragged per-frame region counts — that
fragments the device into half-empty buckets where occupancy, not
FLOPs, is the throughput ceiling (ROADMAP "Ragged batching"). Ragged
Paged Attention (PAPERS.md) shows the TPU-native answer: ONE
fixed-shape program over a packed block, with per-row length/offset
vectors and masked compute, instead of one program per
(shape, fill) combination.

``EVAM_RAGGED=packed`` turns on two cooperating mechanisms:

* **masked packing** (classify-family engines): each submitted item
  carries its REAL region rows (``boxes`` shape ``(k, 4)``, k in
  [0, max_units]); the staging ring packs them end to end into one
  fixed unit block with a segment-id vector (``seg[j]`` = the batch
  row that owns packed unit j, −1 on the pad tail), and the jitted
  step computes per-unit with the pad rows masked to zero
  (`steps.build_classify_step_ragged`). The completer scatters
  results back per item via the sealed batch's ``row_len`` /
  ``row_offset`` vectors. Unit occupancy becomes
  Σk / unit_rows(bucket) instead of the dense path's silent
  Σk / (bucket × max_units);
* **bucket consolidation** (every engine): adjacent batch-size
  buckets share a program — the ladder keeps every other rung
  (plus the floor and the top), halving compile count, program
  memory and cold first-batch stalls (the batch-size study,
  PAPERS.md). Pad rows were always discarded at completion, so
  coarser buckets change occupancy accounting, never results.

``EVAM_RAGGED=off`` (the default until a TPU accuracy window) keeps
today's bucketed dense path byte-identical — the same A/B discipline
as ``EVAM_GATE``. Supervisor rebuilds inherit the
mode through the hub's factory closure.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np

#: valid EVAM_RAGGED values
RAGGED_MODES = ("packed", "off")


def ragged_mode(value: str | None = None) -> str:
    """Resolve + validate the ragged mode (explicit arg beats env)."""
    mode = value or os.environ.get("EVAM_RAGGED", "off")
    if mode not in RAGGED_MODES:
        raise ValueError(
            f"EVAM_RAGGED must be one of {'|'.join(RAGGED_MODES)}, "
            f"got {mode!r}")
    return mode


@dataclasses.dataclass(frozen=True)
class RaggedSpec:
    """Declares ONE engine input as ragged (variable leading dim).

    The spec rides the engine even when ``EVAM_RAGGED=off`` so the
    occupancy accounting can stay honest (a dense classify batch
    computes ``bucket × max_units`` unit rows whatever the real
    region counts were); packing itself only happens in ``packed``
    mode.
    """

    #: name of the ragged input ("boxes" for classify engines)
    input: str
    #: per-unit trailing shape ((4,) — one normalized box)
    unit_shape: tuple[int, ...]
    #: unit dtype
    dtype: np.dtype = np.float32
    #: per-ITEM unit cap (the stage-level ROI budget); a dense item
    #: always carries exactly this many rows, a packed one 0..max
    max_units: int = 8
    #: packed unit rows budgeted PER BATCH ROW in the device shape —
    #: the knob that converts "8 ROI slots per frame, mostly empty"
    #: into "unit_budget slots per frame, shared across the batch".
    #: Floored at max_units so a lone full item always fits.
    unit_budget: int = 4

    def unit_rows(self, bucket: int) -> int:
        """Packed unit rows in the device shape for ``bucket`` items."""
        return max(self.max_units, bucket * self.unit_budget)


def consolidate_buckets(buckets: list[int], align: int = 1) -> list[int]:
    """Thin a power-of-two bucket ladder so adjacent shapes share a
    program: keep the floor, the top, and every OTHER rung between
    (descending from the top so the serving bucket keeps its exact
    shape). Halves compiled-program count; batches that would have
    used a dropped rung round up one rung — their pad rows are masked
    or discarded exactly as before.

    ``align`` is the mesh data-axis size: every kept rung >= align is
    rounded up to a multiple of it AT LADDER BUILD (MeshPlan.pad_batch
    applied here, once), so a sealed block dispatched sharded is never
    re-padded per batch — a rung that isn't divisible by the data axis
    would force an extra host-side copy on EVERY dispatch through that
    bucket. Rungs below align (the fleet mode's single-device small
    buckets) are left alone: they dispatch locally, unsharded."""
    if len(buckets) <= 2:
        kept = list(buckets)
    else:
        keep = {buckets[0], buckets[-1]}
        # every other rung, walking DOWN from the top
        for i in range(len(buckets) - 1, -1, -2):
            keep.add(buckets[i])
        kept = sorted(keep)
    if align > 1:
        kept = sorted({
            -(-b // align) * align if b >= align else b for b in kept
        })
    return kept
