"""Ragged batching A/B (EVAM_RAGGED, engine/ragged.py): masked region
packing through the staging ring — packed-vs-off bit-identical outputs
across fill levels, row scatter-back ordering under sched class
queues, empty-row/zero-region items, bucket consolidation, oversize
splits, and supervisor rebuilds inheriting the mode."""

from __future__ import annotations

import numpy as np
import pytest

from evam_tpu.engine.batcher import BatchEngine
from evam_tpu.engine.ragged import (
    RaggedSpec,
    consolidate_buckets,
    ragged_mode,
)
from evam_tpu.engine.ringbuf import SlotRing
from evam_tpu.sched.classes import SchedConfig

#: "equal" for two differently-shaped programs computing the same rows
_F32_ULPS = 8 * float(np.finfo(np.float32).eps)

SPEC = RaggedSpec(input="boxes", unit_shape=(4,), dtype=np.float32,
                  max_units=8, unit_budget=4)


def _dense_step(params, frames, boxes):
    """[B, R, 4] boxes + [B, F] frames → [B, R, 2]: deterministic
    per-(frame, box) math, so a row's output cannot depend on batch
    composition — the bit-identity oracle."""
    import jax.numpy as jnp

    s = frames[:, :1].astype(jnp.float32)
    a = boxes.sum(-1) + s
    return jnp.stack([a, a * 3], axis=-1)


def _ragged_step(params, frames, boxes, seg):
    """The packed twin: [U, 4] boxes + seg ids, masked pad rows."""
    import jax.numpy as jnp

    valid = seg >= 0
    src = jnp.clip(seg, 0, frames.shape[0] - 1)
    s = frames[src][:, :1].astype(jnp.float32)
    a = boxes.sum(-1)[:, None] + s
    out = jnp.concatenate([a, a * 3], axis=-1)
    return out * valid[:, None]


def _items(n: int, seed: int = 0, counts=None):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        k = (counts[i % len(counts)] if counts
             else int(rng.integers(0, SPEC.max_units + 1)))
        out.append((
            rng.integers(0, 200, (6,), np.uint8),
            rng.random((k, 4)).astype(np.float32),
        ))
    return out


def _engine(name: str, ragged: str, step=None, **kw) -> BatchEngine:
    kwargs = dict(
        step_fn=step or (_ragged_step if ragged == "packed"
                         else _dense_step),
        params=None,
        max_batch=8,
        deadline_ms=2.0,
        input_names=("frames", "boxes"),
        stall_timeout_s=0,
        ragged=ragged,
        ragged_spec=SPEC,
    )
    kwargs.update(kw)
    return BatchEngine(name, **kwargs)


def _submit(eng: BatchEngine, items, packed: bool, **kw):
    futs = []
    for f, bx in items:
        if packed:
            futs.append(eng.submit(frames=f, boxes=bx, **kw))
        else:
            dense = np.zeros((SPEC.max_units, 4), np.float32)
            dense[:len(bx)] = bx
            futs.append(eng.submit(units=len(bx), frames=f, boxes=dense,
                                   **kw))
    return [fu.result(timeout=60) for fu in futs]


class TestRaggedMode:
    def test_mode_validation(self):
        assert ragged_mode("packed") == "packed"
        assert ragged_mode("off") == "off"
        with pytest.raises(ValueError):
            ragged_mode("sideways")

    def test_env_default_off_is_dense(self, monkeypatch):
        monkeypatch.delenv("EVAM_RAGGED", raising=False)
        eng = _engine("rag-default", ragged=None, step=_dense_step)
        try:
            assert eng.ragged == "off" and not eng._packed
            assert eng._ring.ragged is None
            assert eng.buckets == [1, 2, 4, 8]
        finally:
            eng.stop()

    def test_env_var_selects_packed(self, monkeypatch):
        monkeypatch.setenv("EVAM_RAGGED", "packed")
        eng = _engine("rag-env", ragged=None)
        try:
            assert eng.ragged == "packed" and eng._packed
            assert eng._ring.ragged is SPEC
        finally:
            eng.stop()

    def test_consolidated_ladder(self):
        assert consolidate_buckets([1, 2, 4, 8, 16, 32, 64, 128]) == \
            [1, 2, 8, 32, 128]
        assert consolidate_buckets([1, 2]) == [1, 2]
        eng = _engine("rag-ladder", ragged="packed", max_batch=128)
        try:
            # top + floor survive; every other rung shared upward
            assert eng.buckets[0] == 1 and eng.buckets[-1] == 128
            assert len(eng.buckets) < 8
        finally:
            eng.stop()


class TestPackedIdentity:
    def test_bit_identical_across_fill_levels(self):
        """Every fill level (1..max_batch items, region counts 0..8
        including empty) resolves to the dense path's rows, byte for
        byte — the EVAM_RAGGED A/B contract."""
        eng_off = _engine("rag-off", ragged="off")
        eng_pk = _engine("rag-pk", ragged="packed")
        try:
            for fill in (1, 2, 3, 5, 8, 13):
                items = _items(fill, seed=fill)
                out_off = _submit(eng_off, items, packed=False)
                out_pk = _submit(eng_pk, items, packed=True)
                for (f, bx), od, op in zip(items, out_off, out_pk):
                    k = len(bx)
                    assert op.shape[0] == k
                    assert np.array_equal(od[:k], op), f"fill={fill}"
        finally:
            eng_off.stop()
            eng_pk.stop()

    def test_zero_region_item_resolves_empty(self):
        eng = _engine("rag-empty", ragged="packed")
        try:
            items = _items(6, seed=3, counts=[0, 2, 0, 8, 1, 0])
            outs = _submit(eng, items, packed=True)
            for (f, bx), op in zip(items, outs):
                assert op.shape == (len(bx), 2)
        finally:
            eng.stop()

    def test_single_full_item_fits_floor_bucket(self):
        """unit_rows is floored at max_units: a lone 8-region frame
        must pack into the smallest bucket's block."""
        eng = _engine("rag-floor", ragged="packed")
        try:
            items = _items(1, seed=9, counts=[8])
            (out,) = _submit(eng, items, packed=True)
            assert out.shape == (8, 2)
        finally:
            eng.stop()

    def test_honest_unit_occupancy(self):
        """Dense accounting books bucket×max_units computed rows per
        batch; packed books the (smaller) packed block — the same
        real units read as strictly higher occupancy."""
        items = _items(12, seed=5, counts=[1, 2, 3, 0, 2, 1])
        eng_off = _engine("rag-occ-off", ragged="off")
        eng_pk = _engine("rag-occ-pk", ragged="packed")
        try:
            _submit(eng_off, items, packed=False)
            _submit(eng_pk, items, packed=True)
            units = sum(len(bx) for _, bx in items)
            assert eng_off.stats.units == units
            assert eng_pk.stats.units == units
            assert eng_pk.stats.unit_slots < eng_off.stats.unit_slots
            assert (eng_pk.stats.unit_occupancy
                    > eng_off.stats.unit_occupancy)
            assert sum(eng_pk.stats.bucket_batches.values()) == \
                eng_pk.stats.batches
        finally:
            eng_off.stop()
            eng_pk.stop()

    def test_unit_overflow_seals_early(self):
        """Region-heavy items must split across packed batches when
        the unit block fills before the item rows do — and still
        resolve correctly in order."""
        # 8 items × 8 units = 64 units >> unit_rows(8) = 32.
        # Integer-valued inputs keep the float32 oracle exact (a
        # random-float numpy sum can differ from XLA's in the last
        # bit — that would test the oracle, not the engine).
        items = [(np.full((6,), i, np.uint8),
                  np.full((8, 4), float(i), np.float32))
                 for i in range(8)]
        eng = _engine("rag-overflow", ragged="packed")
        try:
            outs = _submit(eng, items, packed=True)
            for i, ((f, bx), op) in enumerate(zip(items, outs)):
                assert op.shape == (8, 2)
                assert np.all(op[:, 0] == 4.0 * i + i)
            assert eng.stats.batches >= 2
        finally:
            eng.stop()


class TestRaggedSched:
    def test_scatter_back_ordering_under_class_queues(self):
        """The dispatcher stages class-ordered picks through the
        ring: each future must still resolve to ITS OWN boxes'
        rows whatever class interleaving dispatch chose."""
        cfg = SchedConfig(deadline_ms={"realtime": 1.0, "standard": 2.0,
                                       "batch": 4.0})
        eng = _engine("rag-sched", ragged="packed", sched=cfg)
        try:
            rng = np.random.default_rng(2)
            futs, expects = [], []
            for i in range(30):
                prio = ("realtime", "standard", "batch")[i % 3]
                k = int(rng.integers(0, 9))
                # integer-valued floats: the oracle 4i + frame value
                # is exact in float32, so row mixups can't hide
                # behind rounding
                f = np.full((6,), i % 100, np.uint8)
                bx = np.full((k, 4), float(i), np.float32)
                futs.append(eng.submit(priority=prio, frames=f,
                                       boxes=bx))
                expects.append((i, k))
            for fu, (i, k) in zip(futs, expects):
                out = fu.result(timeout=60)
                assert out.shape == (k, 2)
                if k:
                    assert np.all(out[:, 0] == 4.0 * i + (i % 100))
        finally:
            eng.stop()


class TestOversizeSplit:
    def test_packed_unit_split_counts(self):
        """Sched + packed: a class pick whose units overflow the top
        unit block splits across batches and counts as oversize."""
        cfg = SchedConfig(deadline_ms={"realtime": 1.0,
                                       "standard": 30.0,
                                       "batch": 4.0})
        eng = _engine("rag-unit-split", ragged="packed", sched=cfg,
                      deadline_ms=30.0)
        try:
            items = _items(8, seed=8, counts=[8])
            outs = _submit(eng, items, packed=True)
            assert all(o.shape == (8, 2) for o in outs)
            assert eng.stats.oversize_splits >= 1
        finally:
            eng.stop()


class TestSupervisorInheritsRagged:
    def test_rebuild_keeps_packed_mode(self):
        """The factory closure is the rebuild recipe: a quarantined
        packed engine must come back packed (same spec, consolidated
        ladder) — EVAM_RAGGED survives the swap."""
        from evam_tpu.engine.supervisor import SupervisedEngine

        def factory() -> BatchEngine:
            return _engine("rag-sup", ragged="packed")

        sup = SupervisedEngine("rag-sup", factory, max_restarts=3,
                               restart_window_s=60.0, backoff_s=0.05)
        try:
            first = sup._engine
            items = _items(3, seed=4)
            out0 = _submit(sup, items, packed=True)
            # force a quarantine via the stalled flag (the watchdog's
            # signal) — the monitor rebuilds from the factory
            first.stalled.set()
            import time as _t

            deadline = _t.time() + 20
            while _t.time() < deadline:
                if sup.state == "running" and sup._engine is not first:
                    break
                _t.sleep(0.05)
            assert sup._engine is not first
            assert sup._engine.ragged == "packed"
            assert sup._engine._packed
            assert sup._engine._ring.ragged is SPEC
            out1 = _submit(sup, items, packed=True)
            for a, b in zip(out0, out1):
                assert np.array_equal(a, b)
            # cumulative counters carried across the swap
            assert sup.stats.batches >= 2
            assert sup.stats.units >= 2 * sum(
                len(bx) for _, bx in items)
        finally:
            sup.stop()

    def test_hub_factory_carries_ragged(self):
        from evam_tpu.engine.hub import EngineHub

        hub = EngineHub(registry=None, plan=None, max_batch=8,
                        supervise=True, stall_timeout_s=0,
                        ragged="packed")
        eng = hub._build("rag-hub", _ragged_step, None,
                         ("frames", "boxes"), ragged_spec=SPEC)
        try:
            assert eng.ragged == "packed"
            rebuilt = eng._factory()
            try:
                assert rebuilt.ragged == "packed" and rebuilt._packed
                assert rebuilt._ring.ragged is SPEC
            finally:
                rebuilt.stop()
        finally:
            eng.stop()


class _Item:
    def __init__(self):
        from concurrent.futures import Future

        self.future = Future()


def _stage(ring, rows, bucket):
    """Stage ``rows`` ([(frames, boxes), ...]) as one pick; returns
    (sealed, leftovers, items)."""
    from evam_tpu.obs.trace import StageClock

    staged = [({"frames": f, "boxes": bx}, _Item()) for f, bx in rows]
    sealed, rest = ring.stage(staged, lambda n, u: bucket, StageClock())
    return sealed, rest, [it for _, it in staged]


class TestRaggedRing:
    def test_pack_seal_descriptor(self):
        ring = SlotRing(capacity=8, depth=2, ragged=SPEC)
        counts = [2, 0, 3, 1]
        sealed, rest, items = _stage(ring, [
            (np.full((6,), k, np.uint8),
             np.full((k, 4), float(k), np.float32)) for k in counts], 8)
        assert rest == [] and sealed.items == items
        assert sealed.n == 4 and sealed.units == 6
        np.testing.assert_array_equal(sealed.row_len, counts)
        np.testing.assert_array_equal(sealed.row_offset, [0, 2, 2, 5])
        u = SPEC.unit_rows(8)
        assert sealed.arrays["boxes"].shape == (u, 4)
        assert sealed.arrays["seg"].shape == (u,)
        np.testing.assert_array_equal(
            sealed.arrays["seg"][:6], [0, 0, 2, 2, 2, 3])
        assert np.all(sealed.arrays["seg"][6:] == -1)
        # pad tail of the packed block is zeroed
        assert np.all(sealed.arrays["boxes"][6:] == 0)
        ring.release(sealed)
        # the block's next use: what the first left in the unit block
        # and the seg vector past the new fill is masked again
        sealed, _, _ = _stage(ring, [
            (np.full((6,), 9, np.uint8),
             np.full((1, 4), 9.0, np.float32))], 8)
        np.testing.assert_array_equal(sealed.arrays["seg"][:1], [0])
        assert np.all(sealed.arrays["seg"][1:] == -1)
        assert np.all(sealed.arrays["boxes"][1:] == 0)
        assert np.all(sealed.arrays["frames"][1:] == 0)
        ring.release(sealed)
        ring.close()

    def test_unit_overflow_comes_back_in_order(self):
        """A pick whose packed unit rows overflow the block's unit
        capacity (before its batch rows run out) seals what fits and
        hands the rest back, in order."""
        ring = SlotRing(capacity=4, depth=2, ragged=SPEC)
        assert ring.unit_capacity == SPEC.unit_rows(4) == 16
        rows = [(np.full((6,), i, np.uint8),
                 np.full((8, 4), float(i), np.float32))
                for i in range(3)]
        sealed, rest, items = _stage(ring, rows, 4)
        assert sealed.n == 2 and sealed.units == 16
        assert sealed.items == items[:2]
        assert [it for _, it in rest] == items[2:]
        np.testing.assert_array_equal(sealed.row_offset, [0, 8])
        ring.close()

    def test_ragged_shape_check(self):
        """Too many unit rows, or a wrong unit shape, fails that
        item's future only."""
        ring = SlotRing(capacity=4, depth=2, ragged=SPEC)
        f = np.zeros((6,), np.uint8)
        sealed, rest, items = _stage(ring, [
            (f, np.zeros((2, 4), np.float32)),
            (f, np.zeros((9, 4), np.float32)),   # > max_units
            (f, np.zeros((2, 5), np.float32)),   # wrong unit shape
            (f, np.zeros((1, 4), np.float32))], 4)
        assert rest == []
        assert sealed.items == [items[0], items[3]]
        np.testing.assert_array_equal(sealed.row_len, [2, 1])
        for i in (1, 2):
            with pytest.raises(ValueError, match="ragged input"):
                items[i].future.result(timeout=0)
        ring.close()


class TestClassifyStageRagged:
    """End-to-end through the real hub + ClassifyStage + classify
    steps: packed submits the frame's real region rows and the
    resulting tensors are identical to the dense path's."""

    @pytest.fixture(scope="class")
    def hubs(self):
        from evam_tpu.engine.hub import EngineHub
        from evam_tpu.models import ModelRegistry, ZOO_SPECS

        small = {k: (64, 64) for k in ZOO_SPECS}
        small["audio_detection/environment"] = (1, 1600)
        narrow = {k: 8 for k in ZOO_SPECS}

        def build(mode):
            return EngineHub(
                ModelRegistry(dtype="float32", input_overrides=small,
                              width_overrides=narrow),
                plan=None, max_batch=8, deadline_ms=2.0,
                supervise=False, stall_timeout_s=0, ragged=mode)

        hub_off, hub_pk = build("off"), build("packed")
        yield hub_off, hub_pk
        hub_off.stop()
        hub_pk.stop()

    @staticmethod
    def _stage(hub):
        from evam_tpu.stages.infer import ClassifyStage

        return ClassifyStage(
            "cls", "object_classification/vehicle_attributes",
            {"threshold": 0.0, "ingest-size": (64, 64)}, hub)

    @staticmethod
    def _ctx(seed: int, k: int):
        from evam_tpu.stages.context import FrameContext, Region

        rng = np.random.default_rng(seed)
        ctx = FrameContext(
            frame=rng.integers(0, 255, (64, 64, 3), np.uint8),
            pts_ns=0, seq=seed, stream_id="rag")
        for j in range(k):
            x0, x1 = sorted(rng.random(2).tolist())
            y0, y1 = sorted(rng.random(2).tolist())
            ctx.regions.append(Region(
                x0=x0, y0=y0, x1=x1, y1=y1, confidence=0.9,
                label_id=0, label="vehicle"))
        return ctx

    def test_packed_stage_matches_dense(self, hubs):
        hub_off, hub_pk = hubs
        st_off, st_pk = self._stage(hub_off), self._stage(hub_pk)
        assert st_pk._packed and not st_off._packed
        assert getattr(st_pk.engine, "ragged", "off") == "packed"
        # fill levels incl. zero-region (no submit) and full budget
        for seed, k in ((1, 2), (2, 0), (3, 8), (4, 1), (5, 5)):
            ctx_o, ctx_p = self._ctx(seed, k), self._ctx(seed, k)
            fut_o, fut_p = st_off.submit(ctx_o), st_pk.submit(ctx_p)
            if k == 0:
                assert fut_o is None and fut_p is None
                continue
            res_o = fut_o.result(timeout=120)
            res_p = fut_p.result(timeout=120)
            assert res_p.shape[0] == k
            # Tolerance, not identity: the dense [B, R] program and
            # the packed [U] program are two different XLA programs,
            # and a backend may fuse/vectorize the softmax tail of
            # differently-shaped programs differently — they differ in
            # the last f32 ulp on this CPU (0.1472458 vs 0.14724581).
            # 8 ulps of float32, fixed from the dtype.
            np.testing.assert_allclose(res_p, res_o[:k], rtol=_F32_ULPS,
                                       atol=0)
            st_off.complete(ctx_o, res_o)
            st_pk.complete(ctx_p, res_p)
            for ro, rp in zip(ctx_o.regions, ctx_p.regions):
                assert len(ro.tensors) == len(rp.tensors)
                for to, tp in zip(ro.tensors, rp.tensors):
                    assert to.name == tp.name
                    assert to.label == tp.label
                    assert to.confidence == pytest.approx(
                        tp.confidence, rel=_F32_ULPS, abs=0)
        # honest accounting flowed through the hub rows
        rows = hub_pk.stats()
        key = "classify:object_classification/vehicle_attributes"
        assert rows[key]["ragged"] == "packed"
        assert 0 < rows[key]["unit_occupancy"] <= 1
        assert rows[key]["bucket_batches"]
        health = hub_pk.readiness()
        assert {"occupancy", "unit_occupancy",
                "compiled_programs"} <= set(health)


class TestPackedWithMesh:
    def test_packed_engine_on_data_mesh(self, eight_devices):
        """Sharded packed engine: the jit in_shardings must cover the
        seg vector too (caught live — a plan-built classify engine
        failed every batch with a pjit arity error while the
        plan-less tests passed)."""
        from evam_tpu.parallel import build_mesh

        plan = build_mesh()
        eng = _engine("rag-mesh", ragged="packed", plan=plan,
                      max_batch=16)
        try:
            items = _items(12, seed=13)
            outs = _submit(eng, items, packed=True)
            for (f, bx), op in zip(items, outs):
                assert op.shape == (len(bx), 2)
        finally:
            eng.stop()
