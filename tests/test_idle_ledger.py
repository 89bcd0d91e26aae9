"""The idle ledger of a batch engine (evam_tpu/obs/trace.py
``divide_idle`` / ``IdleLedger``, evam_tpu/engine/batcher.py): a stretch
with no program on the device is divided by the timeline of the batch
that ended it, a batch launched while another is in flight adds nothing,
the dispatcher's two waits are two names and one state, and none of it
exists with EVAM_TRACE=off."""

from __future__ import annotations

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from evam_tpu.config.settings import reset_settings
from evam_tpu.engine.batcher import BatchEngine
from evam_tpu.obs import trace
from evam_tpu.obs.metrics import metrics

WHERES = {"upstream", "queued", "stage", "upload", "launch"}


def _fresh(monkeypatch, **env: str) -> None:
    monkeypatch.delenv("EVAM_TRACE", raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    reset_settings()
    trace.reset_cache()


def _batch(t_submits, t_take, spans):
    """Items submitted at ``t_submits``, taken by the dispatcher at
    ``t_take``, and the clock of ``spans`` ((name, start, end) rows; a
    wait is a span like any other, as the launcher leaves them)."""
    clock = trace.StageClock()
    clock["submit_wait"] = t_take - t_submits[0]
    for name, t0, t1 in spans:
        (clock.mark if name in trace.STAGE_ORDER else clock.span)(
            name, t0, t1 - t0)
    return [SimpleNamespace(t_submit=t) for t in t_submits], clock


#: a batch's way to the device on a fake clock (seconds): two frames
#: submitted at 10.000 and 10.002, taken at 10.008 (the deadline), a
#: staging block free at 10.009
SPANS = [("slot_write", 10.009, 10.012), ("seal", 10.012, 10.013),
         ("h2d_issue", 10.013, 10.015), ("wait_launcher", 10.015, 10.016),
         ("wait_slot", 10.016, 10.018), ("h2d_wait", 10.0185, 10.020),
         ("launch", 10.020, 10.021)]
WHOLE = {("upstream", "upstream"): 0.5, ("queued", "queued"): 0.008,
         ("stage", "wait_staging"): 0.001, ("stage", "slot_write"): 0.003,
         ("stage", "seal"): 0.001, ("upload", "h2d_issue"): 0.002,
         ("upload", "wait_launcher"): 0.001, ("upload", "wait_slot"): 0.002,
         ("launch", "bookkeep"): 0.0005, ("upload", "h2d_wait"): 0.0015,
         ("launch", "launch"): 0.001}


@pytest.mark.parametrize("since,until,want", [
    # dry since long before the batch's first frame: every part whole
    (9.5, 10.021, WHOLE),
    # the batch before was read back while this one's rows were written
    (10.010, 10.021, {k: (0.002 if k == ("stage", "slot_write") else v)
                      for k, v in WHOLE.items()
                      if k[0] not in ("upstream", "queued")
                      and k[1] != "wait_staging"}),
    # dry from the middle of the queue wait, cut in the middle of the
    # upload: clipped at both ends
    (10.004, 10.0145, {("queued", "queued"): 0.004,
                       ("stage", "wait_staging"): 0.001,
                       ("stage", "slot_write"): 0.003,
                       ("stage", "seal"): 0.001,
                       ("upload", "h2d_issue"): 0.0015}),
    # read back inside the launch call itself
    (10.0205, 10.021, {("launch", "launch"): 0.0005}),
    # read back after the launch was stamped: nothing was idle
    (10.03, 10.021, {}),
])
def test_a_stretch_is_divided_by_where_its_batch_was(since, until, want):
    items, clock = _batch([10.0, 10.002], 10.008, SPANS)
    got = trace.divide_idle(since, until, items, clock)
    assert set(got) == set(want)
    for key, sec in want.items():
        assert got[key] == pytest.approx(sec, abs=1e-9), key
    assert {where for where, _ in got} <= WHERES
    # the parts add up to the stretch, to the microsecond
    assert abs(sum(got.values()) - max(0.0, until - since)) < 1e-6


def test_the_ledger_sums_by_where_and_flushes_to_one_counter(monkeypatch):
    _fresh(monkeypatch)
    ledger = trace.idle_ledger("ledger-t")
    items, clock = _batch([10.0, 10.002], 10.008, SPANS)
    before = _idle_seconds("ledger-t")[1]
    ledger.add(9.5, 10.021, items, clock)
    ledger.add(10.010, 10.021, items, clock)
    assert _idle_seconds("ledger-t")[1] == before  # summed, not yet flushed
    ledger.flush()
    by_where, total = _idle_seconds("ledger-t")
    assert total - before == pytest.approx(0.521 + 0.011, abs=1e-6)
    assert set(by_where) == WHERES
    ledger.flush()  # what was flushed has left the ledger
    assert _idle_seconds("ledger-t")[1] == pytest.approx(total)


def _idle_seconds(engine: str) -> tuple[dict[str, float], float]:
    """``evam_engine_idle_seconds_total`` of one engine by ``where``."""
    by_where: dict[str, float] = {}
    for line in metrics.render().splitlines():
        if (line.startswith("evam_engine_idle_seconds_total{")
                and f'engine="{engine}"' in line):
            where = line.split('where="')[1].split('"')[0]
            by_where[where] = by_where.get(where, 0.0) + float(
                line.split()[-1])
    return by_where, sum(by_where.values())


class _Held:
    """A step's output that is "on the device" until released."""

    def __init__(self, n: int) -> None:
        self.n = n
        self.done = threading.Event()

    def __array__(self, dtype=None, copy=None):
        assert self.done.wait(30)
        return np.zeros((self.n,), np.float32)


def _gated_engine(name: str):
    """An engine whose step returns at once and whose result the test
    holds back: the test decides when a batch leaves the device."""
    eng = BatchEngine(name, lambda p, x: x, params=None, max_batch=1,
                      deadline_ms=1.0, input_names=("x",))
    launched: list[_Held] = []
    cv = threading.Condition()

    def step(params, x):
        out = _Held(x.shape[0])
        with cv:
            launched.append(out)
            cv.notify_all()
        return out

    def wait_for(n: int) -> _Held:
        with cv:
            assert cv.wait_for(lambda: len(launched) >= n, 30)
        return launched[n - 1]

    eng._jit_step = step
    return eng, wait_for


def _until(cond, timeout: float = 10.0) -> None:
    deadline = time.time() + timeout
    while not cond():
        assert time.time() < deadline
        time.sleep(0.002)


def test_only_a_launch_onto_a_dry_engine_adds_to_the_ledger(monkeypatch):
    """The first batch ever and a batch launched while another is in
    flight add nothing; one launched after the last readback adds the
    stretch since that readback, and the dispatcher's two waits carry
    their two names."""
    _fresh(monkeypatch)
    names: list[str] = []

    class _Ann:
        def __init__(self, name: str) -> None:
            names.append(name)

        def __exit__(self, *exc) -> None:
            pass

    eng, wait_for = _gated_engine("idle-t")
    monkeypatch.setattr(trace, "_annotation", _Ann)
    monkeypatch.setattr(trace, "_profiling", lambda: True)
    x = np.ones((3,), np.float32)
    try:
        a = eng.submit(x=x)
        held_a = wait_for(1)
        b = eng.submit(x=x)
        held_b = wait_for(2)  # launched while A is in flight
        _until(lambda: eng._on_device == 2)  # the launcher has counted it
        assert eng._idle_since is None
        held_a.done.set()
        a.result(timeout=30)
        assert eng._idle_since is None  # B is still on the device
        held_b.done.set()
        b.result(timeout=30)
        _until(lambda: eng._idle_since is not None)
        since = eng._idle_since
        assert eng._on_device == 0
        assert not eng._idle._acc and _idle_seconds("idle-t")[1] == 0.0
        time.sleep(0.05)  # the engine idles, dry
        t_submit = time.perf_counter()
        c = eng.submit(x=x)
        wait_for(3).done.set()
        c.result(timeout=30)
        t_done = time.perf_counter()
    finally:
        eng.stop()  # the launcher's last flush takes the ledger along
    by_where, total = _idle_seconds("idle-t")
    assert 0.05 <= t_submit - since <= total <= t_done - since
    # dry for want of a frame, most of it
    assert by_where["upstream"] >= 0.05 and set(by_where) <= WHERES
    assert "evam.dispatch.wait_items" in names
    assert "evam.dispatch.wait_items.fill" in names
    assert not [n for n in names if "wait_result" in n]


def test_two_wait_names_are_one_state_in_the_sums(monkeypatch):
    _fresh(monkeypatch)
    labels = {"engine": "names-t", "thread": "dispatch"}

    def seconds(state: str) -> float:
        return metrics.get_counter("evam_engine_thread_seconds",
                                   {**labels, "state": state})

    sp = trace.thread_spans("names-t", "dispatch")
    sp.to("wait_items", 1.0)
    sp.to("wait_items.fill", 2.0)
    assert sp.where()[0] == "wait_items.fill"
    sp.to("slot_write", 2.5)
    sp.to("wait_staging", 3.0)
    sp.to(None, 3.25)
    assert seconds("wait_items") == pytest.approx(1.5)
    assert seconds("wait_items.fill") == 0.0
    assert seconds("work") == pytest.approx(0.5)
    assert seconds("wait_staging") == pytest.approx(0.25)


def test_trace_off_keeps_no_ledger_and_takes_no_stamp(monkeypatch):
    """EVAM_TRACE=off: the engine holds no ledger, its hooks are the one
    None-check, obs/trace.py reads no clock on the served path, and
    ``/metrics`` has no line of the new series."""
    _fresh(monkeypatch, EVAM_TRACE="off")

    class _NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"time.{name} read with EVAM_TRACE=off")

    assert trace.idle_ledger("off-t") is None
    eng = BatchEngine("off-t", lambda p, x: x * 2.0, params=None,
                      max_batch=2, deadline_ms=1.0, input_names=("x",))
    monkeypatch.setattr(trace, "time", _NoClock())
    try:
        assert eng._idle is None
        for _ in range(3):
            out = eng.submit(x=np.ones((3,), np.float32)).result(timeout=60)
            assert float(out.sum()) == 6.0
        assert eng._idle_since is None and eng._on_device == 0
    finally:
        eng.stop()
    assert _idle_seconds("off-t") == ({}, 0.0)
    assert not [l for l in metrics.render().splitlines()
                if l.startswith("evam_engine_thread_") and "off-t" in l]


# -- the metric files that read the new series ----------------------------

NEW_FILES = (
    [f"{name}.{cell}" for cell in ("replay", "paced")
     for name in ("engine_idle_share", "idle_upstream_share",
                  "idle_queued_share", "idle_stage_share",
                  "idle_upload_share", "idle_launch_share")]
    + ["lm_dry_dispatch_share.replay", "lm_dry_dispatch_share.laguna_replay",
       "chain_cpu_share.replay"])


def _snapshot(t: float, lines: dict[str, float]) -> dict:
    return {"t": t, "metrics": dict(lines)}


def test_the_metric_files_read_the_new_series_and_nothing_on_a_parent():
    """The fifteen files under benchmark/metrics/: each through the
    accepted reader ``prom_delta_ratio`` with no code of its own; over a
    window the five ``where`` shares add up to 100; and on a server from
    before the series (the parent commit) each reads None, not an error,
    so its line leaves the metric out."""
    import json
    from pathlib import Path

    from benchmark.readers import prom_delta_ratio

    idle = "evam_engine_idle_seconds_total"
    thread = "evam_engine_thread_seconds_total"
    before = _snapshot(100.0, {
        f'{idle}{{engine="d",stage="upstream",where="upstream"}}': 1.0,
        f'{thread}{{engine="d",state="wait_items",thread="dispatch"}}': 5.0})
    after = _snapshot(140.0, {
        f'{idle}{{engine="d",stage="upstream",where="upstream"}}': 3.0,
        f'{idle}{{engine="d",stage="queued",where="queued"}}': 4.0,
        f'{idle}{{engine="d",stage="wait_staging",where="stage"}}': 1.0,
        f'{idle}{{engine="d",stage="slot_write",where="stage"}}': 5.0,
        f'{idle}{{engine="d",stage="h2d_issue",where="upload"}}': 6.0,
        f'{idle}{{engine="d",stage="launch",where="launch"}}': 2.0,
        f'{thread}{{engine="d",state="wait_items",thread="dispatch"}}': 9.0,
        f'{thread}{{engine="streams",state="work",thread="chain"}}': 8.0,
        'evam_engine_thread_cpu_seconds_total'
        '{engine="streams",thread="chain"}': 2.0,
        'evam_generate_dry_dispatches_total{kind="decode"}': 3.0,
        'evam_generate_dry_dispatches_total{kind="prefill"}': 0.0,
        'evam_generate_steps_total{kind="decode"}': 40.0,
        'evam_generate_steps_total{kind="prefill"}': 20.0})
    old = _snapshot(140.0, {
        f'{thread}{{engine="d",state="wait_items",thread="dispatch"}}': 9.0,
        'evam_generate_steps_total{kind="decode"}': 40.0})
    metric_dir = Path(__file__).resolve().parent.parent / "benchmark" / "metrics"
    read = {}
    for name in NEW_FILES:
        spec = json.loads((metric_dir / f"{name}.json").read_text())
        assert set(spec) == {"reader", "params"}, name
        assert spec["reader"] == "prom_delta_ratio", name
        ctx = {"before": before, "after": after}
        read[name] = prom_delta_ratio.read(ctx, spec["params"])
        assert prom_delta_ratio.read({"before": before, "after": old},
                                     spec["params"]) is None, name
    for cell in ("replay", "paced"):
        assert read[f"engine_idle_share.{cell}"] == pytest.approx(50.0)
        shares = {w: read[f"idle_{w}_share.{cell}"] for w in WHERES}
        assert shares == pytest.approx({"upstream": 10.0, "queued": 20.0,
                                        "stage": 30.0, "upload": 30.0,
                                        "launch": 10.0})
    assert read["lm_dry_dispatch_share.replay"] == pytest.approx(5.0)
    assert read["lm_dry_dispatch_share.laguna_replay"] == pytest.approx(5.0)
    assert read["chain_cpu_share.replay"] == pytest.approx(25.0)
