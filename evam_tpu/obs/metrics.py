"""Lightweight in-process metrics, exported in Prometheus text format.

The reference exposes no metrics endpoint (SURVEY.md §5.5); this is a
required hardening addition: per-stream FPS, batch occupancy, and
per-stage latency percentiles so the BASELINE targets are
self-measurable from the service itself.
"""

from __future__ import annotations

import bisect
import threading
import time
from collections import defaultdict, deque
from dataclasses import dataclass, field


#: The metric registration table: every ``evam_*`` series the process
#: may emit, with its kind and the label keys call sites may attach.
#: ``evam_tpu.analysis`` (the ``contracts`` pass) enforces that every
#: metric call site in the package names a key registered here with a
#: label-key subset of the spec — register new metrics HERE first.
#: Subset (not equality) because several histograms are observed both
#: in aggregate and per label (e.g. evam_frame_latency_seconds lands
#: one unlabeled series plus a bounded per-class series).
METRIC_SPECS: dict[str, tuple[str, tuple[str, ...]]] = {
    # stream lifecycle / server
    "evam_stream_failures": ("counter", ()),
    "evam_shutdown_leaked_streams": ("gauge", ()),
    "evam_frames_processed": ("counter", ("stream",)),
    "evam_frame_errors": ("counter", ("stream",)),
    # media ingest
    "evam_frames_decoded": ("counter", ("stream",)),
    # drops carry where in the pipeline the frame died ("decode" vs
    # "downstream"); decode.py's plain per-stream drop counter omits it
    "evam_frames_dropped": ("counter", ("stream", "stage")),
    "evam_stream_errors": ("counter", ("stream",)),
    # pipeline stage clock + end-to-end latency
    "evam_stage_seconds": ("histogram", ("stage",)),
    "evam_frame_latency_seconds": ("histogram", ("class",)),
    # engine (batcher/supervisor) health
    "evam_step_seconds": ("histogram", ("engine",)),
    "evam_item_latency_seconds": ("histogram", ("engine",)),
    "evam_engine_stage_seconds": ("histogram", ("engine", "stage")),
    "evam_batch_occupancy": ("histogram", ("engine",)),
    "evam_engine_occupancy": ("gauge", ("engine",)),
    "evam_engine_unit_occupancy": ("gauge", ("engine",)),
    "evam_engine_queue_depth": ("gauge", ("engine",)),
    "evam_engine_queue_age_s": ("gauge", ("engine",)),
    "evam_engine_stalls": ("counter", ("engine",)),
    "evam_engine_state": ("gauge", ("engine",)),
    "evam_engine_restarts": ("counter", ("engine",)),
    "evam_engine_oversize_splits": ("counter", ("engine",)),
    # the generate engine (engine/generate.py): device steps by kind
    # (prefill | decode), what they carried and read, the wait for the
    # first prefill chunk, and what the engine holds
    "evam_generate_step_seconds": ("histogram", ("kind",)),
    "evam_generate_steps": ("counter", ("kind",)),
    # of the steps dispatched while the step before was still held in
    # flight, those that found it already ended: the device ran dry
    # before the dispatch (tracing on; counted at dispatch, the steps
    # above at harvest)
    "evam_generate_dry_dispatches": ("counter", ("kind",)),
    "evam_generate_tokens": ("counter", ("kind",)),
    # cache rows read, per layer THAT HAS a cache (every layer of
    # DeepSeek-V2, the attention layers of Jamba)
    "evam_generate_latent_rows_read": ("counter", ("kind",)),
    # a family with layers under a window (Laguna) only: of those rows,
    # per WINDOW layer, the ones inside the window (read) and the ones of
    # the same sequence behind it (not read); the two add up to the
    # counter above
    "evam_generate_window_rows_read": ("counter", ("kind",)),
    "evam_generate_window_rows_skipped": ("counter", ("kind",)),
    # a decode step (``kind="decode"`` alone), per row that carries a
    # sequence: of the pages its table names, those that hold a row of
    # the sequence and those wholly behind its rows; the two add up to
    # rows x the table's width. The second is what the decode kernel of
    # the plain key-value families neither fetches nor computes
    # (ops/pallas_attention.py ``decode_pages``; a window layer also
    # leaves out the pages wholly before its window, not counted here);
    # a family whose own rows are gathered through XLA reads them all
    "evam_generate_own_pages_read": ("counter", ("kind",)),
    "evam_generate_own_pages_skipped": ("counter", ("kind",)),
    # a chunk of a family whose chunks run the chunk kernel
    # (ops/pallas_attention.py): per kind of such layer (``layers``: mla |
    # attn | attn_full | attn_window) the (query block, key block) pairs
    # of one key-value head's grid, over the kind's layers, by ``class``:
    # whole (every live query row sees every key: no mask is computed),
    # none (no row sees any: not visited), mixed (the rule is
    # asked a score); counted on the host from the chunk's segments with
    # the kernel's own block sizes
    "evam_generate_chunk_key_blocks": ("counter", ("layers", "class")),
    # per-slot recurrent state (a family that keeps none counts 0): slot
    # states a step read and wrote (decode rows; a chunk's segments),
    # sequences started from the prefix snapshot, and the state's bytes
    "evam_generate_state_rows": ("counter", ("kind",)),
    "evam_generate_prefix_restores": ("counter", ()),
    # ONE name, two series (the registry keeps counters and gauges
    # apart): the gauge ``evam_generate_state_bytes``, what the state
    # holds, and the counter ``evam_generate_state_bytes_total{kind}``,
    # the slot state the steps MOVED (rows read and written x a row's
    # bytes over every layer, counted on the host); over
    # ``evam_generate_tokens_total{kind="decode"}`` the state a decoded
    # token costs
    "evam_generate_state_bytes": ("gauge", ("kind",)),
    # a family with latent attention (DeepSeek-V2, Kimi-Linear): the bytes
    # of the shared prefix's materialised heads, held beside the weights
    # for the prefill program (0 for every other family)
    "evam_generate_prefix_heads_bytes": ("gauge", ()),
    # of a decode step's rows read, those of the shared prefix: read
    # once a step for all its live rows (prefix rows x live rows)
    "evam_generate_decode_shared_rows": ("counter", ()),
    "evam_generate_queue_wait_seconds": ("histogram", ()),
    # submit -> a slot (the queue wait above runs on to the first chunk)
    "evam_generate_slot_wait_seconds": ("histogram", ()),
    # the slots an engine took of its ceiling (engine/generate.py
    # ``fit_slots``)
    "evam_generate_slots": ("gauge", ("engine",)),
    "evam_generate_slots_active": ("gauge", ()),
    "evam_generate_pages_in_use": ("gauge", ()),
    "evam_moe_held_assignments": ("counter", ()),
    # tokens x top_k a step and expert layer, counted on the host: every
    # assignment the router made, held here or not. Held over routed is
    # the share of the expert layer's sorted rows that carry work on this
    # chip (an eighth where an eighth of the experts is held and the
    # routing is even, all of them where every expert is)
    "evam_moe_routed_assignments": ("counter", ()),
    # per step and expert layer, the held experts that received at least
    # one assignment (the grouped products read only those experts'
    # weights): what a decode step's bytes follow
    "evam_moe_held_experts_hit": ("counter", ("kind",)),
    # per step and expert layer, the (row tile, expert) pairs ONE of the
    # three grouped products visits (ops/pallas_grouped.py): over the
    # series above, the times a hit expert's matrix is read a product
    "evam_moe_expert_reads": ("counter", ("kind",)),
    # QoS scheduling
    "evam_sched_admitted": ("counter", ("class",)),
    "evam_sched_rejected": ("counter", ("class",)),
    "evam_sched_shed": ("counter", ("class",)),
    # content-adaptive gating
    "evam_gate_ran": ("counter", ("engine",)),
    "evam_gate_skipped": ("counter", ("engine",)),
    # fleet
    "evam_fleet_rebalance_total": ("counter", ("engine",)),
    # persistent AOT executable cache (evam_tpu/aot/): confirmed
    # serves, misses by fallback-ladder rung (absent/version/crc/
    # deserialize/execute), and the on-disk store size after eviction
    "evam_aot_cache_hits": ("counter", ("engine",)),
    "evam_aot_cache_misses": ("counter", ("engine", "reason")),
    "evam_aot_cache_bytes": ("gauge", ()),
    # publishing + EII bridge
    "evam_publish_dropped": ("counter", ("dest",)),
    "evam_eii_published": ("counter", ()),
    "evam_eii_ingest_drops": ("counter", ()),
    # chaos / fault injection
    "evam_faults_injected": ("counter", ("kind",)),
    # crash-consistent stream state (evam_tpu/state/): migrations by
    # why the stream moved (shard_loss/engine_rebuild/scale_down/
    # drain/stale_refresh) and restore failures by degradation rung
    # (crc/version/timeout/apply/capture/double_fault)
    "evam_stream_migrations": ("counter", ("reason",)),
    "evam_ckpt_restore_failures": ("counter", ("reason",)),
    # per-frame tracing (obs/trace.py): tail-sampling retention split
    # by why a frame was kept (error/shed/deadline_miss/slow/sampled)
    # vs dropped, plus flight-recorder artifacts written per engine
    "evam_trace_retained": ("counter", ("reason",)),
    "evam_trace_dropped": ("counter", ()),
    "evam_flight_dumps": ("counter", ("engine",)),
    # the freeze recorder (obs/trace.py): how late the 250 ms heartbeat
    # woke, when that was >= 100 ms, and every garbage collection's
    # pause by generation
    "evam_freeze_seconds": ("histogram", ()),
    "evam_gc_pause_seconds": ("histogram", ("gen",)),
    # waits between the layers: a result resolved by the engine until
    # the stream's runner takes it, and a paced source's frame from its
    # due time until it is fed
    "evam_collect_wait_seconds": ("histogram", ()),
    "evam_source_lag_seconds": ("histogram", ()),
    # parked frames a stream's chain thread resumed, by what the one
    # in-order window would have done with the result at that moment:
    # "resolve" (left it lying: only the future's signal brought the
    # chain there), "feed" (found it: a frame was being fed, or a feed
    # stood blocked on a full window), "drain"
    "evam_runner_resumes": ("counter", ("by",)),
    # wall seconds of the dispatch/launch/complete threads by state
    # ("work", or the wait named by what it waits for) and CPU seconds
    # of the dispatcher's and completer's work stretches; the streams'
    # chain threads the same, summed over streams under engine
    # "streams", thread "chain" (states "work" and "wait_result")
    "evam_engine_thread_seconds": ("counter", ("engine", "thread", "state")),
    "evam_engine_thread_cpu_seconds": ("counter", ("engine", "thread")),
    # seconds in which a batch engine had no program on the device, by
    # where the batch that ended the stretch was (obs/trace.py
    # ``divide_idle``): "upstream" (not yet submitted), "queued" (in the
    # class queue: the deadline's fill, or the dispatcher busy), "stage"
    # (wait_staging, slot_write, seal), "upload" (h2d_issue,
    # wait_launcher, wait_slot, h2d_wait), "launch" (bookkeep, launch);
    # ``stage`` names the span. The five add up to the engine's idle
    "evam_engine_idle_seconds": ("counter", ("engine", "where", "stage")),
}


def _label_str(labels: dict[str, str] | None) -> str:
    if not labels:
        return ""
    if len(labels) == 1:  # most call sites: skip the sort and the join
        for k, v in labels.items():
            return f'{{{k}="{v}"}}'
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def _parse_labels(label_str: str) -> dict[str, str]:
    """Inverse of ``_label_str`` for the values it emits (no escaped
    quotes in our label values)."""
    import re

    return dict(re.findall(r'(\w+)="([^"]*)"', label_str))


@dataclass
class _Histogram:
    """Fixed-reservoir histogram good enough for p50/p99 reporting."""

    max_samples: int = 4096
    samples: list[float] = field(default_factory=list)
    count: int = 0
    total: float = 0.0
    #: bounded (value, exemplar) pairs — OpenMetrics exemplars linking
    #: an observation to a trace id; render() attaches the max-value
    #: pair to the p99 quantile line
    exemplars: deque = field(default_factory=lambda: deque(maxlen=8))

    def observe(self, value: float, exemplar: str | None = None) -> None:
        self.count += 1
        self.total += value
        if exemplar is not None:
            self.exemplars.append((value, exemplar))
        if len(self.samples) < self.max_samples:
            bisect.insort(self.samples, value)
        else:
            # Reservoir-style replacement keeps the histogram bounded.
            idx = self.count % self.max_samples
            self.samples.pop(idx)
            bisect.insort(self.samples, value)

    def quantile(self, q: float) -> float:
        if not self.samples:
            return 0.0
        idx = min(len(self.samples) - 1, int(q * len(self.samples)))
        return self.samples[idx]


class MetricsRegistry:
    """Counters, gauges and histograms with label support."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: dict[tuple[str, str], float] = defaultdict(float)
        self._gauges: dict[tuple[str, str], float] = {}
        self._hists: dict[tuple[str, str], _Histogram] = {}

    def inc(self, name: str, value: float = 1.0, labels: dict[str, str] | None = None) -> None:
        with self._lock:
            self._counters[(name, _label_str(labels))] += value

    def set(self, name: str, value: float, labels: dict[str, str] | None = None) -> None:
        with self._lock:
            self._gauges[(name, _label_str(labels))] = value

    def observe(self, name: str, value: float, labels: dict[str, str] | None = None,
                exemplar: str | None = None) -> None:
        with self._lock:
            key = (name, _label_str(labels))
            if key not in self._hists:
                self._hists[key] = _Histogram()
            self._hists[key].observe(value, exemplar)

    def declare(self, name: str, labels: dict[str, str] | None = None) -> None:
        """Make a histogram's series exist (count 0) before its first
        observation, so a reader of deltas can tell "nothing happened"
        from "this build has no such series"."""
        with self._lock:
            self._hists.setdefault((name, _label_str(labels)), _Histogram())

    def time(self, name: str, labels: dict[str, str] | None = None):
        """Context manager observing elapsed seconds into a histogram."""
        registry = self

        class _Timer:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                registry.observe(name, time.perf_counter() - self.t0, labels)
                return False

        return _Timer()

    def get_counter(self, name: str, labels: dict[str, str] | None = None) -> float:
        with self._lock:
            return self._counters.get((name, _label_str(labels)), 0.0)

    def counter_total(self, name: str) -> float:
        """Sum of one counter across ALL label sets (e.g. total
        evam_engine_restarts over every engine — the bench contract
        line and the chaos soak read it this way)."""
        with self._lock:
            return sum(v for (n, _), v in self._counters.items()
                       if n == name)

    def get_gauge(self, name: str, labels: dict[str, str] | None = None) -> float:
        with self._lock:
            return self._gauges.get((name, _label_str(labels)), 0.0)

    def quantile(self, name: str, q: float, labels: dict[str, str] | None = None) -> float:
        with self._lock:
            hist = self._hists.get((name, _label_str(labels)))
            return hist.quantile(q) if hist else 0.0

    def quantiles_by_label(self, name: str, q: float) -> dict[str, float]:
        """All labeled series of one histogram → {label_str: quantile}
        (the serve bench's per-stage latency decomposition)."""
        with self._lock:
            return {
                labels: hist.quantile(q)
                for (n, labels), hist in self._hists.items()
                if n == name
            }

    def quantiles_grouped(self, name: str, q: float,
                          group_by: str) -> dict[str, float]:
        """One histogram's series folded onto a SINGLE label key:
        {label_value: max quantile across the other labels}. The
        engine stage clock (evam_engine_stage_seconds{engine,stage})
        reports per stage this way — the slowest engine's stage cost
        is the one that bounds the serving path."""
        out: dict[str, float] = {}
        with self._lock:
            series = [
                (labels, hist.quantile(q))
                for (n, labels), hist in self._hists.items()
                if n == name
            ]
        for label_str, value in series:
            key = _parse_labels(label_str).get(group_by)
            if key is None:
                continue
            out[key] = max(out.get(key, 0.0), value)
        return out

    def render(self) -> str:
        """Prometheus text exposition format."""
        lines: list[str] = []
        with self._lock:
            for (name, labels), value in sorted(self._counters.items()):
                lines.append(f"{name}_total{labels} {value}")
            for (name, labels), value in sorted(self._gauges.items()):
                lines.append(f"{name}{labels} {value}")
            for (name, labels), hist in sorted(self._hists.items()):
                lines.append(f"{name}_count{labels} {hist.count}")
                lines.append(f"{name}_sum{labels} {hist.total}")
                for q in (0.5, 0.9, 0.99):
                    sub = labels[:-1] + "," if labels else "{"
                    line = f'{name}{sub}quantile="{q}"}} {hist.quantile(q)}'
                    if q == 0.99 and hist.exemplars:
                        # OpenMetrics exemplar: the slowest recorded
                        # observation names a concrete trace id —
                        # "what was my p99" becomes one /traces pull.
                        val, ex = max(hist.exemplars)
                        line += f' # {{trace_id="{ex}"}} {val}'
                    lines.append(line)
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()


#: Process-global registry used by all components.
metrics = MetricsRegistry()
