"""The generate engine: many device steps per request, state on the device
between them.

``BatchEngine`` (engine/batcher.py) serves one item by one program and
one future. A generation is a request that spans a prefill and tens of
decode steps, joins and leaves a running batch, and resolves ONE future
at its end; this engine serves those. The hub creates and supervises it
like any other (engine/hub.py ``generate_engine``), and it is listed on
``/engines``.

* ``slots`` sequence slots. A submitted request waits for a slot (the
  class's staleness budget applies to THIS wait and to nothing after
  it), then holds it until its last token. ``GenerateSizes.slots`` is the
  CEILING: the engine takes what the family's state leaves room for on its
  device, in whole rungs of the decode ladder (``fit_slots``: weights,
  the prefix's heads, a slot's state row and pages, against the device's
  memory limit less ``RESERVE_BYTES``). 128 for every family whose row is
  megabytes, 32 for Brumby's 170 MB a row.
* One thread runs device steps of FIXED shapes from a small set of
  programs, all compiled by ``warm_async``: ``decode`` over the running
  sequences, padded to a slot bucket, and ``prefill`` of one packed
  chunk of at most ``chunk_tokens`` prompt tokens of at most
  ``max_segments`` sequences (segment ids keep them apart; a prompt may
  continue in the next chunk, as its first segment). While sequences
  decode, prefill is PACED to the decoding it feeds (``next_step_kind``):
  every decode step earns the prompt tokens that its sequences, each
  replaced as it ends, will bring back over their decode steps, a full
  chunk runs when that credit covers it, and at most ``MAX_PREFILL_RUN``
  chunks run between two decode steps, which is also the most credit
  that is kept. So under load chunks run full and evenly spaced,
  generations that were submitted together do not stay together, and what
  the engine completes a second is even. A part-full chunk runs for
  prompts that a
  decode step passed over when the sequences in the engine would not
  fill it within ``PART_CHUNK_PATIENCE`` decode steps, or have not: a
  lone prompt waits one decode step, and no prompt more than that many.
* The model FAMILY is chosen at construction from the installed config's
  ``model_type`` (models/lm ``family``) and says what device state its
  sequences have (``state_shapes``): a pytree that both programs donate
  and update in place. ``pages`` is the cache of the layers that attend
  (engine/pages.py): latent rows in every layer of DeepSeek-V2 (576
  values stored 640 wide: whole lane tiles, so the donated array enters
  and leaves both programs where it lies), key and value rows in Jamba's
  two attention layers; ABSENT for a family none of whose layers keeps
  rows (Brumby: no page is pinned, allocated, written or counted, the
  prefix is the snapshot row alone). The pages of the shared
  instruction prefix are prefilled once in ``warm_async``, never written
  again, and a constant of both programs: a prefill chunk and a decode
  step each read them ONCE for all their rows. A decode row's page table
  holds the sequence's own pages and no others (``[bucket,
  private_pages]``), its context length counts own rows, and the step
  merges the two parts of each row's softmax (models/lm/common.py
  ``merge_softmax_sums``).
* A family with latent attention (DeepSeek-V2, Kimi-Linear) prefills over
  materialised heads (models/lm/mla.py), and the prefix's never change: the
  engine holds them (``prefix_heads``: per latent layer ``k_nope`` and
  ``v`` [heads, prefix rows, 128], 805 MB for DeepSeek-V2's six layers),
  fills them in warm-up as the prefix's chunks complete, by one small
  program run only there, and hands them to the prefill program beside the
  weights: an argument it reads and never writes, donates or copies. The
  decode program does not take them.
* A family with recurrent layers (Jamba's Mamba layers, Kimi-Linear's
  delta-rule layers) also keeps state per SLOT, never paged: arrays
  ``[layers, slots + 2, ...]`` that every decode step reads and writes at
  its rows' slots, in place (ops/slot_rows.py; two live rows of a step
  never name one slot). Row ``slots`` is the
  null row (rows of a step that carry no sequence), row ``slots + 1`` the
  PREFIX SNAPSHOT: the state after the shared prefix's last token, left
  there by warm-up's prefill of the prefix. A prefill chunk tells the
  program, per segment, the row its state starts from (the snapshot for
  a new sequence, the slot's own for a prompt that continues from the
  chunk before) and the row its end state goes to (the slot). A new
  sequence never starts from its slot, so a slot taken again carries
  nothing over. A family whose recurrence runs in blocks of tokens says so
  (``SEGMENT_ALIGN``): the packer starts every segment at a multiple of
  it, and the rows between are rows of no segment, as the chunk's tail.
* Sampling is greedy and there is no stop token: a request runs exactly
  ``max_new_tokens``, so the thread knows every step's make-up without
  reading a result. Each step's sampled ids stay on the device
  (``last_ids``, one per slot) for the next step, and its outputs are
  fetched one step LATE: the device always has the next step queued.
* ``cancel_stream`` (a stream's DELETE) and ``stop`` resolve the
  stream's futures with ``None`` and free its slots and pages at the
  next step.

A future resolves with ``{"ids", "top_ids", "top_logits",
"prefix_tokens"}``: the generated ids and, per generated token, the 8
largest logits with their ids.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from collections import deque
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from evam_tpu.engine.batcher import EngineStats
from evam_tpu.engine.pages import PagePool
from evam_tpu.models.lm import family
from evam_tpu.obs import get_logger, metrics
from evam_tpu.obs import trace
from evam_tpu.ops.pallas_attention import CLASSES, count_classes
from evam_tpu.sched.classes import DEFAULT_PRIORITY, PRIORITIES, SchedConfig
from evam_tpu.sched.shedder import Shedder

log = get_logger("engine.generate")

#: prefill chunks that may run between two decode steps, and the chunks of
#: prefill credit that decode steps can save up. With one (strict
#: alternation) a chunk ran as soon as one prompt waited, part-empty at the
#: price of a full one; with two a chunk can wait until it is full. It
#: does NOT fill the slots: the runner decides how many generations reach
#: the engine (PERF.md section 6, PR 28).
MAX_PREFILL_RUN = 2

#: decode steps that waiting prompts let pass for their chunk to fill, if
#: the sequences in the engine can fill it in as many. Until PR 42 it was
#: one, and a full chunk ran at once: under a closed loop of equal
#: generations the bursts the streams had started in never dispersed, and
#: 11-14 % of a chunk ran empty, more or less by their phases (PERF.md
#: section 6, PR 42). With few sequences it is one still.
PART_CHUNK_PATIENCE = 12

#: decode programs: one every ``slots / DECODE_LADDER`` rows, up to the
#: slots. A decode step costs about 7 ms + 0.2 ms a row of its BUCKET on
#: a v5e, and the rows in service swing with the streams' phases: with
#: 32/64/128 a step from 64 to 128 rows made the rate follow that swing
#: (PERF.md section 6, PR 28).
DECODE_LADDER = 8

#: what the engine leaves of its device's memory limit to everything it
#: does not reckon itself. Two parts, read on a v5e (16.9 GB reported):
#: the detector's engine, both step programs' temporaries and the runtime's
#: own, which took 1.6-1.9 GB beside the six models' weights, state and
#: pages (``memory_peak_bytes`` less ``fit_slots``' sum: PERF.md section 4);
#: and the 2.4 GB that every configuration's fallback line (a peak of
#: 14.5 GB) keeps free for what a run allocates late.
RESERVE_BYTES = 4_300_000_000


def fit_slots(ceiling: int, limit: int | None, fixed: int,
              per_slot: int) -> int:
    """The slots an engine takes: ``ceiling``, or where the device reports
    a memory ``limit`` that it does not leave room for, the most that
    ``fixed`` bytes (weights, the prefix's heads, the state's two spare
    rows, the null and the prefix's pages) and ``per_slot`` bytes a slot
    (its state row and its own pages) fit under ``limit - RESERVE_BYTES``,
    in whole rungs of the decode ladder."""
    if limit is None or not per_slot:
        return ceiling
    fit = (limit - RESERVE_BYTES - fixed) // per_slot
    fit = min(ceiling, fit // DECODE_LADDER * DECODE_LADDER)
    if fit < min(ceiling, DECODE_LADDER):
        raise ValueError(
            f"{fixed / 1e9:.2f} GB of weights and {per_slot / 1e6:.1f} MB a "
            f"slot leave no room for {DECODE_LADDER} slots under "
            f"{limit / 1e9:.2f} GB less {RESERVE_BYTES / 1e9:.1f} GB")
    return fit


def next_step_kind(waiting: int, decoding: bool, prefill_run: int,
                   passed_over: int, chunk_tokens: int,
                   credit: float = float("inf"),
                   owed: float = 0.0) -> str | None:
    """The engine thread's next step. ``waiting``: prompt tokens not yet
    prefilled; ``prefill_run``: prefill steps since the last decode step;
    ``passed_over``: decode steps that ran since while prompts waited;
    ``owed``: the prompt tokens a decode step earns (over the sequences in
    the engine, each one's prompt over its decode steps: what they bring
    back a step when each is replaced as it ends); ``credit``: what the
    decode steps have earned and no chunk has spent. Decode, unless
    prompts wait and nothing decodes, or fewer than ``MAX_PREFILL_RUN``
    chunks ran since the last decode step and either the prompts fill a
    chunk that the credit covers, or they were passed over and a full
    chunk is more than ``PART_CHUNK_PATIENCE`` decode steps away or that
    many have passed."""
    if not waiting:
        return "decode" if decoding else None
    if not decoding:
        return "prefill"
    if prefill_run >= MAX_PREFILL_RUN:
        return "decode"
    if waiting >= chunk_tokens:
        return "prefill" if credit >= chunk_tokens else "decode"
    if passed_over and (passed_over >= PART_CHUNK_PATIENCE
                        or chunk_tokens - waiting
                        > PART_CHUNK_PATIENCE * owed):
        return "prefill"
    return "decode"


@dataclasses.dataclass
class PrefillPace:
    """What the engine thread keeps between steps for ``next_step_kind``:
    prefill steps since the last decode step, the decode steps since that
    passed waiting prompts over, and the prompt tokens that decode steps
    have earned and no chunk has spent (at most ``MAX_PREFILL_RUN``
    chunks' worth, which is where it starts)."""

    chunk_tokens: int
    prefill_run: int = 0
    passed_over: int = 0
    credit: float = dataclasses.field(init=False)

    def __post_init__(self) -> None:
        self.credit = float(MAX_PREFILL_RUN * self.chunk_tokens)

    def next(self, waiting: int, decoding: bool, owed: float) -> str | None:
        return next_step_kind(waiting, decoding, self.prefill_run,
                              self.passed_over, self.chunk_tokens,
                              self.credit, owed)

    def ran(self, kind: str, tokens: int, waiting: bool,
            owed: float) -> None:
        """A step of ``kind`` was dispatched: a chunk of ``tokens`` prompt
        tokens, or a decode step that left prompts ``waiting`` or none and
        earned ``owed``."""
        if kind == "prefill":
            self.prefill_run += 1
            self.passed_over = 0
            self.credit = max(0.0, self.credit - tokens)
        else:
            self.prefill_run = 0
            self.passed_over = self.passed_over + 1 if waiting else 0
            self.credit = min(float(MAX_PREFILL_RUN * self.chunk_tokens),
                              self.credit + owed)


@dataclasses.dataclass(frozen=True)
class GenerateSizes:
    """The engine's fixed shapes (config/settings.py ``LMSettings``:
    the defaults are the deployment's, a rehearsal sets tiny ones).
    ``slots`` is the CEILING an engine is handed; the sizes it serves with
    hold the slots it took (``fit_slots``). The decode ladder follows from
    them."""

    slots: int = 128
    page_tokens: int = 128
    chunk_tokens: int = 512
    max_segments: int = 8
    #: tokens a sequence may add to the prefix: prompt + generated
    private_tokens: int = 384

    @property
    def slot_buckets(self) -> tuple[int, ...]:
        step = max(1, self.slots // DECODE_LADDER)
        return tuple(range(step, self.slots, step)) + (self.slots,)

    @classmethod
    def from_settings(cls, lm) -> "GenerateSizes":
        return cls(slots=lm.slots, page_tokens=lm.page_tokens,
                   chunk_tokens=lm.chunk_tokens,
                   max_segments=lm.max_segments,
                   private_tokens=lm.private_tokens)


class _Seq:
    """One request from submit to its future's result."""

    __slots__ = ("prompt", "max_new", "stream", "priority", "trace",
                 "future", "t_submit", "t_slot", "slot", "pages",
                 "n_prefilled", "n_gen", "ids", "top_ids", "top_logits",
                 "t_first", "t_prefilled", "cancelled")

    def __init__(self, prompt, max_new, stream, priority, ftrace):
        self.prompt = prompt
        self.max_new = max_new
        self.stream = stream
        self.priority = priority
        self.trace = ftrace
        self.future: Future = Future()
        self.t_submit = time.perf_counter()
        self.t_slot: float | None = None
        self.slot = -1
        self.pages: list[int] = []
        #: prompt tokens / generated tokens whose step has been dispatched
        self.n_prefilled = 0
        self.n_gen = 0
        self.ids: list[int] = []
        self.top_ids: list[list[int]] = []
        self.top_logits: list[list[float]] = []
        self.t_first: float | None = None
        self.t_prefilled: float | None = None
        self.cancelled = False


@dataclasses.dataclass
class _Step:
    """A dispatched step whose outputs are still on the device."""

    kind: str
    key: str
    t_dispatch: float
    tokens: int
    rows_read: int
    #: of ``rows_read``, the rows a layer under the family's window read
    #: (all of them for a family without one)
    window_read: int
    #: slot states the step read and wrote (decode rows; a chunk's
    #: segments), and of a chunk's segments those begun from the prefix
    #: snapshot; both 0 for a family that keeps no slot state
    state_rows: int
    restores: int
    #: a decode step: of the pages its sequences' tables name, those that
    #: hold a row of the sequence, and those wholly behind its rows
    own_pages: tuple[int, int]
    #: a chunk of a family whose chunks run the chunk kernel: per kind of
    #: such layer ``(its name, (mixed, whole, not visited))``, the (query
    #: block, key block) pairs of one key-value head's grid over the
    #: kind's layers, by class (ops/pallas_attention.py ``block_classes``)
    key_blocks: list
    #: (row of the outputs, sequence) for every token this step sampled
    takers: list
    top: jax.Array
    ids: jax.Array
    held: jax.Array


def _nbytes(shapes) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))


@functools.lru_cache(maxsize=1024)
def _key_blocks(lm, cfg, seg: bytes, n_prefix: int, n_cont: int,
                prefix_pages: int, cont_pages: int, page_tokens: int) -> list:
    """``_Step.key_blocks`` of a chunk whose segments are ``seg`` (int32):
    the family's ``chunk_key_blocks`` counted by class over each kind's
    layers; none for a family whose chunks do not run the kernel. Kept a
    layout: prompts of a few lengths pack into a few hundred layouts, and
    a new one costs the engine's thread 0.3-0.9 ms of numpy."""
    classes = getattr(lm, "chunk_key_blocks", None)
    return [(name, tuple(layers * k for k in count_classes(pairs)))
            for name, layers, pairs in classes(
                cfg, np.frombuffer(seg, np.int32), n_prefix, n_cont,
                prefix_pages, cont_pages, page_tokens)] if classes else []


class GenerateEngine:
    #: what the hub's rows read of any engine
    ragged = "off"
    #: whether the family keeps cache rows in pages (every family but one
    #: whose ``state_shapes`` has no ``pages``: set in ``__init__``)
    _paged = True

    def __init__(self, name: str, model_cfg: dict, prefix_ids,
                 sizes: GenerateSizes | None = None, plan=None,
                 sched: SchedConfig | None = None,
                 stall_timeout_s: float = 120.0,
                 first_batch_grace: float = 10.0,
                 memory_limit: int | None = None):
        """``memory_limit``: the device's, for a test; None asks the
        device (a CPU reports none: the ceiling)."""
        self.name = name
        self._lm = family(model_cfg["model_type"])
        self.cfg = self._lm.Config.from_dict(model_cfg)
        sz = sizes or GenerateSizes()
        #: what the engine was handed; ``sizes.slots`` is what it took
        self.slots_ceiling = sz.slots
        #: a chunk's segments start at multiples of this many tokens
        self._align = self._lm.SEGMENT_ALIGN
        #: the positions a window layer sees (None: every layer of the
        #: family sees everything earlier)
        self._window = getattr(self.cfg, "window", None)
        #: assignments a live token is ROUTED, summed over the expert
        #: layers (held or not: ``top_k`` a layer; 0 for a family without
        #: experts)
        self._routed_per_token = (len(getattr(self.cfg, "moe_ids", ()))
                                  * getattr(self.cfg, "top_k", 0))
        if sz.chunk_tokens % (self._align * sz.max_segments):
            raise ValueError(
                f"a chunk of {sz.chunk_tokens} tokens is not "
                f"{sz.max_segments} segments of whole blocks of "
                f"{self._align}")
        self.stall_timeout_s = stall_timeout_s
        self.first_batch_grace = first_batch_grace
        prefix = np.asarray(prefix_ids, np.int32)
        if len(prefix) % sz.page_tokens:
            raise ValueError(
                f"the shared prefix ({len(prefix)} tokens) must fill whole "
                f"pages of {sz.page_tokens}")
        if len(prefix) and (prefix.min() < 0 or prefix.max() >= self.cfg.vocab):
            raise ValueError("prefix ids outside the held vocabulary")
        self.prefix = prefix
        self._device = (plan.mesh.devices.flat[0] if plan is not None
                        else jax.devices()[0])
        #: a latent family's: per latent layer the shapes of the prefix's
        #: heads, which the prefill program takes beside the weights
        heads = getattr(self._lm, "prefix_heads_shapes", None)
        self._heads_shapes = (heads(self.cfg, len(prefix))
                              if heads is not None and len(prefix) else ())
        self._heads_bytes = _nbytes(self._heads_shapes)
        self._private_pages = -(-sz.private_tokens // sz.page_tokens)
        #: a family none of whose layers keeps rows has no ``pages``: no
        #: page is pinned, allocated, written or counted
        self._paged = "pages" in self._lm.state_shapes(
            self.cfg, 1, sz.page_tokens, 0)
        self._prefix_pages = (len(prefix) // sz.page_tokens
                              if self._paged else 0)

        def n_pages(slots: int) -> int:
            return 1 + self._prefix_pages + (
                slots * self._private_pages if self._paged else 0)

        def held(slots: int) -> int:
            return _nbytes(self._lm.state_shapes(
                self.cfg, n_pages(slots), sz.page_tokens, slots))

        if memory_limit is None:
            memory_limit = (self._device.memory_stats() or {}).get(
                "bytes_limit")
        self._memory_limit = memory_limit
        self.sizes = sz = dataclasses.replace(sz, slots=fit_slots(
            sz.slots, memory_limit,
            2 * self._lm.param_count(self.cfg) + self._heads_bytes + held(0),
            held(1) - held(0)))
        self.buckets = list(sz.slot_buckets)
        self._pool = PagePool(n_pages(sz.slots), sz.page_tokens)
        self._shared = self._pool.pin(self._prefix_pages)
        self._state_shapes = self._lm.state_shapes(
            self.cfg, self._pool.n_pages, sz.page_tokens, sz.slots)
        #: bytes of what the family keeps per slot (0: pages alone)
        self._state_bytes = _nbytes({
            k: a for k, a in self._state_shapes.items() if k != "pages"})
        #: of which one slot's row
        self._row_bytes = self._state_bytes // (sz.slots + 2)
        self.stats = EngineStats()
        self.warmed = threading.Event()
        self.warm_error: str | None = None
        self.stalled = threading.Event()
        self._stop = threading.Event()
        self._shedder = (Shedder(name, sched.staleness_s())
                         if sched is not None else None)
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        #: under ``_lock``: submitted, not yet admitted to a slot
        self._pending: dict[str, deque[_Seq]] = {
            c: deque() for c in PRIORITIES}
        self._cancel_streams: set[str] = set()
        #: the engine thread's own
        self._free_slots = list(range(sz.slots - 1, -1, -1))
        self._prefilling: deque[_Seq] = deque()
        self._decoding: list[_Seq] = []
        self._inflight: deque[_Step] = deque()
        self._pace = PrefillPace(sz.chunk_tokens)
        self._t_free = time.perf_counter()
        self._step_started: float | None = None
        self._seen: set[str] = set()
        #: per program: seconds of its last warm run (the capacity model)
        self._program_s: dict[str, float] = {}
        self._mean_prompt = 0.0
        self._mean_new = 0.0
        self._done = 0
        self._outstanding: dict[int, _Seq] = {}
        self._spans = trace.thread_spans(name, "generate")
        #: whether dispatches onto a dry device are counted (tracing on)
        self._count_dry = trace.active() is not None
        trace.watch_engine(self)
        self._params = None
        self._state = None
        self._last_ids = None
        self._prefix_heads = ()
        self._build_programs()
        self._thread = threading.Thread(
            target=self._loop, name=f"engine-{name}-generate", daemon=True)
        #: the supervisor's liveness checks name these three
        self._dispatcher = self._launcher = self._completer = self._thread
        self._thread.start()
        self._warm_thread: threading.Thread | None = None
        self._set_gauges()

    # ------------------------------------------------------------ programs

    def _build_programs(self) -> None:
        lm, cfg, sz = self._lm, self.cfg, self.sizes
        shared = (np.asarray(self._shared, np.int32)
                  if self._prefix_pages else None)
        n_cont = self._private_pages
        n_seg = sz.max_segments
        # decoding starts after warm-up: the whole prefix is there
        n_prefix_rows = len(self.prefix)

        with_heads = bool(self._heads_shapes)

        def prefill(params, state, last_ids, heads, mat, aux):
            tokens, seg, pos, dest_page, dest_off = mat
            cont = aux[:n_cont]
            n_prefix, n_cont_rows = aux[n_cont], aux[n_cont + 1]
            last_idx, last_slot, seg_from, seg_to = (
                aux[n_cont + 2:].reshape(4, n_seg))
            state, top, ids, held = lm.prefill_chunk(
                cfg, params, state, tokens, seg, pos, dest_page, dest_off,
                shared, n_prefix, cont, n_cont_rows, last_idx, seg_from,
                seg_to, **({"prefix_heads": heads} if with_heads else {}))
            return (state, last_ids.at[last_slot].set(ids[:, 0]), top, ids,
                    held)

        def decode(params, state, last_ids, mat, page_table):
            slot, pos, ctx_len, dest_page, dest_off, live = mat
            state, top, ids, held = lm.decode_tokens(
                cfg, params, state, last_ids[slot], pos, page_table, ctx_len,
                dest_page, dest_off, live > 0, shared, n_prefix_rows, slot)
            return state, last_ids.at[slot].set(ids[:, 0]), top, ids, held

        self._prefill = jax.jit(prefill, donate_argnums=(1, 2))
        self._decode = jax.jit(decode, donate_argnums=(1, 2))
        #: the prefix's heads from its cached rows as they lie, written
        #: over the held ones (donated and not read: the one buffer)
        self._expand = jax.jit(
            lambda params, state, old: lm.prefix_heads(
                cfg, params, state, shared),
            donate_argnums=2, keep_unused=True) if with_heads else None

    def _allocate(self) -> None:
        """Weights, the family's state (all zero: the snapshot row is
        the state before any token until warm-up has run the prefix) and
        the per-slot last ids, on the device."""
        lm, cfg, sz = self._lm, self.cfg, self.sizes
        with jax.default_device(self._device):
            t0 = time.perf_counter()
            self._params = lm.make_params(cfg)
            self._state = {k: jnp.zeros(a.shape, a.dtype)
                           for k, a in self._state_shapes.items()}
            #: one more than the slots: rows that carry no sequence. A
            #: slot's id is set by its sequence's prefill; until then
            #: the ids differ, for the warm-up's loaded steps
            self._last_ids = jnp.arange(
                sz.slots + 1, dtype=jnp.int32) % cfg.vocab
            self._prefix_heads = jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), self._heads_shapes)
            jax.block_until_ready(self._params)
        log.info(
            "engine %s: %d slots of a ceiling of %d under a limit of %s bytes, "
            "%.2f G parameters, %s on %s in %.1f s", self.name, sz.slots,
            self.slots_ceiling, self._memory_limit,
            lm.param_count(cfg) / 1e9,
            ", ".join(f"{k} {'x'.join(map(str, a.shape))}"
                      for k, a in self._state_shapes.items()),
            self._device, time.perf_counter() - t0)

    # ---------------------------------------------------------------- API

    def submit(self, priority: str = DEFAULT_PRIORITY,
               units: int | None = None, stream: str | None = None,
               trace: "object | None" = None, *, prompt_ids,
               max_new_tokens: int) -> Future:
        """Queue one generation; the future resolves with the result
        dict, with ``None`` if the stream was cancelled, or raises
        ``ShedError`` if the wait for a slot outlasted the class's
        staleness budget."""
        if self._stop.is_set():
            raise RuntimeError(f"engine {self.name} is stopped")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        n, sz = len(prompt), self.sizes
        if not 1 <= n or max_new_tokens < 1:
            raise ValueError("a generation needs a prompt and a length")
        if n + max_new_tokens - 1 > sz.private_tokens:
            raise ValueError(
                f"{n} prompt + {max_new_tokens} new tokens do not fit the "
                f"{sz.private_tokens} a sequence may add to the prefix")
        if prompt.min() < 0 or prompt.max() >= self.cfg.vocab:
            raise ValueError("prompt ids outside the held vocabulary")
        seq = _Seq(prompt, int(max_new_tokens), stream,
                   priority if priority in PRIORITIES else DEFAULT_PRIORITY,
                   trace)
        with self._wake:
            self._pending[seq.priority].append(seq)
            self._outstanding[id(seq)] = seq
            self._wake.notify()
        return seq.future

    def cancel_stream(self, stream: str) -> None:
        """Drop every sequence of ``stream`` at the next step: futures
        resolve with None, slots and pages come back."""
        with self._wake:
            self._cancel_streams.add(stream)
            self._wake.notify()

    def warm_async(self, **_example) -> None:
        """Allocate, compile every program, prefill the shared prefix,
        then serve. ``warmed`` is set when all of it is done."""
        if self._warm_thread is not None:
            return
        self._warm_thread = threading.Thread(
            target=self._warm_guarded, name=f"engine-{self.name}-warm",
            daemon=True)
        self._warm_thread.start()

    def set_example(self, **_example) -> None:
        """No background warm-up was asked for: the first submit pays
        it (tests)."""
        self.warm_async()

    def _warm_guarded(self) -> None:
        try:
            self._warm()
        except Exception as exc:  # noqa: BLE001 - reported on /engines
            log.exception("engine %s warm-up failed", self.name)
            self.warm_error = f"{type(exc).__name__}: {exc}"
        finally:
            self.warmed.set()

    def _warm(self) -> None:
        sz = self.sizes
        self._allocate()
        # the prefix, through the prefill program itself: chunk i attends
        # to the prefix rows the chunks before it wrote, and carries the
        # slot state on in the snapshot row
        t0 = time.perf_counter()
        snapshot = [(0, sz.slots, sz.slots + 1, sz.slots + 1)]
        flat = [p * sz.page_tokens + o for p in self._shared
                for o in range(sz.page_tokens)]
        for lo in range(0, len(self.prefix), sz.chunk_tokens):
            part = self.prefix[lo:lo + sz.chunk_tokens]
            dest = flat[lo:lo + len(part)] or [0] * len(part)
            self._harvest(self._dispatch_prefill_raw(
                part, np.zeros(len(part), np.int32),
                np.arange(lo, lo + len(part)), dest, n_prefix=lo,
                cont=None, n_cont=0, segs=snapshot, takers=[]), count=False)
            if self._expand is not None:
                # the next chunk attends to these rows' heads
                self._prefix_heads = self._expand(
                    self._params, self._state, self._prefix_heads)
        log.info("engine %s: shared prefix of %d tokens prefilled in %.1f s",
                 self.name, len(self.prefix), time.perf_counter() - t0)
        # every program LOADED as in service: every row a token of its
        # own (``_allocate``'s last ids: the held experts are reached),
        # written to the null page; a chunk's segments start from the
        # snapshot and end in the null row. Once to compile, twice more
        # for the capacity model's step times, of which the lesser counts:
        # ``decode:<slots>`` may never run in service, so one run held up
        # here (another engine warms beside this one) would stand for
        # good, and admission refuse streams the engine has room for.
        n = sz.chunk_tokens
        per = -(-n // sz.max_segments)
        chunk = (np.arange(n) % self.cfg.vocab, np.arange(n) // per,
                 len(self.prefix) + np.arange(n) % per,
                 np.arange(n) % sz.page_tokens)
        # a decode row at its longest: the kernel that walks a row's own
        # pages copies and computes those that hold its rows, so a row of
        # one token would be timed at a third of a row in service
        last = sz.private_tokens - 1
        steps = [(f"decode:{b}", lambda b=b: self._dispatch_decode_raw(
            [(slot, last, [0] * self._private_pages) for slot in range(b)],
            b, [])) for b in self.buckets]
        steps.append(("prefill", lambda: self._dispatch_prefill_raw(
            *chunk, len(self.prefix), None, 0, [], [])))
        for run in range(3):
            for key, dispatch in steps:
                t0 = time.perf_counter()
                self._harvest(dispatch(), count=False)
                took = time.perf_counter() - t0
                if run:
                    self._program_s[key] = min(
                        self._program_s.get(key, took), took)

    def stop(self) -> None:
        self._stop.set()
        with self._wake:
            self._wake.notify()
        if self._thread.is_alive():
            self._thread.join(timeout=30)
        self._fail_all(None)

    def abandon(self) -> None:
        """The supervisor's quarantine: resolve what can be resolved,
        join nothing."""
        self._stop.set()
        with self._wake:
            self._wake.notify()
        self._fail_all(TimeoutError(
            f"engine {self.name} was abandoned after a stall"))

    def _fail_all(self, exc: Exception | None) -> None:
        with self._lock:
            seqs = list(self._outstanding.values())
            self._outstanding.clear()
        for seq in seqs:
            if seq.future.done():
                continue
            try:
                if exc is None:
                    seq.future.set_result(None)
                else:
                    seq.future.set_exception(exc)
            except Exception:  # noqa: BLE001 - resolved meanwhile
                pass

    # ------------------------------------------------- the hub's row reads

    def queue_depth(self) -> int:
        with self._lock:
            return sum(len(q) for q in self._pending.values())

    def queue_age_s(self) -> float:
        with self._lock:
            heads = [q[0].t_submit for q in self._pending.values() if q]
        return time.perf_counter() - min(heads) if heads else 0.0

    def class_depths(self) -> dict[str, int]:
        with self._lock:
            return {c: len(q) for c, q in self._pending.items()}

    def shed_counts(self) -> dict[str, int]:
        if self._shedder is None:
            return {c: 0 for c in PRIORITIES}
        return dict(self._shedder.counts)

    def thread_states(self) -> dict[str, tuple]:
        return {"generate": self._spans.where()}

    def refresh_queue_gauges(self) -> None:
        """The supervisor's 0.1 s poll: the backlog gauges, and the
        stall check (a step that has not come back in
        ``stall_timeout_s``, times ``first_batch_grace`` while its
        program may still be compiling)."""
        metrics.set("evam_engine_queue_depth", self.queue_depth(),
                    {"engine": self.name})
        metrics.set("evam_engine_queue_age_s", self.queue_age_s(),
                    {"engine": self.name})
        started = self._step_started
        if started is None or self.stalled.is_set():
            return
        limit = self.stall_timeout_s * (
            1.0 if self.warmed.is_set() else self.first_batch_grace)
        if time.perf_counter() - started > limit:
            log.error("engine %s: a step has not returned in %.0f s",
                      self.name, limit)
            self.stalled.set()

    def capacity_fps(self) -> float:
        """Generations a second with every slot busy, from the requests
        seen and two step times: a generation costs its prompt's share
        of a full prefill chunk and, per new token, one row of the
        decode step over all slots. The times are those of loaded steps
        (``_warm``), moved on by every step served since. 0 before the
        first request ends (cold: admission admits)."""
        t_decode = self._program_s.get(f"decode:{self.sizes.slots}")
        if not self._done or t_decode is None:
            return 0.0
        sz = self.sizes
        per = (self._mean_prompt / sz.chunk_tokens * self._program_s["prefill"]
               + (self._mean_new - 1) * t_decode / sz.slots)
        return 1.0 / per if per > 0 else 0.0

    def pages_in_use(self) -> tuple[int, int]:
        return self._pool.in_use, self._pool.capacity

    def state_slots(self) -> tuple[int, int, int]:
        """(in use, slots, bytes) of the per-slot state; all 0 for a
        family that keeps none."""
        if not self._state_bytes:
            return 0, 0, 0
        sz = self.sizes
        return sz.slots - len(self._free_slots), sz.slots, self._state_bytes

    def slots(self) -> tuple[int, int]:
        """(the slots the engine took, the ceiling it was handed)."""
        return self.sizes.slots, self.slots_ceiling

    def prefix_heads_bytes(self) -> int:
        """Bytes of the shared prefix's heads held beside the weights (0
        for a family without latent attention, or without a prefix)."""
        return self._heads_bytes

    # --------------------------------------------------------- the thread

    def _loop(self) -> None:
        spans = self._spans
        while not self.warmed.wait(0.05):
            if self._stop.is_set():
                return
        if self.warm_error is not None:
            return  # nothing to serve with: the supervisor sees it
        if self._count_dry:
            for kind in ("prefill", "decode"):  # none yet is a reading
                metrics.inc("evam_generate_dry_dispatches", 0.0,
                            {"kind": kind})
        try:
            while not self._stop.is_set():
                self._take_cancels()
                self._admit()
                kind = self._next_kind()
                if kind is None:
                    if self._inflight:
                        self._harvest(self._inflight.popleft())
                        continue
                    spans.to("wait_requests")
                    with self._wake:
                        if not self._has_pending() and not self._stop.is_set():
                            self._wake.wait(0.05)
                    continue
                spans.to("step")
                step = (self._dispatch_prefill() if kind == "prefill"
                        else self._dispatch_decode())
                self._pace.ran(kind, step.tokens, bool(self._prefilling),
                               self._owed())
                self._inflight.append(step)
                while len(self._inflight) > 1:
                    self._harvest(self._inflight.popleft())
        except Exception:  # noqa: BLE001 - the supervisor sees a dead thread
            log.exception("engine %s: the generate thread died", self.name)
            self._fail_all(RuntimeError(
                f"engine {self.name}: the generate thread died"))
        finally:
            spans.to(None)

    def _has_pending(self) -> bool:
        return any(self._pending.values()) or bool(self._cancel_streams)

    def _owed(self) -> float:
        """Prompt tokens a decode step earns: each sequence in the engine
        brings its prompt back once in the decode steps it rides."""
        return sum(len(s.prompt) / max(1, s.max_new - 1)
                   for s in (*self._prefilling, *self._decoding))

    def _next_kind(self) -> str | None:
        return self._pace.next(
            sum(len(s.prompt) - s.n_prefilled for s in self._prefilling),
            bool(self._decoding), self._owed())

    def _take_cancels(self) -> None:
        with self._lock:
            if not self._cancel_streams:
                return
            streams, self._cancel_streams = self._cancel_streams, set()
            dropped = []
            for q in self._pending.values():
                keep = [s for s in q if s.stream not in streams]
                dropped += [s for s in q if s.stream in streams]
                q.clear()
                q.extend(keep)
        for seq in (*self._prefilling, *self._decoding):
            if seq.stream in streams:
                dropped.append(seq)
                self._release(seq)
        self._prefilling = deque(
            s for s in self._prefilling if s.stream not in streams)
        self._decoding = [s for s in self._decoding
                          if s.stream not in streams]
        for seq in dropped:
            seq.cancelled = True
            self._resolve(seq, None)
        self._set_gauges()

    def _admit(self) -> None:
        """Waiting requests into free slots, the better class first;
        those that waited past their class's budget are shed."""
        with self._lock:
            for cls in PRIORITIES:
                q = self._pending[cls]
                if q and self._shedder is not None:
                    fresh = self._shedder.shed(cls, list(q))
                    if len(fresh) != len(q):
                        for seq in set(q) - set(fresh):
                            self._outstanding.pop(id(seq), None)
                        q.clear()
                        q.extend(fresh)
                while q and self._free_slots:
                    seq = q[0]
                    pages = self._pool.alloc(self._pool.pages_for(
                        len(seq.prompt) + seq.max_new - 1)
                        if self._paged else 0)
                    if pages is None:
                        return
                    q.popleft()
                    seq.slot = self._free_slots.pop()
                    seq.pages = pages
                    seq.t_slot = time.perf_counter()
                    metrics.observe("evam_generate_slot_wait_seconds",
                                    seq.t_slot - seq.t_submit)
                    self._prefilling.append(seq)
        self._set_gauges()

    def _release(self, seq: _Seq) -> None:
        """The sequence's slot and pages, back. Steps already queued on
        the device run before whatever uses them next."""
        if seq.slot >= 0:
            self._free_slots.append(seq.slot)
            self._pool.free(seq.pages)
            seq.slot, seq.pages = -1, []

    def _set_gauges(self) -> None:
        metrics.set("evam_generate_slots_active",
                    self.sizes.slots - len(self._free_slots))
        metrics.set("evam_generate_pages_in_use", self._pool.in_use)
        metrics.set("evam_generate_slots", self.sizes.slots,
                    {"engine": self.name})
        metrics.set("evam_generate_state_bytes", self._state_bytes)
        metrics.set("evam_generate_prefix_heads_bytes", self._heads_bytes)

    # ------------------------------------------------------------ dispatch

    def _where(self, pages: list[int], k: int) -> int:
        """Flat cache row (page * page_tokens + offset) of the ``k``-th
        own token of the sequence that holds ``pages`` (the null page's
        first for a family without pages)."""
        pt = self.sizes.page_tokens
        return pages[k // pt] * pt + k % pt if self._paged else 0

    def _dispatch_prefill(self) -> _Step:
        """Pack prompt tokens of the waiting sequences, in order, into
        one chunk. Only the chunk's first segment may continue a prompt
        begun in an earlier chunk."""
        sz = self.sizes
        tokens, seg, pos, dest, segs, takers = [], [], [], [], [], []
        cont, n_cont = None, 0
        now = time.perf_counter()
        while (self._prefilling and len(segs) < sz.max_segments):
            # rows of no segment up to the next aligned start
            pad = -len(tokens) % self._align
            if len(tokens) + pad >= sz.chunk_tokens:
                break
            tokens += [0] * pad
            seg += [-1] * pad
            pos += [0] * pad
            dest += [0] * pad
            seq = self._prefilling[0]
            s = len(segs)
            # a new sequence starts from the prefix snapshot, one that
            # continues from its slot; either leaves its state there
            rows = (seq.slot if seq.n_prefilled else sz.slots + 1, seq.slot)
            if seq.n_prefilled and s:
                break  # a continued prompt opens its own chunk
            if seq.t_first is None:
                seq.t_first = now
                metrics.observe("evam_generate_queue_wait_seconds",
                                now - seq.t_submit)
            if seq.n_prefilled:
                cont, n_cont = seq.pages, seq.n_prefilled
            take = min(len(seq.prompt) - seq.n_prefilled,
                       sz.chunk_tokens - len(tokens))
            for k in range(seq.n_prefilled, seq.n_prefilled + take):
                tokens.append(seq.prompt[k])
                seg.append(s)
                pos.append(len(self.prefix) + k)
                dest.append(self._where(seq.pages, k))
            seq.n_prefilled += take
            if seq.n_prefilled == len(seq.prompt):
                self._prefilling.popleft()
                seq.n_gen = 1
                segs.append((len(tokens) - 1, seq.slot, *rows))
                takers.append((s, seq))
                if seq.max_new > 1:
                    self._decoding.append(seq)
                else:
                    self._release(seq)
            else:
                segs.append((0, sz.slots, *rows))
        return self._dispatch_prefill_raw(
            tokens, seg, pos, dest, len(self.prefix), cont, n_cont, segs,
            takers)

    def _dispatch_prefill_raw(self, tokens, seg, pos, dest, n_prefix, cont,
                              n_cont, segs, takers) -> _Step:
        """``segs``: per segment (index of its last token, the slot that
        takes the sampled id, the slot-state row it starts from, the row
        its end state goes to); the null row where a segment samples
        nothing or is not there. With ``segs`` empty (warm-up's loaded
        chunk) every segment starts from the snapshot."""
        sz = self.sizes
        n = len(tokens)
        live = int((np.asarray(seg) >= 0).sum())
        mat = np.zeros((5, sz.chunk_tokens), np.int32)
        mat[1] = -1
        mat[0, :n], mat[1, :n], mat[2, :n] = tokens, seg, pos
        mat[3, :n] = np.asarray(dest, np.int64) // sz.page_tokens
        mat[4, :n] = np.asarray(dest, np.int64) % sz.page_tokens
        aux = np.zeros(self._private_pages + 2 + 4 * sz.max_segments,
                       np.int32)
        if cont is not None:
            aux[:len(cont)] = cont
        at = self._private_pages
        aux[at], aux[at + 1] = n_prefix, n_cont
        per_seg = aux[at + 2:].reshape(4, sz.max_segments)
        per_seg[1:] = sz.slots
        if not segs:
            per_seg[2] = sz.slots + 1
        for i, row in enumerate(segs):
            per_seg[:, i] = row
        # rows of the cache the chunk reads, per layer: the prefix once
        # (all its tokens share it) and one sequence's earlier rows; a
        # window layer the last of them that the chunk's first token sees
        cached = (n_prefix + n_cont) if n and self._paged else 0
        seen = min(cached, self._window - 1) if self._window else cached
        # the chunk's key blocks by class, as the kernel's own blocks cut
        # what the program is handed (the whole table of continued pages)
        key_blocks = _key_blocks(
            self._lm, self.cfg, mat[1].tobytes(), int(n_prefix), int(n_cont),
            self._prefix_pages, self._private_pages, sz.page_tokens)
        return self._run(
            "prefill", "prefill", self._prefill,
            (self._prefix_heads, mat, aux), tokens=live, rows_read=cached,
            takers=takers, window_read=seen, state_rows=len(segs),
            restores=sum(row[2] == sz.slots + 1 for row in segs),
            key_blocks=key_blocks)

    def _dispatch_decode(self) -> _Step:
        seqs = self._decoding
        bucket = next(b for b in self.buckets if b >= len(seqs))
        # the token fed back is the sequence's newest
        step = self._dispatch_decode_raw(
            [(s.slot, len(s.prompt) + s.n_gen - 1, s.pages) for s in seqs],
            bucket, list(enumerate(seqs)))
        still = []
        for seq in seqs:
            seq.n_gen += 1
            if seq.n_gen < seq.max_new:
                still.append(seq)
            else:
                self._release(seq)
        self._decoding = still
        self._set_gauges()
        return step

    def _dispatch_decode_raw(self, rows, bucket: int, takers) -> _Step:
        """``rows``: (slot, index of the own token fed back, own pages)
        of each row that carries a sequence. The table holds own pages
        only and the context length counts own rows: the prefix is the
        program's."""
        sz = self.sizes
        mat = np.zeros((6, bucket), np.int32)
        mat[0] = sz.slots
        mat[2] = 1
        table = np.zeros((bucket, self._private_pages), np.int32)
        rows_read = window_read = pages_read = 0
        for b, (slot, k, pages) in enumerate(rows):
            row = self._where(pages, k)
            mat[:, b] = (slot, len(self.prefix) + k, k + 1,
                         row // sz.page_tokens, row % sz.page_tokens, 1)
            table[b, :len(pages)] = pages
            if not self._paged:
                continue
            # a row's whole context, the prefix's rows among them
            ctx = len(self.prefix) + k + 1
            rows_read += ctx
            # of which a window layer sees the last ``window``
            window_read += min(ctx, self._window or ctx)
            # the table's pages that hold the k + 1 own rows
            pages_read += k // sz.page_tokens + 1
        return self._run("decode", f"decode:{bucket}", self._decode,
                         (mat, table), tokens=len(rows), rows_read=rows_read,
                         takers=takers, state_rows=len(rows),
                         window_read=window_read, own_pages=(
                             pages_read,
                             len(rows) * self._private_pages * self._paged
                             - pages_read))

    def _run(self, kind: str, key: str, fn, inputs, *, tokens, rows_read,
             takers, state_rows, restores=0, window_read=None,
             own_pages=(0, 0), key_blocks=()) -> _Step:
        if (self._count_dry and self._inflight
                and self._inflight[-1].held.is_ready()):
            # the loop keeps one step in flight while it builds the
            # next: that step has already ended, so the device ran dry
            # before this dispatch (asked, not waited for)
            metrics.inc("evam_generate_dry_dispatches", 1.0,
                        {"kind": kind})
        t0 = time.perf_counter()
        self._step_started = t0
        cold = key not in self._seen
        self._state, self._last_ids, top, ids, held = fn(
            self._params, self._state, self._last_ids, *inputs)
        for out in (top, ids, held):
            out.copy_to_host_async()
        if cold:
            # the call returns when the program is compiled
            self._seen.add(key)
            self.stats.compiled_programs += 1
            self.stats.compile_seconds += time.perf_counter() - t0
        if not self._state_bytes:
            state_rows = restores = 0
        return _Step(kind, key, t0, tokens, rows_read,
                     rows_read if window_read is None else window_read,
                     state_rows, restores, own_pages, key_blocks, takers,
                     top, ids, held)

    # ------------------------------------------------------------- harvest

    def _harvest(self, step: _Step, count: bool = True) -> None:
        """Fetch a step's outputs (waits for the step), hand its sampled
        tokens to their sequences, resolve those that are complete."""
        top = np.asarray(step.top)
        ids = np.asarray(step.ids)
        held, hit, reads = (int(v) for v in np.asarray(step.held))
        now = time.perf_counter()
        self._step_started = (self._inflight[0].t_dispatch
                              if self._inflight else None)
        # the device ran this step from when it was free (the step before
        # came back) or from this step's dispatch, whichever was later
        dt = now - max(self._t_free, step.t_dispatch)
        self._t_free = now
        if not count:
            return
        labels = {"kind": step.kind}
        metrics.observe("evam_generate_step_seconds", dt, labels)
        metrics.inc("evam_generate_steps", 1.0, labels)
        metrics.inc("evam_generate_tokens", float(step.tokens), labels)
        metrics.inc("evam_generate_latent_rows_read",
                    float(step.rows_read), labels)
        if self._window:
            metrics.inc("evam_generate_window_rows_read",
                        float(step.window_read), labels)
            metrics.inc("evam_generate_window_rows_skipped",
                        float(step.rows_read - step.window_read), labels)
        if step.kind == "decode":
            metrics.inc("evam_generate_own_pages_read",
                        float(step.own_pages[0]), labels)
            metrics.inc("evam_generate_own_pages_skipped",
                        float(step.own_pages[1]), labels)
        for name, counts in step.key_blocks:
            for cls, n in zip(CLASSES, counts):
                metrics.inc("evam_generate_chunk_key_blocks", float(n),
                            {"layers": name, "class": cls})
        metrics.inc("evam_generate_state_rows", float(step.state_rows),
                    labels)
        # each of which the step read and wrote, a slot's row of every
        # layer: counted here, the step programs return nothing for it
        metrics.inc("evam_generate_state_bytes",
                    float(2 * step.state_rows * self._row_bytes), labels)
        metrics.inc("evam_generate_prefix_restores", float(step.restores))
        metrics.inc("evam_moe_held_assignments", float(held))
        # of which the sort's rows are: held over routed is the share of
        # them that carry work on this chip
        metrics.inc("evam_moe_routed_assignments",
                    float(step.tokens * self._routed_per_token))
        # per expert layer, the held experts with at least one assignment
        metrics.inc("evam_moe_held_experts_hit", float(hit), labels)
        # and the (row tile, expert) pairs one grouped product visited
        metrics.inc("evam_moe_expert_reads", float(reads), labels)
        st = self.stats
        st.batches += 1
        st.add_stage("launch", dt)
        if step.kind == "decode":
            # row-key pairs that the one pass over the prefix served
            metrics.inc("evam_generate_decode_shared_rows",
                        float(len(self.prefix) * step.tokens * self._paged))
            bucket = int(step.key.split(":")[1])
            st.bucket_batches[bucket] = st.bucket_batches.get(bucket, 0) + 1
            st.occupancy_sum += step.tokens / bucket
        else:
            st.occupancy_sum += step.tokens / self.sizes.chunk_tokens
        self._program_s[step.key] += 0.1 * (dt - self._program_s[step.key])
        for row, seq in step.takers:
            if seq.cancelled:
                continue
            seq.ids.append(int(ids[row, 0]))
            seq.top_ids.append(ids[row].tolist())
            seq.top_logits.append(top[row].tolist())
            if step.kind == "prefill":
                seq.t_prefilled = now
            if len(seq.ids) == seq.max_new:
                self._finish(seq, now)

    def _finish(self, seq: _Seq, now: float) -> None:
        self.stats.items += 1
        self._done += 1
        a = 1.0 / min(self._done, 64)
        self._mean_prompt += a * (len(seq.prompt) - self._mean_prompt)
        self._mean_new += a * (seq.max_new - self._mean_new)
        ft = seq.trace
        if ft is not None:
            ft.add_span("generate.slot_wait", seq.t_submit,
                        seq.t_slot - seq.t_submit)
            ft.add_span("generate.queue_wait", seq.t_submit,
                        seq.t_first - seq.t_submit)
            ft.add_span("generate.prefill", seq.t_first,
                        seq.t_prefilled - seq.t_first)
            ft.add_span("generate.decode", seq.t_prefilled,
                        now - seq.t_prefilled)
        self._resolve(seq, {
            "ids": seq.ids, "top_ids": seq.top_ids,
            "top_logits": seq.top_logits,
            "prefix_tokens": int(len(self.prefix))})

    def _resolve(self, seq: _Seq, result) -> None:
        with self._lock:
            self._outstanding.pop(id(seq), None)
        try:
            seq.future.t_resolved = time.perf_counter()
            seq.future.set_result(result)
        except Exception:  # noqa: BLE001 - failed by abandon meanwhile
            pass
