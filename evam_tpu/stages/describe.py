"""The ``describe`` stage: a short generated text over what the detector
saw in the frame.

Operators summarise what detectors see. The stage renders the frame's
regions into tokens, after a long operator instruction that every frame
shares, and submits one generation to the language model's generate
engine (engine/generate.py); the frame parks on that future like on any
engine's, for seconds rather than milliseconds. ``complete`` attaches

    "description": {"prompt_ids", "ids", "top_ids", "top_logits",
                    "prefix_tokens"}

to the frame's message (the generated ids and, per generated token, the
8 largest logits with their ids).

**The tokenizer is a stand-in.** The model's BPE vocabulary cannot enter
this environment, and the chip holds a slice of the vocabulary anyway
(``vocab_held`` ids). What a frame costs depends on how MANY tokens it
brings, so the rendering is deterministic and fixed-width:

* ids 0-7 are marks (1 frame, 2 object, 3 end of object), 8-23 the
  labels (``8 + label_id % 16``), and from 24 on the numbers: ``bins =
  min(1000, vocab - 24)`` and a value v in [0, 1] is ``24 + min(bins - 1,
  int(v * bins))``;
* a frame's header is 16 ids: the frame mark, 7 numbers from the SHA-256
  of the source URI (byte pairs modulo ``bins``) and the timestamp's 8
  digits to the base ``bins``, least first;
* an object is 8 ids: mark, label, x_min, y_min, x_max, y_max,
  confidence, end mark. At most ``max-objects`` (32) objects: 272 ids;
* the instruction is ``prefix-tokens`` ids ``8 + ((i + 1) * 2654435761
  mod 2**32) mod (vocab - 8)``: it stands for a site description, zones
  and few-shot examples, and costs what those would.
"""

from __future__ import annotations

import hashlib
from concurrent.futures import Future

from evam_tpu.engine.hub import EngineHub
from evam_tpu.obs import get_logger
from evam_tpu.stages.base import AsyncStage
from evam_tpu.stages.context import FrameContext

log = get_logger("stages.describe")

FRAME, OBJ, END_OBJ = 1, 2, 3
LABEL0, NUM0 = 8, 24
HEADER_IDS, OBJECT_IDS = 16, 8


def instruction_ids(n: int, vocab: int) -> list[int]:
    return [LABEL0 + ((i + 1) * 2654435761 % 2**32) % (vocab - LABEL0)
            for i in range(n)]


def _bins(vocab: int) -> int:
    return min(1000, vocab - NUM0)


def _number(v: float, bins: int) -> int:
    return NUM0 + min(bins - 1, max(0, int(v * bins)))


def render_prompt(source_uri: str, timestamp_ns: int, objects: list[tuple],
                  vocab: int, max_objects: int = 32) -> list[int]:
    """``objects``: (label_id, x_min, y_min, x_max, y_max, confidence)."""
    bins = _bins(vocab)
    digest = hashlib.sha256(source_uri.encode()).digest()
    ids = [FRAME]
    ids += [NUM0 + (digest[2 * i] * 256 + digest[2 * i + 1]) % bins
            for i in range(7)]
    ids += [NUM0 + (int(timestamp_ns) // bins ** i) % bins for i in range(8)]
    for label_id, *box, conf in objects[:max_objects]:
        ids += [OBJ, LABEL0 + int(label_id) % 16,
                *(_number(v, bins) for v in box), _number(conf, bins),
                END_OBJ]
    return ids


class DescribeStage(AsyncStage):
    """Properties: ``max-new-tokens`` (48; the pipeline's file may set
    its own default), ``max-objects`` (32),
    ``prefix-tokens`` (``EVAM_LM_SHAPES``: 2048),
    ``model-instance-id``."""

    def __init__(self, name: str, model_key: str, properties: dict,
                 hub: EngineHub):
        self.name = name
        self.model_key = model_key
        self.max_new = int(properties.get("max-new-tokens", 48))
        self.max_objects = int(properties.get("max-objects", 32))
        self.vocab = int(hub.registry.lm_config(model_key)["vocab_held"])
        prefix_tokens = int(properties.get(
            "prefix-tokens", hub.lm.prefix_tokens))
        self.engine = hub.generate_engine(
            model_key, properties.get("model-instance-id"),
            prefix_ids=instruction_ids(prefix_tokens, self.vocab))
        if hub.warmup:
            self.engine.warm_async()
        else:
            self.engine.set_example()
        self._streams: set[str] = set()

    def submit(self, ctx: FrameContext) -> Future | None:
        self._streams.add(ctx.stream_id)
        prompt = render_prompt(
            ctx.source_uri, ctx.pts_ns,
            [(r.label_id, r.x0, r.y0, r.x1, r.y1, r.confidence)
             for r in ctx.regions], self.vocab, self.max_objects)
        ctx.scratch["describe_prompt"] = prompt
        return self.engine.submit(
            priority=ctx.priority, stream=ctx.stream_id, trace=ctx.trace,
            prompt_ids=prompt, max_new_tokens=self.max_new)

    def complete(self, ctx: FrameContext, result) -> list[FrameContext]:
        prompt = ctx.scratch.pop("describe_prompt", None)
        if result is None:
            return []  # the stream was cancelled: nothing to publish
        ctx.messages.append(
            {"description": {"prompt_ids": prompt, **result}})
        return [ctx]

    def cancel(self) -> None:
        for stream in self._streams:
            self.engine.cancel_stream(stream)
