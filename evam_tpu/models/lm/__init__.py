"""Language models served by the generate engine (engine/generate.py).

A second model family beside the IR importer and the vision zoo: plain
JAX, weights made on the device from a seed, installed by
``fetch-models --synthesize-lm`` as a config file and found by
``ModelRegistry.lm_config``. Nothing here is imported by a server that
serves no ``describe`` stage.

A FAMILY is a module with ``Config.from_dict``, ``make_params``,
``param_count``, ``state_shapes`` (the device state of its sequences for
``(pages, page_tokens, slots)``: ``pages``, the cache rows of the layers
that attend, ABSENT for a family none of whose layers keeps rows
(``brumby``: the engine then pins, allocates and counts no page), and
whatever it keeps per slot), optionally ``prefix_heads_shapes`` and ``prefix_heads``
(read-only data of the prefill program that the engine holds beside the
weights: a latent family's materialised heads of the shared prefix's rows,
made from ``params``, the state and the pinned pages once warm-up has
prefilled them, handed to ``prefill_chunk`` as ``prefix_heads``; a family
without them has no such name), optionally ``chunk_key_blocks`` (a family
whose chunks run the chunk attention kernel, ops/pallas_attention.py: on the
host, per kind of such layer, the classes of the kernel call's key blocks
for a chunk of given segments, which the engine counts by), ``prefill_chunk``
and ``decode_tokens``
(each returns the state, the top logits with their ids, and int32 ``[held assignments, held
experts hit, expert matrices read a product]``, zeros for a family
without experts) and ``SEGMENT_ALIGN``
(the multiple of a chunk's tokens at which the engine's packer starts
every segment: 1, or the block of a chunkwise kernel); the installed
config's ``model_type`` names it. A ``Config`` whose layers do not all see
everything earlier says so with ``window`` (the positions a window layer
sees: the engine counts the rows such a layer read and skipped by it).

What more than one family has lives beside them and is called by each:
``common.py`` (tensors, norms, the split softmax, a packed chunk's bounds
and convolution inputs, the head), ``mla.py`` (the latent attention:
``deepseek_v2`` with its rotation and query down-projection,
``kimi_linear`` without either), ``attention.py`` (plain keys and values
under grouped queries, what differs as data of the layer KIND: ``jamba``
one key-value head and no positions, ``lfm2_moe`` eight with head norms
and rotary positions, ``laguna`` two kinds in one model, full layers of 48
query heads under a rescaled partial rotation and window layers of 64
under a plain one, both with a gate on every head's output, ``nemotron_h``
32 query heads over 2 key-value heads and no positions) and
``experts.py`` (the expert layer told which experts it holds:
``deepseek_v2`` softmax scores and group-limited routing, ``kimi_linear``
sigmoid scores with a selection bias, ``lfm2_moe`` the same with no shared
expert and its expert layers' tensors in one stack, ``laguna`` as
Kimi-Linear's in one stack with ALL 256 of a layer's experts held,
``nemotron_h`` 22 chosen of 512 with an eighth held, experts of two
matrices under ``relu^2`` that work in a projected latent). A family whose
blocks are each ONE sublayer (``nemotron_h``: a Mamba-2 mixer, an expert
layer or attention by a pattern string) is a family like the others: what a
block is made of is the family's own, the engine sees ``state_shapes`` and
the two step programs. ``brumby`` is the family whose EVERY layer is
recurrent (power retention of degree 2, ops/pallas_power.py): its
projections, head norms and rotation are ``attention.py``'s ``qkv``, its
state 34 MB a row and layer, its layers one stack under one ``lax.scan``.
"""

from __future__ import annotations

import importlib

FAMILIES = {"deepseek_v2": "deepseek_v2", "jamba": "jamba",
            "kimi_linear": "kimi_linear", "lfm2_moe": "lfm2_moe",
            "laguna": "laguna", "nemotron_h": "nemotron_h",
            "brumby": "brumby"}


def family(model_type: str):
    """The module that serves configs of ``model_type``."""
    if model_type not in FAMILIES:
        raise ValueError(
            f"no language-model family serves model_type {model_type!r} "
            f"({'|'.join(sorted(FAMILIES))})")
    return importlib.import_module(
        f"evam_tpu.models.lm.{FAMILIES[model_type]}")
