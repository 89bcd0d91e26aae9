"""The expert layer of one chip of an expert-parallel deployment, for every
family that has one (models/lm/deepseek_v2.py: softmax scores, group-limited
top-k, one routing group held; models/lm/kimi_linear.py: sigmoid scores with
a selection bias, one group, a quarter of the experts held;
models/lm/lfm2_moe.py: as Kimi-Linear's with half the experts held, no
shared expert, and every expert layer's tensors in ONE stack;
models/lm/laguna.py: as Kimi-Linear's in one stack too, with ALL 256 small
experts of a layer held, 8 a token: the held range is the whole range, a
64-row step's 512 assignments a layer reach most of 256 groups with two or
three rows each, and a 512-token chunk gives every expert some 16 rows, an
eighth of a row tile; models/lm/nemotron_h.py: sigmoid scores with a
selection bias, 22 a token of 512 of which an eighth is held, experts of
TWO matrices under ``relu^2`` that work in a LATENT of a quarter of the
hidden width, and a shared expert of the same two-matrix form on the hidden
itself).

A router over ALL ``n_experts`` (scores in float32), the routed experts
this chip HOLDS (ids ``[held_lo, held_lo + n_held)``, ``n_held`` the
leading axis of the layer's ``expert_*`` tensors) and the shared experts.
The chip adds, for each token, only its held experts' terms (none for a
token none of whose experts is held) and the shared experts; that partial
sum goes on to the next layer. Nothing stands in for absent chips. The
held experts' work follows the tokens routed to them: assignments are
sorted by expert and run through the grouped products of
ops/pallas_grouped.py (off the chip: ``jax.lax.ragged_dot``). Around
those products no operation walks the assignments one at a time and no
pass touches sorted rows that the un-sort does not read (PR 58: on a TPU a
scatter and a gather of single scalars are loops of one trip an element,
~10 ns each): the group sizes, the kept groups and the chosen experts'
scores are dense comparisons that fuse into one reduction each, the
assignments lie CHOICE-major (``[top_k, T]``: a token's ``top_k`` rows
come back ``T`` apart, so the weighted sum adds ``top_k`` slabs ``[T, w]``
and ``top_k`` is never a tile's padded minor axis), and rows that hold no
held assignment are dropped by the select that weights the gathered rows.

What differs between the families is data of the config: ``score_func``
(``softmax`` | ``sigmoid``), ``n_group`` / ``topk_group`` (1: no group
limiting), ``top_k``, ``norm_topk`` (the chosen weights divided by their
sum plus ``topk_eps``), ``scale_routed`` (times ``routed_scale``),
``held_lo``, ``n_shared`` (0: the layer has no ``shared_*`` tensors and
no shared term), ``expert_act`` (``swiglu``: an expert is ``W_down
(silu(W_gate x) * W_up x)``, three matrices; ``relu2``: ``W_down max(W_up x,
0)^2``, two, and the shared expert likewise) and ``moe_latent`` (None: the
experts read and write the hidden; else a width: every token is projected
ONCE to it before the sort (``latent_down``, shared by all experts), the
experts work there, and the weighted sum of THIS chip's experts is projected
back once (``latent_up``): the projection is linear, so the chips' shares add
up behind it as they would before it; the router and the shared expert read
the hidden); and of the layer: ``router_bias`` (added to the scores
for the SELECTION only; the weights are the scores without it). A family
whose expert layers run in one loop body hands ``moe`` every layer's
tensors stacked on a leading axis and the layer as a traced index
(``layer``): the router's slice and the shared experts' are taken, the
experts' stack goes to the grouped products as it is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from evam_tpu.models.lm.common import BF16, F32, mm, on_tpu, swiglu
from evam_tpu.ops import pallas_grouped


def tensor_shapes(cfg, bias: bool) -> dict[str, tuple]:
    """The layer's tensors; each ``expert_*`` is one expert's."""
    h, s = cfg.hidden, cfg.n_shared * cfg.moe_inter
    gated = cfg.expert_act == "swiglu"
    out = {"router": (h, cfg.n_experts)}
    if bias:
        out["router_bias"] = (cfg.n_experts,)
    w = h  # the width the experts work in
    if cfg.moe_latent:
        w = cfg.moe_latent
        out.update(latent_down=(h, w), latent_up=(w, h))
    if s and gated:
        out["shared_gate"] = (h, s)
    if s:
        out.update(shared_up=(h, s), shared_down=(s, h))
    if gated:
        out["expert_gate"] = (w, cfg.moe_inter)
    out.update(expert_up=(w, cfg.moe_inter), expert_down=(cfg.moe_inter, w))
    return out


def relu2(x, up, down):
    """``W_down max(W_up x, 0)^2``: a feed-forward of two matrices."""
    return mm(jnp.square(jax.nn.relu(mm(x, up))), down)


def route(cfg, x, router, bias=None):
    """Top-k over ALL experts: ``(weights [T,k], ids [T,k])``. Scores are
    a float32 softmax or sigmoid. With groups, a group's score is its best
    expert's; the best ``topk_group`` groups are kept (ties: the lower
    index, as ``lax.top_k``), the rest set to 0; then the best ``top_k``
    of what is left. With a ``bias`` [experts] the experts are chosen by
    ``score + bias`` and weighted by the score alone. The kept groups and
    the chosen scores are one-hot comparisons with the ids, exact: the
    ``.at[].set`` and the ``take_along_axis`` they stand for are a loop
    of one trip an element on a TPU."""
    logits = jnp.dot(x.astype(F32), router.astype(F32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if cfg.score_func == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    t = scores.shape[0]
    chosen_by = scores if bias is None else scores + bias.astype(F32)
    if cfg.n_group > 1:
        per_group = cfg.n_experts // cfg.n_group
        group = chosen_by.reshape(t, cfg.n_group, per_group).max(-1)
        _, keep = jax.lax.top_k(group, cfg.topk_group)
        kept = (keep[..., None] == jnp.arange(cfg.n_group)).any(1)
        chosen_by = jnp.where(jnp.repeat(kept, per_group, axis=1), chosen_by,
                              0.0)
    w, ids = jax.lax.top_k(chosen_by, cfg.top_k)
    if bias is not None:
        # a maximum over the one chosen value: a SUM here is merged by the
        # compiler with ``w.sum`` below into one reduction in another order
        w = jnp.where(ids[..., None] == jnp.arange(cfg.n_experts),
                      scores[:, None, :], -jnp.inf).max(-1)
    if cfg.norm_topk and cfg.top_k > 1:
        w = w / (w.sum(-1, keepdims=True) + cfg.topk_eps)
    if cfg.scale_routed:
        w = w * cfg.routed_scale
    return w, ids


def held_experts(cfg, lp: dict, x, w, ids, live, layer=None):
    """The held experts' part of the routed sum, with work that follows
    the assignments routed here: the ``T*k`` assignments are sorted by
    held expert (those of other chips' experts, and of dead rows, last;
    padded to whole row tiles), and the sorted rows go through ONE
    grouped product a call (ops/pallas_grouped.py: gate and up with their
    epilogue, or up alone with ``relu^2``, then down), over all rows whatever
    the routing: a call
    costs the row tiles that hold assignments and reads an expert's
    matrix once a tile that holds some of its rows, so an expert no
    assignment reaches is never read and the rows past the last
    assignment cost nothing. Every assignment to a held expert is
    computed, however uneven the routing. One path for every family:
    measured on a v5e (PR 39) the kernel beats ``ragged_dot`` at 64
    experts of 4.7 MB a matrix and at 20 of 15.7 MB, in chunks and in
    decode steps. The assignments are numbered choice-major (``j * T + i``
    for token ``i``'s ``j``-th expert), the group sizes are a comparison
    of the sort key with the held ids summed over the rows (``bincount``
    is a scatter), and nothing zeroes the sorted rows past the last held
    assignment, which hold whatever the kernel left there: only an
    assignment that is not this chip's gathers one, and the select that
    weights the gathered rows ``[top_k, T, w]`` drops it, NaN or not.
    Returns the sum [T, hidden], the number of held
    assignments, the number of held experts that received at least one
    and the (row tile, expert) pairs one product visits (the times an
    expert's matrix is read). With ``layer`` the ``expert_*`` tensors
    are stacks [layers, held, ...] of which that layer's are read."""
    t, k = ids.shape
    n_held = lp["expert_down"].shape[-3]
    local = ids.T - cfg.held_lo
    mine = (local >= 0) & (local < n_held) & live
    m = pallas_grouped.padded(t * k)
    sort_key = jnp.pad(jnp.where(mine, local, n_held).reshape(-1),
                       (0, m - t * k), constant_values=n_held)
    order = jnp.argsort(sort_key, stable=True)
    sizes = (sort_key[:, None] == jnp.arange(n_held)).sum(0, dtype=jnp.int32)
    n_mine = sizes.sum()
    rows = x[order % t]
    ops = pallas_grouped
    swiglu_rows, relu2_rows, product_rows = (
        (ops.swiglu, ops.relu2, ops.product) if on_tpu()
        else (ops.swiglu_xla, ops.relu2_xla, ops.product_xla))
    if cfg.expert_act == "swiglu":
        hmid = swiglu_rows(rows, lp["expert_gate"], lp["expert_up"], sizes,
                           layer)
    else:
        hmid = relu2_rows(rows, lp["expert_up"], sizes, layer)
    y = product_rows(hmid, lp["expert_down"], sizes, layer)
    back = jnp.argsort(order)[:t * k]
    y = y[back].reshape(k, t, -1).astype(F32)
    # a select, not a product with 0: a row past the last group may be NaN
    out = jnp.where(mine[..., None], y * w.T[..., None], 0.0).sum(0)
    return (out.astype(BF16), n_mine, (sizes > 0).sum().astype(jnp.int32),
            pallas_grouped.n_visits(sizes, m))


def moe(cfg, lp: dict, x, live, layer=None):
    """Held routed terms plus the shared experts (where the config has
    any), and ``[held assignments, held experts hit, expert matrices read
    a product]`` (int32). ``layer``: ``lp`` holds every expert layer's
    tensors stacked, and this is the one to run."""
    router, bias = lp["router"], lp.get("router_bias")
    if layer is not None:
        router, bias = router[layer], None if bias is None else bias[layer]
    def of_layer(name):
        return lp[name] if layer is None else lp[name][layer]

    with jax.named_scope("router"):
        w, ids = route(cfg, x, router, bias)
    rows = x
    if cfg.moe_latent:
        with jax.named_scope("latent_down"):
            rows = mm(x, of_layer("latent_down"))
    with jax.named_scope("experts"):
        y, *counts = held_experts(cfg, lp, rows, w, ids, live, layer)
    if cfg.moe_latent:
        with jax.named_scope("latent_up"):
            y = mm(y, of_layer("latent_up"))
    if cfg.n_shared:
        gated = cfg.expert_act == "swiglu"
        shared = (of_layer(f"shared_{name}") for name in (
            ("gate", "up", "down") if gated else ("up", "down")))
        with jax.named_scope("shared"):
            y = y + (swiglu if gated else relu2)(x, *shared)
    return y, jnp.stack(counts)
