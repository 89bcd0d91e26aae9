"""What a language-model family's two step programs COMPUTE, as a digest of
their jaxprs at the deployment's sizes: the guard of the modules the
families share (models/lm/experts.py, attention.py, mla.py, common.py,
ops/pallas_grouped.py, ops/pallas_attention.py, ops/slot_rows.py). A
family's digest moves when, and only when, an operation of its served path
does, its Pallas kernels' bodies among them (traced for the chip, nothing
is compiled or run); a PR that adds a family beside the others leaves theirs
as they were, bit for bit. Each family's test file holds its digests;
``golden/step_traces.json`` holds, for each, the operations counted by
primitive and the digest of every kernel's body, so that a digest that
moved says WHAT moved (``check``)."""

import hashlib
import json
import re
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from evam_tpu.models.lm import common, family
from evam_tpu.models.lm.presets import PRESETS

RECORD = Path(__file__).resolve().parent / "golden" / "step_traces.json"
#: the deployments whose programs the record holds, one a family
FAMILY_PRESETS = ("deepseek_v2_ep8", "jamba2_3b", "kimi_linear_ep4",
                  "lfm2_moe_ep2", "laguna_xs2_pp8", "brumby_14b_pp8",
                  "nemotron3_super_ep8")


def _step(lm, cfg, program: str):
    """A family's ``decode`` step at 64 rows or its ``prefill`` chunk of
    512 tokens at the deployment's sizes (401 pages of 128, 128 slots, a
    prefix of 16 pages), as shapes: ``(the function, its arguments)``."""
    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    params = jax.eval_shape(lambda: lm.make_params(cfg))
    state = dict(lm.state_shapes(cfg, 401, 128, 128))
    shared = np.arange(1, 17, dtype=np.int32)
    i32 = jnp.int32
    if program == "decode":
        b = 64

        def step(params, state, tokens, pos, table, ctx_len, page, off, live,
                 slot):
            return lm.decode_tokens(cfg, params, state, tokens, pos, table,
                                    ctx_len, page, off, live, shared, 2048,
                                    slot)

        return step, (params, state, s((b,), i32), s((b,), i32),
                      s((b, 3), i32), s((b,), i32), s((b,), i32),
                      s((b,), i32), s((b,), jnp.bool_), s((b,), i32))
    t, n_seg = 512, 8
    heads = (lm.prefix_heads_shapes(cfg, 2048)
             if hasattr(lm, "prefix_heads_shapes") else ())

    def step(params, state, heads, tokens, seg, pos, page, off, cont, n_cont,
             last_idx, seg_from, seg_to, n_prefix):
        return lm.prefill_chunk(
            cfg, params, state, tokens, seg, pos, page, off, shared,
            n_prefix, cont, n_cont, last_idx, seg_from, seg_to,
            **({"prefix_heads": heads} if heads else {}))

    return step, (params, state, heads, *[s((t,), i32)] * 5, s((3,), i32),
                  s((), i32), *[s((n_seg,), i32)] * 3, s((), i32))


def _sub(value):
    """The jaxprs inside one parameter of an equation."""
    if hasattr(value, "eqns"):
        yield value
    elif hasattr(value, "jaxpr") and hasattr(value.jaxpr, "eqns"):
        yield value.jaxpr
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub(v)


def _text(jaxpr) -> str:
    # a host callback prints as its function's address in this process
    return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))


def _count(jaxpr, ops: dict, kernels: dict) -> None:
    """Every equation by its primitive, through loops, branches and
    kernels; a Pallas kernel by its name, with the digest of its body."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            kernel = eqn.params["name"]
            name = f"pallas_call:{kernel}"
            body = hashlib.sha256(
                _text(eqn.params["jaxpr"]).encode()).hexdigest()[:16]
            kernels[kernel] = sorted({*kernels.get(kernel, []), body})
        ops[name] = ops.get(name, 0) + 1
        for value in eqn.params.values():
            for sub in _sub(value):
                _count(sub, ops, kernels)


def trace(preset: str, program: str, on_chip: bool, monkeypatch) -> dict:
    """``digest``: the first 16 hex digits of the SHA-256 of the program's
    jaxpr, traced for the chip (the Pallas kernels) or for the host (their
    XLA twins); ``ops`` and ``kernels``: what a reader needs to see WHAT
    moved when the digest did; ``text``: the jaxpr."""
    monkeypatch.setattr(common, "TARGET_TPU", on_chip)
    model = PRESETS[preset]
    lm = family(model["model_type"])
    step, args = _step(lm, lm.Config.from_dict(model), program)
    closed = jax.make_jaxpr(step)(*args)
    ops, kernels = {}, {}
    _count(closed.jaxpr, ops, kernels)
    text = _text(closed)
    return {"digest": hashlib.sha256(text.encode()).hexdigest()[:16],
            "ops": dict(sorted(ops.items())), "kernels": kernels,
            "text": text}


def check(preset: str, program: str, on_chip: bool, monkeypatch,
          want: str) -> None:
    """The program digests to ``want``; where it does not, say what moved
    against the record in ``golden/step_traces.json`` (operations counted
    by primitive, each kernel's body) and leave the jaxpr where two
    checkouts' can be diffed."""
    got = trace(preset, program, on_chip, monkeypatch)
    if got["digest"] == want:
        return
    key = f"{preset}/{program}/{'chip' if on_chip else 'host'}"
    was = json.loads(RECORD.read_text()).get(key, {"ops": {}, "kernels": {}})
    moved = [f"{name}: {was['ops'].get(name, 0)} -> {got['ops'].get(name, 0)}"
             for name in sorted({*was["ops"], *got["ops"]})
             if was["ops"].get(name, 0) != got["ops"].get(name, 0)]
    moved += [f"the body of kernel {name}"
              for name in sorted({*was["kernels"], *got["kernels"]})
              if was["kernels"].get(name) != got["kernels"].get(name)]
    out = Path(tempfile.gettempdir()) / (key.replace("/", ".") + ".jaxpr.txt")
    out.write_text(got["text"])
    raise AssertionError(
        f"{key} digests to {got['digest']}, not {want}: "
        + ("; ".join(moved) if moved else "the same operations and kernel "
           "bodies: a shape, a parameter or the order moved")
        + f". Its jaxpr is in {out}: diff it against the parent's "
        "(PYTHONPATH=. python tests/_step_trace.py there). If the change is "
        "meant, write the record anew (the same with --write) and put the "
        "new digest in the family's test file.")


def main(argv: list[str]) -> None:
    """``--write``: the record anew, and each digest printed. Otherwise
    every program's jaxpr into the temporary directory, for a diff."""
    import pytest

    record = {}
    with pytest.MonkeyPatch.context() as mp:
        for preset in FAMILY_PRESETS:
            for program in ("decode", "prefill"):
                for on_chip in (True, False):
                    key = f"{preset}/{program}/{'chip' if on_chip else 'host'}"
                    got = trace(preset, program, on_chip, mp)
                    print(key, got["digest"])
                    out = Path(tempfile.gettempdir()) / (
                        key.replace("/", ".") + ".jaxpr.txt")
                    out.write_text(got.pop("text"))
                    record[key] = got
    if "--write" in argv:
        RECORD.write_text(json.dumps(record, indent=1) + "\n")


if __name__ == "__main__":
    import sys

    main(sys.argv[1:])
