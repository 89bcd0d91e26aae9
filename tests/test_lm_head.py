"""The head's selection of its top logits (models/lm/common.py
``top_logits``): exact, and what ``jax.lax.top_k`` returns bit for bit:
descending, among equal logits the lowest id first (the first id is the
greedy sample that the next decode step embeds). Shapes: the two
vocabularies the chip serves (Jamba's 65 536, DeepSeek's slice of
12 800) at a prefill chunk's 8 last rows and at decode buckets."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evam_tpu.models.lm import common, family
from evam_tpu.models.lm.presets import PRESETS

SHAPES = [(8, 65536), (64, 65536), (16, 12800), (128, 12800)]
K = common.TOP_LOGITS


def _normals(rng, rows, n):
    return rng.standard_normal((rows, n)).astype(np.float32)


def _ties(rng, rows, n):
    """Equal logits inside the best eight, and a run of equal logits that
    straddles the eighth place (five above it, six equal for the three
    places left), at ids drawn per row."""
    x = _normals(rng, rows, n)
    top = np.float32(x.max() + 2)
    for r in range(rows):
        at = rng.choice(n, size=11, replace=False)
        x[r, at[:2]] = top
        x[r, at[2:5]] = top - np.float32(0.5)
        x[r, at[5:]] = top - np.float32(1.0)
    x[0, :K + 3] = top      # all of the best eight equal, from id 0
    return x


def _constant(rng, rows, n):
    x = _normals(rng, rows, n)
    x[0] = np.float32(0.25)
    x[-1] = np.float32(-3.0)
    return x


def _ends(rng, rows, n):
    """The largest logit at the first id, at the last, and at both."""
    x = _normals(rng, rows, n)
    top = np.float32(x.max() + 1)
    x[0::3, 0] = top
    x[1::3, -1] = top
    x[2::3, [0, -1]] = top
    return x


KINDS = {"normals": _normals, "ties": _ties, "constant": _constant,
         "ends": _ends}


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("rows,vocab", SHAPES)
def test_top_logits_is_lax_top_k_bit_for_bit(rows, vocab, kind):
    rng = np.random.default_rng(rows * 31 + vocab)
    logits = jnp.asarray(KINDS[kind](rng, rows, vocab))
    want_top, want_ids = jax.lax.top_k(logits, K)
    top, ids = jax.jit(common.top_logits)(logits)
    assert top.dtype == jnp.float32 and ids.dtype == jnp.int32
    assert top.shape == ids.shape == (rows, K)
    np.testing.assert_array_equal(np.asarray(top), np.asarray(want_top))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))


@pytest.mark.parametrize("preset", ["jamba_tiny", "deepseek_v2_tiny"])
def test_head_of_each_family_leads_with_the_greedy_sample(preset):
    """Jamba's head is tied to the embedding, DeepSeek's is its own
    tensor: both go through ``common.head`` and its selection."""
    lm = family(PRESETS[preset]["model_type"])
    cfg = lm.Config.from_dict(PRESETS[preset])
    params = lm.make_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (12, cfg.hidden),
                          jnp.float32).astype(jnp.bfloat16)
    logits, top, ids = jax.jit(lambda p, x: lm.head(cfg, p, x))(params, x)
    assert logits.shape == (12, cfg.vocab) and logits.dtype == jnp.float32
    assert top.shape == ids.shape == (12, K) and ids.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(top[:, 0]),
                                  np.asarray(logits.max(-1)))
    np.testing.assert_array_equal(np.asarray(ids[:, 0]),
                                  np.asarray(logits.argmax(-1)))
    assert (np.diff(np.asarray(top), axis=1) <= 0).all()
