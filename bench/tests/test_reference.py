"""The plain reference against the program's own jnp forward, at small width.

Both configurations' block shapes: GQA with a SwiGLU MLP and a tied head
(phi4-mini), and MQA with a tanh-GELU MLP and a separate head (granite).
"""

import jax
import numpy as np
import pytest

import weights
from reference import Reference, fp8
from small import DENSE, GQA, MQA

from repro.models import Model
from repro.models.config import ModelConfig


def _program_logits(m, w, tokens):
    model = Model(ModelConfig(**m), remat=False, use_kernels=False)
    params = weights.program_params(model.init(None, abstract=True)[0], w)
    logits, _ = model.forward(params, tokens=jax.numpy.asarray(tokens)[None])
    return np.asarray(logits[0, :, : m["vocab_size"]], np.float32)


@pytest.mark.parametrize("m", [GQA, MQA], ids=["gqa-swiglu-tied", "mqa-gelu-untied"])
def test_float32_program_matches_reference(m):
    # Tolerance 1e-4: both sides compute in float32 on the same weights and
    # differ only in the order of summation; logits are of unit scale.
    m = dict(m, dtype="float32")
    w = weights.make_weights(DENSE, m, 5)
    tokens = np.random.default_rng(0).integers(1, m["vocab_size"], 40)
    ref = Reference(DENSE, m).logits(w, tokens)
    got = _program_logits(m, w, tokens)
    assert ref.shape == got.shape
    assert np.max(np.abs(ref - got)) < 1e-4


@pytest.mark.parametrize("m", [GQA, MQA], ids=["gqa-swiglu-tied", "mqa-gelu-untied"])
def test_bfloat16_program_is_near_reference(m):
    # Tolerance 0.1: the served program keeps activations in bfloat16
    # (8 bits of mantissa, relative rounding 4e-3 per op) through two layers
    # and the head; logits are of unit scale.  An error in the block (a
    # missing norm, a wrong rotation) moves logits by about their scale.
    w = weights.make_weights(DENSE, m, 6)
    tokens = np.random.default_rng(1).integers(1, m["vocab_size"], 40)
    ref = Reference(DENSE, m).logits(w, tokens)
    got = _program_logits(m, w, tokens)
    assert np.std(ref) > 0.1  # a tied head at width 64: 0.02 * sqrt(64)
    assert np.max(np.abs(ref - got)) < 0.1


def test_token_gaps_are_zero_for_the_reference_argmax():
    w = weights.make_weights(DENSE, GQA, 7)
    ref = Reference(DENSE, GQA)
    prompt = np.arange(1, 21, dtype=np.int32)
    served = []
    for _ in range(5):
        served.append(int(np.argmax(ref.logits(w, np.r_[prompt, served])[-1])))
    (g,) = ref.token_gaps(w, [(prompt, served)])
    assert g["served"].shape == (5,)
    assert np.max(g["served"]) < 1e-5
    (g,) = ref.token_gaps(w, [(prompt, [(t + 1) % GQA["vocab_size"] for t in served])])
    assert np.min(g["served"]) > 0


def test_fp8_rounding_keeps_three_mantissa_bits_per_scaled_slice():
    x = jax.numpy.asarray(np.random.default_rng(2).normal(size=(8, 64)), jax.numpy.float32)
    q = np.asarray(fp8(x, -1))
    rel = np.abs(q - np.asarray(x)) / np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    assert 0 < np.max(rel) <= 2.0 ** -4
