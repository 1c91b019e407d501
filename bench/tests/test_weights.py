"""The benchmark's weights: made from the seed, laid into the program's tree."""

import jax
import numpy as np
import pytest

import weights
from small import DENSE, GQA, MQA

from repro.models import Model
from repro.models.config import ModelConfig


@pytest.mark.parametrize("m", [GQA, MQA], ids=["gqa", "mqa"])
def test_weights_fill_every_leaf_of_the_program(m):
    model = Model(ModelConfig(**m))
    abstract, _ = model.init(None, abstract=True)
    params = weights.program_params(abstract, weights.make_weights(DENSE, m, 3))
    shapes = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    assert shapes == jax.tree.map(lambda x: (x.shape, x.dtype), abstract)


def test_same_seed_same_weights_and_seeds_past_32_bits_differ():
    a = weights.make_weights(DENSE, GQA, 2**32 + 1)
    b = weights.make_weights(DENSE, GQA, 2**32 + 1)
    c = weights.make_weights(DENSE, GQA, 1)
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not np.array_equal(np.asarray(a["layers.wq"]), np.asarray(c["layers.wq"]))


def test_scales_keep_logits_near_unit():
    w = weights.make_weights(DENSE, GQA, 0)
    assert abs(float(np.std(np.asarray(w["embed"], np.float32))) - 0.02) < 0.002
    d = GQA["d_model"]
    wq = np.asarray(w["layers.wq"], np.float32)
    assert abs(float(np.std(wq)) * np.sqrt(d) - 1.0) < 0.1
    assert np.all(np.asarray(w["layers.ln1"], np.float32) == 1.0)
    # each layer slice is its own draw
    assert not np.array_equal(wq[0], wq[1])


def test_a_leaf_the_benchmark_does_not_make_is_an_error():
    model = Model(ModelConfig(**dict(GQA, qk_norm=True)))
    abstract, _ = model.init(None, abstract=True)
    with pytest.raises(KeyError):
        weights.program_params(abstract, weights.make_weights(DENSE, GQA, 0))
