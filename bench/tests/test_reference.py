"""The plain reference against the program's own forward pass, on the CPU at
a reduced size, both in float32 with the same weights."""
import jax
import numpy as np
import pytest

from bench.drivers.serve_lm import model_config
from bench.reference import dense_lm

HP = {"name": "tiny", "model_type": "qwen2", "hidden_size": 64, "intermediate_size": 96,
      "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
      "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
      "tie_word_embeddings": True, "torch_dtype": "float32"}


@pytest.fixture(scope="module")
def setup():
    from repro.models.model import Model

    cfg = model_config(HP)
    model = Model(cfg)
    params = dense_lm.init_params(123, HP, dtype="float32")
    return model, params


def test_weights_fit_the_program(setup):
    model, params = setup
    want = jax.tree.map(lambda a: (a.shape, a.dtype), model.abstract_params())
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == want


def test_reference_matches_program_forward(setup):
    model, params = setup
    tokens = np.random.default_rng(0).integers(0, 256, 48).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        h, _ = model.forward(params, {"tokens": tokens[None]})
        prog = np.asarray(model._logits(params, h))[0]
    ref = dense_lm.logits(params, HP, tokens)
    assert np.max(np.abs(prog - ref)) <= 1e-4 * np.max(np.abs(ref))


def test_reference_sees_rope_theta_and_eps(setup):
    """A program run at the defaults it would take without the published
    keys (theta 1e4, eps 1e-5) disagrees with the reference."""
    _, params = setup
    tokens = np.random.default_rng(1).integers(0, 256, 48).astype(np.int32)
    ref = dense_lm.logits(params, HP, tokens)
    other = dense_lm.logits(params, dict(HP, rope_theta=1e4), tokens)
    assert np.max(np.abs(other - ref)) > 1e-3 * np.max(np.abs(ref))


def test_served_gaps_zero_for_reference_argmax(setup):
    _, params = setup
    prompt = list(np.random.default_rng(2).integers(0, 256, 16))
    hist = list(prompt)
    for _ in range(6):   # greedy continuation by the reference itself
        hist.append(int(np.argmax(dense_lm.logits(params, HP, np.asarray(hist))[-1])))
    gaps = dense_lm.served_gaps(params, HP, hist, 16, pad_to=32)
    assert gaps.shape == (6,)
    assert np.all(gaps <= 1e-5)
    hist[-1] = (hist[-1] + 1) % 256
    assert dense_lm.served_gaps(params, HP, hist, 16, pad_to=32)[-1] > 0
