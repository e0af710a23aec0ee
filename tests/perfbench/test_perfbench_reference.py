"""The plain reference agrees with the program's own forward pass and loss
at a tiny size on the CPU (float32), GQA included."""

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness

ref = harness.load_module("perfbench/reference/llama_like.py")
adapter = harness.load_module("perfbench/adapters/serve_llama.py")

MODEL = {"vocab_size": 128, "hidden_size": 64, "intermediate_size": 96,
         "num_hidden_layers": 3, "num_attention_heads": 8,
         "num_key_value_heads": 2, "max_position_embeddings": 64,
         "rms_norm_eps": 1e-5, "rope_theta": 1e6,
         "tie_word_embeddings": False, "sliding_window": None}


@pytest.fixture(scope="module")
def setup():
    from paddle_tpu.models import llama as L
    cfg = adapter.llama_config(MODEL, "float32")
    params = L.init_stacked_params(cfg, seed=3)
    # norm weights away from 1, so that a forgotten norm weight shows
    params["ln1"] = params["ln1"] * 1.3
    params["ln_f"] = params["ln_f"] * 0.7
    ids = np.random.default_rng(0).integers(0, 128, (2, 24), dtype=np.int32)
    return L, cfg, params, ids


@pytest.mark.parametrize("head_block", [8, 3])
def test_forward_agrees_with_forward_stacked(setup, head_block):
    L, cfg, params, ids = setup
    want = L.forward_stacked(params, jnp.asarray(ids), cfg)
    got = ref.forward(adapter.ReferenceWeights(params), ids, MODEL,
                      head_block=head_block)
    assert got.dtype == jnp.float32 and got.shape == (2, 24, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_loss_agrees_with_loss_stacked(setup):
    L, cfg, params, ids = setup
    labels = np.roll(ids, -1, axis=-1)
    want = float(L.loss_stacked(params, jnp.asarray(ids),
                                jnp.asarray(labels), cfg))
    got = ref.loss(adapter.ReferenceWeights(params), ids, labels, MODEL)
    assert got == pytest.approx(want, rel=1e-5)


def test_reference_is_causal(setup):
    _, _, params, ids = setup
    w = adapter.ReferenceWeights(params)
    other = ids.copy()
    other[:, 12:] = (other[:, 12:] + 1) % 128
    a, b = ref.forward(w, ids, MODEL), ref.forward(w, other, MODEL)
    np.testing.assert_array_equal(np.asarray(a[:, :12]), np.asarray(b[:, :12]))
    assert not np.allclose(np.asarray(a[:, 12:]), np.asarray(b[:, 12:]))
