"""reference.py (plain jnp, float32) against the program's LlamaForCausalLM
at a tiny size on the CPU, through the family module that hands the weights
over.  float32 against float32, so the tolerance is rounding only."""

import os
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)

from perfbench import reference  # noqa: E402
from perfbench.families import dense_gqa  # noqa: E402

TINY = {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 128,
        "max_position_embeddings": 64, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
        "torch_dtype": "float32"}


def test_logits_and_loss_agree_with_the_programs_model():
    import paddle_tpu as paddle

    model = dense_gqa.build(TINY, seed=3, training=False)
    rng = np.random.default_rng(0)
    stream = rng.integers(0, TINY["vocab_size"], (2, 25)).astype(np.int32)
    ids, labels = stream[:, :-1], stream[:, 1:]
    with paddle.no_grad():
        loss, logits = model(paddle.to_tensor(ids), labels=paddle.to_tensor(labels))
    want = np.asarray(logits._value, np.float32)
    weights = dense_gqa.reference_weights(model)
    sizes = dense_gqa.reference_sizes(TINY)
    got = np.asarray(reference.logits_at(weights, sizes, ids[0], [0, 7, 23]))
    # fp32 both sides; only the order of summation differs
    np.testing.assert_allclose(got, want[0, [0, 7, 23]], rtol=2e-4, atol=2e-5)
    # padding after the read positions changes nothing (causal)
    padded = np.pad(ids[0], (0, 8))
    np.testing.assert_allclose(
        np.asarray(reference.logits_at(weights, sizes, padded, [0, 7, 23])),
        got, rtol=1e-5, atol=1e-6)
    ref_loss = reference.mean_cross_entropy(weights, sizes, ids, labels, rows=16)
    assert abs(ref_loss - float(loss)) < 1e-4 * abs(ref_loss)


def test_the_family_refuses_what_the_model_cannot_express():
    import pytest

    for bad in ({"sliding_window": 4096}, {"head_dim": 32}, {"bias": True},
                {"hidden_act": "gelu"}, {"torch_dtype": "float8"}):
        with pytest.raises(ValueError):
            dense_gqa.check({**TINY, **bad})
