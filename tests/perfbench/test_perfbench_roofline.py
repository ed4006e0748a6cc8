"""roofline.py against the hand sums of ISSUE 23, and the peaks table."""

import json
import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)

from perfbench import roofline  # noqa: E402


def _cfg(name):
    with open(os.path.join(_REPO, "perfbench", "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_layer_is_218_million_and_state_fits_depth_two():
    cfg = _cfg("mistral-7b")
    # 2 x 4096^2 + 2 x 4096 x 1024 + 3 x 4096 x 14336 + 2 x 4096
    assert roofline.layer_params(cfg) == 33554432 + 8388608 + 176160768 + 8192
    assert round(roofline.layer_params(cfg) / 1e6) == 218
    assert cfg["num_hidden_layers"] == 2 and cfg["published"]["num_hidden_layers"] == 32
    # (2 x 218 M + 268 M) x 14 B = 9.9 GB of train state
    assert roofline.model_params(cfg) * 14 / 1e9 == pytest.approx(9.87, abs=0.01)
    head_and_embedding = 2 * cfg["vocab_size"] * cfg["hidden_size"]
    assert head_and_embedding / roofline.model_params(cfg) == pytest.approx(0.38, abs=0.005)
    # 3.6 GFLOP a trained token at 4096
    assert roofline.train_flops_per_token(cfg, 4096) / 1e9 == pytest.approx(3.62, abs=0.01)


def test_internlm2_is_1_889_billion_and_98304_bytes_of_kv_a_token():
    cfg = _cfg("internlm2-1.8b")
    assert roofline.kv_bytes_per_token(cfg) == 24 * 2 * 8 * 128 * 2 == 98304
    assert roofline.model_params(cfg) == pytest.approx(1.889e9, rel=1e-3)
    assert roofline.weight_bytes(cfg) / 1e9 == pytest.approx(3.78, abs=0.005)
    # the pool of both serving cells: 4096 blocks of 16 tokens
    assert 4096 * 16 * roofline.kv_bytes_per_token(cfg) / 1e9 == pytest.approx(6.44, abs=0.005)


def test_least_times_are_bytes_or_flops_over_the_published_peaks():
    cfg = _cfg("internlm2-1.8b")
    facts = {"live_kv_tokens": 10000, "rows": 32}
    least = roofline.decode_token_step_min_s(cfg, facts, "TPU v5 lite")
    assert least == pytest.approx((roofline.weight_bytes(cfg) + 10000 * 98304) / 819e9)
    m = _cfg("mistral-7b")
    step = roofline.train_step_min_s(m, {"batch": 1, "seq": 4096}, "TPU v5 lite")
    assert step == pytest.approx(4096 * roofline.train_flops_per_token(m, 4096) / 197e12)
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("TPU v9")
    assert set(roofline.FUNCTIONS) >= {"train_step_min_s", "decode_token_step_min_s"}
