"""Inference Config/Predictor depth: precision variants, weight-only int8,
warn-or-work switches, warmup, profiling, clone.

Reference: paddle/fluid/inference/api/paddle_analysis_config.h:676
(Precision modes, EnableTensorRtEngine), analysis_predictor.h:100
(Clone, profiling); the variant model is the TRT build-per-precision
engine flow re-done for XLA (built at export, selected at load).
"""

import os
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import static
from paddle_tpu import inference


def _export_mlp(tmp_path, **save_kwargs):
    paddle.seed(11)
    l1, l2 = nn.Linear(64, 256), nn.Linear(256, 16)
    main = static.Program()
    with static.program_guard(main):
        x = static.data("x", [4, 64], "float32")
        out = l2(paddle.tanh(l1(x)))
    prefix = str(tmp_path / "m" / "net")
    static.save_inference_model(prefix, [x], [out], static.Executor(),
                                program=main, **save_kwargs)
    xv = np.random.default_rng(0).standard_normal((4, 64)).astype(np.float32)
    ref = np.tanh(xv @ np.asarray(l1.weight._value) + np.asarray(l1.bias._value))
    ref = ref @ np.asarray(l2.weight._value) + np.asarray(l2.bias._value)
    return prefix, xv, ref


def test_weight_only_int8_export_serves_close_and_smaller(tmp_path):
    prefix, xv, ref = _export_mlp(tmp_path)
    fp32_size = os.path.getsize(prefix + ".pdmodel")

    prefix8, _, _ = _export_mlp(tmp_path / "q", precision="int8")
    int8_size = os.path.getsize(prefix8 + ".pdmodel")
    pred = inference.Predictor(prefix8)
    (ov,) = pred.run([xv])
    # per-channel int8 weight quantization: close, not bit-equal
    assert np.abs(ov - ref).max() < 0.05 * max(1.0, np.abs(ref).max())
    # int8 weights baked -> artifact visibly smaller than the fp32 one
    assert int8_size < fp32_size * 0.6, (int8_size, fp32_size)


def _dequant_oracle(W, bits):
    W32 = np.asarray(W, np.float32)
    amax = np.abs(W32).max(axis=0)
    qmax = 7.0 if bits == 4 else 127.0
    scale = np.where(amax > 0, amax / qmax, 1.0).astype(np.float32)
    q = np.clip(np.round(W32 / scale), -qmax - 1, qmax)
    return q * scale


def test_weight_only_int4_export_matches_dequant_oracle(tmp_path):
    paddle.seed(11)
    l1, l2 = nn.Linear(64, 256), nn.Linear(256, 16)
    main = static.Program()
    with static.program_guard(main):
        x = static.data("x", [4, 64], "float32")
        out = l2(paddle.tanh(l1(x)))
    prefix = str(tmp_path / "m4" / "net")
    static.save_inference_model(prefix, [x], [out], static.Executor(),
                                program=main, precision="weight_only_int4")
    xv = np.random.default_rng(0).standard_normal((4, 64)).astype(np.float32)
    (ov,) = inference.Predictor(prefix).run([xv])
    # exact oracle: the served program must equal fake-quantized numpy math
    w1 = _dequant_oracle(l1.weight._value, 4)
    w2 = _dequant_oracle(l2.weight._value, 4)
    ref = np.tanh(xv @ w1 + np.asarray(l1.bias._value)) @ w2 + np.asarray(
        l2.bias._value)
    np.testing.assert_allclose(ov, ref, atol=2e-5, rtol=1e-4)


def test_precision_variant_selected_at_load(tmp_path):
    prefix, xv, ref = _export_mlp(
        tmp_path, extra_precisions=["bfloat16", "weight_only_int8"])
    assert os.path.exists(prefix + ".bfloat16.pdmodel")

    cfg = inference.Config(prefix)
    cfg.set_precision(inference.PrecisionType.Bfloat16)
    (ov,) = inference.create_predictor(cfg).run([xv])
    np.testing.assert_allclose(ov, ref, atol=0.1, rtol=0.1)  # bf16 tolerance

    cfg8 = inference.Config(prefix)
    cfg8.set_precision("int8")
    (ov8,) = inference.create_predictor(cfg8).run([xv])
    assert np.abs(ov8 - ref).max() < 0.05 * max(1.0, np.abs(ref).max())


def test_missing_int8_variant_raises_listing_available(tmp_path):
    prefix, _, _ = _export_mlp(tmp_path)
    cfg = inference.Config(prefix)
    cfg.set_precision("int8")
    with pytest.raises(RuntimeError, match="float32"):
        inference.create_predictor(cfg)


def test_bf16_without_variant_warns_and_serves_fp32(tmp_path):
    prefix, xv, ref = _export_mlp(tmp_path)
    cfg = inference.Config(prefix)
    cfg.set_precision("bf16")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        pred = inference.create_predictor(cfg)
    assert any("no such variant" in str(x.message) for x in w)
    (ov,) = pred.run([xv])
    np.testing.assert_allclose(ov, ref, atol=1e-5)


def test_config_switches_work_or_warn(tmp_path):
    cfg = inference.Config()
    for call in (
        lambda: cfg.enable_memory_optim(),
        lambda: cfg.switch_ir_optim(False),
        lambda: cfg.enable_mkldnn(),
        lambda: cfg.set_cpu_math_library_num_threads(4),
        lambda: cfg.enable_tensorrt_engine(precision="float16"),
        lambda: cfg.enable_use_gpu(memory_pool_init_size_mb=512),
    ):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            call()
        assert w, f"{call} silently did nothing"
    # the TRT precision request DID carry over
    assert cfg._precision == "float16"
    # working switches do their thing quietly
    from paddle_tpu._core import compile_cache

    cfg.set_optim_cache_dir(compile_cache.enable())  # the suite's own cache
    cfg.disable_glog_info()
    with pytest.raises(ValueError):
        cfg.set_precision("int3")


def test_warmup_profile_and_clone(tmp_path):
    prefix, xv, ref = _export_mlp(tmp_path)
    cfg = inference.Config(prefix)
    cfg.enable_warmup()
    cfg.enable_profile()
    pred = inference.create_predictor(cfg)  # warmup ran inside
    (ov,) = pred.run([xv])
    np.testing.assert_allclose(ov, ref, atol=1e-5)
    stats = pred.profile_stats()
    assert stats["count"] == 1 and stats["last_ms"] > 0.0

    twin = pred.clone()
    h = twin.get_input_handle("x")
    h.copy_from_cpu(xv)
    (tv,) = twin.run()
    np.testing.assert_allclose(tv, ov, atol=1e-6)
    # bindings are separate, weights shared
    assert twin._inputs is not pred._inputs
    assert twin._exported is pred._exported
    assert twin.profile_stats()["count"] == 1  # its own counters


def test_llama_int8_predictor_path(tmp_path):
    """The quantized-LLM serving path end-to-end (VERDICT r4 item 4):
    jit.save tiny-LLaMA logits with weight-only int8 -> Predictor serves
    them close to the fp32 eager forward, from a visibly smaller artifact."""
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    import paddle_tpu.jit as jit

    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny(dtype="float32"))
    m.eval()
    ids = np.random.default_rng(1).integers(1, 250, (1, 12)).astype(np.int32)
    with paddle.no_grad():
        out = m(paddle.to_tensor(ids))
        ref = np.asarray((out[0] if isinstance(out, (tuple, list)) else out)._value)

    path = str(tmp_path / "llama_fp32")
    jit.save(m, path, input_spec=[static.InputSpec([1, 12], "int32", "ids")])
    path8 = str(tmp_path / "llama_int8")
    jit.save(m, path8, input_spec=[static.InputSpec([1, 12], "int32", "ids")],
             precision="int8")
    assert os.path.getsize(path8 + ".pdmodel") < os.path.getsize(path + ".pdmodel") * 0.6

    pred = inference.Predictor(path8)
    (logits,) = pred.run([ids])
    if logits.ndim == ref.ndim + 1 and logits.shape[0] == 1 and ref.shape[0] != 1:
        logits = logits[0]
    # int8 weight-only: argmax (the decoded tokens) should agree almost
    # everywhere and values stay close
    agree = (logits.argmax(-1) == ref.argmax(-1)).mean()
    assert agree >= 0.9, agree
    assert np.abs(logits - ref).max() < 0.25 * max(1.0, np.abs(ref).max())


def test_int8_export_bakes_trained_scope_weights(tmp_path):
    """Executor training persists params to the SCOPE (param_inits keeps the
    init); the quant pass must bake the trained values, not the inits."""
    import jax.numpy as jnp
    from paddle_tpu.static.executor import global_scope

    paddle.seed(2)
    l = nn.Linear(16, 8)
    main = static.Program()
    with static.program_guard(main):
        x = static.data("x", [2, 16], "float32")
        out = l(x)
    exe = static.Executor()
    xv = np.random.default_rng(4).standard_normal((2, 16)).astype(np.float32)
    exe.run(main, feed={"x": xv}, fetch_list=[out])  # materialize scope state
    scope = global_scope()
    wvid = next(v for v in main.param_inits
                if tuple(np.shape(main.param_inits[v])) == (16, 8))
    trained = np.full((16, 8), 0.5, np.float32)  # quantizes EXACTLY (q=127)
    scope.set_var(wvid, jnp.asarray(trained))

    prefix = str(tmp_path / "net")
    static.save_inference_model(prefix, [x], [out], exe, program=main,
                                precision="int8")
    (ov,) = inference.Predictor(prefix).run([xv])
    ref = xv @ trained + np.asarray(l.bias._value)
    np.testing.assert_allclose(ov, ref, atol=1e-5)


def test_precision_alias_matches_export_at_load(tmp_path):
    """'int8' at export and 'int8' at load must meet in one canonical name
    (the manifest stores weight_only_int8)."""
    prefix, xv, _ = _export_mlp(tmp_path, precision="int8")
    import json as _json

    with open(prefix + ".json") as f:
        assert _json.load(f)["precision"] == "weight_only_int8"
    cfg = inference.Config(prefix)
    cfg.set_precision("int8")  # alias -> canonical -> matches main artifact
    pred = inference.create_predictor(cfg)
    pred.run([xv])
