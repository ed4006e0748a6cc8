"""BERT-base finetune throughput (BASELINE.json row 3)."""

from __future__ import annotations

import json
import sys

import os

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import os

    import jax

    if os.environ.get("PADDLE_TPU_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    from paddle_tpu._core import compile_cache

    compile_cache.enable()
    on_accel = jax.devices()[0].platform != "cpu"

    import paddle_tpu as paddle
    from paddle_tpu.device import hard_sync
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import BertConfig, BertForSequenceClassification, bert_tiny

    paddle.seed(0)
    cfg = BertConfig(num_hidden_layers=12) if on_accel else bert_tiny()
    B, S = (32, 128) if on_accel else (4, 32)
    iters = 10 if on_accel else 2
    import contextlib

    cpu = None
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:
        pass
    with (jax.default_device(cpu) if cpu else contextlib.nullcontext()):
        model = BertForSequenceClassification(cfg)
    opt = paddle.optimizer.AdamW(2e-5, parameters=model.parameters())

    def loss_fn(m, i, y):
        with paddle.amp.auto_cast(enable=on_accel):
            return m(i, labels=y)[0]

    step = TrainStep(model, opt, loss_fn)
    rng = np.random.default_rng(0)
    amp_level = "O1"

    from paddle_tpu.device import time_step_ms

    def measure(batch):
        ids = paddle.to_tensor(rng.integers(1, cfg.vocab_size, (batch, S)).astype(np.int32))
        y = paddle.to_tensor(rng.integers(0, 2, (batch,)).astype(np.int32))
        step(ids, y)
        hard_sync(step(ids, y))
        ms = time_step_ms(lambda: step(ids, y), inner=iters)
        return batch * S / (ms / 1e3)

    if on_accel:
        # batch sweep, largest first (the A100 point is a large-batch AMP
        # run; B=32 under-fills the v5e MXU) — OOM falls through
        tokens_per_sec = 0.0
        for batch in (256, 128, 64, 32):
            try:
                tps = measure(batch)
            except Exception as e:  # noqa: BLE001
                msg = f"{type(e).__name__}: {e}"
                print(f"bench_bert: B={batch} failed ({msg[:200]})",
                      file=sys.stderr)
                if "RESOURCE_EXHAUSTED" not in msg and "Out of memory" not in msg:
                    raise
                continue
            if tps > tokens_per_sec:
                tokens_per_sec, B = tps, batch
        if tokens_per_sec == 0.0:
            raise SystemExit("bench_bert: every sweep batch hit device OOM")
        # O2 arm at the winning batch: bf16 params + fp32 masters cut the
        # per-op cast traffic of O1 (the A100 point is full AMP)
        try:
            with (jax.default_device(cpu) if cpu else contextlib.nullcontext()):
                model2 = BertForSequenceClassification(cfg)
            opt2 = paddle.optimizer.AdamW(2e-5, parameters=model2.parameters())
            model2, opt2 = paddle.amp.decorate(model2, opt2, level="O2")

            def loss_fn2(m, i, y):
                with paddle.amp.auto_cast(enable=True, level="O2"):
                    return m(i, labels=y)[0]

            step2 = TrainStep(model2, opt2, loss_fn2)
            ids = paddle.to_tensor(rng.integers(1, cfg.vocab_size, (B, S)).astype(np.int32))
            y = paddle.to_tensor(rng.integers(0, 2, (B,)).astype(np.int32))
            step2(ids, y)
            hard_sync(step2(ids, y))
            tps_o2 = B * S / (time_step_ms(lambda: step2(ids, y), inner=iters) / 1e3)
            if tps_o2 > tokens_per_sec:
                tokens_per_sec, amp_level = tps_o2, "O2"
        except Exception as e:  # additive arm: never sinks the bench
            print(f"bench_bert: O2 arm failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
    else:
        tokens_per_sec = measure(B)

    # vs_baseline: peak-normalized chip-efficiency parity against the
    # written-down A100 reference point (NVIDIA DeepLearningExamples
    # BERT-base phase-1 AMP, ~8.7k seq/s on 8xA100): S=128 1xA100 =
    # 139,264 tok/s (1,088 seq/s).
    from paddle_tpu.device.peaks import A100_PEAK_TFLOPS, device_peak_tflops

    d = jax.devices()[0]
    peak = device_peak_tflops(d.device_kind, d.platform)
    vs_baseline = (tokens_per_sec / peak) / (139264.0 / A100_PEAK_TFLOPS) if peak else 0.0
    print(json.dumps({
        "metric": "bert_finetune_tokens_per_sec",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s",
        "vs_baseline": round(vs_baseline, 4),
        "batch": B,
        "amp": amp_level,
    }))


if __name__ == "__main__":
    sys.exit(main())
