"""ResNet-50 ImageNet-shape training throughput (BASELINE.json row 2).

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline"} like
bench.py; vs_baseline tracks images/sec against the Paddle-on-A100
reference point once recorded (none published in-repo — BASELINE.json)."""

from __future__ import annotations

import json
import sys

import os

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    if os.environ.get("PADDLE_TPU_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    from paddle_tpu._core import compile_cache

    compile_cache.enable()
    on_accel = jax.devices()[0].platform != "cpu"

    import paddle_tpu as paddle
    from paddle_tpu.device import hard_sync
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50, resnet18

    paddle.seed(0)
    cpu = None
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:
        pass
    import contextlib

    with (jax.default_device(cpu) if cpu else contextlib.nullcontext()):
        model = resnet50() if on_accel else resnet18()
    B, H = (64, 224) if on_accel else (4, 64)
    iters = 10 if on_accel else 2
    opt = paddle.optimizer.Momentum(0.1, parameters=model.parameters())
    ce = paddle.nn.CrossEntropyLoss()

    def loss_fn(m, x, y):
        # AMP O1 (bf16 matmul/conv inputs, fp32 loss) — the config the
        # reference's A100 ResNet baseline uses (fp16 AMP there).
        with paddle.amp.auto_cast(enable=on_accel):
            return ce(m(x), y)

    step = TrainStep(model, opt, loss_fn)
    rng = np.random.default_rng(0)

    def measure(batch, n_iters):
        x = paddle.to_tensor(rng.standard_normal((batch, 3, H, H)).astype(np.float32))
        y = paddle.to_tensor(rng.integers(0, 1000, (batch,)).astype(np.int32))
        step(x, y)
        hard_sync(step(x, y))
        from paddle_tpu.device import time_step_ms

        return batch / (time_step_ms(lambda: step(x, y), inner=n_iters) / 1e3)

    amp_level = "O1"
    if on_accel:
        # batch sweep: the MXU wants large batches (the A100 reference point
        # runs B=256-class AMP batches); pick the best-throughput config
        # that fits, largest first so an OOM falls through to smaller B
        images_per_sec, best_b = 0.0, B
        for batch in (512, 256, 128, 64):
            try:
                ips = measure(batch, iters)
            except Exception as e:
                # only resource exhaustion is an expected sweep outcome;
                # anything else is a real regression and must be visible
                msg = f"{type(e).__name__}: {e}"
                print(f"bench_resnet: B={batch} failed ({msg[:200]})",
                      file=sys.stderr)
                if "RESOURCE_EXHAUSTED" not in msg and "Out of memory" not in msg:
                    raise
                continue
            if ips > images_per_sec:
                images_per_sec, best_b = ips, batch
        B = best_b
        if images_per_sec == 0.0:
            images_per_sec = measure(B, iters)
        # O2 arm: bf16 parameters + fp32 master weights — less cast traffic
        # per step than O1's per-op casts (the A100 reference point is full
        # AMP); keep whichever measures faster at the winning batch
        try:
            model2 = resnet50()
            opt2 = paddle.optimizer.Momentum(0.1, parameters=model2.parameters())
            model2, opt2 = paddle.amp.decorate(model2, opt2, level="O2")

            def loss_fn2(m, x, y):
                with paddle.amp.auto_cast(enable=True, level="O2"):
                    return ce(m(x), y)

            step2 = TrainStep(model2, opt2, loss_fn2)
            x = paddle.to_tensor(rng.standard_normal((B, 3, H, H)).astype(np.float32))
            y = paddle.to_tensor(rng.integers(0, 1000, (B,)).astype(np.int32))
            step2(x, y)
            hard_sync(step2(x, y))
            from paddle_tpu.device import time_step_ms

            ips_o2 = B / (time_step_ms(lambda: step2(x, y), inner=iters) / 1e3)
            if ips_o2 > images_per_sec:
                images_per_sec, amp_level = ips_o2, "O2"
        except Exception as e:  # O2 arm is additive: never sinks the bench
            print(f"bench_resnet: O2 arm failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
    else:
        images_per_sec = measure(B, iters)

    # vs_baseline: peak-normalized chip-efficiency parity against the
    # written-down A100 reference point (NVIDIA DeepLearningExamples
    # PaddlePaddle ResNet-50 AMP, ~23.2k img/s on 8xA100): 1xA100 =
    # 2,900 img/s.
    # vs_baseline = (ours/our_peak) / (2900/A100_peak).
    from paddle_tpu.device.peaks import A100_PEAK_TFLOPS, device_peak_tflops

    d = jax.devices()[0]
    peak = device_peak_tflops(d.device_kind, d.platform)
    vs_baseline = (images_per_sec / peak) / (2900.0 / A100_PEAK_TFLOPS) if peak else 0.0
    print(json.dumps({
        "metric": "resnet_train_images_per_sec",
        "value": round(images_per_sec, 2),
        "unit": "images/s",
        "vs_baseline": round(vs_baseline, 4),
        "batch": B,
        "amp": amp_level,
    }))


if __name__ == "__main__":
    sys.exit(main())
