"""PP-OCR-class recognizer training throughput (BASELINE.json row 4).

Prints ONE JSON line like bench.py.  vs_baseline is 0.0 ("track" level —
BASELINE.json records no written-down A100 reference point for this row)."""

from __future__ import annotations

import json
import os
import sys

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
    from paddle_tpu.vision.models import CRNN, ppocr_rec_tiny

    paddle.seed(0)
    model = CRNN(num_classes=96) if on_accel else ppocr_rec_tiny(num_classes=16)
    B, W, L = (64, 320, 24) if on_accel else (2, 48, 3)
    iters = 10 if on_accel else 2
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((B, 3, 32, W)).astype(np.float32))
    labels = paddle.to_tensor(
        rng.integers(1, model.num_classes + 1, (B, L)).astype(np.int64))
    lens = paddle.to_tensor(np.full((B,), L, np.int64))

    def loss_fn(m, xb, lb, ln):
        with paddle.amp.auto_cast(enable=on_accel):
            logp = m(xb)
        return m.loss(logp.astype("float32"), lb, ln)

    step = TrainStep(model, opt, loss_fn)
    step(x, labels, lens)
    hard_sync(step(x, labels, lens))
    from paddle_tpu.device import time_step_ms

    rate_denom_s = time_step_ms(lambda: step(x, labels, lens), inner=iters) / 1e3
    print(json.dumps({
        "metric": "ppocr_rec_train_images_per_sec",
        "value": round(B / rate_denom_s, 2),
        "unit": "images/s",
        "vs_baseline": 0.0,
        "batch": B,
    }))


if __name__ == "__main__":
    sys.exit(main())
