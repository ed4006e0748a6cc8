"""PP-YOLOE-class detector training throughput (BASELINE.json row 4).

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
    from paddle_tpu.vision.models import ppyolo_s, ppyolo_tiny

    paddle.seed(0)
    model = ppyolo_s() if on_accel else ppyolo_tiny(num_classes=4)
    B, H = (32, 320) if on_accel else (2, 64)
    iters = 10 if on_accel else 2
    opt = paddle.optimizer.Momentum(0.01, parameters=model.parameters())

    def loss_fn(m, x):
        with paddle.amp.auto_cast(enable=on_accel):
            outs = m(x)
        # dense surrogate objective over the head maps: exercises the full
        # backbone/neck/head compute the detection losses ride on
        return sum((o.astype("float32") ** 2).mean() for o in outs)

    step = TrainStep(model, opt, loss_fn)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((B, 3, H, H)).astype(np.float32))
    step(x)
    hard_sync(step(x))
    from paddle_tpu.device import time_step_ms

    rate_denom_s = time_step_ms(lambda: step(x), inner=iters) / 1e3
    print(json.dumps({
        "metric": "ppyolo_train_images_per_sec",
        "value": round(B / rate_denom_s, 2),
        "unit": "images/s",
        "vs_baseline": 0.0,
        "batch": B,
    }))


if __name__ == "__main__":
    sys.exit(main())
