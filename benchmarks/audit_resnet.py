"""ResNet-50 on-chip perf audit.

Prints, per batch size: measured img/s, compiled-executable FLOPs/bytes
(profiler.cost_analysis), achieved vs peak FLOPs (MFU), and the HLO fusion
census (how many convolution/fusion ops the compiled step contains — a
conv+BN+ReLU that did NOT fuse shows up as extra elementwise fusions).
Meant for the chip; CPU runs exercise the harness on resnet18 tiny shapes
and their numbers are not device metrics.
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import json
import sys


def main():
    import os

    import jax

    if os.environ.get("PADDLE_TPU_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    from paddle_tpu._core import compile_cache

    compile_cache.enable()
    on_accel = jax.devices()[0].platform != "cpu"

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.device import hard_sync, time_step_ms
    from paddle_tpu.device.peaks import device_peak_tflops
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet18, resnet50

    paddle.seed(0)
    model = resnet50() if on_accel else resnet18()
    B_list = (64, 128, 256) if on_accel else (4,)
    H = 224 if on_accel else 64
    ce = paddle.nn.CrossEntropyLoss()
    opt = paddle.optimizer.Momentum(0.1, parameters=model.parameters())

    def loss_fn(m, x, y):
        with paddle.amp.auto_cast(enable=on_accel):
            return ce(m(x), y)

    step = TrainStep(model, opt, loss_fn)
    rng = np.random.default_rng(0)
    d = jax.devices()[0]
    peak = device_peak_tflops(d.device_kind, d.platform) or 0.0

    for B in B_list:
        x = paddle.to_tensor(rng.standard_normal((B, 3, H, H)).astype(np.float32))
        y = paddle.to_tensor(rng.integers(0, 1000, (B,)).astype(np.int32))
        step(x, y)
        hard_sync(step(x, y))
        ms = time_step_ms(lambda: step(x, y), inner=5 if on_accel else 2)
        ips = B / (ms / 1e3)

        flops = bytes_moved = None
        fusion_census = {}
        try:
            from paddle_tpu import rng as rng_mod

            state_vals = [t._value for t in step._state]
            batch_vals = (x._value, y._value)
            key = rng_mod.next_key()
            lowered = step._compiled.lower(state_vals, batch_vals, key)
            exe = lowered.compile()  # cache hit: already compiled this sig
            cost = exe.cost_analysis()
            cost = cost[0] if isinstance(cost, (list, tuple)) else cost
            cost = dict(cost or {})
            flops = cost.get("flops")
            bytes_moved = cost.get("bytes accessed")
            hlo = exe.as_text()
            for marker in ("convolution", "fusion", "all-reduce", "transpose",
                           "custom-call"):
                fusion_census[marker] = hlo.count(f"{marker}(") + hlo.count(
                    f"{marker}.")
        except Exception as e:  # cost introspection is best-effort
            print(f"audit: cost introspection failed: {e}", file=sys.stderr)

        mfu = None
        if flops and peak:
            mfu = (flops / (ms / 1e3)) / (peak * 1e12)
        print(json.dumps({
            "audit": "resnet",
            "batch": B,
            "images_per_sec": round(ips, 2),
            "step_ms": round(ms, 3),
            "flops_per_step": flops,
            "bytes_per_step": bytes_moved,
            "mfu": round(mfu, 4) if mfu is not None else None,
            "hlo_census": fusion_census,
        }), flush=True)


if __name__ == "__main__":
    main()
