"""Microbenchmark: eager op-dispatch throughput, dispatch cache on vs off.

Measures the hot path this framework actually spends Python time in — the
`apply` funnel (_core/autograd.py) — with FLAGS_eager_op_jit on and off:

- **train**: steps/sec for a small MLP train loop (forward + backward +
  SGD).  With the cache off every op call pays a fresh jax.vjp trace; with
  it on the traced forward+pullback pair is reused — this is the headline
  "repeated-call op throughput" number.
- **grad_ops**: raw differentiable op calls/sec (matmul+tanh chain under
  grad recording, no backward walk) — isolates per-op dispatch cost.
- **fwd_ops**: no-grad composite op calls/sec (softmax chain).  On CPU this
  is roughly break-even (eager jax already serves single primitives from
  its C++ cache; a 1-2 primitive op intentionally stays eager — see
  _core/dispatch._prefers_eager); on a real accelerator the fused single
  dispatch wins.

Prints ONE JSON line shaped like bench.py: {"metric", "value", "unit",
"vs_baseline", ...}.  value is the train-loop speedup (cache on / off);
vs_baseline divides by the 2.0x target, so >= 1.0 means the fast path
delivers.  A CPU benchmark: it forces JAX_PLATFORMS=cpu semantics itself.

Smoke mode (--smoke or PADDLE_TPU_BENCH_SMOKE=1): tiny sizes and iteration
counts so CI can assert the harness emits valid JSON in seconds.  Numerics
parity cache-on vs cache-off is asserted in both modes before timing.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    smoke = "--smoke" in sys.argv or bool(os.environ.get("PADDLE_TPU_BENCH_SMOKE"))

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu import profiler

    if smoke:
        B, D, H, iters, warmup = 2, 8, 16, 5, 2
    else:
        B, D, H, iters, warmup = 16, 64, 128, 200, 10

    rng = np.random.default_rng(0)
    x_np = rng.standard_normal((B, D)).astype(np.float32)
    y_np = rng.standard_normal((B, 1)).astype(np.float32)
    w_np = rng.standard_normal((D, D)).astype(np.float32)

    def build_model():
        paddle.seed(0)
        m = nn.Sequential(nn.Linear(D, H), nn.Tanh(), nn.Linear(H, 1))
        o = opt.SGD(learning_rate=0.01, parameters=m.parameters())
        return m, o

    def train_loop(n, collect=False):
        m, o = build_model()
        x, y = paddle.to_tensor(x_np), paddle.to_tensor(y_np)
        losses = []
        for _ in range(n):
            loss = ((m(x) - y) ** 2).mean()
            loss.backward()
            o.step()
            o.clear_grad()
            if collect:
                losses.append(float(np.asarray(loss._value)))
        return losses

    def grad_ops_loop(n):
        x = paddle.to_tensor(x_np)
        w = paddle.to_tensor(w_np, stop_gradient=False)
        out = None
        for _ in range(n):
            out = paddle.tanh(paddle.matmul(x, w))
        return np.asarray(out._value)

    def fwd_ops_loop(n):
        x = paddle.to_tensor(x_np)
        w = paddle.to_tensor(w_np)
        out = None
        for _ in range(n):
            out = F.softmax(paddle.matmul(x, w), axis=-1)
        return np.asarray(out._value)

    def timed_rate(fn, n):
        fn(warmup)
        t0 = time.perf_counter()
        fn(n)
        return n / (time.perf_counter() - t0)

    # ---- numerics parity gate: cache on must be bit-identical to off
    paddle.set_flags({"FLAGS_eager_op_jit": True})
    on_losses = train_loop(3, collect=True)
    on_g, on_f = grad_ops_loop(3), fwd_ops_loop(3)
    paddle.set_flags({"FLAGS_eager_op_jit": False})
    off_losses = train_loop(3, collect=True)
    off_g, off_f = grad_ops_loop(3), fwd_ops_loop(3)
    numerics_ok = (on_losses == off_losses
                   and np.array_equal(on_g, off_g)
                   and np.array_equal(on_f, off_f))

    # ---- throughput, cache on then off
    results = {}
    for label, fn in (("train", train_loop), ("grad_ops", grad_ops_loop),
                      ("fwd_ops", fwd_ops_loop)):
        paddle.set_flags({"FLAGS_eager_op_jit": True})
        profiler.reset_dispatch_cache()
        on_rate = timed_rate(fn, iters)
        stats = profiler.dispatch_cache_stats()
        paddle.set_flags({"FLAGS_eager_op_jit": False})
        off_rate = timed_rate(fn, iters)
        results[label] = {
            "on_per_sec": round(on_rate, 1),
            "off_per_sec": round(off_rate, 1),
            "speedup": round(on_rate / off_rate, 3),
            "cache_hits": stats["hits"],
            "cache_misses": stats["misses"],
            "cache_traces": stats["traces"],
        }
    paddle.set_flags({"FLAGS_eager_op_jit": True})

    speedup = results["train"]["speedup"]
    print(
        json.dumps(
            {
                "metric": "eager_dispatch_cached_train_speedup",
                "value": speedup,
                "unit": "x",
                "vs_baseline": round(speedup / 2.0, 4),  # target: >= 2x
                "numerics_identical": bool(numerics_ok),
                "detail": results,
                "config": "smoke" if smoke else f"mlp_{D}x{H}_B{B}_it{iters}",
            }
        ),
        flush=True,
    )
    return 0 if numerics_ok else 4


if __name__ == "__main__":
    sys.exit(main())
