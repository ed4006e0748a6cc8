"""ERNIE-MoE-shaped semi-auto training throughput (BASELINE.json stretch row).

Prints ONE JSON line like bench.py.  vs_baseline is 0.0 ("track" level).
Single-chip runs exercise the dense expert compute + gating; the EP
all-to-all path is validated by dryrun_multichip / tests/test_moe.py on
the virtual mesh."""

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
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.incubate.distributed.models.moe import MoELayer
    from paddle_tpu.device import hard_sync
    from paddle_tpu.jit import TrainStep

    d, n_exp, V = (512, 8, 32000) if on_accel else (32, 4, 128)
    B, S = (8, 1024) if on_accel else (2, 16)
    iters = 10 if on_accel else 2

    def expert(i):
        paddle.seed(100 + i)
        return nn.Sequential(nn.Linear(d, 2 * d), nn.Silu(), nn.Linear(2 * d, d))

    class MoEBlock(nn.Layer):
        def __init__(self):
            super().__init__()
            self.norm = nn.LayerNorm(d)
            self.attn = nn.MultiHeadAttention(d, 8 if on_accel else 2)
            self.norm2 = nn.LayerNorm(d)
            self.moe = MoELayer(d, [expert(i) for i in range(n_exp)],
                                gate="gshard", capacity_factor=2.0)

        def forward(self, h):
            h = h + self.attn(self.norm(h))
            return h + self.moe(self.norm2(h))

    class MoELM(nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = nn.Embedding(V, d)
            self.blocks = nn.LayerList([MoEBlock(), MoEBlock()])
            self.head = nn.Linear(d, V)

        def forward(self, ids):
            h = self.emb(ids)
            for b in self.blocks:
                h = b(h)
            return self.head(h)

        def aux_loss(self):
            import functools

            losses = [b.moe.aux_loss for b in self.blocks if b.moe.aux_loss is not None]
            return functools.reduce(lambda a, c: a + c, losses) if losses else None

    paddle.seed(0)
    model = MoELM()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())

    def loss_fn(m, ids, labels):
        with paddle.amp.auto_cast(enable=on_accel):
            logits = m(ids)
        loss = F.cross_entropy(
            logits.astype("float32").reshape([-1, V]), labels.reshape([-1]))
        aux = m.aux_loss()
        return loss + 0.01 * aux.astype("float32") if aux is not None else loss

    step = TrainStep(model, opt, loss_fn)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, V, (B, S)).astype(np.int32))
    labels = paddle.to_tensor(rng.integers(0, V, (B, S)).astype(np.int64))
    step(ids, labels)
    hard_sync(step(ids, labels))
    from paddle_tpu.device import time_step_ms

    rate_denom_s = time_step_ms(lambda: step(ids, labels), inner=iters) / 1e3
    print(json.dumps({
        "metric": "moe_train_tokens_per_sec",
        "value": round(B * S / rate_denom_s, 2),
        "unit": "tokens/s",
        "vs_baseline": 0.0,
        "batch": B,
    }))


if __name__ == "__main__":
    sys.exit(main())
