"""Closed-loop training: `jit.TrainStep` + AdamW (fp32 master weights), steps
back to back over a small pool of seeded batches.

Cell parameters (`train`): batch, seq, pool (seeded batches cycled, a new one
every step), loss_every (the loss is read — and the host waits for the device
— every so many steps), learning_rate, weight_decay, warm_steps,
loss_rtol (first loss against the plain reference).
"""

from __future__ import annotations

import math
import time

import numpy as np

from perfbench import roofline, traffic


def _batches(cfg, p, seed):
    """`pool` batches of next-token pairs: ids [B, S] and the same stream
    shifted by one as labels."""
    import paddle_tpu as paddle

    rng = traffic.rng(seed)
    out = []
    for _ in range(p["pool"]):
        stream = rng.integers(0, cfg["vocab_size"],
                              (p["batch"], p["seq"] + 1)).astype(np.int32)
        out.append((stream[:, :-1], stream[:, 1:]))
    return out, [(paddle.to_tensor(i), paddle.to_tensor(l)) for i, l in out]


def run(ctx) -> dict:
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.jit import TrainStep

    cfg, p, fam, rec = ctx.config, ctx.cell["train"], ctx.family, ctx.rec
    tokens_per_step = p["batch"] * p["seq"]
    host_batches, batches = _batches(cfg, p, ctx.seed)

    t = time.perf_counter()
    model = fam.build(cfg, ctx.seed, training=True)
    jax.block_until_ready([q._value for q in model.parameters()])
    ctx.say(f"model built in {time.perf_counter() - t:.1f} s")

    # the reference, before any update and before the optimizer state exists
    t = time.perf_counter()
    reference = ctx.reference()
    ref_loss = reference.mean_cross_entropy(
        fam.reference_weights(model), fam.reference_sizes(cfg),
        host_batches[0][0], host_batches[0][1])
    ctx.say(f"reference loss of batch 0 before any update {ref_loss:.6f} "
            f"({time.perf_counter() - t:.1f} s)")

    opt = paddle.optimizer.AdamW(learning_rate=p["learning_rate"],
                                 parameters=model.parameters(),
                                 weight_decay=p["weight_decay"])
    step = TrainStep(model, opt, fam.loss_fn)
    losses = []          # device scalars, read after the window
    t = time.perf_counter()
    for i in range(p["warm_steps"]):
        losses.append(step(*batches[i % len(batches)])._value)
        if i == 0:
            jax.block_until_ready(losses[0])
            ctx.say(f"first step (optimizer state + trace + compile + "
                    f"transfer + run) {time.perf_counter() - t:.1f} s")
    jax.block_until_ready(losses[-1])

    compiles0 = profiler.compile_stats()["compiles"]
    done = len(losses)
    t_open = ctx.open_window()
    while time.perf_counter() - t_open < ctx.seconds:
        if ctx.tracer.due(time.perf_counter() - t_open):
            # host and device level before the trace opens, so that the trace
            # holds exactly the steps whose spans it holds
            jax.block_until_ready(losses[-1])
            ctx.tracer.start()
        with rec.span("train.step"):
            losses.append(step(*batches[len(losses) % len(batches)])._value)
        if (len(losses) - done) % p["loss_every"] == 0:
            with rec.span("train.loss_read"):
                float(losses[-1])
    with rec.span("train.loss_read"):
        jax.block_until_ready(losses[-1])
    t_close = time.perf_counter()
    ctx.tracer.stop()
    steps = len(losses) - done
    window_compiles = profiler.compile_stats()["compiles"] - compiles0

    values = [float(x) for x in np.asarray(jax.device_get(losses))]
    pool = len(batches)
    first_pass, last_pass = values[:pool], values[-pool:]
    checks = {
        "every loss is finite": all(math.isfinite(x) for x in values),
        f"first loss {values[0]:.6f} within {p['loss_rtol']:g} of the "
        f"reference {ref_loss:.6f} (relative {abs(values[0] - ref_loss) / abs(ref_loss):.2e})":
            abs(values[0] - ref_loss) <= p["loss_rtol"] * abs(ref_loss),
        f"mean loss of the last pass over the pool {np.mean(last_pass):.4f} "
        f"below the first pass's {np.mean(first_pass):.4f}":
            len(values) >= 2 * pool and np.mean(last_pass) < np.mean(first_pass),
    }
    passes = [float(np.mean(values[i:i + pool]))
              for i in range(0, len(values) - pool + 1, pool)]
    ctx.say("mean loss per pass over the pool, every "
            f"{max(1, len(passes) // 8)}th: "
            + " ".join(f"{x:.3f}" for x in passes[::max(1, len(passes) // 8)]))
    rate = steps * tokens_per_step / (t_close - t_open)
    mfu = (rate * roofline.train_flops_per_token(cfg, p["seq"])
           / ctx.peaks["flops_bf16"]) if ctx.peaks else None
    ctx.say(f"{steps} steps of {tokens_per_step} tokens in "
            f"{t_close - t_open:.3f} s; samples: {steps} steps"
            + (f"; model FLOP/s utilization {100 * mfu:.2f}% of the "
               f"published peak (required FLOPs, causal attention)"
               if mfu is not None else ""))
    return {
        "checks": checks,
        "attempted": steps,
        "failed": 0 if checks["every loss is finite"] else steps,
        "window": (t_open, t_close),
        "end_to_end": {"train_tok_s": rate},
        "counters": {"compile_stats": {"compiles": window_compiles}},
        "facts": {"batch": p["batch"], "seq": p["seq"]},
    }
