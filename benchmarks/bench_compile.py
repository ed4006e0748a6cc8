"""Benchmark: time-to-first-step and steps/sec for a deep LLaMA config,
scan-over-layers (nn.LayerStack) on vs off, plus persistent-cache warm start.

What it measures (the costs ISSUE 2's tentpole attacks):

- **ttfs**: time-to-first-step = build TrainStep + run step 0 (trace + XLA
  compile + execute).  With the unrolled loop this grows linearly with
  depth (N copies of the block in the HLO); with fuse_layer_stack the
  program is one lax.scan body — O(1) in depth.  Headline value =
  ttfs_unrolled / ttfs_scan (target >= 3x for >= 12 layers).
- **steps/sec**: compiled steady-state rate, scan vs unrolled (same fused
  executable quality is the goal; scan must not cost steady-state).
- **loss parity**: the first 5 training losses of both modes must agree
  within tolerance — the speedup must not change the optimization.
- **warm start**: child PROCESSES (real restarts) TrainStep.warmup() the
  same step: one with the persistent cache switched off (cold), then two
  over the repo's one cache directory (_core/compile_cache.py); the last
  must serve its XLA compiles from disk — reports cold vs warm warmup wall
  time, XLA compile seconds, and hit/miss counts.

Prints ONE JSON line shaped like bench.py: {"metric", "value", "unit",
"vs_baseline", ...}; value = the ttfs speedup, vs_baseline divides by the
3.0x acceptance target.  A CPU twin (forces JAX_PLATFORMS=cpu): its times
are not device metrics.  Smoke mode (--smoke / PADDLE_TPU_BENCH_SMOKE=1)
shrinks width/steps but keeps >= 12 layers so depth still dominates.
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
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit, profiler
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    if smoke:
        layers, hidden, inter, heads, seq, batch = 12, 32, 64, 2, 16, 2
        steps, timed_steps = 5, 5
    else:
        layers, hidden, inter, heads, seq, batch = 16, 128, 256, 4, 64, 4
        steps, timed_steps = 5, 20

    vocab = 256

    def build(fuse):
        paddle.seed(0)
        cfg = llama_tiny(
            num_hidden_layers=layers, hidden_size=hidden,
            intermediate_size=inter, num_attention_heads=heads,
            num_key_value_heads=heads, vocab_size=vocab,
            max_position_embeddings=max(seq, 32), dtype="float32",
            fuse_layer_stack=fuse)
        m = LlamaForCausalLM(cfg)
        o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
        return jit.TrainStep(m, o, lambda mm, x, y: mm(x, y)[0])

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.integers(0, vocab, (batch, seq)).astype(np.int32))
    y = paddle.to_tensor(rng.integers(0, vocab, (batch, seq)).astype(np.int32))

    def measure(fuse):
        from paddle_tpu._core import random as rng_mod

        rng_mod.seed(0)
        profiler.compile_stats(reset=True)
        step = build(fuse)
        t0 = time.perf_counter()
        losses = [float(step(x, y)._value)]          # step 0: trace+compile+run
        ttfs = time.perf_counter() - t0
        losses += [float(step(x, y)._value) for _ in range(steps - 1)]
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            step(x, y)
        rate = timed_steps / (time.perf_counter() - t0)
        stats = profiler.compile_stats()
        return {
            "ttfs_s": round(ttfs, 3),
            "steps_per_sec": round(rate, 2),
            "losses": [round(l, 6) for l in losses],
            "trace_s": round(stats["trace_seconds"], 3),
            "compile_s": round(stats["compile_seconds"], 3),
        }

    unrolled = measure(False)
    scan = measure(True)

    loss_match = bool(np.allclose(unrolled["losses"], scan["losses"],
                                  rtol=5e-4, atol=1e-5))
    ttfs_speedup = unrolled["ttfs_s"] / scan["ttfs_s"]
    tracecompile_speedup = (
        (unrolled["trace_s"] + unrolled["compile_s"])
        / max(scan["trace_s"] + scan["compile_s"], 1e-9))

    # ---- warm start: persistent compilation cache across real restarts ---
    import subprocess

    # cold = persistent cache switched OFF (a cache at a temporary path
    # would be cold too, but it could never hit again); fill and warm use
    # the repo's one cache directory (_core/compile_cache.py)
    child = f"""
import json, os, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import paddle_tpu as paddle
from paddle_tpu._core import compile_cache
if os.environ["BENCH_COMPILE_CACHE"] == "off":
    jax.config.update("jax_enable_compilation_cache", False)
else:
    compile_cache.enable()
import paddle_tpu.optimizer as opt
from paddle_tpu import jit, profiler
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

paddle.seed(0)
cfg = llama_tiny(num_hidden_layers={layers}, hidden_size={hidden},
                 intermediate_size={inter}, num_attention_heads={heads},
                 num_key_value_heads={heads}, vocab_size={vocab},
                 max_position_embeddings={max(seq, 32)}, dtype="float32",
                 fuse_layer_stack=True)
m = LlamaForCausalLM(cfg)
o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
step = jit.TrainStep(m, o, lambda mm, x, y: mm(x, y)[0])
rng = np.random.default_rng(0)
x = paddle.to_tensor(rng.integers(0, {vocab}, ({batch}, {seq})).astype(np.int32))
y = paddle.to_tensor(rng.integers(0, {vocab}, ({batch}, {seq})).astype(np.int32))
t0 = time.perf_counter(); step.warmup(x, y); dt = time.perf_counter() - t0
s = profiler.compile_stats()
print(json.dumps({{"warmup_s": round(dt, 3), "compile_s": round(s["compile_seconds"], 3),
                   "hits": s["persistent_cache_hits"], "misses": s["persistent_cache_misses"]}}))
"""
    def restart(cache):
        env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_COMPILE_CACHE=cache)
        r = subprocess.run([sys.executable, "-c", child], env=env,
                           capture_output=True, text=True, timeout=900)
        line = next((ln for ln in reversed(r.stdout.splitlines())
                     if ln.startswith("{")), None)
        if r.returncode != 0 or line is None:
            return {"error": (r.stderr or r.stdout)[-400:]}
        return json.loads(line)

    cold, _fill, warmed = restart("off"), restart("on"), restart("on")
    warm = {"cold": cold, "warm": warmed}
    if "error" not in cold and "error" not in warmed:
        warm["compile_speedup"] = round(
            cold["compile_s"] / max(warmed["compile_s"], 1e-9), 2)

    print(
        json.dumps(
            {
                "metric": "scan_layers_ttfs_speedup",
                "value": round(ttfs_speedup, 3),
                "unit": "x",
                "vs_baseline": round(ttfs_speedup / 3.0, 4),  # target >= 3x
                "trace_compile_speedup": round(tracecompile_speedup, 3),
                "loss_trajectories_match": loss_match,
                "detail": {"unrolled": unrolled, "scan": scan,
                           "warm_start": warm},
                "config": ("smoke_" if smoke else "")
                          + f"llama_L{layers}_d{hidden}_B{batch}xS{seq}",
            }
        ),
        flush=True,
    )
    return 0 if loss_match else 4


if __name__ == "__main__":
    sys.exit(main())
