"""Benchmark: LLaMA decoder pretrain throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The reference publishes no absolute numbers (BASELINE.json: "published" is
empty), so vs_baseline is computed as achieved MFU divided by 0.45 — the
typical Megatron-style MFU Paddle/PaddleNLP reaches for LLaMA pretraining on
A100 (the north-star is "match Paddle-on-A100 tokens/sec/chip", which at
equal MFU is the same comparison up to the peak-FLOPs ratio).  vs_baseline
>= 1.0 means we use our chip at least as efficiently as the reference uses
its GPU.

No chip, no number: the measuring path runs in this one process and refuses
to start unless jax's first device is an accelerator (exit 2, nothing on
stdout).  `--smoke` is the explicit CPU twin: the same code at a toy size,
to check the payload's SHAPE.  What it prints is labelled a CPU run
("platform": "cpu", metric "cpu_smoke_tokens_per_sec", mfu null) and is
never a device metric.
"""

from __future__ import annotations

import json
import sys

METRIC = "llama_pretrain_tokens_per_sec_per_chip"


def _pipeline_detail(S: int = 4, M: int = 16) -> dict:
    """Simulator-backed pipeline-schedule section (ROADMAP item 3): bubble
    fraction per registered schedule at the flagship (S, M), pure host math
    from fleet/meta_parallel/schedules.py — CPU-falsifiable, rides every
    payload so tools/check_bench_regression.py can gate bubble growth
    (lower is better) the moment a schedule table changes."""
    from paddle_tpu.distributed.fleet.meta_parallel import schedules as sched

    out = {"S": S, "M": M, "schedules": {}, "peak_residency": {}}
    for name in sched.available_schedules():
        r = sched.simulate(name, S, M)
        out["schedules"][name] = round(r.bubble_fraction, 6)
        out["peak_residency"][name] = r.peak_residency
    return out


def run(smoke: bool = False) -> int:
    import numpy as np
    import jax

    if smoke:
        jax.config.update("jax_platforms", "cpu")
    from paddle_tpu._core import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

    platform = jax.devices()[0].platform
    on_accel = platform != "cpu"
    if not (on_accel or smoke):
        print("bench.py: jax found no accelerator (first device is "
              f"{jax.devices()[0]}); nothing measured.  `--smoke` runs the "
              "CPU twin.", file=sys.stderr)
        return 2

    import paddle_tpu as paddle
    from paddle_tpu.device import hard_sync
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if on_accel:
        # Flagship config: hidden 2048 fills the MXU tile better than 1024.
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=2048,
            intermediate_size=5632,
            num_hidden_layers=8,
            num_attention_heads=16,
            num_key_value_heads=16,
            max_position_embeddings=1024,
            dtype="bfloat16",
        )
        B, S, iters = 4, 1024, 10
    else:  # dev smoke on CPU
        cfg = LlamaConfig(
            vocab_size=1024,
            hidden_size=256,
            intermediate_size=688,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=4,
            max_position_embeddings=512,
            dtype="float32",
        )
        B, S, iters = 2, 128, 3

    # Build (param init) on the host CPU backend: init is eager per-op
    # work.  The whole hot path is the compiled TrainStep; it pulls the
    # state to the accelerator on the first call.
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters(), weight_decay=0.01)

    def loss_fn(m, ids, labels):
        loss, _ = m(ids, labels=labels)
        return loss

    step = TrainStep(model, opt, loss_fn)
    rng = np.random.default_rng(0)

    from paddle_tpu.device import time_step_ms

    def measure(batch):
        ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, size=(batch, S)).astype(np.int32))
        labels = paddle.to_tensor(rng.integers(0, cfg.vocab_size, size=(batch, S)).astype(np.int64))
        step(ids, labels)  # builds optimizer state on host, compiles, runs
        hard_sync(step(ids, labels))
        ms = time_step_ms(lambda: step(ids, labels), inner=iters)
        return batch * S / (ms / 1e3)

    # per-config MFU: a gain must be visible per swept config, not just
    # for the winner
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops_per_token = 6 * n_params + 12 * cfg.num_hidden_layers * cfg.hidden_size * S

    from paddle_tpu.device.peaks import device_peak_tflops

    kind = jax.devices()[0].device_kind.lower()
    # no peak, no MFU: the CPU twin prints null, never a utilization
    peak = device_peak_tflops(kind, platform) if on_accel else None

    def _mfu(tps: float):
        if peak is None:
            return None
        return round((tps * flops_per_token / 1e12) / peak, 4)

    configs = []
    if on_accel:
        # batch sweep, largest first: bigger batches fill the MXU better
        # until HBM runs out — an OOM falls through to the next size
        tokens_per_sec, best_b = 0.0, B
        for batch in (16, 8, 4):
            try:
                tps = measure(batch)
            except Exception as e:  # noqa: BLE001
                msg = f"{type(e).__name__}: {e}"
                print(f"bench: B={batch} failed ({msg[:200]})", file=sys.stderr)
                if "RESOURCE_EXHAUSTED" not in msg and "Out of memory" not in msg:
                    raise
                continue
            configs.append({"config": f"hidden2048_L8_bf16_B{batch}",
                            "tokens_per_sec": round(tps, 2),
                            "mfu": _mfu(tps)})
            if tps > tokens_per_sec:
                tokens_per_sec, best_b = tps, batch
        B = best_b
        if tokens_per_sec == 0.0:
            print("bench.py: all sweep batch sizes hit device OOM",
                  file=sys.stderr)
            return 3
    else:
        tokens_per_sec = measure(B)
        configs.append({"config": "cpu_smoke",
                        "tokens_per_sec": round(tokens_per_sec, 2),
                        "mfu": _mfu(tokens_per_sec)})

    mfu = _mfu(tokens_per_sec)

    payload = {
        "metric": METRIC if on_accel else "cpu_smoke_tokens_per_sec",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s",
        "vs_baseline": None if mfu is None else round(mfu / 0.45, 4),
        "mfu": mfu,
        "platform": platform,
        "device_kind": kind,
        "config": (f"hidden2048_L8_bf16_B{B}" if on_accel else "cpu_smoke"),
        "configs": configs,
        "detail": {"pipeline": _pipeline_detail()},
    }
    print(json.dumps(payload), flush=True)
    if smoke:
        _assert_smoke(payload)
        print("BENCH_SMOKE_OK", flush=True)
    return 0


def _assert_smoke(payload: dict):
    """--smoke contract: the CPU twin proves the payload SHAPE the on-chip
    run will carry — per-config mfu fields and the simulator-backed
    pipeline section with ZB-H1 strictly under 1F1B — so a field
    regression fails in CI, not in the first round on a chip."""
    assert payload["value"] > 0, payload
    assert payload["configs"], "configs sweep section missing"
    for c in payload["configs"]:
        assert "mfu" in c and "tokens_per_sec" in c and "config" in c, c
    pl = payload["detail"]["pipeline"]
    scheds = pl["schedules"]
    for name in ("FThenB", "1F1B", "ZB-H1"):
        assert name in scheds, f"{name} missing from pipeline section"
    assert scheds["ZB-H1"] < scheds["1F1B"], scheds
    assert pl["peak_residency"]["ZB-H1"] <= pl["peak_residency"]["1F1B"], pl


if __name__ == "__main__":
    sys.exit(run(smoke="--smoke" in sys.argv))
