"""Per-chip peak bf16 TFLOP/s (single source for the benchmark suite's
MFU / vs_baseline math — bench.py, benchmarks/bench_resnet.py,
benchmarks/bench_bert.py, profiler.estimate_mfu).

Published peaks, keyed by a substring of jax's `device_kind` (Google Cloud
documentation, "TPU v4" / "TPU v5e" / "TPU v5p" / "TPU v6e" system
architecture pages).  A device that is not in the table is an error, not a
default: a utilization against a guessed peak is not a measurement."""

from __future__ import annotations

A100_PEAK_TFLOPS = 312.0  # bf16, the reference baselines' GPU

_PEAK_BF16_TFLOPS = (
    ("v6e", 918.0),
    ("trillium", 918.0),
    ("v5 lite", 197.0),  # jax reports a v5e chip as "TPU v5 lite"
    ("v5e", 197.0),
    ("v5p", 459.0),
    ("v4", 275.0),
)


def device_peak_tflops(device_kind: str, platform: str) -> float:
    """Peak bf16 TFLOP/s for a jax device kind; 0.0 for the CPU platform
    (no MFU there); ValueError for an accelerator the table does not know."""
    if platform == "cpu":
        return 0.0
    kind = device_kind.lower()
    for key, peak in _PEAK_BF16_TFLOPS:
        if key in kind:
            return peak
    raise ValueError(
        f"no published peak for device kind {device_kind!r} (platform "
        f"{platform!r}) in paddle_tpu/device/peaks.py; add it with its source")
