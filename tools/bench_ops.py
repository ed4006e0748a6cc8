"""Per-op micro-benchmark harness.

Reference roles: test/legacy_test/benchmark.py (per-op ms timing harness)
and tools/ci_op_benchmark.sh + tools/check_op_benchmark_result.py (CI gate
comparing per-op timings between two builds).

TPU-native: each case jit-compiles one hot op at a standard shape and
times it with the RTT-cancelling readback-synced timer the kernel
autotuner uses (`paddle_tpu.ops.autotune._time_fn` — block_until_ready
resolves at dispatch on the remote transport, so naive timing is
fiction).  Emits one JSON document; `--compare old.json` exits 1 on
relative regressions beyond `--threshold`, mirroring the reference CI.

Usage:
    python tools/bench_ops.py --out ops_v5e.json
    python tools/bench_ops.py --out new.json --compare ops_v5e.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cases(quick=False):
    """-> list of (name, build) where build() returns (jitted_fn, args,
    flops, moved_bytes).  Shapes are the framework's hot tier; `quick`
    shrinks them so CPU CI can execute the harness end-to-end."""
    import jax
    import jax.numpy as jnp

    S = 256 if quick else 4096
    H = 128 if quick else 4096
    B = 2 if quick else 8
    L = 128 if quick else 1024
    dt = jnp.float32 if quick else jnp.bfloat16
    isz = jnp.dtype(dt).itemsize
    k0 = jax.random.PRNGKey(0)

    def matmul():
        a = jax.random.normal(k0, (S, H), dt)
        b = jax.random.normal(k0, (H, H), dt)
        return jax.jit(lambda a, b: a @ b), (a, b), 2 * S * H * H, (S * H + H * H + S * H) * isz

    def batched_matmul():
        a = jax.random.normal(k0, (B, L, H), dt)
        b = jax.random.normal(k0, (B, H, H), dt)
        return (jax.jit(lambda a, b: jnp.einsum("blh,bhk->blk", a, b)), (a, b),
                2 * B * L * H * H, (B * L * H * 2 + B * H * H) * isz)

    def softmax():
        x = jax.random.normal(k0, (B * L, H), dt)
        return jax.jit(lambda x: jax.nn.softmax(x, -1)), (x,), 5 * B * L * H, 2 * B * L * H * isz

    def layer_norm():
        from paddle_tpu.ops import fused_layer_norm

        x = jax.random.normal(k0, (B * L, H), dt)
        w = jnp.ones((H,), dt)
        bb = jnp.zeros((H,), dt)
        return (jax.jit(lambda x, w, b: fused_layer_norm(x, w, b, epsilon=1e-5)), (x, w, bb),
                8 * B * L * H, 2 * B * L * H * isz)

    def rms_norm():
        from paddle_tpu.ops import fused_rms_norm

        x = jax.random.normal(k0, (B * L, H), dt)
        w = jnp.ones((H,), dt)
        return (jax.jit(lambda x, w: fused_rms_norm(x, w, epsilon=1e-5)), (x, w),
                4 * B * L * H, 2 * B * L * H * isz)

    def swiglu():
        from paddle_tpu.ops import swiglu as _swiglu

        a = jax.random.normal(k0, (B * L, H), dt)
        b = jax.random.normal(k0, (B * L, H), dt)
        return (jax.jit(lambda a, b: _swiglu(a, b)), (a, b),
                5 * B * L * H, 3 * B * L * H * isz)

    def flash_attention():
        from paddle_tpu.ops import flash_attention as _fa

        n, hd = (2, 64) if quick else (8, 128)
        q, k, v = (jax.random.normal(kk, (1, L, n, hd), dt)
                   for kk in jax.random.split(k0, 3))
        return (jax.jit(lambda q, k, v: _fa(q, k, v, causal=True)), (q, k, v),
                2 * 2 * n * L * L * hd // 2, 4 * L * n * hd * isz)

    def embedding():
        tbl = jax.random.normal(k0, (32000, H), dt)
        ids = jax.random.randint(k0, (B * L,), 0, 32000)
        return (jax.jit(lambda t, i: jnp.take(t, i, axis=0)), (tbl, ids),
                0, B * L * H * isz * 2)

    def matmul_epilogue_fused():
        from paddle_tpu.ops import matmul_bias_act

        x = jax.random.normal(k0, (S, H), dt)
        w = jax.random.normal(k0, (H, H), dt)
        b = jnp.zeros((H,), dt)
        return (jax.jit(lambda x, w, b: matmul_bias_act(x, w, b, "gelu_tanh")),
                (x, w, b), 2 * S * H * H, (S * H * 2 + H * H) * isz)

    def matmul_epilogue_unfused():
        # the XLA chain the fusion replaces — same shapes, same JSON block,
        # so the gate can compare fused vs unfused directly on chip
        x = jax.random.normal(k0, (S, H), dt)
        w = jax.random.normal(k0, (H, H), dt)
        b = jnp.zeros((H,), dt)
        return (jax.jit(lambda x, w, b: jax.nn.gelu(x @ w + b, approximate=True)),
                (x, w, b), 2 * S * H * H, (S * H * 2 + H * H) * isz)

    def adamw_update():
        n = S * H
        p, g, m, v = (jax.random.normal(kk, (n,), jnp.float32)
                      for kk in jax.random.split(k0, 4))

        def upd(p, g, m, v):
            m = 0.9 * m + 0.1 * g
            v = 0.999 * v + 0.001 * g * g
            return p - 1e-3 * (m / (jnp.sqrt(v) + 1e-8) + 0.01 * p), m, v

        return jax.jit(upd), (p, g, m, v), 12 * n, 7 * n * 4

    return [(f.__name__, f) for f in (
        matmul, batched_matmul, softmax, layer_norm, rms_norm, swiglu,
        flash_attention, embedding, matmul_epilogue_fused,
        matmul_epilogue_unfused, adamw_update)]


def run(quick=False, iters=3):
    import jax

    from paddle_tpu.ops.autotune import _time_fn

    results = {}
    for name, build in _cases(quick):
        try:
            fn, args, flops, moved = build()
            ms = _time_fn(fn, args, iters=iters,
                          inner=1 if quick else None,
                          target_ms=50.0 if quick else 300.0)
        except Exception as e:  # noqa: BLE001 — record, don't abort the sweep
            results[name] = {"error": f"{type(e).__name__}: {e}"[:200]}
            print(f"  ERROR {name}: {results[name]['error']}", flush=True)
            continue
        entry = {"ms": round(ms, 4)}
        if flops:
            entry["tflops"] = round(flops / ms / 1e9, 2)
        if moved:
            entry["gbps"] = round(moved / ms / 1e6, 1)
        results[name] = entry
        print(f"  {name}: {entry}", flush=True)
    d = jax.devices()[0]
    return {"device_kind": d.device_kind, "platform": d.platform,
            "tier": "quick" if quick else "full",
            "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"), "ops": results}


def compare(new, old, threshold):
    """-> list of regression strings (empty = gate passes).

    An op that timed cleanly in `old` but errors or disappears in `new`
    IS a regression — going from 2ms to broken must not pass the gate."""
    bad = []
    for name, prev in old.get("ops", {}).items():
        if "ms" not in prev or prev["ms"] <= 0:
            continue
        entry = new.get("ops", {}).get(name)
        if entry is None:
            bad.append(f"{name}: {prev['ms']:.4f} ms -> MISSING from new run")
            continue
        if "ms" not in entry:
            bad.append(f"{name}: {prev['ms']:.4f} ms -> "
                       f"{entry.get('error', 'no timing')}")
            continue
        rel = entry["ms"] / prev["ms"] - 1.0
        if rel > threshold:
            bad.append(f"{name}: {prev['ms']:.4f} -> {entry['ms']:.4f} ms "
                       f"(+{rel * 100:.1f}% > {threshold * 100:.0f}%)")
    return bad


def main(argv=None):
    p = argparse.ArgumentParser(description="per-op micro-benchmarks")
    p.add_argument("--out", default=None, help="write results JSON here")
    p.add_argument("--compare", default=None, help="old results to gate against")
    p.add_argument("--threshold", type=float, default=0.05)
    p.add_argument("--quick", action="store_true",
                   help="tiny shapes / cpu-safe (CI smoke)")
    args = p.parse_args(argv)

    res = run(quick=args.quick)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
        print(f"wrote {args.out}")
    errors = [k for k, v in res["ops"].items() if "error" in v]
    if errors:
        print(f"ERRORS in: {', '.join(errors)}")
    if args.compare:
        with open(args.compare) as f:
            old = json.load(f)
        mismatch = [f"{f} ({old.get(f)} vs {res.get(f)})"
                    for f in ("device_kind", "tier")
                    if old.get(f) != res.get(f)]
        if mismatch:
            print(f"compare: {', '.join(mismatch)} mismatch; not gating")
        else:
            bad = compare(res, old, args.threshold)
            for b in bad:
                print(f"REGRESSION {b}")
            if bad:
                return 1
            print("no regressions")
    return 2 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
