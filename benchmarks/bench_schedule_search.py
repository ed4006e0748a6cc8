"""Benchmark: cost-model-driven Pallas schedule search + measured-win gate.

Exercises the full search loop on three searched subjects no
named pattern covers (the XLA fusion-miss classes of arXiv 2301.13062):

- **matmul chain** — matmul→bias-add→relu→mean tail (matmul-rooted with a
  reduction tail): searched, gated, and — when the schedule wins —
  substituted, with fused-vs-XLA numerics asserted either way.
- **K-tiled matmul chain** (phase 2) — the same class at a contraction dim
  large enough that ``block_k`` splits enter the candidate space; smoke
  mode pins a genuinely K-tiled config as the winner so the accumulator
  kernel path is the one asserted.
- **softmax chain** — a manually decomposed softmax (reduction-rooted DAG):
  in smoke mode its schedule deliberately LOSES so the gate's disable path
  is exercised: the decision persists as a disabled entry in the
  per-device autotune cache and a cold reload must skip the subgraph
  without a single re-measurement.

Timing: in full mode candidates are measured for real through
cost_model.OpCostModel.measure (hard_sync device barrier — meaningful on
TPU; on CPU the kernels run in Pallas interpret mode, where XLA-only
usually wins and the gate honestly disables — a win-or-disabled verdict is
recorded either way, never a faked value).  Smoke mode (--smoke or
PADDLE_TPU_BENCH_SMOKE=1) injects a deterministic roofline-shaped cost
model via schedule_search.measure_override so CI asserts the DECISION
LOGIC — accept vs disable vs never-refire — bit-stably offline, with
numerics always checked for real.

Prints ONE JSON line shaped like bench.py: {"metric", "value", ...}.
value = the best accepted schedule's measured win ratio over XLA (0.0 when
the gate disabled everything — an honest loss is not a regression signal;
tools/check_bench_regression.py skips zero values).
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    smoke = "--smoke" in sys.argv or bool(os.environ.get("PADDLE_TPU_BENCH_SMOKE"))

    import jax

    if jax.default_backend() != "tpu":
        jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.ops import autotune as at
    from paddle_tpu.static import schedule_search as ss
    from paddle_tpu.static.program import Program, program_guard
    from paddle_tpu.static.rewrite import ScheduleSearchPass
    from paddle_tpu.static.verify import differential_check

    # decisions land in a scratch per-device cache, not the checked-in seeds
    cache_dir = tempfile.mkdtemp(prefix="sched_bench_")
    paddle.set_flags({"FLAGS_autotune_cache_dir": cache_dir})
    at._CACHES.clear()
    ss.reset_schedule_search_stats()

    if smoke:
        M, K, N = 32, 16, 64
        B, S, H = 2, 8, 32
        MT, KT, NT = 32, 256, 64
    elif jax.default_backend() == "tpu":
        M, K, N = 1024, 512, 512
        B, S, H = 8, 128, 512
        MT, KT, NT = 1024, 2048, 1024
    else:
        # full mode off-chip: real timing of interpret-mode kernels — keep
        # shapes small enough that an honest all-disabled outcome is cheap
        M, K, N = 128, 64, 128
        B, S, H = 4, 32, 64
        MT, KT, NT = 64, 512, 128

    def _feed(prog, name, shape):
        return prog.add_feed(
            prog.new_var(jax.ShapeDtypeStruct(shape, np.float32), name))

    def capture_matmul_chain():
        prog = Program()
        with program_guard(prog):
            x = _feed(prog, "x", (M, K))
            w = _feed(prog, "w", (K, N))
            b = _feed(prog, "b", (N,))
            h = paddle.matmul(x, w)
            h = h + b
            h = F.relu(h)
            out = paddle.mean(h, axis=-1, keepdim=True)
        return prog, out

    def capture_ktiled_chain():
        prog = Program()
        with program_guard(prog):
            x = _feed(prog, "x", (MT, KT))
            w = _feed(prog, "w", (KT, NT))
            b = _feed(prog, "b", (NT,))
            out = F.relu(paddle.matmul(x, w) + b)
        return prog, out

    def capture_softmax_chain():
        prog = Program()
        with program_guard(prog):
            x = _feed(prog, "x", (B, S, H))
            m = paddle.max(x, axis=-1, keepdim=True)
            t = paddle.exp(x - m)
            s = paddle.sum(t, axis=-1, keepdim=True)
            out = t / s
        return prog, out

    measured_labels = []

    def smoke_measure(fn, args, *, label, config):
        """Deterministic roofline-shaped cost model: the matmul chains'
        schedules win (the large-K twin only through a genuinely K-tiled
        config; grid overhead mildly penalizes tiny blocks), the softmax
        chain's schedules deliberately LOSE to XLA."""
        measured_labels.append(label)
        if config is None:
            return 1.0
        if label.startswith("schedule/reduce"):
            return 4.0  # the deliberately-bad schedule family
        if f"k={KT}" in label:
            # the K-tiled twin: only a contraction split beats XLA here
            return 0.3 if config.get("block_k", KT) < KT else 4.0
        steps = (M // config["block_rows"]) * (N // config["block_cols"])
        return 0.4 + 0.002 * steps

    def cache_entries(kernel):
        slug_file = os.path.join(cache_dir, at.device_kind_slug() + ".json")
        if not os.path.exists(slug_file):
            return {}
        raw = json.load(open(slug_file))
        return raw.get(kernel, {})

    def cache_entry(kernel, key_sub=""):
        for k, v in cache_entries(kernel).items():
            if key_sub in k:
                return v
        return None

    def run_case(name, capture, kernel, key_sub="", budget=3):
        """Search one Program subgraph; return its decision record with
        REAL fused-vs-XLA numerics parity."""
        prog, out = capture()
        reference = prog.clone()
        searcher = ss.ScheduleSearcher(budget=budget, iters=1, warmup=1)
        n = ScheduleSearchPass([out._vid], searcher=searcher).apply(prog)
        types = [op.type for op in prog.global_block().ops]
        fused_type = next((t for t in types if t.startswith("sched_chain_")),
                          None)
        numerics_ok = True
        if n:
            numerics_ok = differential_check(
                reference, prog, [out._vid], raise_on_error=False) == []
        return {
            "substituted": n,
            "fused_op": fused_type,
            "numerics_identical": bool(numerics_ok),
            "cache_entry": cache_entry(kernel, key_sub),
        }

    ctx = (ss.measure_override(smoke_measure) if smoke
           else contextlib.nullcontext())
    with ctx:
        matmul_case = run_case("matmul_chain", capture_matmul_chain,
                               "schedule/matmul", key_sub=f"k={K}|")
        ktiled_case = run_case("ktiled_matmul", capture_ktiled_chain,
                               "schedule/matmul", key_sub=f"k={KT}|")
        softmax_case = run_case("softmax_chain", capture_softmax_chain,
                                "schedule/reduce")

        # never-refire: cold cache reload, a disabled subgraph must be
        # skipped without a single new measurement
        at._CACHES.clear()
        before = len(measured_labels) if smoke else \
            ss.schedule_search_stats()["measured"]
        prog2, out2 = capture_softmax_chain()
        ScheduleSearchPass(
            [out2._vid],
            searcher=ss.ScheduleSearcher(budget=3, iters=1, warmup=1)
        ).apply(prog2)
        after = len(measured_labels) if smoke else \
            ss.schedule_search_stats()["measured"]
        never_refired = (after == before)

    stats = ss.schedule_search_stats()
    # headline value: the best accepted schedule's measured win over XLA
    # (any case may win or lose under real timing; smoke pins the set)
    win = 0.0
    for case in (matmul_case, ktiled_case, softmax_case):
        entry = case["cache_entry"] or {}
        if case["substituted"] and not entry.get("config", {}).get("disabled"):
            win = max(win, float((entry.get("meta") or {}).get("win", 0.0)
                                 or 0.0))
    disabled_entry = softmax_case["cache_entry"] or {}
    numerics_ok = (matmul_case["numerics_identical"]
                   and ktiled_case["numerics_identical"]
                   and softmax_case["numerics_identical"])
    min_win = float(paddle.get_flags("FLAGS_schedule_search_min_win")[
        "FLAGS_schedule_search_min_win"])

    paddle.set_flags({"FLAGS_autotune_cache_dir": ""})
    at._CACHES.clear()
    shutil.rmtree(cache_dir, ignore_errors=True)

    print(
        json.dumps(
            {
                "metric": "schedule_search_measured_win",
                "value": round(win, 4),
                "unit": "x",
                "vs_baseline": round(win / min_win, 4) if win else 0.0,
                "numerics_identical": bool(numerics_ok),
                "detail": {
                    "matmul_chain": matmul_case,
                    "ktiled_matmul": ktiled_case,
                    "softmax_chain": softmax_case,
                    "disabled_persisted": bool(disabled_entry.get(
                        "config", {}).get("disabled")),
                    "never_refired": bool(never_refired),
                    "counters": stats,
                },
                "config": ("smoke" if smoke
                           else f"mm{M}x{K}x{N}_kt{MT}x{KT}x{NT}"
                                f"_sm{B}x{S}x{H}"),
            }
        ),
        flush=True,
    )
    ok = numerics_ok and never_refired
    if smoke:
        # the deterministic cost model must produce exactly these decisions
        ktc = (ktiled_case["cache_entry"] or {}).get("config", {})
        ok = ok and matmul_case["substituted"] == 1 and win > 1.0 \
            and softmax_case["substituted"] == 0 \
            and bool(disabled_entry.get("config", {}).get("disabled")) \
            and ktiled_case["substituted"] == 1 \
            and 0 < ktc.get("block_k", 0) < KT
    return 0 if ok else 4


if __name__ == "__main__":
    sys.exit(main())
