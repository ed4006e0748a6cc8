"""Serving decode: macro-step (chunked) continuous batching vs per-token
dispatch, plus a depth sweep showing decode trace+compile is depth-constant
under the LayerStack scan (BASELINE.json serving tier; reference lineage
block_multi_head_attention + the decode servers over it).

Two claims measured:
- **Macro-step speedup**: `GenerationEngine` with FLAGS_decode_chunk D
  emits [B, D] tokens per compiled dispatch (one host round-trip + one
  device sync per chunk instead of per token) — tokens/s vs the per-token
  path (D=1), with bit-identical greedy token streams.
- **Depth-constant decode compile**: with `fuse_layer_stack` the paged KV
  pools thread through the LayerStack scan body as per-layer state, so the
  first macro-step's trace+compile no longer scales ~linearly in layer
  count (16-layer vs 4-layer first-step wall within ~1.5x).
- **Prefix-cache KV reuse**: N requests sharing one long system prompt —
  with `prefix_cache=True` admission matches the cached prefix at page
  granularity and prefills only the suffix.  Reports end-to-end tokens/s
  on vs off (admission + decode in the wall), prefill-avoided tokens, and
  per-token latency percentiles (p50/p95), with a greedy-parity gate.
- **int8 KV capacity**: at IDENTICAL pool-block bytes, how many requests
  an int8-quantized pool admits before queueing vs a bf16 pool —
  allocator arithmetic, so the ratio is deterministic and timing-free.
- **SLO load percentiles**: an oversubscribed (2x max_batch) workload
  reporting p50/p95/p99 time-to-first-token (prefill + queueing delay)
  and inter-token latency, replayed on a TP-sharded twin over 2 (virtual
  when on CPU) devices with a greedy stream-parity gate
  (tools/check_bench_regression.py gates the percentiles too).
- **Snapshot/restore**: save a LIVE mid-flight engine through the atomic
  commit protocol and restore it (serving/snapshot.py) — save_ms /
  restore_ms / committed bytes, with a resume-parity gate (the restored
  engine's continued streams must equal an uninterrupted run's).  The
  timings feed check_bench_regression's snapshot gate (growth beyond the
  SLO threshold is the regression — the preemption budget this buys).
- **Overload discipline**: the adversarial mix — one very long prompt
  submitted mid-decode of a full batch of short streams.  Atomic
  admission stalls every resident stream for the whole prefill; chunked
  interleaving (FLAGS_prefill_chunk_blocks) bounds the stall at one
  block per macro-step, so the residents' p99 inter-token latency must
  drop at equal throughput, with ALL streams bit-identical between the
  two engines.  A preemption sub-scenario parks a LOW-priority stream
  under a HIGH arrival and re-admits it: the resumed stream must equal
  an uninterrupted reference token for token
  (check_bench_regression's overload gate consumes the p99 ITL).

Prints ONE JSON line like the other benches.  vs_baseline is 0.0 until a
reference serving point is recorded (none published in-repo).
`--smoke` / PADDLE_TPU_BENCH_SMOKE shrinks sizes for CI
(tests/test_bench_decode.py)."""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _drain(eng, prompts, max_new):
    """Run requests to completion; return {rid: generated tokens}."""
    for rid, p in prompts.items():
        eng.add_request(rid, p, max_new_tokens=max_new)
    while eng.has_work():
        eng.step()
    return {rid: eng.result(rid) for rid in prompts}


def main():
    # the SLO load benchmark's TP twin needs >= 2 devices even on a CPU
    # box: force 2 virtual host devices BEFORE jax's
    # backend initializes (tests/conftest.py does the same with 8).
    # Only the host platform is affected; real accelerators ignore it.
    _xla = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _xla:
        os.environ["XLA_FLAGS"] = (
            _xla + " --xla_force_host_platform_device_count=2")
    import jax

    if os.environ.get("PADDLE_TPU_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    # persistent compile cache OFF: the depth sweep times real trace+compile
    jax.config.update("jax_enable_compilation_cache", False)
    smoke = os.environ.get("PADDLE_TPU_BENCH_SMOKE") or "--smoke" in sys.argv
    on_accel = jax.devices()[0].platform != "cpu"

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM, llama_tiny
    from paddle_tpu.serving import GenerationEngine

    paddle.seed(0)
    if on_accel:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=4096,
            dtype="bfloat16")
        B, prompt_len, iters, chunk = 8, 128, 8, 8
    elif smoke:
        cfg = llama_tiny(vocab_size=256, hidden_size=64, intermediate_size=176,
                         num_attention_heads=4, num_key_value_heads=4,
                         max_position_embeddings=8192, dtype="float32")
        B, prompt_len, iters, chunk = 2, 8, 2, 8
    else:
        # CPU proxy: a thin-width model keeps per-step device compute small
        # so the measured contrast is the per-dispatch host overhead the
        # macro-step amortizes (the TPU-relevant quantity; the accel branch
        # measures a serving-scale config instead)
        cfg = llama_tiny(vocab_size=256, hidden_size=64, intermediate_size=176,
                         num_attention_heads=4, num_key_value_heads=4,
                         max_position_embeddings=8192, dtype="float32")
        B, prompt_len, iters, chunk = 2, 8, 4, 8
    model = LlamaForCausalLM(cfg)
    model.eval()

    rng = np.random.default_rng(0)
    prompts = {f"r{i}": list(rng.integers(0, cfg.vocab_size, prompt_len))
               for i in range(B)}

    # ---- greedy parity: chunked == per-token, bit for bit ---------------
    par_new = 24
    par_blocks = B * (-(-(prompt_len + par_new) // 16) + 1)
    ref = _drain(GenerationEngine(model, max_batch=B, block_size=16,
                                  num_blocks=par_blocks, decode_chunk=1),
                 prompts, par_new)
    got = _drain(GenerationEngine(model, max_batch=B, block_size=16,
                                  num_blocks=par_blocks, decode_chunk=chunk),
                 prompts, par_new)
    tokens_match = ref == got
    if not tokens_match:
        print(f"bench_decode: PARITY FAILURE {ref} vs {got}", file=sys.stderr)

    # ---- tokens/s: per-token dispatch vs macro-step ---------------------
    # Direct timing with an EXACT call budget: step() ends in a device
    # sync (np.asarray of the tokens), so wall time over N macro-steps
    # already includes the per-dispatch round trip — which is precisely
    # the cost macro-stepping amortizes.  An adaptive difference timer
    # (time_step_ms) is wrong here: its retry escalation makes the call
    # count nondeterministic (draining slots mid-measurement), and the
    # bigger max_new it forces inflates the paged pool, so the per-token
    # scatter's pool copy — identical work on both paths — swamps the
    # dispatch contrast being measured.
    def measure(D):
        ticks = 3 * iters
        max_new = (ticks + 2) * D + prompt_len
        nb = B * (-(-(prompt_len + max_new) // 16) + 1)
        eng = GenerationEngine(model, max_batch=B, block_size=16,
                               num_blocks=nb, decode_chunk=D)
        for rid, p in prompts.items():
            eng.add_request(rid, p, max_new_tokens=max_new)
        eng.step()  # compile
        t0 = time.perf_counter()
        for _ in range(ticks):
            eng.step()
        dt = time.perf_counter() - t0
        assert eng.has_work(), "slots drained mid-measurement; raise max_new"
        return B * D * ticks / dt

    from paddle_tpu.serving import decode_stats, reset_decode_stats

    per_token_tps = measure(1)
    # counters reported below must describe the CHUNKED claim, not the
    # parity/per-token phases that ran in this same process
    reset_decode_stats()
    chunked_tps = measure(chunk)
    st = decode_stats()
    speedup = chunked_tps / per_token_tps if per_token_tps else 0.0

    # ---- depth sweep: first macro-step wall (trace + compile) -----------
    # fuse_layer_stack threads the paged pools through the LayerStack scan
    # body, so the step program holds ONE layer body regardless of depth
    depth_sweep = {}
    if not on_accel:
        depths = (2, 6) if smoke else (4, 16)

        def first_step_wall(n_layers):
            paddle.seed(1)
            dcfg = llama_tiny(vocab_size=256, hidden_size=64,
                              intermediate_size=176, num_attention_heads=4,
                              num_key_value_heads=4,
                              num_hidden_layers=n_layers,
                              max_position_embeddings=256, dtype="float32",
                              fuse_layer_stack=True)
            m = LlamaForCausalLM(dcfg)
            m.eval()
            eng = GenerationEngine(m, max_batch=2, block_size=16,
                                   num_blocks=8, decode_chunk=chunk)
            eng.add_request("d", [3, 1, 4, 1], max_new_tokens=chunk * 2 + 2)
            t0 = time.perf_counter()
            eng.step()  # traces + compiles the macro-step program
            return time.perf_counter() - t0

        shallow, deep = depths
        t_shallow = first_step_wall(shallow)
        t_deep = first_step_wall(deep)
        depth_sweep = {
            "scan_layers": True,
            "shallow_layers": shallow,
            "deep_layers": deep,
            "shallow_first_step_s": round(t_shallow, 3),
            "deep_first_step_s": round(t_deep, 3),
            "ratio": round(t_deep / t_shallow, 3) if t_shallow else 0.0,
        }

    # ---- shared-prefix workload: prefix cache on vs off -----------------
    # N requests over ONE long system prompt (+ a small distinct user
    # tail): cache-on prefills the shared prefix once and every later
    # admission references its pages — end-to-end wall includes admission,
    # which is exactly where the win lives.
    from paddle_tpu.serving import GenerationEngine as _GE

    n_req = 4 if smoke else 8
    pre_len = 32 if smoke else 192
    tail_len, sp_new = 4, 4 if smoke else 16
    sp_s0 = pre_len + tail_len
    sp_rng = np.random.default_rng(7)
    shared = list(sp_rng.integers(0, cfg.vocab_size, pre_len))
    sp_prompts = {f"s{i}": shared + list(sp_rng.integers(0, cfg.vocab_size,
                                                         tail_len))
                  for i in range(n_req)}
    sp_blocks = n_req * (-(-(sp_s0 + sp_new) // 16) + 1)

    def run_shared(prefix_on):
        reset_decode_stats()
        eng = _GE(model, max_batch=n_req, block_size=16,
                  num_blocks=sp_blocks, decode_chunk=chunk,
                  prefix_cache=prefix_on)
        lat_ms = []
        t0 = time.perf_counter()
        for rid, p in sp_prompts.items():
            eng.add_request(rid, p, max_new_tokens=sp_new)
        while eng.has_work():
            ts = time.perf_counter()
            emitted = sum(len(v) if isinstance(v, list) else 1
                          for v in eng.step().values())
            if emitted:
                lat_ms += [1e3 * (time.perf_counter() - ts) / emitted] * emitted
        wall = time.perf_counter() - t0
        toks = sum(len(eng.result(r)) for r in sp_prompts)
        return {"tokens_per_sec": toks / wall,
                "results": {r: eng.result(r) for r in sp_prompts},
                "prefill_avoided_tokens": decode_stats()["prefix_hit_tokens"],
                "latency_p50_ms": float(np.percentile(lat_ms, 50)),
                "latency_p95_ms": float(np.percentile(lat_ms, 95))}

    sp_off = run_shared(False)
    sp_on = run_shared(True)
    prefix_match = sp_off["results"] == sp_on["results"]
    if not prefix_match:
        print("bench_decode: PREFIX PARITY FAILURE", file=sys.stderr)
    shared_prefix = {
        "requests": n_req,
        "prefix_tokens": pre_len,
        "prefix_speedup": round(
            sp_on["tokens_per_sec"] / sp_off["tokens_per_sec"], 2)
        if sp_off["tokens_per_sec"] else 0.0,
        "prefill_avoided_tokens": sp_on["prefill_avoided_tokens"],
        "tokens_match": prefix_match,
        "off": {k: round(v, 3) for k, v in sp_off.items()
                if k not in ("results",)},
        "on": {k: round(v, 3) for k, v in sp_on.items()
               if k not in ("results",)},
    }

    # ---- int8 KV capacity: resident requests at identical pool bytes ----
    # bf16 pools on a bf16 model vs int8 pools sized to the SAME block-pool
    # byte budget; admit identical-shape requests until one queues.  Pure
    # allocator arithmetic — deterministic, no timing.
    paddle.seed(2)
    from paddle_tpu.models.llama import llama_tiny as _tiny

    qcfg = _tiny(vocab_size=256, hidden_size=64, intermediate_size=176,
                 num_attention_heads=4, num_key_value_heads=4,
                 max_position_embeddings=8192, dtype="bfloat16")
    qmodel = LlamaForCausalLM(qcfg)
    qmodel.eval()
    q_nkv = qcfg.num_key_value_heads
    q_hd = qcfg.hidden_size // qcfg.num_attention_heads
    q_layers = qcfg.num_hidden_layers
    elems = q_nkv * 16 * q_hd
    per_block_bf16 = q_layers * 2 * elems * 2            # K+V, 2B/elem
    per_block_int8 = q_layers * 2 * (elems + q_nkv * 4)  # + f32 scales
    nb_bf16 = 10 if smoke else 16
    budget = nb_bf16 * per_block_bf16
    nb_int8 = budget // per_block_int8
    cap_prompt_len, cap_new = 28, 4  # 2 blocks per request at bs=16

    def admitted(kv_dtype, nb):
        eng = _GE(qmodel, max_batch=nb, block_size=16, num_blocks=nb,
                  kv_cache_dtype=kv_dtype)
        count = 0
        crng = np.random.default_rng(3)
        while True:
            p = list(crng.integers(0, qcfg.vocab_size, cap_prompt_len))
            if eng.add_request(f"c{count}", p, max_new_tokens=cap_new) is None:
                return count
            count += 1

    res_bf16 = admitted("bf16", nb_bf16)
    res_int8 = admitted("int8", int(nb_int8))
    capacity = {
        "pool_block_bytes": budget,
        "bf16_blocks": nb_bf16,
        "int8_blocks": int(nb_int8),
        "bf16_resident_requests": res_bf16,
        "int8_resident_requests": res_int8,
        "capacity_ratio": round(res_int8 / res_bf16, 2) if res_bf16 else 0.0,
    }

    # ---- SLO load benchmark: TTFT + inter-token latency percentiles ----
    # An oversubscribed workload: 2x max_batch requests submit up front,
    # so half QUEUE and admit as slots drain — time-to-first-token then
    # includes prefill AND queueing delay, the quantity an SLO actually
    # bounds.  Inter-token latency spreads each macro-step's wall over
    # the tokens it emitted per row (tokens surface per-chunk by design).
    # The same workload replays on a TP-sharded twin over the 2 (virtual)
    # devices forced above, with a greedy-parity gate: the sharded engine
    # must emit bit-identical streams (docs/DECODE.md sharded serving).
    lb = 2 if smoke else 4
    l_new = 6 if smoke else 24
    l_prompt = 8 if smoke else 32
    l_rng = np.random.default_rng(5)
    l_prompts = {f"l{i}": list(l_rng.integers(0, cfg.vocab_size, l_prompt))
                 for i in range(2 * lb)}
    l_blocks = lb * (-(-(l_prompt + l_new) // 16) + 1)

    def run_load(mesh):
        paddle.seed(0)
        lmodel = LlamaForCausalLM(cfg)  # fresh: shard_llama mutates
        lmodel.eval()
        eng = GenerationEngine(lmodel, max_batch=lb, block_size=16,
                               num_blocks=l_blocks, decode_chunk=chunk,
                               mesh=mesh)
        # warm the compiled prefill/decode paths: the percentiles should
        # describe steady-state serving, not the first-trace compile
        eng.add_request("warm", l_prompts["l0"], max_new_tokens=l_new)
        while eng.has_work():
            eng.step()
        submit, ttft, itl, last = {}, {}, [], {}
        t0 = time.perf_counter()
        for rid, p in l_prompts.items():
            submit[rid] = time.perf_counter()
            first = eng.add_request(rid, p, max_new_tokens=l_new)
            if first is not None:
                now = time.perf_counter()
                ttft[rid] = now - submit[rid]
                last[rid] = now
        while eng.has_work():
            ts = time.perf_counter()
            out = eng.step()
            now = time.perf_counter()
            for rid, toks in out.items():
                n = len(toks) if isinstance(toks, list) else 1
                if rid not in ttft:  # queue-admitted: first token is here
                    ttft[rid] = now - submit[rid]
                    # the rest of this chunk spreads over THIS step's
                    # wall (anchoring at `now` would record zero-length
                    # gaps and deflate the ITL percentiles)
                    last[rid] = ts
                    n -= 1
                if n > 0:
                    gap = (now - last[rid]) / n
                    itl.extend([gap] * n)
                    last[rid] = now
        wall = time.perf_counter() - t0

        def pct(xs):
            return {p: round(float(np.percentile(xs, int(p[1:]))) * 1e3, 3)
                    for p in ("p50", "p95", "p99")}

        toks = sum(len(eng.result(r)) for r in l_prompts)
        return {"ttft_ms": pct(list(ttft.values())), "itl_ms": pct(itl),
                "tokens_per_sec": round(toks / wall, 2),
                "results": {r: eng.result(r) for r in l_prompts}}

    slo_single = run_load(None)
    slo_tp, tp_match = None, True
    if len(jax.devices()) >= 2:
        from paddle_tpu.distributed.auto_parallel import ProcessMesh

        slo_tp = run_load(ProcessMesh(np.arange(2), ["mp"]))
        tp_match = slo_tp["results"] == slo_single["results"]
        if not tp_match:
            print("bench_decode: TP LOAD PARITY FAILURE", file=sys.stderr)
    slo = {
        "requests": 2 * lb,
        "max_batch": lb,
        "new_tokens": l_new,
        "tp_tokens_match": tp_match,
        "single": {k: v for k, v in slo_single.items() if k != "results"},
        "tp": (None if slo_tp is None
               else {k: v for k, v in slo_tp.items() if k != "results"}),
    }

    # ---- snapshot/restore: live-engine fault tolerance timing ----------
    # One mid-flight engine (resident greedy requests) snapshots through
    # the atomic commit protocol and restores onto a fresh engine; the
    # restored engine must finish every stream exactly as an
    # uninterrupted twin — the bit-exact-resume contract, timed.  Wall
    # numbers are the preemption budget: what a SIGTERM costs to honor.
    import shutil as _shutil

    from paddle_tpu.serving import restore_engine, snapshot_stats

    def run_snap(snap_dir):
        eng = GenerationEngine(model, max_batch=B, block_size=16,
                               num_blocks=par_blocks, decode_chunk=chunk)
        for rid, p in prompts.items():
            eng.add_request(rid, p, max_new_tokens=par_new)
        eng.step()  # mid-flight: pools poured, streams open
        if snap_dir is None:
            while eng.has_work():
                eng.step()
            return {r: eng.result(r) for r in prompts}, None
        t0 = time.perf_counter()
        eng.snapshot(snap_dir)
        save_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng2 = restore_engine(model, snap_dir)
        restore_s = time.perf_counter() - t0
        while eng2.has_work():
            eng2.step()
        return ({r: eng2.result(r) for r in prompts},
                {"save_ms": round(save_s * 1e3, 3),
                 "restore_ms": round(restore_s * 1e3, 3)})

    snap_ref, _ = run_snap(None)
    snap_stats0 = snapshot_stats()
    snap_dir = tempfile.mkdtemp(prefix="bench_decode_snap_")
    try:
        snap_got, snap_timing = run_snap(snap_dir)
    finally:
        _shutil.rmtree(snap_dir, ignore_errors=True)
    snap_match = snap_got == snap_ref
    if not snap_match:
        print("bench_decode: SNAPSHOT RESUME PARITY FAILURE", file=sys.stderr)
    snapshot = dict(
        snap_timing,
        bytes=snapshot_stats()["bytes"] - snap_stats0["bytes"],
        resume_tokens_match=snap_match,
    )

    # ---- overload: long prefill vs resident streams' inter-token SLO ----
    # The adversarial mix: ov_b short streams are mid-decode when one
    # long prompt arrives.  The atomic engine prefills it in one stall at
    # the admission boundary; the chunked engine pours one block per
    # macro-step between decode dispatches.  Measured on the RESIDENT
    # streams only — the long request's prefill is the disturbance, the
    # residents' p99 ITL is the quantity under test.
    from paddle_tpu.profiler import decode_stats as _dstats

    # chunk = one pool block.  On the CPU proxy the eager forward has a
    # ~90-200ms per-dispatch floor, so the contrast only shows once the
    # prompt's quadratic attention dwarfs it: at 4096 tokens in 512-token
    # blocks the atomic stall is ~8x the worst single chunk (measured
    # ~1.9s vs ~0.26s) AND chunked throughput is higher because the
    # residents never stop decoding (on a TPU the fused prefill chain
    # makes far smaller chunks pay off; the direction is what gates).
    if on_accel:
        ov_bs, ov_b, ov_prompt, ov_long, ov_new = 512, 8, 16, 2048, 32
    elif smoke:
        ov_bs, ov_b, ov_prompt, ov_long, ov_new = 512, 8, 8, 2048, 8
    else:
        ov_bs, ov_b, ov_prompt, ov_long, ov_new = 512, 8, 8, 4096, 16
    ov_rng = np.random.default_rng(9)
    ov_shorts = {f"o{i}": list(ov_rng.integers(0, cfg.vocab_size, ov_prompt))
                 for i in range(ov_b)}
    ov_lp = list(ov_rng.integers(0, cfg.vocab_size, ov_long))
    # per-seq table width is num_blocks // max_batch: size the pool so
    # every slot's table can hold the LONG request's pages
    ov_blocks = (ov_b + 1) * (-(-(ov_long + ov_new) // ov_bs) + 1)

    def run_overload(chunked):
        eng = GenerationEngine(model, max_batch=ov_b + 1, block_size=ov_bs,
                               num_blocks=ov_blocks, decode_chunk=2,
                               prefill_chunk_blocks=1 if chunked else None)
        # warm with the LONG prompt shape: both the atomic full-length
        # prefill and the block-wide chunk forwards compile here, so the
        # measured stall is prefill COMPUTE, not trace+compile
        eng.add_request("warm", ov_lp, max_new_tokens=ov_new)
        while eng.has_work():
            eng.step()
        for rid, p in ov_shorts.items():
            eng.add_request(rid, p, max_new_tokens=ov_new)
        eng.step()  # residents mid-decode when the long prompt lands
        itl, last, t0 = [], {}, time.perf_counter()
        steps = 0
        while eng.has_work() or steps == 0:
            if steps == 1:
                # submitted INSIDE the measured window, after the first
                # step anchored every resident's `last`: the atomic
                # engine's synchronous admission prefill lands between
                # two measured steps instead of hiding before t0
                eng.add_request("long", ov_lp, max_new_tokens=ov_new)
            ts = time.perf_counter()
            out = eng.step()
            now = time.perf_counter()
            steps += 1
            for rid, toks in out.items():
                if rid == "long":
                    continue
                n = len(toks) if isinstance(toks, list) else 1
                if rid not in last:
                    last[rid] = ts
                    n -= 1
                if n > 0:
                    itl.extend([(now - last[rid]) / n] * n)
                    last[rid] = now
        wall = time.perf_counter() - t0
        toks = sum(len(eng.result(r)) for r in ov_shorts) + \
            len(eng.result("long"))
        return {"itl_p99_ms": round(float(np.percentile(itl, 99)) * 1e3, 3),
                "tokens_per_sec": round(toks / wall, 2),
                "results": {r: eng.result(r)
                            for r in list(ov_shorts) + ["long"]}}

    ov_chunks0 = _dstats()["prefill_chunks"]
    ov_atomic = run_overload(chunked=False)
    ov_atomic_chunks = _dstats()["prefill_chunks"] - ov_chunks0
    ov_chunked = run_overload(chunked=True)
    ov_prefill_chunks = (_dstats()["prefill_chunks"] - ov_chunks0
                         - ov_atomic_chunks)
    ov_match = ov_chunked["results"] == ov_atomic["results"]
    if not ov_match:
        print("bench_decode: OVERLOAD PARITY FAILURE", file=sys.stderr)

    # preemption sub-scenario: a seeded LOW stream parked by a HIGH
    # arrival (single slot forces the eviction), re-admitted, and checked
    # token-for-token against a never-preempted reference
    pre_p = ov_shorts["o1"]

    def run_preempt(preempt):
        eng = GenerationEngine(model, max_batch=1, block_size=16,
                               num_blocks=ov_blocks, decode_chunk=2)
        eng.add_request("low", pre_p, max_new_tokens=ov_new,
                        temperature=0.7, seed=11,
                        priority="low" if preempt else "normal")
        eng.step()
        if preempt:
            eng.add_request("high", ov_shorts["o2"], max_new_tokens=4,
                            priority="high")
        while eng.has_work():
            eng.step()
        return eng.result("low")

    pre_ref = run_preempt(False)
    pre_stats0 = _dstats()
    pre_got = run_preempt(True)
    pre_stats = _dstats()
    preemptions = pre_stats["preemptions"] - pre_stats0["preemptions"]
    readmits = (pre_stats["preempt_readmits"]
                - pre_stats0["preempt_readmits"])
    preempt_match = pre_got == pre_ref and preemptions >= 1 and readmits >= 1
    if not preempt_match:
        print("bench_decode: PREEMPT RESUME PARITY FAILURE", file=sys.stderr)

    overload = {
        "residents": ov_b,
        "long_prompt_tokens": ov_long,
        "itl_p99_ms_chunked": ov_chunked["itl_p99_ms"],
        "itl_p99_ms_atomic": ov_atomic["itl_p99_ms"],
        "tokens_per_sec_chunked": ov_chunked["tokens_per_sec"],
        "tokens_per_sec_atomic": ov_atomic["tokens_per_sec"],
        "streams_identical": ov_match,
        "prefill_chunks": ov_prefill_chunks,
        "preemptions": preemptions,
        "preempt_readmits": readmits,
        "preempted_stream_identical": pre_got == pre_ref,
    }

    print(json.dumps({
        "metric": "serving_decode_chunked_speedup",
        "value": round(speedup, 2),
        "unit": "x",
        "vs_baseline": 0.0,
        "tokens_match": tokens_match,
        "detail": {
            "batch": B,
            "chunk": chunk,
            "per_token_tokens_per_sec": round(per_token_tps, 2),
            "chunked_tokens_per_sec": round(chunked_tps, 2),
            "depth_sweep": depth_sweep,
            "shared_prefix": shared_prefix,
            "int8_kv_capacity": capacity,
            "slo": slo,
            "snapshot": snapshot,
            "overload": overload,
            "decode_stats": {
                "dispatches": st["dispatches"],
                "tokens": st["tokens"],
                "sync_seconds": round(st["sync_seconds"], 4),
            },
        },
    }))
    return 0 if (tokens_match and prefix_match and tp_match
                 and snap_match and ov_match and preempt_match) else 1


if __name__ == "__main__":
    sys.exit(main())
