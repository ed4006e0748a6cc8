#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py               # one TPU chip: train phase, serve phase
    python chip_smoke.py --four-chips  # four chips: the sharded train step and
                                       # the one-chip step it is compared with

Drives the two entry points users of this framework call — the compiled train
step (`paddle_tpu.jit.TrainStep`) and the serving engine
(`paddle_tpu.serving.GenerationEngine`) — at the full widths of
`models.llama.llama_7b` (hidden 4096, 32x128 heads, FFN 11008, vocab 32000,
bf16) with seeded random weights.  Depth is the only thing cut, and only as far
as the chip's memory forces; the script prints the depth it chose and why.

One process, no child that needs the chip.  It refuses to go on unless jax's
first device is a TPU: there is no CPU route through `main`.  It sets no
platform and names no cache directory; the compile cache follows the one rule
of `paddle_tpu/_core/compile_cache.py` (JAX_COMPILATION_CACHE_DIR if set, else
<checkout>/.jax_cache), so a second run in the same place finds the first
run's executables.  `tests/test_chip_smoke.py` rehearses the phase functions on
the CPU with a tiny config.

A check that fails raises: no try/except around a phase, no retry tier, and the
exit code is then nonzero.  The last line of standard output is one JSON
object, {"ok": true, "device": {"platform", "kind", "count"}}, printed only
after every phase passed.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time

# Stated bf16 tolerances.  The Pallas step and its plain-jnp twin (and the
# one-chip and four-chip steps) run the same math through different kernels in
# bfloat16 (8 significant bits); their mean cross-entropy over thousands of
# tokens agrees far better than one bf16 ulp of a single value, and 2% of the
# loss bounds it with room.
LOSS_RTOL = 2e-2
# Arguments of the train step per parameter: bf16 weight (2) + fp32 master
# weight, first and second moment (12).  Gradients are temporaries.
TRAIN_STATE_BYTES_PER_PARAM = 14
# Share of the device's memory the sized arguments + temporaries may take: the
# rest is for the program itself, the batch, the copy of the state that
# arrives from the host on the first call, and fragmentation.
MEMORY_SHARE = 0.8


def say(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str):
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")
    say(f"ok: {what}")


# --------------------------------------------------------------- depth ----

def llama_params(cfg, layers: int) -> int:
    h, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    kv = cfg.num_key_value_heads * (h // cfg.num_attention_heads)
    per_layer = 2 * h * h + 2 * h * kv + 3 * h * f + 2 * h  # attn, mlp, norms
    return layers * per_layer + 2 * v * h + h  # + embedding, lm head, norm


def train_temp_bytes(cfg, layers: int, batch: int, seq: int) -> int:
    """Temporaries of the LARGER of the two steps the phase runs — the
    plain-jnp twin, whose attention keeps its [B, N, S, S] probabilities
    (fp32 logits + bf16 probs: 6 bytes an entry) where flash attention keeps
    O(S).  Fitted to XLA's own count: `memory_analysis().temp_size_in_bytes`
    of the twin step compiled for a described v5e at llama_7b widths gave
    1.18 / 1.96 / 2.69 GiB at depth 1 / 2 / 3 (batch 1 x 2048) and 3.95 GiB
    at depth 2, batch 2; this form gives 1.21 / 1.99 / 2.77 and 3.98.  The
    Pallas step's were 0.46 / 0.76 / 1.06 GiB."""
    per_token = (layers * (cfg.num_attention_heads * seq * 6
                           + 4 * cfg.hidden_size)
                 + 7 * cfg.vocab_size)
    return batch * seq * per_token


def choose_depth(kind: str, cfg, bytes_limit: int, *, batch=1, seq=0,
                 ceiling=32) -> tuple[int, str]:
    """Largest depth whose sized footprint fits MEMORY_SHARE of the device."""
    budget = int(bytes_limit * MEMORY_SHARE)

    def need(layers):
        if kind == "train":
            return (llama_params(cfg, layers) * TRAIN_STATE_BYTES_PER_PARAM
                    + train_temp_bytes(cfg, layers, batch, seq))
        return llama_params(cfg, layers) * 2  # serving: bf16 weights

    depth = max((n for n in range(1, ceiling + 1) if need(n) <= budget),
                default=0)
    if depth == 0:
        raise RuntimeError(
            f"{kind}: even one layer needs {need(1) / 2**30:.1f} GiB > "
            f"{budget / 2**30:.1f} GiB budget")
    why = (f"{depth} layer(s) need {need(depth) / 2**30:.2f} GiB of the "
           f"{budget / 2**30:.2f} GiB budget ({MEMORY_SHARE:.0%} of "
           f"{bytes_limit / 2**30:.2f} GiB); "
           + (f"{depth + 1} would need {need(depth + 1) / 2**30:.2f} GiB"
              if depth < ceiling else f"capped at {ceiling}"))
    return depth, why


# --------------------------------------------------------------- train ----

def _loss_fn(model, ids, labels):
    return model(ids, labels=labels)[0]


def _seeded_batch(cfg, batch, seq, seed):
    import numpy as np

    import paddle_tpu as paddle

    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    return paddle.to_tensor(ids), paddle.to_tensor(labels)


def _build_train_step(cfg, seed, lr):
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    return model, opt, TrainStep(model, opt, _loss_fn)


def _release():
    """Drop what the previous step left on the device (its arrays die with
    their Python owners; the executables with jax's caches)."""
    import jax

    gc.collect()
    jax.clear_caches()


def _twin_first_loss(cfg, ids, labels, seed, lr) -> float:
    """First-step loss of the SAME step built with FLAGS_use_pallas=false:
    the plain-jnp twin of every Pallas kernel on the path."""
    import paddle_tpu as paddle

    paddle.set_flags({"FLAGS_use_pallas": "false"})
    try:
        _model, _opt, step = _build_train_step(cfg, seed, lr)
        loss = float(step(ids, labels))
    finally:
        paddle.set_flags({"FLAGS_use_pallas": "auto"})
    del _model, _opt, step
    _release()
    return loss


def _on(device, arrays) -> bool:
    return all(a.devices() == {device} for a in arrays)


def train_phase(cfg, *, batch, seq, steps, seed, device, lr=3e-4) -> dict:
    """TrainStep over `cfg` for `steps` steps on one seeded batch."""
    import jax

    from paddle_tpu import ops, profiler
    from paddle_tpu.device import hard_sync

    on_tpu = device.platform == "tpu"
    say(f"train: {cfg.num_hidden_layers} layer(s), "
        f"{llama_params(cfg, cfg.num_hidden_layers) / 1e6:.0f}M parameters, "
        f"batch {batch} x seq {seq}, AdamW + fp32 master weights")
    ids, labels = _seeded_batch(cfg, batch, seq, seed)

    twin = _twin_first_loss(cfg, ids, labels, seed, lr)
    say(f"train: plain-jnp twin (FLAGS_use_pallas=false) first loss {twin:.4f}")

    model, opt, step = _build_train_step(cfg, seed, lr)
    before = profiler.compile_stats()
    t0 = time.perf_counter()
    first = step(ids, labels)
    jax.block_until_ready(first._value)
    cold_s = time.perf_counter() - t0
    after = profiler.compile_stats()
    say(f"train: first step {cold_s:.1f} s = optimizer state made on the "
        "host + trace + compile + transfer + run; of it tracing "
        f"{after['trace_seconds'] - before['trace_seconds']:.1f} s, XLA "
        f"compile {after['compile_seconds'] - before['compile_seconds']:.1f}"
        f" s; persistent cache hits "
        f"{after['persistent_cache_hits'] - before['persistent_cache_hits']}"
        f" misses "
        f"{after['persistent_cache_misses'] - before['persistent_cache_misses']}")
    losses = [float(first)] + [float(step(ids, labels))
                               for _ in range(steps - 1)]
    say("train: losses " + " ".join(f"{x:.4f}" for x in losses))

    # the same step under the two barriers (device/__init__.py hard_sync was
    # written for a transport whose block_until_ready returned at dispatch)
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        last = step(ids, labels)
    jax.block_until_ready(last._value)
    ms_bur = (time.perf_counter() - t0) * 1e3 / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        last = step(ids, labels)
    hard_sync(last)
    ms_sync = (time.perf_counter() - t0) * 1e3 / reps
    tokens = batch * seq
    say(f"train: step {ms_bur:.1f} ms under jax.block_until_ready, "
        f"{ms_sync:.1f} ms under device.hard_sync (mean of {reps}; "
        f"{tokens / ms_bur * 1e3:.0f} resp. {tokens / ms_sync * 1e3:.0f} "
        f"tokens/s); ratio {ms_bur / ms_sync:.3f} — block_until_ready is "
        + ("honest here: it waits for the device"
           if ms_bur > 0.9 * ms_sync
           else "NOT honest here: it returns before the device is done"))

    check(all(math.isfinite(x) for x in losses), "every train loss is finite")
    check(losses[-1] < losses[0],
          f"train loss fell on the repeated batch ({losses[0]:.4f} -> "
          f"{losses[-1]:.4f})")
    check(abs(losses[0] - twin) <= LOSS_RTOL * abs(twin),
          f"first loss {losses[0]:.4f} agrees with the plain-jnp twin "
          f"{twin:.4f} within {LOSS_RTOL:.0%}")
    # params were initialised eagerly and the optimizer state was made on the
    # host (jit/__init__.py _ensure_built): show that all of it arrived
    params = [p._value for p in model.parameters()]
    state = [t._value for t in opt.opt_state_tensors()]
    check(_on(device, [last._value]), f"the loss lives on {device}")
    check(_on(device, params),
          f"all {len(params)} parameters live on {device} after the step")
    check(_on(device, state),
          f"all {len(state)} optimizer-state tensors live on {device}")
    if on_tpu:
        check(ops.use_pallas(), "ops.use_pallas() is true")
        text = step.lower(ids, labels).compile().as_text()
        check("tpu_custom_call" in text,
              "the compiled train step contains tpu_custom_call "
              f"({text.count('tpu_custom_call')} mentions): Pallas kernels "
              "compiled by Mosaic, not interpreted")
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        say(f"train: peak device memory {stats['peak_bytes_in_use'] / 2**30:.2f}"
            f" GiB of {stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
    del model, opt, step
    _release()
    return {"losses": losses, "twin": twin, "cold_s": cold_s,
            "ms_block_until_ready": ms_bur, "ms_hard_sync": ms_sync}


# --------------------------------------------------------------- serve ----

def serve_phase(cfg, *, prompt_lens, max_new_tokens, seed, device,
                max_batch=4, block_size=16, num_blocks=256) -> dict:
    """GenerationEngine over `cfg` in eval mode: a handful of requests of
    different prompt lengths (one more than the batch holds, so one queues),
    stepped until all finish."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.serving import GenerationEngine

    say(f"serve: {cfg.num_hidden_layers} layer(s), "
        f"{llama_params(cfg, cfg.num_hidden_layers) / 1e6:.0f}M parameters "
        f"in {cfg.dtype}, {len(prompt_lens)} requests, prompts "
        f"{list(prompt_lens)}, {max_new_tokens} new tokens each, "
        f"max_batch {max_batch}, {num_blocks} blocks of {block_size}")
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    engine = GenerationEngine(model, max_batch=max_batch,
                              block_size=block_size, num_blocks=num_blocks)
    rng = np.random.default_rng(seed)
    prompts = {f"r{i}": rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for i, n in enumerate(prompt_lens)}

    before = profiler.compile_stats()
    t0 = time.perf_counter()
    for rid, prompt in prompts.items():
        engine.add_request(rid, prompt, max_new_tokens=max_new_tokens)
    admit_s = time.perf_counter() - t0
    steps = 0
    t0 = time.perf_counter()
    while engine.has_work():
        engine.step()
        steps += 1
        if steps > len(prompts) * max_new_tokens:  # one token a step at worst
            raise RuntimeError("chip_smoke check failed: the engine stopped "
                               f"making progress after {steps} steps")
    decode_s = time.perf_counter() - t0
    after = profiler.compile_stats()
    say(f"serve: admission + prefill {admit_s:.1f} s, {steps} macro-steps "
        f"{decode_s:.1f} s (both include compiles: "
        f"{after['compiles'] - before['compiles']} compiles, "
        f"{after['compile_seconds'] - before['compile_seconds']:.1f} s; "
        f"persistent cache hits "
        f"{after['persistent_cache_hits'] - before['persistent_cache_hits']} "
        f"misses "
        f"{after['persistent_cache_misses'] - before['persistent_cache_misses']})")

    results = {rid: list(engine.result(rid)) for rid in prompts}
    check(all(len(toks) == max_new_tokens for toks in results.values()),
          f"every request completed with {max_new_tokens} tokens")
    check(all(0 <= t < cfg.vocab_size for toks in results.values()
              for t in toks), f"every token is in [0, {cfg.vocab_size})")

    # reference: the argmax of a plain full forward of the prompt.  The
    # engine's prefill computes the last position's logits alone, so a
    # matmul of another shape may round a bf16 logit one ulp apart: a first
    # token whose reference logit is within one ulp of the maximum is the
    # same answer.
    exact = 0
    with paddle.no_grad():
        for rid, prompt in prompts.items():
            logits = model(paddle.to_tensor(prompt[None, :]))
            last = np.asarray(logits._value[0, -1].astype("float32"))
            top = float(last.max())
            ulp = abs(top) * 2.0 ** -7 if cfg.dtype == "bfloat16" else 0.0
            got = results[rid][0]
            exact += int(got == int(last.argmax()))
            check(last[got] >= top - ulp,
                  f"{rid} (prompt {len(prompt)}): first token {got} is the "
                  f"argmax of the plain full forward "
                  f"(logit {last[got]:.4f}, max {top:.4f})")
    say(f"serve: {exact} of {len(prompts)} first tokens equal the reference "
        "argmax exactly (the rest within one bf16 ulp of it)")
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        say(f"serve: peak device memory (process so far) "
            f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB")
    del engine, model
    _release()
    return {"results": results, "exact_first_tokens": exact}


# ---------------------------------------------------------- four chips ----

def four_chip_phase(cfg, *, batch, seq, steps, seed, devices, lr=3e-4) -> dict:
    """ShardedTrainStep over a dp=2 x mp=2 ProcessMesh of `devices`, against
    the one-chip TrainStep on devices[0] with the same seed and batch."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import ProcessMesh, ShardedTrainStep
    from paddle_tpu.models.llama import LlamaForCausalLM, shard_llama

    check(len(devices) >= 4, f"four devices to build the mesh from "
          f"({len(devices)} found)")
    devices = list(devices[:4])
    say(f"four chips: {cfg.num_hidden_layers} layer(s), "
        f"{llama_params(cfg, cfg.num_hidden_layers) / 1e6:.0f}M parameters, "
        f"batch {batch} x seq {seq}, dp=2 x mp=2 over {devices}")
    ids, labels = _seeded_batch(cfg, batch, seq, seed)

    # what it is compared with: the one-chip step (Pallas kernels on a TPU)
    model, opt, step = _build_train_step(cfg, seed, lr)
    t0 = time.perf_counter()
    ref = [float(step(ids, labels)) for _ in range(steps)]
    say(f"four chips: one-chip reference losses "
        + " ".join(f"{x:.4f}" for x in ref)
        + f" ({time.perf_counter() - t0:.1f} s with compile)")
    del model, opt, step
    _release()

    mesh = ProcessMesh(np.array([d.id for d in devices]).reshape(2, 2),
                       ["dp", "mp"])
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    shard_llama(model, mesh, mp_axis="mp")
    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    step = ShardedTrainStep(model, opt, _loss_fn, mesh)
    t0 = time.perf_counter()
    losses = []
    for i in range(steps):
        losses.append(float(step(ids, labels)))
        say(f"four chips: step {i} loss {losses[-1]:.4f} "
            f"({time.perf_counter() - t0:.1f} s since start)")

    check(all(math.isfinite(x) for x in losses),
          "every sharded loss is finite")
    for i, (got, want) in enumerate(zip(losses, ref)):
        check(abs(got - want) <= LOSS_RTOL * abs(want),
              f"step {i}: sharded loss {got:.4f} agrees with the one-chip "
              f"loss {want:.4f} within {LOSS_RTOL:.0%}")

    # code that never saw two chips may put everything on the first
    def shards(t):
        return {s.device: s.data.nbytes for s in t._value.addressable_shards}

    layer0 = model.model.layers[0]
    col = layer0.self_attn.q_proj.weight     # Shard(1) on mp, replicated on dp
    row = layer0.mlp.down_proj.weight        # Shard(0) on mp
    for name, w in (("q_proj.weight", col), ("down_proj.weight", row)):
        per = shards(w)
        check(set(per) == set(devices),
              f"{name} has a shard on each of the four devices")
        check(set(per.values()) == {w._value.nbytes // 2},
              f"{name}: each device holds 1/2 of its "
              f"{w._value.nbytes / 2**20:.0f} MiB (mp=2, replicated over dp)")
    moment = next(acc for (kind, pid), acc in opt._accumulators.items()
                  if pid == id(col) and kind != "master_weight"
                  and acc._value.shape == col._value.shape)
    per = shards(moment)
    check(set(per) == set(devices)
          and set(per.values()) == {moment._value.nbytes // 4},
          "q_proj's AdamW moment: each device holds 1/4 (ZeRO-1 over dp on "
          "top of mp)")
    holders = {d for p in model.parameters() for d in p._value.devices()}
    check(holders == set(devices),
          "the parameters sit on four distinct devices")
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            say(f"four chips: {d} peak memory "
                f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB")
    del model, opt, step
    _release()
    return {"losses": losses, "reference": ref}


# ---------------------------------------------------------------- main ----

def _environment_lines(cache_dir):
    """What was built or read from outside the committed files."""
    import os

    from paddle_tpu import _native
    from paddle_tpu._core.compile_cache import ENV_VAR
    from paddle_tpu.ops import autotune

    say(f"compile cache: {cache_dir} "
        f"({ENV_VAR if os.environ.get(ENV_VAR) else 'the checkout default'})")
    say("native runtime (paddle_tpu/_native/src/*.cc, built on first import): "
        + ("built and loaded" if _native.AVAILABLE
           else f"NOT available — {_native.BUILD_ERROR or 'no build attempted'}"))
    tuned = autotune.cache()
    user = os.path.exists(tuned.user_path)
    say(f"kernel tiles: committed table {os.path.basename(tuned.seed_path)} "
        f"({'found' if os.path.exists(tuned.seed_path) else 'MISSING'}); "
        f"{tuned.user_path} {'exists' if user else 'absent'}, runtime-tuned "
        f"entries contributing: {tuned.runtime_entries}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the sharded train step on a dp=2 x mp=2 "
                         "mesh of four chips and the one-chip step it is "
                         "compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: jax's first device is {dev}, not a TPU; this "
              "script has no CPU route", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs four chips, jax sees "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from paddle_tpu._core import compile_cache
    from paddle_tpu.models.llama import llama_7b

    say(f"device: {dev.device_kind} ({dev.platform}), {len(devices)} visible; "
        f"jax {jax.__version__}")
    _environment_lines(compile_cache.enable())
    limit = (dev.memory_stats() or {})["bytes_limit"]
    widths = llama_7b()
    seq = 2048
    t_start = time.perf_counter()

    if args.four_chips:
        batch = 2  # one sequence per data-parallel group
        depth, why = choose_depth("train", widths, limit, batch=batch, seq=seq,
                                  ceiling=1)
        say(f"four chips: depth {depth} — {why}; one layer is enough to put "
            "every sharded weight kind on the mesh, and the one-chip "
            "reference must fit device 0 whole")
        four_chip_phase(llama_7b(num_hidden_layers=depth), batch=batch,
                        seq=seq, steps=3, seed=args.seed, devices=devices)
    else:
        batch = 1
        depth, why = choose_depth("train", widths, limit, batch=batch, seq=seq)
        say(f"train: depth {depth} — {why}")
        train_phase(llama_7b(num_hidden_layers=depth), batch=batch, seq=seq,
                    steps=4, seed=args.seed, device=dev)
        depth, why = choose_depth("serve", widths, limit, ceiling=8)
        say(f"serve: depth {depth} — {why}")
        serve_phase(llama_7b(num_hidden_layers=depth),
                    prompt_lens=(5, 16, 37, 64, 100), max_new_tokens=12,
                    seed=args.seed, device=dev)

    say(f"all phases passed in {time.perf_counter() - t_start:.0f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
