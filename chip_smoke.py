#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py               # one TPU chip: train phase, serve phase
    python chip_smoke.py --four-chips  # four chips: the sharded train step and
                                       # the one-chip step it is compared with
    python chip_smoke.py --mla-moe-logits  # one chip: the latent-attention /
                                       # routed-expert model's LOGITS through
                                       # the engine against its plain reference
    python chip_smoke.py --window-moe-logits  # one chip: the window / full
                                       # attention model's LOGITS through the
                                       # engine (pages and rings) against its
                                       # plain reference, and the controls
                                       # that must fail (window ignored, gate
                                       # left out, bfloat16 router / softmax)
    python chip_smoke.py --cca-moe-logits  # one chip: the convolved-latent
                                       # attention / top-1 expert model's
                                       # LOGITS through the engine (pages and
                                       # state a slot) against its plain
                                       # reference, the controls (conv, shift,
                                       # carry, skip left out) and the probes
                                       # on the same inputs (router type, the
                                       # conv tail across a block boundary,
                                       # decode attention over its pools)
    python chip_smoke.py --dense-softmax   # one chip: the dense decode attention
                                       # (the path it selects: the Pallas
                                       # kernel on a chip) over bfloat16 paged
                                       # pools at the two cells' geometries
                                       # against a float32 softmax on the
                                       # same rows
    python chip_smoke.py --flash-softmax   # one chip: the flash kernels, forward
                                       # and backward, on bfloat16 inputs
                                       # against a float64 softmax

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


# The latent-attention / routed-expert logit comparison (--mla-moe-logits;
# PERF.md section 6, PR 27, has the readings these were set from).  The error
# of a row of logits is max |program - reference| over the vocabulary in units
# of the reference row's standard deviation.
# A token whose k-th and (k+1)-th router logits lie within ROUTER_TIE_TAU of
# each other, one of the two experts held here, is a NEAR TIE: rounding in the
# program's bfloat16 hidden state may hand it another expert's output, a
# different result and not a less precise one.  Near ties are counted and
# compared against the looser MLA_MOE_TIE_TOL; every other row against
# MLA_MOE_LOGIT_TOL.
MLA_MOE_LOGIT_TOL = 0.08
MLA_MOE_TIE_TOL = 0.6
ROUTER_TIE_TAU = 0.05
# Rows of logits are five layers of bfloat16 activations away from the float32
# reference (an rms of 0.012 sigma), which hides what one type inside the
# router or the softmax does.  So those two are ALSO compared on the same
# inputs, where nothing upstream differs (`precision_probes`):
# the share of (token, expert layer) pairs for which the program's router,
# handed the reference's router inputs, chooses the reference's experts
# (float32 at highest precision: every pair but an exact tie; bfloat16: about
# 0.7, CPU rehearsal at the published router's widths) ...
ROUTE_AGREEMENT_MIN = 0.95
# ... and the rms error of the program's decode attention over a paged pool
# against a float32 softmax on the same bfloat16 queries and rows, relative to
# the output's rms, with scores spread as a trained model's are (standard
# deviation SOFTMAX_SCORE_SPREAD; the seeded weights give about 0.3, where
# thousands of near-equal probabilities average any rounding away).  CPU
# rehearsal at the cell's sizes: float32 softmax 0.0012-0.0016 (what rounding
# the probabilities to the rows' type costs), bfloat16 softmax 0.016-0.018.
SOFTMAX_SCORE_SPREAD = 4.0
SOFTMAX_RMS_TOL = 0.005


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


# ------------------------------------- latent attention, routed experts ----

def _rms_error(got, want):
    """rms of the error over the rms of what was wanted."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(((got - want) ** 2).mean() / (want ** 2).mean()))


def _row_error(got, want):
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / want.std())


def _engine_logits(fam, ref, cfg_file, *, seed, prompt_lens, decoded,
                   block_size, label, tol, tie_tol, tau=None) -> dict:
    """A configuration of the family `fam` (a perfbench family module; `ref`
    its reference) served by GenerationEngine: one prompt of each length, and
    LOGITS, not tokens, against the plain float32 reference's full forward
    pass at three places each — the prefill program's last position, and the
    decode step through the resident pools after `decoded[0]` and
    `decoded[1]` decoded tokens, teacher-forced on the engine's own tokens.
    Rows are held to `tol` sigma of the reference (`tie_tol` for a near tie
    of the row's own routing, within `tau`: ROUTER_TIE_TAU where none is
    given) by `rows_within`, which the caller runs once
    every reading has been said.  Returns the model, the reference's weights
    and sizes, per request (ids, places, reference logits, near ties) and the
    program's logits, the errors, and `rows_within`.

    The logits are the engine's own: the prefill program is the executable the
    admission ran (`_prefill_fns`), and the decode logits are the contract's
    decode step — what the macro-step scans — over the engine's RESIDENT pools
    at that boundary (every cache class's: a window class's rings through the
    slots' ring tables); each is tied to the stream by its argmax being the
    token the engine then emitted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.serving import GenerationEngine

    chunk = 8    # FLAGS_decode_chunk's default: tokens a macro-step
    assert all(d % chunk == 0 for d in decoded), "whole macro-steps"
    model = fam.build(cfg_file, seed, training=False)
    cfg = model.config
    jax.block_until_ready([p._value for p in model.parameters()])
    blocks = max(-(-(n + decoded[-1] + 2 * chunk) // block_size)
                 for n in prompt_lens)
    eng = GenerationEngine(model, max_batch=len(prompt_lens),
                           block_size=block_size,
                           num_blocks=blocks * len(prompt_lens))
    contract = model.serving_contract()
    rng = np.random.default_rng([seed % 2 ** 63, 27])
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in prompt_lens]
    say(f"{label}: {cfg.num_hidden_layers} layers, hidden "
        f"{cfg.hidden_size}, experts {cfg.held} held, cache pools "
        f"{[p.name for p in contract.spec.pools]}, "
        f"prompts {list(prompt_lens)}, logits at the prefill and after "
        f"{list(decoded)} decoded tokens")

    def prefill_logits(i):
        s = len(prompts[i])
        s_pad = eng._prefill_bucket(s, 0)
        ids = np.zeros((1, s_pad), np.int32)
        ids[0, :s] = prompts[i]
        return np.asarray(eng._prefill_program(s_pad, 0)(
            [t._value for t in eng._state], ids, np.int32(s), None)[0],
            np.float32)

    def decode_logits():
        """The next token's logits of every resident row, from the pools as
        the engine holds them now (functional: the pools are not touched)."""
        rows = eng.next_token_logits()
        return [rows[f"r{i}"] for i in range(len(prompts))]

    def drive():
        """Admit, decode to both boundaries; the program's logits per request
        and place, and the tokens."""
        firsts = [eng.add_request(f"r{i}", p, max_new_tokens=decoded[-1] + 2 * chunk)
                  for i, p in enumerate(prompts)]
        got = [[prefill_logits(i)] for i in range(len(prompts))]
        for i, first in enumerate(firsts):
            check(int(got[i][0].argmax()) == first,
                  f"r{i}: the prefill program's argmax is the first token")
        steps = 0
        for d in decoded:
            while steps < d // chunk:
                eng.step()
                steps += 1
            lg = decode_logits()
            for i in range(len(prompts)):
                got[i].append(lg[i])
        eng.step()       # the tokens those last logits predict
        toks = [list(eng._results[f"r{i}"]) for i in range(len(prompts))]
        for i in range(len(prompts)):
            check(int(got[i][-1].argmax()) == toks[i][decoded[-1] + 1],
                  f"r{i}: the decode step's argmax over the resident pool is "
                  f"the token the engine emitted next")
        while eng.has_work():
            eng.step()
        return got, toks

    t0 = time.perf_counter()
    got, toks = drive()
    say(f"{label}: engine driven in {time.perf_counter() - t0:.1f} s")
    weights, sizes = fam.reference_weights(model), fam.reference_sizes(cfg_file)
    errors, ties, refs, rows = [], [], [], []
    for i, p in enumerate(prompts):
        t0 = time.perf_counter()
        ids = np.concatenate([p, np.asarray(toks[i][:decoded[-1] + 1], np.int32)])
        at = [len(p) - 1] + [len(p) + d for d in decoded]
        want, tie = ref.logits_and_near_ties(
            weights, sizes, ids, at, ROUTER_TIE_TAU if tau is None else tau)
        want, tie = np.asarray(want), np.asarray(tie)
        refs.append((ids, at, want, tie))
        for j, place in enumerate(["prefill"] + [f"decoded {d}" for d in decoded]):
            err = _row_error(got[i][j], want[j])
            errors.append(err)
            ties.append(bool(tie[j]))
            limit = tie_tol if tie[j] else tol
            rows.append((err <= limit,
                         f"r{i} (prompt {len(p)}) {place}: logits within "
                         f"{limit} sigma of the reference ({err:.4f}"
                         + (", a near tie of its own routing)" if tie[j]
                            else ")")))
        say(f"{label}: reference of r{i} in {time.perf_counter() - t0:.1f} s; "
            "rows " + ", ".join(f"{e:.4f}" for e in errors[-len(at):]))
    clean = [e for e, t in zip(errors, ties) if not t]
    say(f"{label}: {len(errors)} rows compared, {sum(ties)} near ties; "
        f"worst clean row {max(clean):.4f} sigma, worst of all "
        f"{max(errors):.4f} sigma (limits {tol} and {tie_tol})")
    del eng

    def rows_within():
        for ok, what in rows:
            check(ok, what)

    return {"model": model, "weights": weights, "sizes": sizes, "refs": refs,
            "got": got, "prompts": prompts, "errors": errors, "ties": ties,
            "rows_within": rows_within}


def mla_moe_logits_phase(cfg_file, *, seed, device, prompt_lens, decoded,
                         block_size) -> dict:
    """The configuration `cfg_file` (a perfbench configuration of the
    `mla_moe` family) through `_engine_logits` (the prefill program and the
    decode step through the paged latent pool against the float32
    reference), then `precision_probes`."""
    from paddle_tpu import profiler, serving
    from perfbench import reference_mla_moe as ref
    from perfbench.families import mla_moe as fam

    serving.reset_decode_stats()
    traces = profiler.compile_stats()
    run = _engine_logits(fam, ref, cfg_file, seed=seed,
                         prompt_lens=prompt_lens, decoded=decoded,
                         block_size=block_size, label="mla_moe logits",
                         tol=MLA_MOE_LOGIT_TOL, tie_tol=MLA_MOE_TIE_TOL)
    run["rows_within"]()
    out = {"errors": run["errors"], "ties": run["ties"]}
    # which form the engine's decode attention took, and what it read
    st, now = serving.decode_stats(), profiler.compile_stats()
    out["positions_read"] = st["attn_positions_read"]
    out["positions_live"] = st["attn_positions_live"]
    out["kernel_traces"], out["xla_traces"] = (
        now[k] - traces[k] for k in ("paged_kernel_traces",
                                     "paged_xla_traces"))
    say(f"mla_moe logits: the engine's decode attention read "
        f"{out['positions_read']} positions for {out['positions_live']} live "
        f"(amplification "
        f"{out['positions_read'] / max(1, out['positions_live']):.3f}); traced "
        f"{out['kernel_traces']} times as the kernel paged_decode, "
        f"{out['xla_traces']} as XLA's form")
    out.update(precision_probes(
        run["model"], run["weights"], run["sizes"],
        run["refs"][0][0][:len(run["prompts"][0])], seed=seed,
        block_size=block_size, lens=[n + decoded[-1] for n in prompt_lens]))
    _release()
    return out


def _bfloat16_route(m, router_w, *, top_k, scale, normalize=True,
                    scoring="sigmoid"):
    """`models.experts.route` with every type lowered: the control."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.experts import SCORINGS

    bf = jnp.bfloat16
    score = SCORINGS[scoring](jnp.dot(m.astype(bf), router_w.astype(bf)))
    top_s, top_i = jax.lax.top_k(score, top_k)
    w = top_s / jnp.sum(top_s, -1, keepdims=True) if normalize else top_s
    return top_i.astype(jnp.int32), (w * bf(scale)).astype(jnp.float32)


def _bfloat16_softmax_attention(q, pool, tables, lens, *, rank, width):
    """`models.mla_moe.absorbed_attention`'s XLA form with the scores
    rounded to bfloat16 and the softmax computed in it: the control (the
    pool may be wider than q: `pool_width`)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import paged_attention as pa

    keys = pa.paged_gather(pool, tables)[:, 0, :, :q.shape[-1]]
    score = jnp.einsum("bnr,bsr->bns", q, keys,
                       preferred_element_type=jnp.float32)
    score = score.astype(jnp.bfloat16) / jnp.bfloat16(math.sqrt(width))
    seen = jnp.arange(keys.shape[1])[None, :] < lens[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None, :], score, -1e30), axis=-1)
    return jnp.einsum("bns,bsr->bnr", p.astype(keys.dtype), keys[..., :rank],
                      preferred_element_type=jnp.float32)


def precision_probes(model, weights, sizes, ids, *, seed, block_size, lens):
    """The router and the decode softmax, each against the reference ON THE
    SAME INPUTS, and each once more with its types lowered to bfloat16 (the
    controls, which `main` requires to fail at the published widths; a
    rehearsal's few dozen tokens and eight experts have no near ties to
    show): what a row of logits cannot tell.

    Router: `families/mla_moe.routing_agreement` over the prompt `ids` — the
    program's `route` on the reference's own router inputs.  Softmax: the
    program's `absorbed_attention`, in the form it SELECTS for the pool the
    engine would allocate (`pool_width`: on a chip the Pallas kernel
    `paged_decode`, each row's own pages; the line says which), over a
    paged pool of seeded rows (one row a live token, `lens` live tokens a
    sequence, pages in a shuffled order) against
    `reference_mla_moe.absorbed_attention`, queries scaled so that the
    scores spread as a trained model's do."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import profiler
    from paddle_tpu.models import mla_moe
    from paddle_tpu.ops import paged_attention as pa
    from perfbench import reference_mla_moe as ref
    from perfbench.families import mla_moe as fam

    def xla_form(q, pool, tables, lens, *, rank, width):
        # what `absorbed_attention` ran before PR 35, and runs off a chip
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pool.shape[-1] - q.shape[-1])))
        return pa._shared_row_xla(q, pool, tables, lens, rank,
                                  1.0 / math.sqrt(width))

    cfg = model.config
    out = {}
    for name, router in (("route_agreement", None),
                         ("route_agreement_bfloat16", _bfloat16_route)):
        out[name], pairs = fam.routing_agreement(model, weights, sizes, ids,
                                                 ref, route=router)
    say(f"mla_moe probes: on the reference's router inputs the program's "
        f"router chooses the reference's experts for "
        f"{100 * out['route_agreement']:.2f}% of {pairs} (token, expert layer)"
        f" pairs; a bfloat16 router for "
        f"{100 * out['route_agreement_bfloat16']:.2f}% (limit "
        f"{100 * ROUTE_AGREEMENT_MIN:.0f}%)")
    check(out["route_agreement"] >= ROUTE_AGREEMENT_MIN,
          "the program's router agrees with the reference on its own inputs")

    rank, width = cfg.kv_lora_rank, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    heads, row = cfg.num_attention_heads, cfg.latent_width
    per = -(-max(lens) // block_size)
    draw = np.random.default_rng([seed % 2 ** 63, 28])
    tables = jnp.asarray(draw.permutation(per * len(lens)).reshape(len(lens), per),
                         jnp.int32)
    k_pool, k_q = jax.random.split(jax.random.key(seed % 2 ** 31))
    pool = jax.random.normal(k_pool, (per * len(lens), 1, block_size, row),
                             jnp.float32).astype(jnp.bfloat16)
    lens = jnp.asarray(lens, jnp.int32)
    rows = np.asarray(pool[:, 0])[np.asarray(tables)].reshape(
        len(lens), per * block_size, row)
    # as the engine holds it: the row rounded up to whole lane tiles, zeros
    pool = jnp.pad(pool, ((0, 0),) * 3 + ((0, cfg.pool_width - row),))
    before = profiler.compile_stats()
    for spread in (SOFTMAX_SCORE_SPREAD, 0.3):
        # unit-variance rows: a score's deviation is |q| / sqrt(width)
        q = (jax.random.normal(k_q, (len(lens), heads, row), jnp.float32)
             * spread * math.sqrt(width / row)).astype(jnp.bfloat16)
        want = np.asarray(ref.absorbed_attention(q, rows, lens, rank, width))
        for name, fn in (("float32", mla_moe.absorbed_attention),
                         ("xla", xla_form),
                         ("bfloat16", _bfloat16_softmax_attention)):
            got = np.asarray(jax.jit(
                lambda q, pool, tables, lens, fn=fn: fn(
                    q, pool, tables, lens, rank=rank, width=width))(
                        q, pool, tables, lens))
            out[f"softmax_rms_{name}_spread_{spread:g}"] = _rms_error(got,
                                                                      want)
        say(f"mla_moe probes: decode attention over {list(map(int, lens))} live "
            f"rows, scores spread {spread:g}: rms error "
            f"{out[f'softmax_rms_float32_spread_{spread:g}']:.5f} of the "
            f"output's rms (XLA's form "
            f"{out[f'softmax_rms_xla_spread_{spread:g}']:.5f}); with the "
            f"softmax in bfloat16 "
            f"{out[f'softmax_rms_bfloat16_spread_{spread:g}']:.5f}"
            + (f" (limit {SOFTMAX_RMS_TOL})" if spread == SOFTMAX_SCORE_SPREAD
               else " (the seeded weights' spread: no limit)"))
    after = profiler.compile_stats()
    out["softmax_form"] = ("paged_decode" if after["paged_kernel_traces"]
                           > before["paged_kernel_traces"] else "xla")
    say(f"mla_moe probes: the decode attention took the form "
        f"{out['softmax_form']} over a pool {pool.shape[-1]} wide")
    s = f"{SOFTMAX_SCORE_SPREAD:g}"
    check(out[f"softmax_rms_float32_spread_{s}"] <= SOFTMAX_RMS_TOL,
          "the program's decode softmax agrees with a float32 softmax on the "
          "same inputs")
    return out


# The window / full attention comparison (--window-moe-logits; PERF.md
# section 6, PR 31, has the readings these were set from: my chip runs).
# Rows of logits as above.  CLEAN rows read 0.071-0.098 sigma (nine rows, one
# seed) and up to 0.144 (223 rows of a second seed); a mechanism LEFT OUT of
# the reference puts the program's rows 3.09-3.82 sigma (the window ignored on
# the sliding layers) and 4.62-5.33 sigma (the gate left out) from that wrong
# reference: the limit stands between, with room on both sides.  A row whose
# OWN top-10 is a near tie (13% of tokens swap a held expert against the
# float32 reference: another result, not a less precise one) read up to 1.38
# sigma in 33 such rows here and 1.57 in the cell's own rows; its limit, the
# cell's `tie_logit_sigma`, still fails both controls.
WINDOW_MOE_LOGIT_TOL = 0.3
WINDOW_MOE_TIE_TOL = 2.3
# The program's softmax router on the reference's inputs read 0.9999-1.0000, a
# bfloat16 router 0.9391 (top-10 of 256 by softmax has closer ties than
# pangu's top-8 by sigmoid, 0.82 there): 0.97 leaves room on both sides.
WINDOW_ROUTE_AGREEMENT_MIN = 0.97


def _bfloat16_softmax_window(q, kc, vc, ring_tables, lens, window):
    """`ops.paged_attention.paged_window_attention` with the scores rounded
    to bfloat16 and the softmax computed in it: the control."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import paged_attention as pa

    f32 = jnp.float32
    keys = pa.paged_gather(kc, ring_tables).astype(f32)      # [B, Nkv, C, H]
    vals = pa.paged_gather(vc, ring_tables).astype(f32)
    b, t, n, h = q.shape
    span = keys.shape[2]
    qg = q.astype(f32).reshape(b, t, keys.shape[1], -1, h)
    score = jnp.einsum("btkgh,bksh->bkgts", qg, keys)
    score = score.astype(jnp.bfloat16) / jnp.bfloat16(math.sqrt(h))
    last = (lens - 1)[:, None]
    at = last - jnp.mod(last - jnp.arange(span)[None, :], span)   # [B, C]
    seen = (at >= 0) & (at > last - window)
    p = jax.nn.softmax(jnp.where(seen[:, None, None, None, :], score, -1e30),
                       axis=-1)
    out = jnp.einsum("bkgts,bksh->btkgh", p.astype(f32), vals)
    return out.reshape(b, t, n, h).astype(q.dtype)


def window_softmax_probe(*, seed, heads=72, kv_heads=8, head_dim=128,
                         block_size=128, window=512,
                         lens=(300, 512, 2100, 8200)) -> dict:
    """The window read a sliding layer's decode step runs
    (`paged_window_attention`, one token a row) over bfloat16 rings at
    laguna-s-2.1's heads, `lens` positions behind each row (below, at and
    past the window; the ring written position by position as decode writes
    it, so a long row has wrapped it many times), against
    `reference_window_moe.window_attention` (float32, highest precision) on
    the SAME bfloat16 queries and rows in order of position: rms error over
    the output's rms, scores spread as a trained model's; and once more with
    the softmax in bfloat16, the control."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.contract import CacheClass
    from paddle_tpu.ops import paged_attention as pa
    from perfbench import reference_window_moe as ref

    rows = len(lens)
    ring = CacheClass((0,), (), window=window).ring_blocks(block_size)
    span = ring * block_size
    top = max(lens)
    k_k, k_v, k_q = jax.random.split(jax.random.key(seed % 2 ** 31), 3)
    keys = jax.random.normal(k_k, (rows, top, kv_heads, head_dim),
                             jnp.float32).astype(jnp.bfloat16)
    vals = jax.random.normal(k_v, (rows, top, kv_heads, head_dim),
                             jnp.float32).astype(jnp.bfloat16)
    q = (jax.random.normal(k_q, (rows, 1, heads, head_dim), jnp.float32)
         * SOFTMAX_SCORE_SPREAD).astype(jnp.bfloat16)
    draw = np.random.default_rng([seed % 2 ** 63, 31])
    tables = jnp.asarray(draw.permutation(ring * rows).reshape(rows, ring),
                         jnp.int32)
    # what decode leaves in a ring: position t in slot t % span, the latest
    # writer wins; written here span positions at a time, oldest first
    kc = jnp.zeros((ring * rows, kv_heads, block_size, head_dim), jnp.bfloat16)
    vc = jnp.zeros_like(kc)
    for start in range(0, top, span):
        n = min(span, top - start)
        pos = jnp.broadcast_to(jnp.arange(start, start + n, dtype=jnp.int32),
                               (rows, n))
        # a row stops at its own length: rewrite its last position after that
        pos = jnp.minimum(pos, jnp.asarray(lens, jnp.int32)[:, None] - 1)
        take = jnp.take_along_axis
        kc = pa.ring_write_chunk(kc, take(keys, pos[:, :, None, None], 1),
                                 tables, pos)
        vc = pa.ring_write_chunk(vc, take(vals, pos[:, :, None, None], 1),
                                 tables, pos)
    lens_a = jnp.asarray(lens, jnp.int32)
    want = np.asarray(ref.window_attention(q[:, 0], keys, vals, lens_a, window))
    out = {}
    for name, fn in (("float32", pa.paged_window_attention),
                     ("bfloat16", _bfloat16_softmax_window)):
        got = jax.jit(fn, static_argnums=5)(q, kc, vc, tables, lens_a, window)
        out[f"window_softmax_rms_{name}"] = _rms_error(
            got[:, 0].astype(jnp.float32), want)
    read, live = pa.window_positions(tables, block_size, lens_a, window)
    out["positions_read"], out["positions_live"] = int(read), int(live)
    say(f"window probe: decode attention over rings of {ring} x {block_size} "
        f"positions behind rows of {list(lens)} ({heads} / {kv_heads} heads x "
        f"{head_dim}, window {window}, bfloat16 rings, {out['positions_read']}"
        f" positions read for {out['positions_live']} live), scores spread "
        f"{SOFTMAX_SCORE_SPREAD:g}: rms error "
        f"{out['window_softmax_rms_float32']:.5f} of the output's rms; with "
        f"the softmax in bfloat16 {out['window_softmax_rms_bfloat16']:.5f} "
        f"(limit {SOFTMAX_RMS_TOL})")
    check(out["window_softmax_rms_float32"] <= SOFTMAX_RMS_TOL,
          "the window read agrees with a float32 softmax on the same rows")
    return out


def window_moe_logits_phase(cfg_file, *, seed, device, prompt_lens, decoded,
                            block_size, control_prompt=-1) -> dict:
    """The configuration `cfg_file` (a perfbench configuration of the
    `window_moe` family) through `_engine_logits`: the prefill program
    (windowed flash on the sliding layers) and the decode step through the
    pages AND the rings after `decoded` tokens (the rings have wrapped: every
    prompt is longer than a window) against the float32 reference.  Then the
    CONTROLS, which `main` requires to fail: the program's rows of request
    `control_prompt` against the reference with the window ignored on the
    sliding layers, and with the gate left out; the program's router and a
    bfloat16 one on the reference's router inputs; the window read and a
    bfloat16-softmax one on the same rows (`window_softmax_probe`)."""
    import numpy as np

    from perfbench import reference_window_moe as ref
    from perfbench.families import window_moe as fam

    run = _engine_logits(fam, ref, cfg_file, seed=seed,
                         prompt_lens=prompt_lens, decoded=decoded,
                         block_size=block_size, label="window_moe logits",
                         tol=WINDOW_MOE_LOGIT_TOL, tie_tol=WINDOW_MOE_TIE_TOL)
    out = {"errors": run["errors"], "ties": run["ties"]}
    model, weights, sizes = run["model"], run["weights"], run["sizes"]
    ids, at, want, _tie = run["refs"][control_prompt]
    got = run["got"][control_prompt]
    for control in ("ignore_window", "no_gate"):
        t0 = time.perf_counter()
        wrong = np.asarray(ref.logits_at(weights, {**sizes, control: True},
                                         ids, at))
        out[control] = [_row_error(g, w) for g, w in zip(got, wrong)]
        out[control + "_reference_moved"] = [
            _row_error(w, r) for w, r in zip(wrong, want)]
        say(f"window_moe logits: against the reference with {control} "
            f"(prompt {len(ids) - decoded[-1] - 1}) the program's rows stand "
            + ", ".join(f"{e:.4f}" for e in out[control])
            + f" sigma off (limit {WINDOW_MOE_LOGIT_TOL}; the two references "
            "stand " + ", ".join(f"{e:.4f}" for e in out[control + "_reference_moved"])
            + f" apart); {time.perf_counter() - t0:.1f} s")
    prompt = run["refs"][0][0][:len(run["prompts"][0])]
    for name, router in (("route_agreement", None),
                         ("route_agreement_bfloat16", _bfloat16_route)):
        out[name], pairs = fam.routing_agreement(model, weights, sizes, prompt,
                                                 ref, route=router)
    say(f"window_moe probes: on the reference's router inputs the program's "
        f"softmax router chooses the reference's experts for "
        f"{100 * out['route_agreement']:.2f}% of {pairs} (token, expert layer)"
        f" pairs; a bfloat16 router for "
        f"{100 * out['route_agreement_bfloat16']:.2f}% (limit "
        f"{100 * WINDOW_ROUTE_AGREEMENT_MIN:.0f}%)")
    check(out["route_agreement"] >= WINDOW_ROUTE_AGREEMENT_MIN,
          "the program's router agrees with the reference on its own inputs")
    cfg = model.config
    out.update(window_softmax_probe(
        seed=seed, heads=max(cfg.num_attention_heads_per_layer),
        kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        block_size=block_size, window=cfg.sliding_window,
        lens=tuple(sorted({cfg.sliding_window - 1, cfg.sliding_window}
                          | {n + decoded[-1] for n in prompt_lens}))))
    run["rows_within"]()     # after every reading has been said
    _release()
    return out


# ------------------------------- attention in a convolved latent (ZAYA1) ----

# The --cca-moe-logits comparison (PERF.md section 6, PR 34, has the readings
# these were set from).  A row's error is max |program - reference| over the
# vocabulary in the reference row's standard deviations, as above.  The router
# is top-1: a NEAR TIE is a token whose first and second selection scores
# (p + beta, probabilities) lie within CCA_ROUTER_TIE_TAU of each other in
# some layer.  Readings (my chip runs, PR 34, seed 2147484013): clean rows
# 0.0405-0.0581, one near tie that flipped 0.5067 (four that did not
# 0.043-0.055); the rows of the 8,192-token request against the reference with
# the depthwise convolution left out 0.3259-0.5595, the value shift 0.2195-
# 0.5176, the carry 1.4267-1.8632, the skip choice's yield 0.0876-0.3705; a
# bfloat16 router agrees with the reference on 99.54% of 24,576 pairs where
# the program's agrees on 100.00%.
CCA_MOE_LOGIT_TOL = 0.15
CCA_MOE_TIE_TOL = 1.5
CCA_ROUTER_TIE_TAU = 0.02
CCA_ROUTE_AGREEMENT_MIN = 0.998
# the convolutions' tail across a block boundary: rms error of the decode
# form's q, k and v (state carried from the position before) against the
# float32 reference over the whole sequence, relative to their rms (0.0028-
# 0.0030 read; 0.87-0.94 with the tail dropped)
CCA_CONV_TAIL_TOL = 0.03
CCA_CONTROLS = ("no_conv0", "no_shift", "no_carry", "skip_zero")


def _bfloat16_route_mlp(m, r_prev, w, *, eps):
    """`models.cca_moe.route_mlp` with every type lowered: the control."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    t = lambda a: a.astype(bf)  # noqa: E731
    r = jnp.dot(t(m), t(w["down_w"])) + t(w["down_b"]) + t(w["gamma"]) * t(r_prev)
    rf = r.astype(jnp.float32)
    n = (rf * jax.lax.rsqrt(jnp.mean(rf * rf, -1, keepdims=True) + eps)).astype(bf)
    a = jax.nn.gelu(jnp.dot(n * t(w["norm_g"]), t(w["w1"])) + t(w["b1"]),
                    approximate=False)
    a = jax.nn.gelu(jnp.dot(a, t(w["w2"])) + t(w["b2"]), approximate=False)
    p = jax.nn.softmax(jnp.dot(a, t(w["w3"])), axis=-1)
    chosen = jnp.argmax(p + t(w["beta"]), axis=-1).astype(jnp.int32)
    weight = jnp.take_along_axis(p, chosen[:, None], axis=1)
    return chosen[:, None], weight.astype(jnp.float32), r.astype(jnp.float32)


def cca_conv_tail_probe(model, weights, sizes, *, seed, block_size) -> dict:
    """The convolutions' tail across a block boundary, on the SAME inputs:
    layer 0's attention sublayer is handed a seeded normed input of
    block_size + 2 positions; its PREFILL form runs the first block_size - 1
    and leaves the state a slot keeps, its DECODE form then takes positions
    block_size - 1, block_size and block_size + 1 one at a time, each from
    the state the one before left (the second and third lie in the next page
    of the K/V pools; what the convolutions and the value shift need of the
    token before comes from the state, never from a page).  Its q, k and v
    at those three positions against the float32 reference's over the whole
    sequence; and once more with the state zeroed at the boundary (the tail
    dropped), the control."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu._core.tensor import Tensor
    from perfbench import reference_cca_moe as ref

    attn = model.model.layers[0].self_attn
    cfg = model.config
    s = block_size + 2
    draw = np.random.default_rng([seed % 2 ** 63, 29])
    dt = attn.qk_proj.weight._value.dtype
    n = jnp.asarray(draw.standard_normal((1, s, cfg.hidden_size)), dt)
    w = weights["layers"][0]
    want = ref._latent(
        n[0].astype(jnp.float32),
        {k: w[k] for k in ("w_qk", "w_v", "conv0_w", "conv0_b", "conv1_w",
                           "conv1_b", "tau")},
        heads=sizes["heads"], kv_heads=sizes["kv_heads"], d=sizes["head_dim"],
        theta=sizes["theta"], rotary=sizes["rotary"], dt=jnp.float32,
        no_conv0=False, no_conv1=False, no_shift=False)
    p = block_size - 1

    @jax.jit
    def carried(n, drop):
        _o, _k, _v, state = attn.prefill(Tensor(n[:, :p]))
        state = jax.tree_util.tree_map(
            lambda x: jnp.where(drop, jnp.zeros_like(x), x), state)
        z, c, v2 = state
        z, c = z[:, :, 0], c[:, :, 0]      # the one "head" of C channels
        out = []
        for t in range(p, s):
            q, k, v, (z, c, v2) = attn.project(
                Tensor(n[:, t:t + 1]), (z, c, v2),
                jnp.full((1, 1), t, jnp.int32))
            out.append((q[0, 0], k[0, 0], v[0, 0]))
        return out

    out = {}
    for name, drop in (("conv_tail_rms", False), ("conv_tail_rms_dropped", True)):
        got = carried(n, drop)
        out[name] = max(
            _rms_error(g.astype(jnp.float32), x[t])
            for t, step in zip(range(p, s), got) for g, x in zip(step, want))
    say(f"cca probes: the convolutions' tail across the block boundary at "
        f"{block_size}: decode-form q, k, v at positions {p}..{s - 1} stand "
        f"{out['conv_tail_rms']:.5f} of their rms from the float32 reference "
        f"over the whole sequence; with the state zeroed at the boundary "
        f"{out['conv_tail_rms_dropped']:.5f} (limit {CCA_CONV_TAIL_TOL})")
    check(out["conv_tail_rms"] <= CCA_CONV_TAIL_TOL,
          "the decode form carries the convolutions' tail across a block "
          "boundary as the whole-sequence reference has it")
    return out


def cca_moe_logits_phase(cfg_file, *, seed, device, prompt_lens, decoded,
                         block_size, control_prompt=-1) -> dict:
    """The configuration `cfg_file` (a perfbench configuration of the
    `cca_moe` family) through `_engine_logits`: the prefill program and the
    decode step through the pages AND the state a slot after `decoded` tokens
    against the float32 reference.  Then the CONTROLS, which `main` requires
    to fail: the program's rows of request `control_prompt` against the
    reference with the depthwise convolution, the value shift, the carry
    across depth or the skip choice's yield left out; and the probes on the
    same inputs that no end-to-end row can give: the program's router and a
    bfloat16 one on the reference's router inputs, the convolutions' tail
    across a block boundary (`cca_conv_tail_probe`), the decode attention
    over paged pools of this model's geometry (`dense_softmax_probe`)."""
    import numpy as np

    from perfbench import reference_cca_moe as ref
    from perfbench.families import cca_moe as fam

    run = _engine_logits(fam, ref, cfg_file, seed=seed,
                         prompt_lens=prompt_lens, decoded=decoded,
                         block_size=block_size, label="cca_moe logits",
                         tol=CCA_MOE_LOGIT_TOL, tie_tol=CCA_MOE_TIE_TOL,
                         tau=CCA_ROUTER_TIE_TAU)
    out = {"errors": run["errors"], "ties": run["ties"]}
    model, weights, sizes = run["model"], run["weights"], run["sizes"]
    ids, at, want, _tie = run["refs"][control_prompt]
    got = run["got"][control_prompt]
    for control in CCA_CONTROLS:
        t0 = time.perf_counter()
        wrong = np.asarray(ref.logits_at(weights, {**sizes, control: True},
                                         ids, at))
        out[control] = [_row_error(g, w) for g, w in zip(got, wrong)]
        say(f"cca_moe logits: against the reference with {control} (prompt "
            f"{len(ids) - decoded[-1] - 1}) the program's rows stand "
            + ", ".join(f"{e:.4f}" for e in out[control])
            + f" sigma off (limit {CCA_MOE_LOGIT_TOL}; the two references "
            "stand " + ", ".join(f"{_row_error(w, r):.4f}"
                                 for w, r in zip(wrong, want))
            + f" apart); {time.perf_counter() - t0:.1f} s")
    prompt = run["refs"][0][0][:len(run["prompts"][0])]
    for name, router in (("route_agreement", None),
                         ("route_agreement_bfloat16", _bfloat16_route_mlp)):
        out[name], pairs = fam.routing_agreement(model, weights, sizes, prompt,
                                                 ref, route=router)
    say(f"cca probes: on the reference's router inputs the program's MLP "
        f"router makes the reference's choice for "
        f"{100 * out['route_agreement']:.2f}% of {pairs} (token, layer) "
        f"pairs; a bfloat16 router for "
        f"{100 * out['route_agreement_bfloat16']:.2f}% (limit "
        f"{100 * CCA_ROUTE_AGREEMENT_MIN:.1f}%)")
    check(out["route_agreement"] >= CCA_ROUTE_AGREEMENT_MIN,
          "the program's router agrees with the reference on its own inputs")
    out.update(cca_conv_tail_probe(model, weights, sizes, seed=seed,
                                   block_size=block_size))
    cfg = model.config
    out.update(dense_softmax_probe(
        seed=seed, name=f"{cfg_file.get('name', 'cca_moe')} K/V pools",
        heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, block_size=block_size,
        table_width=-(-(max(prompt_lens) + decoded[-1] + 16) // block_size),
        lens=tuple(n + decoded[-1] for n in prompt_lens)))
    run["rows_within"]()     # after every reading has been said
    _release()
    return out



def _bfloat16_softmax_dense(q, kc, vc, tables, lens):
    """`ops.paged_attention.paged_chunk_attention` with the scores rounded
    to bfloat16 and the softmax computed in it, so that the probabilities
    are bfloat16: the control."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import paged_attention as pa

    # bfloat16 VALUES in float32 containers: the products are those of
    # bfloat16 operands on any backend (the CPU's has no bfloat16 dot of
    # every shape), the sums float32
    f32 = jnp.float32
    keys = pa.paged_gather(kc, tables).astype(f32)
    vals = pa.paged_gather(vc, tables).astype(f32)
    b, t, n, h = q.shape
    qg = q.astype(f32).reshape(b, t, keys.shape[1], -1, h)
    score = jnp.einsum("btkgh,bksh->bkgts", qg, keys)
    score = score.astype(jnp.bfloat16) / jnp.bfloat16(math.sqrt(h))
    seen = jnp.arange(keys.shape[2])[None, :] < lens[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None, None, None, :], score, -1e30),
                       axis=-1)
    out = jnp.einsum("bkgts,bksh->btkgh", p.astype(f32), vals)
    return out.reshape(b, t, n, h).astype(q.dtype)


# (name, query heads, K/V heads, head width, block size, table width, live
# positions a row): the two geometries the serving cells hand the dense
# decode attention (PERF.md section 4)
DENSE_PROBE_SHAPES = (
    ("internlm2-1.8b", 16, 8, 128, 16, 96, (130, 300, 512)),
    ("laguna-s-2.1 full layer", 48, 8, 128, 128, 67, (2200, 4900, 8576)),
)


def dense_softmax_probe(*, seed, name="internlm2-1.8b", heads=16, kv_heads=8,
                        head_dim=128, block_size=16, table_width=96,
                        lens=(130, 300, 512)):
    """The dense twin of the latent softmax probe: the decode attention a
    dense model's macro-step runs (`paged_chunk_attention`, one token a row:
    the path it SELECTS here, the Pallas kernel over each row's own pages
    or the XLA form over the ladder's width; the probe says which) over
    bfloat16 K/V pools, `lens` live positions a row behind a table of
    `table_width` pages in a shuffled order, against a softmax computed in
    float64 on the host over the SAME bfloat16 queries and rows: rms error
    over the output's rms, scores spread as a trained model's; beside it
    the XLA form on the same rows; and once more with the softmax in
    bfloat16, the control."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import paged_attention as pa

    rows = len(lens)
    draw = np.random.default_rng([seed % 2 ** 63, 28])
    tables = jnp.asarray(draw.permutation(table_width * rows).reshape(
        rows, table_width), jnp.int32)
    k_k, k_v, k_q = jax.random.split(jax.random.key(seed % 2 ** 31), 3)
    shape = (table_width * rows, kv_heads, block_size, head_dim)
    kc = jax.random.normal(k_k, shape, jnp.float32).astype(jnp.bfloat16)
    vc = jax.random.normal(k_v, shape, jnp.float32).astype(jnp.bfloat16)
    # unit-variance keys: a score's deviation is |q| / sqrt(head_dim)
    q = (jax.random.normal(k_q, (rows, 1, heads, head_dim), jnp.float32)
         * SOFTMAX_SCORE_SPREAD).astype(jnp.bfloat16)
    lens = jnp.asarray(lens, jnp.int32)

    def held(cache):  # [rows, kv_heads, S, head_dim], float64, on the host
        pages = np.asarray(cache.astype(jnp.float32), np.float64)[
            np.asarray(tables)]
        return np.moveaxis(pages, 2, 1).reshape(rows, kv_heads, -1, head_dim)

    keys, vals = held(kc), held(vc)
    qh = np.asarray(q.astype(jnp.float32), np.float64)[:, 0].reshape(
        rows, kv_heads, -1, head_dim)
    want = np.zeros((rows, kv_heads, heads // kv_heads, head_dim))
    for i, n in enumerate(np.asarray(lens)):
        score = np.einsum("kgh,ksh->kgs", qh[i], keys[i, :, :n]) / math.sqrt(
            head_dim)
        p = np.exp(score - score.max(-1, keepdims=True))
        want[i] = np.einsum("kgs,ksh->kgh", p / p.sum(-1, keepdims=True),
                            vals[i, :, :n])
    want = want.reshape(rows, 1, heads, head_dim)
    out = {"path": "kernel" if pa.reads_own_pages(kc) else "xla"}
    for form, fn in (("float32", pa.paged_chunk_attention),
                     ("xla", lambda *a: pa._paged_chunk_xla(*a, None)),
                     ("bfloat16", _bfloat16_softmax_dense)):
        out[f"dense_softmax_rms_{form}"] = _rms_error(
            jax.jit(fn)(q, kc, vc, tables, lens).astype(jnp.float32), want)
    read, live = pa.attn_positions(tables, block_size, lens, pool=kc)
    out["positions_read"], out["positions_live"] = int(read), int(live)
    say(f"dense probe, {name}: decode attention through the "
        f"{'Pallas kernel' if out['path'] == 'kernel' else 'XLA form'} over "
        f"{list(map(int, lens))} live positions of "
        f"{table_width * block_size} ({heads} / {kv_heads} heads x "
        f"{head_dim}, blocks of {block_size}, bfloat16 pools, "
        f"{out['positions_read']} positions read for "
        f"{out['positions_live']} live), scores spread "
        f"{SOFTMAX_SCORE_SPREAD:g}: rms error "
        f"{out['dense_softmax_rms_float32']:.5f} of the output's rms (the "
        f"XLA form on the same rows {out['dense_softmax_rms_xla']:.5f}); "
        f"with the softmax in bfloat16 "
        f"{out['dense_softmax_rms_bfloat16']:.5f} (limit {SOFTMAX_RMS_TOL})")
    check(out["dense_softmax_rms_float32"] <= SOFTMAX_RMS_TOL,
          "the dense decode attention agrees with a float32 softmax on the "
          "same rows")
    check(out["dense_softmax_rms_float32"]
          <= 1.05 * out["dense_softmax_rms_xla"] + 1e-6,
          "the selected path reads no worse than the XLA form beside it")
    return out


# -------------------------------------------------- the flash kernels ----

# (name, positions, heads, kv heads, q/k width, v width, backward too): the
# two shapes the benchmark's cells hand `ops.flash_attention`: the latent
# model's longest prefill bucket (models/mla_moe.py) and the train cell's step
FLASH_PROBE_SHAPES = (
    ("latent prefill", 8192, 128, 128, 192, 128, False),
    ("train step", 4096, 32, 8, 128, 128, True),
)


def _float64_attention(q, k, v, w=None):
    """Causal softmax(q k^T / sqrt(width)) v in float64 on the host for the
    query heads `q` [g, S, d] of ONE key/value head `k` [S, d], `v`
    [S, d_v]; with the output's cotangent `w` [g, S, d_v] also dq, dk, dv."""
    import numpy as np

    scale = 1.0 / math.sqrt(q.shape[-1])
    seen = np.tril(np.ones((q.shape[1], k.shape[0]), bool))
    out, dq = np.zeros(q.shape[:2] + v.shape[1:]), np.zeros(q.shape)
    dk, dv = np.zeros(k.shape), np.zeros(v.shape)
    for g in range(q.shape[0]):
        s = np.where(seen, q[g] @ k.T * scale, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[g] = p @ v
        if w is not None:
            dp = w[g] @ v.T
            ds = p * (dp - (p * dp).sum(-1, keepdims=True)) * scale
            dq[g], dk, dv = ds @ k, dk + ds.T @ q[g], dv + p.T @ w[g]
    return (out, dq, dk, dv) if w is not None else (out,)


def _bfloat16_scores_attention(q, k, v):
    """The control: causal attention ([B, S, N, d], any head grouping) with
    the SCORES rounded to bfloat16 and the softmax computed in it."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    b, s, n, d = q.shape
    qg = q.astype(f32).reshape(b, s, k.shape[2], -1, d)
    score = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(f32))
    score = score.astype(jnp.bfloat16) / jnp.bfloat16(math.sqrt(d))
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((s, s), bool)), score,
                                 -1e30), axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(f32), v.astype(f32))
    return out.reshape(b, s, n, v.shape[-1]).astype(q.dtype)


def flash_softmax_probe(*, seed, shapes=FLASH_PROBE_SHAPES, sampled=2):
    """The prefill twin of the two decode probes: `ops.flash_attention` on
    bfloat16 inputs (the matrix unit gets bfloat16 operands, `p` and `ds`
    rounded once; every sum, maximum and exponential float32), causal, at
    `shapes`, against a float64 softmax on the host over the SAME bfloat16
    inputs for `sampled` key/value heads and every query head of their
    groups: rms error over the result's rms for the output and, where the
    shape is trained on, dq, dk and dv; queries spread as a trained
    model's scores.  Then the control on the same heads: the scores
    rounded to bfloat16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import profiler
    from paddle_tpu.ops.flash_attention import flash_attention

    def loss(fn, w):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()

    def host(x):  # [1, S, n, d] -> [n, S, d] float64
        return np.moveaxis(np.asarray(x.astype(jnp.float32), np.float64)[0],
                           1, 0)

    traced = profiler.compile_stats()
    out = {}
    for at, (name, seq, heads, kv_heads, width, v_width, grads) in enumerate(
            shapes):
        group = heads // kv_heads
        keys = jax.random.split(jax.random.key((seed + at) % 2 ** 31), 4)
        dims = ((heads, width), (kv_heads, width), (kv_heads, v_width),
                (heads, v_width))
        q, k, v, w = (jax.random.normal(r, (1, seq, n, d), jnp.float32
                                        ).astype(jnp.bfloat16)
                      for r, (n, d) in zip(keys, dims))
        q = (q.astype(jnp.float32) * SOFTMAX_SCORE_SPREAD).astype(jnp.bfloat16)
        held = np.random.default_rng([seed % 2 ** 63, 30, at]).choice(
            kv_heads, size=min(sampled, kv_heads), replace=False)
        asked = np.concatenate([np.arange(h * group, (h + 1) * group)
                                for h in held])
        heads_of = (asked, asked, held, held)  # of out and dq; of dk and dv

        def results(fn, q, k, v, w, sampled_already):
            got = [jax.jit(fn)(q, k, v)]
            if grads:
                got += jax.jit(jax.grad(loss(fn, w.astype(jnp.float32)),
                                        (0, 1, 2)))(q, k, v)
            return [host(x if sampled_already else x[:, :, h])
                    for x, h in zip(got, heads_of)]

        names = ("out", "dq", "dk", "dv")[:4 if grads else 1]
        picked = (q[:, :, asked], k[:, :, held], v[:, :, held], w[:, :, asked])
        qs, ks, vs, ws = (host(x) for x in picked)
        want = {n: [] for n in names}
        for i in range(len(held)):
            rows = slice(i * group, (i + 1) * group)
            for n, x in zip(names, _float64_attention(
                    qs[rows], ks[i], vs[i], ws[rows] if grads else None)):
                want[n].append(x if x.ndim == 3 else x[None])
        want = [np.concatenate(want[n]) for n in names]
        tag = name.replace(" ", "_")
        sides = (("flash", results(
                     lambda q, k, v: flash_attention(q, k, v, causal=True),
                     q, k, v, w, False)),
                 ("bfloat16_scores", results(_bfloat16_scores_attention,
                                             *picked, True)))
        for side, got in sides:
            for n, g, t in zip(names, got, want):
                out[f"{side}_rms_{n}.{tag}"] = _rms_error(g, t)
        say(f"flash probe, {name}: causal, {seq} positions, {heads} / "
            f"{kv_heads} heads x {width} (v {v_width}), bfloat16, {len(held)} "
            f"key/value heads against float64, queries spread "
            f"{SOFTMAX_SCORE_SPREAD:g}: rms error over the result's rms "
            + ", ".join(f"{n} {out[f'flash_rms_{n}.{tag}']:.5f}" for n in names)
            + "; with the scores in bfloat16 "
            + ", ".join(f"{n} {out[f'bfloat16_scores_rms_{n}.{tag}']:.5f}"
                        for n in names) + f" (limit {SOFTMAX_RMS_TOL})")
        check(all(out[f"flash_rms_{n}.{tag}"] <= SOFTMAX_RMS_TOL
                  for n in names),
              f"the flash kernels agree with a float64 softmax on the same "
              f"bfloat16 inputs ({name})")
    now = profiler.compile_stats()
    for key in ("flash_bf16_operand_traces", "flash_f32_operand_traces"):
        out[key] = now[key] - traced[key]
    say(f"flash probe: traced with bfloat16 operands "
        f"{out['flash_bf16_operand_traces']} times, with float32 operands "
        f"{out['flash_f32_operand_traces']} times")
    check(out["flash_bf16_operand_traces"] > 0
          == out["flash_f32_operand_traces"],
          "bfloat16 inputs reach the matrix unit as bfloat16 operands")
    return out


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
    ap.add_argument("--mla-moe-logits", action="store_true",
                    help="run ONLY the logit comparison of the latent-"
                         "attention / routed-expert configuration "
                         "(perfbench/configs/openpangu-ultra-moe-718b.json) "
                         "through GenerationEngine against its reference")
    ap.add_argument("--window-moe-logits", action="store_true",
                    help="only the window / full attention configuration "
                         "(perfbench/configs/laguna-s-2.1.json) through "
                         "GenerationEngine, logits against the float32 "
                         "reference, with the controls that must fail")
    ap.add_argument("--cca-moe-logits", action="store_true",
                    help="one chip: perfbench/configs/zaya1-8b.json through "
                         "GenerationEngine, logits (prefill, decode through "
                         "pages and state a slot) against the float32 "
                         "reference; the controls and the probes on the same "
                         "inputs")
    ap.add_argument("--dense-softmax", action="store_true",
                    help="run ONLY the dense decode attention over "
                         "bfloat16 paged pools (internlm2-1.8b's and "
                         "laguna-s-2.1's geometries, the selected path) "
                         "against a float32 softmax on the same rows")
    ap.add_argument("--flash-softmax", action="store_true",
                    help="run ONLY the flash kernels on bfloat16 inputs (the "
                         "latent prefill's forward at 8,192 positions, the "
                         "train step's forward and backward at 4,096) "
                         "against a float64 softmax on the same inputs")
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

    if args.mla_moe_logits:
        import os

        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "perfbench", "configs",
                               "openpangu-ultra-moe-718b.json")) as f:
            cfg_file = json.load(f)
        out = mla_moe_logits_phase(cfg_file, seed=args.seed, device=dev,
                                   prompt_lens=(2048, 4096, 8192),
                                   decoded=(8, 64), block_size=128)
        check(out["softmax_form"] == "paged_decode"
              and out["kernel_traces"] > 0 == out["xla_traces"],
              "on the chip the decode attention, in the probe and in the "
              "engine, is the kernel paged_decode over each row's own pages")
        check(out["route_agreement_bfloat16"] < ROUTE_AGREEMENT_MIN,
              "a bfloat16 router does NOT agree with the reference on its "
              "inputs: the comparison tells it from float32")
        check(out[f"softmax_rms_bfloat16_spread_{SOFTMAX_SCORE_SPREAD:g}"]
              > SOFTMAX_RMS_TOL,
              "a bfloat16 softmax does NOT agree with a float32 softmax on "
              "the same inputs: the comparison tells it from float32")
    elif args.window_moe_logits:
        import os

        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "perfbench", "configs",
                               "laguna-s-2.1.json")) as f:
            cfg_file = json.load(f)
        out = window_moe_logits_phase(cfg_file, seed=args.seed, device=dev,
                                      prompt_lens=(2048, 4096, 8192),
                                      decoded=(8, 64), block_size=128)
        for control in ("ignore_window", "no_gate"):
            check(max(out[control]) > WINDOW_MOE_LOGIT_TOL,
                  f"the reference with {control} does NOT agree with the "
                  "program: the comparison tells the mechanism left out")
        check(out["route_agreement_bfloat16"] < WINDOW_ROUTE_AGREEMENT_MIN,
              "a bfloat16 router does NOT agree with the reference on its "
              "inputs: the comparison tells it from float32")
        check(out["window_softmax_rms_bfloat16"] > SOFTMAX_RMS_TOL,
              "a bfloat16 softmax does NOT agree with a float32 softmax on "
              "the same rows: the comparison tells it from float32")
    elif args.cca_moe_logits:
        import os

        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "perfbench", "configs",
                               "zaya1-8b.json")) as f:
            cfg_file = json.load(f)
        out = cca_moe_logits_phase(cfg_file, seed=args.seed, device=dev,
                                   prompt_lens=(2048, 4096, 8192),
                                   decoded=(8, 64), block_size=128)
        for control in CCA_CONTROLS:
            check(max(out[control]) > CCA_MOE_LOGIT_TOL,
                  f"the reference with {control} does NOT agree with the "
                  "program: the comparison tells the mechanism left out")
        check(out["route_agreement_bfloat16"] < CCA_ROUTE_AGREEMENT_MIN,
              "a bfloat16 router does NOT agree with the reference on its "
              "inputs: the comparison tells it from float32")
        check(out["conv_tail_rms_dropped"] > CCA_CONV_TAIL_TOL,
              "the convolutions with their tail dropped at a block boundary "
              "do NOT agree with the reference")
        check(out["dense_softmax_rms_bfloat16"] > SOFTMAX_RMS_TOL,
              "a bfloat16 softmax does NOT agree with a float32 softmax on "
              "the same rows: the comparison tells it from float32")
    elif args.dense_softmax:
        for (name, heads, kv_heads, head_dim, block_size, table_width,
             lens) in DENSE_PROBE_SHAPES:
            out = dense_softmax_probe(
                seed=args.seed, name=name, heads=heads, kv_heads=kv_heads,
                head_dim=head_dim, block_size=block_size,
                table_width=table_width, lens=lens)
            check(out["dense_softmax_rms_bfloat16"] > SOFTMAX_RMS_TOL,
                  "a bfloat16 softmax does NOT agree with a float32 softmax "
                  "on the same rows: the comparison tells it from float32")
    elif args.flash_softmax:
        out = flash_softmax_probe(seed=args.seed)
        check(all(v > SOFTMAX_RMS_TOL for k, v in out.items()
                  if k.startswith("bfloat16_scores_rms_out.")),
              "scores rounded to bfloat16 do NOT agree with a float64 "
              "softmax on the same inputs: the comparison tells them from "
              "float32")
    elif args.four_chips:
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
