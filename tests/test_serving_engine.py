"""Continuous-batching generation engine (paddle_tpu/serving).

Reference lineage: block_multi_head_attention_kernel.cu + the
continuous-batching servers over it — requests share one KV block pool via
block tables, joining/leaving the decode batch between steps.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import GenerationEngine


def _model():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(41)
    cfg = llama_tiny(vocab_size=128, hidden_size=32, intermediate_size=64,
                     num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=4, max_position_embeddings=64,
                     dtype="float32")
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _ref_generate(model, prompt, n):
    out = model.generate(paddle.to_tensor(np.asarray(prompt, np.int32)[None]),
                         max_new_tokens=n, cache="paged", block_size=8)
    return np.asarray(out._value).reshape(-1).tolist()


def test_single_request_matches_generate():
    model = _model()
    prompt = [5, 9, 17, 33, 2]
    ref = _ref_generate(model, prompt, 8)
    eng = GenerationEngine(model, max_batch=2, block_size=8, num_blocks=16)
    eng.add_request("r", prompt, max_new_tokens=8)
    while eng.has_work():
        eng.step()
    assert eng.result("r") == ref


def test_continuous_batching_requests_join_mid_flight():
    """Two requests with different prompt lengths; the second is admitted
    after the first has already decoded two tokens — both must match their
    standalone generations exactly."""
    model = _model()
    p1, p2 = [5, 9, 17, 33, 2], [7, 11, 3]
    ref1 = _ref_generate(model, p1, 8)
    ref2 = _ref_generate(model, p2, 6)

    # decode_chunk=2: at the flag default (8) request 'a' would finish
    # inside the first macro-step and 'b' would decode alone — the
    # co-resident mid-flight join this test exists for needs short chunks
    eng = GenerationEngine(model, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=2)
    eng.add_request("a", p1, max_new_tokens=8)
    eng.step()
    eng.step()
    eng.add_request("b", p2, max_new_tokens=6)  # joins mid-flight
    while eng.has_work():
        eng.step()
    assert eng.result("a") == ref1
    assert eng.result("b") == ref2


def test_block_recycling_and_slot_reuse():
    """A completed request's pool pages return to the free list and a new
    request decodes correctly on the recycled pages."""
    model = _model()
    eng = GenerationEngine(model, max_batch=1, block_size=8, num_blocks=4)
    free0 = len(eng._free)
    p = [4, 8, 15]
    ref = _ref_generate(model, p, 5)
    eng.add_request("one", p, max_new_tokens=5)
    while eng.has_work():
        eng.step()
    assert eng.result("one") == ref
    assert len(eng._free) == free0  # pages recycled

    ref2 = _ref_generate(model, [16, 23], 5)
    eng.add_request("two", [16, 23], max_new_tokens=5)
    while eng.has_work():
        eng.step()
    assert eng.result("two") == ref2


def test_pool_exhaustion_queues_for_retry():
    """A request the pool can't hold RIGHT NOW is queued (add_request ->
    None) and admitted at a later macro-step boundary once blocks drain —
    with the same tokens an immediately-admitted run produces.  Requests
    that can NEVER fit (wider than the per-seq table) still raise."""
    model = _model()
    p = list(range(1, 9))
    ref = _ref_generate(model, p, 7)
    eng = GenerationEngine(model, max_batch=2, block_size=8, num_blocks=2)
    assert eng.add_request("a", p, max_new_tokens=7) is not None  # 2 blocks
    assert eng.add_request("b", p, max_new_tokens=7) is None      # queued
    assert eng.pending_requests() == ["b"]
    while eng.has_work():
        eng.step()
    assert eng.result("a") == ref
    assert eng.result("b") == ref  # retried request decodes identically

    with pytest.raises(RuntimeError, match="table width"):
        eng.add_request("w", list(range(40)), max_new_tokens=40)


def test_eos_stops_early():
    model = _model()
    # discover the greedy second token, then declare it the EOS id
    probe = GenerationEngine(model, max_batch=1, block_size=8, num_blocks=8)
    probe.add_request("p", [5, 9], max_new_tokens=4)
    while probe.has_work():
        probe.step()
    toks = probe.result("p")
    eos = toks[1]
    eng = GenerationEngine(model, max_batch=1, block_size=8, num_blocks=8,
                           eos_token_id=eos)
    eng.add_request("e", [5, 9], max_new_tokens=10)
    while eng.has_work():
        eng.step()
    got = eng.result("e")
    assert got[-1] == eos and len(got) <= len(toks)


# ------------------------------------------------------------- TP serving
# (VERDICT r3 #6: an mp>1 model must be servable; reference capability is
# analysis_predictor's multi-device serving path)


def test_mp_sharded_engine_matches_single_device():
    """Continuous-batching decode of an mp=2 model on the 8-device CPU mesh
    produces the same tokens as the single-device engine: weights carry
    Megatron placements, the paged-KV pool is sharded over KV heads, ONE
    compiled decode program serves the mesh."""
    import jax
    from jax.sharding import NamedSharding
    from paddle_tpu.distributed.auto_parallel import ProcessMesh

    p1, p2 = [5, 9, 17, 33, 2], [7, 11, 3]
    ref_model = _model()
    ref1 = _ref_generate(ref_model, p1, 8)
    ref2 = _ref_generate(ref_model, p2, 6)

    model = _model()  # same seed -> same weights
    mesh = ProcessMesh(np.arange(8).reshape(4, 2), ["dp", "mp"])
    eng = GenerationEngine(model, max_batch=2, block_size=8, num_blocks=16,
                           mesh=mesh, mp_axis="mp")
    # weights really carry mp placements
    qw = model.model.layers[0].self_attn.q_proj.weight
    assert isinstance(qw._value.sharding, NamedSharding)
    assert "mp" in str(qw._value.sharding.spec)
    # pool pages sharded over the KV-head dim
    assert "mp" in str(eng._pools[0][0].sharding.spec)

    eng.add_request("a", p1, max_new_tokens=8)
    eng.step()
    eng.add_request("b", p2, max_new_tokens=6)  # joins mid-flight
    while eng.has_work():
        eng.step()
    assert eng.result("a") == ref1
    assert eng.result("b") == ref2


def test_mp_predictor_runs_partitioned():
    """Predictor with Config.enable_tensor_parallel serves the exported
    program over the mesh with identical outputs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec
    import paddle_tpu.nn as nn
    import paddle_tpu.static as static
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.static.program import Program, program_guard

    paddle.seed(7)
    fc1, fc2 = nn.Linear(16, 64), nn.Linear(64, 8)
    prog = Program()
    with program_guard(prog):
        xv = prog.add_feed(prog.new_var(
            jax.ShapeDtypeStruct((4, 16), np.float32), "x"))
        import paddle_tpu.nn.functional as Fn
        out = paddle.tanh(fc2(Fn.relu(fc1(xv))))
    import tempfile, os

    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "m")
        exe = static.Executor()
        static.save_inference_model(prefix, [xv], [out], exe, program=prog)

        x = np.random.default_rng(0).normal(size=(4, 16)).astype(np.float32)
        ref = create_predictor(Config(prefix)).run([x])[0]

        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("mp",))
        cfg = Config(prefix)
        cfg.enable_tensor_parallel(mesh, input_specs=[PartitionSpec()])
        got = create_predictor(cfg).run([x])[0]
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_save_time_pass_and_precision_control():
    """Export-time optimization surface (reference AnalysisConfig
    pass_builder + precision mode): named passes + precision run over a
    clone before export; the manifest records them; numerics shift by at
    most low-precision rounding; the source program is untouched."""
    import tempfile, os
    import jax
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as Fn
    import paddle_tpu.static as static
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.static.program import Program, program_guard

    paddle.seed(3)
    fc1, fc2 = nn.Linear(16, 32), nn.Linear(32, 4)
    prog = Program()
    with program_guard(prog):
        xv = prog.add_feed(prog.new_var(
            jax.ShapeDtypeStruct((4, 16), np.float32), "x"))
        out = paddle.tanh(fc2(Fn.relu(fc1(xv))))
    types_before = [op.type for op in prog.global_block().ops]

    x = np.random.default_rng(0).normal(size=(4, 16)).astype(np.float32)
    with tempfile.TemporaryDirectory() as td:
        exe = static.Executor()
        p32 = os.path.join(td, "fp32")
        static.save_inference_model(p32, [xv], [out], exe, program=prog)
        ref = create_predictor(Config(p32)).run([x])[0]

        p16 = os.path.join(td, "bf16")
        static.save_inference_model(
            p16, [xv], [out], exe, program=prog,
            passes=["dead_code_elimination"], precision="bfloat16")
        import json as _json

        manifest = _json.load(open(p16 + ".json"))
        assert manifest["passes"] == ["dead_code_elimination",
                                      "auto_parallel_fp16:bfloat16"]
        got = create_predictor(Config(p16)).run([x])[0]
        np.testing.assert_allclose(got, ref, rtol=3e-2, atol=3e-2)
        assert not np.allclose(got, ref, rtol=1e-7, atol=1e-9)  # really bf16

    # the SOURCE program was cloned, not mutated
    assert [op.type for op in prog.global_block().ops] == types_before

    # invalid precision is loud
    import pytest as _pytest

    with _pytest.raises(ValueError, match="precision"):
        static.save_inference_model("/tmp/x", [xv], [out], program=prog,
                                    precision="int3")


def test_per_request_sampling_in_shared_program():
    """Greedy and temperature-sampled requests decode TOGETHER in the one
    compiled program: the greedy slot still matches standalone generate,
    the sampled slot is deterministic per (seed, join order)."""
    model = _model()
    p1, p2 = [5, 9, 17, 33, 2], [7, 11, 3]
    ref1 = _ref_generate(model, p1, 8)

    def run():
        eng = GenerationEngine(model, max_batch=2, block_size=8, num_blocks=16)
        eng.add_request("greedy", p1, max_new_tokens=8)
        eng.add_request("hot", p2, max_new_tokens=6, temperature=5.0, seed=42)
        while eng.has_work():
            eng.step()
        return eng.result("greedy"), eng.result("hot")

    g1, h1 = run()
    g2, h2 = run()
    assert g1 == ref1 == g2          # greedy unaffected by the hot neighbor
    assert h1 == h2                  # deterministic per seed + join order
    assert all(0 <= t < 128 for t in h1)
    ref2 = _ref_generate(model, p2, 6)
    assert h1 != ref2                # hot sampling really deviates from greedy

    # same seed, two sampled requests: DISTINCT streams (per-request nonce)
    eng = GenerationEngine(model, max_batch=2, block_size=8, num_blocks=16)
    eng.add_request("a", p2, max_new_tokens=6, temperature=5.0, seed=1)
    eng.add_request("b", p2, max_new_tokens=6, temperature=5.0, seed=1)
    while eng.has_work():
        eng.step()
    assert eng.result("a") != eng.result("b")


def test_chunked_prefill_engine_matches_unchunked():
    """prefill_chunk processes long prompts in fixed-size chunks through
    the shared cached forward; decode output is identical to whole-prompt
    prefill (the bottom-right cross-length attention path)."""
    prompt = list(np.random.default_rng(11).integers(0, 128, 23))
    ref_eng = GenerationEngine(_model(), max_batch=2, block_size=8,
                               num_blocks=32)
    ref_eng.add_request("r", prompt, max_new_tokens=7)
    while ref_eng.has_work():
        ref_eng.step()
    chunked = GenerationEngine(_model(), max_batch=2, block_size=8,
                               num_blocks=32, prefill_chunk=5)
    chunked.add_request("r", prompt, max_new_tokens=7)
    while chunked.has_work():
        chunked.step()
    assert chunked.result("r") == ref_eng.result("r")


def test_speculative_engine_matches_plain_engine():
    """Continuous-batching speculative decoding: per-slot greedy
    acceptance over the shared paged pool produces EXACTLY the plain
    engine's tokens — including a request that joins mid-flight."""
    p1, p2 = [5, 9, 17, 33, 2], [7, 11, 3]
    ref = GenerationEngine(_model(), max_batch=2, block_size=8, num_blocks=32)
    ref.add_request("a", p1, max_new_tokens=9)
    ref.step()
    ref.add_request("b", p2, max_new_tokens=6)
    while ref.has_work():
        ref.step()

    paddle.seed(77)
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    draft = LlamaForCausalLM(llama_tiny(
        vocab_size=128, hidden_size=16, intermediate_size=32,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=64, dtype="float32"))
    draft.eval()
    eng = GenerationEngine(_model(), max_batch=2, block_size=8,
                           num_blocks=32, draft_model=draft,
                           num_speculative_tokens=3)
    eng.add_request("a", p1, max_new_tokens=9)
    eng.step()
    eng.add_request("b", p2, max_new_tokens=6)
    while eng.has_work():
        eng.step()
    assert eng.result("a") == ref.result("a")
    assert eng.result("b") == ref.result("b")


def test_speculative_engine_self_draft_accepts_everything():
    """Draft == target: all proposals accepted, output identical, and the
    whole request completes in ~N/(K+1) verify steps."""
    prompt = [5, 9, 17, 33, 2]
    ref = GenerationEngine(_model(), max_batch=2, block_size=8, num_blocks=32)
    ref.add_request("r", prompt, max_new_tokens=12)
    while ref.has_work():
        ref.step()
    target = _model()
    eng = GenerationEngine(target, max_batch=2, block_size=8, num_blocks=32,
                           draft_model=target, num_speculative_tokens=3)
    eng.add_request("r", prompt, max_new_tokens=12)
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
    assert eng.result("r") == ref.result("r")
    assert steps <= -(-11 // 4) + 1, steps  # 11 post-prefill tokens, K+1=4


def test_speculative_engine_rejects_sampled_slots():
    target = _model()
    eng = GenerationEngine(target, max_batch=2, block_size=8, num_blocks=32,
                           draft_model=target)
    with pytest.raises(ValueError, match="greedy-only"):
        eng.add_request("r", [1, 2, 3], max_new_tokens=4, temperature=0.7)


def test_speculative_engine_zero_slack_blocks_no_corruption():
    """Verify overshoot near max_len must land in OWNED headroom pages,
    never through the table-padding column into trusted K/V: prompt 5 +
    max_new 11 = exactly 2 blocks of 8 with zero slack (the corruption
    geometry), K=3."""
    prompt = [5, 9, 17, 33, 2]
    ref = GenerationEngine(_model(), max_batch=2, block_size=8, num_blocks=32)
    ref.add_request("r", prompt, max_new_tokens=11)
    while ref.has_work():
        ref.step()
    target = _model()
    eng = GenerationEngine(target, max_batch=2, block_size=8, num_blocks=32,
                           draft_model=target, num_speculative_tokens=3)
    eng.add_request("r", prompt, max_new_tokens=11)
    while eng.has_work():
        eng.step()
    assert eng.result("r") == ref.result("r")


def test_spec_stats_observability():
    target = _model()
    eng = GenerationEngine(target, max_batch=2, block_size=8, num_blocks=32,
                           draft_model=target, num_speculative_tokens=3)
    eng.add_request("r", [5, 9, 17], max_new_tokens=9)
    while eng.has_work():
        eng.step()
    st = eng.spec_stats()
    assert st["ticks"] >= 1 and st["emitted"] >= 8
    assert st["accepted"] == st["proposed"]  # self-draft accepts all
    plain = GenerationEngine(_model(), max_batch=1, block_size=8,
                             num_blocks=16)
    assert plain.spec_stats() is None
