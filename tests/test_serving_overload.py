"""Overload discipline in the serving tier: chunked prefill interleaved
with decode (FLAGS_prefill_chunk_blocks), priority/SLO-class admission, and
preemptible LOW-priority requests (FLAGS_preempt_low_priority).

The bit-exactness backbone: a prefill chunk is one pool block, every chunk
keeps its own full-chunk geometry, and the per-block pour computes the
same per-block-per-head scales the batched atomic pour computes — so the chunk boundary is pure data
movement and chunked streams are token-for-token identical to atomic
admission.  Preempted requests park their pool pages host-side verbatim
(pool_get_blocks/pool_set_blocks) and resume bit-identically because the
sampling key is derived from the submit-time nonce, folded per generated
token.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.profiler import decode_stats


def _model(**kw):
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(41)
    cfg = llama_tiny(vocab_size=128, hidden_size=32, intermediate_size=64,
                     num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=4, max_position_embeddings=128,
                     dtype="float32", **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _drain(eng, reqs, **kw):
    for rid, p in reqs:
        eng.add_request(rid, p, **kw)
    while eng.has_work():
        eng.step()
    return {rid: eng.result(rid) for rid, _ in reqs}


# --------------------------------------------- chunked == atomic, matrix
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("prefix", [False, True])
@pytest.mark.parametrize("sampling", ["greedy", "seeded"])
def test_chunked_prefill_bit_identical_to_atomic(kv_dtype, prefix, sampling):
    """Interleaved chunked prefill produces streams token-for-token equal
    to atomic-prefill admission across pool dtypes, prefix-cache modes and
    sampling modes — same submissions, same seeds, same everything."""
    m = _model()
    rng = np.random.default_rng(11)
    reqs = [("a", list(rng.integers(1, 128, 21))),
            ("b", list(rng.integers(1, 128, 9))),
            ("c", list(rng.integers(1, 128, 13)))]
    skw = ({"temperature": 0.8, "seed": 5} if sampling == "seeded" else {})
    ekw = dict(max_batch=2, block_size=8, num_blocks=32, decode_chunk=2)
    if kv_dtype:
        ekw["kv_cache_dtype"] = kv_dtype
    if prefix:
        ekw["prefix_cache"] = True

    ref = _drain(GenerationEngine(m, **ekw), reqs, max_new_tokens=8, **skw)
    stats0 = decode_stats()["prefill_chunks"]
    got = _drain(GenerationEngine(m, prefill_chunk_blocks=1, **ekw),
                 reqs, max_new_tokens=8, **skw)
    assert got == ref
    # the chunked engine actually chunked (21-token prompt = 3+ chunks)
    assert decode_stats()["prefill_chunks"] - stats0 >= 3


def test_chunked_prefill_interleaves_with_decode():
    """A long prompt admitted while short streams decode advances one
    block per macro-step (budget=1 under active decode) instead of
    stalling the decode batch for its whole prefill."""
    m = _model()
    eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=32,
                           decode_chunk=2, prefill_chunk_blocks=1)
    eng.add_request("s", [5, 9, 17], max_new_tokens=12)
    eng.step()  # s resident and decoding
    rng = np.random.default_rng(3)
    eng.add_request("long", list(rng.integers(1, 128, 30)),
                    max_new_tokens=4)
    eng.step()
    # after one macro-step the long request is parked mid-prefill: it has
    # poured pages but no sampled token yet, and the short stream advanced
    assert "long" in eng.prefilling_requests()
    assert eng.result("long") is None
    assert len(eng.result("s")) >= 2
    while eng.has_work():
        eng.step()
    ref = _drain(GenerationEngine(m, max_batch=1, block_size=8,
                                  num_blocks=32, decode_chunk=2),
                 [("long", list(np.random.default_rng(3)
                                .integers(1, 128, 30)))],
                 max_new_tokens=4)
    assert eng.result("long") == ref["long"]


# -------------------------------------- mid-prefill prefix hit on a chunk
def test_mid_prefill_prefix_hit_on_poured_boundary():
    """Blocks poured mid-prefill enter the radix tree immediately: a
    request sharing the long prompt's first pages hits them while the long
    prefill is still in flight — and both streams stay bit-identical to a
    cold engine's."""
    m = _model()
    rng = np.random.default_rng(7)
    head = list(rng.integers(1, 128, 16))          # 2 full blocks
    long_p = head + list(rng.integers(1, 128, 16))  # 4 blocks total
    short_p = head + [3, 44]                        # shares the 2 blocks

    cold = {}
    for rid, p in (("long", long_p), ("short", short_p)):
        cold.update(_drain(GenerationEngine(m, max_batch=1, block_size=8,
                                            num_blocks=32, decode_chunk=2),
                           [(rid, p)], max_new_tokens=6))

    eng = GenerationEngine(m, max_batch=3, block_size=8, num_blocks=32,
                           decode_chunk=2, prefill_chunk_blocks=1,
                           prefix_cache=True)
    # a resident decode row caps the prefill budget at 1 chunk/step so the
    # long prefill is genuinely mid-flight when "short" arrives
    eng.add_request("s", [5, 9], max_new_tokens=16)
    eng.step()
    eng.add_request("long", long_p, max_new_tokens=6)
    eng.step()   # pours long's first chunk -> tree holds 1 block
    eng.step()   # pours the second        -> tree holds `head` entirely
    assert "long" in eng.prefilling_requests()
    before = decode_stats()
    eng.add_request("short", short_p, max_new_tokens=6)
    while eng.has_work():
        eng.step()
    after = decode_stats()
    assert after["prefix_hits"] == before["prefix_hits"] + 1
    assert (after["prefix_hit_tokens"]
            == before["prefix_hit_tokens"] + len(head))
    assert eng.result("long") == cold["long"]
    assert eng.result("short") == cold["short"]


# ------------------------------------------------- preemption bit-parity
def test_preempt_park_readmit_bit_parity():
    """A LOW request parked mid-decode by a HIGH arrival resumes
    bit-identically: the re-admitted stream equals the never-preempted
    reference token for token (seeded sampling — the strictest mode)."""
    m = _model()
    p_low, p_high = [5, 9, 17, 33, 2], [7, 11, 3, 40]

    ref = _drain(GenerationEngine(m, max_batch=1, block_size=8,
                                  num_blocks=32, decode_chunk=2),
                 [("lo", p_low)], max_new_tokens=10, temperature=0.7,
                 seed=3)

    before = decode_stats()
    eng = GenerationEngine(m, max_batch=1, block_size=8, num_blocks=32,
                           decode_chunk=2)
    eng.add_request("lo", p_low, max_new_tokens=10, temperature=0.7,
                    seed=3, priority="low")
    eng.step()
    eng.step()
    mid = list(eng.result("lo"))
    assert 0 < len(mid) < 10  # genuinely mid-decode
    eng.add_request("hi", p_high, max_new_tokens=4, priority="high")
    eng.step()
    assert "lo" in eng.parked_requests()  # evicted, pages host-side
    while eng.has_work():
        eng.step()
    after = decode_stats()
    assert eng.result("lo") == ref["lo"]
    assert after["preemptions"] == before["preemptions"] + 1
    assert after["preempt_readmits"] == before["preempt_readmits"] + 1
    assert after["parked_requests"] == 0


def test_preempt_flag_off_disables_parking():
    """FLAGS_preempt_low_priority=False: a HIGH arrival waits for the slot
    instead of evicting the LOW resident."""
    m = _model()
    paddle.set_flags({"FLAGS_preempt_low_priority": False})
    try:
        before = decode_stats()["preemptions"]
        eng = GenerationEngine(m, max_batch=1, block_size=8, num_blocks=32,
                               decode_chunk=2)
        eng.add_request("lo", [5, 9, 17], max_new_tokens=6, priority="low")
        eng.step()
        eng.add_request("hi", [7, 11, 3], max_new_tokens=4,
                        priority="high")
        eng.step()
        assert eng.parked_requests() == []
        while eng.has_work():
            eng.step()
        assert decode_stats()["preemptions"] == before
        assert len(eng.result("hi")) == 4
    finally:
        paddle.set_flags({"FLAGS_preempt_low_priority": True})


# ----------------------------------------------------- priority ordering
def test_priority_admission_order_under_slot_exhaustion():
    """With the single slot busy, a HIGH submission queued AFTER a LOW one
    is admitted first when the slot frees — (priority, submit-seq) order,
    not FIFO."""
    m = _model()
    paddle.set_flags({"FLAGS_preempt_low_priority": False})
    try:
        eng = GenerationEngine(m, max_batch=1, block_size=8, num_blocks=32,
                               decode_chunk=2)
        eng.add_request("n", [5, 9, 17], max_new_tokens=4)
        eng.step()
        eng.add_request("lo", [7, 11], max_new_tokens=3, priority="low")
        eng.add_request("hi", [3, 40], max_new_tokens=3, priority="high")
        while eng.result("hi") is None:
            eng.step()
        # HIGH entered while LOW is still waiting
        assert eng.result("lo") is None
        while eng.has_work():
            eng.step()
        assert len(eng.result("lo")) == 3
        st = decode_stats()
        assert st["admitted_high"] >= 1 and st["admitted_low"] >= 1
    finally:
        paddle.set_flags({"FLAGS_preempt_low_priority": True})


def test_add_request_rejects_unknown_priority():
    m = _model()
    eng = GenerationEngine(m, max_batch=1, block_size=8, num_blocks=16)
    with pytest.raises(ValueError):
        eng.add_request("x", [1, 2, 3], priority="urgent")


# -------------------------------------------------------- flags plumbing
def test_prefill_chunk_flag_invalidates_and_takes_effect():
    """FLAGS_prefill_chunk_blocks is read dynamically: flipping it clears
    compiled macro-steps (flags listener) and switches an existing engine
    between atomic and interleaved admission — with identical streams."""
    m = _model()
    rng = np.random.default_rng(19)
    reqs = [("a", list(rng.integers(1, 128, 17))),
            ("b", list(rng.integers(1, 128, 6)))]

    ref = _drain(GenerationEngine(m, max_batch=2, block_size=8,
                                  num_blocks=32, decode_chunk=2),
                 reqs, max_new_tokens=6)

    eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=32,
                           decode_chunk=2)
    eng.add_request("warm", [9, 5, 2], max_new_tokens=2)
    while eng.has_work():
        eng.step()  # builds + caches a compiled macro-step
    assert eng._step_fns
    paddle.set_flags({"FLAGS_prefill_chunk_blocks": 1})
    try:
        assert not eng._step_fns  # listener invalidated the cache
        chunks0 = decode_stats()["prefill_chunks"]
        got = _drain(eng, reqs, max_new_tokens=6)
        assert got == ref
        assert decode_stats()["prefill_chunks"] > chunks0
    finally:
        paddle.set_flags({"FLAGS_prefill_chunk_blocks": 0})


def test_ctor_overrides_flag_and_validates():
    m = _model()
    with pytest.raises(ValueError):
        GenerationEngine(m, num_blocks=8, prefill_chunk_blocks=-1)
    # ctor value pins the engine regardless of the global flag
    paddle.set_flags({"FLAGS_prefill_chunk_blocks": 2})
    try:
        eng = GenerationEngine(m, max_batch=1, block_size=8, num_blocks=16,
                               prefill_chunk_blocks=0)
        assert eng._prefill_chunk_blocks() == 0
    finally:
        paddle.set_flags({"FLAGS_prefill_chunk_blocks": 0})


# ------------------------------------------------ snapshot/drain interplay
def test_drain_demotes_prefilling_and_parked(tmp_path):
    """drain() demotes mid-prefill and parked requests back to pending
    submissions so a lame-duck engine hands them off instead of holding
    pool pages."""
    m = _model()
    eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=32,
                           decode_chunk=2, prefill_chunk_blocks=1)
    eng.add_request("s", [5, 9], max_new_tokens=8)
    eng.step()
    rng = np.random.default_rng(23)
    eng.add_request("long", list(rng.integers(1, 128, 30)),
                    max_new_tokens=4)
    eng.step()
    assert "long" in eng.prefilling_requests()
    n = eng.drain(dir=str(tmp_path))
    assert n >= 1
    assert eng.prefilling_requests() == []
    assert any(r["rid"] == "long" for r in eng._pending)
