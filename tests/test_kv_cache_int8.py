"""Int8 paged-KV quantization (FLAGS_kv_cache_dtype='int8'): QuantPool
op-level accuracy, the serving-engine parity gate (greedy streams match
bf16 pools on short contexts, bounded logit drift on long ones), capacity
arithmetic, and composition with the prefix cache (docs/DECODE.md)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.serving import GenerationEngine


def _model(seed=11, **kw):
    paddle.seed(seed)
    cfg = llama_tiny(vocab_size=128, hidden_size=32, intermediate_size=64,
                     num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=4, max_position_embeddings=256,
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


# ------------------------------------------------------------ op-level tier
def test_quant_pool_alloc_and_bytes():
    k8, v8 = pa.alloc_paged_cache(8, 2, 16, 4, dtype="int8")
    kb, vb = pa.alloc_paged_cache(8, 2, 16, 4, dtype=jnp.bfloat16)
    assert isinstance(k8, pa.QuantPool) and isinstance(v8, pa.QuantPool)
    assert k8.data.dtype == jnp.int8 and k8.scale.shape == (8, 2)
    assert pa.pool_num_kv_heads(k8) == pa.pool_num_kv_heads(kb) == 2
    # payload halves vs bf16; tiny f32 scale sidecar rides along
    assert k8.data.nbytes * 2 == kb.nbytes
    assert pa.pool_nbytes(k8) == k8.data.nbytes + k8.scale.nbytes


def test_quant_write_gather_roundtrip_accuracy():
    """paged_write_chunk into an int8 pool then paged_gather recovers the
    stored values to int8 precision (per-block-per-head scales)."""
    rng = np.random.default_rng(0)
    kc, _ = pa.alloc_paged_cache(4, 2, 8, 4, dtype="int8")
    new = jnp.asarray(rng.normal(size=(1, 16, 2, 4)).astype(np.float32))
    tables = jnp.asarray([[0, 2, 3]], jnp.int32)
    positions = jnp.arange(16, dtype=jnp.int32)[None]
    kc = pa.paged_write_chunk(kc, new, tables, positions)
    got = pa.paged_gather(kc, tables)[0, :, :16]           # [Nkv, 16, H]
    want = jnp.moveaxis(new[0], 1, 0)                      # [Nkv, 16, H]
    # quantization step is amax/127 per (block, head): ~1% of the range
    amax = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= amax / 127.0 + 1e-6


def test_quant_running_max_rescales_resident_payload():
    """A decode write whose amax exceeds the block's scale grows the scale
    and RESCALES the resident payload — earlier tokens stay decodable."""
    kc, _ = pa.alloc_paged_cache(2, 1, 4, 2, dtype="int8")
    tables = jnp.asarray([[0]], jnp.int32)
    small = jnp.full((1, 1, 1, 2), 0.5, jnp.float32)
    big = jnp.full((1, 1, 1, 2), 8.0, jnp.float32)
    kc = pa.paged_write_chunk(kc, small, tables, jnp.asarray([[0]]))
    s0 = float(kc.scale[0, 0])
    kc = pa.paged_write_chunk(kc, big, tables, jnp.asarray([[1]]))
    assert float(kc.scale[0, 0]) > s0
    view = pa.paged_gather(kc, tables)[0, 0]               # [4, 2]
    np.testing.assert_allclose(np.asarray(view[0]), 0.5, atol=8.0 / 127 + 1e-6)
    np.testing.assert_allclose(np.asarray(view[1]), 8.0, atol=8.0 / 127 + 1e-6)


def test_quant_pour_blocks_resets_stale_scales():
    """paged_pour_blocks SETS fresh scales (prefill into recycled blocks):
    a block that once held huge values quantizes new small ones finely."""
    kc, _ = pa.alloc_paged_cache(2, 1, 4, 2, dtype="int8")
    tables = jnp.asarray([[0]], jnp.int32)
    kc = pa.paged_pour_blocks(kc, jnp.full((1, 1, 4, 2), 100.0), [0])
    kc = pa.paged_pour_blocks(kc, jnp.full((1, 1, 4, 2), 0.25), [0])
    assert float(kc.scale[0, 0]) == pytest.approx(0.25 / 127.0)
    view = pa.paged_gather(kc, tables)[0, 0]
    np.testing.assert_allclose(np.asarray(view), 0.25, rtol=0.02)


def test_quant_chunk_attention_matches_exact_reference():
    """paged_chunk_attention over an int8 pool tracks the full-precision
    pool's output within quantization tolerance at a LONG context."""
    rng = np.random.default_rng(1)
    b, t, n, h, bs, blocks_per_seq = 1, 2, 2, 8, 8, 16    # S = 128
    q = jnp.asarray(rng.normal(size=(b, t, n, h)).astype(np.float32))
    kf, vf = pa.alloc_paged_cache(blocks_per_seq, n, bs, h, jnp.float32)
    kq, vq = pa.alloc_paged_cache(blocks_per_seq, n, bs, h, "int8")
    tables = jnp.arange(blocks_per_seq, dtype=jnp.int32)[None]
    kv = rng.normal(size=(blocks_per_seq, n, bs, h)).astype(np.float32)
    vv = rng.normal(size=(blocks_per_seq, n, bs, h)).astype(np.float32)
    kf, vf = pa.paged_pour_blocks(kf, jnp.asarray(kv), range(blocks_per_seq)), \
        pa.paged_pour_blocks(vf, jnp.asarray(vv), range(blocks_per_seq))
    kq, vq = pa.paged_pour_blocks(kq, jnp.asarray(kv), range(blocks_per_seq)), \
        pa.paged_pour_blocks(vq, jnp.asarray(vv), range(blocks_per_seq))
    lens = jnp.asarray([blocks_per_seq * bs], jnp.int32)
    ref = pa.paged_chunk_attention(q, kf, vf, tables, lens)
    got = pa.paged_chunk_attention(q, kq, vq, tables, lens)
    # attention output is a convex combination of V rows: int8 V error is
    # ~amax/127 per element and the K error only perturbs the weights
    assert float(jnp.max(jnp.abs(got - ref))) < 0.15
    assert float(jnp.mean(jnp.abs(got - ref))) < 0.03


# ------------------------------------------------------- engine parity tier
def test_int8_engine_greedy_matches_bf16_short_contexts():
    """The parity gate: greedy token streams from int8 pools equal the
    full-precision pools' streams on short contexts — chunked decode and
    speculative tiers included."""
    m = _model()
    rng = np.random.default_rng(3)
    reqs = [("a", list(rng.integers(0, 128, 12))),
            ("b", list(rng.integers(0, 128, 7)))]

    for kw in ({}, {"decode_chunk": 4}):
        ref = _drain(GenerationEngine(m, max_batch=2, block_size=8,
                                      num_blocks=32, **kw),
                     reqs, max_new_tokens=8)
        got = _drain(GenerationEngine(m, max_batch=2, block_size=8,
                                      num_blocks=32, kv_cache_dtype="int8",
                                      **kw),
                     reqs, max_new_tokens=8)
        assert got == ref, f"engine kwargs {kw}"


def _first_decode_logits(eng):
    """Logits of slot 0's first decode forward over the RESIDENT pool —
    the same computation _build_step's scan body runs, minus sampling;
    the engine's state is left untouched (functional pool updates are
    discarded)."""
    from paddle_tpu._core.autograd import no_grad
    from paddle_tpu._core.tensor import Tensor
    from paddle_tpu.models.llama import _decode_layers_paged

    s = eng._slots[0]
    W = eng._max_blocks_per_seq
    row = list(s.blocks) + [s.blocks[-1]] * (W - len(s.blocks))
    tables = jnp.asarray([row], jnp.int32)
    lens = jnp.asarray([s.seq_len + 1], jnp.int32)
    tok = jnp.asarray([[s.last_token]], jnp.int32)
    model = eng.model
    with no_grad():
        h = model.model.embed_tokens(Tensor(tok))
        cos = model.model.rope_cos._value
        sin = model.model.rope_sin._value
        h, _, _ = _decode_layers_paged(
            model.model.layers, h, cos, sin,
            list(eng._pools[0]), list(eng._pools[1]), tables, lens)
        h = model.model.norm(h)
        return np.asarray(model._logits(h)._value[0, -1, :], np.float32)


def test_int8_engine_bounded_logit_drift_long_context():
    """Long contexts need not stay bit-identical — the gate is BOUNDED
    drift: the first decode forward's logits over a 150-token resident
    int8 pool stay close to the full-precision pool's logits, and the
    first generated token (produced by the exact, unquantized prefill
    forward) matches exactly."""
    m = _model(seed=12)
    prompt = list(np.random.default_rng(4).integers(0, 128, 150))

    def admit(**kw):
        eng = GenerationEngine(m, max_batch=1, block_size=8, num_blocks=64,
                               **kw)
        eng.add_request("r", prompt, max_new_tokens=4)
        assert eng._slots[0].active
        return eng

    ref_eng = admit()
    q_eng = admit(kv_cache_dtype="int8")
    # first token rides the prefill logits — exact on both paths
    assert q_eng._slots[0].last_token == ref_eng._slots[0].last_token
    ref = _first_decode_logits(ref_eng)
    got = _first_decode_logits(q_eng)
    spread = float(ref.max() - ref.min())
    drift = np.abs(got - ref)
    assert float(drift.max()) < 0.10 * spread
    assert float(drift.mean()) < 0.02 * spread


def test_int8_composes_with_prefix_cache():
    """A quantized pool caches quantized prefix pages.  On this SHORT
    shared prefix the composed streams equal int8 cache-off bit for bit;
    the general contract is only bounded drift — with the cache on, the
    suffix prefill attends DEQUANTIZED prefix K/V where a full re-prefill
    attends exact activations (docs/DECODE.md caveat), so long prefixes
    may diverge within the int8 drift budget."""
    m = _model()
    shared = list(np.random.default_rng(5).integers(0, 128, 16))
    reqs = [("a", shared + [3, 7]), ("b", shared + [9])]
    ref = _drain(GenerationEngine(m, max_batch=2, block_size=8,
                                  num_blocks=32, kv_cache_dtype="int8"),
                 reqs, max_new_tokens=6)
    got = _drain(GenerationEngine(m, max_batch=2, block_size=8,
                                  num_blocks=32, kv_cache_dtype="int8",
                                  prefix_cache=True),
                 reqs, max_new_tokens=6)
    assert got == ref


def test_int8_speculative_greedy_matches_full_precision():
    """Spec verify writes its whole K+1 chunk (including later-REJECTED
    draft tokens) through the running-max quant path before acceptance
    rolls lens back — a rejected outlier can grow a block's scale for
    good.  The gate: greedy spec streams still match the full-precision
    spec engine on short contexts."""
    target = _model(seed=41)
    paddle.seed(42)
    dcfg = llama_tiny(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=256,
                      dtype="float32")
    draft = LlamaForCausalLM(dcfg)
    draft.eval()
    rng = np.random.default_rng(8)
    reqs = [("a", list(rng.integers(0, 128, 12))),
            ("b", list(rng.integers(0, 128, 7)))]
    ref = _drain(GenerationEngine(target, max_batch=2, block_size=8,
                                  num_blocks=32, draft_model=draft),
                 reqs, max_new_tokens=8)
    got = _drain(GenerationEngine(target, max_batch=2, block_size=8,
                                  num_blocks=32, draft_model=draft,
                                  kv_cache_dtype="int8"),
                 reqs, max_new_tokens=8)
    assert got == ref


def test_int8_capacity_at_fixed_bytes():
    """The capacity claim, allocator-arithmetic form: at identical
    pool-block bytes an int8 pool admits >= 1.8x the resident requests of
    a bf16 pool (satellite twin of the bench_decode workload)."""
    paddle.seed(2)
    cfg = llama_tiny(vocab_size=128, hidden_size=64, intermediate_size=128,
                     num_attention_heads=4, num_key_value_heads=4,
                     max_position_embeddings=4096, dtype="bfloat16")
    m = LlamaForCausalLM(cfg)
    m.eval()
    nkv = cfg.num_key_value_heads
    hd = cfg.hidden_size // cfg.num_attention_heads
    elems = nkv * 16 * hd
    per_block_bf16 = cfg.num_hidden_layers * 2 * elems * 2
    per_block_int8 = cfg.num_hidden_layers * 2 * (elems + nkv * 4)
    nb_bf16 = 10
    nb_int8 = (nb_bf16 * per_block_bf16) // per_block_int8

    def admitted(kv_dtype, nb):
        eng = GenerationEngine(m, max_batch=nb, block_size=16, num_blocks=nb,
                               kv_cache_dtype=kv_dtype)
        rng = np.random.default_rng(3)
        count = 0
        while True:
            p = list(rng.integers(0, 128, 28))  # 2 blocks each (+4 new)
            if eng.add_request(f"c{count}", p, max_new_tokens=4) is None:
                return count
            count += 1

    res_bf16 = admitted("bf16", nb_bf16)
    res_int8 = admitted("int8", int(nb_int8))
    assert res_int8 / res_bf16 >= 1.8


def test_int8_rejects_mesh_and_bad_dtype():
    m = _model()
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        GenerationEngine(m, num_blocks=8, kv_cache_dtype="fp8")


def test_int8_plus_mesh_constructs_sharded():
    """The PR-6/PR-9 NotImplementedError is GONE: int8 pools compose with
    the TP mesh engine — QuantPool payload AND its per-block-per-head
    scales both come back committed to the KV-head sharding (the same
    PartitionSpec covers the rank-4 payload and the rank-2 scales), and
    the per-device telemetry reports the sharding-divided bytes.  Stream
    parity mesh-vs-single-device lives in tests/test_serving_mesh.py
    (isolated worker — this module rides a round-robin shard, so no
    multi-device decode dispatch happens here)."""
    from paddle_tpu.distributed import ProcessMesh
    from paddle_tpu.serving import decode_stats

    m = _model(seed=13)
    mesh = ProcessMesh(np.arange(2).reshape(2), ["mp"])
    eng = GenerationEngine(m, num_blocks=8, kv_cache_dtype="int8",
                           mesh=mesh)
    kp = eng._pools[0][0]
    assert isinstance(kp, pa.QuantPool)
    assert "mp" in str(kp.data.sharding.spec)
    assert "mp" in str(kp.scale.sharding.spec)
    st = decode_stats()
    assert st["mesh_shape"] == "mp2"
    assert st["pool_bytes_per_device"] * 2 == st["pool_bytes"]
    # and each knob alone still works
    GenerationEngine(m, num_blocks=8, kv_cache_dtype="int8")
    GenerationEngine(_model(seed=13), num_blocks=8, kv_cache_dtype="bf16",
                     mesh=mesh)
