"""Schedule search, phase 2: the decode hot chain (ops/decode_chain.py +
serving adoption; docs/SCHEDULE_SEARCH.md).

The contract under test: the serving macro-step's per-token chain — paged
gather → dequant → sdpa core → running-max quant-write — is a searchable
subgraph.  Candidates must pass a numerics PARITY gate vs the unfused XLA
twin before they may even be measured (bf16 bit-exact, int8 pools
bit-exact + attention inside the PR-6 drift budget); accepted verdicts
persist per device kind under schedule/decode_* and serve cold reloads
with ZERO re-measurement; an engine whose verdict is accepted emits token
streams BIT-IDENTICAL to the unfused engine; mixed-dtype QuantPool
chains are costed per-leaf by the roofline (int8 payload bytes + f32
scale bytes, never one dtype for the whole subgraph).  Measurement is
injected through schedule_search.measure_override so every decision here
is deterministic on CPU; the real measure path belongs to the bench on a
chip.
"""

import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops import autotune as at
from paddle_tpu.ops import decode_chain as dc
from paddle_tpu.static import schedule_search as ss
from paddle_tpu import serving


@pytest.fixture()
def tmp_cache(tmp_path):
    """Fresh autotune cache under a tmp dir + zeroed search counters."""
    paddle.set_flags({"FLAGS_autotune_cache_dir": str(tmp_path)})
    at._CACHES.clear()
    ss.reset_schedule_search_stats()
    serving.reset_schedule_decode_stats()
    yield tmp_path
    paddle.set_flags({"FLAGS_autotune_cache_dir": ""})
    at._CACHES.clear()
    ss.reset_schedule_search_stats()
    serving.reset_schedule_decode_stats()


def _spec(kv="bf16", **kw):
    base = dict(batch=2, num_heads=4, num_kv_heads=2, head_dim=8,
                block_size=4, max_blocks=2, num_blocks=8, kv=kv,
                dtype=np.float32)
    base.update(kw)
    return dc.DecodeChainSpec(**base)


def _win(fn, args, *, label, config):
    return 0.4 if config is not None else 1.0


def _lose(fn, args, *, label, config):
    return 4.0 if config is not None else 1.0


# ------------------------------------------------------------ spec tier


def test_candidate_space_by_kv_kind():
    """bf16 chains enumerate the bit-exact 'batch' layout only; int8
    chains add the tolerance-gated 'rows' layout; loop-gather unrolls
    divide the table width."""
    bf16 = _spec("bf16").enumerate_configs()
    assert {c["layout"] for c in bf16} == {"batch"}
    int8 = _spec("int8").enumerate_configs()
    assert {c["layout"] for c in int8} == {"batch", "rows"}
    for c in bf16 + int8:
        if c["gather"] == "loop":
            assert 2 % c["unroll"] == 0  # max_blocks == 2
    # rows layout never builds for bf16 — the einsum re-association
    # would break the bit-exact contract
    with pytest.raises(ValueError):
        _spec("bf16").build({"layout": "rows", "gather": "take"})


def test_mixed_dtype_roofline_bytes_hand_computed():
    """The satellite fix: QuantPool chains cost int8 payload bytes AND
    f32 scale bytes per leaf.  Hand-computed for B=2 N=4 Nkv=2 H=8 bs=4
    W=2 NB=8 f32 compute dtype:

      int8 pools:  payload 8*2*4*8*1 = 512 B, scales 8*2*4 = 64 B
                   reads  = 2*(512+64)        = 1152
                   writes = 2*(2*2*4*8 + 2*2*4) = 288  (touched blocks
                            rewritten by the running-max rescale + scales)
      f32 pools:   payload 8*2*4*8*4 = 2048 B -> reads 4096
                   writes = 2*(2*2*8*4) = 256  (one token slot per row)
      both:        q 256 + k_new/v_new 256 + tables 16 + lens 8 + out 256
    """
    fixed = 256 + 256 + 16 + 8 + 256
    cfg = {"layout": "batch", "gather": "take"}
    assert _spec("int8").traffic_bytes(cfg) == 1152 + 288 + fixed
    assert _spec("bf16").traffic_bytes(cfg) == 4096 + 256 + fixed
    # the 'rows' layout re-stages the pool leaves once per batch row
    rows_cfg = {"layout": "rows", "gather": "take"}
    assert (_spec("int8").traffic_bytes(rows_cfg)
            == 2 * 1152 + 288 + fixed)
    # per-leaf honesty is what makes the int8 gather traffic ~a quarter
    # of the f32 twin's instead of "one dtype for the whole subgraph"
    assert _spec("int8").traffic_bytes(cfg) < _spec("bf16").traffic_bytes(cfg)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_all_candidates_parity_vs_unfused_twin(kv):
    """Every candidate passes the parity gate: pools bit-exact for both
    kinds, attention bit-exact for bf16 (whole-batch replay of the exact
    unfused ops) and drift-bounded for int8's per-row layout."""
    spec = _spec(kv)
    args = spec.synthetic_args()
    ref = jax.jit(spec.reference())(*args)
    for cfg in spec.enumerate_configs():
        fn = jax.jit(spec.build(cfg))
        assert spec.parity_ok(fn, args, ref), cfg
        if kv == "bf16":
            # the batch layout's contract is BIT-exactness, leaf for leaf
            got = fn(*args)
            for r, g in zip(jax.tree_util.tree_leaves(ref),
                            jax.tree_util.tree_leaves(got)):
                assert bool((r == g).all()), cfg


def test_parity_gate_blocks_wrong_candidates(tmp_cache):
    """A candidate whose numerics differ must never be measured, however
    fast: the gate rejects it before the stopwatch starts."""
    spec = _spec("bf16")

    class LyingSpec(dc.DecodeChainSpec):
        def build(self, config):
            inner = dc.DecodeChainSpec.build(self, config)

            def wrong(*args):
                o, kc, vc = inner(*args)
                return o + 1e-3, kc, vc  # fast and wrong

            return wrong

    lying = LyingSpec(**spec.__dict__)
    calls = []

    def counting(fn, args, *, label, config):
        if config is not None:
            calls.append(config)
        return 0.1

    with ss.measure_override(counting):
        decision = ss.ScheduleSearcher(budget=3).search(lying)
    assert calls == []  # nothing measured
    assert not decision.accepted
    assert ss.schedule_search_stats()["pruned_parity"] > 0


def test_search_persists_and_cold_reload_never_remeasures(tmp_cache):
    """Accepted AND disabled decode verdicts persist under the
    schedule/decode_* namespaces; a cold reload serves both with zero
    re-measurement (the accepted config still parity-re-gates — a cache
    file is trusted about speed, never numerics)."""
    with ss.measure_override(_win):
        d1 = dc.ensure_decision(_spec("bf16"))
    with ss.measure_override(_lose):
        d2 = dc.ensure_decision(_spec("int8"))
    assert d1.status == "accepted" and d1.win > 1.0
    assert d2.status == "disabled"
    raw = json.load(open(os.path.join(
        str(tmp_cache), at.device_kind_slug() + ".json")))
    (entry,) = raw["schedule/decode_bf16"].values()
    assert entry["meta"]["win"] > 1.0
    assert entry["config"]["layout"] == "batch"
    (dentry,) = raw["schedule/decode_int8"].values()
    assert dentry["config"] == {"disabled": True}

    at._CACHES.clear()
    calls = []

    def counting(fn, args, *, label, config):
        calls.append(config)
        return 1.0

    with ss.measure_override(counting):
        d3 = dc.ensure_decision(_spec("bf16"))
        d4 = dc.ensure_decision(_spec("int8"))
    assert calls == []
    assert d3.status == "cache" and d3.config == entry["config"]
    assert d4.status == "cache_disabled"
    assert ss.schedule_search_stats()["disabled_hits"] >= 1


def test_chunk_paths_refuse_chain_cfg():
    """The fused chain covers the single-token step only: the chunked /
    speculative-verify path must refuse a config loudly, never silently
    ignore it."""
    from paddle_tpu.models.llama import _decode_layers_paged

    with pytest.raises(ValueError, match="single-token"):
        _decode_layers_paged(None, None, None, None, [], [], None, None,
                             chunk=True,
                             chain_cfg={"layout": "batch",
                                        "gather": "take"})


# ------------------------------------------------------------ engine tier


def _model(seed=41):
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(seed)
    m = LlamaForCausalLM(llama_tiny(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, dtype="float32"))
    m.eval()
    return m


def _workload(eng):
    """Greedy + mid-flight seeded-sampling join — the stream shape every
    fused-vs-unfused comparison replays identically."""
    eng.add_request("g", [5, 9, 17, 33, 2], max_new_tokens=8)
    eng.step()
    eng.add_request("s", [7, 11, 3], max_new_tokens=6, temperature=3.0,
                    seed=42)
    while eng.has_work():
        eng.step()
    return {"g": eng.result("g"), "s": eng.result("s")}


def _engine(kv="bf16"):
    from paddle_tpu.serving import GenerationEngine

    return GenerationEngine(_model(), max_batch=2, block_size=8,
                            num_blocks=16, kv_cache_dtype=kv)


@pytest.fixture()
def sched_flags(tmp_cache):
    yield tmp_cache
    paddle.set_flags({"FLAGS_schedule_search": False})


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_engine_fused_streams_match_unfused(sched_flags, kv):
    """The acceptance crux: a macro-step that adopted an accepted fused
    decode-chain config emits token streams BIT-IDENTICAL to the unfused
    engine — greedy and seeded sampling, bf16 and int8 pools (the int8
    winner is the bit-exact batch layout; even its drift budget goes
    unspent)."""
    ref = _workload(_engine(kv))
    paddle.set_flags({"FLAGS_schedule_search": True})
    with ss.measure_override(_win):
        eng = _engine(kv)
        got = _workload(eng)
    assert got == ref
    stats = serving.schedule_decode_stats()
    assert stats["decode_chains_found"] == 1
    assert stats["decode_chains_accepted"] == 1
    assert stats["decode_chains_mesh_skipped"] == 0
    # the verdict persisted under this engine's geometry
    raw = json.load(open(os.path.join(
        str(sched_flags), at.device_kind_slug() + ".json")))
    assert f"schedule/decode_{kv}" in raw


def test_engine_disabled_verdict_keeps_unfused_path(sched_flags):
    """A measured loss keeps the unfused ops and counts as disabled —
    streams unchanged, nothing faked."""
    ref = _workload(_engine())
    paddle.set_flags({"FLAGS_schedule_search": True})
    with ss.measure_override(_lose):
        got = _workload(_engine())
    assert got == ref
    stats = serving.schedule_decode_stats()
    assert stats["decode_chains_found"] == 1
    assert stats["decode_chains_accepted"] == 0
    assert stats["decode_chains_disabled"] == 1


def test_engine_cold_reload_serves_with_zero_remeasures(sched_flags):
    """The satellite proof: after one engine's accepted verdict persists,
    a cold process (fresh cache objects, fresh engine) serves the fused
    step with ZERO measure calls — and the streams still match."""
    ref = _workload(_engine())
    paddle.set_flags({"FLAGS_schedule_search": True})
    with ss.measure_override(_win):
        _workload(_engine())
    # "new process": drop the in-memory cache objects and counters
    at._CACHES.clear()
    serving.reset_schedule_decode_stats()
    ss.reset_schedule_search_stats()
    calls = []

    def counting(fn, args, *, label, config):
        calls.append(config)
        return 1.0

    with ss.measure_override(counting):
        got = _workload(_engine())
    assert calls == []
    assert got == ref
    stats = serving.schedule_decode_stats()
    assert stats["decode_chains_accepted"] == 1
    assert ss.schedule_search_stats()["cache_hits"] >= 1


def test_flag_change_rearms_engine_verdict(sched_flags):
    """set_flags invalidates the compiled steps AND the decode-chain
    verdict together: flipping the search off mid-life re-resolves to the
    unfused path at the next step."""
    from paddle_tpu.serving import GenerationEngine

    paddle.set_flags({"FLAGS_schedule_search": True})
    with ss.measure_override(_win):
        eng = GenerationEngine(_model(), max_batch=2, block_size=8,
                               num_blocks=16, decode_chunk=2)
        eng.add_request("a", [5, 9, 17], max_new_tokens=6)
        eng.step()
        assert eng._decode_chain_cfg is not None  # adopted
        paddle.set_flags({"FLAGS_schedule_search": False})
        assert eng._decode_chain_cfg is serving._CHAIN_UNSET
        while eng.has_work():
            eng.step()
        assert eng._decode_chain_cfg is None  # re-resolved: unfused
    assert len(eng.result("a")) == 6


def test_engine_prefill_chain_adopted_streams_match(sched_flags):
    """Long-prompt pours stop being a pure XLA chain: an engine with a
    fixed prefill_chunk searches the fused prefill-attention candidate at
    the canonical chunk geometry, and an adoption runs every divisible
    chunk's attention core as one Pallas dispatch — with the poured
    stream BIT-IDENTICAL to the search-off engine.  A measured loss
    keeps the XLA pour and counts as disabled, streams unchanged."""
    from paddle_tpu.serving import GenerationEngine

    def run():
        eng = GenerationEngine(_model(), max_batch=2, block_size=8,
                               num_blocks=16, prefill_chunk=4)
        eng.add_request("p", list(range(1, 21)), max_new_tokens=6)
        while eng.has_work():
            eng.step()
        return eng.result("p")

    ref = run()
    paddle.set_flags({"FLAGS_schedule_search": True})
    with ss.measure_override(_win):
        got = run()
    assert got == ref
    stats = serving.schedule_decode_stats()
    assert stats["prefill_chains_found"] == 1
    assert stats["prefill_chains_accepted"] == 1
    # the measured-loss twin: honest disable, same stream
    serving.reset_schedule_decode_stats()
    at._CACHES.clear()
    paddle.set_flags({"FLAGS_autotune_cache_dir":
                      str(sched_flags / "lose")})
    with ss.measure_override(_lose):
        got2 = run()
    assert got2 == ref
    stats = serving.schedule_decode_stats()
    assert stats["prefill_chains_disabled"] == 1
    assert stats["prefill_chains_accepted"] == 0


def test_profiler_merges_decode_counters_and_footer(sched_flags):
    paddle.set_flags({"FLAGS_schedule_search": True})
    with ss.measure_override(_win):
        _workload(_engine())
    from paddle_tpu import profiler

    stats = profiler.schedule_search_stats()
    assert stats["decode_chains_found"] == 1
    assert stats["decode_chains_accepted"] == 1
    assert stats["subgraphs_found"] >= 1  # search-tier keys still merged
    p = profiler.Profiler(timer_only=True)
    p.start()
    p.stop()
    text = p.summary()
    assert "Schedule search:" in text
    assert "Decode chains: found=1 accepted=1" in text
