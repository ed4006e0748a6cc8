"""TP-sharded serving: the WHOLE engine feature set over the mesh
(ROADMAP item 1, docs/DECODE.md sharded-serving section).

The contract under test: an engine built with ``mesh=`` must serve every
feature the single-device engine serves — full-precision AND int8 paged
pools (payload + quant scales sharded leaf-wise on the KV-head dim),
multi-tenant adapter packs (A/B factors on their base projections'
Megatron split), greedy AND seeded-sampling requests — with token
streams BIT-IDENTICAL to the single-device engine, on 2- and 4-device
meshes.  Hot-swapping an adapter on a sharded engine stays
zero-recompile, the mesh lint passes the sharded engine clean, and the
telemetry reports sharding-divided per-device pool bytes.

Every test here dispatches GSPMD-partitioned decode programs over the
in-process multi-device XLA:CPU communicator — the intermittent
SIGSEGV class tools/run_tier1.py contains — so this module rides a
DEDICATED isolated worker (ISOLATED_DEFAULT), never a round-robin shard.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.distributed.auto_parallel import ProcessMesh
from paddle_tpu.nn.lora import apply_lora, lora_state_dict
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.serving import GenerationEngine

_KW = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=4, max_position_embeddings=64,
           dtype="float32")


def _cfg(**kw):
    from paddle_tpu.models.llama import llama_tiny

    base = dict(_KW)
    base.update(kw)
    return llama_tiny(**base)


def _model(seed=41, **kw):
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle.seed(seed)
    m = LlamaForCausalLM(_cfg(**kw))
    m.eval()
    return m


def _mesh(mp):
    return ProcessMesh(np.arange(mp), ["mp"])


def _adapter_sd(base, key_seed, rank=4):
    from paddle_tpu.models.llama import LlamaForCausalLM

    ft = LlamaForCausalLM(_cfg())
    ft.set_state_dict(base.state_dict())
    ft.eval()
    apply_lora(ft, rank=rank, alpha=8)
    key = jax.random.PRNGKey(key_seed)
    for name, p in ft.named_parameters():
        if name.endswith(("lora_A", "lora_B")):
            key, sk = jax.random.split(key)
            scale = 0.2 if name.endswith("lora_B") else 0.05
            p._bind(jax.random.normal(sk, p._value.shape,
                                      jnp.float32) * scale)
    return lora_state_dict(ft)


def _drain(eng):
    while eng.has_work():
        eng.step()


# Greedy + seeded-sampled requests, with a mid-flight join: the workload
# every mesh-vs-single comparison below replays identically (submit order
# fixes the PRNG nonces, so sampled streams are comparable bit-for-bit).
def _run_workload(eng):
    eng.add_request("g", [5, 9, 17, 33, 2], max_new_tokens=8)
    eng.step()
    eng.add_request("s", [7, 11, 3], max_new_tokens=6,
                    temperature=3.0, seed=42)  # joins mid-flight
    _drain(eng)
    return {"g": eng.result("g"), "s": eng.result("s")}


# ------------------------------------------------ plain × {bf16, int8}
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("mp", [2, 4])
def test_plain_engine_mesh_matches_single_device(mp, kv_dtype):
    """Greedy AND seeded-sampling streams bit-identical mesh-vs-single
    for full-precision and int8 pools, on 2- and 4-device meshes."""
    ref = _run_workload(GenerationEngine(
        _model(), max_batch=2, block_size=8, num_blocks=16,
        kv_cache_dtype=kv_dtype))
    eng = GenerationEngine(_model(), max_batch=2, block_size=8,
                           num_blocks=16, kv_cache_dtype=kv_dtype,
                           mesh=_mesh(mp))
    # pools really committed to the KV-head sharding (scales too on int8)
    for _part, arr in pa.pool_parts(eng._pools[0][0]):
        assert "mp" in str(arr.sharding.spec)
    got = _run_workload(eng)
    assert got == ref
    assert len(got["s"]) == 6 and got["s"] != got["g"][:6]


# ------------------------------------------------- adapters × mesh
_AD_PROMPTS = {"a0": [5, 9, 17, 33, 2], "a1": [7, 11, 3, 20],
               "base": [5, 9, 17, 33, 2]}
_AD_OF = {"a0": "t0", "a1": "t1", "base": None}


def _run_adapter_workload(eng, sds):
    for name, sd in sds.items():
        eng.register_adapter(name, sd, alpha=8)
    for rid, prompt in _AD_PROMPTS.items():
        eng.add_request(rid, prompt, max_new_tokens=6, adapter=_AD_OF[rid])
    eng.add_request("samp", [15, 4, 40], max_new_tokens=5,
                    temperature=2.5, seed=9, adapter="t0")
    _drain(eng)
    return {rid: eng.result(rid)
            for rid in list(_AD_PROMPTS) + ["samp"]}


@pytest.mark.parametrize("kv_dtype,mp", [("bf16", 2), ("bf16", 4),
                                         ("int8", 2)])
def test_adapter_engine_mesh_matches_single_device(mp, kv_dtype):
    """Mixed-adapter batches (two tenants + a base row + a sampled
    adapter row) decode in ONE sharded dispatch, bit-identical to the
    single-device adapter engine — the PR-10 adapters×mesh
    NotImplementedError is gone; int8×adapters×mesh composes too."""
    base = _model()
    sds = {f"t{i}": _adapter_sd(base, key_seed=10 + i) for i in range(2)}

    def build(mesh):
        return GenerationEngine(_model(), max_batch=4, block_size=8,
                                num_blocks=32, kv_cache_dtype=kv_dtype,
                                adapters={"rank": 4, "max_adapters": 2},
                                mesh=mesh)

    ref = _run_adapter_workload(build(None), sds)
    assert len({tuple(v) for v in ref.values()}) >= 3  # tenants differ
    eng = build(_mesh(mp))
    # pack factors ride the base projections' Megatron split: col targets
    # shard B's out dim, row targets shard A's in dim
    a_q, b_q = eng._pack.ab["self_attn.q_proj"]
    a_o, b_o = eng._pack.ab["self_attn.o_proj"]
    assert "mp" in str(b_q.sharding.spec) and "mp" not in str(
        a_q.sharding.spec)
    assert "mp" in str(a_o.sharding.spec) and "mp" not in str(
        b_o.sharding.spec)
    got = _run_adapter_workload(eng, sds)
    assert got == ref


def test_sharded_hot_swap_zero_recompiles():
    """Acceptance gate: adapter hot-swap on a SHARDED engine performs
    zero XLA recompiles after a warm swap cycle — set_slot's scatter
    re-commits every pack array to its recorded placement, so the swap
    executables and the decode step keep one argument-sharding
    signature across swaps (nn.AdapterPack._replace)."""
    model = _model()
    sd_a = _adapter_sd(model, key_seed=40)
    sd_b = _adapter_sd(model, key_seed=41)
    sd_w = _adapter_sd(model, key_seed=42)
    prompt = list(range(1, 25))

    eng = GenerationEngine(model, max_batch=2, block_size=8, num_blocks=32,
                           adapters={"rank": 4, "max_adapters": 1},
                           prefix_cache=True, mesh=_mesh(2))
    # warm cycle: swap machinery scatters + the eager dispatch cache's
    # prefill hotness ramp both settle before the measured window
    for name, sd in (("a", sd_a), ("w", sd_w)):
        eng.register_adapter(name, sd, alpha=8)
        eng.add_request(f"r_{name}", prompt, max_new_tokens=4, adapter=name)
        _drain(eng)

    c0 = profiler.compile_stats()["compiles"]
    eng.register_adapter("b", sd_b, alpha=8)  # evicts idle 'w': a swap
    eng.add_request("rb", prompt, max_new_tokens=4, adapter="b")
    _drain(eng)
    assert profiler.compile_stats()["compiles"] - c0 == 0
    assert eng.result("rb")  # the swapped tenant actually served
    # the pack stayed committed to its placements through the swap
    a_o, _b_o = eng._pack.ab["self_attn.o_proj"]
    assert "mp" in str(a_o.sharding.spec)


# ----------------------------------------- lint + telemetry satellites
def test_sharded_engine_lints_clean_and_reports_per_device():
    """A full-feature sharded engine (int8 + adapters, mp=2) constructs
    clean under FLAGS_verify_sharding, its HBM estimate divides the pool
    AND scale groups by the mesh, and decode_stats/summary report the
    per-device bytes + mesh shape."""
    from paddle_tpu.static.mesh_lint import lint_engine
    from paddle_tpu.serving import decode_stats

    prev = {"FLAGS_verify_sharding":
            paddle.get_flags("FLAGS_verify_sharding")["FLAGS_verify_sharding"]}
    paddle.set_flags({"FLAGS_verify_sharding": True})
    try:
        eng = GenerationEngine(_model(), max_batch=2, block_size=8,
                               num_blocks=16, kv_cache_dtype="int8",
                               adapters={"rank": 4, "max_adapters": 2},
                               mesh=_mesh(2))
        violations, est = lint_engine(eng)
        assert violations == []
        single = GenerationEngine(_model(), max_batch=2, block_size=8,
                                  num_blocks=16, kv_cache_dtype="int8",
                                  adapters={"rank": 4, "max_adapters": 2})
        _ok, est1 = lint_engine(single)
        # per-device pool/scale bytes are the single-device bytes / mp
        assert est["kv_pools"] * 2 == est1["kv_pools"]
        assert est["kv_scales"] * 2 == est1["kv_scales"]
    finally:
        paddle.set_flags(prev)

    # the LAST engine built was the single-device twin; rebuild sharded
    eng = GenerationEngine(_model(), max_batch=2, block_size=8,
                           num_blocks=16, mesh=_mesh(2))
    st = decode_stats()
    assert st["mesh_shape"] == "mp2"
    assert st["pool_bytes_per_device"] * 2 == st["pool_bytes"]
    eng.add_request("r", [5, 9, 17], max_new_tokens=3)
    _drain(eng)
    prof = profiler.Profiler(timer_only=True)
    with prof:
        pass
    out = prof.summary()
    assert "Sharded serving: mesh=mp2" in out
    assert "pool_bytes/device=%d" % st["pool_bytes_per_device"] in out


def test_sharded_engine_replicated_pools_match_single_device():
    """A TP engine whose head counts the mp axis does not divide (4 query
    heads, 1 KV head, mp = 2) keeps its pools replicated (the
    constructor's fallback) and decodes the single-device engine's
    streams."""
    kw = dict(num_attention_heads=4, num_key_value_heads=1)
    ref = _run_workload(GenerationEngine(
        _model(**kw), max_batch=2, block_size=8, num_blocks=16))
    eng = GenerationEngine(_model(**kw), max_batch=2, block_size=8,
                           num_blocks=16, mesh=_mesh(2))
    for _part, arr in pa.pool_parts(eng._pools[0][0]):
        assert "mp" not in str(arr.sharding.spec)
    assert _run_workload(eng) == ref
