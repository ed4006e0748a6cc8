"""Speculative decode over the TP mesh — the last engine feature to
compose (ROADMAP item 1, docs/DECODE.md sharded-serving section).

Contract: a speculative engine built with ``mesh=`` shards the TARGET
and the DRAFT (weights via shard_llama, each model's pools over its own
KV-head count) and emits token streams bit-identical to the
single-device speculative engine — which itself emits the plain
engine's streams, so the whole chain is anchored to ordinary decode.
Composes with int8 pools (draft pools quantize too) and with adapter
packs (the draft proposes with the BASE model; the target verifies
through each row's adapter, so acceptance only ever keeps tokens the
adapted model would decode).

Multi-device GSPMD dispatches over the in-process XLA:CPU communicator —
this module rides a DEDICATED tools/run_tier1.py isolated worker.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed.auto_parallel import ProcessMesh
from paddle_tpu.nn.lora import apply_lora, lora_state_dict
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


def _draft(seed=77):
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle.seed(seed)
    # a REAL (smaller) draft: 2 KV heads still divide mp=2; mp=4 rides
    # the replicated-draft-pool fallback path (warned, still correct)
    m = LlamaForCausalLM(_cfg(hidden_size=16, intermediate_size=32,
                              num_hidden_layers=1, num_attention_heads=2,
                              num_key_value_heads=2))
    m.eval()
    return m


def _mesh(mp):
    return ProcessMesh(np.arange(mp), ["mp"])


def _drain(eng):
    while eng.has_work():
        eng.step()


def _run(eng):
    eng.add_request("a", [5, 9, 17, 33, 2], max_new_tokens=9)
    eng.step()
    eng.add_request("b", [7, 11, 3], max_new_tokens=6)  # joins mid-flight
    _drain(eng)
    return {"a": eng.result("a"), "b": eng.result("b")}


@pytest.mark.parametrize("kv_dtype,mp", [("bf16", 2), ("int8", 2),
                                         ("bf16", 4)])
def test_spec_engine_mesh_matches_single_device(mp, kv_dtype):
    """Speculative × mesh (× int8): streams bit-identical to the
    single-device speculative engine, including a mid-flight join.  The
    PR-9/10 'not combined with the tensor-parallel mesh engine'
    ValueError is gone."""
    def build(mesh):
        return GenerationEngine(_model(), max_batch=2, block_size=8,
                                num_blocks=32, draft_model=_draft(),
                                num_speculative_tokens=3,
                                kv_cache_dtype=kv_dtype, mesh=mesh)

    ref = _run(build(None))
    if mp == 4:
        # draft nkv=2 does not divide mp=4: the draft pools replicate
        # (warned) while the target pools stay sharded — still bit-exact
        with pytest.warns(UserWarning, match="draft KV pool replicated"):
            eng = build(_mesh(mp))
    else:
        eng = build(_mesh(mp))
        dk = eng._d_pools[0][0]
        assert "mp" in str(getattr(dk, "data", dk).sharding.spec)
    kp = eng._pools[0][0]
    assert "mp" in str(getattr(kp, "data", kp).sharding.spec)
    got = _run(eng)
    assert got == ref
    st = eng.spec_stats()
    assert st["ticks"] >= 1 and st["accepted"] >= 0


def test_spec_mesh_matches_plain_engine():
    """The sharded speculative engine's streams equal the PLAIN
    single-device engine's — acceptance semantics survive the mesh, not
    just the spec-vs-spec comparison."""
    plain = GenerationEngine(_model(), max_batch=2, block_size=8,
                             num_blocks=32)
    ref = _run(plain)
    eng = GenerationEngine(_model(), max_batch=2, block_size=8,
                           num_blocks=32, draft_model=_draft(),
                           num_speculative_tokens=3, mesh=_mesh(2))
    assert _run(eng) == ref


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


def test_spec_adapters_mesh_full_compose():
    """The whole stack at once — speculative × adapters × mesh: a batch
    mixing two tenants and a base row on a 2-device mesh emits EXACTLY
    the single-device plain adapter engine's streams (the base-model
    draft proposes, the sharded adapted target verifies)."""
    base = _model()
    sds = {f"t{i}": _adapter_sd(base, key_seed=10 + i) for i in range(2)}
    reqs = {"a0": ("t0", [5, 9, 17, 33, 2]), "a1": ("t1", [7, 11, 3, 20]),
            "base": (None, [5, 9, 17, 33, 2])}

    def run(draft, mesh):
        eng = GenerationEngine(_model(), max_batch=3, block_size=8,
                               num_blocks=32, draft_model=draft,
                               num_speculative_tokens=3,
                               adapters={"rank": 4, "max_adapters": 2},
                               mesh=mesh)
        for name, sd in sds.items():
            eng.register_adapter(name, sd, alpha=8)
        for rid, (ad, prompt) in reqs.items():
            eng.add_request(rid, prompt, max_new_tokens=6, adapter=ad)
        _drain(eng)
        return {rid: eng.result(rid) for rid in reqs}

    ref = run(None, None)  # plain single-device adapter engine
    assert len({tuple(v) for v in ref.values()}) == 3
    assert run(_draft(), _mesh(2)) == ref


def test_spec_sampled_slots_still_rejected_on_mesh():
    """Speculative slots stay greedy-only on the mesh (sampled acceptance
    needs rejection sampling — unchanged contract)."""
    eng = GenerationEngine(_model(), max_batch=2, block_size=8,
                           num_blocks=32, draft_model=_draft(),
                           mesh=_mesh(2))
    with pytest.raises(ValueError, match="greedy-only"):
        eng.add_request("r", [1, 2, 3], max_new_tokens=4, temperature=0.7)
