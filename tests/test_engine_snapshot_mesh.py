"""Engine-snapshot topology migration (serving/snapshot.py): a snapshot
taken on ONE topology restores onto a DIFFERENT one through the
reshard-on-load path — single-device ↔ TP mesh in both directions, bf16
and int8 pools, with the mesh lint validating placements at restore-time
construction.  Streams continue bit-identically vs an uninterrupted
single-device engine (the PR-11 sharded-parity contract extends across
the snapshot boundary).

This module dispatches GSPMD-partitioned decode programs over the
in-process multi-device communicator — the known SIGSEGV class — so it
rides a DEDICATED run_tier1 isolated worker (ISOLATED_DEFAULT), never a
round-robin shard."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.auto_parallel import ProcessMesh
from paddle_tpu.serving import GenerationEngine, restore_engine

_KW = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=4, max_position_embeddings=64,
           dtype="float32")

P1, P2 = [5, 9, 17, 33, 2], [7, 11, 3]


def _model(seed=41):
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(seed)
    m = LlamaForCausalLM(llama_tiny(**_KW))
    m.eval()
    return m


def _drain(eng):
    while eng.has_work():
        eng.step()


def _build(model, mesh=None, **kw):
    eng = GenerationEngine(model, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=2, mesh=mesh, **kw)
    eng.add_request("g", P1, max_new_tokens=8)
    eng.add_request("s", P2, max_new_tokens=6, temperature=5.0, seed=3)
    return eng


def _reference(**kw):
    ref = _build(_model(), **kw)
    _drain(ref)
    return {r: ref.result(r) for r in ("g", "s")}


def test_single_to_mesh_restore_bit_identical(tmp_path):
    """A single-device snapshot restores onto an mp=2 mesh: pool pages
    commit to the KV-head sharding, weights get Megatron placements, the
    mesh lint runs at restore-time construction, and the continued
    greedy + sampled streams equal the uninterrupted single-device
    run."""
    from jax.sharding import NamedSharding

    ref = _reference()
    eng = _build(_model())
    eng.step()
    eng.snapshot(str(tmp_path))

    mesh = ProcessMesh(np.arange(2), ["mp"])
    m2 = _model()  # fresh unsharded weights, same seed
    paddle.set_flags({"FLAGS_verify_sharding": True})
    try:
        eng2 = restore_engine(m2, str(tmp_path), mesh=mesh)
    finally:
        paddle.set_flags({"FLAGS_verify_sharding": False})
    assert isinstance(eng2._pools[0][0].sharding, NamedSharding)
    assert "mp" in str(eng2._pools[0][0].sharding.spec)
    qw = m2.model.layers[0].self_attn.q_proj.weight
    assert "mp" in str(qw._value.sharding.spec)
    _drain(eng2)
    assert {r: eng2.result(r) for r in ("g", "s")} == ref


def test_mesh_to_single_restore_bit_identical(tmp_path):
    """The elastic scale-DOWN direction: an mp=2 engine's snapshot — its
    pool metadata holds per-shard records with global offsets — restores
    onto one device via drain(), the migration primitive, and finishes
    identically."""
    ref = _reference()
    eng = _build(_model(), mesh=ProcessMesh(np.arange(2), ["mp"]))
    eng.step()
    step = eng.drain(str(tmp_path))
    with pytest.raises(RuntimeError, match="draining"):
        eng.add_request("late", P2, max_new_tokens=3)

    eng2 = restore_engine(_model(), str(tmp_path), step=step)
    assert eng2._pools[0][0].sharding is None or len(
        eng2._pools[0][0].sharding.device_set) == 1
    _drain(eng2)
    assert {r: eng2.result(r) for r in ("g", "s")} == ref


def test_mesh_to_wider_mesh_int8_restore(tmp_path):
    """Reshard BETWEEN meshes with quantized pools: an mp=2 int8 engine's
    snapshot restores onto an mp=4 mesh — payload and per-block-per-head
    scales re-place leaf-wise — and the streams still match the
    uninterrupted single-device int8 engine."""
    ref = _reference(kv_cache_dtype="int8")
    eng = _build(_model(), mesh=ProcessMesh(np.arange(2), ["mp"]),
                 kv_cache_dtype="int8")
    eng.step()
    eng.snapshot(str(tmp_path))

    mesh4 = ProcessMesh(np.arange(4), ["mp"])
    eng2 = restore_engine(_model(), str(tmp_path), mesh=mesh4)
    assert eng2._kv_dtype == "int8"
    assert "mp" in str(eng2._pools[0][0].data.sharding.spec)
    _drain(eng2)
    assert {r: eng2.result(r) for r in ("g", "s")} == ref
