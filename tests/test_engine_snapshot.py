"""Live-engine snapshot / bit-exact resume (serving/snapshot.py,
docs/CHECKPOINT.md serving section, ROADMAP item 5).

Contract under test: `EngineSnapshot.save` captures a LIVE
GenerationEngine mid-flight through the CheckpointManager commit
protocol, and `restore_engine` rebuilds a fresh engine whose continued
greedy AND seeded-sampled streams are BIT-identical to an uninterrupted
engine — composed with every serving feature: queued admissions, prefix
cache, int8 pools, LoRA adapter packs, speculative decode, and flag
changes between save and restore.  The subprocess SIGKILL matrix lives in
test_engine_snapshot_crash.py; topology migration (single ↔ TP mesh) in
the isolated test_engine_snapshot_mesh.py worker."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.serving import (EngineSnapshot, GenerationEngine,
                                restore_engine, reset_snapshot_stats,
                                snapshot_stats)

_KW = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=4, max_position_embeddings=64,
           dtype="float32")


def _model(seed=41, **kw):
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(seed)
    base = dict(_KW)
    base.update(kw)
    m = LlamaForCausalLM(llama_tiny(**base))
    m.eval()
    return m


def _drain(eng):
    out = {}
    while eng.has_work():
        for rid, toks in eng.step().items():
            out.setdefault(rid, []).extend(
                toks if isinstance(toks, list) else [toks])
    return out


P1, P2 = [5, 9, 17, 33, 2], [7, 11, 3]


def _submit(eng):
    eng.add_request("g", P1, max_new_tokens=8)
    eng.add_request("s", P2, max_new_tokens=6, temperature=5.0, seed=3)


def _results(eng, rids=("g", "s")):
    return {rid: eng.result(rid) for rid in rids}


def test_mid_flight_snapshot_resumes_bit_identical(tmp_path):
    """Snapshot after one macro-step, restore onto a fresh engine, run to
    completion: greedy and seeded-sampled streams match the uninterrupted
    engine token for token (pools, slots, PRNG keys, fold counters all
    restored exactly)."""
    m = _model()
    ref = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=2)
    _submit(ref)
    _drain(ref)

    eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=2)
    _submit(eng)
    eng.step()
    step = eng.snapshot(str(tmp_path))
    assert EngineSnapshot(str(tmp_path)).latest_step() == step

    reset_snapshot_stats()
    eng2 = restore_engine(m, str(tmp_path))
    _drain(eng2)
    assert _results(eng2) == _results(ref)
    assert snapshot_stats()["restores"] == 1
    # the source engine is untouched by the snapshot: it finishes too
    _drain(eng)
    assert _results(eng) == _results(ref)


def test_pending_queue_and_nonce_counter_survive(tmp_path):
    """A request QUEUED at snapshot time (pool pressure) is admitted by
    the restored engine with its submit-time PRNG nonce intact, and a
    request submitted only AFTER restore draws the stream the
    uninterrupted engine would give it (the nonce counter itself is
    state)."""
    m = _model()

    def run(snapshot_after=None):
        eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=2,
                               decode_chunk=2)
        p = list(range(1, 9))
        eng.add_request("a", p, max_new_tokens=7)
        assert eng.add_request("b", p, max_new_tokens=7,
                               temperature=4.0, seed=1) is None  # queued
        eng.step()
        if snapshot_after is not None:
            eng.snapshot(snapshot_after)
            eng = restore_engine(m, snapshot_after)
            assert eng.pending_requests() == ["b"]
        _drain(eng)
        # a THIRD request after the (possible) restore: distinct nonce
        eng.add_request("c", P2, max_new_tokens=5, temperature=4.0, seed=1)
        _drain(eng)
        return {r: eng.result(r) for r in ("a", "b", "c")}

    ref = run()
    got = run(snapshot_after=str(tmp_path))
    assert got == ref
    assert got["b"] != got["c"]  # same seed, distinct nonces — still true


def test_prefix_cache_tree_survives_restore(tmp_path):
    """Cached prefix pages (tree nodes, refcounts, LRU order) restore: an
    admission AFTER restore hits the pages the pre-snapshot engine
    cached, and the served stream matches an uninterrupted cache-on
    engine."""
    from paddle_tpu.serving import decode_stats, reset_decode_stats

    m = _model()
    shared = list(np.random.default_rng(0).integers(0, 128, 16))

    def run(snapshot_dir=None):
        eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16,
                               decode_chunk=2, prefix_cache=True)
        eng.add_request("w", shared + [3], max_new_tokens=4)
        _drain(eng)  # warms the tree with the shared prefix
        if snapshot_dir is not None:
            eng.snapshot(snapshot_dir)
            eng = restore_engine(m, snapshot_dir)
            assert len(eng._prefix) > 0  # tree really came back
        reset_decode_stats()
        eng.add_request("x", shared + [9, 4], max_new_tokens=5)
        _drain(eng)
        return eng.result("x"), decode_stats()

    ref_toks, ref_st = run()
    got_toks, got_st = run(snapshot_dir=str(tmp_path))
    assert got_toks == ref_toks
    assert got_st["prefix_hits"] == ref_st["prefix_hits"] == 1
    assert got_st["prefix_hit_tokens"] == ref_st["prefix_hit_tokens"] > 0


def test_int8_pools_roundtrip_bit_exact(tmp_path):
    """Int8 engine: quantized payload AND per-block-per-head scales
    restore bit-exactly, so the resumed stream equals the uninterrupted
    int8 engine's (identical arithmetic on identical pool bytes — within
    the PR-6 drift budget by construction, bit-equal in practice)."""
    m = _model()
    ref = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=2, kv_cache_dtype="int8")
    _submit(ref)
    _drain(ref)

    eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=2, kv_cache_dtype="int8")
    _submit(eng)
    eng.step()
    eng.snapshot(str(tmp_path))
    eng2 = restore_engine(m, str(tmp_path))
    assert eng2._kv_dtype == "int8"
    # payload and scales are bit-equal to the source engine's
    np.testing.assert_array_equal(np.asarray(eng2._pools[0][0].data),
                                  np.asarray(eng._pools[0][0].data))
    np.testing.assert_array_equal(np.asarray(eng2._pools[0][0].scale),
                                  np.asarray(eng._pools[0][0].scale))
    _drain(eng2)
    assert _results(eng2) == _results(ref)


def test_adapter_pack_slots_and_epochs_survive(tmp_path):
    """LoRA engine: registry, slot contents, LRU marks and epochs
    restore.  Mixed-tenant streams continue bit-identically, the slot map
    is intact, and a post-restore re-register bumps the restored epoch —
    the stale subtree of the OLD epoch can never cross-match."""
    from tests.test_serving_lora import _adapter_sd

    m = _model()
    sd0, sd1 = _adapter_sd(m, 7), _adapter_sd(m, 13)

    def build():
        eng = GenerationEngine(m, max_batch=3, block_size=8, num_blocks=24,
                               decode_chunk=2, adapters=4,
                               prefix_cache=True)
        eng.register_adapter("t0", sd0)
        eng.register_adapter("t1", sd1)
        eng.add_request("a", P1, max_new_tokens=7, adapter="t0")
        eng.add_request("b", P1, max_new_tokens=7, adapter="t1")
        eng.add_request("c", P2, max_new_tokens=5)
        return eng

    ref = build()
    _drain(ref)

    eng = build()
    eng.step()
    eng.snapshot(str(tmp_path))
    eng2 = restore_engine(m, str(tmp_path))
    assert eng2.adapter_slots() == eng.adapter_slots()
    assert eng2._slot_epochs == eng._slot_epochs
    _drain(eng2)
    assert ({r: eng2.result(r) for r in "abc"}
            == {r: ref.result(r) for r in "abc"})
    # post-restore hot swap: epoch advances past the restored value
    before = list(eng2._slot_epochs)
    slot = eng2.register_adapter("t0", _adapter_sd(m, 99))
    assert eng2._slot_epochs[slot] == before[slot] + 1


def test_speculative_engine_roundtrip(tmp_path):
    """Speculative engine: draft pools, per-slot draft coverage and
    acceptance counters restore; the resumed engine emits exactly the
    uninterrupted speculative engine's tokens."""
    m = _model()
    draft = _model(seed=77, hidden_size=16, intermediate_size=32,
                   num_hidden_layers=1, num_attention_heads=2,
                   num_key_value_heads=2)

    def build():
        eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=32,
                               draft_model=draft, num_speculative_tokens=3)
        eng.add_request("a", P1, max_new_tokens=9)
        eng.add_request("b", P2, max_new_tokens=6)
        return eng

    ref = build()
    _drain(ref)

    eng = build()
    eng.step()
    eng.snapshot(str(tmp_path))
    with pytest.raises(ValueError, match="draft_model"):
        restore_engine(m, str(tmp_path))  # speculative snapshot is loud
    eng2 = restore_engine(m, str(tmp_path), draft_model=draft)
    assert eng2._spec_stats["ticks"] == eng._spec_stats["ticks"]
    _drain(eng2)
    assert ({r: eng2.result(r) for r in "ab"}
            == {r: ref.result(r) for r in "ab"})


def test_restore_under_changed_decode_chunk_flags(tmp_path):
    """A snapshot taken at one FLAGS_decode_chunk restores cleanly when
    the flag differs: compiled steps rebuild for the new D, streams stay
    bit-identical (the engine's every-D contract), and a flag flip AFTER
    restore still invalidates the restored engine's executables through
    the WeakSet listener."""
    m = _model()
    ref = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=1)
    _submit(ref)
    _drain(ref)

    paddle.set_flags({"FLAGS_decode_chunk": 4})
    try:
        eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16)
        _submit(eng)
        eng.step()
        eng.snapshot(str(tmp_path))
        paddle.set_flags({"FLAGS_decode_chunk": 2})
        eng2 = restore_engine(m, str(tmp_path))
        out = eng2.step()
        assert all(len(v) <= 2 for v in out.values())  # new D is live
        # flag flip mid-serving: the restored engine's step fns drop too
        assert eng2._step_fns
        paddle.set_flags({"FLAGS_decode_chunk": 3})
        assert not eng2._step_fns
        _drain(eng2)
    finally:
        paddle.set_flags({"FLAGS_decode_chunk": 8})
    assert _results(eng2) == _results(ref)


def test_drain_closes_admissions_and_hands_off(tmp_path):
    """drain() = final snapshot + admissions closed: the drained engine
    refuses new requests, finishes ONLY its residents (the queued request
    rode the snapshot and is the restore target's to serve — a lame duck
    serving it too would double-serve it), never overwrites the handoff
    snapshot from post-drain boundaries, and the restored engine serves
    resident AND queued requests to the uninterrupted streams."""
    m = _model()
    ref = GenerationEngine(m, max_batch=1, block_size=8, num_blocks=4,
                           decode_chunk=2)
    p = list(range(1, 9))
    ref.add_request("a", p, max_new_tokens=7)
    assert ref.add_request("b", p, max_new_tokens=6) is None  # queued
    _drain(ref)

    # the flag-driven automatic path is live, to prove drain disarms it
    paddle.set_flags({"FLAGS_engine_snapshot_dir": str(tmp_path),
                      "FLAGS_engine_snapshot_interval": 1})
    try:
        eng = GenerationEngine(m, max_batch=1, block_size=8, num_blocks=4,
                               decode_chunk=2)
        eng.add_request("a", p, max_new_tokens=7)
        assert eng.add_request("b", p, max_new_tokens=6) is None
        step = eng.drain(str(tmp_path))
        assert snapshot_stats()["drains"] >= 1
        with pytest.raises(RuntimeError, match="draining"):
            eng.add_request("late", P2, max_new_tokens=3)
        # the drained engine finishes residents ONLY: "b" stays unserved
        # here, and the lame-duck boundaries write no further snapshots
        _drain(eng)
        assert eng.result("a") == ref.result("a")
        assert eng.result("b") is None
        assert not eng.has_work()  # queued "b" is not the lame duck's work
        assert EngineSnapshot(str(tmp_path)).latest_step() == step
    finally:
        paddle.set_flags({"FLAGS_engine_snapshot_dir": "",
                          "FLAGS_engine_snapshot_interval": 0})
    # the handed-off snapshot serves everything, open for business
    eng2 = EngineSnapshot(str(tmp_path)).restore(m, step=step)
    assert eng2.pending_requests() == ["b"]
    _drain(eng2)
    assert eng2.result("a") == ref.result("a")
    assert eng2.result("b") == ref.result("b")
    eng2.add_request("late", P2, max_new_tokens=3)  # restored engine admits
    _drain(eng2)


def test_drain_empty_engine_and_double_drain_idempotent(tmp_path):
    """drain() edge cases the headline test leaves uncovered: an EMPTY
    engine drains cleanly (the snapshot is still a valid handoff — empty
    clusters scale down too), a second drain() returns the SAME committed
    handoff step without writing another snapshot (an orchestrator
    retrying a timed-out drain must not hand the restore target a
    different state per retry), and the drained engine refuses
    add_request with the documented error either way."""
    m = _model()
    eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=2)
    # idle drain: nothing resident, nothing queued
    step = eng.drain(str(tmp_path))
    store = EngineSnapshot(str(tmp_path))
    assert store.latest_step() == step
    committed = store.all_steps()
    # double-drain: same step, no new commit, drains counted once more at
    # most — the handoff state is immutable once taken
    reset_snapshot_stats()
    assert eng.drain(str(tmp_path)) == step
    assert store.all_steps() == committed
    assert snapshot_stats()["saves"] == 0  # idempotent: no re-snapshot
    # ...but only for the SAME directory: a step tag that exists nowhere
    # under the new dir must never be handed to an orchestrator
    with pytest.raises(ValueError, match="already drained"):
        eng.drain(str(tmp_path / "elsewhere"))
    with pytest.raises(RuntimeError, match="draining"):
        eng.add_request("late", P2, max_new_tokens=3)
    assert not eng.has_work()
    assert eng.step() == {}  # lame-duck stepping an empty engine is fine
    # the handoff restores to a fully OPEN empty engine
    eng2 = EngineSnapshot(str(tmp_path)).restore(m, step=step)
    assert eng2.pending_requests() == []
    eng2.add_request("fresh", P1, max_new_tokens=3)
    _drain(eng2)
    assert isinstance(eng2.result("fresh"), list)


def test_sigterm_preemption_snapshots_at_boundary(tmp_path):
    """The SIGTERM mirror of CheckpointManager's flag-flip design: the
    handler only flips a flag; the NEXT macro-step boundary writes the
    final snapshot (never mid-dispatch), preemption_saved goes true, and
    the restored engine finishes every stream bit-identically."""
    import os
    import signal

    m = _model()
    ref = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=2)
    _submit(ref)
    _drain(ref)

    eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=2)
    _submit(eng)
    eng.step()
    paddle.set_flags({"FLAGS_engine_snapshot_dir": str(tmp_path)})
    eng.install_preemption_handler()
    try:
        os.kill(os.getpid(), signal.SIGTERM)  # handler flips the flag only
        assert eng.preemption_requested and not eng.preemption_saved
        assert EngineSnapshot(str(tmp_path)).latest_step() is None
        eng.step()  # boundary: the final snapshot commits HERE
        assert eng.preemption_saved
        st = EngineSnapshot(str(tmp_path)).latest_step()
        assert st is not None
    finally:
        eng.uninstall_preemption_handler()
        paddle.set_flags({"FLAGS_engine_snapshot_dir": ""})
    eng2 = restore_engine(m, str(tmp_path))
    _drain(eng2)
    assert _results(eng2) == _results(ref)


def test_preemption_honored_on_idle_engine(tmp_path):
    """A SIGTERM that lands while the engine has NO work must still
    commit its final snapshot at the next step() call (the idle early
    return is a boundary too) — otherwise the documented
    `while not eng.preemption_saved: eng.step()` exit loop would spin
    until the orchestrator escalates to SIGKILL."""
    import os
    import signal

    m = _model()
    eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=2)
    eng.add_request("g", P1, max_new_tokens=4)
    _drain(eng)  # engine now idle, state worth saving (results, caches)
    paddle.set_flags({"FLAGS_engine_snapshot_dir": str(tmp_path)})
    eng.install_preemption_handler()
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        assert eng.preemption_requested
        assert eng.step() == {}  # idle boundary: final snapshot commits
        assert eng.preemption_saved
    finally:
        eng.uninstall_preemption_handler()
        paddle.set_flags({"FLAGS_engine_snapshot_dir": ""})
    eng2 = restore_engine(m, str(tmp_path))
    assert eng2.result("g") == eng.result("g")


def test_periodic_interval_snapshots(tmp_path):
    """FLAGS_engine_snapshot_interval: step() snapshots every N
    macro-steps into the flag directory, step-tagged by the engine's
    boundary count, with retention keeping the newest valid ones."""
    m = _model()
    eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=1)
    eng.add_request("g", P1, max_new_tokens=8)
    paddle.set_flags({"FLAGS_engine_snapshot_dir": str(tmp_path),
                      "FLAGS_engine_snapshot_interval": 2})
    try:
        for _ in range(5):
            eng.step()
    finally:
        paddle.set_flags({"FLAGS_engine_snapshot_dir": "",
                          "FLAGS_engine_snapshot_interval": 0})
    store = EngineSnapshot(str(tmp_path))
    steps = store.all_steps()
    assert steps and all(s % 2 == 0 for s in steps)
    assert len(steps) <= 2  # default retention


def test_corrupt_snapshot_skipped_and_counted(tmp_path):
    """A snapshot damaged after commit (bit rot / truncation) fails
    checksum verification: latest_step falls back to the older valid one,
    restore serves it, and corrupt_skipped counts the torn dir once."""
    import os

    m = _model()
    eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=2)
    _submit(eng)
    eng.step()
    store = EngineSnapshot(str(tmp_path), max_to_keep=3)
    s1 = store.save(eng)
    eng.step()
    s2 = store.save(eng)
    assert store.latest_step() == s2 > s1
    # truncate the newest snapshot's extras: manifest hash now mismatches
    victim = os.path.join(str(tmp_path), f"step_{s2:08d}", "extras.pkl")
    with open(victim, "r+b") as f:
        f.truncate(16)
    reset_snapshot_stats()
    # a FRESH store (the restart-after-damage shape) re-verifies; the
    # saving store's mtime-keyed cache deliberately trusts what it just
    # hashed, exactly like CheckpointManager's _verify_dir cache
    store = EngineSnapshot(str(tmp_path), max_to_keep=3)
    assert store.latest_step() == s1
    assert snapshot_stats()["corrupt_skipped"] == 1
    # resolving again (any number of fresh instances) never re-counts
    # the same torn dir: the health counter dedup is process-wide
    assert EngineSnapshot(str(tmp_path)).latest_step() == s1
    assert snapshot_stats()["corrupt_skipped"] == 1
    eng2 = restore_engine(m, str(tmp_path))  # lands on the valid s1
    _drain(eng2)
    assert isinstance(eng2.result("g"), list)


def test_geometry_mismatch_is_loud(tmp_path):
    """Restoring onto a DIFFERENT model is refused with the differing
    fields named — poured K/V from other weights can never silently
    serve."""
    m = _model()
    eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16)
    eng.add_request("g", P1, max_new_tokens=4)
    eng.snapshot(str(tmp_path))
    other = _model(hidden_size=64, intermediate_size=128)
    with pytest.raises(ValueError, match="hidden_size"):
        restore_engine(other, str(tmp_path))


def test_snapshot_stats_and_summary_footer(tmp_path, capsys):
    """profiler.snapshot_stats() schema + the 'Engine snapshot:' footer
    in Profiler.summary() (serving-owned counters, decode_stats
    contract)."""
    m = _model()
    eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=2)
    _submit(eng)
    eng.step()
    reset_snapshot_stats()
    eng.snapshot(str(tmp_path))
    restore_engine(m, str(tmp_path))
    st = profiler.snapshot_stats()
    assert st["saves"] == 1 and st["restores"] == 1
    assert st["bytes"] > 0 and st["snapshot_seconds"] > 0
    assert st["corrupt_skipped"] == 0 and st["drains"] == 0

    prof = profiler.Profiler(timer_only=True)
    prof.start()
    prof.stop()
    out = prof.summary()
    capsys.readouterr()
    assert "Engine snapshot: saves=" in out
    assert profiler.snapshot_stats(reset=True)["saves"] == 1
    assert profiler.snapshot_stats()["saves"] == 0
