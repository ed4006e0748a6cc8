"""Disaggregated serving cluster (serving/cluster.py + serving/router.py,
docs/SERVING_CLUSTER.md, ROADMAP item 2).

Two tiers:

- **Unit tier** (no processes): every robustness decision is a plain host
  state machine in serving/router.py — chained block hashes, the cluster
  prefix index, the durable intake log (torn-tail tolerance), the
  miss-threshold failure detector (fake clock), retry_backoff deadlines,
  and the RequestRouter's per-position dedup/merge + re-dispatch sets.
  Plus the engine-side cluster surface: explicit submit-time nonces and
  pool-native page adoption (`adopt_pages` + `pool_get_blocks`).
- **E2E tier** (REAL OS processes over TCPStore + ShmRing): a live
  cluster serves greedy + sampled streams bit-identical to one local
  engine, ships prefill pages with prefix-affinity routing, and
  drain-migrates queued requests on scale-down with no double-serving.

The SIGKILL crash matrix lives in test_serving_cluster_crash.py.  Both
modules fork and kill processes, so they ride DEDICATED
tools/run_tier1.py isolated workers — never the shared shard."""

import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.serving.router import (ClusterPrefixIndex, FailureDetector,
                                       IntakeLog, RequestRouter,
                                       block_hashes, retry_backoff)

_HERE = os.path.dirname(os.path.abspath(__file__))
_MODEL_SPEC = os.path.join(_HERE, "cluster_common.py") + ":make_model"

from tests.cluster_common import make_model, make_model_bf16  # noqa: E402

_EKW = dict(max_batch=2, block_size=8, num_blocks=32, decode_chunk=2)

# two prompts sharing one full 8-token block (the shipped/affinity unit)
# plus distinct tails, and one short sampled prompt with no full block
_SHARED = [5, 9, 17, 33, 2, 8, 7, 4]
P_G1 = _SHARED + [22, 3]
P_G2 = _SHARED + [9, 1]
P_S1 = [7, 11, 3]


# ---------------------------------------------------------------- unit tier
def test_block_hashes_are_chained_prefix_identity():
    bs = 4
    a = block_hashes([1, 2, 3, 4, 5, 6, 7, 8, 9], bs)
    assert len(a) == 2  # the partial third block never hashes
    b = block_hashes([1, 2, 3, 4, 5, 6, 7, 8], bs)
    assert a == b[:2] and len(b) == 2
    # a change in block 0 changes EVERY later hash (chaining): equal hash
    # at depth i must mean equal whole prefix, not equal chunk
    c = block_hashes([9, 2, 3, 4, 5, 6, 7, 8], bs)
    assert c[0] != a[0] and c[1] != a[1]
    # same chunk content at a different depth hashes differently
    d = block_hashes([5, 6, 7, 8], bs)
    assert d[0] != a[1]


def test_prefix_index_affinity_and_drop():
    idx = ClusterPrefixIndex(block_size=4)
    idx.record(0, [1, 2, 3, 4, 5, 6, 7, 8])
    idx.record(1, [1, 2, 3, 4])
    rank, depth = idx.best_replica([1, 2, 3, 4, 5, 6, 7, 8, 9])
    assert (rank, depth) == (0, 2)  # deepest holder wins
    rank, depth = idx.best_replica([1, 2, 3, 4, 99, 98, 97, 96])
    assert depth == 1 and rank in (0, 1)
    assert idx.best_replica([9, 9, 9, 9]) == (None, 0)
    # `among` restricts to live replicas; a dead rank's pages drop wholesale
    rank, depth = idx.best_replica([1, 2, 3, 4, 5, 6, 7, 8], among={1})
    assert (rank, depth) == (1, 1)
    idx.drop_rank(0)
    rank, depth = idx.best_replica([1, 2, 3, 4, 5, 6, 7, 8])
    assert (rank, depth) == (1, 1)


def test_intake_log_replay_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "intake.jsonl")
    log = IntakeLog(path)
    log.append({"ev": "submit", "rid": "a", "prompt": [1, 2], "opts": {},
                "nonce": 0})
    log.append({"ev": "tokens", "rid": "a", "start": 0, "toks": [7, 8]})
    log.close()
    # a SIGKILL mid-append leaves a torn trailing line: replay drops it
    with open(path, "a") as f:
        f.write('{"ev": "tok')
    recs = IntakeLog.replay(path)
    assert [r["ev"] for r in recs] == ["submit", "tokens"]
    # an INTERIOR torn line is corruption, not a crash artifact: loud
    with open(path, "w") as f:
        f.write('{"ev": "submit"}\n{"torn\n{"ev": "done"}\n')
    with pytest.raises(ValueError, match="corrupt"):
        IntakeLog.replay(path)
    assert IntakeLog.replay(str(tmp_path / "missing.jsonl")) == []


def test_retry_backoff_shared_deadline_and_counting():
    import random

    calls = {"n": 0}
    retries = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 4:
            raise TimeoutError("transient")
        return "ok"

    assert retry_backoff(flaky, timeout_s=5.0, base_s=0.001,
                         rng=random.Random(0),
                         on_retry=retries.append) == "ok"
    assert calls["n"] == 4 and len(retries) == 3

    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        retry_backoff(lambda: (_ for _ in ()).throw(TimeoutError("x")),
                      timeout_s=0.25, base_s=0.01, cap_s=0.05,
                      rng=random.Random(0))
    assert time.monotonic() - t0 < 1.0  # ONE deadline, not per-attempt
    # non-retryable errors propagate immediately
    with pytest.raises(ValueError):
        retry_backoff(lambda: (_ for _ in ()).throw(ValueError("real")),
                      timeout_s=5.0)


def test_retry_backoff_jitter_bounded_by_cap():
    """The sleep between attempts is full jitter on min(delay, cap_s):
    never negative, never above the cap even after the exponential
    doubling passes it — the contract that keeps N retrying callers from
    synchronizing into a thundering herd with unbounded gaps."""
    import random

    class SpyRng:
        def __init__(self):
            self.bounds = []

        def uniform(self, lo, hi):
            self.bounds.append((lo, hi))
            return 0.0  # no actual sleeping

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 6:
            raise ConnectionError("transient")
        return "ok"

    rng = SpyRng()
    assert retry_backoff(flaky, timeout_s=30.0, base_s=0.01, cap_s=0.04,
                         rng=rng) == "ok"
    # delays double 0.01, 0.02, 0.04, 0.08, 0.16 — but the jitter bound
    # saturates at cap_s
    assert [hi for _, hi in rng.bounds] == \
        [0.01, 0.02, 0.04, 0.04, 0.04]
    assert all(lo == 0.0 for lo, _ in rng.bounds)  # full jitter from 0

    # the real rng draws stay inside [0, cap_s] too
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        retry_backoff(lambda: (_ for _ in ()).throw(TimeoutError("x")),
                      timeout_s=0.1, base_s=0.001, cap_s=0.01,
                      rng=random.Random(7))
    assert time.monotonic() - t0 < 0.5


def test_prefix_index_drop_rank_shared_chain():
    """Two replicas share a chained-hash prefix; dropping one must peel
    ONLY its ranks out of the shared entries (the survivor keeps serving
    the common prefix) and drop its exclusive deeper entries wholesale."""
    idx = ClusterPrefixIndex(block_size=4)
    common = [1, 2, 3, 4, 5, 6, 7, 8]
    idx.record(0, common + [9, 10, 11, 12])  # rank 0: 3 blocks deep
    idx.record(1, common)                    # rank 1: the shared 2 blocks
    assert idx.best_replica(common + [9, 10, 11, 12]) == (0, 3)

    idx.drop_rank(0)
    # the shared chain survives via rank 1; rank 0's depth-3 page is gone
    assert idx.best_replica(common + [9, 10, 11, 12]) == (1, 2)
    assert idx.best_replica(common) == (1, 2)
    # internal maps really shrank: no orphaned hash buckets, no rank-0
    # residue to resurrect a corpse's affinity
    assert 0 not in idx._ranks
    assert all(0 not in holders for holders in idx._by_hash.values())
    assert len(idx._by_hash) == 2

    # dropping the survivor empties the index; a re-drop is a no-op
    idx.drop_rank(1)
    idx.drop_rank(1)
    assert idx._by_hash == {} and idx._ranks == {}
    assert idx.best_replica(common) == (None, 0)


def test_intake_log_replay_multi_record_torn_tail(tmp_path):
    """A SIGKILL tears at most the FINAL record: replay over a long log
    keeps every whole record and drops only a trailing partial — while a
    torn line with records AFTER it is corruption and stays loud, no
    matter how deep the log."""
    path = str(tmp_path / "intake.jsonl")
    log = IntakeLog(path)
    records = []
    for i in range(20):
        rec = {"ev": "tokens", "rid": f"r{i % 3}", "start": 4 * i,
               "toks": [i, i + 1]}
        records.append(rec)
        log.append(rec)
    log.close()
    assert IntakeLog.replay(path) == records

    with open(path, "a") as f:
        f.write('{"ev": "done", "rid": "r0", "n"')  # torn final append
    assert IntakeLog.replay(path) == records

    # interior tear: every line after it parses, but durability already
    # lied — loud, with the 1-based line number
    with open(path) as f:
        lines = f.readlines()
    lines[10] = lines[10][:9] + "\n"
    with open(path, "w") as f:
        f.writelines(lines)
    with pytest.raises(ValueError, match="line 11"):
        IntakeLog.replay(path)


def test_failure_detector_miss_threshold_and_boot_grace():
    clock = {"t": 0.0}
    missed = []
    det = FailureDetector(100, 3, clock=lambda: clock["t"],
                          on_miss=missed.append, boot_grace_s=5.0)
    det.track("r0")
    # boot window: the counter sits at its creation value (0) while the
    # worker imports jax — NOT dead until the boot grace, and no miss
    # telemetry noise from a normal boot
    clock["t"] = 0.4
    det.observe("r0", 0)
    assert det.dead_ranks() == [] and missed == []
    clock["t"] = 1.0
    det.observe("r0", 1)  # first real heartbeat: steady-state rules arm
    assert det.misses("r0") == 0
    clock["t"] = 1.25
    det.observe("r0", 1)
    assert det.dead_ranks() == []  # 2 misses < 3
    assert missed == [2]
    clock["t"] = 1.31
    assert det.dead_ranks() == ["r0"]  # 3rd missed period
    assert sum(missed) == 3  # each missed period reported exactly once
    # a beat resets the window
    det.observe("r0", 2)
    assert det.dead_ranks() == [] and det.misses("r0") == 0
    # a rank that NEVER beats dies at the boot grace
    det.track("r1")
    clock["t"] = 6.5
    assert "r1" in det.dead_ranks()
    det.forget("r1")
    assert "r1" not in det.dead_ranks()


def test_request_router_dedup_merge_and_redispatch(tmp_path):
    r = RequestRouter(block_size=4, log_path=str(tmp_path / "log.jsonl"))
    r.add_replica(0)
    r.add_replica(1)
    req = r.submit("a", [1, 2, 3, 4, 5], max_new=4, temperature=0.0, seed=0)
    assert req.nonce == 0
    # idempotent acceptance: a resubmitted rid keeps its first nonce
    assert r.submit("a", [1, 2, 3, 4, 5]).nonce == 0
    assert r.submit("b", [9, 9]).nonce == 1
    r.assign("a", 0)
    r.assign("b", 0)
    assert r.load(0) == 2
    assert r.on_tokens("a", 0, [10, 11]) == [10, 11]
    # re-emission after fail-over: overlap dedups, the tail appends
    assert r.on_tokens("a", 0, [10, 11, 12]) == [12]
    # divergence is corruption, never silently merged
    with pytest.raises(RuntimeError, match="diverge"):
        r.on_tokens("a", 1, [99])
    # a gap means a lost event: loud
    with pytest.raises(RuntimeError, match="gap|starts at"):
        r.on_tokens("b", 3, [1])
    # replica death: unfinished owned rids come back for re-dispatch
    r.on_tokens("b", 0, [20])
    r.on_done("b", 1)
    assert r.result("b") == [20]
    assert r.on_replica_dead(0) == ["a"]  # done "b" never moves
    assert r.unassigned() == ["a"]
    # the journal rebuilds the same state in a fresh router
    r2 = RequestRouter(block_size=4)
    r2.restore(IntakeLog.replay(str(tmp_path / "log.jsonl")))
    assert r2.result("b") == [20]
    assert r2.request("a").tokens == [10, 11, 12]
    assert r2.request("a").nonce == 0
    assert r2.submit("c", [1]).nonce == 2  # counter resumes PAST the log
    # drain: queued (never-started) rids migrate, residents stay
    r2.add_replica(1)
    r2.assign("a", 1)
    r2.assign("c", 1)
    assert r2.on_drained(1, ["c"]) == ["c"]
    assert r2.request("a").owner == 1 and r2.request("c").owner is None


def test_done_redelivery_counts_hit_toks_once():
    """REVIEW regression: the wire is at-least-once (TcpRing re-sends
    its in-flight frame whole after a drop), and `done` carries the
    prefix-hit watermark as a DELTA — a redelivered `done` must not
    double-count it into `prefix_hit_tokens`.  on_done returns True only
    on the FIRST completion and the handler gates the add on it."""
    from paddle_tpu.serving import cluster as cl

    r = RequestRouter(block_size=4)
    r.add_replica(0)
    r.submit("a", [1, 2, 3], max_new=1, temperature=0.0, seed=0)
    r.assign("a", 0)
    r.on_tokens("a", 0, [7])
    assert r.on_done("a", 1) is True
    assert r.on_done("a", 1) is False  # redelivered: not first
    assert r.on_done("ghost", 0) is False  # unknown rid: never counted

    class _Shell:
        router = r

    r.submit("b", [4, 5], max_new=1, temperature=0.0, seed=0)
    r.assign("b", 0)
    r.on_tokens("b", 0, [9])
    before = cl._CLUSTER_STATS["prefix_hit_tokens"]
    try:
        msg = {"rid": "b", "n": 1, "hit_toks": 8}
        cl.EngineCluster._ev_done(_Shell(), None, msg)
        cl.EngineCluster._ev_done(_Shell(), None, dict(msg))  # dup frame
        assert cl._CLUSTER_STATS["prefix_hit_tokens"] - before == 8
    finally:
        cl._CLUSTER_STATS["prefix_hit_tokens"] = before


def test_router_pick_replica_affinity_then_load():
    r = RequestRouter(block_size=4)
    for i in range(3):
        r.add_replica(i)
    p = [1, 2, 3, 4, 5, 6, 7, 8]
    r.submit("a", p)
    r.assign("a", 2)  # records the prompt's hashes for replica 2
    assert r.pick_replica(p) == 2  # affinity beats emptier replicas
    assert r.pick_replica([9, 9, 9, 9, 9]) in (0, 1)  # cold: least load
    assert r.pick_replica(p, among={0, 1}) in (0, 1)  # dead excluded


def test_explicit_nonce_reproduces_stream():
    """The bit-exact fail-over keystone: (seed, nonce) is request
    identity.  An engine given EXPLICIT nonces (the router's assignment)
    draws exactly the streams another engine produced with its local
    counter — submission order, engine instance, and admission timing
    all drop out."""
    m = make_model()
    ref = GenerationEngine(m, **_EKW)
    ref.add_request("x", P_S1, max_new_tokens=5, temperature=5.0, seed=3)
    ref.add_request("y", P_S1, max_new_tokens=5, temperature=5.0, seed=3)
    while ref.has_work():
        ref.step()

    eng = GenerationEngine(m, **_EKW)
    # reversed submission order, explicit nonces pinned to the identity
    eng.add_request("y", P_S1, max_new_tokens=5, temperature=5.0, seed=3,
                    nonce=1)
    eng.add_request("x", P_S1, max_new_tokens=5, temperature=5.0, seed=3,
                    nonce=0)
    while eng.has_work():
        eng.step()
    assert eng.result("x") == ref.result("x")
    assert eng.result("y") == ref.result("y")
    assert eng.result("x") != eng.result("y")  # distinct nonces still true
    # the local counter advanced PAST the explicit nonces: no collision
    assert eng._req_counter == 2


def _prefill_pages_for(model, prompt, kv="bf16"):
    from paddle_tpu.serving.cluster_worker import _prefill_pages

    n = (len(prompt) - 1) // _EKW["block_size"]
    return _prefill_pages(model, prompt, n, _EKW["block_size"], kv)


def test_adopt_pages_prefix_hit_bit_exact():
    """Shipped pages adopt as refcount-zero cached prefix pages, the next
    admission prefix-hits them, and the served stream is BIT-identical to
    a local-prefill engine (full-precision pools; the engine pours and
    the prefill worker pours through the same math)."""
    from paddle_tpu.serving import decode_stats, reset_decode_stats

    m = make_model()
    ref = GenerationEngine(m, prefix_cache=True, **_EKW)
    ref.add_request("g", P_G1, max_new_tokens=6)
    while ref.has_work():
        ref.step()

    eng = GenerationEngine(m, prefix_cache=True, **_EKW)
    toks, k_layers, v_layers = _prefill_pages_for(m, P_G1)
    assert eng.adopt_pages(toks, k_layers, v_layers) == 1
    # adopted pages are resident-but-reclaimable (refcount 0), exactly
    # like pages whose owning request finished
    assert len(eng._prefix) == 1
    reset_decode_stats()
    eng.add_request("g", P_G1, max_new_tokens=6)
    while eng.has_work():
        eng.step()
    st = decode_stats()
    assert st["prefix_hits"] == 1 and st["prefix_hit_tokens"] == 8
    assert eng.result("g") == ref.result("g")
    # re-adoption of a cached prefix is a no-op, not a duplicate page
    toks, k_layers, v_layers = _prefill_pages_for(m, P_G1)
    assert eng.adopt_pages(toks, k_layers, v_layers) == 0


def test_adopt_pages_int8_ship_deterministic_and_lossless():
    """The two facts bit-exact fail-over rests on for int8 shipping:
    (a) shipping is DETERMINISTIC — a re-dispatched request re-ships
    byte-identical pages (same forward, same quantization), so the new
    replica serves the same stream; (b) ship-then-place is LOSSLESS — the
    wire carries the pool's own int8 payload + f32 scales and
    `pool_set_blocks` lands them verbatim, never re-quantizing."""
    m = make_model()
    toks, k1, v1 = _prefill_pages_for(m, P_G1, kv="int8")
    _t, k2, _v2 = _prefill_pages_for(m, P_G1, kv="int8")
    for a, b in zip(k1, k2):  # (a): re-ship is bit-identical
        np.testing.assert_array_equal(a["payload"], b["payload"])
        np.testing.assert_array_equal(a["scale"], b["scale"])

    eng = GenerationEngine(m, prefix_cache=True,
                           **dict(_EKW, kv_cache_dtype="int8"))
    assert eng.adopt_pages(toks, k1, v1) == 1
    ab = eng._prefix.match(toks)[0]
    for li in range(2):  # (b): adopted pool blocks == the shipped leaves
        np.testing.assert_array_equal(
            np.asarray(eng._pools[0][li].data[ab]), k1[li]["payload"][0])
        np.testing.assert_array_equal(
            np.asarray(eng._pools[0][li].scale[ab]), k1[li]["scale"][0])
    # and an int8 admission over adopted pages serves a complete stream
    eng.add_request("g", P_G1, max_new_tokens=4)
    while eng.has_work():
        eng.step()
    assert len(eng.result("g")) == 4


def test_int8_ship_halves_wire_bytes_vs_bf16():
    m = make_model_bf16()
    _t, k8, v8 = _prefill_pages_for(m, P_G1, kv="int8")
    _t, kbf, vbf = _prefill_pages_for(m, P_G1, kv="bf16")

    def nbytes(layers):
        return sum(a.nbytes for lay in layers for a in lay.values())

    ratio = (nbytes(k8) + nbytes(v8)) / (nbytes(kbf) + nbytes(vbf))
    assert ratio < 0.6, ratio  # int8 payload halves bf16; scales ride along


def test_adopt_pages_loud_on_bad_shapes_and_modes():
    m = make_model()
    eng = GenerationEngine(m, prefix_cache=False, **_EKW)
    with pytest.raises(RuntimeError, match="prefix cache"):
        eng.adopt_pages(P_G1, [], [])
    eng = GenerationEngine(m, prefix_cache=True, **_EKW)
    toks, k_layers, v_layers = _prefill_pages_for(m, P_G1)
    with pytest.raises(ValueError, match="layers"):
        eng.adopt_pages(toks, k_layers[:1], v_layers)
    bad = [{k: v[:, :2] for k, v in lay.items()} for lay in k_layers]
    with pytest.raises(ValueError, match="geometry"):
        eng.adopt_pages(toks, bad, v_layers)
    # pool-kind mismatch (bf16 pages into an int8 pool) is THIS error,
    # not a KeyError deep in pool_set_blocks: the sender quantized for
    # the wrong pool kind and a respawn-retry loop cannot fix that
    eng8 = GenerationEngine(make_model(), prefix_cache=True,
                            **dict(_EKW, kv_cache_dtype="int8"))
    with pytest.raises(ValueError, match="kind|leaves"):
        eng8.adopt_pages(toks, k_layers, v_layers)


def test_restored_replica_delivers_results_that_finished_before_its_snapshot():
    """A replica that outlives its router finishes its residents and
    snapshots them finished-but-undelivered.  Restored, it claims them in
    its resume report, and must then hand them over although nothing is
    left to step (it used to report progress only after a step: the
    claimed streams never arrived and the cluster waited for ever)."""
    import pickle

    from paddle_tpu.serving.cluster_worker import (_claimed_rids,
                                                   _decode_serve)

    eng = GenerationEngine(make_model(), prefix_cache=True, **_EKW)
    eng.add_request("g", P_G1, max_new_tokens=4)
    while eng.has_work():
        eng.step()
    want = list(eng.result("g"))
    tracked = _claimed_rids(eng)       # what a restored engine resurrects
    assert tracked == {"g"} and not eng.has_work()

    class Ring:
        def pop(self, timeout_ms):
            return pickle.dumps({"t": "stop"})

    class Out:
        def __init__(self):
            self.pushed = []

        def push(self, msg):
            self.pushed.append(msg)

    class Killer:
        def hit(self, point):
            pass

    out = Out()
    _decode_serve({"snapshot_dir": None}, eng, tracked, Ring(), out,
                  Killer())
    kinds = [(m["t"], m.get("rid")) for m in out.pushed]
    assert kinds == [("tokens", "g"), ("done", "g"), ("bye", None)], kinds
    assert out.pushed[0]["start"] == 0 and out.pushed[0]["toks"] == want
    assert out.pushed[1]["n"] == len(want)


# ------------------------------------------------------- adapter namespaces
# cluster adapter specs: (name, rank, alpha, seed) — alpha 1024 so that
# the tiny model's FIRST greedy token on P_G1 moves under each adapter,
# to three different tokens for base / tenant-a / tenant-b (asserted once,
# in test_adopt_pages_adapter_namespace_isolation_and_stale_epoch): tenant
# streams must be OBSERVABLY distinct, or isolation tests prove nothing.
# At alpha 64 tenant-a's stream coincided with the base model's
_ADAPTER_SPECS = [("tenant-a", 4, 1024.0, 11), ("tenant-b", 4, 1024.0, 12)]


def test_cluster_adapter_table_lockstep_with_engine_registration():
    """cluster_adapter_table is a PROMISE about engine behaviour — spec i
    lands at (slot i+1, epoch 1) — kept only because every worker
    registers the specs in order on a fresh engine.  Pin the table to the
    real registration path so a slot-assignment or epoch-bump change
    breaks HERE, not as a silent cluster-wide cache mismatch."""
    from paddle_tpu.serving.cluster_worker import _register_cluster_adapters
    from paddle_tpu.serving.router import cluster_adapter_table

    table = cluster_adapter_table(_ADAPTER_SPECS)
    assert table == {"tenant-a": (1, 1), "tenant-b": (2, 1)}

    eng = GenerationEngine(make_model(), prefix_cache=True,
                           adapters={"rank": 4, "max_adapters": 2}, **_EKW)
    _register_cluster_adapters(eng, {"adapters": _ADAPTER_SPECS})
    for name, (slot, epoch) in table.items():
        got = eng._slot_of(name)
        assert got == slot, (name, got, slot)
        assert eng._slot_epochs[got] == epoch
    # re-registration (a snapshot-restored engine re-running boot) must
    # leave resident names untouched: an epoch bump here would desync
    # this engine's namespace from the rest of the fleet
    _register_cluster_adapters(eng, {"adapters": _ADAPTER_SPECS})
    assert eng._slot_epochs[1] == 1 and eng._slot_epochs[2] == 1


def test_block_hashes_adapter_namespaces_disjoint():
    # the ns seeds the hash CHAIN, so one prompt under base / tenant-a /
    # tenant-a-after-epoch-bump / tenant-b yields pairwise-disjoint
    # chains — the cluster index can never alias tenants' pages
    chains = [block_hashes(P_G1, 8),
              block_hashes(P_G1, 8, ns=(1, 1)),
              block_hashes(P_G1, 8, ns=(1, 2)),
              block_hashes(P_G1, 8, ns=(2, 1))]
    for i in range(len(chains)):
        for j in range(i + 1, len(chains)):
            assert not set(chains[i]) & set(chains[j]), (i, j)


def test_adopt_pages_adapter_namespace_isolation_and_stale_epoch():
    """Shipped adapter pages land in exactly the (slot, epoch) namespace
    pinned at SHIP time: the tenant's own admission prefix-hits them,
    no other tenant (nor the base model) ever cross-matches, a stale
    epoch strands the shipment LOUDLY, and a base engine refuses
    namespaced pages outright."""
    from paddle_tpu.nn.lora import adapter_prefill_scope
    from paddle_tpu.serving import (decode_stats, lora_stats,
                                    reset_decode_stats)
    from paddle_tpu.serving.cluster_worker import (
        _build_prefill_pack, _cluster_adapter_state, _prefill_pages,
        _register_cluster_adapters)

    m = make_model()
    spec = {"adapters": _ADAPTER_SPECS}
    # pages poured through tenant-a's weights, the prefill-worker path
    pack = _build_prefill_pack(m, spec)
    scope = adapter_prefill_scope(m.model.layers, pack, 1)
    toks, k_l, v_l = _prefill_pages(m, P_G1, 1, _EKW["block_size"],
                                    "bf16", scope=scope)

    eng = GenerationEngine(m, prefix_cache=True,
                           adapters={"rank": 4, "max_adapters": 2}, **_EKW)
    _register_cluster_adapters(eng, spec)
    assert eng.adopt_pages(toks, k_l, v_l, ns=(1, 1)) == 1
    reset_decode_stats()
    eng.add_request("qa", P_G1, max_new_tokens=4, adapter="tenant-a")
    while eng.has_work():
        eng.step()
    st = decode_stats()
    assert st["prefix_hits"] == 1 and st["prefix_hit_tokens"] == 8

    # the OTHER tenant and the base model never match tenant-a's pages
    for rid, adapter in (("qb", "tenant-b"), ("qc", None)):
        reset_decode_stats()
        eng.add_request(rid, P_G1, max_new_tokens=4, adapter=adapter)
        while eng.has_work():
            eng.step()
        assert decode_stats()["prefix_hits"] == 0, (rid, adapter)
    # and the tenants' streams are genuinely distinct computations, from
    # the first greedy token on (what _ADAPTER_SPECS' alpha was chosen for)
    firsts = {eng.result(rid)[0] for rid in ("qa", "qb", "qc")}
    assert len(firsts) == 3, firsts

    # stale epoch: tenant-a re-registers (epoch bumps), so a shipment
    # pinned at the OLD epoch holds K/V this engine no longer serves —
    # dropped loudly, never cached
    eng.register_adapter("tenant-a", _cluster_adapter_state(m, 4, 99),
                         alpha=64.0)
    assert eng._slot_epochs[1] == 2
    drops0 = lora_stats()["ship_ns_drops"]
    assert eng.adopt_pages(toks, k_l, v_l, ns=(1, 1)) == 0
    assert lora_stats()["ship_ns_drops"] == drops0 + 1

    # a namespace this pack cannot name is a spec disagreement, not a
    # droppable race
    with pytest.raises(ValueError, match="out of range"):
        eng.adopt_pages(toks, k_l, v_l, ns=(7, 1))

    # a base engine must never accept adapter-poured K/V into its
    # un-namespaced prefix cache
    base = GenerationEngine(m, prefix_cache=True, **_EKW)
    with pytest.raises(ValueError, match="without"):
        base.adopt_pages(toks, k_l, v_l, ns=(1, 1))


# ----------------------------------------------------------------- e2e tier
def _mk_cluster(workdir, **kw):
    from paddle_tpu.serving.cluster import EngineCluster

    kw.setdefault("heartbeat_ms", 100)
    kw.setdefault("miss_threshold", 20)
    return EngineCluster(_MODEL_SPEC, engine_kwargs=_EKW,
                         workdir=str(workdir), **kw)


def _single_engine_reference(submissions, max_batch=4):
    eng = GenerationEngine(make_model(),
                           **dict(_EKW, max_batch=max_batch),
                           prefix_cache=True)
    for rid, prompt, opts in submissions:
        eng.add_request(rid, prompt, **opts)
    while eng.has_work():
        eng.step()
    return {rid: eng.result(rid) for rid, _p, _o in submissions}


_WORKLOAD = [
    ("g1", P_G1, dict(max_new_tokens=8)),
    ("g2", P_G2, dict(max_new_tokens=8)),
    ("s1", P_S1, dict(max_new_tokens=6, temperature=5.0, seed=3)),
]


def _cluster_e2e_matches_single_engine(tmp_path):
    from paddle_tpu.serving.cluster import cluster_stats

    ref = _single_engine_reference(_WORKLOAD)
    c = _mk_cluster(tmp_path / "wd", num_replicas=2, num_prefill=1)
    try:
        for rid, prompt, opts in _WORKLOAD:
            c.submit(rid, prompt,
                     max_new_tokens=opts["max_new_tokens"],
                     temperature=opts.get("temperature", 0.0),
                     seed=opts.get("seed", 0))
        c.serve(timeout_s=240)
        got = {rid: c.result(rid) for rid, _p, _o in _WORKLOAD}
        # full-precision pools: the shipped-page path reproduces the
        # local engine's streams on this workload (the GUARANTEED
        # contract — killed-vs-unkilled cluster bit-exactness — lives in
        # test_serving_cluster_crash.py; this cross-architecture match is
        # the stronger observed property for bf16/f32 pools)
        assert got == ref, (got, ref)
        # prefix affinity routed the shared-prefix pair to ONE replica
        assert (c.router.request("g1").owner
                == c.router.request("g2").owner)
        st = cluster_stats()
        assert st["replicas_alive"] == 2
        assert st["pages_shipped"] >= 2 and st["ship_bytes"] > 0
        assert st["redispatches"] == 0
        # idempotent resubmission: no duplicate serve, stream unchanged
        c.submit("g1", P_G1, max_new_tokens=8)
        c.serve(timeout_s=30)
        assert c.result("g1") == ref["g1"]
    finally:
        c.shutdown()


def _cluster_drain_scale_down(tmp_path):
    from paddle_tpu.serving.cluster import cluster_stats, \
        reset_cluster_stats

    # max_batch 1: the first request occupies replica 0's only slot, the
    # same-prefix followers QUEUE on it (affinity routes them there)
    ekw = dict(_EKW, max_batch=1)
    # "a" is long on purpose: the drain must land while it is RESIDENT
    # (so "b"/"c" are still queued on the worker and genuinely migrate)
    subs = [("a", P_G1, dict(max_new_tokens=40)),
            ("b", P_G2, dict(max_new_tokens=8)),
            ("c", _SHARED + [1, 2], dict(max_new_tokens=8))]
    ref = _single_engine_reference(subs, max_batch=1)

    from paddle_tpu.serving.cluster import EngineCluster

    reset_cluster_stats()
    c = EngineCluster(_MODEL_SPEC, engine_kwargs=ekw,
                      workdir=str(tmp_path / "wd"), num_replicas=2,
                      heartbeat_ms=100, miss_threshold=20)
    try:
        for rid, prompt, opts in subs:
            c.submit(rid, prompt, **{
                "max_new_tokens": opts["max_new_tokens"]})
        owner = c.router.request("a").owner
        assert all(c.router.request(r).owner == owner for r in "abc")
        # let replica `owner` admit "a" (first token delivered) so "b"/"c"
        # are genuinely queued on the worker when the drain lands
        deadline = time.monotonic() + 120
        while not c.router.request("a").tokens:
            c.poll()
            assert time.monotonic() < deadline
            time.sleep(0.002)
        c.scale_down(owner)
        c.serve(timeout_s=240)
        got = {rid: c.result(rid) for rid, _p, _o in subs}
        assert got == ref, (got, ref)
        st = cluster_stats()
        # the queued pair migrated; the resident finished on the lame duck
        assert st["drain_migrations"] == 2
        assert st["replicas_alive"] == 1
        survivors = {c.router.request(r).owner for r in ("b", "c")}
        assert owner not in survivors
    finally:
        c.shutdown()


def _cluster_telemetry_footer(tmp_path):
    from paddle_tpu import profiler
    from paddle_tpu.profiler.statistics import cluster_line

    st = profiler.cluster_stats()
    assert set(st) >= {"replicas_alive", "heartbeats_missed",
                       "redispatches", "pages_shipped", "ship_retries",
                       "drain_migrations"}
    line = cluster_line(dict(st, replicas_alive=2, pages_shipped=3))
    assert "Serving cluster:" in line and "pages_shipped=3" in line
    assert cluster_line({k: 0 for k in st}) == ""
    # reset zeroes traffic counters but keeps the alive gauge
    before = profiler.cluster_stats()["replicas_alive"]
    profiler.cluster_stats(reset=True)
    after = profiler.cluster_stats()
    assert after["replicas_alive"] == before
    assert after["redispatches"] == 0


def _cluster_priority_ahead_of_long(tmp_path):
    # SLO-class admission end-to-end: the replica's only free slot is
    # held by a chunk-interleaved LOW-priority long prefill when a HIGH
    # request lands — the worker engine preempts the LOW request (parks
    # or demotes it) and the HIGH stream completes FIRST, while every
    # final stream still matches an uncontended single engine's
    # (submit-time nonces make the re-admitted stream bit-identical).
    from paddle_tpu.serving.cluster import EngineCluster

    rng = np.random.default_rng(17)
    p_long = [int(t) for t in rng.integers(1, 128, 40)]
    subs = [("w", P_G1, dict(max_new_tokens=20)),
            ("long", p_long, dict(max_new_tokens=16, temperature=5.0,
                                  seed=3, priority="low")),
            ("hi", P_S1, dict(max_new_tokens=6, priority="high"))]
    ref = _single_engine_reference(subs, max_batch=4)

    ekw = dict(_EKW, prefill_chunk_blocks=1)
    c = EngineCluster(_MODEL_SPEC, engine_kwargs=ekw,
                      workdir=str(tmp_path / "wd"), num_replicas=1,
                      heartbeat_ms=100, miss_threshold=20)
    try:
        for rid, prompt, opts in subs:
            c.submit(rid, prompt,
                     max_new_tokens=opts["max_new_tokens"],
                     temperature=opts.get("temperature", 0.0),
                     seed=opts.get("seed", 0),
                     priority=opts.get("priority", "normal"))
        deadline = time.monotonic() + 120
        while c.result("hi") is None:
            assert time.monotonic() < deadline, "hi never completed"
            c.poll()
            time.sleep(0.002)
        # the HIGH request finished while the LOW long request (which
        # was submitted before it) is still in flight
        assert c.result("long") is None
        c.serve(timeout_s=240)
        got = {rid: c.result(rid) for rid, _p, _o in subs}
        assert got == ref, (got, ref)
    finally:
        c.shutdown()


def _cluster_adapter_e2e_tcp(tmp_path):
    """Adapter-aware page shipping over the TcpRing data plane: tenant
    requests prefill through their adapter's weights on the prefill
    worker, ship namespaced pages, and the decode replica's admission
    prefix-hits the ADOPTED pages — asserted through the router-side
    cluster counter (`prefix_hit_tokens`, relayed as per-`done` deltas),
    the cross-host cache contract of docs/SERVING_CLUSTER.md.  Streams
    must match a single adapter engine's, and tenants must observably
    diverge from each other and from the base model."""
    from paddle_tpu.serving.cluster import cluster_stats, \
        reset_cluster_stats
    from paddle_tpu.serving.cluster_worker import _register_cluster_adapters

    subs = [("a1", P_G1, dict(max_new_tokens=8, adapter="tenant-a")),
            ("b1", P_G1, dict(max_new_tokens=8, adapter="tenant-b")),
            ("base", P_G1, dict(max_new_tokens=8))]
    ref_eng = GenerationEngine(make_model(),
                               **dict(_EKW, max_batch=4),
                               prefix_cache=True,
                               adapters={"rank": 4, "max_adapters": 2})
    _register_cluster_adapters(ref_eng, {"adapters": _ADAPTER_SPECS})
    for rid, prompt, opts in subs:
        ref_eng.add_request(rid, prompt, **opts)
    while ref_eng.has_work():
        ref_eng.step()
    ref = {rid: ref_eng.result(rid) for rid, _p, _o in subs}

    reset_cluster_stats()
    c = _mk_cluster(tmp_path / "wd", num_replicas=2, num_prefill=1,
                    adapters=_ADAPTER_SPECS, transport="tcp")
    try:
        with pytest.raises(KeyError, match="not a cluster adapter"):
            c.submit("x", P_G1, max_new_tokens=4, adapter="tenant-z")
        for rid, prompt, opts in subs:
            c.submit(rid, prompt,
                     max_new_tokens=opts["max_new_tokens"],
                     adapter=opts.get("adapter"))
        c.serve(timeout_s=240)
        got = {rid: c.result(rid) for rid, _p, _o in subs}
        assert got == ref, (got, ref)
        # tenancy is observable: each tenant's stream diverges
        assert got["a1"] != got["base"] and got["a1"] != got["b1"]
        st = cluster_stats()
        # THE acceptance counter: shipped namespaced pages were adopted
        # and prefix-HIT by the tenant admissions on the decode replicas
        # (P_G1 carries one full 8-token block per request)
        assert st["prefix_hit_tokens"] >= 8, st
        assert st["pages_shipped"] >= 3 and st["ship_bytes"] > 0
        # and the whole exchange genuinely rode the socket plane
        assert st["tcp_bytes"] > 0 and st["frames_sent"] > 0, st
    finally:
        c.shutdown()


# The e2e payloads fork real engine processes and kill them; each runs in
# tier-1 through the dedicated isolated worker for this module, and the
# pieces run as separate pytest cases for attribution.
def test_cluster_e2e_matches_single_engine(tmp_path):
    _cluster_e2e_matches_single_engine(tmp_path)


def test_cluster_adapter_tenants_prefix_hit_shipped_pages_tcp(tmp_path):
    _cluster_adapter_e2e_tcp(tmp_path)


def test_cluster_priority_completes_ahead_of_long_prefill(tmp_path):
    _cluster_priority_ahead_of_long(tmp_path)


def test_cluster_drain_scale_down_no_double_serve(tmp_path):
    _cluster_drain_scale_down(tmp_path)


def test_cluster_telemetry_schema_and_footer(tmp_path):
    _cluster_telemetry_footer(tmp_path)
