"""Warm-start tier: engine AOT warmup, warm-standby readiness, and the
warmed-respawn compile-cache contract (serving/__init__.py `warmup`,
serving/cluster.py standby tier, docs/SERVING_CLUSTER.md; ROADMAP item 5).

Three tiers:

- **Detector units** (fake clock): `mark_warmed` ends the boot-grace
  carve-out — a worker that announced `warmed=True` and then stalls is
  declared dead within the NORMAL miss threshold, while cold boots keep
  the grace window.
- **Engine units**: `GenerationEngine.warmup()` AOT-compiles the macro
  -step executables against the engine's recorded geometry; the warmed
  executable is the one `step()` dispatches (identity, not just
  equality), streams are bit-identical to a lazily-compiled engine, and
  `EngineSnapshot.config()` exposes the recorded geometry that decides
  whether warm executables carry onto a restored engine.
- **Cluster e2e**: a warm standby that stalls (SIGSTOP) dies on the
  steady-state miss budget, never the boot grace; and (fresh per-test
  persistent cache) a SIGKILLed decode replica's respawned replacement
  boots with persistent compile-cache HITS > 0 — asserted from its boot
  report, not assumed.

This module forks standby/replica workers and SIGKILLs them: it rides a
DEDICATED tools/run_tier1.py isolated worker, never the shared shard."""

import os
import signal
import time

import pytest

jax = pytest.importorskip("jax")
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

from paddle_tpu.serving.router import FailureDetector  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_MODEL_SPEC = os.path.join(_HERE, "cluster_common.py") + ":make_model"
_EKW = dict(max_batch=2, block_size=8, num_blocks=32, decode_chunk=2)


# ------------------------------------------------------- detector units
def test_mark_warmed_ends_boot_grace():
    """A warm worker that stalls is dead within the normal miss budget —
    the boot-grace carve-out exists only for cold boots still paying
    import + compile before their first heartbeat."""
    clock = {"t": 0.0}
    det = FailureDetector(100, 3, clock=lambda: clock["t"],
                          boot_grace_s=5.0)
    det.track("w")
    det.mark_warmed("w")
    # 0.3s = miss_threshold * heartbeat: dead NOW, grace does not apply
    clock["t"] = 0.35
    assert det.dead_ranks() == ["w"]


def test_cold_boot_keeps_grace_without_warm_report():
    clock = {"t": 0.0}
    det = FailureDetector(100, 3, clock=lambda: clock["t"],
                          boot_grace_s=5.0)
    det.track("w")
    clock["t"] = 0.35  # far past the miss budget, inside the grace
    assert det.dead_ranks() == []
    clock["t"] = 5.0
    assert det.dead_ranks() == ["w"]


def test_mark_warmed_restarts_miss_window_at_report():
    """The warm report itself is proof of life: the miss clock starts at
    the report, not at track() — a slow warmup must not instantly kill
    the worker that just finished it."""
    clock = {"t": 0.0}
    det = FailureDetector(100, 3, clock=lambda: clock["t"],
                          boot_grace_s=5.0)
    det.track("w")
    clock["t"] = 4.9  # warmup took nearly the whole grace window
    det.mark_warmed("w")
    clock["t"] = 5.0  # 0.1s after the report: one miss at most
    assert det.dead_ranks() == []
    clock["t"] = 5.3
    assert det.dead_ranks() == ["w"]


def test_mark_warmed_then_heartbeats_stay_alive():
    clock = {"t": 0.0}
    det = FailureDetector(100, 3, clock=lambda: clock["t"],
                          boot_grace_s=5.0)
    det.track("w")
    det.mark_warmed("w")
    for i in range(1, 20):
        clock["t"] = i * 0.1
        det.observe("w", i)
        assert det.dead_ranks() == []


# --------------------------------------------------------- engine units
def _make_engine(**over):
    import sys

    sys.path.insert(0, _HERE)
    from cluster_common import make_model
    from paddle_tpu.serving import GenerationEngine

    kw = dict(_EKW)
    kw.update(over)
    return GenerationEngine(make_model(), **kw)


def _drain(eng, reqs):
    for rid, prompt, opts in reqs:
        eng.add_request(rid, prompt, **opts)
    while eng.has_work():
        eng.step()
    return {rid: eng.result(rid) for rid, _p, _o in reqs}


_REQS = [
    ("a", [5, 9, 17, 33, 2, 8, 7, 4, 22, 3], dict(max_new_tokens=8)),
    ("b", [7, 11, 3], dict(max_new_tokens=6, temperature=5.0, seed=3)),
]


def test_warmup_compiles_the_executable_step_dispatches():
    eng = _make_engine()
    assert eng._step_fns == {}
    rep = eng.warmup()
    D = eng._effective_chunk()
    assert rep["chunks"] == [D]
    assert rep["seconds"] > 0
    compiled = eng._step_fns[D]
    got = _drain(eng, _REQS)
    assert all(got.values())
    # identity: serving dispatched the warmed executable, it did not
    # silently rebuild (a rebuild would mean warmup warmed nothing)
    assert eng._step_fns[D] is compiled


def test_warmed_streams_bit_identical_to_lazy():
    cold = _drain(_make_engine(), _REQS)
    warm_eng = _make_engine()
    warm_eng.warmup()
    warm = _drain(warm_eng, _REQS)
    assert warm == cold


def test_warmup_extra_chunks_and_validation():
    eng = _make_engine()
    rep = eng.warmup(chunks=[1, 2])
    assert rep["chunks"] == [1, 2]
    assert set(eng._step_fns) == {1, 2}
    with pytest.raises(ValueError):
        eng.warmup(chunks=[0])


def test_snapshot_config_records_geometry(tmp_path):
    from paddle_tpu.serving.snapshot import EngineSnapshot

    eng = _make_engine()
    store = EngineSnapshot(str(tmp_path / "snaps"))
    store.save(eng)
    cfg = store.config()
    assert cfg["max_batch"] == _EKW["max_batch"]
    assert cfg["block_size"] == _EKW["block_size"]
    assert cfg["num_blocks"] == _EKW["num_blocks"]
    assert not cfg["has_draft"]
    empty = EngineSnapshot(str(tmp_path / "none"))
    with pytest.raises(RuntimeError):
        empty.config()


def test_carries_executables_gates_on_geometry(tmp_path):
    from paddle_tpu.serving.cluster_worker import _carries_executables
    from paddle_tpu.serving.snapshot import EngineSnapshot

    eng = _make_engine()
    store = EngineSnapshot(str(tmp_path / "snaps"))
    store.save(eng)
    cfg = store.config()
    assert _carries_executables(eng, cfg)
    # a geometry mismatch (different pool) must NOT carry: the compiled
    # signature would not match the restored engine's buffers
    other = dict(cfg, num_blocks=cfg["num_blocks"] * 2)
    assert not _carries_executables(eng, other)


# ----------------------------------------------------------- cluster e2e
def test_stalled_warm_standby_dies_on_steady_state_budget(tmp_path):
    """A standby that reported ready and then stalls (SIGSTOP — the
    process is alive, so the parent-exit fast path never fires) is
    declared dead within the NORMAL miss budget, nowhere near the 30s
    boot grace: its warm report already armed steady-state accounting."""
    from paddle_tpu.serving.cluster import EngineCluster, cluster_stats

    c = EngineCluster(_MODEL_SPEC, num_replicas=1, num_prefill=0,
                      engine_kwargs=_EKW, workdir=str(tmp_path / "wd"),
                      heartbeat_ms=100, miss_threshold=10, standby=1)
    try:
        deadline = time.monotonic() + 180
        while cluster_stats()["standbys_warm"] < 1:
            c.poll()
            assert time.monotonic() < deadline, "standby never warmed"
            time.sleep(0.01)
        skey = next(k for k in c._workers if k[0] == "standby")
        assert c.detector.boot_grace_s >= 30.0  # the window NOT applied
        os.kill(c._workers[skey].proc.pid, signal.SIGSTOP)
        t0 = time.monotonic()
        try:
            # miss budget = 10 * 100ms; declared dead well within a
            # small multiple of it (poll jitter), never the boot grace
            while c._workers[skey].alive:
                c.poll()
                assert time.monotonic() - t0 < 10.0, \
                    "stalled warm standby outlived the miss budget"
                time.sleep(0.02)
        finally:
            try:  # burial SIGKILLs the stopped proc; pid may be reaped
                os.kill(c._workers[skey].proc.pid, signal.SIGCONT)
            except ProcessLookupError:
                pass
        assert time.monotonic() - t0 < 10.0 < c.detector.boot_grace_s
    finally:
        c.shutdown()


def test_respawned_worker_boots_with_persistent_cache_hits(
        tmp_path, monkeypatch):
    """The warmed-respawn contract, asserted not assumed: gen-1 workers
    populate a FRESH persistent compile cache through the shared
    _core/compile_cache helper; the respawned replacement's boot report
    must then show persistent_cache_hits > 0 (its warmup was served from
    the cache the first generation wrote)."""
    from paddle_tpu.serving.cluster import (EngineCluster, cluster_stats,
                                            reset_cluster_stats)

    cache = tmp_path / "fresh_cache"
    # workers inherit it; jax reads it itself and code sets no directory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    reset_cluster_stats()
    c = EngineCluster(_MODEL_SPEC, num_replicas=1, num_prefill=0,
                      engine_kwargs=_EKW, workdir=str(tmp_path / "wd"),
                      heartbeat_ms=100, miss_threshold=10,
                      snapshot_interval=1)
    try:
        c.submit("r0", [5, 9, 17, 33, 2, 8, 7, 4, 22, 3],
                 max_new_tokens=24)
        c.submit("r1", [7, 11, 3], max_new_tokens=24, temperature=5.0,
                 seed=3)
        deadline = time.monotonic() + 240
        while not c.router.request("r0").tokens:
            c.poll()
            assert time.monotonic() < deadline, "stream never started"
            time.sleep(0.005)
        os.kill(c._workers[("decode", 0)].proc.pid, signal.SIGKILL)
        c.serve(timeout_s=240)
        stats = cluster_stats()
        assert stats["respawns"] >= 1, stats
        # the replacement AOT-warmed (report folded into telemetry) and
        # its compiles were served from the persistent cache
        assert stats["warmups"] >= 2, stats
        assert stats["respawn_compile_hits"] > 0, stats
        assert c.result("r0") and c.result("r1")
    finally:
        c.shutdown()
