"""Cluster SIGKILL crash-injection matrix (serving/cluster.py,
docs/SERVING_CLUSTER.md; the serving-cluster extension of the
test_engine_snapshot_crash.py matrix).

A DRIVER subprocess runs a real cluster — router in the driver process, N
decode replicas + a prefill worker as its own OS child processes — over
the native TCPStore and ShmRing, serving a fixed greedy+sampled workload
with KV-page shipping.  Crash injection SIGKILLs one enumerated
participant at one enumerated protocol point:

- a DECODE REPLICA after accepting a request, mid-stream (intake-log
  replay fail-over), mid-stream with boundary snapshots armed
  (EngineSnapshot restore fail-over), and right after adopting shipped
  pages;
- the PREFILL WORKER before and in the middle of a page shipment;
- the WARM-STANDBY tier (ROADMAP item 5): a decode death with a warm
  standby parked is recovered by PROMOTION (the standby claims the dead
  replica's snapshot — no respawn), and a standby SIGKILLed mid-warmup
  degrades recovery to the respawn fallback without losing a request;
- the ROUTER itself right after journaling an acceptance and mid-serving
  (the driver process dies; a SECOND driver run over the same workdir
  replays the durable intake log, sweeps the orphaned workers, and
  finishes).

Every completed run must produce streams BIT-IDENTICAL to the unkilled
reference — zero accepted requests lost, no stream corrupted, no request
served twice (the router's canonical per-position merge enforces all
three).  The worker and standby matrices run TWICE — once over ShmRing
(single box) and once over the TcpRing socket data plane between two
localhost "hosts" (serving/transport.py), both compared against the ONE
shm reference, so a kill that tears live TCP connections mid-frame must
still recover bit-exactly.  This module forks and kills real processes:
it rides a DEDICATED tools/run_tier1.py isolated worker, never the
shared shard."""

import json
import os
import signal
import subprocess
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))

_DRIVER = r"""
import json
import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
from paddle_tpu._core import compile_cache

compile_cache.enable()  # the suite's one cache (tests/conftest.py)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)

from paddle_tpu.serving.cluster import EngineCluster, cluster_stats

(workdir, out_path, model_spec, router_kill, worker_role, worker_kill,
 snapshot_interval, standby, wait_standby, transport) = sys.argv[1:11]

worker_kill_map = {}
if worker_kill.startswith("{"):
    # multi-participant kills: {"role:idx": "point:nth", ...}
    for k, v in json.loads(worker_kill).items():
        role, idx = k.split(":")
        worker_kill_map[(role, int(idx))] = v
elif worker_kill:
    worker_kill_map[(worker_role, 0)] = worker_kill

EKW = dict(max_batch=2, block_size=8, num_blocks=32, decode_chunk=2)
SHARED = [5, 9, 17, 33, 2, 8, 7, 4]
WORKLOAD = [
    ("g1", SHARED + [22, 3], dict(max_new_tokens=8)),
    ("g2", SHARED + [9, 1], dict(max_new_tokens=8)),
    ("s1", [7, 11, 3], dict(max_new_tokens=6, temperature=5.0, seed=3)),
]

c = EngineCluster(model_spec, num_replicas=2, num_prefill=1,
                  engine_kwargs=EKW, workdir=workdir,
                  heartbeat_ms=100, miss_threshold=10,
                  snapshot_interval=int(snapshot_interval),
                  kill=router_kill, worker_kill=worker_kill_map,
                  standby=int(standby), transport=transport)
try:
    if int(wait_standby):
        # the case under test is PROMOTION: the kill must find a WARM
        # standby, not race its boot
        import time
        deadline = time.monotonic() + 180
        while cluster_stats()["standbys_warm"] < int(wait_standby):
            c.poll()
            if time.monotonic() > deadline:
                raise TimeoutError("standby tier never warmed")
            time.sleep(0.01)
    for rid, prompt, opts in WORKLOAD:
        c.submit(rid, prompt, max_new_tokens=opts["max_new_tokens"],
                 temperature=opts.get("temperature", 0.0),
                 seed=opts.get("seed", 0))
    c.serve(timeout_s=240)
    with open(out_path, "w") as f:
        json.dump({rid: c.result(rid) for rid, _p, _o in WORKLOAD}, f)
    print("STATS", json.dumps(cluster_stats()))
    print("DONE")
finally:
    c.shutdown()
"""

_MODEL_SPEC = os.path.join(_HERE, "cluster_common.py") + ":make_model"


def _run_driver(tmp_path, workdir, out, router_kill="", worker_role="",
                worker_kill="", snapshot_interval=0, standby=0,
                wait_standby=0, transport="shm"):
    script = tmp_path / "driver.py"
    script.write_text(_DRIVER)
    repo_root = os.path.dirname(_HERE)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo_root + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    cmd = [sys.executable, str(script), str(workdir), str(out),
           _MODEL_SPEC, router_kill, worker_role, worker_kill,
           str(snapshot_interval), str(standby), str(wait_standby),
           transport]
    return subprocess.run(cmd, capture_output=True, text=True, timeout=480,
                          env=env)


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """The unkilled (shm) cluster run: the streams every killed variant
    — on EITHER transport — must reproduce token for token.  Comparing
    tcp runs against the shm reference additionally pins stream
    transport-independence: the data plane may reorder wall-clock, never
    tokens."""
    td = tmp_path_factory.mktemp("cluster_ref")
    out = td / "ref.json"
    r = _run_driver(td, td / "wd", out)
    assert "DONE" in r.stdout, (r.stdout + r.stderr)[-3000:]
    return json.loads(out.read_text())


# shm: process-shared rings (single box); tcp: TcpRing sockets between
# two localhost "hosts" (serving/transport.py) — the FULL kill matrix
# re-runs on each data plane, bit-exact against the one shm reference
_TRANSPORTS = ["shm", "tcp"]

# (who dies, at which protocol point, boundary snapshots armed?)
_WORKER_MATRIX = [
    ("decode", "decode-after-accept:1", 0),
    ("decode", "decode-mid-stream:1", 0),   # intake-log replay fail-over
    ("decode", "decode-mid-stream:2", 1),   # EngineSnapshot restore fail-over
    ("decode", "decode-after-adopt:1", 0),  # dies holding shipped pages
    ("prefill", "prefill-before-ship:1", 0),
    ("prefill", "prefill-mid-ship:1", 0),   # partial shipment on the wire
]


@pytest.mark.parametrize("transport", _TRANSPORTS)
@pytest.mark.parametrize("role,point,snap", _WORKER_MATRIX,
                         ids=[p for _r, p, _s in _WORKER_MATRIX])
def test_worker_kill_matrix_streams_bit_identical(tmp_path, reference,
                                                  role, point, snap,
                                                  transport):
    """SIGKILL one worker process at the named point: the router detects
    the death (heartbeats/child-exit), re-dispatches every accepted-but-
    unfinished request (replayed from the intake log, restored from the
    dead replica's boundary snapshot, or re-shipped through a fresh
    prefill worker), and the completed streams equal the unkilled run's
    bit for bit — on the shm plane and again over TcpRing sockets, where
    the kill also tears the victim's live connections mid-frame."""
    out = tmp_path / "out.json"
    r = _run_driver(tmp_path, tmp_path / "wd", out, worker_role=role,
                    worker_kill=point, snapshot_interval=snap,
                    transport=transport)
    assert "DONE" in r.stdout, (r.stdout + r.stderr)[-3000:]
    got = json.loads(out.read_text())
    assert got == reference, (got, reference)
    stats = json.loads(
        [ln for ln in r.stdout.splitlines()
         if ln.startswith("STATS ")][-1][len("STATS "):])
    # the injected kill really happened: a replacement process spawned
    assert stats["respawns"] >= 1, stats
    if role == "decode" and not snap:
        # replay fail-over: requests genuinely moved (the restore path
        # instead CLAIMS them back via the replacement's resume report,
        # so redispatches may legitimately stay 0 there)
        assert stats["redispatches"] >= 1, stats
    if role == "prefill":
        assert stats["ship_retries"] >= 1, stats


@pytest.mark.parametrize("transport", _TRANSPORTS)
def test_standby_promotion_claims_snapshot_bit_identical(tmp_path,
                                                         reference,
                                                         transport):
    """Warm-standby fail-over (ROADMAP item 5): a decode replica is
    SIGKILLed mid-stream with boundary snapshots armed and a WARM standby
    parked.  The standby is PROMOTED — no process spawns — claims the
    dead replica's snapshot directory, restores its residents, and every
    completed stream equals the unkilled run's bit for bit (the
    bit-exact fail-over contract re-asserted on the promotion path, on
    both data planes)."""
    out = tmp_path / "out.json"
    r = _run_driver(tmp_path, tmp_path / "wd", out, worker_role="decode",
                    worker_kill="decode-mid-stream:2", snapshot_interval=1,
                    standby=1, wait_standby=1, transport=transport)
    assert "DONE" in r.stdout, (r.stdout + r.stderr)[-3000:]
    got = json.loads(out.read_text())
    assert got == reference, (got, reference)
    stats = json.loads(
        [ln for ln in r.stdout.splitlines()
         if ln.startswith("STATS ")][-1][len("STATS "):])
    # the warm standby took the slot; the respawn path never ran
    assert stats["promotions"] >= 1, stats
    assert stats["respawns"] == 0, stats


@pytest.mark.parametrize("transport", _TRANSPORTS)
def test_standby_killed_mid_warmup_falls_back_to_respawn(tmp_path,
                                                         reference,
                                                         transport):
    """The standby ITSELF is SIGKILLed mid-warmup, then a decode replica
    dies mid-stream before the backfilled standby can warm: recovery
    falls back to the (cache-warmed) respawn path.  Zero requests lost,
    streams bit-identical — a dead standby never weakens the fail-over
    contract, it only costs the fast path."""
    kills = json.dumps({"standby:0": "standby-mid-warmup:1",
                        "decode:0": "decode-mid-stream:1"})
    out = tmp_path / "out.json"
    r = _run_driver(tmp_path, tmp_path / "wd", out, worker_kill=kills,
                    snapshot_interval=1, standby=1, transport=transport)
    assert "DONE" in r.stdout, (r.stdout + r.stderr)[-3000:]
    got = json.loads(out.read_text())
    assert got == reference, (got, reference)
    stats = json.loads(
        [ln for ln in r.stdout.splitlines()
         if ln.startswith("STATS ")][-1][len("STATS "):])
    # the decode death was recovered by a respawn (the dead standby left
    # no warm candidate in time); promotions are not asserted zero —
    # the backfilled standby MAY win the race on a slow box, and either
    # recovery path must uphold the same stream contract
    assert stats["respawns"] >= 1 or stats["promotions"] >= 1, stats


@pytest.mark.parametrize("router_kill,snap,transport", [
    ("router-after-accept:1", 0, "shm"),
    ("router-mid-serving:1", 0, "shm"),
    # boundary snapshots armed: the restarted router's replicas RESTORE
    # and claim their residents via resume reports — the replay backlog
    # must hold for those claims instead of double-dispatching the same
    # rids onto other replicas
    ("router-mid-serving:1", 1, "shm"),
    # over TcpRing the restarted router binds FRESH listener ports and
    # re-publishes every ep:<ring> key on its new control store — the
    # orphan sweep plus endpoint re-publication path
    ("router-mid-serving:1", 0, "tcp"),
], ids=["after-accept", "mid-serving", "mid-serving-snapshots",
        "mid-serving-tcp"])
def test_router_kill_then_restart_replays_intake_log(tmp_path, reference,
                                                     router_kill, snap,
                                                     transport):
    """SIGKILL the ROUTER PROCESS itself (after journaling the first
    acceptance / after delivering the first token event): a fresh router
    over the same workdir sweeps the orphaned workers, replays the
    durable intake log — completed streams served from the journal,
    unfinished requests re-dispatched — and finishes every stream
    bit-identically.  An accepted request never dies with the router."""
    wd = tmp_path / "wd"
    r = _run_driver(tmp_path, wd, tmp_path / "x.json",
                    router_kill=router_kill, snapshot_interval=snap,
                    transport=transport)
    assert r.returncode == -signal.SIGKILL, (r.stdout + r.stderr)[-3000:]
    assert os.path.exists(wd / "intake.jsonl")

    out = tmp_path / "resumed.json"
    r2 = _run_driver(tmp_path, wd, out, snapshot_interval=snap,
                     transport=transport)
    assert "DONE" in r2.stdout, (r2.stdout + r2.stderr)[-3000:]
    got = json.loads(out.read_text())
    assert got == reference, (got, reference)
