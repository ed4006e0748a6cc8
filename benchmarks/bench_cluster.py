"""Disaggregated serving cluster: throughput + fail-over latency, with a
zero-loss / bit-exact-fail-over parity gate (serving/cluster.py,
docs/SERVING_CLUSTER.md; ROADMAP items 2 and 5).

Phases, all over REAL OS processes (router + N decode replicas + a
prefill worker on TCPStore/ShmRing):

- **Baseline**: an unkilled cluster serves the workload; the headline
  metric is end-to-end cluster tokens/s (submit -> last completion wall),
  with KV pages shipped prefill->decode counted (int8-halved wire bytes
  when the pool is int8).
- **Fail-over matrix**: the same workload three times; once every stream
  is in flight, the busiest replica is SIGKILLed.  One run per recovery
  mode:

    cold          warmup=False, no standby — respawn pays fork + jax
                  import + model build + LAZY first-step compile on the
                  recovery critical path (the pre-warm-start behaviour)
    warm_respawn  warmup=True, no standby — the respawned worker AOT-
                  warms (persistent-cache-served) BEFORE claiming its
                  snapshot, so compiles never land mid-serving; its boot
                  report must show persistent_cache_hits > 0
    standby       warmup=True, standby=1 — a pre-forked warm standby is
                  PROMOTED into the dead slot: no fork, no import, no
                  compile on the recovery path at all

  Reported per mode: first_token_ms — failure DETECTION to the first NEW
  token on a victim-owned stream (the user-visible recovery latency).
  The top-level detect_ms/recover_ms describe the standby run (the
  recovery path this cluster actually prefers when the tier is armed);
  per-mode numbers ride detail.failover.first_token_ms.  lost counts
  accepted requests that never completed (MUST be 0 in every mode) and
  streams_match requires every mode's streams to equal the unkilled
  run's bit for bit — the fail-over contract, re-asserted on every
  promotion path.

rc is 0 only when lost == 0 AND streams_match across ALL modes — the
latency numbers are never reported off a run that dropped or corrupted a
request.  Prints ONE JSON line like the other benches;
tools/check_bench_regression.py gates the failover latencies and the
per-mode first-token numbers (lower is better, SLO threshold).
`--smoke` / PADDLE_TPU_BENCH_SMOKE shrinks sizes for CI
(tests/test_bench_cluster.py).  `--transport tcp` (or
PADDLE_TPU_BENCH_TRANSPORT=tcp) runs every phase over the TcpRing
socket data plane between two localhost "hosts" — same zero-loss /
bit-exact gates, plus a detail.transport section (kind, tcp_bytes,
reconnects, frames) that check_bench_regression.py gates (skipping
silently on pre-transport payloads).  PADDLE_TPU_BENCH_DEADLINE_S
widens every internal wait wall on loaded CI hosts.  This bench forks
and kills processes: CPU-runnable by construction, no accelerator
required (cluster workers run on the CPU today — ROADMAP R5)."""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_bench_model():
    """Deterministic tiny llama built identically in EVERY cluster
    process (the worker imports this file by path)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(7)
    cfg = llama_tiny(vocab_size=256, hidden_size=64, intermediate_size=176,
                     num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=4, max_position_embeddings=256,
                     dtype="float32")
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _workload(n_req, max_new):
    shared = [5, 9, 17, 33, 2, 8, 7, 4, 11, 29, 3, 31, 6, 12, 20, 17]
    out = []
    for i in range(n_req):
        out.append((f"r{i}", shared + [i + 1, (i * 7) % 200 + 1],
                    max_new))
    return out


def _run_cluster(workdir, spec, ekw, work, kill_busiest=False, *,
                 warmup=True, standby=0, snapshot_interval=0,
                 transport="shm"):
    from paddle_tpu.serving.cluster import EngineCluster, cluster_stats

    shutil.rmtree(workdir, ignore_errors=True)
    c = EngineCluster(spec, num_replicas=2, num_prefill=1,
                      engine_kwargs=ekw, workdir=workdir,
                      heartbeat_ms=100, miss_threshold=10,
                      snapshot_interval=snapshot_interval,
                      warmup=warmup, standby=standby,
                      transport=transport)
    fo = {"detect_ms": 0.0, "first_token_ms": 0.0, "recover_ms": 0.0}
    try:
        # the shared wall for every wait below: CI hosts running six
        # test jobs stretch fork/compile walls, so the budget is
        # env-tunable (tests/test_bench_cluster.py raises it under load)
        budget = float(os.environ.get("PADDLE_TPU_BENCH_DEADLINE_S", 240))
        deadline = time.monotonic() + budget
        if standby:
            # the mode under test is PROMOTION: killing before the
            # standby is warm would measure the respawn fallback instead
            while cluster_stats()["standbys_warm"] < standby:
                c.poll()
                if time.monotonic() > deadline:
                    raise TimeoutError("standby tier never warmed")
                time.sleep(0.005)
        t0 = time.monotonic()
        for rid, prompt, max_new in work:
            c.submit(rid, prompt, max_new_tokens=max_new)
        if kill_busiest:
            # wait until every stream is genuinely in flight
            while any(not c.router.request(rid).tokens
                      for rid, _p, _m in work):
                c.poll()
                if time.monotonic() > deadline:
                    raise TimeoutError("streams never all started")
                time.sleep(0.002)
            victim = max(c.router.replicas(), key=c.router.load)
            w = c._workers[("decode", victim)]
            # victim-owned unfinished streams: the first NEW token on any
            # of them is the user-visible end of the recovery outage.
            # Ownership must be read BEFORE the kill (death releases it)
            victims = [rid for rid, _p, _m in work
                       if c.router.request(rid).owner == victim
                       and not c.router.request(rid).done]
            before = cluster_stats()
            t_kill = time.monotonic()
            os.kill(w.proc.pid, 9)  # SIGKILL: no goodbye, no flush
            # detection is visible as a re-dispatch (replay fail-over),
            # the replacement spawn (restore/claim path), or a standby
            # promotion (warm-start path)
            def _detected():
                st = cluster_stats()
                return any(st[k] != before[k] for k in
                           ("redispatches", "respawns", "promotions"))
            while not _detected():
                c.poll()
                if time.monotonic() > deadline:
                    raise TimeoutError("death never detected")
                time.sleep(0.001)
            t_detect = time.monotonic()
            fo["detect_ms"] = (t_detect - t_kill) * 1000
            # baseline counts AFTER detection: the dead worker's ring may
            # still have held pre-kill tokens that the detection polls
            # merged — those are delivery backlog, not recovery, and must
            # not zero the first-token clock
            counts = {rid: len(c.router.request(rid).tokens)
                      for rid in victims}

            def _first_new_token():
                return any(len(c.router.request(rid).tokens) > n0
                           for rid, n0 in counts.items())
            while counts and not _first_new_token():
                c.poll()
                if time.monotonic() > deadline:
                    raise TimeoutError("victim streams never resumed")
                time.sleep(0.001)
            fo["first_token_ms"] = (time.monotonic() - t_detect) * 1000
            c.serve(timeout_s=budget)
            fo["recover_ms"] = (time.monotonic() - t_kill) * 1000
        else:
            c.serve(timeout_s=budget)
        wall = time.monotonic() - t0
        results = {rid: c.result(rid) for rid, _p, _m in work}
        stats = cluster_stats(reset=True)
        return results, wall, stats, fo
    finally:
        c.shutdown()


def main():
    import jax

    if os.environ.get("PADDLE_TPU_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    smoke = os.environ.get("PADDLE_TPU_BENCH_SMOKE") or "--smoke" in sys.argv
    # --transport tcp (or PADDLE_TPU_BENCH_TRANSPORT=tcp) runs the SAME
    # phases over the socket data plane: two localhost "hosts", every
    # parity gate unchanged — zero lost, bit-exact fail-over streams
    transport = os.environ.get("PADDLE_TPU_BENCH_TRANSPORT", "shm")
    if "--transport" in sys.argv:
        i = sys.argv.index("--transport")
        if i + 1 >= len(sys.argv):
            raise SystemExit("--transport needs a value: shm | tcp")
        transport = sys.argv[i + 1]
    spec = os.path.abspath(__file__) + ":make_bench_model"
    ekw = dict(max_batch=2, block_size=8, num_blocks=48, decode_chunk=4)
    # streams must OUTLIVE the kill: short smoke streams complete before
    # the SIGKILL lands and leave nothing to fail over
    n_req, max_new = (3, 32) if smoke else (6, 48)
    work = _workload(n_req, max_new)
    base = tempfile.mkdtemp(prefix="bench_cluster_")
    modes = (("cold", dict(warmup=False, standby=0)),
             ("warm_respawn", dict(warmup=True, standby=0)),
             ("standby", dict(warmup=True, standby=1)))
    try:
        ref, wall, base_stats, _fo = _run_cluster(
            os.path.join(base, "ref"), spec, ekw, work,
            transport=transport)
        total_tokens = sum(len(v) for v in ref.values() if v)
        tps = total_tokens / wall if wall else 0.0

        runs = {}
        for mode, kw in modes:
            got, _w, stats, fo = _run_cluster(
                os.path.join(base, mode), spec, ekw, work,
                kill_busiest=True, snapshot_interval=1,
                transport=transport, **kw)
            runs[mode] = {
                "got": got, "stats": stats, "fo": fo,
                "lost": sum(1 for rid, _p, _m in work if not got.get(rid)),
                "match": got == ref,
            }
    finally:
        shutil.rmtree(base, ignore_errors=True)

    lost = sum(r["lost"] for r in runs.values())
    streams_match = all(r["match"] for r in runs.values())
    sb, wr = runs["standby"], runs["warm_respawn"]
    print(json.dumps({
        "metric": "cluster_tokens_per_sec",
        "value": round(tps, 2),
        "unit": "tok/s",
        "vs_baseline": 0.0,
        "tokens_match": streams_match,
        "detail": {
            "replicas": 2,
            "prefill_workers": 1,
            "requests": n_req,
            "total_tokens": total_tokens,
            "failover": {
                "detect_ms": round(sb["fo"]["detect_ms"], 1),
                "recover_ms": round(sb["fo"]["recover_ms"], 1),
                "first_token_ms": {
                    m: round(runs[m]["fo"]["first_token_ms"], 1)
                    for m, _kw in modes},
                "lost": lost,
                "streams_match": streams_match,
                "redispatches": sum(
                    r["stats"]["redispatches"] for r in runs.values()),
                "promotions": sb["stats"]["promotions"],
                "respawn_compile_hits":
                    wr["stats"]["respawn_compile_hits"],
            },
            "ship": {
                "pages": base_stats["pages_shipped"],
                "bytes": base_stats["ship_bytes"],
                "retries": base_stats["ship_retries"],
            },
            "transport": {
                "kind": transport,
                "tcp_bytes": base_stats.get("tcp_bytes", 0),
                "reconnects": base_stats.get("reconnects", 0),
                "frames_sent": base_stats.get("frames_sent", 0),
                "frames_recv": base_stats.get("frames_recv", 0),
            },
        },
    }))
    return 0 if (lost == 0 and streams_match) else 1


if __name__ == "__main__":
    sys.exit(main())
