"""CI smoke for benchmarks/bench_cluster.py — the CPU-falsifiable twin of
the cluster throughput + fail-over latency claims (control flow, counts
and stream parity; its CPU times are not device metrics).

Runs the bench in --smoke mode as a subprocess (it forks and SIGKILLs
real cluster processes, which is also why this module rides a DEDICATED
tools/run_tier1.py isolated worker) and asserts the payload contract the
regression gate consumes: zero lost requests, bit-matching fail-over
streams across EVERY recovery mode, positive fail-over latencies, pages
actually shipped, and the warm-start acceptance floor — standby
promotion's detect->first-token beats cold respawn by at least 2x, and
the warmed respawn booted with persistent compile-cache hits > 0.

A second run exercises --transport tcp: the same gates over the TcpRing
socket data plane (two localhost "hosts"), plus the transport counter
section the regression gate reads.

Load discipline: under run_tier1 --jobs 6 the host runs six test
workers, so (a) every internal bench wait rides a widened
PADDLE_TPU_BENCH_DEADLINE_S wall, and (b) the standby-vs-cold 2x floor
— a timing RATIO of two single-shot process recoveries — gets ONE
retry of the whole bench before failing: a real regression fails both
runs, a scheduler spike only one."""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(extra_args=()):
    env = dict(os.environ, PADDLE_TPU_BENCH_SMOKE="1",
               PADDLE_TPU_BENCH_CPU="1", JAX_PLATFORMS="cpu",
               PADDLE_TPU_BENCH_DEADLINE_S="480")
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "benchmarks",
                                      "bench_cluster.py"), "--smoke",
         *extra_args],
        capture_output=True, text=True, timeout=840, env=env, cwd=_REPO)
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    line = [ln for ln in r.stdout.strip().splitlines()
            if ln.startswith("{")][-1]
    return json.loads(line)


def _assert_payload(payload, transport):
    assert payload["metric"] == "cluster_tokens_per_sec"
    assert payload["value"] > 0
    assert payload["tokens_match"] is True
    fo = payload["detail"]["failover"]
    # the acceptance criteria the bench gates on: a SIGKILLed replica
    # loses ZERO accepted requests and the recovered streams are the
    # unkilled run's bit for bit
    assert fo["lost"] == 0
    assert fo["streams_match"] is True
    assert fo["detect_ms"] > 0 and fo["recover_ms"] >= fo["detect_ms"]
    ft = fo["first_token_ms"]
    for mode in ("cold", "warm_respawn", "standby"):
        assert ft[mode] > 0, ft
    # the standby run really promoted, and the warmed respawn really
    # booted off the persistent cache — asserted, not assumed
    assert fo["promotions"] >= 1, fo
    assert fo["respawn_compile_hits"] > 0, fo
    assert payload["detail"]["ship"]["pages"] >= 1
    assert payload["detail"]["ship"]["bytes"] > 0
    tr = payload["detail"]["transport"]
    assert tr["kind"] == transport
    if transport == "tcp":
        # the socket plane genuinely carried the cluster: bytes and
        # frames counted, and nothing needed a reconnect on localhost
        assert tr["tcp_bytes"] > 0 and tr["frames_sent"] > 0, tr
        assert tr["frames_recv"] > 0, tr
    else:
        assert tr["tcp_bytes"] == 0, tr
    return ft


def _floor_checked(extra_args, transport):
    payload = _run_bench(extra_args)
    ft = _assert_payload(payload, transport)
    # warm-start matrix: every recovery mode measured, and the promotion
    # path's detect->first-token beats cold respawn by >= 2x (the
    # ROADMAP item-5 acceptance floor — 2x is deliberately loose next to
    # the typical ~20x, but a single-shot ratio can still flake when six
    # test jobs contend for cores, hence one whole-bench retry)
    if ft["standby"] * 2 > ft["cold"]:
        payload = _run_bench(extra_args)
        ft = _assert_payload(payload, transport)
        assert ft["standby"] * 2 <= ft["cold"], ft


def test_transport_flag_missing_value_is_a_clean_error():
    # `--transport` as the LAST argument: a usage error, not an
    # IndexError traceback (the parse runs before any bench work)
    env = dict(os.environ, PADDLE_TPU_BENCH_CPU="1", JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "benchmarks",
                                      "bench_cluster.py"), "--transport"],
        capture_output=True, text=True, timeout=180, env=env, cwd=_REPO)
    assert r.returncode != 0
    assert "needs a value" in (r.stdout + r.stderr)
    assert "IndexError" not in r.stderr


def test_bench_cluster_smoke_payload():
    _floor_checked((), "shm")


def test_bench_cluster_smoke_payload_tcp():
    _floor_checked(("--transport", "tcp"), "tcp")
