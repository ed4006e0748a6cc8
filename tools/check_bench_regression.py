"""Benchmark regression gate.

Reference: tools/ci_op_benchmark.sh + tools/check_op_benchmark_result.py —
the reference CI compares op-benchmark logs between base and PR builds and
fails on relative regressions beyond a threshold.

Usage:
    python tools/check_bench_regression.py OLD.json NEW.json \
        [--threshold 0.05]

Each file holds the driver-recorded bench payload: either the raw JSON line
bench.py prints ({"metric", "value", ...}) or the driver wrapper with
stdout/rc fields.  Exit 1 (loud) when the new value regresses more than
`threshold` relative to the old on the same metric; missing/failed runs
(rc != 0 or value 0) are reported but never counted as regressions — a
run that did not measure must not mask or fabricate a perf signal.

Serving payloads carrying the SLO-percentile section (bench_decode.py
detail.slo.single: p50/p95/p99 time-to-first-token + inter-token latency)
are ALSO gated, with the direction inverted (latency growing beyond
--slo-threshold is the regression) and a wider default threshold — tail
percentiles jitter more than throughput means.  Payloads lacking the
section on either side skip the latency gate silently.

Serving payloads carrying the snapshot section (bench_decode.py
detail.snapshot: save_ms/restore_ms of a live mid-flight engine snapshot,
serving/snapshot.py) gate like the SLO percentiles — lower is better, so
growth beyond --slo-threshold is the regression (the wall cost of
honoring a preemption) — and skip silently on pre-snapshot payloads.

Serving payloads carrying the overload section (bench_decode.py
detail.overload: resident-stream p99 inter-token latency under a long
mid-decode prefill, chunked-interleaved vs atomic admission) gate both
ITL numbers lower-is-better at --slo-threshold — the chunked side is the
product, the atomic side the workload control — and skip silently on
pre-chunking payloads.

Cluster payloads carrying the fail-over section (bench_cluster.py
detail.failover: detect_ms from SIGKILL to the router's first re-dispatch,
recover_ms to every stream complete) gate like the SLO percentiles —
lower is better, growth beyond --slo-threshold is the regression — and
skip silently on pre-cluster payloads.  A fail-over run that LOST a
request records rc != 0 and is skipped as unhealthy rather than gated:
zero-loss is an acceptance criterion, not a trend.

Cluster payloads carrying the transport section (bench_cluster.py
detail.transport: {"kind", "tcp_bytes", "reconnects", "frames_sent",
"frames_recv"}) gate the SOCKET data plane when both sides ran
--transport tcp: reconnects must not grow at all (a localhost cluster
run never legitimately drops a connection — any new reconnect is a
transport bug, not jitter) and tcp_bytes growth beyond the regular
--threshold means framing overhead regressed.  Pre-transport payloads
(no section) and shm runs skip silently.

Training payloads carrying the pipeline-schedule section (bench.py
detail.pipeline.schedules: per-schedule bubble fraction from the static
simulator, fleet/meta_parallel/schedules.py) gate each schedule's bubble
LOWER-is-better at the regular --threshold — the numbers are
deterministic host math, so any growth means a schedule table got worse
— and skip silently on pre-schedule payloads.
"""

from __future__ import annotations

import argparse
import json
import sys


def _payload_dict(path):
    """The bench payload dict for a driver-recorded file, unwrapping the
    {"rc", "stdout"/"tail"} driver envelope -> (dict, None) or
    (None, reason)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        return None, f"unreadable ({e})"
    if isinstance(data, dict) and ("stdout" in data or "tail" in data):
        rc = data.get("rc", data.get("returncode"))
        if rc not in (0, None):
            return None, f"rc={rc}"
        text = str(data.get("stdout") or data.get("tail") or "")
        for line in reversed(text.strip().splitlines()):
            try:
                inner = json.loads(line)
            except ValueError:
                continue
            if isinstance(inner, dict) and "metric" in inner:
                data = inner
                break
        else:
            return None, "no metric line in stdout"
    if not isinstance(data, dict):
        return None, "no metric field"
    return data, None


def load_payload(path):
    """-> (metric, value) or (None, reason)."""
    data, err = _payload_dict(path)
    if data is None:
        return None, err
    if "metric" not in data:
        return None, "no metric field"
    try:
        value = float(data.get("value", 0.0))
    except (TypeError, ValueError):
        return None, f"non-numeric value {data.get('value')!r}"
    if value <= 0.0:
        return None, "zero/failed value"
    return (data["metric"], value), None


def load_slo(path):
    """The SLO-percentile section of a serving bench payload
    (bench_decode.py detail.slo.single: {"ttft_ms": {p50, p95, p99},
    "itl_ms": {...}}), or None when the payload has no such section —
    pre-SLO rounds and non-serving benches simply skip the latency
    gate."""
    data, _err = _payload_dict(path)
    if not isinstance(data, dict):
        return None
    slo = (data.get("detail") or {}).get("slo")
    if not isinstance(slo, dict):
        return None
    return slo.get("single")


def load_snapshot(path):
    """The snapshot-timing section of a serving bench payload
    (bench_decode.py detail.snapshot: {"save_ms", "restore_ms", "bytes",
    "resume_tokens_match"}), or None when absent — pre-snapshot rounds
    and non-serving benches skip the gate."""
    data, _err = _payload_dict(path)
    if not isinstance(data, dict):
        return None
    snap = (data.get("detail") or {}).get("snapshot")
    return snap if isinstance(snap, dict) else None


def load_overload(path):
    """The overload section of a serving bench payload (bench_decode.py
    detail.overload: {"itl_p99_ms_chunked", "itl_p99_ms_atomic",
    "tokens_per_sec_chunked", ...}), or None when absent — pre-chunking
    rounds and non-serving benches skip the gate."""
    data, _err = _payload_dict(path)
    if not isinstance(data, dict):
        return None
    ov = (data.get("detail") or {}).get("overload")
    return ov if isinstance(ov, dict) else None


def load_failover(path):
    """The fail-over section of a cluster bench payload (bench_cluster.py
    detail.failover: {"detect_ms", "recover_ms", "lost", "streams_match",
    "first_token_ms": {"cold", "warm_respawn", "standby"}}), or None when
    absent — pre-cluster rounds and non-cluster benches skip the gate.
    Payloads written before the warm-start round carry no first_token_ms
    dict; that sub-gate skips silently for them."""
    data, _err = _payload_dict(path)
    if not isinstance(data, dict):
        return None
    fo = (data.get("detail") or {}).get("failover")
    return fo if isinstance(fo, dict) else None


def load_transport(path):
    """The transport section of a cluster bench payload (bench_cluster.py
    detail.transport: {"kind", "tcp_bytes", "reconnects", "frames_sent",
    "frames_recv"}), or None when absent — payloads written before the
    socket data plane existed skip the gate silently."""
    data, _err = _payload_dict(path)
    if not isinstance(data, dict):
        return None
    tr = (data.get("detail") or {}).get("transport")
    return tr if isinstance(tr, dict) else None


def load_pipeline(path):
    """The pipeline-schedule section of a training bench payload (bench.py
    detail.pipeline: {"S", "M", "schedules": {"1F1B": bubble, ...}}), or
    None when absent — pre-schedule rounds skip the gate."""
    data, _err = _payload_dict(path)
    if not isinstance(data, dict):
        return None
    pl = (data.get("detail") or {}).get("pipeline")
    if not isinstance(pl, dict):
        return None
    sch = pl.get("schedules")
    return sch if isinstance(sch, dict) else None


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("old")
    p.add_argument("new")
    p.add_argument("--threshold", type=float, default=0.05,
                   help="max allowed relative regression (default 5%%)")
    p.add_argument("--slo-threshold", type=float, default=0.5,
                   help="max allowed relative latency-percentile growth "
                        "for the serving SLO section (default 50%% — "
                        "CPU-measured tail percentiles jitter far more "
                        "than throughput means)")
    args = p.parse_args(argv)

    old, old_err = load_payload(args.old)
    new, new_err = load_payload(args.new)
    if old is None or new is None:
        print(f"bench gate: SKIP — old: {old_err or 'ok'}; new: {new_err or 'ok'} "
              "(unhealthy runs are never counted as regressions)")
        return 0
    om, ov = old
    nm, nv = new
    if om != nm:
        print(f"bench gate: SKIP — metrics differ ({om} vs {nm})")
        return 0
    rel = (nv - ov) / ov
    status = "REGRESSION" if rel < -args.threshold else "ok"
    print(f"bench gate [{om}]: {ov:.2f} -> {nv:.2f} ({rel:+.2%}) {status}")
    rc = 1 if status == "REGRESSION" else 0

    # SLO-percentile gate (serving benches): latencies are LOWER-is-
    # better, so the regression direction inverts.  Percentiles present
    # on only one side (pre-SLO rounds) skip silently — an added metric
    # must not fail the round that adds it.
    old_slo, new_slo = load_slo(args.old), load_slo(args.new)
    if old_slo and new_slo:
        for section in ("ttft_ms", "itl_ms"):
            o, n = old_slo.get(section), new_slo.get(section)
            if not (isinstance(o, dict) and isinstance(n, dict)):
                continue
            for pk in ("p50", "p95", "p99"):
                if pk not in o or pk not in n or not o[pk] > 0:
                    continue
                rel = (float(n[pk]) - float(o[pk])) / float(o[pk])
                stat = ("REGRESSION" if rel > args.slo_threshold else "ok")
                print(f"bench gate [slo {section} {pk}]: {o[pk]:.2f} -> "
                      f"{n[pk]:.2f} ms ({rel:+.2%}) {stat}")
                if stat == "REGRESSION":
                    rc = 1

    # snapshot-timing gate (serving fault tolerance): save/restore wall
    # of a live-engine snapshot, lower-is-better like the SLO section
    # and sharing its wider threshold (single-shot wall timings jitter).
    # Sides missing the section (pre-snapshot rounds) skip silently.
    old_snap, new_snap = load_snapshot(args.old), load_snapshot(args.new)
    if old_snap and new_snap:
        for sk in ("save_ms", "restore_ms"):
            try:
                o, n = float(old_snap.get(sk, 0)), float(new_snap.get(sk, 0))
            except (TypeError, ValueError):
                continue
            if not o > 0 or not n > 0:
                continue
            rel = (n - o) / o
            stat = "REGRESSION" if rel > args.slo_threshold else "ok"
            print(f"bench gate [snapshot {sk}]: {o:.2f} -> {n:.2f} ms "
                  f"({rel:+.2%}) {stat}")
            if stat == "REGRESSION":
                rc = 1

    # overload inter-token-latency gate (chunked prefill interleaving):
    # the adversarial mix's resident-stream p99 ITL under the long-prompt
    # disturbance, lower-is-better at the SLO threshold like the other
    # tail-latency walls.  Both the chunked and the atomic sides gate —
    # the chunked number is the product, the atomic one the control (a
    # regression there means the workload drifted, not the interleaver).
    # Sides missing the section (pre-chunking rounds) skip silently.
    old_ov, new_ov = load_overload(args.old), load_overload(args.new)
    if old_ov and new_ov:
        for ok in ("itl_p99_ms_chunked", "itl_p99_ms_atomic"):
            try:
                o, n = float(old_ov.get(ok, 0)), float(new_ov.get(ok, 0))
            except (TypeError, ValueError):
                continue
            if not o > 0 or not n > 0:
                continue
            rel = (n - o) / o
            stat = "REGRESSION" if rel > args.slo_threshold else "ok"
            print(f"bench gate [overload {ok}]: {o:.2f} -> {n:.2f} ms "
                  f"({rel:+.2%}) {stat}")
            if stat == "REGRESSION":
                rc = 1

    # fail-over latency gate (serving cluster): SIGKILL-to-detection and
    # SIGKILL-to-recovery walls, lower-is-better at the SLO threshold
    # (single-shot process-kill timings jitter like tail percentiles).
    # Sides missing the section (pre-cluster rounds) skip silently; a
    # side that lost a request never got here (its rc != 0 already
    # skipped the whole payload as unhealthy).
    old_fo, new_fo = load_failover(args.old), load_failover(args.new)
    if old_fo and new_fo:
        for fk in ("detect_ms", "recover_ms"):
            try:
                o, n = float(old_fo.get(fk, 0)), float(new_fo.get(fk, 0))
            except (TypeError, ValueError):
                continue
            if not o > 0 or not n > 0:
                continue
            rel = (n - o) / o
            stat = "REGRESSION" if rel > args.slo_threshold else "ok"
            print(f"bench gate [failover {fk}]: {o:.1f} -> {n:.1f} ms "
                  f"({rel:+.2%}) {stat}")
            if stat == "REGRESSION":
                rc = 1
        # detect -> first-token per recovery mode (warm-start round):
        # the user-visible outage per path, lower-is-better at the SLO
        # threshold.  Pre-warm-start payloads carry no first_token_ms
        # dict — the sub-gate skips silently for them.
        oft, nft = old_fo.get("first_token_ms"), new_fo.get("first_token_ms")
        if isinstance(oft, dict) and isinstance(nft, dict):
            for mode in sorted(set(oft) & set(nft)):
                try:
                    o, n = float(oft[mode]), float(nft[mode])
                except (TypeError, ValueError):
                    continue
                if not o > 0 or not n > 0:
                    continue
                rel = (n - o) / o
                stat = "REGRESSION" if rel > args.slo_threshold else "ok"
                print(f"bench gate [failover first_token {mode}]: "
                      f"{o:.1f} -> {n:.1f} ms ({rel:+.2%}) {stat}")
                if stat == "REGRESSION":
                    rc = 1

    # transport gate (socket data plane): only when BOTH sides ran the
    # tcp transport.  Pre-transport payloads (no detail.transport) and
    # shm runs skip silently — a silent skip, never a fabricated signal.
    old_tr, new_tr = load_transport(args.old), load_transport(args.new)
    if (old_tr and new_tr
            and old_tr.get("kind") == "tcp" and new_tr.get("kind") == "tcp"):
        try:
            o_rc = int(old_tr.get("reconnects", 0))
            n_rc = int(new_tr.get("reconnects", 0))
        except (TypeError, ValueError):
            o_rc = n_rc = 0
        # reconnects are not jitter: a localhost bench never legitimately
        # drops a connection, so ANY growth is a transport regression
        stat = "REGRESSION" if n_rc > o_rc else "ok"
        print(f"bench gate [transport reconnects]: {o_rc} -> {n_rc} {stat}")
        if stat == "REGRESSION":
            rc = 1
        try:
            o_b = float(old_tr.get("tcp_bytes", 0))
            n_b = float(new_tr.get("tcp_bytes", 0))
        except (TypeError, ValueError):
            o_b = n_b = 0.0
        if o_b > 0 and n_b > 0:
            rel = (n_b - o_b) / o_b
            stat = "REGRESSION" if rel > args.threshold else "ok"
            print(f"bench gate [transport tcp_bytes]: {o_b:.0f} -> "
                  f"{n_b:.0f} ({rel:+.2%}) {stat}")
            if stat == "REGRESSION":
                rc = 1

    # pipeline-schedule gate: per-schedule simulator bubble fraction,
    # LOWER is better (growth means the schedule table regressed — the
    # numbers are deterministic host math, so the regular threshold
    # applies, not the jittery SLO one).  Sides missing the section
    # (pre-schedule rounds) skip silently.
    old_pl, new_pl = load_pipeline(args.old), load_pipeline(args.new)
    if old_pl and new_pl:
        for name in sorted(set(old_pl) & set(new_pl)):
            try:
                o, n = float(old_pl[name]), float(new_pl[name])
            except (TypeError, ValueError):
                continue
            if o <= 0:
                # zero is the BEST bubble (unlike throughput, where 0 is
                # unhealthy): any growth from a true zero-bubble baseline
                # is a regression, never a skip
                stat = "REGRESSION" if n > 1e-9 else "ok"
                print(f"bench gate [pipeline {name}]: bubble {o:.4f} -> "
                      f"{n:.4f} {stat}")
            else:
                rel = (n - o) / o
                stat = "REGRESSION" if rel > args.threshold else "ok"
                print(f"bench gate [pipeline {name}]: bubble {o:.4f} -> "
                      f"{n:.4f} ({rel:+.2%}) {stat}")
            if stat == "REGRESSION":
                rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
