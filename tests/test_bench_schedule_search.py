"""Tier-1 smoke of benchmarks/bench_schedule_search.py + regression-gate
wiring.

The --smoke twin must keep emitting the one-line JSON payload the driver
parses, with the deterministic decision set intact: the matmul chain's
searched schedule accepted with a >1x recorded win, the K-tiled twin
accepted through a genuinely contraction-split config (phase 2), the
softmax chain's schedule disabled by the measured-win gate, the
disabled entry never re-measured on a cold reload, and the fused paths
matching XLA-only numerics.  Plus: the payload must flow through
tools/check_bench_regression.py (the CI bench gate).
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke():
    env = dict(os.environ, PADDLE_TPU_BENCH_SMOKE="1", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "bench_schedule_search.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert out.returncode == 0, (out.stderr or out.stdout)[-800:]
    line = next(ln for ln in reversed(out.stdout.splitlines())
                if ln.startswith("{"))
    return json.loads(line)


def test_bench_schedule_search_smoke_decisions():
    payload = _run_smoke()
    assert payload["metric"] == "schedule_search_measured_win"
    assert payload["unit"] == "x"
    assert payload["value"] > 1.0  # best accepted schedule's recorded win
    assert payload["numerics_identical"] is True
    detail = payload["detail"]
    # the gate accepted a known-good tiling...
    mm = detail["matmul_chain"]
    assert mm["substituted"] == 1 and mm["fused_op"] == "sched_chain_4"
    assert mm["cache_entry"]["meta"]["win"] > 1.0
    assert "block_rows" in mm["cache_entry"]["config"]
    # ...the large-K twin only through a genuinely K-tiled schedule...
    kt = detail["ktiled_matmul"]
    assert kt["substituted"] == 1 and kt["fused_op"] == "sched_chain_3"
    assert 0 < kt["cache_entry"]["config"]["block_k"] < 256
    assert kt["cache_entry"]["meta"]["win"] > 1.0
    # ...and disabled the deliberately-bad one, persistently
    sm = detail["softmax_chain"]
    assert sm["substituted"] == 0
    assert sm["cache_entry"]["config"] == {"disabled": True}
    assert detail["disabled_persisted"] is True
    assert detail["never_refired"] is True
    counters = detail["counters"]
    assert counters["accepted"] == 2 and counters["disabled"] == 1
    assert counters["measured"] > 0 and counters["disabled_hits"] >= 1


def test_bench_payload_flows_through_regression_gate(tmp_path):
    """tools/check_bench_regression.py must parse the new bench JSON: same
    value -> ok (rc 0); a big drop -> REGRESSION (rc 1)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import check_bench_regression as gate
    finally:
        sys.path.pop(0)

    payload = {"metric": "schedule_search_measured_win", "value": 2.5,
               "unit": "x"}
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(payload))
    new.write_text(json.dumps(payload))
    assert gate.main([str(old), str(new)]) == 0
    new.write_text(json.dumps(dict(payload, value=1.0)))
    assert gate.main([str(old), str(new)]) == 1
    # an all-disabled run (value 0 — honest loss, e.g. CPU interpret mode)
    # is never counted as a regression
    new.write_text(json.dumps(dict(payload, value=0.0)))
    assert gate.main([str(old), str(new)]) == 0
