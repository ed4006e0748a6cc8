"""bench.py must stay runnable — a syntax/import/harness regression here
forfeits a round's measurement.  Its refusal to measure without an
accelerator is tested (fast) in tests/test_chip_smoke.py."""

import json
import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_bench_smoke_flag_asserts_payload_fields():
    """`bench.py --smoke` is the CPU twin of the on-chip payload: it must
    emit the full payload (per-config mfu + the simulator pipeline
    section) and self-assert the field contract (BENCH_SMOKE_OK)."""
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"), "--smoke"],
        capture_output=True, text=True, timeout=840)
    assert out.returncode == 0, out.stderr[-500:]
    assert "BENCH_SMOKE_OK" in out.stdout
    line = next(ln for ln in reversed(out.stdout.splitlines())
                if ln.startswith("{"))
    payload = json.loads(line)
    # a CPU run, labelled as one: never a device metric
    assert payload["platform"] == "cpu" and payload["mfu"] is None
    assert payload["metric"] == "cpu_smoke_tokens_per_sec"
    assert payload["configs"] and all("mfu" in c for c in payload["configs"])
    sch = payload["detail"]["pipeline"]["schedules"]
    assert sch["ZB-H1"] < sch["1F1B"]
