"""Wiring tests for the sharded, crash-isolated tier-1 runner
(tools/run_tier1.py, ROADMAP item 5) — the check_bench_regression.py
pattern: the TOOLING is tested mechanically, not trusted.

Covered: deterministic shard partitioning, the isolated-worker routing of
the known 8-device collective suites, a crash in one shard reported
WITHOUT killing siblings, signal-death retry semantics (isolated shards
retry intermittent crashes; genuine failures never retry), and the
shared-compile-cache env plumbing.  The fake shard payloads import no jax
— each subprocess is milliseconds of pytest, so the whole file stays
cheap."""

import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from tools.run_tier1 import (
    ISOLATED_DEFAULT,
    Shard,
    build_plan,
    partition_files,
    run_shard,
    run_isolated_test,
)

_REPO_TESTS = os.path.dirname(os.path.abspath(__file__))


# ------------------------------------------------------------ partitioning
def test_partition_deterministic_and_covering():
    files = [f"test_{c}.py" for c in "gecafdb"]
    a = partition_files(files, 3)
    b = partition_files(list(reversed(files)), 3)
    assert a == b  # input order never changes the plan
    flat = [f for bucket in a for f in bucket]
    assert sorted(flat) == sorted(files)  # every file exactly once
    # round-robin over the SORTED list
    assert a[0] == ["test_a.py", "test_d.py", "test_g.py"]
    assert a[1] == ["test_b.py", "test_e.py"]
    assert a[2] == ["test_c.py", "test_f.py"]


def test_build_plan_isolates_collective_modules():
    plan = build_plan(_REPO_TESTS, shards=4)
    iso = [s for s in plan if s.isolated]
    rest = [s for s in plan if not s.isolated]
    # every present isolated module got a DEDICATED single-file worker
    iso_names = {os.path.basename(s.files[0]) for s in iso}
    present = {f for f in ISOLATED_DEFAULT
               if os.path.exists(os.path.join(_REPO_TESTS, f))}
    assert iso_names == present
    assert all(len(s.files) == 1 for s in iso)
    # and no isolated module leaked into a round-robin shard
    rest_files = {os.path.basename(f) for s in rest for f in s.files}
    assert not (rest_files & present)
    # identical call, identical plan
    plan2 = build_plan(_REPO_TESTS, shards=4)
    assert [(s.name, s.files) for s in plan] == \
        [(s.name, s.files) for s in plan2]
    # the multi-tenant LoRA modules ride ordinary round-robin shards —
    # no 8-device collectives, so no dedicated isolated worker
    for mod in ("test_lora.py", "test_serving_lora.py",
                "test_bench_lora.py"):
        assert mod in rest_files, mod
    # the TP-sharded serving modules dispatch GSPMD decode programs over
    # the in-process multi-device communicator every test: DEDICATED
    # isolated workers, never round-robin (and never slow-marked).  The
    # snapshot topology-migration module restores engines ONTO meshes —
    # same crash class, same containment.
    for mod in ("test_serving_mesh.py", "test_serving_mesh_spec.py",
                "test_engine_snapshot_mesh.py"):
        assert mod in iso_names, mod
    # the engine-snapshot core + subprocess SIGKILL-matrix modules are
    # single-device (kills land in SUBPROCESS serving loops): ordinary
    # round-robin shards
    for mod in ("test_engine_snapshot.py", "test_engine_snapshot_crash.py"):
        assert mod in rest_files, mod
    # the serving-CLUSTER modules fork and SIGKILL real router/replica
    # processes (heartbeat fail-over, drain migration, the cluster crash
    # matrix, the fail-over bench): DEDICATED isolated workers, never
    # round-robin, never slow-marked
    for mod in ("test_serving_cluster.py", "test_serving_cluster_crash.py",
                "test_bench_cluster.py"):
        assert mod in iso_names, mod
    # the warm-start module forks standby workers and SIGKILLs them
    # mid-warmup — same fork/SIGKILL crash class, same dedicated worker
    assert "test_cluster_warm.py" in iso_names
    # the pipeline-schedule parity suite dispatches split-backward GSPMD
    # pipeline programs over 4/8-device in-process meshes every test: a
    # DEDICATED isolated worker, never round-robin, never slow-marked
    assert "test_zb_schedules.py" in iso_names
    # while the bench-gate and simulator-only tests stay round-robin
    assert "test_bench_gate.py" in rest_files
    # the protocol-lint suite is pure abstraction (model checker + AST
    # pass — no fork, no ring, no device): ordinary round-robin shard
    assert "test_protocol_lint.py" in rest_files


# -------------------------------------------------------- crash isolation
def _write(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


def test_crash_in_one_shard_reported_siblings_complete(tmp_path):
    crash = _write(tmp_path, "test_crash.py", """\
        import os, signal

        def test_boom():
            os.kill(os.getpid(), signal.SIGSEGV)
        """)
    good = _write(tmp_path, "test_good.py", """\
        def test_fine():
            assert 1 + 1 == 2

        def test_also_fine():
            assert True
        """)
    s_crash = Shard(name="crashy", files=[crash])
    s_good = Shard(name="goody", files=[good])
    run_shard(s_crash, timeout=120)
    run_shard(s_good, timeout=120)
    # the crash is contained and NAMED...
    assert s_crash.crashed and s_crash.signal == signal.SIGSEGV
    assert not s_crash.ok
    # ...and the sibling's results are complete, not collateral damage
    assert s_good.ok and s_good.counts.get("passed") == 2
    assert s_good.retries == 0


def test_plain_failure_parsed_not_crash(tmp_path):
    mixed = _write(tmp_path, "test_mixed.py", """\
        def test_ok():
            assert True

        def test_bad():
            assert False, "genuine failure"
        """)
    shard = Shard(name="mixed", files=[mixed])
    run_shard(shard, timeout=120)
    assert shard.rc == 1 and not shard.crashed
    assert shard.counts.get("passed") == 1
    assert shard.counts.get("failed") == 1


def test_isolated_shard_retries_intermittent_crash(tmp_path):
    # crash on the FIRST run (no sentinel), pass on the retry — the
    # intermittent 8-device communicator shape
    flaky = _write(tmp_path, "test_flaky.py", f"""\
        import os, signal

        _SENTINEL = {str(tmp_path / "ran_once")!r}

        def test_flaky_crash():
            if not os.path.exists(_SENTINEL):
                open(_SENTINEL, "w").close()
                os.kill(os.getpid(), signal.SIGSEGV)
            assert True
        """)
    shard = Shard(name="iso:flaky", files=[flaky], isolated=True)
    run_shard(shard, timeout=120,
              retry_crashed=1)
    assert shard.ok and shard.retries == 1
    assert shard.counts.get("passed") == 1

    # a NON-isolated shard never retries: crash-class containment is for
    # the known communicator modules, not a blanket flake-hider
    os.remove(str(tmp_path / "ran_once"))
    shard2 = Shard(name="flaky2", files=[flaky], isolated=False)
    run_shard(shard2, timeout=120,
              retry_crashed=1)
    assert shard2.crashed and shard2.retries == 0


def test_always_crashing_isolated_shard_exhausts_retries(tmp_path):
    hard = _write(tmp_path, "test_hard_crash.py", """\
        import os, signal

        def test_always_crashes():
            os.kill(os.getpid(), signal.SIGKILL)
        """)
    shard = Shard(name="iso:hard", files=[hard], isolated=True)
    run_shard(shard, timeout=120,
              retry_crashed=1)
    assert shard.crashed and shard.signal == signal.SIGKILL
    assert shard.retries == 1  # retried once, then reported honestly


def test_shards_find_the_cache_by_the_one_rule(tmp_path, monkeypatch):
    """A shard is told no cache directory: it inherits
    JAX_COMPILATION_CACHE_DIR where the parent has it, and the retired
    test-cache variable is never exported."""
    probe = _write(tmp_path, "test_probe_env.py", """\
        import os

        def test_cache_env():
            assert "PADDLE_TPU_TEST_CACHE_DIR" not in os.environ
            assert os.environ["JAX_COMPILATION_CACHE_DIR"] == \\
                os.environ["_EXPECTED_CACHE"]
        """)
    cache = str(tmp_path / "shared_cache")
    monkeypatch.setenv("_EXPECTED_CACHE", cache)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    monkeypatch.delenv("PADDLE_TPU_TEST_CACHE_DIR", raising=False)
    shard = Shard(name="env", files=[probe])
    run_shard(shard, timeout=120)
    assert shard.ok and shard.counts.get("passed") == 1


# -------------------------------------------- in-suite isolation helper
def _ri_failing_payload():
    raise AssertionError("deliberate payload failure")


def _ri_hanging_payload():
    # parks far past any test timeout: every attempt times out no matter
    # how fast the worker bootstrap runs (a warm jax import can finish
    # inside 1s, so "the import eats the budget" is NOT deterministic)
    time.sleep(600)


def test_run_isolated_test_genuine_failure_no_retry():
    """rc > 0 (an assertion failure in the worker) fails IMMEDIATELY with
    the worker's tail in the message — retries are only for signal-deaths
    (the un-slow-marked test_fleet suite relies on exactly this split)."""
    with pytest.raises(AssertionError) as ei:
        run_isolated_test("tests.test_run_tier1", "_ri_failing_payload",
                          retries=3, timeout=180)
    msg = str(ei.value)
    assert "rc 1" in msg
    assert "1 attempt(s)" in msg  # never retried
    assert "deliberate payload failure" in msg


def test_run_isolated_test_timeout_retries_like_signal_death():
    """A HUNG worker is the deadlock half of the crash class this
    mechanism contains: TimeoutExpired must consume retries and surface
    as a signal-style failure, not escape as a raw exception."""
    with pytest.raises(AssertionError) as ei:
        # the payload parks forever, so every attempt times out
        # deterministically — regardless of how fast the worker
        # bootstrap (jax import) happens to be on a warm cache
        run_isolated_test("tests.test_run_tier1", "_ri_hanging_payload",
                          retries=1, timeout=1)
    msg = str(ei.value)
    assert "signal" in msg
    assert "2 attempt(s)" in msg  # retried once, then reported
    assert "timed out after 1s" in msg


def test_runner_entry_list_mode():
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(_REPO_TESTS),
                                      "tools", "run_tier1.py"), "--list"],
        stdout=subprocess.PIPE, text=True, timeout=60)
    assert out.returncode == 0
    assert "iso:test_fleet [isolated]" in out.stdout
    assert "shard0" in out.stdout
