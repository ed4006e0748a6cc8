#!/usr/bin/env python
"""Sharded, crash-isolated tier-1 test runner (ROADMAP item 5).

The tier-1 suite outgrew its budget (>9 min observed) and the in-process
8-device XLA:CPU collectives SIGSEGV intermittently on jax 0.4.37 — a
mid-suite segfault kills the WHOLE pytest process, so real coverage kept
leaking into `slow`.  This runner fixes both mechanically:

- **Sharding**: test FILES are partitioned deterministically (sorted,
  round-robin) into N subprocess shards that run concurrently; total wall
  time divides by the job count instead of paying one serial sweep.
- **Crash isolation**: a shard that dies on a signal fails ALONE — its
  siblings' results stand, and the report names the crashed shard, the
  signal, and the last test it reached.
- **Isolated workers**: the modules known to exercise the in-process
  8-device communicator (the SIGSEGV class) each get a DEDICATED worker
  shard by default, with one automatic retry on signal-death (the crash
  is intermittent infra, not an assertion failure; genuine test failures
  never retry).
- **Shared compile cache**: every shard finds ONE persistent XLA
  compile-cache dir by the rule in paddle_tpu/_core/compile_cache.py
  (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache — applied by
  tests/conftest.py), so repeated model compiles are warm across shards
  and across runs.

Usage:
  python tools/run_tier1.py                 # full tier-1, default shards
  python tools/run_tier1.py --jobs 6        # concurrency
  python tools/run_tier1.py --list          # show the deterministic plan
  python tools/run_tier1.py -k decode       # forwarded pytest -k filter

`run_isolated_test(module, func)` is the in-suite face of the same
mechanism: a tier-1 test whose payload can segfault the process runs it
in a bootstrapped subprocess and retries signal-deaths — used by
tests/test_fleet.py::test_group_sharded_levels (previously slow-marked).
"""

from __future__ import annotations

import argparse
import concurrent.futures
import os
import re
import signal as signal_mod
import subprocess
import sys
import time
from dataclasses import dataclass, field

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Modules that drive the in-process multi-device XLA:CPU communicator
# hard enough to hit the intermittent jax-0.4.37 SIGSEGV/SIGABRT class
# (CHANGES.md PR 2/3 timing notes): each runs in its OWN worker shard so
# a crash never takes sibling results down, and signal-deaths retry once.
# The TP-sharded serving modules dispatch GSPMD-partitioned decode
# programs over 2- and 4-device meshes every test — same crash class,
# same containment.
ISOLATED_DEFAULT = (
    "test_fleet.py",
    "test_dist_passes.py",
    "test_pipeline.py",
    "test_moe.py",
    "test_ring_attention.py",
    "test_multiprocess_collective.py",
    "test_sharded_embedding.py",
    "test_serving_mesh.py",
    "test_serving_mesh_spec.py",
    "test_engine_snapshot_mesh.py",
    # The schedule-search bench smoke test drives a subprocess of its own.
    "test_bench_schedule_search.py",
    # The serving-cluster modules fork real engine/router processes and
    # SIGKILL them mid-protocol (heartbeat fail-over, drain migration,
    # the cluster crash matrix, the fail-over bench) — never in a shared
    # worker, where an orphaned subprocess or a poisoned shm ring could
    # take sibling modules' results down with it.
    "test_serving_cluster.py",
    "test_serving_cluster_crash.py",
    "test_bench_cluster.py",
    # Warm-start tier: forks standby workers, SIGKILLs them mid-warmup,
    # and asserts a respawned worker's persistent-cache hit counters —
    # same fork/SIGKILL crash class, same containment.
    "test_cluster_warm.py",
    # The pipeline-schedule parity suite dispatches GSPMD split-backward
    # pipeline programs (custom-vjp scan pairs with ring ppermutes) over
    # 4- and 8-device in-process meshes every test — the same crash class,
    # the same containment.
    "test_zb_schedules.py",
)

_PYTEST_BASE = ["-q", "--continue-on-collection-errors",
                "-p", "no:cacheprovider", "-p", "no:xdist",
                "-p", "no:randomly"]

_SUMMARY_RE = re.compile(
    r"(\d+) (passed|failed|errors?|skipped|deselected|xfailed|xpassed|"
    r"warnings?)")


@dataclass
class Shard:
    name: str
    files: list
    isolated: bool = False
    # results
    rc: int = None
    counts: dict = field(default_factory=dict)
    duration: float = 0.0
    signal: int = 0
    retries: int = 0
    tail: str = ""

    @property
    def ok(self):
        # 5 = nothing collected (a marker filter can empty a shard)
        return self.rc in (0, 5)

    @property
    def crashed(self):
        return self.rc is not None and self.rc < 0


def partition_files(files, shards):
    """Deterministic round-robin partition of the SORTED file list —
    identical inputs always produce identical shard assignments, so a
    failure reproduces with the same plan on every machine."""
    buckets = [[] for _ in range(max(1, shards))]
    for i, f in enumerate(sorted(files)):
        buckets[i % len(buckets)].append(f)
    return [b for b in buckets if b]


def build_plan(tests_dir, shards, isolated=ISOLATED_DEFAULT):
    """The full deterministic run plan: one dedicated shard per isolated
    module present, plus `shards` round-robin shards over the rest."""
    all_files = sorted(
        f for f in os.listdir(tests_dir)
        if f.startswith("test_") and f.endswith(".py"))
    iso = [f for f in all_files if f in set(isolated)]
    rest = [f for f in all_files if f not in set(isolated)]
    plan = [Shard(name=f"iso:{f[:-3]}",
                  files=[os.path.join(tests_dir, f)], isolated=True)
            for f in iso]
    for i, bucket in enumerate(partition_files(rest, shards)):
        plan.append(Shard(
            name=f"shard{i}",
            files=[os.path.join(tests_dir, f) for f in bucket]))
    return plan


def _parse_counts(output):
    counts = {}
    for line in reversed(output.splitlines()):
        found = _SUMMARY_RE.findall(line)
        if found and any(k in ("passed", "failed", "error", "errors")
                         for _n, k in found):
            for n, key in found:
                counts[key.rstrip("s") if key != "passed" else key] = int(n)
            break
    return counts


def run_shard(shard, marker="not slow", timeout=1800, extra_args=(),
              retry_crashed=1, python=None):
    """Run one shard in a subprocess; fills the Shard's result fields.
    Signal-deaths of ISOLATED shards retry up to retry_crashed times —
    the 8-device communicator crash is intermittent infra, and a retry
    that passes means the tests pass; assertion failures never retry."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    cmd = [python or sys.executable, "-m", "pytest", *shard.files,
           *_PYTEST_BASE, "-m", marker, *extra_args]
    attempts = 1 + (retry_crashed if shard.isolated else 0)
    t0 = time.monotonic()
    for attempt in range(attempts):
        try:
            proc = subprocess.run(
                cmd, cwd=_REPO_ROOT, env=env, timeout=timeout,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
            rc, out = proc.returncode, proc.stdout or ""
        except subprocess.TimeoutExpired as e:
            rc = -signal_mod.SIGKILL
            out = ((e.stdout or b"").decode("utf-8", "replace")
                   if isinstance(e.stdout, bytes) else (e.stdout or ""))
            out += f"\n<run_tier1: shard timed out after {timeout}s>"
        shard.rc = rc
        shard.counts = _parse_counts(out)
        shard.tail = "\n".join(out.splitlines()[-30:])
        if rc < 0:
            shard.signal = -rc
            if attempt + 1 < attempts:
                shard.retries += 1
                continue
        break
    shard.duration = time.monotonic() - t0
    return shard


def _fmt_counts(counts):
    order = ("passed", "failed", "error", "skipped", "deselected")
    parts = [f"{counts[k]} {k}" for k in order if counts.get(k)]
    return ", ".join(parts) if parts else "no summary parsed"


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--tests-dir", default=os.path.join(_REPO_ROOT, "tests"))
    ap.add_argument("--shards", type=int, default=6,
                    help="round-robin shards over the non-isolated files")
    ap.add_argument("--jobs", type=int,
                    default=max(1, min(6, (os.cpu_count() or 2) // 4)),
                    help="concurrent shard subprocesses")
    ap.add_argument("-m", "--marker", default="not slow")
    ap.add_argument("--timeout", type=int, default=1800,
                    help="per-shard wall clock limit (seconds)")
    ap.add_argument("--retry-crashed", type=int, default=1,
                    help="signal-death retries for isolated shards")
    ap.add_argument("--no-isolate", action="store_true",
                    help="disable the dedicated collective-module workers")
    ap.add_argument("--list", action="store_true",
                    help="print the deterministic plan and exit")
    ap.add_argument("pytest_args", nargs="*",
                    help="extra args forwarded to every pytest shard "
                         "(e.g. -k decode)")
    args = ap.parse_args(argv)

    isolated = () if args.no_isolate else ISOLATED_DEFAULT
    plan = build_plan(args.tests_dir, args.shards, isolated=isolated)
    if args.list:
        for shard in plan:
            tag = " [isolated]" if shard.isolated else ""
            print(f"{shard.name}{tag}: "
                  f"{' '.join(os.path.basename(f) for f in shard.files)}")
        return 0

    print(f"run_tier1: {len(plan)} shards "
          f"({sum(s.isolated for s in plan)} isolated), jobs={args.jobs}, "
          f"marker={args.marker!r}")
    t0 = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(args.jobs) as pool:
        futures = [
            pool.submit(run_shard, shard, marker=args.marker,
                        timeout=args.timeout,
                        extra_args=tuple(args.pytest_args),
                        retry_crashed=args.retry_crashed)
            for shard in plan
        ]
        for fut in concurrent.futures.as_completed(futures):
            shard = fut.result()
            status = "ok" if shard.ok else (
                f"CRASHED (signal {shard.signal})" if shard.crashed
                else f"FAILED (rc {shard.rc})")
            retr = f" [retried {shard.retries}x]" if shard.retries else ""
            print(f"  {shard.name:<32} {status:<22} "
                  f"{shard.duration:7.1f}s  {_fmt_counts(shard.counts)}"
                  f"{retr}", flush=True)

    wall = time.monotonic() - t0
    total = {}
    for shard in plan:
        for k, n in shard.counts.items():
            total[k] = total.get(k, 0) + n
    bad = [s for s in plan if not s.ok]
    print(f"\nrun_tier1: {_fmt_counts(total)} across {len(plan)} shards "
          f"in {wall:.1f}s wall")
    for shard in bad:
        print(f"\n--- {shard.name} "
              f"({'signal ' + str(shard.signal) if shard.crashed else 'rc ' + str(shard.rc)}) "
              f"last output ---")
        print(shard.tail)
    if bad:
        print(f"\nrun_tier1: {len(bad)} shard(s) failed "
              f"({sum(s.crashed for s in bad)} crashed) — "
              "siblings' results above are complete")
        return 1
    print("run_tier1: all shards green")
    return 0


# ---------------------------------------------------------------------------
# in-suite crash isolation (tests that exercise the SIGSEGV class)

_WORKER_BOOTSTRAP = """\
import os
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
from paddle_tpu._core import compile_cache
compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
import importlib
getattr(importlib.import_module({module!r}), {func!r})()
"""


def run_isolated_test(module, func, retries=2, timeout=300):
    """Run `module.func()` in a bootstrapped subprocess (8 virtual CPU
    devices, persistent compile cache — the tests/conftest.py environment)
    and raise AssertionError on failure.  A signal-death retries up to
    `retries` times: the in-process 8-device communicator crash is
    intermittent infra, while an assertion failure (rc > 0) fails
    immediately.  This is how a SIGSEGV-prone payload runs INSIDE tier-1
    without being able to kill the suite process."""
    code = _WORKER_BOOTSTRAP.format(module=module, func=func)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    last_rc, last_out = None, ""
    for attempt in range(1 + retries):
        try:
            proc = subprocess.run(
                [sys.executable, "-c", code], cwd=_REPO_ROOT, env=env,
                timeout=timeout, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)
            last_rc, last_out = proc.returncode, proc.stdout or ""
        except subprocess.TimeoutExpired as e:
            # a hung worker is the DEADLOCK half of the crash class this
            # mechanism contains: retryable, like a signal-death
            out = e.stdout or b""
            last_out = (out.decode("utf-8", "replace")
                        if isinstance(out, bytes) else out)
            last_out += f"\n<worker timed out after {timeout}s>"
            last_rc = -signal_mod.SIGKILL
        if last_rc == 0:
            return attempt
        if last_rc > 0:  # genuine failure: never retry
            break
    tail = "\n".join(last_out.splitlines()[-25:])
    kind = (f"signal {-last_rc}" if last_rc < 0 else f"rc {last_rc}")
    raise AssertionError(
        f"isolated worker {module}.{func} failed ({kind}) after "
        f"{attempt + 1} attempt(s):\n{tail}")


if __name__ == "__main__":
    sys.exit(main())
