"""Start-up seen from inside: where a process's seconds went before it was
ready, told by the program and not by its caller's stopwatch.

`phase(name, counter)` is the one tool: a `RecordEvent` span and, at the same
boundary, host seconds added to `startup_stats()[counter]`.  It marks
construction and first-use paths only (`GenerationEngine.__init__`, the build
call of a `TrainStep`, the FIRST call of a program the framework built); in
steady state none of these sites runs.  `startup_stats()` adds the package's
import and the compile ledger's four sums (`_core/compile_cache.py`), so that
one dict accounts for the process.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time

from paddle_tpu._core import compile_cache
from paddle_tpu.profiler import RecordEvent

__all__ = ["phase", "first_use", "startup_stats"]

_seconds = dict.fromkeys((
    "engine_build_seconds", "engine_pool_alloc_seconds",
    "engine_state_alloc_seconds", "train_build_seconds",
    "train_optimizer_state_seconds", "train_build_trace_seconds",
    "program_first_use_seconds"), 0.0)
_counts = {"programs_first_used": 0}
# the import, then the phases that no other phase encloses: their seconds,
# and the compile ledger's seconds that passed inside them
_outer = {"seconds": 0.0, "ledger": 0.0}
_import = {"began": time.perf_counter(), "seconds": 0.0, "jax": 0.0,
           "before": 0.0}
_nesting = threading.local()


def _process_age() -> float | None:
    """Seconds since this process started, by /proc (None where there is
    none): its start in clock ticks after boot against the uptime."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def imported(began: float, jax_done: float, done: float):
    """`paddle_tpu/__init__.py`'s three clock readings: its first line, after
    `import jax`, its last line."""
    age = _process_age()
    _import.update(began=began, seconds=done - began, jax=jax_done - began,
                   before=0.0 if age is None else max(0.0, age - (done - began)))
    _outer["seconds"] += done - began
    _outer["ledger"] += compile_cache.ledger_wall_seconds()


@contextlib.contextmanager
def phase(name: str, counter: str, **args):
    """One piece of start-up: the span `name` (a `profiler.SPAN_NAMES` entry)
    and its host seconds in `startup_stats()[counter]`.  Also a decorator."""
    depth = getattr(_nesting, "depth", 0)
    _nesting.depth = depth + 1
    ledger0 = compile_cache.ledger_wall_seconds() if not depth else 0.0
    t0 = time.perf_counter()
    try:
        with RecordEvent(name, **args):
            yield
    finally:
        dt = time.perf_counter() - t0
        _nesting.depth = depth
        _seconds[counter] += dt
        if not depth:
            _outer["seconds"] += dt
            _outer["ledger"] += compile_cache.ledger_wall_seconds() - ledger0


@contextlib.contextmanager
def first_use(program: str, key):
    """The FIRST use of a program the framework built: `program` as
    `profiler.PROGRAM_NAMES` has it (the speculative pair, which one tick
    builds, as `jit_draft_step+jit_verify_step`), `key` what selects this
    build of it ((s_pad, m_len), the chunk, the batch's shapes).  A pour
    shape has no span: a pour runs on every admission, and
    `compile_stats()["by_program"]["jit__pour_new_blocks"]` already times
    each shape it built.  The caller waits for the
    result inside, so the span holds trace + lowering + compile or cache read
    + transfer + first run; inside a traced window it lies on the device
    trace's clock, and the idle gap around it can be put down to it.
    Entered INLINE where the program is called, never through a wrapping
    function: a frame more on the stack while Mosaic lowers a kernel can
    cost seconds (PERF.md section 6, PRs 25 and 36)."""
    with phase("program.first_use", "program_first_use_seconds",
               program=program, key=str(key)):
        yield
    _counts["programs_first_used"] += 1


def startup_stats() -> dict:
    """Where this process's seconds went since `import paddle_tpu` began
    (monotonic, process-wide: snapshot it when the replica reports ready).

    `before_import_seconds`: from the process's start to that import's first
    line (the interpreter, the caller's own imports, jax among them if it
    came first, and whatever the caller did first: bringing up a TPU backend
    with `jax.devices()` takes 9-12 s on a v5e host); the program can name
    nothing in it, so it is no part of `accounted_seconds`; 0.0 where /proc
    is absent.  `elapsed_seconds` since that import began.  The parts:
    `import_seconds` (`import_jax_seconds` of it jax: 0 if the caller had
    imported jax);
    `engine_build_seconds` (`GenerationEngine.__init__`) with
    `engine_pool_alloc_seconds` (the paged classes' pools) and
    `engine_state_alloc_seconds` (what a slot owns for good: a window class's
    rings, a state class's rows, the ring and scratch tables), each waited
    for; `train_build_seconds` (the call that builds a `TrainStep`) with
    `train_optimizer_state_seconds` and `train_build_trace_seconds` (the
    first call of the jitted step until it returns); `program_first_use_
    seconds` over `programs_first_used` (`first_use`; a train step's lies
    inside its build).  The compile ledger's sums for the WHOLE process, most
    of them inside the parts above: `trace_seconds`, `lower_seconds`,
    `compile_miss_seconds`, `cache_read_seconds` (`compile_stats()`, never
    reset here) and `compile_outside_seconds`, the ledger's seconds outside
    every part above (eager ops of the caller's model construction, a
    reference's pieces).  `accounted_seconds` = import + the parts that no
    other part encloses + `compile_outside_seconds`; `unaccounted_seconds` =
    elapsed - accounted: what the program cannot name (the caller's model
    construction and waits beyond their compiles, the reference)."""
    out = {"before_import_seconds": _import["before"],
           "elapsed_seconds": time.perf_counter() - _import["began"],
           "import_seconds": _import["seconds"],
           "import_jax_seconds": _import["jax"], **_seconds, **_counts}
    for k in ("trace_seconds", "lower_seconds", "compile_miss_seconds",
              "cache_read_seconds"):
        out[k] = compile_cache.lifetime(k)
    out["compile_outside_seconds"] = (compile_cache.ledger_wall_seconds()
                                      - _outer["ledger"])
    out["accounted_seconds"] = (_outer["seconds"]
                                + out["compile_outside_seconds"])
    out["unaccounted_seconds"] = (out["elapsed_seconds"]
                                  - out["accounted_seconds"])
    return out
