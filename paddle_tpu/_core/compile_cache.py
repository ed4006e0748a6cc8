"""Persistent XLA compilation cache + compile telemetry.

Cold starts dominate time-to-first-step for deep models: every process pays
trace + XLA compile for the train/serve step from scratch.  JAX ships a
persistent on-disk compilation cache (the TVM paper's persistent tuning-log
idea applied to whole executables); this module wires it behind
``FLAGS_compilation_cache_dir`` so a warm start deserializes yesterday's
executable instead of recompiling, and taps ``jax.monitoring`` for
trace-time / compile-time / cache-hit counters that
``paddle_tpu.profiler.compile_stats()`` surfaces next to the PR-1 eager
dispatch-cache stats.

Set the flag via env (``FLAGS_compilation_cache_dir=/path``) before import,
or at runtime with ``paddle.set_flags({"FLAGS_compilation_cache_dir":
"/path"})`` — the flags listener applies it immediately.  Pair with
``jit.TrainStep.warmup(sample_batch)`` to pay the (first-run) compile before
traffic.

THE COMPILE LEDGER (``compile_stats()``).  jax times every program it builds
through four stages and says so through ``jax.monitoring``; the listeners
here keep all of it, by program:

- ``traces`` / ``trace_seconds``: ``jaxpr_trace_duration``, the Python of the
  jitted function run once over tracers.  A jitted helper called while a
  program is traced fires its own event INSIDE the program's, so this sum
  counts those seconds in both (``nested_seconds`` says how many).
- ``lowerings`` / ``lower_seconds``: ``jaxpr_to_mlir_module_duration``, the
  jaxpr lowered to an MLIR module; a Pallas kernel's Mosaic lowering runs
  inside it.  No cache holds a lowering: a warm start pays it again.
- ``compiles`` / ``compile_seconds``: ``backend_compile_duration``, which
  ENCLOSES the persistent cache's read.  Split by how it ended:
  ``cache_read_seconds`` (jax's own ``cache_retrieval_time_sec``: the entry
  read from disk, deserialised and loaded, hits only) and
  ``compile_miss_seconds`` (the ``backend_compile`` durations in which no
  hit was seen, a miss or no cache in use: XLA really compiled).
- ``persistent_cache_hits`` / ``_misses``: the cache's own events.
- ``nested_seconds``: seconds of the three ``*_seconds`` sums above spent in
  an event that ran inside another (a helper traced inside a program's trace,
  an eager op compiled while a program is traced), so that
  ``trace_seconds + lower_seconds - nested_seconds + compile_miss_seconds +
  cache_read_seconds`` is time that passed once (``ledger_wall_seconds``).
- ``by_program``: ``name -> {traces, trace_seconds, lowerings,
  lower_seconds, compiles, compile_seconds, cache_hits, cache_misses,
  cache_read_seconds, first_seen_s}`` (``first_seen_s``: seconds after this
  module was imported, among the first lines of ``import paddle_tpu``, once
  jax is in).  jax names one program three ways:
  ``decode_macro_step`` on the trace event, ``jit(decode_macro_step)`` on
  lowering and compile, ``jit_decode_macro_step`` on a device trace's XLA
  Modules line.  ONE form is kept, the device trace's.  The hit, miss and
  retrieval events carry no name and fire inside ``backend_compile``: they
  go to the program whose ``backend_compile`` closes next.  At most
  ``MAX_PROGRAM_ROWS`` rows; later names are summed under ``(other)``
  (a name handed to ``keep_rows`` always keeps a row of its own).

``paddle_tpu.profiler.compile_stats()`` adds the ``framework_*`` sums over
``profiler.PROGRAM_NAMES``; this module knows no program of a higher layer.

WHERE the cache lives follows one rule, for every process of this repo
(tests, benches, tools, cluster workers, chip_smoke.py): if
``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it and code sets
NO directory; otherwise ``enable()`` uses the flag's value and, failing
that, the fixed ``<checkout>/.jax_cache``.  Fixed, because the directory is
part of jax's cache key: a cache that moves never hits.
"""

from __future__ import annotations

import os
import threading
import time

from . import flags

__all__ = ["configure", "enable", "default_dir", "compile_stats",
           "reset_compile_stats", "count", "ledger_wall_seconds", "lifetime",
           "keep_rows", "MAX_PROGRAM_ROWS"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_lock = threading.Lock()
_listeners_installed = False
_configured_dir: str | None = None

MAX_PROGRAM_ROWS = 128
_OTHER = "(other)"
_kept_rows: set = set()         # names the cap never folds (`keep_rows`)
_BEGAN = time.perf_counter()    # `first_seen_s` counts from here

# populated by jax.monitoring listeners (see _install_listeners)
_stats = {
    "traces": 0,
    "trace_seconds": 0.0,
    "lowerings": 0,
    "lower_seconds": 0.0,
    "compiles": 0,
    "compile_seconds": 0.0,
    "compile_miss_seconds": 0.0,
    "cache_read_seconds": 0.0,
    "nested_seconds": 0.0,
    "persistent_cache_hits": 0,
    "persistent_cache_misses": 0,
    # counted by the code being traced (`count`): which operand type the
    # flash kernels' matrix products were traced with (ops/flash_attention)
    "flash_bf16_operand_traces": 0,
    "flash_f32_operand_traces": 0,
    # ... and which path decode attention over a paged pool was traced
    # through: the Pallas kernel or the XLA form (ops/paged_attention)
    "paged_kernel_traces": 0,
    "paged_xla_traces": 0,
}
_by_program: dict = {}
# what `ledger_wall_seconds` adds up; `reset_compile_stats` carries it over,
# so that start-up's account (profiler.startup_stats) never runs backwards
_WALL_KEYS = ("trace_seconds", "lower_seconds", "nested_seconds",
              "compile_miss_seconds", "cache_read_seconds")
_carried = dict.fromkeys(_WALL_KEYS, 0.0)

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# a stage's event -> its count and its seconds, in _stats and in a row
_STAGES = {_TRACE_EVENT: ("traces", "trace_seconds"),
           _LOWER_EVENT: ("lowerings", "lower_seconds"),
           _COMPILE_EVENT: ("compiles", "compile_seconds")}


class _Open(threading.local):
    """This thread's stage events still open (how many), and the cache's
    nameless events seen since the last `backend_compile` closed."""

    def __init__(self):
        self.depth = 0
        self.hits = self.misses = 0
        self.read = 0.0


_open = _Open()


def _program_name(fun_name) -> str:
    """The ONE form a program's name is kept in: the device trace's,
    `jit_decode_macro_step` (module docstring)."""
    if not fun_name:
        return "(unnamed)"
    api, paren, rest = fun_name.partition("(")
    if paren and rest.endswith(")"):       # jit(decode_macro_step)
        return api + "_" + rest[:-1]
    return "jit_" + fun_name               # decode_macro_step


def _row(name: str) -> dict:
    row = _by_program.get(name)
    if row is None:
        if len(_by_program) >= MAX_PROGRAM_ROWS and name not in _kept_rows:
            name = _OTHER
            row = _by_program.get(name)
            if row is not None:
                return row
        row = _by_program[name] = {
            "traces": 0, "trace_seconds": 0.0, "lowerings": 0,
            "lower_seconds": 0.0, "compiles": 0, "compile_seconds": 0.0,
            "cache_hits": 0, "cache_misses": 0, "cache_read_seconds": 0.0,
            "first_seen_s": time.perf_counter() - _BEGAN}
    return row


def _on_event(event: str, **kw):
    if event == _HIT_EVENT:
        _stats["persistent_cache_hits"] += 1
        _open.hits += 1
    elif event == _MISS_EVENT:
        _stats["persistent_cache_misses"] += 1
        _open.misses += 1


def _on_scalar(event: str, value, **kw):
    # jax records a stage's start time as a scalar when the stage OPENS
    # (tests/test_compile_ledger.py holds that this jax does: without it
    # nothing would read as nested)
    if event in _STAGES:
        _open.depth += 1


def _on_duration(event: str, duration: float, fun_name=None, **kw):
    stage = _STAGES.get(event)
    if stage is None:
        if event == _READ_EVENT:
            _stats["cache_read_seconds"] += duration
            _open.read += duration
        return
    o = _open
    if o.depth:
        o.depth -= 1
    if o.depth:                 # it ran inside another stage's event
        _stats["nested_seconds"] += duration
    n, secs = stage
    row = _row(_program_name(fun_name))
    _stats[n] += 1
    _stats[secs] += duration
    row[n] += 1
    row[secs] += duration
    if event == _COMPILE_EVENT:
        row["cache_hits"] += o.hits
        row["cache_misses"] += o.misses
        row["cache_read_seconds"] += o.read
        if not o.hits:          # a miss, or no cache in use: XLA compiled
            _stats["compile_miss_seconds"] += duration
        o.hits = o.misses = 0
        o.read = 0.0


def keep_rows(names):
    """Names whose rows `MAX_PROGRAM_ROWS` never folds into `(other)`: a
    higher layer registers the programs it reads by name."""
    _kept_rows.update(names)


def count(name: str):
    """One more of a trace-time event that the traced code counts itself."""
    _stats[name] += 1


def _install_listeners():
    global _listeners_installed
    with _lock:
        if _listeners_installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_scalar_listener(_on_scalar)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listeners_installed = True


def default_dir() -> str:
    """``<checkout>/.jax_cache`` (listed in .gitignore)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on by the one rule (module docstring) and
    return the directory in use."""
    return configure(str(flags.flag("FLAGS_compilation_cache_dir") or "")
                     or default_dir())


def configure(cache_dir: str | None = None):
    """Point jax's persistent compilation cache at ``cache_dir`` (default:
    the FLAGS_compilation_cache_dir value; empty disables).  Safe to call
    repeatedly; re-pointing resets jax's in-memory view of the cache.
    Where JAX_COMPILATION_CACHE_DIR is set, that directory stands and none
    is set here, whatever ``cache_dir`` says."""
    global _configured_dir
    _install_listeners()
    import jax

    env_dir = os.environ.get(ENV_VAR)
    if cache_dir is None:
        cache_dir = str(flags.flag("FLAGS_compilation_cache_dir") or "")
    cache_dir = env_dir or cache_dir or None
    if cache_dir == _configured_dir:
        return cache_dir
    if not env_dir:
        from jax.experimental.compilation_cache import compilation_cache as cc

        # drop the once-per-task "is the cache in use" decision so a dir set
        # AFTER the first compile still takes effect
        cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    if cache_dir is not None:
        jax.config.update("jax_enable_compilation_cache", True)
        # default min-compile-time gate (1s) would skip exactly the small
        # steps CI and CPU smoke runs compile; persist everything
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _configured_dir = cache_dir
    return cache_dir


def compile_stats() -> dict:
    """The compile ledger of this process (module docstring; monotonic, see
    reset_compile_stats).  `cache_dir` is the active persistent cache
    directory or None; `by_program` a copy of the table."""
    _install_listeners()
    out = dict(_stats)
    out["by_program"] = {n: dict(r) for n, r in _by_program.items()}
    out["cache_dir"] = _configured_dir
    return out


def ledger_wall_seconds() -> float:
    """Seconds this process has spent tracing, lowering, compiling (misses)
    and reading the persistent cache, each second once, whatever was reset."""
    s = {k: _carried[k] + _stats[k] for k in _WALL_KEYS}
    return (s["trace_seconds"] + s["lower_seconds"] - s["nested_seconds"]
            + s["compile_miss_seconds"] + s["cache_read_seconds"])


def lifetime(key: str) -> float:
    """One of the ledger's `*_seconds` sums since the process began,
    whatever was reset."""
    return _carried[key] + _stats[key]


def reset_compile_stats():
    for k in _WALL_KEYS:
        _carried[k] += _stats[k]
    for k in _stats:
        _stats[k] = 0 if isinstance(_stats[k], int) else 0.0
    _by_program.clear()


@flags.on_change
def _on_flags_change(changed):
    if "FLAGS_compilation_cache_dir" in changed:
        configure()


# Env-var / default wiring at import: a dir set via FLAGS_compilation_cache_dir
# in the environment engages the cache before any compile happens.
if flags.flag("FLAGS_compilation_cache_dir"):
    configure()
else:
    _install_listeners()
