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

from . import flags

__all__ = ["configure", "enable", "default_dir", "compile_stats",
           "reset_compile_stats", "count"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_lock = threading.Lock()
_listeners_installed = False
_configured_dir: str | None = None

# populated by jax.monitoring listeners (see _install_listeners)
_stats = {
    "traces": 0,
    "trace_seconds": 0.0,
    "compiles": 0,
    "compile_seconds": 0.0,
    "persistent_cache_hits": 0,
    "persistent_cache_misses": 0,
    "compile_seconds_saved": 0.0,
    # counted by the code being traced (`count`): which operand type the
    # flash kernels' matrix products were traced with (ops/flash_attention)
    "flash_bf16_operand_traces": 0,
    "flash_f32_operand_traces": 0,
    # ... and which path decode attention over a paged pool was traced
    # through: the Pallas kernel or the XLA form (ops/paged_attention)
    "paged_kernel_traces": 0,
    "paged_xla_traces": 0,
}

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"


def _on_event(event: str, **kw):
    if event == _HIT_EVENT:
        _stats["persistent_cache_hits"] += 1
    elif event == _MISS_EVENT:
        _stats["persistent_cache_misses"] += 1


def _on_duration(event: str, duration: float, **kw):
    if event == _TRACE_EVENT:
        _stats["traces"] += 1
        _stats["trace_seconds"] += duration
    elif event == _COMPILE_EVENT:
        _stats["compiles"] += 1
        _stats["compile_seconds"] += duration
    elif event == _SAVED_EVENT:
        _stats["compile_seconds_saved"] += duration


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
    """Trace/compile/persistent-cache counters for this process (monotonic;
    see reset_compile_stats).  `cache_dir` is the active persistent cache
    directory or None."""
    _install_listeners()
    out = dict(_stats)
    out["cache_dir"] = _configured_dir
    return out


def reset_compile_stats():
    for k in _stats:
        _stats[k] = 0 if isinstance(_stats[k], int) else 0.0


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
