"""Global flag registry.

Equivalent of the reference's exported-flags system (paddle/phi/core/flags.h:141,
paddle.get_flags/set_flags) with env-var override (FLAGS_*), minus the C++
gflags machinery — a process-wide Python registry is the right weight here.
"""

from __future__ import annotations

import os
from typing import Any

__all__ = ["define_flag", "get_flags", "set_flags", "flag", "on_change"]

_FLAGS: dict[str, dict[str, Any]] = {}

# Callbacks fired after every set_flags() with the list of changed flag
# names.  The eager dispatch cache registers one: op bodies may read flags
# at trace time, so any flag change must invalidate cached traces.
_listeners: list = []


def on_change(callback):
    """Register `callback(changed_names)` to run after each set_flags()."""
    _listeners.append(callback)
    return callback


def _coerce(value, default):
    if isinstance(default, bool):
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "on")
        return bool(value)
    if isinstance(default, int):
        return int(value)
    if isinstance(default, float):
        return float(value)
    return value


def define_flag(name: str, default, help_str: str = ""):
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    env = os.environ.get(name)
    value = _coerce(env, default) if env is not None else default
    _FLAGS[name] = {"value": value, "default": default, "help": help_str}
    return value


def flag(name: str):
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    return _FLAGS[name]["value"]


def get_flags(flags=None) -> dict:
    if flags is None:
        return {k: v["value"] for k, v in _FLAGS.items()}
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for name in flags:
        key = name if name.startswith("FLAGS_") else "FLAGS_" + name
        out[name] = _FLAGS[key]["value"]
    return out


def set_flags(flags: dict):
    changed = []
    for name, value in flags.items():
        key = name if name.startswith("FLAGS_") else "FLAGS_" + name
        if key not in _FLAGS:
            define_flag(key, value)
        else:
            new = _coerce(value, _FLAGS[key]["default"])
            if new == _FLAGS[key]["value"]:
                continue  # no-op re-set: don't invalidate listeners' caches
            _FLAGS[key]["value"] = new
        changed.append(key)
    if changed:
        for cb in list(_listeners):
            cb(changed)


# Core flags (subset of the reference's 71 exported flags that are meaningful on TPU).
define_flag("FLAGS_check_nan_inf", False, "Scan op outputs for NaN/Inf in eager mode")
define_flag("FLAGS_default_dtype", "float32", "Default floating dtype for creation ops")
define_flag("FLAGS_tpu_matmul_precision", "default", "jax matmul precision: default|high|highest")
define_flag("FLAGS_eager_op_jit", True, "Route eager composite ops through cached jax.jit")
define_flag(
    "FLAGS_eager_op_cache_size",
    1024,
    "Max entries in the eager dispatch fast-path cache (LRU; see _core.dispatch)",
)
define_flag(
    "FLAGS_scan_layers",
    False,
    "Force nn.LayerStack scan-over-layers for models with a fuse_layer_stack "
    "config knob (depth-constant trace/compile; models/llama.py, models/gpt.py)",
)
define_flag(
    "FLAGS_decode_chunk",
    8,
    "Macro-step decode width D: paged decode advances D tokens per compiled "
    "dispatch (lax.scan inside the jitted step; token streams bit-identical "
    "for every D).  Consumed by LlamaForCausalLM.generate and "
    "serving.GenerationEngine; 1 = per-token dispatch",
)
define_flag(
    "FLAGS_prefill_chunk_blocks",
    0,
    "Per-macro-step prefill budget for interleaved chunked prefill, in pool "
    "blocks: each serving step() runs at most this many block-sized prefill "
    "chunks before the decode dispatch (deadline pressure may double it; "
    "serving.GenerationEngine).  0 = atomic prefill at admission (legacy)",
)
define_flag(
    "FLAGS_preempt_low_priority",
    True,
    "Allow the serving admission scheduler to preempt LOW-priority requests "
    "when a higher-priority request cannot be admitted: their pool pages are "
    "parked host-side and the stream resumes bit-identically on re-admission "
    "(submit-time nonces; serving.GenerationEngine)",
)
define_flag(
    "FLAGS_compilation_cache_dir",
    "",
    "Directory for JAX's persistent XLA compilation cache: warm process "
    "starts reload compiled steps from disk (_core.compile_cache)",
)
define_flag(
    "FLAGS_use_pallas_fusion",
    True,
    "Substitute attention/rms-norm/swiglu subgraphs in captured Programs "
    "with Pallas kernels before lowering (static.rewrite.PallasFusionPass)",
)
define_flag(
    "FLAGS_verify_programs",
    False,
    "Verify-mode for the static IR (static/verify.py): ProgramVerifier runs "
    "around every program pass and on the Executor's compile path, and "
    "rewritten programs are differentially replayed against the original "
    "on the live feed (docs/VERIFIER.md)",
)
define_flag(
    "FLAGS_checkpoint_kill_point",
    "",
    "Dev-mode fault injection for the checkpoint commit protocol: the "
    "process SIGKILLs itself when CheckpointManager reaches this named "
    "point (after-shard-write | before-manifest | mid-manifest | "
    "after-commit) — crash consistency is tested mechanically "
    "(distributed/checkpoint/manager.py, docs/CHECKPOINT.md)",
)
define_flag(
    "FLAGS_checkpoint_verify_on_save",
    False,
    "Belt-and-braces: re-read and checksum-verify a checkpoint directory "
    "immediately after its atomic commit (CheckpointManager; the write "
    "thread raises on mismatch instead of letting a bad checkpoint be "
    "discovered at restore time)",
)
define_flag(
    "FLAGS_prefix_cache",
    False,
    "Radix/prefix KV reuse in serving.GenerationEngine: admission matches "
    "the longest cached token-id prefix at page granularity and takes "
    "references to those pool pages instead of re-prefilling them; full "
    "prompt blocks written by prefill are inserted back into the tree and "
    "refcount-zero leaves are evicted LRU under pool pressure "
    "(docs/DECODE.md)",
)
define_flag(
    "FLAGS_kv_cache_dtype",
    "bf16",
    "Paged-KV pool storage dtype for serving.GenerationEngine: 'bf16' "
    "(default) keeps full-precision pools in the model's serving dtype; "
    "'int8' stores quantized values with per-block-per-head scales carried "
    "alongside the pool and dequantized on gather inside the jitted decode "
    "step — roughly double the resident requests at fixed pool bytes "
    "(ops/paged_attention.QuantPool, docs/DECODE.md)",
)
define_flag(
    "FLAGS_schedule_search",
    False,
    "Cost-model-driven Pallas schedule search over discovered reduction-/"
    "matmul-rooted subgraphs (static/schedule_search.py): enumerate "
    "candidate tilings, prune by roofline + VMEM budget, measure the "
    "survivors, and substitute only schedules that beat XLA by the "
    "measured-win margin — losing subgraphs persist as disabled in the "
    "per-device autotune cache (docs/SCHEDULE_SEARCH.md)",
)
define_flag(
    "FLAGS_schedule_search_budget",
    6,
    "Max schedule candidates measured on device per discovered subgraph "
    "(the top-K survivors of the roofline + VMEM prunes); tests pin this "
    "low to bound tier-1 wall time",
)
define_flag(
    "FLAGS_schedule_search_min_win",
    1.05,
    "Measured-win gate margin: a searched Pallas schedule must beat the "
    "XLA-only twin by at least this ratio or the subgraph is recorded as "
    "disabled for this device kind and never re-measured",
)
define_flag(
    "FLAGS_verify_sharding",
    False,
    "Mesh lint for the distributed tier (static/mesh_lint.py): statically "
    "analyze sharded computations — placement/axis congruence, collective "
    "participation (incl. data-dependent-predicate collectives, the "
    "deadlock/SIGSEGV class), use-after-donation, per-device HBM "
    "estimates — around program passes, on the Executor's compile path, "
    "and when TrainStep/ShardedTrainStep/GenerationEngine build "
    "(docs/MESH_LINT.md).  Same contract as FLAGS_verify_programs: no "
    "device collective is ever launched by the analysis",
)
define_flag(
    "FLAGS_mesh_lint_replicated_mb",
    8.0,
    "Mesh-lint threshold (MiB): a tensor at least this large that ends up "
    "fully replicated on a multi-device mesh is flagged as "
    "replicated-giant with its per-device byte cost (static/mesh_lint.py)",
)
define_flag(
    "FLAGS_mesh_lint_hbm_budget_gb",
    0.0,
    "Mesh-lint per-device HBM budget (GiB; 0 disables): the estimated "
    "sharding-divided bytes per device (params + optimizer state + KV "
    "pools) above this raises an over-budget violation "
    "(static/mesh_lint.py, docs/MESH_LINT.md)",
)
define_flag(
    "FLAGS_lora_max_adapters",
    8,
    "Usable adapter slots in a serving AdapterPack (nn/lora.py): a "
    "GenerationEngine built with adapters= pre-allocates this many "
    "hot-swappable LoRA slots PLUS the reserved slot 0 (the zero-adapter "
    "base-model identity).  Geometry is fixed at engine construction — "
    "register_adapter/evict_adapter mutate slot contents only, so "
    "compiled decode steps never recompile on a swap (docs/LORA.md)",
)
define_flag(
    "FLAGS_engine_snapshot_dir",
    "",
    "Serving fault tolerance (serving/snapshot.py, docs/CHECKPOINT.md): "
    "directory for live GenerationEngine snapshots.  When set, "
    "engine.step() calls maybe_snapshot() at every macro-step boundary — "
    "a pending SIGTERM preemption flag (install_preemption_handler) or "
    "the FLAGS_engine_snapshot_interval period then commits a restorable "
    "snapshot through the SAME atomic rename protocol as "
    "CheckpointManager.  Empty disables the automatic path (explicit "
    "engine.snapshot(dir)/drain(dir) calls still work)",
)
define_flag(
    "FLAGS_engine_snapshot_interval",
    0,
    "Macro-steps between periodic live-engine snapshots "
    "(FLAGS_engine_snapshot_dir must be set; 0 = preemption-triggered "
    "only).  Snapshots are written at macro-step boundaries, never "
    "mid-dispatch — the serving mirror of CheckpointManager's "
    "save_interval_steps (serving/snapshot.py)",
)
define_flag(
    "FLAGS_cluster_heartbeat_ms",
    100,
    "Disaggregated serving cluster (serving/cluster.py, "
    "docs/SERVING_CLUSTER.md): heartbeat period — every worker bumps its "
    "TCPStore counter twice per period from a background thread, and the "
    "router's failure detector counts elapsed periods without an advance "
    "as misses",
)
define_flag(
    "FLAGS_cluster_heartbeat_misses",
    30,
    "Miss threshold of the cluster failure detector: a replica whose "
    "heartbeat counter has not advanced for this many consecutive "
    "FLAGS_cluster_heartbeat_ms periods is declared dead — its prefix "
    "pages leave the cluster index and its accepted-but-unfinished "
    "requests re-dispatch (serving/cluster.py)",
)
define_flag(
    "FLAGS_cluster_standby",
    0,
    "Warm standby tier of the disaggregated serving cluster "
    "(serving/cluster.py): EngineCluster pre-forks this many standby "
    "worker processes that have already paid jax import + trace + "
    "persistent-cache-served compile against the cluster's engine "
    "geometry.  On a detected decode-replica death, promotion hands a "
    "warm standby the dead replica's snapshot dir and re-keys its rings "
    "into the replica slot — skipping the respawn entirely; a consumed "
    "standby is backfilled asynchronously.  0 disables the tier "
    "(respawn-with-warmup remains the recovery path)",
)
define_flag(
    "FLAGS_cluster_transport",
    "shm",
    "Data-plane transport of the disaggregated serving cluster "
    "(serving/transport.py, docs/SERVING_CLUSTER.md multi-host section): "
    "'shm' rides process-shared ShmRing buffers (single box), 'tcp' rides "
    "length-framed TcpRing sockets with endpoints published through the "
    "TCPStore control tier — the same producer/consumer contract "
    "(TimeoutError is backpressure, never death), so the SIGKILL crash "
    "matrix and bit-exact fail-over hold verbatim on either.  "
    "EngineCluster(transport=...) overrides per cluster",
)
define_flag(
    "FLAGS_cluster_attach_timeout_ms",
    30_000,
    "Shared attach deadline for a cluster worker's boot-time channel "
    "setup (serving/cluster_worker.py): the TCPStore client connect, "
    "both ring attaches (shm attach retry or TcpRing endpoint wait + "
    "dial — serving/transport.py) each ride this budget with "
    "capped-backoff retries, because a worker routinely outraces the "
    "router's bind/publish under load and first-refusal failure would "
    "melt boots into respawn churn",
)
define_flag(
    "FLAGS_pipeline_schedule",
    "1F1B",
    "Default pipeline schedule for PipelineStack/pipeline_llama/"
    "pipeline_gpt built with schedule=None: one of the registered "
    "schedule names (fleet/meta_parallel/schedules.py — FThenB | 1F1B | "
    "ZB-H1).  ZB-H1 runs the zero-bubble split backward: grad-input (B) "
    "on the critical path, grad-weight (W) deferred per the schedule's "
    "tick table.  Changing the flag re-resolves flag-following stacks "
    "and invalidates their cached built steps, the same contract as "
    "FLAGS_decode_chunk (docs/PIPELINE.md)",
)
define_flag(
    "FLAGS_scan_body_guard",
    False,
    "Dev-mode guard: warn when the same lax.scan body function object is "
    "traced under two distinct jit entries — jax's scan-jaxpr cache would "
    "serve the first trace's closed-over tracers to the second "
    "(docs/SCAN_LAYERS.md; _core/dispatch.py)",
)
