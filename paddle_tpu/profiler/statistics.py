"""Per-op aggregated profiler statistics tables.

Reference: python/paddle/profiler/profiler_statistic.py — StatisticData
aggregates the event tree into the Overview / Operator / Kernel / UserDefined
summary tables printed by Profiler.summary(), sortable via SortedKeys, with
per-row Calls / Total / Avg / Max / Min and ratio columns.

TPU-native: host spans (RecordEvent) are the event source; the funnel tags
every op span "op::<type>", steps are tagged by the profiler itself, and
remaining spans are user-defined.  Device time on this runtime is the
compiled step's wall share (XLA owns kernel scheduling; per-kernel device
times live in the TensorBoard/XPlane trace the chrome export lines up with).
"""

from __future__ import annotations

from dataclasses import dataclass, field

__all__ = ["EventSummary", "StatisticData", "summary_text",
           "dispatch_cache_line", "compile_cache_line", "startup_line",
           "decode_line", "lora_line"]

_UNITS = {"s": 1e9, "ms": 1e6, "us": 1e3, "ns": 1.0}


@dataclass
class EventSummary:
    """Aggregated stats for one event name (reference EventSummary)."""

    name: str
    calls: int = 0
    total_ns: int = 0
    max_ns: int = 0
    min_ns: int = field(default=2 ** 63 - 1)

    def add(self, dur_ns):
        self.calls += 1
        self.total_ns += dur_ns
        self.max_ns = max(self.max_ns, dur_ns)
        self.min_ns = min(self.min_ns, dur_ns)

    @property
    def avg_ns(self):
        return self.total_ns / self.calls if self.calls else 0.0


def _category(name):
    if name.startswith("op::"):
        return "Operator"
    if name.startswith("step"):
        return "ProfileStep"
    if "dataloader" in name.lower() or name.startswith("io::"):
        return "Dataloader"
    if name.startswith("comm::") or name.startswith("nccl") or "all_reduce" in name:
        return "Communication"
    return "UserDefined"


class StatisticData:
    """Aggregates spans into per-category EventSummary maps
    (reference StatisticData over the node trees)."""

    def __init__(self, spans, step_spans=()):
        self.by_category: dict[str, dict[str, EventSummary]] = {}
        self.wall_ns = 0
        t0, t1 = None, None
        for s in spans:
            cat = _category(s.name)
            bucket = self.by_category.setdefault(cat, {})
            ev = bucket.get(s.name)
            if ev is None:
                ev = bucket[s.name] = EventSummary(s.name)
            ev.add(s.end_ns - s.start_ns)
            t0 = s.start_ns if t0 is None else min(t0, s.start_ns)
            t1 = s.end_ns if t1 is None else max(t1, s.end_ns)
        self.step_spans = list(step_spans)
        if self.step_spans:
            self.wall_ns = sum(d for _, d in self.step_spans)
        elif t0 is not None:
            self.wall_ns = t1 - t0

    def sorted_events(self, category, sorted_by=None):
        from paddle_tpu.profiler import SortedKeys

        events = list(self.by_category.get(category, {}).values())
        key = {
            None: lambda e: -e.total_ns,
            SortedKeys.CPUTotal: lambda e: -e.total_ns,
            SortedKeys.GPUTotal: lambda e: -e.total_ns,
            SortedKeys.CPUAvg: lambda e: -e.avg_ns,
            SortedKeys.GPUAvg: lambda e: -e.avg_ns,
            SortedKeys.CPUMax: lambda e: -e.max_ns,
            SortedKeys.GPUMax: lambda e: -e.max_ns,
            SortedKeys.CPUMin: lambda e: e.min_ns,
            SortedKeys.GPUMin: lambda e: e.min_ns,
        }.get(sorted_by, lambda e: -e.total_ns)
        return sorted(events, key=key)


def _fmt_time(ns, unit):
    return f"{ns / _UNITS[unit]:.3f}"


def _table(title, headers, rows, widths):
    total_w = sum(widths)
    out = [
        "-" * total_w,
        title.center(total_w),
        "-" * total_w,
        "".join(h.rjust(w) if i else h.ljust(w) for i, (h, w) in enumerate(zip(headers, widths))),
        "=" * total_w,
    ]
    for row in rows:
        out.append("".join(
            (c.rjust(w) if i else c.ljust(w))
            for i, (c, w) in enumerate(zip(row, widths))))
    out.append("-" * total_w)
    return out


def summary_text(spans, step_spans=(), sorted_by=None, op_detail=True,
                 time_unit="ms", views=None):
    """The reference Profiler.summary() table set: Overview + per-category
    tables with Calls / Total / Avg / Max / Min / Ratio(%)."""
    if time_unit not in _UNITS:
        raise ValueError(f"time_unit must be one of {sorted(_UNITS)}")
    data = StatisticData(spans, step_spans)
    wall = max(data.wall_ns, 1)
    u = time_unit
    lines = []

    # ---- Overview: wall breakdown per category (reference OverView)
    rows = []
    for cat, events in sorted(data.by_category.items()):
        tot = sum(e.total_ns for e in events.values())
        calls = sum(e.calls for e in events.values())
        rows.append([cat, str(calls), _fmt_time(tot, u),
                     f"{100.0 * tot / wall:.2f}"])
    if data.step_spans:
        rows.append(["ProfileStep(wall)", str(len(data.step_spans)),
                     _fmt_time(data.wall_ns, u), "100.00"])
    lines += _table(f"Overview Summary (time unit: {u})",
                    ["Category", "Calls", f"Total({u})", "Ratio(%)"],
                    rows, [34, 10, 16, 12])
    lines.append("")

    # ---- per-category detail tables
    wanted = set(views) if views else None
    for cat in sorted(data.by_category):
        if wanted is not None and cat not in wanted:
            continue
        if cat == "ProfileStep" and not op_detail:
            continue
        rows = []
        for e in data.sorted_events(cat, sorted_by):
            name = e.name[4:] if e.name.startswith("op::") else e.name
            rows.append([
                name[:38], str(e.calls), _fmt_time(e.total_ns, u),
                _fmt_time(e.avg_ns, u), _fmt_time(e.max_ns, u),
                _fmt_time(e.min_ns, u), f"{100.0 * e.total_ns / wall:.2f}",
            ])
        title = {"Operator": "Operator Summary", "UserDefined": "UserDefined Summary",
                 "Dataloader": "Dataloader Summary", "Communication": "Communication Summary",
                 "ProfileStep": "ProfileStep Summary"}.get(cat, f"{cat} Summary")
        lines += _table(f"{title} (time unit: {u})",
                        ["Name", "Calls", f"Total({u})", f"Avg({u})",
                         f"Max({u})", f"Min({u})", "Ratio(%)"],
                        rows, [39, 8, 13, 13, 13, 13, 10])
        lines.append("")

    if data.step_spans:
        n = len(data.step_spans)
        lines.append(
            f"steps: {n}  avg step: {data.wall_ns / n / _UNITS[u]:.3f} {u}")
    return "\n".join(lines)


def dispatch_cache_line(stats: dict) -> str:
    """One-line rendering of the eager dispatch-cache counters for
    Profiler.summary(); empty when the fast path has seen no traffic."""
    if not (stats.get("hits") or stats.get("misses") or stats.get("bypasses")):
        return ""
    total = stats["hits"] + stats["misses"]
    rate = 100.0 * stats["hits"] / total if total else 0.0
    return (
        "Eager dispatch cache [%s]: hits=%d misses=%d (%.1f%% hit) traces=%d "
        "evictions=%d bypasses=%d entries=%d/%d"
        % ("on" if stats.get("enabled") else "off", stats["hits"],
           stats["misses"], rate, stats["traces"], stats["evictions"],
           stats["bypasses"], stats["size"], stats["capacity"])
    )


def decode_line(stats: dict) -> str:
    """One-line rendering of the serving decode counters for
    Profiler.summary(); empty when no engine dispatched or admitted this
    process.  With committed admissions, a line splits their host time into
    the four phases the `serving.admit.*` spans time, and gives the prefill
    programs' hit share (calls that found their program built) beside it.
    With the prefix cache or capacity counters active, a second line
    reports hits/misses/avoided-prefill-tokens/evictions and pool bytes
    per resident request (the int8-KV capacity metric).  A model with routed
    experts adds a line "Expert load"."""
    adm = stats.get("admissions", 0)
    if not stats.get("dispatches") and not adm:
        return ""
    toks = stats.get("tokens", 0)
    disp = stats.get("dispatches", 0)
    line = (
        "Serving decode: tokens=%d dispatches=%d (%.1f tok/dispatch, "
        "last chunk D=%d) tokens/s=%.1f sync=%.3fs of %.3fs"
        % (toks, disp, toks / disp if disp else 0.0,
           stats.get("last_chunk", 0), stats.get("tokens_per_sec", 0.0),
           stats.get("sync_seconds", 0.0), stats.get("step_seconds", 0.0))
    )
    if adm:
        # the admission split: where the host time of a committed atomic
        # admission went (docs/DECODE.md "Reading an admission")
        ms = lambda k: 1e3 * stats.get(k, 0.0) / adm  # noqa: E731
        line += (
            "\nAdmission split: %d admitted, %.1f ms each = match %.1f + "
            "prefill %.1f (%.0f eager ops) + first token %.1f + pour %.1f"
            % (adm, ms("admit_seconds"), ms("admit_match_seconds"),
               ms("admit_prefill_seconds"),
               stats.get("admit_eager_ops", 0) / adm,
               ms("admit_first_token_seconds"), ms("admit_pour_seconds"))
        )
        calls = stats.get("prefill_program_calls", 0)
        if calls or stats.get("prefill_eager_fallbacks"):
            # compiled prefill: how many admissions found their (bucket,
            # prefix length) program ready, and how many stayed eager
            line += (
                "; prefill programs: %d calls, %.0f%% ready (%d built), "
                "%d pad tokens, %d eager fallbacks"
                % (calls,
                   100.0 * (1 - stats.get("prefill_programs_built", 0)
                            / calls) if calls else 0.0,
                   stats.get("prefill_programs_built", 0),
                   stats.get("prefill_pad_tokens", 0),
                   stats.get("prefill_eager_fallbacks", 0)))
        queued = stats.get("queued_admissions", 0)
        if queued:
            line += "; %d waited %.1f ms in the queue" % (
                queued, 1e3 * stats.get("queue_wait_seconds", 0.0) / queued)
    lookups = stats.get("prefix_hits", 0) + stats.get("prefix_misses", 0)
    if lookups or stats.get("resident_peak"):
        line += (
            "\nPrefix cache: hits=%d misses=%d prefill_avoided_tokens=%d "
            "evictions=%d; pool bytes/resident=%.0f (peak %d resident)"
            % (stats.get("prefix_hits", 0), stats.get("prefix_misses", 0),
               stats.get("prefix_hit_tokens", 0),
               stats.get("prefix_evictions", 0),
               stats.get("pool_bytes_per_resident", 0.0),
               stats.get("resident_peak", 0))
        )
    steps = stats.get("moe_layer_steps", 0)
    if steps:
        # routed experts (docs/DECODE.md "Reading expert load"): counted on
        # the device over the decode steps' expert layers, committed rows only
        asg, held = stats.get("moe_assignments", 0), stats.get("moe_held_assignments", 0)
        line += (
            "\nExpert load: %d expert-layer steps, %d assignments, %d held "
            "here (%.4f); a step touches %.2f held experts, its busiest "
            "takes %.2f tokens; prefill %d of %d held"
            % (steps, asg, held, held / asg if asg else 0.0,
               stats.get("moe_experts_touched", 0) / steps,
               stats.get("moe_peak_expert_assignments", 0) / steps,
               stats.get("moe_prefill_held_assignments", 0),
               stats.get("moe_prefill_assignments", 0))
        )
    if stats.get("mesh_shape"):
        # TP-sharded engine: per-device pool footprint vs the global total
        line += (
            "\nSharded serving: mesh=%s pool_bytes/device=%d (global %d)"
            % (stats["mesh_shape"], stats.get("pool_bytes_per_device", 0),
               stats.get("pool_bytes", 0))
        )
    classes = sum(stats.get("admitted_" + c, 0)
                  + stats.get("completed_" + c, 0)
                  for c in ("high", "normal", "low"))
    if (stats.get("prefill_chunks") or stats.get("preemptions")
            or stats.get("parked_requests") or classes):
        # overload-discipline tier: interleaved prefill chunks, the
        # preemption parking lot, and the per-SLO-class breakdown
        line += (
            "\nServing admission: prefill_chunks=%d preemptions=%d "
            "readmits=%d parked=%d; admitted h/n/l=%d/%d/%d "
            "completed h/n/l=%d/%d/%d"
            % (stats.get("prefill_chunks", 0), stats.get("preemptions", 0),
               stats.get("preempt_readmits", 0),
               stats.get("parked_requests", 0),
               stats.get("admitted_high", 0), stats.get("admitted_normal", 0),
               stats.get("admitted_low", 0), stats.get("completed_high", 0),
               stats.get("completed_normal", 0),
               stats.get("completed_low", 0))
        )
    return line


def lora_line(stats: dict) -> str:
    """One-line rendering of the multi-tenant LoRA serving counters for
    Profiler.summary(); empty when no adapter-pack engine ran this
    process (docs/LORA.md)."""
    if not (stats.get("swaps") or stats.get("gather_dispatches")
            or stats.get("slots_resident")):
        return ""
    return (
        "LoRA serving: slots=%d/%d resident, swaps=%d evictions=%d "
        "gather_dispatches=%d cache_epochs=%d"
        % (stats.get("slots_resident", 0), stats.get("slots_total", 0),
           stats.get("swaps", 0), stats.get("evictions", 0),
           stats.get("gather_dispatches", 0), stats.get("cache_epochs", 0))
    )


def verify_line(stats: dict) -> str:
    """One-line rendering of the IR verify-mode counters for
    Profiler.summary(); empty when FLAGS_verify_programs never ran.
    A nonzero rewrites_refused alone still renders the line: the rewrite
    driver rolls fusions back flag-independently, and a refusal is exactly
    the red flag verify_stats() tells users to watch for."""
    if not (stats.get("programs_verified") or stats.get("differential_checks")
            or stats.get("rewrites_refused")):
        return ""
    return (
        "IR verify: programs=%d failed=%d violations=%d abstract_skips=%d; "
        "differential checks=%d failed=%d; rewrites refused=%d"
        % (stats["programs_verified"], stats["programs_failed"],
           stats["violations"], stats["abstract_eval_skips"],
           stats["differential_checks"], stats["differential_failures"],
           stats["rewrites_refused"])
    )


def mesh_line(stats: dict) -> str:
    """One-line rendering of the mesh-lint counters for Profiler.summary();
    empty when FLAGS_verify_sharding never ran this process.  entries_failed
    or violations nonzero is the red flag: a placement/collective/donation
    hazard reached a build path (the error names the site)."""
    if not (stats.get("entries_linted") or stats.get("collectives_checked")
            or stats.get("placements_checked")):
        return ""
    return (
        "Mesh lint: entries=%d failed=%d violations=%d; collectives=%d "
        "constraints=%d placements=%d donation_checks=%d mem_estimates=%d "
        "trace_skips=%d"
        % (stats["entries_linted"], stats["entries_failed"],
           stats["violations"], stats["collectives_checked"],
           stats["constraints_checked"], stats["placements_checked"],
           stats["donation_checks"], stats["memory_estimates"],
           stats["trace_skips"])
    )


def protocol_line(stats: dict) -> str:
    """One-line rendering of the protocol-lint counters for
    Profiler.summary(); empty when neither the model checker nor the
    blocking-call pass ran this process.  violations or deadlocks nonzero
    is the red flag: an interleaving of the abstract cluster model broke
    a named invariant (the ProtocolLintError carries the minimal
    counterexample trace), or a blocking call site escaped the shared
    deadline discipline."""
    if not (stats.get("scenarios_checked") or stats.get("files_linted")):
        return ""
    return (
        "Protocol lint: scenarios=%d states=%d transitions=%d "
        "invariant_checks=%d violations=%d deadlocks=%d; files=%d "
        "functions=%d blocking_calls=%d"
        % (stats["scenarios_checked"], stats["model_states"],
           stats["model_transitions"], stats["invariant_checks"],
           stats["violations"], stats["deadlocks"], stats["files_linted"],
           stats["functions_scanned"], stats["blocking_calls_checked"])
    )


def schedule_line(stats: dict) -> str:
    """One-line rendering of the Pallas schedule-search counters for
    Profiler.summary(); empty when the search tier never ran this process.
    `disabled` nonzero is healthy honesty (the measured-win gate found XLA
    faster and said so); `measured` climbing in steady state means shape
    churn is defeating the per-device schedule cache."""
    if not (stats.get("subgraphs_found") or stats.get("cache_hits")
            or stats.get("disabled_hits")):
        return ""
    return (
        "Schedule search: subgraphs=%d candidates=%d pruned_roofline=%d "
        "pruned_vmem=%d measured=%d accepted=%d "
        "disabled=%d build_errors=%d; cache hits=%d disabled_hits=%d"
        % (stats["subgraphs_found"], stats["candidates"],
           stats["pruned_roofline"], stats["pruned_vmem"],
           stats["measured"], stats["accepted"], stats["disabled"],
           stats.get("build_errors", 0),
           stats["cache_hits"], stats["disabled_hits"])
    )


def checkpoint_line(stats: dict) -> str:
    """One-line rendering of the CheckpointManager counters for
    Profiler.summary(); empty when no checkpoint activity this process.
    corrupt_skipped or errors nonzero is the red flag: auto-resume passed
    over a torn checkpoint, or a background write failed."""
    if not (stats.get("saves") or stats.get("restores")
            or stats.get("corrupt_skipped")):
        return ""
    return (
        "Checkpoint: saves=%d (async=%d) commits=%d bytes=%d "
        "snapshot=%.3fs write=%.3fs backpressure=%.3fs gc_deleted=%d; "
        "restores=%d corrupt_skipped=%d errors=%d"
        % (stats["saves"], stats["async_saves"], stats["commits"],
           stats["bytes_written"], stats["snapshot_seconds"],
           stats["write_seconds"], stats["backpressure_seconds"],
           stats["gc_deleted"], stats["restores"], stats["corrupt_skipped"],
           stats["errors"])
    )


def cluster_line(stats: dict) -> str:
    """One-line rendering of the disaggregated serving-cluster counters
    for Profiler.summary(); empty when no cluster ran this process
    (serving/cluster.py).  redispatches nonzero means a replica died or
    drained and its accepted requests moved — the fail-over machinery
    working, surfaced so an unstable fleet is visible at a glance.  The
    warm-start tier rides the same line: standbys_warm is the live gauge,
    promotions counts standbys that took a dead replica's slot, warmups/
    warmup_s the worker AOT warm reports, and respawn_cache h/m the
    persistent compile-cache hits/misses respawned workers booted with."""
    if not (stats.get("replicas_alive") or stats.get("redispatches")
            or stats.get("pages_shipped") or stats.get("drain_migrations")
            or stats.get("heartbeats_missed") or stats.get("standbys_warm")
            or stats.get("promotions") or stats.get("warmups")):
        return ""
    return (
        "Serving cluster: replicas_alive=%d heartbeats_missed=%d "
        "redispatches=%d pages_shipped=%d ship_bytes=%d ship_retries=%d "
        "drain_migrations=%d standbys_warm=%d promotions=%d warmups=%d "
        "warmup_s=%.2f respawn_cache=%dh/%dm"
        % (stats["replicas_alive"], stats["heartbeats_missed"],
           stats["redispatches"], stats["pages_shipped"],
           stats["ship_bytes"], stats["ship_retries"],
           stats["drain_migrations"], stats.get("standbys_warm", 0),
           stats.get("promotions", 0), stats.get("warmups", 0),
           stats.get("warmup_seconds", 0.0),
           stats.get("respawn_compile_hits", 0),
           stats.get("respawn_compile_misses", 0))
    )


def snapshot_line(stats: dict) -> str:
    """One-line rendering of the live-engine snapshot counters for
    Profiler.summary(); empty when no engine snapshot activity this
    process (serving/snapshot.py).  corrupt_skipped nonzero means a kill
    landed mid-commit and restore passed over the torn dir — the
    protocol working as designed, surfaced so nobody wonders where a
    snapshot went."""
    if not (stats.get("saves") or stats.get("restores")
            or stats.get("corrupt_skipped")):
        return ""
    return (
        "Engine snapshot: saves=%d restores=%d bytes=%d snapshot=%.3fs "
        "corrupt_skipped=%d drains=%d"
        % (stats["saves"], stats["restores"], stats["bytes"],
           stats["snapshot_seconds"], stats["corrupt_skipped"],
           stats["drains"])
    )


def pipeline_line(stats: dict) -> str:
    """One-line rendering of the pipeline-schedule counters for
    Profiler.summary(); empty when no pipeline program ran this process
    (fleet/meta_parallel/schedules.py, docs/PIPELINE.md).  w_slots nonzero
    means a zero-bubble split-backward schedule is live; overlap_issued
    counts the collective-permute hops of comm/compute-overlap grad-sync
    chains."""
    if not (stats.get("programs") or stats.get("overlap_issued")):
        return ""
    return (
        "Pipeline: programs=%d ticks=%d slots F=%d B=%d W=%d "
        "bubble_ticks=%d overlap_issued=%d"
        % (stats["programs"], stats["ticks"], stats["f_slots"],
           stats["b_slots"], stats["w_slots"], stats["bubble_ticks"],
           stats["overlap_issued"])
    )


def compile_cache_line(stats: dict) -> str:
    """One-line rendering of the compile ledger (trace, lowering, compile,
    the framework's own programs' share of them, and the persistent cache's
    measured reads) for Profiler.summary(); empty when nothing compiled this
    process."""
    if not (stats.get("compiles") or stats.get("traces")):
        return ""
    line = (
        "XLA compile: traces=%d (%.2fs) lowerings=%d (%.2fs) compiles=%d "
        "(%.2fs, %.2fs of it XLA compiling)"
        % (stats["traces"], stats["trace_seconds"], stats["lowerings"],
           stats["lower_seconds"], stats["compiles"],
           stats["compile_seconds"], stats["compile_miss_seconds"])
    )
    if "framework_compiles" in stats:
        line += (
            "; of these the framework's own programs: compiles=%d, lowering "
            "%.2fs, compile %.2fs"
            % (stats["framework_compiles"], stats["framework_lower_seconds"],
               stats["framework_compile_seconds"]))
    if stats.get("cache_dir"):
        line += (
            "; persistent cache [%s]: hits=%d misses=%d read=%.2fs"
            % (stats["cache_dir"], stats["persistent_cache_hits"],
               stats["persistent_cache_misses"],
               stats["cache_read_seconds"])
        )
    return line


def startup_line(stats: dict, by_program: dict | None = None) -> str:
    """One-line rendering of profiler.startup_stats(): seconds by phase, and
    the three slowest rows of compile_stats()["by_program"] when given."""
    s = stats
    line = (
        "Start-up: %.1fs before import paddle_tpu, then %.1fs: import %.1f "
        "(jax %.1f), engine "
        "build %.1f (pools %.1f, state %.1f), train build %.1f (optimizer "
        "state %.1f, first call %.1f), first use of %d programs %.1f (a train "
        "step's lies inside its build), compile work outside these %.1f; "
        "accounted, each second once, %.1f + unaccounted %.1f; process-wide: "
        "trace %.1f, lowering %.1f, XLA compile %.1f, cache read %.1f"
        % (s["before_import_seconds"], s["elapsed_seconds"],
           s["import_seconds"], s["import_jax_seconds"],
           s["engine_build_seconds"], s["engine_pool_alloc_seconds"],
           s["engine_state_alloc_seconds"], s["train_build_seconds"],
           s["train_optimizer_state_seconds"], s["train_build_trace_seconds"],
           s["programs_first_used"], s["program_first_use_seconds"],
           s["compile_outside_seconds"], s["accounted_seconds"],
           s["unaccounted_seconds"], s["trace_seconds"], s["lower_seconds"],
           s["compile_miss_seconds"], s["cache_read_seconds"])
    )

    def cost(row):
        return row["trace_seconds"] + row["lower_seconds"] + row["compile_seconds"]

    slowest = sorted((by_program or {}).items(), key=lambda kv: -cost(kv[1]))[:3]
    if slowest:
        line += "; slowest programs: " + ", ".join(
            "%s %.1fs (%d built: trace %.1f + lowering %.1f + compile %.1f)"
            % (n, cost(r), r["lowerings"], r["trace_seconds"],
               r["lower_seconds"], r["compile_seconds"])
            for n, r in slowest)
    return line
