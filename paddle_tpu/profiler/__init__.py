"""paddle.profiler parity.

Reference: python/paddle/profiler/profiler.py:346 (Profiler with
HostTracer + CudaTracer/CUPTI, chrome-trace export, statistics tables,
schedules) over paddle/fluid/platform/profiler/.

TPU-native composition:
- **Host tracer**: RecordEvent, the program's one span primitive.  Every span
  is a `jax.profiler.TraceAnnotation`, so it lands on the clock of whatever
  trace session is open (this module's Profiler or a caller's own
  `jax.profiler.start_trace`) and costs about a microsecond when none is;
  while a Profiler records, spans also go to its host buffer with their
  parent and arguments.  SPAN_NAMES lists every name the program emits.
- **Device tracer**: jax.profiler start/stop_trace — XLA's XPlane/TensorBoard
  trace IS the CUPTI analog (per-kernel device timeline compiled in by XLA).
- Export: chrome trace JSON from host spans (device timeline lives in the
  XPlane dump directory), `summary()` statistics table aggregated by event.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from dataclasses import dataclass, field
from enum import Enum

import jax

__all__ = [
    "Profiler",
    "RecordEvent",
    "ProfilerTarget",
    "ProfilerState",
    "make_scheduler",
    "export_chrome_tracing",
    "SPAN_NAMES",
    "SCOPE_NAMES",
    "PROGRAM_NAMES",
]

# Every span name the program emits, layer by layer (PERF.md section 3 says
# which metric reads each).  `op::<name>` (one per eager op, only while a
# Profiler records) is the one family not listed.
SPAN_NAMES = (
    # entry points: jit.TrainStep.__call__
    "jit.train_step", "jit.train_step.build",
    "jit.train_step.build.optimizer_state", "jit.train_step.build.trace",
    "jit.train_step.dispatch",
    # start-up (profiler/startup.py; construction and first-use paths only):
    # GenerationEngine.__init__ and what it allocates, and the FIRST call of
    # a program the framework built, with `program` (a PROGRAM_NAMES entry)
    # and `key` (what selects this build of it)
    "serving.engine.build", "serving.engine.build.pools",
    "serving.engine.build.state", "program.first_use",
    # admission, scheduler, cache manager: serving.GenerationEngine
    "serving.admit", "serving.admit.match", "serving.admit.prefill",
    "serving.admit.first_token", "serving.admit.pour",
    "serving.step", "serving.step.schedule", "serving.step.dispatch",
    "serving.step.sync", "serving.step.retire",
)

# The programs the framework's hot path builds, as a device trace's XLA
# Modules line names them (PERF.md section 3): TrainStep's step, the engine's
# macro-step, admission prefill, the two pours, the speculative pair, and
# to_static's function.  `compile_stats()` sums the `framework_*` keys over
# these alone.  `jit_logit_rows` (GenerationEngine.next_token_logits), which
# only a check calls, is left out on purpose: a check inside a window is no
# rebuild of the hot path.
PROGRAM_NAMES = (
    "jit_train_step", "jit_decode_macro_step", "jit_prefill_program",
    "jit__pour_new_blocks", "jit__pour_stacked_blocks", "jit_draft_step",
    "jit_verify_step", "jit_static_function",
)

# `jax.named_scope`s INSIDE the compiled programs (the prefill program, the
# decode macro-step): they reach the device operations' names and metadata
# in a trace, not the host's span buffer, so they are listed apart from
# SPAN_NAMES (which a Profiler run must reproduce exactly).  The Pallas
# kernels' names (`name=` on every pallas_call) are in docs/DECODE.md.
SCOPE_NAMES = (
    # models/mla_moe.py: latent attention's two paths; models/experts.py:
    # the routed experts
    "mla.prefill", "mla.decode", "moe.route", "moe.experts", "moe.shared",
    # models/window_moe.py: attention by cache class and path (full layers
    # read pages, sliding layers a window: the flash kernel's window in the
    # prefill program, the slot's ring in the macro-step), the per-head gate;
    # its expert layer is models/experts.py's, under the moe.* scopes above
    "attn.full.prefill", "attn.window.prefill", "attn.full.decode",
    "attn.window.decode", "attn.gate",
    # models/cca_moe.py: the projections into the latent, the two causal
    # convolutions, the q-k mean, norms and rope (cca.conv); the attention
    # over them (the flash kernel in the prefill program, the paged write
    # and read in the macro-step: cca.attend); the MLP router over the state
    # carried across depth (moe.router_mlp); its experts' loop is
    # models/experts.py's, under moe.route / moe.experts above
    "cca.conv", "cca.attend", "moe.router_mlp",
    # models/dsa_moe.py: the learned sparse indexer's scores (dsa.index) and
    # its exact top-k with the gather of the rows it chose (dsa.select); the
    # full layers' decode over that selection (mla.sparse.decode), the
    # sliding layers' latent attention in its window (mla.window.*: the
    # flash kernel's window in the prefill program, the slot's latent ring
    # in the macro-step); the full layers' prefill runs under mla.prefill
    "dsa.index", "dsa.select", "mla.sparse.decode", "mla.window.prefill",
    "mla.window.decode",
)

_active_profiler = None  # checked by the op funnel (cheap global)


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


@dataclass
class _Span:
    name: str
    start_ns: int
    end_ns: int
    tid: int
    category: str = "host"
    parent: str | None = None  # the span open on this thread when it began
    args: dict = field(default_factory=dict)  # e.g. rid: one id per request


class _HostEventBuffer:
    def __init__(self):
        self.spans = []
        self._lock = threading.Lock()

    def add(self, span):
        with self._lock:
            self.spans.append(span)


_open_spans = threading.local()  # .stack: this thread's open RecordEvents


class RecordEvent:
    """Host span (reference platform/profiler RecordEvent): name, start,
    end, the span that caused it, and keyword arguments (spans of one
    request carry its `rid`).

    `begin()` always enters a `jax.profiler.TraceAnnotation(name, **args)`:
    with no trace session open that costs about a microsecond and records
    nothing; with one open — a Profiler's or the caller's own
    `jax.profiler.start_trace` — the span is on the trace's clock beside
    the device's operations, its arguments are the event's stats, and its
    parent is the event that contains it on the same thread line.  While a
    Profiler records, `end()` also appends the span to its host buffer."""

    def __init__(self, name: str, event_type=None, **args):
        self.name = name
        self.args = args
        self.parent = None
        self._ann = None
        self._t0 = None

    def begin(self):
        stack = getattr(_open_spans, "stack", None)
        if stack is None:
            stack = _open_spans.stack = []
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def end(self):
        if self._ann is None:  # never begun, or ended twice
            return
        t1 = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        self._ann = None
        stack = getattr(_open_spans, "stack", ())
        if self in stack:  # not the top when begin()/end() pairs cross
            stack.remove(self)
        prof = _active_profiler
        if prof is not None:
            prof._buffer.add(_Span(self.name, self._t0, t1,
                                   threading.get_ident(), parent=self.parent,
                                   args=self.args))

    __enter__ = begin

    def __exit__(self, *exc):
        self.end()


from paddle_tpu._core import compile_cache as _compile_cache  # noqa: E402
from . import startup  # noqa: E402  (it needs RecordEvent)

_compile_cache.keep_rows(PROGRAM_NAMES)   # never folded into `(other)`

startup_stats = startup.startup_stats


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0, skip_first: int = 0):
    """Reference profiler.make_scheduler: step -> ProfilerState."""

    period = closed + ready + record

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


class Profiler:
    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None, timer_only=False, record_shapes=False, profile_memory=False, with_flops=False):
        self.targets = targets or [ProfilerTarget.CPU, ProfilerTarget.TPU]
        self.scheduler = scheduler if callable(scheduler) else None
        if isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self.scheduler = make_scheduler(closed=lo, ready=0, record=hi - lo, repeat=1)
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self._buffer = _HostEventBuffer()
        self._step = 0
        self._recording = False
        # where start() put the device trace: jax writes
        # <trace_dir>/plugins/profile/<time>/*.xplane.pb (None: timer_only
        # or no TPU target)
        self.trace_dir = None
        self._tracing = False
        self._step_spans = []
        self._step_t0 = None

    # ---------------------------------------------------------------- state
    def start(self):
        """Open the device trace, then start recording host spans.  The
        xplane goes under the directory the `on_trace_ready` exporter names
        (`export_chrome_tracing(dir_name)`), else a new temporary one; either
        way `self.trace_dir` says where.  A `jax.profiler.start_trace` that
        fails (another session is open) raises: a Profiler that silently
        traced nothing is worse than none."""
        global _active_profiler
        if not self.timer_only and ProfilerTarget.TPU in self.targets:
            self.trace_dir = (getattr(self.on_trace_ready, "dir_name", None)
                              or tempfile.mkdtemp(prefix="paddle_tpu_profile_"))
            jax.profiler.start_trace(self.trace_dir)
            self._tracing = True
        if self.scheduler is not None:
            state = self.scheduler(0)
            _active_profiler = (
                self if state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN) else None
            )
        else:
            _active_profiler = self
        self._recording = True
        self._step_t0 = time.perf_counter_ns()
        return self

    def stop(self):
        global _active_profiler
        self._recording = False
        _active_profiler = None
        if self._tracing:
            self._tracing = False
            jax.profiler.stop_trace()
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def step(self, num_samples=None):
        now = time.perf_counter_ns()
        if self._step_t0 is not None:
            self._step_spans.append((self._step, now - self._step_t0))
        self._step_t0 = now
        self._step += 1
        if self.scheduler is not None:
            state = self.scheduler(self._step)
            global _active_profiler
            if state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
                _active_profiler = self
            else:
                _active_profiler = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # --------------------------------------------------------------- export
    def export_chrome_tracing(self, path, *args):
        export_chrome_tracing(self, path)

    def export(self, path, format="json"):
        export_chrome_tracing(self, path)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        """Aggregated statistics tables (reference profiler_statistic.py):
        Overview + per-category (Operator/Dataloader/UserDefined/...) tables
        with Calls/Total/Avg/Max/Min/Ratio columns, sortable via SortedKeys.
        Ends with the eager dispatch-cache counters when the fast path has
        seen traffic."""
        from .statistics import (checkpoint_line, cluster_line,
                                 compile_cache_line, decode_line,
                                 dispatch_cache_line, lora_line, mesh_line,
                                 pipeline_line, protocol_line, schedule_line,
                                 snapshot_line, startup_line, summary_text,
                                 verify_line)

        out = summary_text(self._buffer.spans, self._step_spans,
                           sorted_by=sorted_by, op_detail=op_detail,
                           time_unit=time_unit, views=views)
        cache_line = dispatch_cache_line(dispatch_cache_stats())
        if cache_line:
            out = out + "\n" + cache_line
        comp = compile_stats()
        comp_line = compile_cache_line(comp)
        if comp_line:
            out = (out + "\n" + comp_line + "\n"
                   + startup_line(startup_stats(), comp["by_program"]))
        dec_line = decode_line(decode_stats())
        if dec_line:
            out = out + "\n" + dec_line
        lr_line = lora_line(lora_stats())
        if lr_line:
            out = out + "\n" + lr_line
        ver_line = verify_line(verify_stats())
        if ver_line:
            out = out + "\n" + ver_line
        ml_line = mesh_line(mesh_lint_stats())
        if ml_line:
            out = out + "\n" + ml_line
        pr_line = protocol_line(protocol_lint_stats())
        if pr_line:
            out = out + "\n" + pr_line
        sched_line = schedule_line(schedule_search_stats())
        if sched_line:
            out = out + "\n" + sched_line
        ckpt_line = checkpoint_line(checkpoint_stats())
        if ckpt_line:
            out = out + "\n" + ckpt_line
        snap_line = snapshot_line(snapshot_stats())
        if snap_line:
            out = out + "\n" + snap_line
        cl_line = cluster_line(cluster_stats())
        if cl_line:
            out = out + "\n" + cl_line
        pp_line = pipeline_line(pipeline_stats())
        if pp_line:
            out = out + "\n" + pp_line
        print(out)
        return out


def export_chrome_tracing(profiler, path: str | None = None):
    """Write `profiler`'s host spans to `path` as chrome-trace JSON; each
    event's `args` hold its parent span and its keyword arguments (`rid`).

    Called with a directory name instead (reference
    profiler.export_chrome_tracing(dir_name)), returns an `on_trace_ready`
    handler that writes `<dir_name>/host_spans.json` when the Profiler
    stops; the Profiler puts its device trace under the same directory."""
    if isinstance(profiler, (str, os.PathLike)):
        dir_name = os.fspath(profiler)

        def handle(prof):
            export_chrome_tracing(prof, os.path.join(dir_name, "host_spans.json"))

        handle.dir_name = dir_name
        return handle
    events = []
    for s in profiler._buffer.spans:
        events.append(
            {
                "name": s.name,
                "cat": s.category,
                "ph": "X",
                "ts": s.start_ns / 1e3,
                "dur": (s.end_ns - s.start_ns) / 1e3,
                "pid": 0,
                "tid": s.tid % 10_000,
                "args": {"parent": s.parent, **s.args},
            }
        )
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f, default=str)  # a rid may be any object
    return path


class SortedKeys:
    """Summary-table sort keys (reference:
    python/paddle/profiler/profiler_statistic.py SortedKeys)."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView:
    """Summary view selector (reference: profiler.py SummaryView)."""

    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


__all__ += ["SortedKeys", "SummaryView"]


def dispatch_cache_stats(reset: bool = False) -> dict:
    """Counters of the eager dispatch fast path (FLAGS_eager_op_jit):
    hits / misses / traces / evictions / bypasses plus size, capacity and
    whether the path is enabled.  `reset=True` zeroes the counters (cached
    entries stay).  A healthy steady-state training loop shows hits
    dominating with traces flat; climbing traces mean shape/dtype churn is
    defeating the cache."""
    from paddle_tpu._core import dispatch

    stats = dispatch.cache.stats()
    if reset:
        dispatch.cache.reset_stats()
    return stats


def reset_dispatch_cache():
    """Drop every cached dispatch entry and zero the counters."""
    from paddle_tpu._core import dispatch

    dispatch.cache.clear()
    dispatch.cache.reset_stats()


def decode_stats(reset: bool = False) -> dict:
    """Serving decode counters (paddle_tpu.serving): compiled-program
    dispatches, emitted tokens, host sync seconds (time blocked
    materializing device results), total step seconds and derived
    tokens_per_sec.  Macro-step decoding (FLAGS_decode_chunk > 1) shows
    tokens >> dispatches; tokens ~= dispatches means every token pays a
    host round-trip (the per-token path).  Also the prefix-cache tier
    (FLAGS_prefix_cache): prefix_hits/_misses per admission,
    prefix_hit_tokens (prompt tokens whose prefill was avoided by page
    reuse), prefix_evictions (LRU reclaims under pool pressure); and the
    capacity tier: pool_bytes of the most recent engine, resident_peak
    concurrently-active requests, and derived pool_bytes_per_resident —
    the number int8 KV pools (FLAGS_kv_cache_dtype) roughly halve.
    The overload-discipline tier (docs/DECODE.md admission scheduler):
    prefill_chunks (interleaved block-sized prefill chunks run between
    decode dispatches), preemptions / preempt_readmits (LOW-priority
    parking traffic), parked_requests (a GAUGE of the live parking lot,
    preserved across resets like the LoRA slot gauges), and the
    per-SLO-class admitted_/completed_{high,normal,low} breakdown.
    The admission split (committed atomic admissions only): admissions,
    admit_seconds and its phases admit_{match,prefill,first_token,pour}
    _seconds — each taken at the boundary its `serving.admit.*` span
    marks — admit_eager_ops (op-funnel calls of the prefill forward), and
    queued_admissions / queue_wait_seconds (submit -> the attempt that
    committed, for requests that waited in the pending queue).
    Zeros when no engine ran.  Serving owns the counters — one schema,
    no drift."""
    from paddle_tpu import serving

    return serving.decode_stats(reset=reset)


def lora_stats(reset: bool = False) -> dict:
    """Multi-tenant LoRA serving counters (paddle_tpu.serving + nn/lora.py,
    docs/LORA.md): adapter slots resident/total on the most recent pack
    engine, hot swaps (adapter installs into a slot) and evictions, decode
    dispatches that gathered per-row adapter A/B from the pack, and
    prefix-cache slot-epoch bumps (each invalidates exactly one slot's
    cached subtree).  Zeros when no adapter engine ran.  The serving
    module owns the counters — one schema, no drift."""
    from paddle_tpu import serving

    return serving.lora_stats(reset=reset)


def compile_stats(reset: bool = False) -> dict:
    """The compile ledger of this process (fed by jax.monitoring; every key
    is explained in _core.compile_cache's docstring): traces, trace_seconds;
    lowerings, lower_seconds (no cache holds a lowering); compiles,
    compile_seconds and its two ends cache_read_seconds (hits) and
    compile_miss_seconds (XLA really compiled); nested_seconds;
    persistent_cache_hits / _misses; by_program, the same by program name
    (`jit_decode_macro_step`: the form a device trace shows); cache_dir.
    Added here, summed over the rows of PROGRAM_NAMES alone (the programs
    the framework's hot path builds; a reference's eager pieces or a
    caller's own jitted function do not count): framework_compiles,
    framework_lower_seconds, framework_compile_seconds.  A warm start
    (TrainStep.warmup + FLAGS_compilation_cache_dir) shows hits with
    near-zero compile_miss_seconds; climbing framework_compiles in steady
    state mean signature churn is defeating jax's executable cache, and
    by_program says whose."""
    stats = _compile_cache.compile_stats()
    rows = [stats["by_program"][n] for n in PROGRAM_NAMES
            if n in stats["by_program"]]
    stats["framework_compiles"] = sum(r["compiles"] for r in rows)
    stats["framework_lower_seconds"] = sum(r["lower_seconds"] for r in rows)
    stats["framework_compile_seconds"] = sum(r["compile_seconds"] for r in rows)
    if reset:
        _compile_cache.reset_compile_stats()
    return stats


def verify_stats(reset: bool = False) -> dict:
    """Static-IR verify-mode counters (FLAGS_verify_programs; see
    static/verify.py and docs/VERIFIER.md): programs verified/failed,
    violations found, abstract-eval skips, differential checks run/failed,
    and pattern rewrites the use-def guard refused.  A healthy verified run
    shows failures and violations at zero; non-zero rewrites_refused means
    a fusion pattern tried to consume a value the program still needs."""
    from paddle_tpu.static import verify as _verify

    return _verify.verify_stats(reset=reset)


def mesh_lint_stats(reset: bool = False) -> dict:
    """Mesh-lint counters (FLAGS_verify_sharding; see static/mesh_lint.py
    and docs/MESH_LINT.md): entries linted (programs + train steps +
    serving engines) and failed, violations found, collectives and
    sharding constraints congruence-checked, tensor placements validated,
    donation-contract checks, per-device memory estimates computed, and
    op fns the abstract tracer had to skip.  A healthy verified run shows
    failed and violations at zero; nonzero means a placement/collective/
    donation hazard reached a build path — the raised MeshLintError names
    the site.  The mesh_lint module owns the counters — one schema, no
    drift."""
    from paddle_tpu.static import mesh_lint as _ml

    return _ml.mesh_lint_stats(reset=reset)


def protocol_lint_stats(reset: bool = False) -> dict:
    """Protocol-lint counters (see static/protocol_lint.py and
    docs/PROTOCOL_LINT.md): model-check scenarios run, abstract-cluster
    states and transitions explored, per-state invariant evaluations,
    violations and deadlocks found, plus the blocking-call AST pass
    (files linted, functions scanned, blocking call sites classified).
    A healthy run shows violations and deadlocks at zero — nonzero means
    an interleaving of the abstract router/replica/prefill/standby model
    broke a named invariant of serving/protocol.py (the raised
    ProtocolLintError carries the minimal counterexample trace) or a
    wait escaped retry_backoff's shared-deadline discipline.  The
    protocol_lint module owns the counters — one schema, no drift."""
    from paddle_tpu.static import protocol_lint as _pl

    return _pl.protocol_lint_stats(reset=reset)


def schedule_search_stats(reset: bool = False) -> dict:
    """Pallas schedule-search counters (FLAGS_schedule_search; see
    static/schedule_search.py and docs/SCHEDULE_SEARCH.md): subgraphs
    discovered and searched, candidate tilings enumerated, candidates
    pruned by the roofline model vs the VMEM budget, candidates
    measured on device, subgraphs accepted
    (schedule beat XLA by the win margin) vs disabled, and cache service
    (accepted configs / disabled skips reloaded from the per-device
    autotune cache).  Steady state shows cache hits with measured flat —
    climbing measured means shape churn is defeating the schedule cache.
    The schedule_search module owns those counters — one schema, no
    drift."""
    from paddle_tpu.static import schedule_search as _ss

    return _ss.schedule_search_stats(reset=reset)


def snapshot_stats(reset: bool = False) -> dict:
    """Live-engine snapshot counters (serving/snapshot.py,
    docs/CHECKPOINT.md serving section): engine snapshots saved and
    restored, bytes committed through the atomic protocol, seconds spent
    capturing+committing, torn snapshots skipped while resolving the
    newest restorable state, and drain() migrations.  Healthy:
    corrupt_skipped at zero (nonzero means a kill landed mid-commit and
    auto-restore passed over the torn dir — by design, but worth
    knowing).  The serving module owns the counters — one schema, no
    drift."""
    from paddle_tpu import serving

    return serving.snapshot_stats(reset=reset)


def cluster_stats(reset: bool = False) -> dict:
    """Disaggregated serving-cluster counters (serving/cluster.py,
    docs/SERVING_CLUSTER.md): live decode replicas (a gauge), heartbeat
    periods missed across the fleet, requests re-dispatched after a
    replica death or drain, KV pages (and wire bytes) shipped
    prefill->decode, retries on the shipping path, and queued requests
    migrated by graceful drains.  Healthy steady state shows
    heartbeats_missed and redispatches flat; climbing redispatches means
    replicas are dying faster than they respawn.  The warm-start tier
    adds standbys_warm (gauge of ready standbys), promotions (standbys
    that took a dead replica's slot), warmups/warmup_seconds (worker AOT
    warm reports), and respawn_compile_hits/misses (the persistent
    compile-cache counters respawned workers reported at boot —
    hits > 0 is the warmed-respawn contract).  The cluster module owns
    the counters — one schema, no drift."""
    from paddle_tpu.serving import cluster as _cluster

    return _cluster.cluster_stats(reset=reset)


def pipeline_stats(reset: bool = False) -> dict:
    """Pipeline-schedule counters (fleet/meta_parallel/schedules.py,
    docs/PIPELINE.md): pipeline step programs built, scan ticks traced
    (forward + split-backward), F/B/W stage-microbatch slots, stage-ticks
    spent on warmup/drain bubble work, and collective-permute hops issued
    by comm/compute-overlap chains (ShardedTrainStep comm_overlap /
    overlap_grad_sync).  Counted when a program is built or dispatched
    from python — once per trace under a compiled TrainStep, per call in
    eager (the mesh-lint counter convention).  w_slots nonzero means a
    zero-bubble split-backward schedule (ZB-H1) is live.  The schedules
    module owns the counters — one schema, no drift."""
    from paddle_tpu.distributed.fleet.meta_parallel import schedules as _sched

    return _sched.pipeline_stats(reset=reset)


def checkpoint_stats(reset: bool = False) -> dict:
    """CheckpointManager counters (distributed/checkpoint/manager.py):
    saves issued (async_saves of them backgrounded), atomic commits,
    bytes written, seconds split into snapshot (synchronous device→host)
    vs write (background disk IO) vs backpressure (save() blocked on an
    in-flight write), GC deletions, restores, and checkpoints skipped as
    corrupt/torn during auto-resume.  Healthy: corrupt_skipped and errors
    at zero, backpressure near zero (writes finish inside the save
    interval).  The checkpoint module owns the counters — one schema, no
    drift."""
    from paddle_tpu.distributed.checkpoint import manager as _ckpt_manager

    return _ckpt_manager.checkpoint_stats(reset=reset)


__all__ += ["dispatch_cache_stats", "reset_dispatch_cache", "compile_stats",
            "startup_stats",
            "decode_stats", "lora_stats", "verify_stats", "mesh_lint_stats",
            "schedule_search_stats", "checkpoint_stats", "snapshot_stats",
            "cluster_stats", "pipeline_stats", "protocol_lint_stats"]


def _compile_and_analyze(fn, example_args):
    """jit-compile fn on the current backend and normalize its cost
    analysis (list vs dict across jax versions)."""
    import jax

    from paddle_tpu._core.tensor import Tensor

    vals = [a._value if isinstance(a, Tensor) else a for a in example_args]
    compiled = jax.jit(fn).lower(*vals).compile()
    analyses = compiled.cost_analysis()
    if isinstance(analyses, (list, tuple)):
        analyses = analyses[0] if analyses else {}
    return compiled, vals, dict(analyses or {})


def cost_analysis(fn, *example_args):
    """Compile `fn` for the current backend and return XLA's cost analysis
    (flops, bytes accessed, ...) — the per-op cost table the reference
    builds by profiling (python/paddle/cost_model/static_op_benchmark.json),
    here read straight from the compiler."""
    return _compile_and_analyze(fn, example_args)[2]


def estimate_mfu(fn, *example_args, runtime_s=None, peak_tflops=None):
    """Model-FLOPs-utilization report for a compiled step.

    flops come from XLA's cost analysis of the compiled executable;
    runtime_s (measured seconds per call; measured here with one timed call
    after warmup when omitted); peak from the device kind
    (device/peaks.py).  Returns {"flops", "runtime_s", "achieved_tflops",
    "peak_tflops", "mfu"} — mfu is 0.0 on CPU (no meaningful peak)."""
    import time

    import jax

    from paddle_tpu.device.peaks import device_peak_tflops

    compiled, vals, analyses = _compile_and_analyze(fn, example_args)
    flops = float(analyses.get("flops", 0.0))
    if runtime_s is None:
        # adaptive timer (readback-synced, differences two batch lengths
        # so the fixed dispatch + readback cost drops out — the same
        # methodology the kernel autotuner uses)
        from paddle_tpu.ops.autotune import _time_fn

        runtime_s = _time_fn(compiled, vals, iters=2) / 1e3
    d = jax.devices()[0]
    if peak_tflops is None:
        peak_tflops = device_peak_tflops(d.device_kind, d.platform)
    achieved = flops / runtime_s / 1e12 if runtime_s > 0 else 0.0
    mfu = achieved / peak_tflops if peak_tflops else 0.0
    return {
        "flops": flops,
        "runtime_s": runtime_s,
        "achieved_tflops": achieved,
        "peak_tflops": peak_tflops,
        "mfu": mfu,
    }


__all__ += ["cost_analysis", "estimate_mfu"]
