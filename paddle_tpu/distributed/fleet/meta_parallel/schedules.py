"""Pipeline schedules as DATA — registry, static simulator, engine lowering.

Reference counterpart: the schedule pass family
(python/paddle/distributed/passes/pipeline_scheduler_pass.py:47-566 —
FThenB / 1F1B variants selected as pass attributes, not hand-written
runtimes) plus the zero-bubble schedule literature (ZB-H1: split the
backward into a grad-INPUT pass B on the critical path and a deferred
grad-WEIGHT pass W that fills the warmup/drain bubbles, keeping 1F1B's
activation memory).

This module owns three faces of "a schedule":

1. **The table** — `Schedule.stage_programs(S, M)` returns, per stage, the
   ordered {F, B, W} slot sequence; `Schedule.table(S, M)` time-aligns it
   into the classic per-tick grid (unit slot costs).  This is the data the
   docs print and the simulator walks.
2. **The simulator** — `simulate(schedule, S, M, costs)` computes makespan,
   bubble fraction and peak activation residency from the table alone:
   CPU-falsifiable proof that ZB-H1's bubble is strictly below 1F1B's at
   equal (S, M) with NO residency growth (the W slots fill waits that
   1F1B's fused backward serializes), no TPU needed.  Slot dependencies:
   F(m,s) needs F(m,s-1); B(m,s) needs B(m,s+1) (or F(m,S-1) on the last
   stage); W(m,s) needs B(m,s).
3. **The engine plan** — `Schedule.engine_plan(S, M)` lowers the table to
   the int32 tick arrays (`b_tick`, `w_tick`) the SPMD split-backward scan
   in pipeline.py consumes.  The SPMD engine runs every stage in ONE
   program, so per-stage idle slots do not exist at runtime; what the plan
   encodes is the *deferral* structure: at backward tick r the scan
   executes the grad-input pass of forward tick `b_tick[r]` and the
   deferred grad-weight pass of forward tick `w_tick[r]` (-1 = none).  A
   future interleaved/VPP-zero-bubble schedule plugs in by registering new
   tables + plan — the scan body never changes.

Selection: `PipelineStack(schedule=None)` (and `pipeline_llama` /
`pipeline_gpt` / the `pipeline_scheduler` pass) resolves the schedule from
`FLAGS_pipeline_schedule`; a flags listener re-resolves flag-following
stacks and drops their cached built steps on change — the same contract
as FLAGS_decode_chunk for serving engines.

The module also owns the pipeline telemetry (`pipeline_stats()`, surfaced
through paddle_tpu.profiler like the serving/checkpoint counters) and the
comm/compute-overlap primitive `overlap_grad_sync` the sharded train step
uses to turn GSPMD's single fused grad all-reduce into a reduce-scatter +
explicit collective-permute all-gather chain XLA's latency-hiding
scheduler can interleave with compute (docs/PIPELINE.md).
"""

from __future__ import annotations

import weakref
from dataclasses import dataclass

from paddle_tpu._core import flags as _flags

__all__ = [
    "Costs", "SimResult", "Schedule", "register_schedule", "get_schedule",
    "available_schedules", "simulate", "pipeline_stats", "overlap_grad_sync",
]


# --------------------------------------------------------------------- costs
@dataclass(frozen=True)
class Costs:
    """Per-slot cost weights.  `f`/`b`/`w` are wall costs of the forward,
    grad-input, and grad-weight passes of ONE stage-microbatch; a FUSED
    backward slot (non-split schedules) costs b + w.  `w_residency` is the
    activation units a split B keeps alive (the stored boundary input +
    output cotangent) until its deferred W runs; a forward slot stores 1
    unit, a fused backward frees it entirely."""

    f: float = 1.0
    b: float = 1.0
    w: float = 1.0
    w_residency: float = 1.0


@dataclass(frozen=True)
class SimResult:
    makespan: float
    bubble_fraction: float      # 1 - useful_work / (S * makespan)
    peak_residency: float       # max over stages of live activation units
    stage_residency: tuple      # per-stage peaks
    total_work: float


# ------------------------------------------------------------------ schedules
class Schedule:
    """Base: a named schedule that can emit per-stage slot programs.

    split_backward=False means the backward is one fused slot (kind "B",
    cost b + w, frees the whole activation); True means B and W are
    separate slots and the engine runs the split-backward scan."""

    name: str = ""
    split_backward: bool = False

    def stage_programs(self, S, M):  # -> list[list[(kind, microbatch)]]
        raise NotImplementedError

    # ---- table: time-aligned per-tick grid (unit slot costs; fused B = 2)
    def table(self, S, M):
        """list of rows, one per tick; row[s] is 'F3'/'B1'/'W0'/'' — the
        classic pipeline diagram, derived from the same simulation the
        bubble numbers come from."""
        costs = Costs(1.0, 1.0, 1.0)
        start, _finish, makespan = _timings(self, S, M, costs)
        n_ticks = int(round(makespan))
        rows = [["" for _ in range(S)] for _ in range(n_ticks)]
        for (kind, m, s), t0 in start.items():
            dur = _slot_cost(kind, costs, self.split_backward)
            for dt in range(int(round(dur))):
                rows[int(round(t0)) + dt][s] = f"{kind}{m}"
        return rows

    # ---- engine lowering (consumed by the split-backward scan)
    def engine_plan(self, S, M):
        """int32 arrays driving the SPMD backward scan: at backward tick r
        run the grad-input pass of forward tick b_tick[r] and the deferred
        grad-weight pass of forward tick w_tick[r] (-1 = no slot).  The
        grad-input chain is ring-ordered (strict reverse forward-tick
        order); the schedule's freedom is the W deferral window."""
        if not self.split_backward:
            raise ValueError(
                f"schedule {self.name!r} has a fused backward; the engine "
                "plan exists only for split-backward schedules")
        T = M + S - 1
        D = self.engine_w_lag(S, M)
        TB = T + D
        b_tick = [T - 1 - r if r < T else -1 for r in range(TB)]
        w_tick = [T - 1 - (r - D) if D <= r < T + D else -1 for r in range(TB)]
        return {"T": T, "D": D, "TB": TB, "b_tick": b_tick, "w_tick": w_tick}

    def engine_w_lag(self, S, M) -> int:
        """Backward-tick deferral of each W slot behind its B slot."""
        raise NotImplementedError

    def bubble_fraction(self, S, M, costs: Costs = Costs()) -> float:
        return simulate(self, S, M, costs).bubble_fraction


class FThenB(Schedule):
    """GPipe: all forwards, then all (fused) backwards.  Fewest recompute
    FLOPs, every stage's activations live through the whole forward."""

    name = "FThenB"

    def stage_programs(self, S, M):
        return [[("F", m) for m in range(M)] + [("B", m) for m in range(M)]
                for _ in range(S)]


class OneFOneB(Schedule):
    """1F1B: warmup of S - s forwards, then strict one-forward-one-backward
    (fused) steady state.  Peak activation residency S - s per stage."""

    name = "1F1B"

    def stage_programs(self, S, M):
        out = []
        for s in range(S):
            warm = min(S - s, M)
            prog = [("F", m) for m in range(warm)]
            nf, nb = warm, 0
            while nb < M:
                prog.append(("B", nb))
                nb += 1
                if nf < M:
                    prog.append(("F", nf))
                    nf += 1
            out.append(prog)
        return out


class ZBH1(Schedule):
    """ZB-H1 zero-bubble: the backward splits into B (grad-input, critical
    path — it feeds the upstream stage) and W (grad-weight, off-path).  A
    stage runs B the moment it is ready, keeps at most the 1F1B warmup
    count of activations in flight, and fills every wait with a pending W
    — the memory-neutral member of the zero-bubble family (peak residency
    equals 1F1B's S - s by construction; the greedy below enforces it as
    a hard cap)."""

    name = "ZB-H1"
    split_backward = True

    def stage_programs(self, S, M):
        # Greedy discrete-event construction with unit costs.  Priority at
        # each stage decision point: B if ready now, else F if ready now
        # and the memory cap (in-flight acts + pending W residuals + 1 <=
        # S - s) allows, else a pending W, else idle to the next dep event.
        costs = Costs(1.0, 1.0, 1.0)
        progs = [[] for _ in range(S)]
        t_free = [0.0] * S
        nf = [0] * S            # next forward microbatch per stage
        nb = [0] * S            # next backward microbatch per stage
        wq = [[] for _ in range(S)]  # pending W microbatches (FIFO)
        finish = {}             # (kind, m, s) -> finish time

        def dep(kind, m, s):
            if kind == "F":
                return finish.get(("F", m, s - 1), 0.0) if s > 0 else 0.0
            if kind == "B":
                key = ("F", m, s) if s == S - 1 else ("B", m, s + 1)
                return finish.get(key)
            return finish.get(("B", m, s))  # W

        def put(kind, m, s, start):
            c = {"F": costs.f, "B": costs.b, "W": costs.w}[kind]
            progs[s].append((kind, m))
            finish[(kind, m, s)] = start + c
            t_free[s] = start + c

        total = 3 * M  # F + B + W slots per stage
        while any(len(progs[s]) < total for s in range(S)):
            progressed = False
            for s in range(S):
                while len(progs[s]) < total:
                    t = t_free[s]
                    cap = S - s
                    live = (nf[s] - nb[s]) + len(wq[s]) * costs.w_residency
                    b_dep = dep("B", nb[s], s) if nb[s] < M else None
                    f_dep = dep("F", nf[s], s) if nf[s] < M else None
                    if b_dep is not None and b_dep <= t:
                        put("B", nb[s], s, t)
                        wq[s].append(nb[s])
                        nb[s] += 1
                    elif (f_dep is not None and f_dep <= t
                          and live + 1 <= cap):
                        put("F", nf[s], s, t)
                        nf[s] += 1
                    elif wq[s]:
                        put("W", wq[s].pop(0), s, t)
                    else:
                        # idle until the earliest known dep event
                        events = [d for d in (b_dep, f_dep)
                                  if d is not None and d > t]
                        if not events:
                            break  # dep not scheduled yet: other stages first
                        t_free[s] = min(events)
                        continue
                    progressed = True
            if not progressed:
                raise RuntimeError(
                    f"ZB-H1 schedule construction deadlocked at S={S}, M={M}")
        return progs

    def engine_w_lag(self, S, M) -> int:
        # The SPMD scan has one uniform timeline; the W deferral window is
        # the worst-case table lag — stage 0 may hold a W through the whole
        # drain, i.e. S - 1 backward ticks (>= 1 so deferred accumulation
        # is structurally exercised even at S == 1... S >= 2 in practice).
        return max(1, S - 1)


# ------------------------------------------------------------------- registry
_REGISTRY: dict = {}


def register_schedule(cls):
    inst = cls()
    if not inst.name:
        raise ValueError("schedule class needs a name")
    _REGISTRY[inst.name] = inst
    return cls


def get_schedule(name: str) -> Schedule:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown pipeline schedule {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def available_schedules():
    return sorted(_REGISTRY)


for _cls in (FThenB, OneFOneB, ZBH1):
    register_schedule(_cls)


def resolve_schedule_flag() -> str:
    """FLAGS_pipeline_schedule -> a registered schedule name (loud on a
    typo: a silently ignored schedule flag would fake a perf win)."""
    name = str(_flags.flag("FLAGS_pipeline_schedule"))
    get_schedule(name)
    return name


# ------------------------------------------------------------------ simulator
def _slot_cost(kind, costs: Costs, split: bool) -> float:
    if kind == "F":
        return costs.f
    if kind == "B":
        return costs.b if split else costs.b + costs.w
    return costs.w


def _timings(schedule: Schedule, S, M, costs: Costs):
    """Fixed-point slot timing for the schedule's per-stage programs.
    Start times are uniquely determined by per-stage order + cross-stage
    deps (longest path over a DAG), so iteration order cannot change the
    result."""
    programs = schedule.stage_programs(S, M)
    split = schedule.split_backward
    start, finish = {}, {}
    ptr = [0] * S
    t_free = [0.0] * S

    def dep_time(kind, m, s):
        if kind == "F":
            return finish.get(("F", m, s - 1), 0.0) if s > 0 else 0.0
        if kind == "B":
            key = ("F", m, s) if s == S - 1 else ("B", m, s + 1)
            return finish.get(key)
        return finish.get(("B", m, s))

    remaining = sum(len(p) for p in programs)
    while remaining:
        progressed = False
        for s in range(S):
            while ptr[s] < len(programs[s]):
                kind, m = programs[s][ptr[s]]
                d = dep_time(kind, m, s)
                if d is None:
                    break
                t0 = max(t_free[s], d)
                start[(kind, m, s)] = t0
                finish[(kind, m, s)] = t0 + _slot_cost(kind, costs, split)
                t_free[s] = finish[(kind, m, s)]
                ptr[s] += 1
                remaining -= 1
                progressed = True
        if not progressed:
            stuck = {st: programs[st][ptr[st]] for st in range(S)
                     if ptr[st] < len(programs[st])}
            raise RuntimeError(
                f"schedule {schedule.name!r} has a dependency cycle at "
                f"S={S}, M={M} (stuck slots per stage: {stuck})")
    return start, finish, max(finish.values(), default=0.0)


def simulate(schedule, S, M, costs: Costs = Costs()) -> SimResult:
    """Static evaluation of a schedule's table: makespan, bubble fraction,
    peak per-stage activation residency.  Pure host math — a model of the
    schedule, not a measurement (no pipeline has been timed on a chip; see
    ROADMAP S6)."""
    if isinstance(schedule, str):
        schedule = get_schedule(schedule)
    programs = schedule.stage_programs(S, M)
    split = schedule.split_backward
    start, _finish, makespan = _timings(schedule, S, M, costs)

    peaks = []
    for s in range(S):
        order = sorted(programs[s], key=lambda km: start[(km[0], km[1], s)])
        live, peak = 0.0, 0.0
        for kind, _m in order:
            if kind == "F":
                live += 1.0
            elif kind == "B":
                live -= 1.0
                if split:
                    live += costs.w_residency
            else:  # W
                live -= costs.w_residency
            peak = max(peak, live)
        peaks.append(peak)

    per_stage_work = M * (costs.f + costs.b + costs.w)
    total = S * per_stage_work
    bubble = 1.0 - total / (S * makespan) if makespan else 0.0
    return SimResult(makespan=makespan, bubble_fraction=bubble,
                     peak_residency=max(peaks), stage_residency=tuple(peaks),
                     total_work=total)


# ------------------------------------------------------------------ telemetry
_STATS = {
    "programs": 0,        # pipeline step programs built/dispatched
    "ticks": 0,           # scan ticks traced (fwd + split-bwd)
    "f_slots": 0,         # stage-microbatch forward slots
    "b_slots": 0,         # grad-input slots (split) or fused backward slots
    "w_slots": 0,         # deferred grad-weight slots (split schedules only)
    "bubble_ticks": 0,    # stage-ticks spent on warmup/drain bubble work
    "overlap_issued": 0,  # collective-permute hops issued by overlap chains
}


def pipeline_stats(reset: bool = False) -> dict:
    """Counters of the pipeline-schedule subsystem (this module owns them —
    one schema, no drift; surfaced via paddle_tpu.profiler.pipeline_stats
    and the Profiler.summary() "Pipeline:" footer).  Counted when a
    pipeline step is BUILT/dispatched from python (once per trace under a
    compiled TrainStep, per call in eager), like the mesh-lint counters."""
    out = dict(_STATS)
    if reset:
        for k in _STATS:
            _STATS[k] = 0
    return out


def _count_program(schedule_name, S, M, n_virtual=1):
    sched = _REGISTRY.get(schedule_name)
    T = M * n_virtual + S - 1
    _STATS["programs"] += 1
    _STATS["f_slots"] += S * M
    _STATS["b_slots"] += S * M
    ticks = T
    if sched is not None and sched.split_backward:
        plan = sched.engine_plan(S, M)
        ticks += plan["TB"]
        _STATS["w_slots"] += S * M
        _STATS["bubble_ticks"] += S * (T - M) + S * (plan["TB"] - M)
    else:
        # fused backward replays the T ticks in reverse (scan transpose)
        ticks += T
        _STATS["bubble_ticks"] += 2 * S * (T - M)
    _STATS["ticks"] += ticks


# ------------------------------------------------- flag-following stacks
_STACKS: "weakref.WeakSet" = weakref.WeakSet()


def register_stack(stack):
    _STACKS.add(stack)


@_flags.on_change
def _on_flag_change(changed):
    # Same contract as FLAGS_decode_chunk for serving engines: any stack
    # that follows the flag re-resolves its schedule and drops every cached
    # built step (the eager dispatch cache is cleared by its own listener).
    if "FLAGS_pipeline_schedule" not in changed:
        return
    try:
        resolve_schedule_flag()
    except ValueError:
        # invalid value: a listener must not blow up set_flags mid-walk —
        # existing stacks keep their schedule; the loud error fires where
        # the flag is actually consumed (new stack construction / resolve)
        return
    for stack in list(_STACKS):
        stack._on_schedule_flag_change()


# --------------------------------------------- comm/compute overlap primitive
def overlap_grad_sync(val, mesh, axis: str):
    """Decompose a GSPMD-fused gradient all-reduce into reduce-scatter +
    an explicit ring all-gather of (axis_size - 1) collective-permute hops.

    `val` is a gradient already summed over `axis` semantically (the loss
    runs over the axis-sharded batch in one program); GSPMD would
    materialize one fused all-reduce right before every use.  Constraining
    the value to be axis-sharded makes XLA emit the reduce-scatter half,
    and the ppermute chain rebuilds the replicated value hop by hop — each
    hop is an independent async collective the latency-hiding scheduler
    can overlap with the optimizer math of already-arrived chunks (and,
    under a ZB pipeline, with the W-pass ticks it does not depend on).
    Values are bit-identical to the fused all-reduce (a gather of shards
    reassociates nothing).

    Returns `val` unchanged when the axis is absent/size-1 or no dim is
    divisible by it.  Statically checkable by the mesh lint: the chain is
    a plain shard_map over `axis` with a full-permutation ppermute.
    """
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec

    from paddle_tpu.distributed.shard_map_compat import shard_map

    jmesh = getattr(mesh, "jax_mesh", mesh)
    if axis not in jmesh.axis_names:
        return val
    n = int(dict(jmesh.shape)[axis])
    if n <= 1 or getattr(val, "ndim", 0) == 0:
        return val
    # shard the largest divisible dim
    dims = sorted(range(val.ndim), key=lambda d: -val.shape[d])
    dim = next((d for d in dims if val.shape[d] % n == 0 and val.shape[d] >= n),
               None)
    if dim is None:
        return val

    spec = [None] * val.ndim
    spec[dim] = axis
    val = lax.with_sharding_constraint(
        val, NamedSharding(jmesh, PartitionSpec(*spec)))

    c = val.shape[dim] // n
    ring = [(r, (r + 1) % n) for r in range(n)]

    def ring_allgather(block):
        import jax.numpy as jnp

        idx = lax.axis_index(axis)
        out_shape = list(block.shape)
        out_shape[dim] = n * c
        out = jnp.zeros(out_shape, block.dtype)

        def place(buf, blk, slot):
            starts = [0] * blk.ndim
            starts[dim] = slot * c
            return lax.dynamic_update_slice(buf, blk, starts)

        out = place(out, block, idx)

        def hop(carry, i):
            blk, buf = carry
            blk = lax.ppermute(blk, axis, ring)
            src = (idx - i - 1) % n
            buf = place(buf, blk, src)
            return (blk, buf), None

        (_, out), _ = lax.scan(hop, (block, out),
                               jnp.arange(n - 1, dtype=jnp.int32))
        return out

    _STATS["overlap_issued"] += n - 1
    in_spec = PartitionSpec(*spec)
    # check_vma off: after n-1 hops every rank holds every block, which
    # jax's varying-axes inference cannot see through the ppermute chain
    return shard_map(ring_allgather, mesh=jmesh, in_specs=(in_spec,),
                     out_specs=PartitionSpec(), axis_names={axis},
                     check_vma=False)(val)
