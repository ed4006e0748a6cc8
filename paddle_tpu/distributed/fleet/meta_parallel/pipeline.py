"""Pipeline parallelism — SPMD pipeline engine over a 'pp' mesh axis.

Reference counterpart: fleet PipelineLayer partitioning
(python/paddle/distributed/fleet/meta_parallel/parallel_layers/pp_layers.py:237,
SegmentLayers:92) + the 1F1B runtime engine
(meta_parallel/pipeline_parallel.py:648 train_batch, :431
forward_backward_pipeline) + p2p send/recv
(pp_utils/p2p_communication.py:313,512) + the schedule pass family
(python/paddle/distributed/passes/pipeline_scheduler_pass.py:47-566 —
FThenB / 1F1B variants as data, not code).

TPU-native redesign: instead of per-rank processes exchanging activations
over NCCL p2p with a hand-written fwd/bwd interleave, the pipeline is ONE
SPMD program:

- The N identical blocks' parameters are stacked [n_stages, layers_per_stage,
  ...] and sharded over the 'pp' mesh axis — each stage's weights live on its
  own devices, like the reference's per-rank layer partition.
- The microbatch rotation is a single `lax.scan` over T = M + S - 1 ticks
  inside shard_map (manual over 'pp' only; dp/mp stay GSPMD-auto); per tick
  each stage computes its chunk and the boundary activation hops one stage
  via lax.ppermute on ICI — the p2p_communication.py equivalent.  scan keeps
  compile time independent of the microbatch count (the unrolled round-1
  engine retraced every tick).
- Schedules are DATA, selecting the autodiff memory profile:
  * "1F1B" (default): each tick's stage computation is wrapped in
    jax.checkpoint, so the forward stores only the per-tick boundary
    activations; the backward then recomputes one stage-tick and
    backpropagates it, tick by tick in reverse — the bounded-activation
    1F1B profile (peak residency: boundary tensors + ONE stage's
    activations), without hand-writing the backward schedule.
  * "FThenB": no per-tick checkpoint; XLA stores every stage's internals for
    the whole forward (GPipe memory, fewest recompute FLOPs).
  The bubble fraction (S-1)/(M+S-1) is schedule-intrinsic and identical for
  both — raise num_microbatches to shrink it.
- Activation recompute per layer (use_recompute=True, jax.checkpoint inside
  the stage) replaces the reference's RecomputeFunction inside stages.

Constraints (same as the reference's uniform SegmentLayers path): all TRUNK
blocks structurally identical, block output shape == input shape, and
len(blocks) % pp_degree == 0.  num_microbatches may exceed the stage count
(steady-state 1F1B, reference pipeline_parallel.py:431) — it must divide the
batch.

Non-uniform stages (reference SegmentLayers:92 puts embedding on the first
stage and the head on the last): `first_stage` / `last_stage` layers ride
the same SPMD program guarded by `lax.cond(stage == 0 / S-1, ...)`, so the
embedding runs only where stage 0's devices execute and the head only on the
last stage — the cond keeps the FLOPs off the other stages at runtime.  The
ring still carries the uniform trunk activation; the input buffer holds the
raw model input (e.g. token ids) and the output buffer the head's output
(e.g. logits), whose shapes may both differ from the trunk activation.
Cost-weighted trunk segmentation (SegmentLayers seg_method="uniform"/
param-weighted) degenerates to uniform here because trunk blocks are
structurally identical — the heterogeneity LLMs actually have (embedding/
head) is exactly what first_stage/last_stage carry; `segment_layers` below
keeps the reference's cut algorithm available for planner parity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from paddle_tpu.distributed.shard_map_compat import shard_map
from jax.sharding import NamedSharding, PartitionSpec

from paddle_tpu._core.autograd import apply, no_grad
from paddle_tpu._core.tensor import Parameter, Tensor
from paddle_tpu.nn import Layer


def _pvary(x, axes):
    # jax>=0.9 renames pvary -> pcast(..., to='varying'); support both.
    # jax<0.6 has neither AND no varying-manual-axes type system — there
    # shard_map(check_rep=False) accepts replicated values directly, so the
    # cast is correctly a no-op.
    # Idempotent: values already varying over the axes pass through — but
    # only that case; any other ValueError (bad axis name, bad to=) raises.
    try:
        if hasattr(lax, "pcast"):
            return lax.pcast(x, axes, to="varying")
        if hasattr(lax, "pvary"):
            return lax.pvary(x, axes)
        return x
    except ValueError as e:
        if "from=varying" in str(e) or "already" in str(e):
            return x
        raise

__all__ = ["PipelineStack", "segment_layers", "pipeline_parallel"]

# "VPP" is engine-structural (circular token ring); the rest live in the
# schedules registry (fleet/meta_parallel/schedules.py) — ZB-H1 selects the
# split-backward scan pair below.
_SCHEDULES = ("1F1B", "FThenB", "VPP", "ZB-H1")


def segment_layers(weights, num_stages, method: str = "uniform"):
    """Cut a heterogeneous layer list into pipeline stages (reference
    SegmentLayers, fleet pp_layers.py:92): returns num_stages+1 cut points.

    method="uniform": equal layer counts (remainder spread to the front);
    method="param" (reference seg_method="layer:..."/parameter-weighted):
    balance the per-stage sum of `weights` (e.g. parameter counts) greedily
    along the prefix-sum, the reference's segment_parts strategy."""
    n = len(weights)
    if num_stages < 1 or n < num_stages:
        raise ValueError(f"cannot cut {n} layers into {num_stages} stages")
    if method == "uniform":
        base, rem = divmod(n, num_stages)
        cuts = [0]
        for s in range(num_stages):
            cuts.append(cuts[-1] + base + (1 if s < rem else 0))
        return cuts
    if method == "param":
        total = float(sum(weights))
        prefix = [0.0]
        for w in weights:
            prefix.append(prefix[-1] + float(w))
        cuts = [0]
        for s in range(1, num_stages):
            target = total * s / num_stages
            # closest prefix point that keeps at least one layer per stage
            lo, hi = cuts[-1] + 1, n - (num_stages - s)
            best = min(range(lo, hi + 1), key=lambda i: abs(prefix[i] - target))
            cuts.append(best)
        cuts.append(n)
        return cuts
    raise ValueError(f"unknown segment method {method!r}")


class PipelineStack(Layer):
    """Replaces a LayerList of identical blocks with a pipelined stack.

    schedule="VPP" (interleaved virtual pipeline, reference
    PipelineParallelWithInterleave pipeline_parallel.py:890 + the VPP
    scheduler pass): each device owns `num_virtual_stages` non-contiguous
    layer chunks (chunk c on device c % S) and the rotation is a circular
    token ring — each device carries ONE (microbatch, chunk) token per tick,
    device 0 injects a fresh microbatch whenever a completed token returns.
    T = M*v + S - 1 ticks, so the bubble shrinks v-fold to
    (S-1)/(M*v + S-1) at the cost of v x more ppermute hops — the VPP
    trade exactly."""

    def __init__(self, blocks, mesh, pp_axis: str = "pp", num_microbatches=None,
                 use_recompute: bool = False, schedule: str = None,
                 num_virtual_stages: int = 1, first_stage=None, last_stage=None):
        super().__init__()
        from paddle_tpu.distributed.auto_parallel import ProcessMesh
        from paddle_tpu.distributed.auto_parallel.api import placements_to_spec

        from . import schedules as _schedules

        # schedule=None follows FLAGS_pipeline_schedule; the schedules-module
        # flag listener re-resolves such stacks on set_flags (and drops their
        # cached built steps) — the FLAGS_decode_chunk contract.
        self._follow_flag = schedule is None
        if schedule is None:
            schedule = _schedules.resolve_schedule_flag()
        if schedule not in _SCHEDULES:
            raise ValueError(f"schedule must be one of {_SCHEDULES}, got {schedule!r}")
        self._fn_cache = {}
        _schedules.register_stack(self)
        blocks = list(blocks)
        if not blocks:
            raise ValueError("PipelineStack needs at least one block")
        if not isinstance(mesh, ProcessMesh):
            mesh = ProcessMesh(mesh)
        self._mesh = mesh
        self._pp_axis = pp_axis
        self._n_stages = mesh.get_dim_size(pp_axis)
        self._n_layers = len(blocks)
        self._n_virtual = int(num_virtual_stages) if schedule == "VPP" else 1
        if self._n_virtual < 1:
            raise ValueError("num_virtual_stages must be >= 1")
        n_chunks = self._n_stages * self._n_virtual
        if self._n_layers % n_chunks != 0:
            raise ValueError(
                f"{self._n_layers} blocks not divisible into {n_chunks} "
                f"chunks ({self._n_stages} stages x {self._n_virtual} virtual)"
            )
        self._layers_per_stage = self._n_layers // self._n_stages
        if num_microbatches is not None and num_microbatches < 1:
            raise ValueError(f"num_microbatches must be >= 1, got {num_microbatches}")
        self._num_microbatches = num_microbatches
        self._use_recompute = use_recompute
        self._schedule = schedule

        # first/last stage extras (embedding / head): NOT registered as
        # sublayers — their params stay registered wherever the caller keeps
        # them (so optimizers see each exactly once); forward() threads the
        # same Tensor objects through the tape, which routes their grads.
        object.__setattr__(self, "_first", first_stage)
        object.__setattr__(self, "_last", last_stage)
        self._first_tensors = list(first_stage.state_dict().values()) if first_stage else []
        self._last_tensors = list(last_stage.state_dict().values()) if last_stage else []

        # Template block: bypass Layer registration so its params stay out of
        # this layer's state_dict (they become dead storage bound over by the
        # traced stage function).
        object.__setattr__(self, "_template", blocks[0])
        tpl_state = blocks[0].state_dict()
        self._keys = list(tpl_state.keys())
        self._tpl_tensors = [tpl_state[k] for k in self._keys]

        states = [b.state_dict() for b in blocks]
        for st in states:
            if list(st.keys()) != self._keys:
                raise ValueError("pipeline blocks must be structurally identical")

        jmesh = mesh.jax_mesh
        S, Lps, v = self._n_stages, self._layers_per_stage, self._n_virtual
        # VPP block order: device d holds chunks {d, S+d, 2S+d, ...}; its
        # local [v, Lpc] layout maps (j, i) -> block (j*S + d)*Lpc + i.
        # v == 1 reduces to the contiguous [S, Lps] split.
        lpc = Lps // v
        order = [
            (j * S + d) * lpc + i
            for d in range(S)
            for j in range(v)
            for i in range(lpc)
        ]
        for key, tpl in zip(self._keys, self._tpl_tensors):
            vals = [states[b][key]._value for b in order]
            stacked = jnp.stack(vals).reshape((S, Lps) + vals[0].shape)
            if getattr(tpl, "process_mesh", None) is not None and tpl.placements:
                block_spec = list(placements_to_spec(tpl.process_mesh, tpl.placements))
            else:
                block_spec = []
            spec = PartitionSpec(pp_axis, None, *block_spec)
            stacked = jax.device_put(stacked, NamedSharding(jmesh, spec))
            p = Parameter(stacked, trainable=not tpl.stop_gradient)
            p.stop_gradient = tpl.stop_gradient
            self.add_parameter(self._mangle(key), p)

    @staticmethod
    def _mangle(key: str) -> str:
        return "stacked__" + key.replace(".", "__")

    def stacked_parameters(self):
        return [self._parameters[self._mangle(k)] for k in self._keys]

    def bubble_fraction(self, num_microbatches=None) -> float:
        """Pipeline bubble (S-1)/(M*v + S-1) — reference pipeline math; the
        interleaved factor v divides the bubble (pipeline_parallel.py:890)."""
        m = num_microbatches or self._num_microbatches or self._n_stages
        return (self._n_stages - 1) / (m * self._n_virtual + self._n_stages - 1)

    def _edge_call(self, layer, tensors):
        """Traced call of a first/last stage layer: bind the incoming traced
        param values over the layer's tensors, run it, restore."""
        def call(h_val, vals):
            originals = [t._value for t in tensors]
            try:
                for t, v in zip(tensors, vals):
                    t._bind(v)
                with no_grad():
                    out = layer(Tensor(h_val))
                return out._value if isinstance(out, Tensor) else out
            finally:
                for t, v in zip(tensors, originals):
                    t._bind(v)
        return call

    # ------------------------------------------------------------------ fwd
    def forward(self, h, *bcast):
        S = self._n_stages
        M = self._num_microbatches or S
        B = h.shape[0]
        if B % M != 0:
            raise ValueError(f"batch {B} not divisible into {M} microbatches")
        bcast_t = [b for b in bcast if isinstance(b, Tensor)]
        self._bcast_template = [b if isinstance(b, Tensor) else None for b in bcast]

        # trunk-activation and output shapes per microbatch: the first/last
        # stage layers may change both (ids -> hidden, hidden -> logits).
        # The probes run layers through the funnel, so under static capture
        # they MUST suspend recording (same rule as program.record's op
        # bodies) — otherwise eval_shape tracers get baked into the program.
        from paddle_tpu.static.program import suspend_capture

        mb_struct = jax.ShapeDtypeStruct((B // M,) + tuple(int(s) for s in h.shape[1:]), h._value.dtype)
        with suspend_capture():
            if self._first is not None:
                call = self._edge_call(self._first, self._first_tensors)
                vals = [t._value for t in self._first_tensors]
                h_struct = jax.eval_shape(lambda hv: call(hv, vals), mb_struct)
            else:
                h_struct = mb_struct
            if self._last is not None:
                call = self._edge_call(self._last, self._last_tensors)
                vals = [t._value for t in self._last_tensors]
                out_struct = jax.eval_shape(lambda hv: call(hv, vals), h_struct)
            else:
                out_struct = h_struct
        self._h_struct, self._out_struct = h_struct, out_struct

        x = h.reshape([M, B // M] + list(h.shape[1:]))
        args = (*self.stacked_parameters(), *self._first_tensors,
                *self._last_tensors, x, *bcast_t)
        self._maybe_mesh_lint(M, args)
        from . import schedules as _schedules

        _schedules._count_program(self._schedule, self._n_stages, M,
                                  self._n_virtual)
        out = apply("pipeline_stack", self._get_fn(M), *args)
        return out.reshape([B] + list(out_struct.shape[1:]))

    # ------------------------------------------------- schedule management
    def set_schedule(self, schedule: str):
        """Select a schedule explicitly (pipeline_scheduler pass face);
        drops cached built steps so the next forward traces the new one."""
        if schedule not in _SCHEDULES:
            raise ValueError(f"schedule must be one of {_SCHEDULES}, got {schedule!r}")
        if self._n_virtual > 1 and schedule != "VPP":
            # VPP stacks interleave the stacked weights in chunk order
            # ((j*S + d)*lpc + i); every other engine reads them
            # contiguously — switching would silently compose blocks in a
            # permuted global order.
            raise ValueError(
                f"stack was built interleaved (num_virtual_stages="
                f"{self._n_virtual}); its weights are stacked in VPP chunk "
                f"order — rebuild the stack to use schedule {schedule!r}")
        self._follow_flag = False
        if schedule != self._schedule:
            self._schedule = schedule
            self._fn_cache.clear()
            self._mesh_linted_at = None

    def _on_schedule_flag_change(self):
        """schedules-module flag listener: FLAGS_pipeline_schedule changed."""
        if not getattr(self, "_follow_flag", False):
            return
        from . import schedules as _schedules

        new = _schedules.resolve_schedule_flag()
        if new != self._schedule:
            self._schedule = new
            self._fn_cache.clear()
            self._mesh_linted_at = None

    def _get_fn(self, M):
        """Cached built step per (schedule, M, probed shapes, bcast mask) —
        what the flags listener invalidates.  Scan bodies are defined inside
        the traced callables, so a cached fn is safe to re-trace under a
        different jit (docs/SCAN_LAYERS.md body-identity rule)."""
        struct_key = tuple(
            (tuple(s.shape), str(s.dtype)) if s is not None else None
            for s in (getattr(self, "_h_struct", None),
                      getattr(self, "_out_struct", None)))
        key = (self._schedule, M, struct_key,
               tuple(b is not None for b in self._bcast_template))
        fn = self._fn_cache.get(key)
        if fn is None:
            fn = self._fn_cache[key] = self._make_fn(M)
        return fn

    def _maybe_mesh_lint(self, M, args):
        """FLAGS_verify_sharding hook: abstractly walk the assembled
        pipeline program (ring ppermutes, the stage-0/last-stage conds,
        the final psum) against the mesh BEFORE the first dispatch — a
        ring built for the wrong stage count or a mis-axised hop is a
        named error here, not an 8-device rendezvous hang.  Once per
        (stack, microbatch count); the trace is abstract only."""
        from paddle_tpu._core import flags as _flags

        if not _flags.flag("FLAGS_verify_sharding"):
            return
        if getattr(self, "_mesh_linted_at", None) == M:
            return
        from paddle_tpu.static.mesh_lint import MeshLinter, _finish

        avals = [jax.ShapeDtypeStruct(t._value.shape, t._value.dtype)
                 for t in args]
        linter = MeshLinter(mesh=self._mesh)
        # Every built-in schedule lints clean as-is: the edge layers' VJP
        # transpose-psums are hoisted OUT of the stage-predicated conds by
        # construction (see pipe()'s pp-varying casts), so any
        # conditional-collective that DOES surface here is a user block's
        # own data-dependent collective — the real deadlock class.
        fn = self._get_fn(M)
        violations = linter.lint_callable(
            fn, *avals, site=f"pipeline_stack[{self._schedule}]")
        if self._schedule == "ZB-H1":
            # The split backward is a hand-scheduled scan with its own ring
            # ppermutes and grad psums — the new deadlock surface.  Lint the
            # whole vjp program too (jax autodiff never sees it at runtime:
            # the custom_vjp bwd IS the program being checked here).
            out_struct = getattr(self, "_out_struct", None)
            mb_shape = tuple(out_struct.shape) if out_struct is not None \
                else tuple(avals[-1].shape[1:])
            mb_dtype = out_struct.dtype if out_struct is not None \
                else avals[-1].dtype
            cot = jax.ShapeDtypeStruct((M,) + mb_shape, mb_dtype)

            def grad_prog(*a):
                ins, ct = a[:-1], a[-1]
                diff = [i for i, v in enumerate(ins)
                        if jnp.issubdtype(v.dtype, jnp.inexact)]
                dset = set(diff)

                def g(*dv):
                    it = iter(dv)
                    return fn(*[next(it) if i in dset else ins[i]
                                for i in range(len(ins))])

                _, vjp = jax.vjp(g, *[ins[i] for i in diff])
                return vjp(ct)

            violations += linter.lint_callable(
                grad_prog, *avals, cot,
                site=f"pipeline_stack[{self._schedule}].backward")
        _finish(violations, "Mesh lint failed (PipelineStack)",
                raise_on_error=True)
        self._mesh_linted_at = M

    def _make_fn(self, M):
        if self._schedule == "ZB-H1":
            return self._make_zb_fn(M)
        S = self._n_stages
        Lps = self._layers_per_stage
        pp = self._pp_axis
        jmesh = self._mesh.jax_mesh
        n_keys = len(self._keys)
        template = self._template
        tpl_tensors = self._tpl_tensors
        bcast_template = self._bcast_template
        use_recompute = self._use_recompute
        per_tick_remat = self._schedule in ("1F1B", "VPP")
        n_virtual = self._n_virtual
        lpc = Lps // n_virtual
        nf, nl = len(self._first_tensors), len(self._last_tensors)
        # set by forward(); None when _make_fn is driven directly (tests,
        # structure inspection) — then trunk-in == trunk-out == x's shape
        h_struct = getattr(self, "_h_struct", None)
        out_struct = getattr(self, "_out_struct", None)
        first_call = (
            self._edge_call(self._first, self._first_tensors) if self._first else None
        )
        last_call = (
            self._edge_call(self._last, self._last_tensors) if self._last else None
        )

        def pipe_vpp(stacked, x, bcast_vals, stage, first_vals=(), last_vals=()):
            """Circular token ring (see class docstring): each device carries
            one (microbatch m, chunk c) token; device 0 injects when a
            completed (c == V) token returns.  T = M*v + S - 1 ticks."""
            V = S * n_virtual
            ring = [(i, (i + 1) % S) for i in range(S)]
            wlocal = [w[0] for w in stacked]  # [v*lpc, ...] local chunks

            def chunk_fn(chunk_local, h_val):
                # run the lpc layers of local chunk `chunk_local` (traced idx)
                for i in range(lpc):
                    li = chunk_local * lpc + i
                    params_i = [
                        lax.dynamic_index_in_dim(w, li, 0, keepdims=False)
                        for w in wlocal
                    ]
                    h_val = layer_call(params_i, h_val, bcast_vals)
                return h_val

            if per_tick_remat:
                chunk_fn = jax.checkpoint(chunk_fn)

            # the last microbatch is injected at ((M-1)//S)*V + (M-1)%S and
            # computes its final chunk V-1 ticks later; for M % S == 0 this
            # reduces to M*v + S - 1
            T = ((M - 1) // S) * V + ((M - 1) % S) + V

            def tick(carry, t):
                h, m_idx, c_idx, next_m, out = carry
                dead = c_idx >= V
                inject = jnp.logical_and(jnp.logical_and(stage == 0, dead), next_m < M)
                m_new = jnp.where(inject, next_m, m_idx)
                c_new = jnp.where(inject, 0, c_idx)
                raw = lax.dynamic_index_in_dim(x, jnp.clip(next_m, 0, M - 1), 0, keepdims=False)
                if first_call is not None:
                    # pre-cast cond inputs to pp-varying (see non-VPP note)
                    fed = lax.cond(
                        inject,
                        lambda r: first_call(r, first_vals),
                        lambda r: _pvary(jnp.zeros(h_struct.shape, h_struct.dtype), (pp,)),
                        _pvary(raw, (pp,)),
                    )
                else:
                    fed = raw
                h_in = jnp.where(inject, fed, h)
                next_m2 = jnp.where(inject, next_m + 1, next_m)
                active = c_new < V
                chunk_local = jnp.clip(c_new // S, 0, n_virtual - 1)
                y = chunk_fn(chunk_local, h_in)
                y = jnp.where(active, y, h_in)
                c_after = jnp.where(active, c_new + 1, c_new)
                done_now = jnp.logical_and(active, c_after == V)
                m_out = jnp.clip(m_new, 0, M - 1)
                cur = lax.dynamic_index_in_dim(out, m_out, 0, keepdims=False)
                if last_call is not None:
                    val = lax.cond(
                        done_now,
                        lambda yy: last_call(yy, last_vals),
                        lambda yy: _pvary(jnp.zeros(out_struct.shape, out_struct.dtype), (pp,)),
                        y,
                    )
                else:
                    val = y
                out = lax.dynamic_update_index_in_dim(
                    out, jnp.where(done_now, val, cur), m_out, 0
                )
                h_next = lax.ppermute(y, pp, ring)
                m_next = lax.ppermute(m_new, pp, ring)
                c_next = lax.ppermute(c_after, pp, ring)
                return (h_next, m_next, c_next, next_m2, out), None

            zeros_h = (jnp.zeros(h_struct.shape, h_struct.dtype)
                       if h_struct is not None else jnp.zeros_like(x[0]))
            zeros_out = (jnp.zeros((M,) + tuple(out_struct.shape), out_struct.dtype)
                         if out_struct is not None else jnp.zeros_like(x))
            carry0 = (
                _pvary(zeros_h, (pp,)),
                _pvary(jnp.asarray(-1, jnp.int32), (pp,)),
                _pvary(jnp.asarray(V, jnp.int32), (pp,)),  # dead: inject
                _pvary(jnp.asarray(0, jnp.int32), (pp,)),
                _pvary(zeros_out, (pp,)),
            )
            (_, _, _, _, out), _ = lax.scan(tick, carry0, jnp.arange(T, dtype=jnp.int32))
            return lax.psum(out, pp)

        def layer_call(params_i, h_val, bcast_vals):
            originals = [t._value for t in tpl_tensors]
            try:
                for t, v in zip(tpl_tensors, params_i):
                    t._bind(v)
                it = iter(bcast_vals)
                args = [Tensor(next(it)) if b is not None else None for b in bcast_template]
                with no_grad():
                    out = template(Tensor(h_val), *args)
                return out._value if isinstance(out, Tensor) else out
            finally:
                for t, v in zip(tpl_tensors, originals):
                    t._bind(v)

        def pipe(*vals):
            stacked = vals[:n_keys]           # each [1, Lps, ...] local
            # pp-varying casts up front: their transpose-psums then run
            # uniformly on every device, outside any stage-predicated cond
            first_vals = [_pvary(v, (pp,)) for v in vals[n_keys:n_keys + nf]]
            last_vals = [_pvary(v, (pp,)) for v in vals[n_keys + nf:n_keys + nf + nl]]
            x = vals[n_keys + nf + nl]        # [M, mb, ...] (replicated over pp)
            bcast_vals = vals[n_keys + nf + nl + 1:]
            stage = lax.axis_index(pp)
            wlocal = [w[0] for w in stacked]  # [Lps, ...]

            def stage_fn(h_val):
                for i in range(Lps):
                    params_i = [w[i] for w in wlocal]
                    call = (lambda ps, hv: layer_call(ps, hv, bcast_vals))
                    if use_recompute:
                        call = jax.checkpoint(call)
                    h_val = call(params_i, h_val)
                return h_val

            if per_tick_remat:
                stage_fn = jax.checkpoint(stage_fn)

            if n_virtual > 1:
                return pipe_vpp(stacked, x, bcast_vals, stage, first_vals, last_vals)

            T = M + S - 1
            ring = [(i, (i + 1) % S) for i in range(S)]

            def tick(carry, t):
                buf, out = carry
                # stage 0 feeds microbatch t (last one repeated through the
                # drain ticks — the classic warmup/drain bubble); others eat
                # the boundary activation that just hopped in on the ring.
                m_in = jnp.clip(t, 0, M - 1)
                raw = lax.dynamic_index_in_dim(x, m_in, 0, keepdims=False)
                if first_call is not None:
                    # cond keeps the embedding off stages != 0 at runtime.
                    # EVERYTHING entering the cond is pre-cast to pp-varying
                    # (params at the top of pipe, raw here): an unvarying
                    # value used inside a stage-predicated branch would get
                    # its transpose-psum(pp) placed inside the branch, which
                    # only one stage executes -> collective deadlock.
                    fed = lax.cond(
                        stage == 0,
                        lambda r: first_call(r, first_vals),
                        lambda r: _pvary(jnp.zeros(h_struct.shape, h_struct.dtype), (pp,)),
                        _pvary(raw, (pp,)),
                    )
                else:
                    fed = raw
                inp = jnp.where(stage == 0, fed, buf)
                y = stage_fn(inp)
                # last stage owns microbatch t-(S-1)'s output
                m_out = jnp.clip(t - (S - 1), 0, M - 1)
                cur = lax.dynamic_index_in_dim(out, m_out, 0, keepdims=False)
                write = jnp.logical_and(stage == S - 1, t >= S - 1)
                if last_call is not None:
                    # head (e.g. lm-head matmul) only runs on write ticks of
                    # the last stage
                    val = lax.cond(
                        write,
                        lambda yy: last_call(yy, last_vals),
                        lambda yy: _pvary(jnp.zeros(out_struct.shape, out_struct.dtype), (pp,)),
                        y,
                    )
                else:
                    val = y
                out = lax.dynamic_update_index_in_dim(
                    out, jnp.where(write, val, cur), m_out, 0
                )
                buf = lax.ppermute(y, pp, ring)
                return (buf, out), None

            # carries become pp-varying inside the loop; type them so upfront
            zeros_h = (jnp.zeros(h_struct.shape, h_struct.dtype)
                       if h_struct is not None else jnp.zeros_like(x[0]))
            zeros_out = (jnp.zeros((M,) + tuple(out_struct.shape), out_struct.dtype)
                         if out_struct is not None else jnp.zeros_like(x))
            carry0 = (
                _pvary(zeros_h, (pp,)),
                _pvary(zeros_out, (pp,)),
            )
            (_, out), _ = lax.scan(tick, carry0, jnp.arange(T, dtype=jnp.int32))
            # outputs live on the last stage; psum replicates them over pp
            # (non-last stages contributed zeros)
            return lax.psum(out, pp)

        def fn(*vals):
            in_specs = tuple(PartitionSpec(pp) for _ in range(n_keys)) + tuple(
                PartitionSpec() for _ in range(len(vals) - n_keys)
            )
            return shard_map(
                pipe,
                mesh=jmesh,
                in_specs=in_specs,
                out_specs=PartitionSpec(),
                axis_names={pp},
            )(*vals)

        return fn

    # ----------------------------------------------------- ZB split backward
    def _make_zb_fn(self, M):
        """The zero-bubble engine pair: a forward scan that stores ONLY the
        per-tick boundary activations, and a hand-scheduled backward scan
        (jax.custom_vjp) consuming the schedule's engine plan — at backward
        tick r it runs the grad-INPUT pass of forward tick b_tick[r] (the B
        slot: recompute the tick under jax.vjp w.r.t. the boundary input,
        reverse-ppermute the cotangent to the upstream stage) and the
        DEFERRED grad-WEIGHT pass of forward tick w_tick[r] (the W slot:
        vjp w.r.t. the stage/edge parameters from the stored cotangents).
        Grad-weight deferral changes only the accumulation order, so grads
        match the fused 1F1B backward within jit-reassociation tolerance.

        Assumes deterministic stage fns (the recompute replays the forward;
        fresh per-call RNG — dropout — would diverge between the fwd trace
        and the bwd recompute; same limitation as any uncoordinated remat).
        """
        import numpy as np

        from . import schedules as _schedules

        S = self._n_stages
        Lps = self._layers_per_stage
        pp = self._pp_axis
        jmesh = self._mesh.jax_mesh
        n_keys = len(self._keys)
        template = self._template
        tpl_tensors = self._tpl_tensors
        bcast_template = self._bcast_template
        use_recompute = self._use_recompute
        nf, nl = len(self._first_tensors), len(self._last_tensors)
        h_struct = getattr(self, "_h_struct", None)
        out_struct = getattr(self, "_out_struct", None)
        first_call = (
            self._edge_call(self._first, self._first_tensors) if self._first else None
        )
        last_call = (
            self._edge_call(self._last, self._last_tensors) if self._last else None
        )

        plan = _schedules.get_schedule(self._schedule).engine_plan(S, M)
        T, TB = plan["T"], plan["TB"]
        # host arrays: this runs under the caller's trace and the engine is
        # cached on the object, so a jnp constant made here would be a
        # tracer of a trace that has ended by the next call
        b_tick = np.asarray(plan["b_tick"], np.int32)
        w_tick = np.asarray(plan["w_tick"], np.int32)
        ring = [(i, (i + 1) % S) for i in range(S)]
        ring_rev = [(i, (i - 1) % S) for i in range(S)]

        def layer_call(params_i, h_val, bcast_vals):
            originals = [t._value for t in tpl_tensors]
            try:
                for t, v in zip(tpl_tensors, params_i):
                    t._bind(v)
                it = iter(bcast_vals)
                args = [Tensor(next(it)) if b is not None else None
                        for b in bcast_template]
                with no_grad():
                    out = template(Tensor(h_val), *args)
                return out._value if isinstance(out, Tensor) else out
            finally:
                for t, v in zip(tpl_tensors, originals):
                    t._bind(v)

        def stage_fn(wlocal, h_val, bcast_vals):
            for i in range(Lps):
                params_i = [w[i] for w in wlocal]
                call = (lambda ps, hv: layer_call(ps, hv, bcast_vals))
                if use_recompute:
                    call = jax.checkpoint(call)
                h_val = call(params_i, h_val)
            return h_val

        def _idx(arr, i):
            return lax.dynamic_index_in_dim(arr, i, 0, keepdims=False)

        def _upd(arr, v, i):
            return lax.dynamic_update_index_in_dim(arr, v, i, 0)

        def tick_core(wlocal, first_vals, last_vals, buf, raw, bcast_vals,
                      t, stage):
            """One forward tick WITHOUT the ring hop / out write: returns
            (y, val).  val is the candidate output-buffer value — the head
            output under the write-tick cond, else y; the caller (and the
            cotangent extraction in the backward) masks it by `write`."""
            if first_call is not None:
                fed = lax.cond(
                    stage == 0,
                    lambda r: first_call(r, first_vals),
                    lambda r: _pvary(jnp.zeros(h_struct.shape, h_struct.dtype), (pp,)),
                    _pvary(raw, (pp,)),
                )
            else:
                fed = raw
            inp = jnp.where(stage == 0, fed, buf)
            y = stage_fn(wlocal, inp, bcast_vals)
            if last_call is not None:
                write = jnp.logical_and(stage == S - 1, t >= S - 1)
                val = lax.cond(
                    write,
                    lambda yy: last_call(yy, last_vals),
                    lambda yy: _pvary(jnp.zeros(out_struct.shape, out_struct.dtype), (pp,)),
                    y,
                )
            else:
                val = y
            return y, val

        def _unpack(vals):
            stacked = vals[:n_keys]
            first_vals = tuple(_pvary(v, (pp,)) for v in vals[n_keys:n_keys + nf])
            last_vals = tuple(_pvary(v, (pp,))
                              for v in vals[n_keys + nf:n_keys + nf + nl])
            x = vals[n_keys + nf + nl]
            bcast_vals = tuple(vals[n_keys + nf + nl + 1:])
            return stacked, first_vals, last_vals, x, bcast_vals

        def _zeros_h(x):
            return (jnp.zeros(h_struct.shape, h_struct.dtype)
                    if h_struct is not None else jnp.zeros_like(x[0]))

        def _zeros_out(x):
            return (jnp.zeros((M,) + tuple(out_struct.shape), out_struct.dtype)
                    if out_struct is not None else jnp.zeros_like(x))

        def pipe_fwd(*vals):
            stacked, first_vals, last_vals, x, bcast_vals = _unpack(vals)
            stage = lax.axis_index(pp)
            wlocal = [w[0] for w in stacked]

            def tick(carry, t):
                buf, out = carry
                raw = _idx(x, jnp.clip(t, 0, M - 1))
                y, val = tick_core(wlocal, first_vals, last_vals, buf, raw,
                                   bcast_vals, t, stage)
                m_out = jnp.clip(t - (S - 1), 0, M - 1)
                write = jnp.logical_and(stage == S - 1, t >= S - 1)
                cur = _idx(out, m_out)
                out = _upd(out, jnp.where(write, val, cur), m_out)
                buf_next = lax.ppermute(y, pp, ring)
                # ys: the tick's INPUT boundary — the only stored residual
                return (buf_next, out), buf

            carry0 = (_pvary(_zeros_h(x), (pp,)), _pvary(_zeros_out(x), (pp,)))
            (_, out), buf_store = lax.scan(tick, carry0,
                                           jnp.arange(T, dtype=jnp.int32))
            return lax.psum(out, pp), buf_store[None]  # [1, T, mb...] local

        def pipe_bwd(*args):
            vals, store, g_out_in = args[:-2], args[-2], args[-1]
            stacked, first_vals, last_vals, x, bcast_vals = _unpack(vals)
            stage = lax.axis_index(pp)
            wlocal = [w[0] for w in stacked]
            buf_store = store[0]  # [T, mb...]
            x_diff = jnp.issubdtype(x.dtype, jnp.inexact)
            bc_diff = tuple(jnp.issubdtype(b.dtype, jnp.inexact)
                            for b in bcast_vals)

            zh = _zeros_h(x)
            zv = (jnp.zeros(out_struct.shape, out_struct.dtype)
                  if out_struct is not None else zh)

            def btick(carry, r):
                (g_buf, g_out, g_x, g_bc, gp, gf, gl, gy_buf, gv_buf) = carry
                # ---------------- B slot: grad-input of forward tick t
                t = _idx(b_tick, r)
                bv = t >= 0
                tc = jnp.clip(t, 0, T - 1)
                # cotangent of this tick's y arriving on the reversed ring
                g_y = jnp.where(bv, lax.ppermute(g_buf, pp, ring_rev), 0)
                m_out = jnp.clip(tc - (S - 1), 0, M - 1)
                write = jnp.logical_and(
                    jnp.logical_and(stage == S - 1, tc >= S - 1), bv)
                cur = _idx(g_out, m_out)
                g_val = jnp.where(write, cur, jnp.zeros_like(cur))
                g_out = _upd(g_out, jnp.where(write, jnp.zeros_like(cur), cur),
                             m_out)
                buf_t = _idx(buf_store, tc)
                m_in = jnp.clip(tc, 0, M - 1)
                raw_t = _idx(x, m_in)

                diff_b = (buf_t,) + ((raw_t,) if x_diff else ()) + tuple(
                    b for b, d in zip(bcast_vals, bc_diff) if d)

                def f_b(*db):
                    it = iter(db)
                    buf_ = next(it)
                    raw_ = next(it) if x_diff else raw_t
                    bc_ = tuple(next(it) if d else b
                                for b, d in zip(bcast_vals, bc_diff))
                    return tick_core(wlocal, first_vals, last_vals, buf_,
                                     raw_, bc_, tc, stage)

                _, vjp_b = jax.vjp(f_b, *diff_b)
                gb = list(vjp_b((g_y, g_val)))
                g_buf_new = gb.pop(0)
                if x_diff:
                    g_raw = gb.pop(0)
                    g_x = _upd(g_x, _idx(g_x, m_in) + g_raw, m_in)
                g_bc = tuple(
                    (acc + gb.pop(0)) if d else acc
                    for acc, d in zip(g_bc, bc_diff))
                # store this tick's output cotangents for the deferred W
                gy_buf = _upd(gy_buf, jnp.where(bv, g_y, _idx(gy_buf, tc)), tc)
                gv_buf = _upd(gv_buf, jnp.where(bv, g_val, _idx(gv_buf, tc)), tc)

                # ---------------- W slot: deferred grad-weight of tick tw
                tw = _idx(w_tick, r)
                wv = tw >= 0
                twc = jnp.clip(tw, 0, T - 1)
                gy_w = jnp.where(wv, _idx(gy_buf, twc), 0)
                gv_w = jnp.where(wv, _idx(gv_buf, twc), 0)
                buf_w = _idx(buf_store, twc)
                raw_w = _idx(x, jnp.clip(twc, 0, M - 1))

                def f_w(wl, fv, lv):
                    return tick_core(wl, fv, lv, buf_w, raw_w, bcast_vals,
                                     twc, stage)

                _, vjp_w = jax.vjp(f_w, wlocal, first_vals, last_vals)
                gw, gfv, glv = vjp_w((gy_w, gv_w))
                gp = [a + b for a, b in zip(gp, gw)]
                gf = tuple(a + b for a, b in zip(gf, gfv))
                gl = tuple(a + b for a, b in zip(gl, glv))
                return (g_buf_new, g_out, g_x, g_bc, gp, gf, gl,
                        gy_buf, gv_buf), None

            carry0 = (
                _pvary(jnp.zeros_like(zh), (pp,)),          # g_buf
                _pvary(g_out_in, (pp,)),                    # psum transpose
                jnp.zeros_like(x) if x_diff else jnp.zeros((), x.dtype),
                tuple(jnp.zeros_like(b) if d else jnp.zeros((), b.dtype)
                      for b, d in zip(bcast_vals, bc_diff)),
                [jnp.zeros_like(w) for w in wlocal],
                tuple(jnp.zeros_like(v) for v in first_vals),
                tuple(jnp.zeros_like(v) for v in last_vals),
                _pvary(jnp.zeros((T,) + zh.shape, zh.dtype), (pp,)),
                _pvary(jnp.zeros((T,) + zv.shape, zv.dtype), (pp,)),
            )
            (g_buf, g_out, g_x, g_bc, gp, gf, gl, _, _), _ = lax.scan(
                btick, carry0, jnp.arange(TB, dtype=jnp.int32))

            # replicated inputs: sum the per-stage contributions uniformly
            # (outside any stage-predicated cond — the mesh-lint contract)
            out = [g[None] for g in gp]                      # [1, Lps, ...]
            out += [lax.psum(g, pp) for g in gf]
            out += [lax.psum(g, pp) for g in gl]
            if x_diff:
                out.append(lax.psum(g_x, pp))
            out += [lax.psum(g, pp) for g, d in zip(g_bc, bc_diff) if d]
            return tuple(out)

        # bcast args reaching the engine are the Tensor-valued ones only
        # (forward() filters; layer_call reinserts the None placeholders)
        n_bcast = sum(b is not None for b in bcast_template)
        in_specs = tuple(PartitionSpec(pp) for _ in range(n_keys)) + tuple(
            PartitionSpec() for _ in range(nf + nl + 1 + n_bcast))

        # check_vma/check_rep off: the stage-predicated conds intentionally
        # produce stage-varying values from replicated inputs (the same
        # reason the 2-D-mesh path rides the partial-manual fallback) — the
        # mesh lint, not the rep checker, owns collective congruence here.
        # jitted: an EAGER call (ShardedTrainStep's step 0) would run jax's
        # eager shard_map, which in jax 0.9 refuses partial-manual axes
        # with a pp-sharded output ("out_specs refers to 'dp'")
        def fwd_sm(*vals):
            return jax.jit(shard_map(
                pipe_fwd, mesh=jmesh, in_specs=in_specs,
                out_specs=(PartitionSpec(), PartitionSpec(pp)),
                axis_names={pp}, check_vma=False))(*vals)

        @jax.custom_vjp
        def zb(*vals):
            return fwd_sm(*vals)[0]

        def zb_fwd(*vals):
            out, store = fwd_sm(*vals)
            return out, (vals, store)

        def zb_bwd(res, g):
            vals, store = res
            x = vals[n_keys + nf + nl]
            bcast_vals = vals[n_keys + nf + nl + 1:]
            x_diff = jnp.issubdtype(x.dtype, jnp.inexact)
            bc_diff = [jnp.issubdtype(b.dtype, jnp.inexact) for b in bcast_vals]
            n_grads = (n_keys + nf + nl + (1 if x_diff else 0)
                       + sum(bc_diff))
            grad_specs = tuple(PartitionSpec(pp) for _ in range(n_keys)) + \
                tuple(PartitionSpec() for _ in range(n_grads - n_keys))
            grads = jax.jit(shard_map(
                pipe_bwd, mesh=jmesh,
                in_specs=in_specs + (PartitionSpec(pp), PartitionSpec()),
                out_specs=grad_specs,
                axis_names={pp}, check_vma=False))(*vals, store, g)
            grads = list(grads)
            out = []
            for i, v in enumerate(vals):
                if i < n_keys + nf + nl:
                    out.append(grads.pop(0))
                elif i == n_keys + nf + nl:  # x
                    out.append(grads.pop(0) if x_diff
                               else np.zeros(v.shape, jax.dtypes.float0))
                else:
                    d = bc_diff[i - (n_keys + nf + nl + 1)]
                    out.append(grads.pop(0) if d
                               else np.zeros(v.shape, jax.dtypes.float0))
            return tuple(out)

        zb.defvjp(zb_fwd, zb_bwd)
        return zb


def pipeline_parallel(model, mesh, schedule: str = None, **kwargs):
    """Model-dispatching pipeline entry (the reference pipeline_parallel.py
    name): convert `model` to run its trunk (and edges, where the model
    pipeliner supports them) over the 'pp' mesh axis under `schedule`
    (None -> FLAGS_pipeline_schedule).  LlamaForCausalLM routes to
    pipeline_llama, GPTForCausalLM to pipeline_gpt; a plain list of
    structurally identical blocks builds a PipelineStack directly."""
    from paddle_tpu.models.gpt import GPTForCausalLM, pipeline_gpt
    from paddle_tpu.models.llama import LlamaForCausalLM, pipeline_llama

    if isinstance(model, LlamaForCausalLM):
        return pipeline_llama(model, mesh, schedule=schedule, **kwargs)
    if isinstance(model, GPTForCausalLM):
        return pipeline_gpt(model, mesh, schedule=schedule, **kwargs)
    if isinstance(model, (list, tuple)):
        return PipelineStack(list(model), mesh, schedule=schedule, **kwargs)
    raise TypeError(
        f"pipeline_parallel: no pipeliner for {type(model).__name__}; use "
        "PipelineStack directly for custom block stacks")
