"""Eager autograd engine: a vjp tape.

Capability equivalent of the reference's eager autograd
(paddle/fluid/eager/backward.cc:105 RunBackward, grad_node_info.h:197
GradNodeBase, grad_tensor_holder.h) re-designed for XLA:

- Instead of per-op handwritten GradNode classes generated from backward.yaml,
  every differentiable op call goes through `apply(name, fn, *args)`, which
  uses jax.vjp to execute the forward ONCE and capture a reusable backward
  closure holding on-device residuals.  That closure *is* the grad node.
- `backward_from` replicates the reference's dual-queue dependency-counted
  walk (backward.cc:24-65 in-degree computation, :126-165 queue loop) over
  these nodes, accumulating cotangents per node output (GradTensorHolder
  equivalent) and writing leaf grads into Tensor.grad
  (GradNodeAccumulation equivalent).
- Because jax.vjp composes with tracing, the same tape works inside jax.jit:
  a whole train step written imperatively (forward, loss.backward(),
  opt.step()) can be traced and compiled end-to-end — the TPU answer to the
  reference's C++ hot path.
"""

from __future__ import annotations

import contextlib
import threading
import weakref
from collections import deque

import jax
import jax.numpy as jnp

from .tensor import Tensor
from . import dispatch
from . import flags

__all__ = [
    "apply",
    "backward_from",
    "backward_multi",
    "grad",
    "no_grad",
    "enable_grad",
    "set_grad_enabled",
    "is_grad_enabled",
]


class _GradState(threading.local):
    def __init__(self):
        self.enabled = True
        self.touch_recorders = []  # stack of lists capturing Tensor inputs


_state = _GradState()


class TouchRecorder:
    """Collects op-input Tensors (and the ids of Tensors CREATED meanwhile,
    so callers can filter out branch-local intermediates)."""

    def __init__(self):
        self.inputs: list = []
        self.created: set = set()

    def external_inputs(self):
        out, seen = [], set()
        for t in self.inputs:
            if id(t) not in seen and id(t) not in self.created:
                seen.add(id(t))
                out.append(t)
        return out


@contextlib.contextmanager
def record_touched_tensors(rec: "TouchRecorder"):
    """Record every Tensor that flows into an op while active (used by
    control-flow capture to discover closure-captured inputs)."""
    _state.touch_recorders.append(rec)
    try:
        yield rec
    finally:
        _state.touch_recorders.pop()
_static_prog_mod = None  # lazy ref to paddle_tpu.static.program (capture hook)
_profiler_mod = None  # lazy ref to paddle_tpu.profiler (host event hook)


def is_grad_enabled() -> bool:
    return _state.enabled


def set_grad_enabled(enabled: bool):
    _state.enabled = bool(enabled)


@contextlib.contextmanager
def no_grad():
    prev = _state.enabled
    _state.enabled = False
    try:
        yield
    finally:
        _state.enabled = prev


@contextlib.contextmanager
def enable_grad():
    prev = _state.enabled
    _state.enabled = True
    try:
        yield
    finally:
        _state.enabled = prev


class GradNode:
    """One recorded op: backward closure + graph edges.

    Mirrors GradNodeBase (reference grad_node_info.h:197): `inputs` are the
    next edges, `out_avals` the shapes/dtypes of this op's forward outputs
    (needed to materialize zero cotangents for unused outputs).
    """

    __slots__ = (
        "name",
        "vjp_fn",
        "inputs",
        "out_avals",
        "out_tree",
        "n_outputs",
        "out_refs",
        "released",
        "rebuild",
        "taped_vjp",
        "__weakref__",
    )

    def __init__(self, name, vjp_fn, inputs, out_avals, out_tree):
        self.name = name
        self.vjp_fn = vjp_fn
        self.inputs = inputs  # list[Tensor] — differentiable inputs, vjp order
        self.out_avals = out_avals  # list[(shape, dtype)]
        self.out_tree = out_tree
        self.n_outputs = len(out_avals)
        self.out_refs = []  # list[weakref to output Tensors], for hooks
        self.released = False
        # (fn, fixed_vals, diff_set, n_args, kwargs, input_snapshot): enough
        # to re-run the forward under jax.vjp with the cotangents as EXTRA
        # differentiable inputs — the create_graph=True path (double
        # backward; reference builds generated double-grad nodes,
        # python/paddle/base/dygraph/base.py:645).  input_snapshot holds the
        # record-time values so in-place mutation between forward and the
        # create_graph walk is detected, not silently recomputed-over.
        self.rebuild = None
        # create_graph path for CUSTOM-backward nodes (PyLayer): a callable
        # (cot_tensors) -> input grads running the user backward WITH grad
        # recording — autodiffing the forward would be wrong for e.g.
        # straight-through estimators.
        self.taped_vjp = None

    def release(self):
        self.vjp_fn = None
        self.rebuild = None
        self.released = True


def _maybe_amp_cast(name, args):
    """AMP O1 cast hook (reference: AMP logic in generated ad_funcs,
    paddle/fluid/eager/amp_utils.h): white-listed ops run in the low dtype,
    black-listed ops in float32, others follow their inputs."""
    try:
        from paddle_tpu import amp as amp_mod
    except ImportError:
        return args
    st = amp_mod.amp_state()
    if not st.enabled:
        return args
    if name in amp_mod.white_list():
        target = st.dtype
    elif name in amp_mod.black_list():
        target = jnp.float32
    else:
        return args

    def cast(a):
        if isinstance(a, Tensor) and jnp.issubdtype(a._value.dtype, jnp.floating):
            if a._value.dtype != target:
                if a.stop_gradient or not _state.enabled:
                    return Tensor(a._value.astype(target))
                # grad-carrying tensors cast through the tape so the cotangent
                # is cast back on the way down
                return apply("amp_cast", lambda v: v.astype(target), a)
        return a

    return tuple(cast(a) for a in args)


def _nanfail(ok, name):
    if not bool(ok):
        raise FloatingPointError(f"NaN/Inf detected in output of op '{name}'")


def _check_nan_inf(name, vals):
    """FLAGS_check_nan_inf: eager values checked synchronously; traced values
    get an in-graph host callback so the check ALSO fires inside compiled
    steps (reference runs it in-kernel, paddle/phi/kernels/
    check_numerics_kernel.h — round-1 skipped tracers, making the flag dead
    on the only path that matters)."""
    import functools as _ft

    for v in vals:
        if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating):
            if isinstance(v, jax.core.Tracer):
                jax.debug.callback(_ft.partial(_nanfail, name=name), jnp.all(jnp.isfinite(v)))
            elif bool(jnp.any(~jnp.isfinite(v))):
                raise FloatingPointError(f"NaN/Inf detected in output of op '{name}'")


def apply(name, fn, *args, n_outputs=None, **kwargs):
    """Profiler/static-capture wrapper around the eager funnel; see
    _apply_impl for the semantics."""
    global _static_prog_mod, _profiler_mod
    if _static_prog_mod is None:
        try:
            from paddle_tpu.static import program as _spm

            _static_prog_mod = _spm
        except ImportError:
            _static_prog_mod = False
    if _static_prog_mod and _static_prog_mod.in_static_capture():
        return _static_prog_mod.current_main_program().record(name, fn, args, kwargs)

    if _profiler_mod is None:
        try:
            from paddle_tpu import profiler as _pm

            _profiler_mod = _pm
        except ImportError:
            _profiler_mod = False
    if _profiler_mod and _profiler_mod._active_profiler is not None:
        with _profiler_mod.RecordEvent(f"op::{name}"):
            return _apply_impl(name, fn, *args, n_outputs=n_outputs, **kwargs)
    return _apply_impl(name, fn, *args, n_outputs=n_outputs, **kwargs)


_funnel_calls = 0  # every op that went through _apply_impl, this process


def funnel_calls() -> int:
    """Ops executed through the funnel so far: a caller counts the eager
    dispatches of a region by the difference (serving's admit_eager_ops)."""
    return _funnel_calls


def _apply_impl(name, fn, *args, n_outputs=None, **kwargs):
    """Execute op `fn` over Tensor/raw args, recording a grad node if needed.

    fn receives raw jax values positionally (same order as args) and must
    return a jax value or a tuple/list of them.  kwargs are static.
    Non-Tensor args and stop_gradient Tensors are closed over (not
    differentiated).  Integer/bool outputs never require grad.

    Inside a static program_guard the `apply` wrapper records an Operator
    instead of executing — the whole op surface is static-capturable for free
    (the reference gets the same dual-mode from its YAML codegen emitting
    both dygraph ad_funcs and PIR ops).

    With FLAGS_eager_op_jit on, repeated calls with the same signature route
    through the dispatch cache (_core.dispatch): the no-grad path runs a
    cached jax.jit of fn, the grad path a cached jitted jax.vjp pair — the
    per-op Python retrace cost is paid once per signature, not per call.
    """
    global _funnel_calls
    _funnel_calls += 1
    args = _maybe_amp_cast(name, args)
    tensors = [a for a in args if isinstance(a, Tensor)]
    if _state.touch_recorders:
        # append raw; consumers dedupe by id() (Tensor __eq__ is elementwise)
        _state.touch_recorders[-1].inputs.extend(tensors)
    needs_grad = _state.enabled and any(not t.stop_gradient for t in tensors)

    handle = (dispatch.lookup(name, fn, args, kwargs, needs_grad)
              if flags.flag("FLAGS_eager_op_jit") else None)

    if not needs_grad:
        out = dispatch.FALLBACK
        if handle is not None and handle.hit:
            out = handle.call_nograd()
        if out is dispatch.FALLBACK:
            vals = [a._value if isinstance(a, Tensor) else a for a in args]
            out = fn(*vals, **kwargs)
            if handle is not None and not handle.hit:
                handle.record(out)
        if flags.flag("FLAGS_check_nan_inf"):
            _check_nan_inf(name, jax.tree_util.tree_leaves(out))

        def _mk(v):
            t = Tensor(v, stop_gradient=True)
            if _state.touch_recorders:
                for rec in _state.touch_recorders:
                    rec.created.add(id(t))
            return t

        return jax.tree_util.tree_map(
            _mk, out, is_leaf=lambda x: not isinstance(x, (tuple, list, dict))
        )

    # Partition: differentiable (float tensors with stop_gradient=False) vs closed-over.
    diff_idx = []
    for i, a in enumerate(args):
        if isinstance(a, Tensor) and not a.stop_gradient and jnp.issubdtype(
            jnp.asarray(a._value).dtype if not hasattr(a._value, "dtype") else a._value.dtype,
            jnp.inexact,
        ):
            diff_idx.append(i)
    diff_tensors = [args[i] for i in diff_idx]
    diff_set = set(diff_idx)
    fixed_vals = [None if i in diff_set else (a._value if isinstance(a, Tensor) else a) for i, a in enumerate(args)]

    res = dispatch.FALLBACK
    if handle is not None and handle.hit:
        res = handle.call_grad(diff_idx)
    if res is not dispatch.FALLBACK:
        out, vjp_fn = res
    else:
        def g(*diff_vals):
            it = iter(diff_vals)
            full = [next(it) if i in diff_set else fixed_vals[i] for i in range(len(args))]
            return fn(*full, **kwargs)

        out, vjp_fn = jax.vjp(g, *(t._value for t in diff_tensors))
        if handle is not None and not handle.hit:
            handle.record(out)
    flat_out, out_tree = jax.tree_util.tree_flatten(out)
    if flags.flag("FLAGS_check_nan_inf"):
        _check_nan_inf(name, flat_out)
    out_avals = [(v.shape, v.dtype) for v in flat_out]
    node = GradNode(name, vjp_fn, diff_tensors, out_avals, out_tree)
    node.rebuild = (fn, fixed_vals, diff_set, len(args), kwargs,
                    tuple(t._value for t in diff_tensors))

    out_tensors = []
    for i, v in enumerate(flat_out):
        is_float = jnp.issubdtype(v.dtype, jnp.inexact)
        t = Tensor(v, stop_gradient=not is_float)
        if is_float:
            t._grad_node = node
            t._out_index = i
        out_tensors.append(t)
        node.out_refs.append(weakref.ref(t))
    if _state.touch_recorders:
        for rec in _state.touch_recorders:
            rec.created.update(id(t) for t in out_tensors)
    return jax.tree_util.tree_unflatten(out_tree, out_tensors)


# --------------------------------------------------------------------- engine


def _accumulate(holder, idx, val):
    cur = holder[idx]
    holder[idx] = val if cur is None else cur + val


def backward_from(root: Tensor, grad_tensor=None, retain_graph: bool = False):
    if grad_tensor is None:
        if root.size != 1:
            raise RuntimeError(
                "backward() on a non-scalar tensor requires an explicit grad_tensor"
            )
        grad_val = jnp.ones_like(root._value)
    else:
        grad_val = grad_tensor._value if isinstance(grad_tensor, Tensor) else jnp.asarray(grad_tensor)
    backward_multi([root], [grad_val], retain_graph)


def backward_multi(roots, grad_vals, retain_graph: bool = False):
    """Dependency-counted reverse walk (reference backward.cc:105)."""
    with no_grad():
        _backward_impl(roots, grad_vals, retain_graph, leaf_targets=None)


def _reachable_graph(root_nodes, create_graph=False):
    """BFS the node graph; return set of nodes + in-degree (number of consumer
    nodes whose vjp contributes cotangents into this node).

    Normal mode stops at released nodes (their outputs act as leaves, the
    long-standing partial-backward boundary); create_graph mode keeps them so
    the walk raises the clear already-released error instead of silently
    truncating the second-order graph."""
    seen = set()
    indeg = {}
    q = deque(root_nodes)
    for n in root_nodes:
        seen.add(n)
        indeg.setdefault(n, 0)
    while q:
        node = q.popleft()
        for t in node.inputs:
            child = t._grad_node
            if child is not None and (create_graph or not child.released):
                indeg[child] = indeg.get(child, 0) + 1
                if child not in seen:
                    seen.add(child)
                    q.append(child)
    return seen, indeg


def _run_hooks(tensor, grad_val):
    """Type-preserving: raw in → raw out; Tensor in (create_graph walk) →
    Tensor out, so hook results stay on the tape."""
    as_tensor = isinstance(grad_val, Tensor)
    for hook in list(tensor._hooks):
        res = hook(grad_val if as_tensor else Tensor(grad_val))
        if res is not None:
            if as_tensor:
                grad_val = res if isinstance(res, Tensor) else Tensor(res)
            else:
                grad_val = res._value if isinstance(res, Tensor) else res
    return grad_val


def _vjp_through_tape(node, cot_tensors):
    """Compute node's input cotangents THROUGH the tape (create_graph=True).

    Re-runs the recorded forward under jax.vjp inside `apply`, with both the
    original differentiable inputs and the incoming cotangents as
    differentiable inputs of a new '<name>_grad' node — so the returned
    grads carry grad nodes and support another backward() (the reference's
    generated double-grad GradNodes, e.g. MatmulDoubleGradNode).  Costs one
    forward recompute per node, the standard higher-order trade.
    """
    if node.released or node.rebuild is None:
        raise RuntimeError(
            f"Grad node '{node.name}' already released; pass retain_graph=True "
            "to the earlier backward()/grad() call to differentiate through "
            "this graph again."
        )
    fn, fixed_vals, diff_set, n_args, kwargs, snapshot = node.rebuild
    for t, snap in zip(node.inputs, snapshot):
        if t._value is not snap:
            raise RuntimeError(
                f"an input of op '{node.name}' needed for create_graph=True "
                "has been modified by an in-place operation since it was "
                "recorded"
            )
    k = len(node.inputs)

    def vjp_apply(*vals):
        diff_vals, cot_flat = vals[:k], vals[k:]

        def g(*dv):
            it = iter(dv)
            full = [next(it) if i in diff_set else fixed_vals[i] for i in range(n_args)]
            return fn(*full, **kwargs)

        _, vjp_fn = jax.vjp(g, *diff_vals)
        cot = jax.tree_util.tree_unflatten(node.out_tree, list(cot_flat))
        return tuple(vjp_fn(cot))

    outs = apply(f"{node.name}_grad", vjp_apply, *node.inputs, *cot_tensors)
    return list(outs) if isinstance(outs, (tuple, list)) else [outs]


def _backward_impl(roots, grad_vals, retain_graph, leaf_targets,
                   create_graph=False, boundary_ids=()):
    """If leaf_targets is not None: return grads for those tensors instead of
    writing .grad (used by paddle.grad).

    With create_graph=True every cotangent in flight is a Tensor and every
    vjp runs through `apply` (see _vjp_through_tape), so the returned grads
    are themselves differentiable."""
    holders = {}  # node -> list of cotangent values per output
    root_nodes = []
    leaf_grads = {}  # id(tensor) -> value (for leaf_targets mode)
    target_ids = {id(t) for t in leaf_targets} if leaf_targets is not None else None

    def _record_target(t, g):
        leaf_grads[id(t)] = g if id(t) not in leaf_grads else leaf_grads[id(t)] + g

    for root, gval in zip(roots, grad_vals):
        node = root._grad_node
        if node is None:
            # Root is a leaf: its grad is the seed itself.
            if not root.stop_gradient:
                gval = _run_hooks(root, gval)
                if leaf_targets is None:
                    _acc_tensor_grad(root, gval)
                else:
                    leaf_grads[id(root)] = (
                        gval if id(root) not in leaf_grads else leaf_grads[id(root)] + gval
                    )
            continue
        if node not in holders:
            holders[node] = [None] * node.n_outputs
            root_nodes.append(node)
        _accumulate(holders[node], root._out_index, gval)

    if not root_nodes:
        return leaf_grads

    nodes, indeg = _reachable_graph(root_nodes, create_graph=create_graph)
    ready = deque(n for n in nodes if indeg.get(n, 0) == 0)
    processed = set()

    while ready:
        node = ready.popleft()
        if node in processed:
            continue
        processed.add(node)
        cots = holders.get(node, [None] * node.n_outputs)
        full = []
        for i, (shape, dt) in enumerate(node.out_avals):
            v = cots[i]
            if v is None:
                v = Tensor(jnp.zeros(shape, dt)) if create_graph else jnp.zeros(shape, dt)
            else:
                ref = node.out_refs[i]() if i < len(node.out_refs) else None
                if ref is not None and ref._hooks:
                    v = _run_hooks(ref, v)
            full.append(v)
        if create_graph:
            if node.taped_vjp is not None:
                in_grads = node.taped_vjp(full)
            else:
                in_grads = _vjp_through_tape(node, full)
        else:
            cot_struct = jax.tree_util.tree_unflatten(node.out_tree, full)
            if node.released or node.vjp_fn is None:
                raise RuntimeError(
                    f"Grad node '{node.name}' already released; pass retain_graph=True "
                    "to backward() to backprop twice through the same graph."
                )
            in_grads = node.vjp_fn(cot_struct)
        # An explicit retain_graph=False releases even under create_graph:
        # the grad-of-grad nodes built by _vjp_through_tape carry their own
        # closures, so the first-order residuals can be freed.
        if not retain_graph:
            node.release()

        for t, g in zip(node.inputs, in_grads):
            if g is None:
                continue
            if id(t) in boundary_ids:
                # no_grad_set: this tensor receives no gradient and blocks
                # propagation into its producers (reference
                # python/paddle/base/dygraph/base.py grad no_grad_vars)
                child = t._grad_node
                if child is not None and child in indeg:
                    indeg[child] -= 1
                    if indeg[child] == 0:
                        ready.append(child)
                continue
            if getattr(g, "dtype", None) is not None and g.dtype == jax.dtypes.float0:
                continue
            child = t._grad_node
            if child is None or (child not in nodes):
                if not t.stop_gradient:
                    g = _run_hooks(t, g)
                    if leaf_targets is None:
                        _acc_tensor_grad(t, g)
                    else:
                        _record_target(t, g)
            else:
                if target_ids is not None and id(t) in target_ids:
                    _record_target(t, _run_hooks(t, g))
                if child not in holders:
                    holders[child] = [None] * child.n_outputs
                _accumulate(holders[child], t._out_index, g)
                indeg[child] -= 1
                if indeg[child] == 0:
                    ready.append(child)
    return leaf_grads


def _acc_tensor_grad(t: Tensor, g):
    if t.grad is None:
        t.grad = Tensor(g, stop_gradient=True)
    elif not hasattr(t.grad, "_value"):
        # a SelectedRows sparse grad already accumulated here (sparse
        # Embedding hook) now meets a dense contribution: densify
        t.grad = Tensor(t.grad.accumulate(g), stop_gradient=True)
    else:
        t.grad = Tensor(t.grad._value + g, stop_gradient=True)


def grad(
    outputs,
    inputs,
    grad_outputs=None,
    retain_graph=None,
    create_graph: bool = False,
    only_inputs: bool = True,
    allow_unused: bool = False,
    no_grad_vars=None,
):
    """paddle.grad equivalent (reference python/paddle/base/dygraph/base.py:615;
    create_graph=True builds the double-backward graph like the reference's
    generated double-grad nodes — see _vjp_through_tape)."""
    outputs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    if grad_outputs is None:
        grad_vals = [jnp.ones_like(o._value) for o in outputs]
    else:
        grad_outputs = grad_outputs if isinstance(grad_outputs, (list, tuple)) else [grad_outputs]
        grad_vals = [
            jnp.ones_like(o._value) if g is None else (g._value if isinstance(g, Tensor) else jnp.asarray(g))
            for o, g in zip(outputs, grad_outputs)
        ]
    # Reference semantics: retain_graph defaults to create_graph.
    retain = bool(retain_graph) if retain_graph is not None else bool(create_graph)
    boundary = {id(t) for t in (no_grad_vars or ())}
    if create_graph:
        # Cotangents must ride the tape: seed with Tensors (a grad_outputs
        # Tensor keeps its own grad node so grads can flow into it too) and
        # walk with grad recording ON.
        seeds = []
        for gv, go in zip(
            grad_vals, grad_outputs if grad_outputs is not None else [None] * len(grad_vals)
        ):
            seeds.append(go if isinstance(go, Tensor) else Tensor(gv))
        with enable_grad():
            leaf_grads = _backward_impl(
                outputs, seeds, retain, leaf_targets=inputs, create_graph=True,
                boundary_ids=boundary,
            )
    else:
        with no_grad():
            leaf_grads = _backward_impl(outputs, grad_vals, retain,
                                        leaf_targets=inputs,
                                        boundary_ids=boundary)
    results = []
    for t in inputs:
        g = leaf_grads.get(id(t))
        if g is None:
            if not allow_unused:
                raise RuntimeError(
                    "One of the differentiated tensors appears unused; pass allow_unused=True"
                )
            results.append(None)
        elif isinstance(g, Tensor):
            results.append(g)
        else:
            results.append(Tensor(g, stop_gradient=True))
    return results
