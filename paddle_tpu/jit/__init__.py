"""paddle.jit equivalent (reference: python/paddle/jit/api.py:240 to_static,
python/paddle/jit/sot bytecode capture).

TPU-native design: because every op in this framework is jax-traceable and
the autograd tape composes with tracing, "dynamic-to-static" needs no CPython
frame hook — jax.jit IS the graph capture.  `to_static` wraps a callable (or
Layer) so calls are traced once per input signature and run as one compiled
XLA program, with the AST-mode dy2static transformer (jit/dy2static)
rewriting python control flow over tensors into lax.cond/while_loop.
`TrainStep` functionalizes a full imperative train step (forward,
loss.backward(), optimizer.step()) into one compiled, donated-state program —
the replacement for the reference's C++ eager hot path + fused optimizer
kernels.

CAPTURE-TIER SCOPE: the reference ships TWO capture modes — AST transform
(full graph) and SOT bytecode interception with guard-based graph breaks
(python/paddle/jit/sot/translate.py:99, eval_frame.c).  SOT exists because
the reference's eager tier cannot be traced directly, so unsupported
constructs need transparent fallback mid-function.  Here the eager tier IS
the traceable tier: every op works under jax tracing, untraceable constructs
(data-dependent shapes) raise documented errors naming the fix, and AST mode
covers control flow — so a bytecode tier would add CPython-version-coupled
machinery without new capability.  Decision: AST-only, revisit only if a
concrete workload needs guard-based partial graphs.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu._core import random as rng_mod
from paddle_tpu._core.autograd import no_grad
from paddle_tpu._core.tensor import Parameter, Tensor
from paddle_tpu.profiler import RecordEvent, startup

__all__ = ["to_static", "TrainStep", "not_to_static", "save", "load", "ignore_module"]


def _host_device():
    """default_device(cpu) context, or a no-op if no cpu backend exists
    (JAX_PLATFORMS naming an accelerator only)."""
    import contextlib

    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        return contextlib.nullcontext()


def _unwrap(x):
    return x._value if isinstance(x, Tensor) else x


def _wrap(x):
    return Tensor(x) if isinstance(x, jax.Array) else x


class _StaticFunction:
    """Compiled wrapper around a function or Layer.forward.

    The whole transformed function compiles to one XLA executable per
    (training mode, arg structure, static python args) — and the call is
    routed through the `apply` funnel, so the tape can differentiate
    THROUGH the compiled program (the reference's run_program op records a
    grad op the same way, python/paddle/jit/dy2static/partial_program.py).
    Non-Tensor positional args (python ints/floats/bools) are STATIC: they
    keep exact python semantics inside (loop bounds, flags) and a new value
    triggers a recompile, like the reference's input_spec specialization.
    """

    def __init__(self, fn, layer=None, full_graph=True, backend=None):
        from paddle_tpu.jit.dy2static import ast_transform

        # AST-mode dy2static (reference ast_transformer.py): rewrite python
        # if/while/and/or/not over tensors into lax control flow converters;
        # falls back to the original fn when source is unavailable.
        self._fn = ast_transform(fn)
        self._orig_fn = fn
        self._layer = layer
        self._cache = {}

    def _state_tensors(self):
        if self._layer is None:
            return []
        return list(self._layer.state_dict().values())

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled:  # jit.enable_to_static(False) escape hatch
            return self._orig_fn(*args, **kwargs)
        from paddle_tpu._core.autograd import apply

        layer = self._layer
        state = self._state_tensors()
        # array-valued kwargs are dynamic traced inputs just like positional
        # arrays; only python scalars & co. stay static
        kwargs = {
            k: (Tensor(jnp.asarray(v)) if isinstance(v, (np.ndarray, jax.Array)) else v)
            for k, v in kwargs.items()
        }
        static_kwargs = {k: v for k, v in kwargs.items() if not isinstance(v, Tensor)}
        tensor_kwargs = {k: v for k, v in kwargs.items() if isinstance(v, Tensor)}

        flat, tree = jax.tree_util.tree_flatten(
            list(args), is_leaf=lambda x: isinstance(x, Tensor)
        )
        # array-valued leaves (Tensor / ndarray / jax.Array) are DYNAMIC
        # traced inputs; only python scalars & co. are static
        flat = [
            Tensor(jnp.asarray(l)) if isinstance(l, (np.ndarray, jax.Array)) else l
            for l in flat
        ]
        t_idx = tuple(i for i, l in enumerate(flat) if isinstance(l, Tensor))
        t_set = set(t_idx)
        static_leaves = tuple(
            (i, flat[i]) for i in range(len(flat)) if i not in t_set
        )
        kw_names = tuple(sorted(tensor_kwargs))
        key_parts = [
            layer.training if layer else None, tree, t_idx, kw_names,
        ]
        cacheable = True
        try:
            # type names disambiguate 1 / 1.0 / True (equal+same hash in
            # python, but different trace-time constants)
            typed = tuple((i, type(v).__name__, v) for i, v in static_leaves)
            hash(typed)
            key_parts.append(typed)
        except TypeError:
            cacheable = False  # unhashable python leaf: compile-per-call
            key_parts.append(None)
        try:
            kw_typed = tuple(
                sorted((k, type(v).__name__, v) for k, v in static_kwargs.items())
            )
            hash(kw_typed)
            key_parts.append(kw_typed)
        except TypeError:
            cacheable = False
            key_parts.append(None)
        cache_key = tuple(key_parts)

        entry = self._cache.get(cache_key) if cacheable else None
        if entry is None:
            fn = self._fn
            n_s, n_t, n_k = len(state), len(t_idx), len(kw_names)

            # capture only the STATIC leaves (not `flat`, which holds the
            # first call's tensor buffers and would pin them for the cache
            # entry's lifetime)
            proto = [None] * len(flat)
            for i, v in static_leaves:
                proto[i] = v

            @jax.jit
            def static_function(state_vals, t_vals, kw_vals, key):
                originals = [t._value for t in state]
                try:
                    for t, v in zip(state, state_vals):
                        t._bind(v)
                    full = list(proto)
                    for i, v in zip(t_idx, t_vals):
                        full[i] = _wrap(v)
                    rebuilt = jax.tree_util.tree_unflatten(tree, full)
                    wrapped_kw = {k: _wrap(v) for k, v in zip(kw_names, kw_vals)}
                    with rng_mod.key_scope(key), no_grad():
                        out = fn(*rebuilt, **wrapped_kw, **static_kwargs)
                    return jax.tree_util.tree_map(
                        _unwrap, out, is_leaf=lambda x: isinstance(x, Tensor)
                    )
                finally:
                    for t, v in zip(state, originals):
                        t._bind(v)

            holder = {}

            def op_fn(*vals, _key=None):
                sv = list(vals[:n_s])
                tv = list(vals[n_s:n_s + n_t])
                kv = list(vals[n_s + n_t:])
                out = static_function(sv, tv, kv, _key)
                flat_out, out_tree = jax.tree_util.tree_flatten(out)
                holder["tree"] = out_tree
                return tuple(flat_out) if len(flat_out) != 1 else flat_out[0]

            entry = (op_fn, holder)
            if cacheable:
                self._cache[cache_key] = entry
        op_fn, holder = entry

        inputs = list(state) + [flat[i] for i in t_idx] + [tensor_kwargs[k] for k in kw_names]
        key = rng_mod.next_key()
        if not inputs:  # pure-python call: nothing for the tape to track
            res = op_fn(_key=key)
            res = (
                tuple(_wrap(r) for r in res)
                if isinstance(res, tuple)
                else _wrap(res)
            )
        else:
            res = apply("dy2static_run", functools.partial(op_fn, _key=key), *inputs)
        # out structure comes from THIS call's trace (op_fn ran just now),
        # so shape-dependent output trees stay correct across shapes
        out_tree = holder["tree"]
        leaves = list(res) if isinstance(res, (tuple, list)) else [res]
        return jax.tree_util.tree_unflatten(out_tree, leaves)


def to_static(function=None, input_spec=None, build_strategy=None, backend=None,
              mode="ast", **kwargs):
    """Decorator/wrapper: compile a function or Layer (reference jit/api.py:240).

    mode="ast" (default): whole-function trace+jit (the AST dy2static tier).
    mode="sot": bytecode-level capture with guards and graph-break fallback
    (jit/sot.py — the reference's symbolic-opcode-translation tier)."""

    def decorate(obj):
        from paddle_tpu.nn import Layer

        if mode == "sot":
            from .sot import symbolic_translate

            if isinstance(obj, Layer):
                obj.forward = symbolic_translate(obj.forward)
                return obj
            return symbolic_translate(obj)
        if isinstance(obj, Layer):
            sf = _StaticFunction(obj.forward, layer=obj)
            obj.forward = sf
            return obj
        return _StaticFunction(obj)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


class TrainStep:
    """Functionalize an imperative train step into one compiled XLA program.

    Usage:
        step = TrainStep(model, optimizer, loss_fn)   # loss_fn(model, *batch)->loss
        loss = step(x, y)                             # compiled after warmup

    Step 0 runs eagerly (creates optimizer accumulator state); subsequent
    steps run a jitted program whose inputs/outputs are the flat state pytree
    (params + buffers + optimizer state), with state donated so XLA updates
    in place (HBM-neutral, like the reference's in-place optimizer kernels).
    """

    def __init__(self, model, optimizer, loss_fn, scaler=None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.scaler = scaler
        self._compiled = None
        self._state = None
        self._aot = {}  # batch signature -> AOT-compiled executable

    def _collect_state(self):
        tensors = list(self.model.state_dict().values())
        tensors += self.optimizer.opt_state_tensors()
        if self.scaler is not None and self.scaler.is_enable():
            tensors += self.scaler.state_tensors()
        return tensors

    def _post_backward(self):
        """Hook between loss.backward() and optimizer.step() inside the
        traced program — ShardedTrainStep's comm/compute overlap rewrites
        gradients here (grad-sync decomposition, docs/PIPELINE.md)."""

    def _pin_state(self, vals):
        """Hook on the state the traced step returns — ShardedTrainStep
        pins every output to the sharding its input was placed with."""
        return vals

    def _eager_step(self, *batch):
        loss = self.loss_fn(self.model, *batch)
        if self.scaler is not None and self.scaler.is_enable():
            self.scaler.scale(loss).backward()
            self.scaler.step(self.optimizer)
        else:
            loss.backward()
            self.optimizer.step()
        self.optimizer.clear_grad()
        return loss

    def _ensure_built(self):
        if self._compiled is None:
            # Materialize optimizer accumulators WITHOUT an eager
            # forward/backward (hundreds of per-op XLA compiles, and every
            # activation live at once).  The zero-grad journaled step runs
            # on the host CPU backend (uncommitted params follow it there —
            # state already device_put to an accelerator stays); the
            # compiled step moves the fresh state to the accelerator on its
            # first call, where chip_smoke.py checks that it arrived.
            # GradScaler state is device tensors (amp/__init__.py) and joins
            # the state list.
            params = [p for p in self.optimizer._parameter_list if not p.stop_gradient]
            with startup.phase("jit.train_step.build.optimizer_state",
                               "train_optimizer_state_seconds"):
                with _host_device():
                    self.optimizer._journaled_step(params)
                self._state = self._collect_state()
            self._build()

    @staticmethod
    def _batch_sig(batch_vals):
        leaves, tree = jax.tree_util.tree_flatten(batch_vals)
        sig = []
        for v in leaves:
            if not hasattr(v, "dtype"):
                # python-scalar leaf: normalize through jnp so the signature
                # matches warmup()'s aval-based one ('int32', not 'int')
                v = jnp.asarray(v)
            sig.append((tuple(v.shape), str(v.dtype)))
        return (tree, tuple(sig))

    def _maybe_mesh_lint(self, batch):
        """FLAGS_verify_sharding hook: statically lint the freshly built
        step (placements, collective congruence, donation contract,
        per-device memory estimate) before the first dispatch — the
        abstract analysis never launches a collective, so a placement bug
        fails HERE with a named site instead of hanging the mesh
        (static/mesh_lint.py, docs/MESH_LINT.md)."""
        from paddle_tpu._core import flags as _flags

        if not _flags.flag("FLAGS_verify_sharding"):
            return
        from paddle_tpu.static.mesh_lint import lint_train_step

        lint_train_step(self, *batch, raise_on_error=True)

    def __call__(self, *batch):
        """One step.  Spans: `jit.train_step` around the call; on the call
        that builds the step, `jit.train_step.build` with its two halves —
        `.build.optimizer_state` (the accumulators, made on the host CPU
        backend) and `.build.trace` (the first call of the jitted step:
        jax traces, lowers and compiles or reads the cache there, then
        enqueues); on every later call `jit.train_step.dispatch`.  The build
        call is also the step's `program.first_use`: it WAITS for the first
        loss, and each of its spans adds its seconds to
        `profiler.startup_stats()` (`train_build_seconds` and its parts)."""
        with RecordEvent("jit.train_step"):
            if self._compiled is not None:
                return self._dispatch(batch,
                                      RecordEvent("jit.train_step.dispatch"))
            with startup.phase("jit.train_step.build", "train_build_seconds"):
                self._ensure_built()
                self._maybe_mesh_lint(batch)
                shapes = [tuple(getattr(b, "shape", ())) for b in batch]
                with startup.first_use("jit_train_step", shapes):
                    loss = self._dispatch(batch, startup.phase(
                        "jit.train_step.build.trace",
                        "train_build_trace_seconds"))
                    jax.block_until_ready(loss._value)
                return loss

    def _dispatch(self, batch, span):
        """One call of the step under `span`, a span not yet entered."""
        batch_vals = jax.tree_util.tree_map(_unwrap, batch, is_leaf=lambda x: isinstance(x, Tensor))
        key = rng_mod.next_key()
        if self.optimizer._lr_scheduler is not None:
            self.optimizer._sync_lr()  # scheduler advanced eagerly between steps
        state_vals = [t._value for t in self._state]
        # signature lookup only when warmup() populated AOT executables —
        # the plain path stays free of per-step flatten cost
        step_fn = (self._aot.get(self._batch_sig(batch_vals), self._compiled)
                   if self._aot else self._compiled)
        with span:
            new_state, loss_val = step_fn(state_vals, batch_vals, key)
        for t, v in zip(self._state, new_state):
            t._bind(v)
        return Tensor(loss_val)

    def lower(self, *batch):
        """AOT entry: trace the step for `batch` (Tensors, arrays, or
        jax.ShapeDtypeStructs) and return the jax Lowered object without
        running it — `.compile()` pays XLA compilation ahead of traffic."""
        self._ensure_built()

        def aval(x):
            v = _unwrap(x)
            if isinstance(v, jax.ShapeDtypeStruct):
                return v
            v = jnp.asarray(v)
            return jax.ShapeDtypeStruct(v.shape, v.dtype)

        batch_avals = jax.tree_util.tree_map(
            aval, batch, is_leaf=lambda x: isinstance(x, Tensor))
        state_avals = [jax.ShapeDtypeStruct(t._value.shape, t._value.dtype)
                       for t in self._state]
        # key aval derived WITHOUT consuming a global RNG tick: warmup must
        # not shift the training random stream
        key_aval = jax.eval_shape(lambda: jax.random.fold_in(
            jax.random.key(0), 0))
        return self._compiled.lower(state_avals, batch_avals, key_aval)

    def warmup(self, *batch):
        """Pay trace + XLA compile for `batch`'s signature before traffic
        (values or ShapeDtypeStructs; no step is executed, no state or RNG
        advances).  The executable is kept, so the first real step with
        this signature runs it directly; with FLAGS_compilation_cache_dir
        set the compile also persists across process restarts.  Returns
        self for chaining: TrainStep(...).warmup(x, y)."""
        lowered = self.lower(*batch)
        compiled = lowered.compile()

        def aval(x):
            v = _unwrap(x)
            return v if isinstance(v, jax.ShapeDtypeStruct) else jnp.asarray(v)

        batch_avals = jax.tree_util.tree_map(
            aval, batch, is_leaf=lambda x: isinstance(x, Tensor))
        self._aot[self._batch_sig(batch_avals)] = compiled
        return self

    def _build(self):
        model, optimizer, loss_fn, scaler = self.model, self.optimizer, self.loss_fn, self.scaler
        state = self._state

        # the function's name is the program's: `jit_train_step` on the
        # trace's XLA Modules line and in PjitFunction(...) host events
        @functools.partial(jax.jit, donate_argnums=(0,))
        def train_step(state_vals, batch_vals, key):
            originals = [t._value for t in state]
            grads_saved = [getattr(t, "grad", None) for t in state]
            try:
                for t, v in zip(state, state_vals):
                    t._bind(v)
                    t.grad = None
                    t._grad_node = None
                with rng_mod.key_scope(key):
                    batch = jax.tree_util.tree_map(
                        _wrap, batch_vals, is_leaf=lambda x: isinstance(x, jax.Array)
                    )
                    loss = loss_fn(model, *batch)
                    if scaler is not None and scaler.is_enable():
                        scaler.scale(loss).backward()
                        self._post_backward()
                        scaler.step(optimizer)
                    else:
                        loss.backward()
                        self._post_backward()
                        optimizer.step()
                    optimizer.clear_grad()
                new_vals = self._pin_state([t._value for t in state])
                return new_vals, loss._value
            finally:
                for t, v, g in zip(state, originals, grads_saved):
                    t._bind(v)
                    t.grad = g
                    t._grad_node = None

        self._compiled = train_step


def save(layer, path, input_spec=None, **configs):
    """paddle.jit.save (reference jit/api.py:849 emits .pdmodel/.pdiparams).

    With input_spec (paddle.static.InputSpec list) the layer's forward is
    captured into a static Program and exported as the StableHLO deploy
    artifact (loadable by paddle_tpu.inference.Predictor / jit.load); params
    are also saved as .pdparams for state_dict-style reload.
    """
    from paddle_tpu.framework.io_utils import save as fsave

    state = {"state_dict": dict(layer.state_dict()), "class": type(layer).__name__}
    fsave(state, path + ".pdparams")

    if input_spec:
        import paddle_tpu.static as static

        main = static.Program()
        with static.program_guard(main):
            feeds = [
                static.data(s.name or f"x{i}", s.shape, s.dtype)
                for i, s in enumerate(input_spec)
            ]
            was_training = layer.training
            layer.eval()
            try:
                out = layer(*feeds)
            finally:
                if was_training:
                    layer.train()
            fetch = list(out) if isinstance(out, (tuple, list)) else [out]
        # forward deploy-time optimization configs (passes/precision/
        # extra_precisions — the reference jit.save's build_strategy analog)
        export_kw = {k: configs[k] for k in
                     ("passes", "precision", "extra_precisions") if k in configs}
        static.save_inference_model(path, feeds, fetch, program=main,
                                    **export_kw)


def load(path, **configs):
    """Returns a Predictor if a .pdmodel artifact exists, else the saved
    state payload."""
    import os

    if os.path.exists(path + ".pdmodel"):
        from paddle_tpu.inference import Predictor

        return Predictor(path)
    from paddle_tpu.framework.io_utils import load as fload

    return fload(path + ".pdparams")


# ----------------------------------------------------------- compat surface
# TranslatedLayer is what jit.load returns in the reference
# (python/paddle/jit/translated_layer.py); here load() returns the Predictor
# over the saved StableHLO artifact, so the name aliases that type for
# isinstance checks on loaded models.
from paddle_tpu.inference import Predictor as TranslatedLayer  # noqa: E402


def enable_to_static(flag: bool = True):
    """Globally toggle to_static capture (reference:
    python/paddle/jit/api.py enable_to_static); when off, decorated functions
    run eagerly — the debugging escape hatch."""
    global _to_static_enabled
    _to_static_enabled = bool(flag)


_to_static_enabled = True


_dy2static_log_level = 0


def set_code_level(level: int = 100, also_to_stdout: bool = False):
    """Log transformed code of dy2static (reference jit/api.py). Level > 0
    prints the AST-transformed source when to_static compiles a function."""
    global _dy2static_log_level
    _dy2static_log_level = int(level)


def set_verbosity(level: int = 0, also_to_stdout: bool = False):
    """Verbosity for dy2static logging (reference parity)."""
    global _dy2static_log_level
    _dy2static_log_level = max(_dy2static_log_level, int(level))
