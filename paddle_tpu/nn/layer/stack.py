"""Stacked-layer scan engine: depth-constant trace and compile.

A Python ``for`` loop over N homogeneous decoder blocks traces and compiles
each block separately, so HLO size, trace time and XLA compile time grow
linearly with depth — a 32-layer LLaMA pays ~32x the compile of one block
and every process start recompiles from scratch.  ``LayerStack`` stacks the
parameters of N identical blocks along a new leading axis and executes the
stack as ONE ``jax.lax.scan`` whose body is the block traced once: the
program XLA sees is O(1) in depth ("Operator Fusion in XLA" shows fusion
works best over compact programs; MaxText/praxis use the same scan-over-
layers layout at scale).

Differentiability rides the `apply` funnel exactly like ``dy2static_run``:
the whole scan is one taped op, jax.vjp supplies the backward (scan
transposes to a reverse scan), and stacked-parameter grads accumulate into
the stacked Parameters so optimizers need no changes.

Recompute tiers (the reference's ``recompute_granularity``, PaddleNLP
llama modeling.py) are implemented with ``jax.checkpoint`` inside the scan
body:

- ``"full"``       — the body is wrapped in plain ``jax.checkpoint``
  (``nothing_saveable``): backward recomputes the whole block from its
  carry input.
- ``"full_attn"``  — no body-level checkpoint; cooperative blocks consult
  :func:`current_recompute_tier` and run their attention sublayer under
  ``fleet.recompute`` (a nested ``jax.checkpoint``), so exactly the
  attention sublayer recomputes while MLP/norm residuals stay saved
  (``LlamaDecoderLayer`` does this).
- ``"core_attn"``  — no body-level checkpoint; the core softmax(qk)v runs
  under its own ``jax.checkpoint`` (``scaled_dot_product_attention``
  consults the tier), so only the attention probabilities rematerialize.

Checkpoint-layout compatibility: state_dict keys for a stack at path ``P``
are ``P.<template key>`` with a leading ``[N, ...]`` axis, vs the unstacked
``P.<i>.<template key>``.  :func:`adapt_state_dict` converts either
direction against a target model (hooked into ``Layer.set_state_dict``), so
existing per-layer checkpoints load into scan models and scan checkpoints
load into loop models.
"""

from __future__ import annotations

import contextlib
import re
import threading

import jax
import jax.numpy as jnp

from paddle_tpu._core.tensor import Parameter, Tensor

from .layers import Layer

__all__ = [
    "LayerStack",
    "adapt_state_dict",
    "stack_state_dict",
    "unstack_state_dict",
    "current_recompute_tier",
    "recompute_tier_scope",
]

RECOMPUTE_TIERS = (None, "full", "full_attn", "core_attn")


class _TierState(threading.local):
    def __init__(self):
        self.tier = None


_tier_state = _TierState()


def current_recompute_tier():
    """The active recompute granularity (None outside a tier scope).
    Consulted by cooperative layers: ``scaled_dot_product_attention`` wraps
    its core in jax.checkpoint under 'core_attn'; blocks implement
    'full_attn' themselves by running their attention sublayer under
    ``fleet.recompute`` (see LlamaDecoderLayer)."""
    return _tier_state.tier


@contextlib.contextmanager
def recompute_tier_scope(tier):
    """Install a recompute granularity for the enclosed forward (used by
    LayerStack's scan body and by models running the unrolled loop with a
    sub-layer granularity)."""
    if tier not in RECOMPUTE_TIERS:
        raise ValueError(
            f"recompute granularity must be one of {RECOMPUTE_TIERS}, got {tier!r}")
    prev = _tier_state.tier
    _tier_state.tier = tier
    try:
        yield
    finally:
        _tier_state.tier = prev


def _is_stochastic(layer) -> bool:
    """Heuristic for blocks that draw training-time randomness: Dropout-type
    sublayers, or any sublayer carrying a positive dropout rate attribute
    (MultiHeadAttention stores `dropout` and calls functional dropout with
    no Dropout sublayer).  A baked key inside the scan body would reuse ONE
    mask across every layer and step, so err toward threading keys."""
    name = type(layer).__name__
    if "Dropout" in name:
        return True
    for attr in ("dropout", "dropout_p", "drop_rate"):
        v = getattr(layer, attr, None)
        if isinstance(v, (int, float)) and v > 0:
            return True
    return False


def _body_wrapper(tier):
    """The scan-body jax.checkpoint wrapper for a tier (None = identity).
    full_attn / core_attn remat inside the block itself (nested checkpoint
    engaged via the tier scope), so the body saves normally there."""
    if tier == "full":
        return jax.checkpoint
    return lambda f: f


class LayerStack(Layer):
    """Stack N homogeneous blocks into scanned, stacked-parameter form.

    ``forward(h, *args, **kwargs)`` threads ``h`` as the scan carry through
    every block; ``*args``/``**kwargs`` broadcast unchanged to each block
    (non-Tensor args and all kwargs are static).  Each block must return a
    single Tensor of ``h``'s shape.

    Iteration/indexing yield a per-layer *view*: the template block with
    tape-recorded slices of the stacked parameters bound in — so per-layer
    code paths (KV-cache decode, tensor-parallel placement walks,
    ``context_parallel_llama``) keep working; grads through a view flow
    into the stacked Parameters.  ALL views alias ONE template object and
    each ``stack[i]`` rebinds it in place: consume a view before taking the
    next (``for blk in stack: blk(...)``), never materialize several at
    once — ``list(stack)`` yields N references that all hold the LAST
    layer's weights.  (Attribute writes on a view, e.g. setting a mode
    flag, intentionally reach every layer — the shared-template contract
    context_parallel_llama uses.)

    ``recompute`` selects the granularity tier (see module docstring);
    ``needs_rng`` threads a distinct per-layer PRNG key through the scan
    body (auto-detected from Dropout sublayers) so stochastic blocks draw
    per-layer randomness instead of a frozen key.
    """

    def __init__(self, layers, recompute=None, needs_rng=None):
        super().__init__()
        layers = list(layers)
        if not layers:
            raise ValueError("LayerStack needs at least one layer")
        if recompute not in RECOMPUTE_TIERS:
            raise ValueError(
                f"recompute must be one of {RECOMPUTE_TIERS}, got {recompute!r}")
        template = layers[0]
        sds = [l.state_dict() for l in layers]
        ref_sd = sds[0]
        ref_struct = {k: (tuple(v._value.shape), str(v._value.dtype))
                      for k, v in ref_sd.items()}
        for i, (l, sd) in enumerate(zip(layers[1:], sds[1:]), 1):
            if type(l) is not type(template):
                raise TypeError(
                    f"LayerStack blocks must be homogeneous: block 0 is "
                    f"{type(template).__name__}, block {i} is {type(l).__name__}")
            struct = {k: (tuple(v._value.shape), str(v._value.dtype))
                      for k, v in sd.items()}
            if struct != ref_struct:
                raise ValueError(
                    f"LayerStack blocks must share one parameter structure; "
                    f"block {i} differs from block 0")
        # the template is a binding slot, NOT a sublayer: its own parameters
        # are shadowed by the stacked ones and must stay out of state_dict()
        self.__dict__["_template"] = template
        self._num_layers = len(layers)
        self._recompute = recompute

        param_names = {n for n, _ in template.named_parameters()}
        self._param_keys, self._buffer_keys = [], []
        for key in ref_sd:
            stacked = jnp.stack([sd[key]._value for sd in sds])
            if key in param_names:
                src = dict(template.named_parameters())[key]
                p = Parameter(stacked, trainable=not src.stop_gradient)
                self.add_parameter(key, p)
                self._param_keys.append(key)
            else:
                self.register_buffer(key, Tensor(stacked))
                self._buffer_keys.append(key)
        self._stack_keys = self._param_keys + self._buffer_keys
        # template-side binding slots, resolved once: (registry dict, name)
        self._slots = {}
        for key in self._stack_keys:
            owner = template
            *path, short = key.split(".")
            for part in path:
                owner = owner._sub_layers[part]
            reg = owner._parameters if short in owner._parameters else owner._buffers
            self._slots[key] = (reg, short)
        if needs_rng is None:
            needs_rng = any(_is_stochastic(l)
                            for l in template.sublayers(include_self=True))
        self._needs_rng = bool(needs_rng)

    # ------------------------------------------------------------ inspection
    @property
    def num_layers(self) -> int:
        return self._num_layers

    def stack_keys(self):
        """Per-layer template state keys, in stacked-state order."""
        return list(self._stack_keys)

    def __len__(self):
        return self._num_layers

    def _stacked_tensor(self, key):
        return (self._parameters[key] if key in self._parameters
                else self._buffers[key])

    def _bind_view(self, i):
        if not -self._num_layers <= i < self._num_layers:
            raise IndexError(f"layer index {i} out of range [0, {self._num_layers})")
        i = i % self._num_layers
        self._sync_template_mode()
        for key in self._stack_keys:
            reg, short = self._slots[key]
            reg[short] = self._stacked_tensor(key)[i]
        return self.__dict__["_template"]

    def __getitem__(self, i):
        return self._bind_view(i)

    def __iter__(self):
        for i in range(self._num_layers):
            yield self._bind_view(i)

    # -------------------------------------------------------------- forward
    def _sync_template_mode(self):
        # train()/eval() walk registered sublayers setting .training — the
        # hidden template is invisible to that walk, so mirror the stack's
        # mode onto it here (forward and view paths both call this)
        tpl = self.__dict__["_template"]
        if tpl.training != self.training:
            tpl.train() if self.training else tpl.eval()

    def forward(self, h, *args, **kwargs):
        from paddle_tpu.tensor._ops_common import apply

        self._sync_template_mode()

        if not isinstance(h, Tensor):
            h = Tensor(jnp.asarray(h))
        for k, v in kwargs.items():
            if isinstance(v, Tensor):
                raise TypeError(
                    f"LayerStack broadcast kwargs must be static; pass "
                    f"Tensor {k!r} positionally")
        tensor_pos = tuple(i for i, a in enumerate(args) if isinstance(a, Tensor))
        tensor_args = [args[i] for i in tensor_pos]
        statics = tuple((i, a) for i, a in enumerate(args)
                        if not isinstance(a, Tensor))
        state = [self._stacked_tensor(k) for k in self._stack_keys]
        extra = []
        if self._needs_rng and self.training:
            from paddle_tpu._core import random as rng_mod

            # raw (non-Tensor) arg: concrete in eager, a traced key inside
            # TrainStep/jit — either way split per layer inside the scan
            extra = [rng_mod.next_key()]
        return apply(
            "layer_stack_scan", self._scan_raw, *state, h, *tensor_args, *extra,
            _tensor_pos=tensor_pos, _statics=statics, _n_args=len(args),
            _kw=tuple(sorted(kwargs.items())), _has_key=bool(extra),
            _training=self.training,
        )

    def _scan_raw(self, *vals, _tensor_pos, _statics, _n_args, _kw, _has_key,
                  _training):
        """Raw scan body host fn (runs under the funnel's jax.vjp / jit
        trace).  A bound method so the dispatch cache can key it by
        (code, self): steady-state eager steps reuse one cached
        forward+pullback trace for the whole stack."""
        n_state = len(self._stack_keys)
        state_vals = list(vals[:n_state])
        carry0 = vals[n_state]
        rest = list(vals[n_state + 1:])
        base_key = rest.pop() if _has_key else None
        template = self.__dict__["_template"]
        slots = [self._slots[k] for k in self._stack_keys]
        kwargs = dict(_kw)
        from paddle_tpu._core import autograd as core_ag
        from paddle_tpu._core import random as rng_mod

        def body(carry, xs):
            slices, key = xs
            originals = [reg[short] for reg, short in slots]
            try:
                for (reg, short), v in zip(slots, slices):
                    reg[short] = Tensor(v)
                full = [None] * _n_args
                for i, a in _statics:
                    full[i] = a
                for i, v in zip(_tensor_pos, rest):
                    full[i] = Tensor(v)
                key_ctx = (rng_mod.key_scope(key) if key is not None
                           else contextlib.nullcontext())
                with key_ctx, core_ag.no_grad(), \
                        recompute_tier_scope(self._recompute):
                    out = template(Tensor(carry), *full, **kwargs)
                if not isinstance(out, Tensor):
                    raise TypeError(
                        "LayerStack blocks must return a single Tensor "
                        f"carry; got {type(out).__name__}")
                return out._value, None
            finally:
                for (reg, short), v in zip(slots, originals):
                    reg[short] = v

        body = _body_wrapper(self._recompute)(body)
        xs_keys = (jax.random.split(base_key, self._num_layers)
                   if base_key is not None else None)
        carry, _ = jax.lax.scan(
            body, carry0, (tuple(state_vals), xs_keys))
        return carry

    # ------------------------------------------------------- decode scan
    def decode_scan(self, body, h, k_state, v_state, extra=None):
        """Scan the stack ONCE over stacked per-layer KV state (the paged
        decode tier): ``body(layer, h, kc, vc) -> (h, kc, vc)`` is the
        per-layer decode step (e.g. ``models.llama._decode_layer_paged``
        with the broadcast args closed over); ``h`` is the Tensor carry;
        ``k_state``/``v_state`` are raw arrays with a leading layer axis
        ``[N, ...]`` riding the scan as xs/ys.  Returns
        ``(h, new_k_state, new_v_state)`` in the same stacked layout.

        ``extra``: an optional READ-ONLY pytree of per-layer state — every
        leaf carries the same leading ``[N, ...]`` layer axis and rides
        the scan as additional xs (sliced per layer, never returned as
        ys).  When given, the body takes a fourth argument:
        ``body(layer, h, kc, vc, extra_slice)``.  The multi-tenant LoRA
        AdapterPack threads its slot-stacked A/B matrices through here
        (nn/lora.py, docs/LORA.md).

        This is the serving-side counterpart of :meth:`forward`: the paged
        KV pools thread through the scan as per-layer state, so a decode
        step program traces and XLA-compiles ONE layer body regardless of
        depth.  Inference-only — it runs under ``no_grad`` inside the
        caller's jitted step (decode never differentiates), so it skips
        the ``apply`` funnel and recompute tiers entirely.
        """
        has_extra = extra is not None

        def step(layer, carry, xs):
            out, kc, vc = body(layer, Tensor(carry), *xs)
            if not isinstance(out, Tensor):
                raise TypeError(
                    "decode_scan body must return (Tensor, kc, vc); "
                    f"got {type(out).__name__} carry")
            return out._value, (kc, vc)

        if not isinstance(h, Tensor):
            h = Tensor(jnp.asarray(h))
        carry, (new_k, new_v) = self.scan(
            step, h._value,
            (k_state, v_state, extra) if has_extra else (k_state, v_state))
        return Tensor(carry), new_k, new_v

    def scan(self, body, carry, xs=None):
        """Scan the stack ONCE with any carry: ``body(layer, carry, xs_slice)
        -> (carry, ys)``, `layer` the template bound to one layer's weights,
        `carry` any pytree of raw arrays (a model whose layers hand on more
        than the hidden state carries it here), `xs` a pytree whose leaves
        have a leading layer axis ``[N, ...]`` and ride as per-layer state;
        returns ``(carry, ys)``, ys stacked the same way.  Inference-only,
        under ``no_grad`` inside the caller's jitted program, like
        :meth:`decode_scan`, which is this with a Tensor carry and the two
        K/V pools as xs and ys."""
        from paddle_tpu._core import autograd as core_ag

        self._sync_template_mode()
        template = self.__dict__["_template"]
        slots = [self._slots[k] for k in self._stack_keys]
        state_vals = [self._stacked_tensor(k)._value
                      for k in self._stack_keys]

        def scan_body(carry, xs):
            slices, own = xs
            originals = [reg[short] for reg, short in slots]
            try:
                for (reg, short), v in zip(slots, slices):
                    reg[short] = Tensor(v)
                with core_ag.no_grad():
                    return body(template, carry, own)
            finally:
                for (reg, short), v in zip(slots, originals):
                    reg[short] = v

        return jax.lax.scan(scan_body, carry, (tuple(state_vals), xs))


def shard_stacked_params(stack: "LayerStack", mesh, place_fn, col_keys,
                         row_keys):
    """Megatron TP placement over a LayerStack's stacked weights.

    The layer axis is axis 0, so relative to per-layer placement everything
    shifts right one: column-parallel weights [N, in, out] shard axis 2 and
    their biases [N, out] axis 1; row-parallel weights shard axis 1.
    ``place_fn(shard_axis)`` builds the full placement list (the caller owns
    the mesh-axis bookkeeping); ``col_keys``/``row_keys`` are sublayer paths
    relative to the block (e.g. "self_attn.q_proj")."""
    from paddle_tpu.distributed.auto_parallel import Shard, shard_tensor

    for key, p in list(stack._parameters.items()):
        prefix, _, leaf = key.rpartition(".")
        placement = None
        if prefix in col_keys:
            placement = Shard(2) if leaf == "weight" else Shard(1)
        elif prefix in row_keys and leaf == "weight":
            placement = Shard(1)
        if placement is not None:
            stack._parameters[key] = shard_tensor(
                p, mesh, place_fn(placement), stop_gradient=p.stop_gradient)
    return stack


# ------------------------------------------------------- layout converters


def stack_state_dict(state_dict: dict, prefix: str, num_layers: int,
                     keys=None) -> dict:
    """Convert ``{prefix}.{i}.{key}`` per-layer entries into one stacked
    ``{prefix}.{key}`` entry each (leading axis = layer).  Non-matching
    entries pass through untouched."""
    out = dict(state_dict)
    pre = f"{prefix}." if prefix else ""  # prefix "" = the stack IS the root
    if keys is None:
        pat = re.compile(re.escape(pre) + r"0\.(.+)$")
        keys = [m.group(1) for k in state_dict if (m := pat.match(k))]
    for key in keys:
        per_layer = [f"{pre}{i}.{key}" for i in range(num_layers)]
        if not all(p in state_dict for p in per_layer):
            continue
        vals = []
        for p in per_layer:
            v = out.pop(p)
            vals.append(v._value if isinstance(v, Tensor) else jnp.asarray(v))
        out[f"{pre}{key}"] = Tensor(jnp.stack(vals))
    return out


def unstack_state_dict(state_dict: dict, prefix: str, num_layers: int,
                       keys) -> dict:
    """Inverse of :func:`stack_state_dict`: split ``{prefix}.{key}`` stacked
    entries back into ``{prefix}.{i}.{key}`` per-layer entries."""
    out = dict(state_dict)
    pre = f"{prefix}." if prefix else ""
    for key in keys:
        name = f"{pre}{key}"
        if name not in state_dict:
            continue
        v = out.pop(name)
        arr = v._value if isinstance(v, Tensor) else jnp.asarray(v)
        if arr.shape[0] != num_layers:
            raise ValueError(
                f"stacked entry {name!r} has leading dim {arr.shape[0]}, "
                f"expected {num_layers}")
        for i in range(num_layers):
            out[f"{pre}{i}.{key}"] = Tensor(arr[i])
    return out


def adapt_state_dict(model: Layer, state_dict: dict, own=None) -> dict:
    """Convert a checkpoint between stacked and unstacked decoder layouts to
    match ``model``'s own layout (no-op when layouts already agree).

    Both directions are driven by the model: a LayerStack at path P stacks
    matching ``P.{i}.{key}`` checkpoint entries; a per-layer stack of keys
    ``P.{i}.{key}`` in the model unstacks a matching ``P.{key}`` entry whose
    leading dim equals the layer count.  ``own`` lets the caller reuse an
    already-built ``model.state_dict()``.
    """
    out = state_dict
    # stacked model <- unstacked checkpoint (include_self: the stack may BE
    # the root model being loaded, with path "")
    for path, sub in model.named_sublayers(include_self=True):
        if isinstance(sub, LayerStack):
            pre = f"{path}." if path else ""
            missing = [k for k in sub.stack_keys()
                       if f"{pre}{k}" not in state_dict]
            if missing and f"{pre}0.{missing[0]}" in state_dict:
                out = stack_state_dict(out, path, len(sub), sub.stack_keys())
    # unstacked model <- stacked checkpoint
    if own is None:
        own = model.state_dict()
    pat = re.compile(r"^(.*?)\.(\d+)\.(.+)$")
    groups: dict = {}
    for name in own:
        m = pat.match(name)
        if m:
            prefix, idx, key = m.group(1), int(m.group(2)), m.group(3)
            g = groups.setdefault((prefix, key), set())
            g.add(idx)
    for (prefix, key), idxs in groups.items():
        n = len(idxs)
        if idxs != set(range(n)):
            continue
        stacked_name = f"{prefix}.{key}"
        if stacked_name in out and f"{prefix}.0.{key}" not in out:
            v = out[stacked_name]
            arr = v._value if isinstance(v, Tensor) else jnp.asarray(v)
            if arr.ndim and arr.shape[0] == n:
                out = unstack_state_dict(out, prefix, n, [key])
    return out
