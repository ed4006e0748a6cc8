"""The routed-expert layer every served model with experts shares: the router
(`route`), the held experts' part of the layer for experts already chosen
(`expert_loop`; `routed_experts` is `route` and then the loop), and the
layers that hold their weights (`SwiGLU`, `RoutedExperts`).  models/mla_moe.py
(sigmoid scoring, openPangu-Ultra-MoE's family) and models/window_moe.py
(softmax scoring, Laguna's) both build their expert layers from here; what
differs between them (scoring, top-k, the scale, the shared expert's width)
is an argument taken from the model's own config.  models/cca_moe.py's
router is not one matrix (an MLP over a state carried across depth, top-1
with a skip choice): it chooses for itself and calls `expert_loop`.

The layer is DROPLESS and is told which contiguous range of experts it holds
(`held = (first, count)`): it routes over ALL `routed` experts, normalises
over the k chosen wherever they live, computes only its own experts' part
(plus the shared expert) and passes that partial result on: one chip's
share of an expert-parallel layer, without the exchange.  Nothing stands in
for the absent chips.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu._core.tensor import Tensor

__all__ = ["EXPERT_TILE", "SCORINGS", "route", "routed_experts",
           "expert_loop", "SwiGLU", "RoutedExperts", "add_counts"]

# rows of one expert processed per pass of the expert loop (prefill); a
# decode step's pass is its whole batch
EXPERT_TILE = 256

# the router's score of an expert from its logits over ALL experts
SCORINGS = {"sigmoid": jax.nn.sigmoid,
            "softmax": lambda logits: jax.nn.softmax(logits, axis=-1)}


def route(m, router_w, *, top_k, scale, normalize=True, scoring="sigmoid",
          bias=None):
    """The router: m [T, h], router_w [h, E] -> (chosen [T, k] int32, w [T, k]
    float32).  s = `scoring`(m W_r) over ALL E experts ("sigmoid", or
    "softmax" over the E logits), in float32 with the product at highest
    precision whatever the types handed in (on a TPU a float32 product is
    bfloat16 passes by default, and a score's eighth and ninth expert lie
    within that rounding of each other for one token in six); the k best;
    w_e = scale * s_e / (sum of the k chosen scores).  `bias` [E] (a
    correction bias, `noaux_tc`): the k best of s + bias are chosen, and
    the weights are still the chosen experts' unbiased s."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(m.astype(jnp.float32), router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if bias is None:
            top_s, top_i = jax.lax.top_k(SCORINGS[scoring](logits), top_k)
        else:
            s = SCORINGS[scoring](logits)
            _, top_i = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
            top_s = jnp.take_along_axis(s, top_i, axis=-1)
        w = top_s
        if normalize:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return top_i.astype(jnp.int32), w * jnp.float32(scale)


def routed_experts(m, router_w, gate_up, down, *, held, top_k, scale,
                   normalize=True, scoring="sigmoid", active=None,
                   tile=EXPERT_TILE, bias=None):
    """The held experts' part of a routed-expert layer whose router is one
    matrix: `route` over router_w [h, E] (E: ALL experts; `bias` its
    correction bias, if any), then `expert_loop` over what it chose (w
    normalised over all top_k chosen, wherever they live)."""
    top_i, w = route(m, router_w, top_k=top_k, scale=scale,
                     normalize=normalize, scoring=scoring, bias=bias)
    return expert_loop(m, top_i, w, gate_up, down, held=held, active=active,
                       tile=tile)


def expert_loop(m, chosen, w, gate_up, down, *, held, active=None,
                tile=EXPERT_TILE, routed=None, base=None):
    """The held experts' part of a routed-expert layer, dropless, for experts
    ALREADY CHOSEN: m [T, h]; chosen [T, k] int32 and w [T, k] float32, each
    row's experts among ALL of them and their weights, from whatever router
    the caller has.

    gate_up and down: the weights of experts first .. first + count - 1,
    [h, 2f] and [f, h] each, either a LIST of `count` arrays (one array an
    expert: the loop over experts is then unrolled, an expert's passes a
    code path of their own) or ONE array with a leading axis (the loop over
    experts is then one loop whose body indexes the stack: a program
    1/count the size; held expert e is entry e, or entry `base + e` where
    `base`, a traced int32, says where this layer's experts begin in a
    stack of several layers'); in both an expert nobody chose runs no pass
    and reads no weight.  active [T] bool or None (rows that are not
    committed work route nowhere and are not counted).  Returns (out [T, h]
    float32, counts): out = sum over each row's chosen experts THAT ARE
    HELD of w_e E_e(m); counts = the int32 scalars assignments, held, peak
    (rows on the busiest held expert), touched (held experts with a row),
    layer_steps (1 if any row is live) and skipped (live rows none of whose
    choices is an expert: an index at or past `routed`, the count of ALL
    experts, is a router's "no expert"; 0 where `routed` is not given).

    Static shapes throughout, so it runs inside the macro-step's scan and
    the prefill program: the (row, choice) pairs are sorted by held expert
    (one stable argsort; pairs of absent experts sort behind), and each
    held expert runs ceil(rows / tile) passes of `tile` rows of its
    contiguous range — a while loop whose trip count is data, so an expert
    nobody chose reads no weight, and no row is ever dropped whatever the
    router does."""
    first, count = held
    t, top_k = m.shape[0], chosen.shape[1]
    with jax.named_scope("moe.route"):
        local = chosen - first
        mine = (local >= 0) & (local < count)
        live = jnp.ones((t,), bool) if active is None else active
        mine = mine & live[:, None]
        key = jnp.where(mine, local, count).reshape(-1)         # [T*k]
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        rows_of = order // top_k          # the row of each sorted pair
        w_of = w.reshape(-1)[order]
        per = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                      dtype=jnp.int32)                          # [count]
        start = jnp.cumsum(per) - per
    tile = min(tile, t)
    n_pairs = t * top_k

    stacked = not isinstance(gate_up, (list, tuple))
    f = down[0].shape[0]

    def one_tile(e, i, out):
        # indexed INSIDE the pass: a stack is sliced only by a pass that
        # runs, as a list's array is read only by its own pass
        at_e = e if base is None else base + e
        w_gu, w_d = gate_up[at_e], down[at_e]
        at = start[e] + i * tile + jnp.arange(tile, dtype=jnp.int32)
        ok = at < start[e] + per[e]
        at = jnp.minimum(at, n_pairs - 1)
        rows = rows_of[at]
        x = m[rows]                                         # [tile, h]
        gu = jnp.dot(x, w_gu, preferred_element_type=jnp.float32)
        act = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(m.dtype)
        y = jnp.dot(act, w_d, preferred_element_type=jnp.float32)
        y = y * jnp.where(ok, w_of[at], 0.0)[:, None]
        return out.at[rows].add(y)

    def expert_pass(e, out):
        if stacked and tile == t:
            # one pass holds every row (a decode step): the loop's 0 or 1
            # trips written as the conditional they are, so that the slice
            # of the stack stays inside it (as a loop's invariant XLA lifts
            # it out and reads all `count` experts a step: 4.93 against 5.82
            # ms for four layers of 32 rows at laguna's widths, PERF.md)
            return jax.lax.cond(per[e] > 0, lambda o: one_tile(e, 0, o),
                                lambda o: o, out)
        return jax.lax.fori_loop(0, -(-per[e] // tile),
                                 lambda i, o: one_tile(e, i, o), out)

    out = jnp.zeros((t, m.shape[1]), jnp.float32)
    with jax.named_scope("moe.experts"):
        if stacked:
            out = jax.lax.fori_loop(0, count, expert_pass, out)
        else:
            for e in range(count):
                out = expert_pass(e, out)
    counts = {"assignments": jnp.sum(live, dtype=jnp.int32) * top_k,
              "held": jnp.sum(per), "peak": jnp.max(per),
              "touched": jnp.sum(per > 0, dtype=jnp.int32),
              "layer_steps": jnp.any(live).astype(jnp.int32),
              "skipped": jnp.int32(0) if routed is None else jnp.sum(
                  live & jnp.all(chosen >= routed, axis=-1), dtype=jnp.int32)}
    return out, counts


def add_counts(totals, counts):
    """Sum an expert layer's `counts` (None for a dense layer) into the
    running `totals` of a model's walk over its layers (None at first)."""
    if counts is None:
        return totals
    if totals is None:
        return counts
    return {k: totals[k] + v for k, v in counts.items()}


class SwiGLU(nn.Layer):
    """silu(x W_g) * (x W_u) -> W_d, gate and up fused into one matmul: the
    dense layers' FFN and the shared expert (the Pallas swiglu kernel on a
    TPU, as models/llama.LlamaMLP)."""

    def __init__(self, hidden, width):
        super().__init__()
        self.gate_up_proj = nn.Linear(hidden, 2 * width, bias_attr=False)
        self.down_proj = nn.Linear(width, hidden, bias_attr=False)

    def forward(self, x):
        gate, up = paddle.split(self.gate_up_proj(x), 2, axis=-1)
        from paddle_tpu import ops as _ops

        if _ops.use_pallas():
            import paddle_tpu.incubate.nn.functional as _FF

            return self.down_proj(_FF.swiglu(gate, up))
        return self.down_proj(F.silu(gate) * up)


class RoutedExperts(nn.Layer):
    """The expert layer's FFN: router over all `routed` experts, the held
    experts' weights (`held = (first, count)`), the shared expert
    (`shared_width` wide; ungated).  `forward` returns (f, counts).
    `stacked`: the held experts' weights as TWO parameters with a leading
    expert axis (`gate_up` [count, h, 2 width], `down` [count, width, h],
    each matrix drawn as an `nn.Linear`'s is) and not as `count` `SwiGLU`s:
    `routed_experts` then runs ONE loop over experts, and the programs that
    hold an expert layer are `count` times smaller.  `score_bias`: the
    router's correction bias [routed] (`noaux_tc`: it moves the choice, not
    the weights), a parameter `score_correction_bias`, zero as built."""

    def __init__(self, hidden, width, *, routed, held, top_k, scale,
                 normalize=True, scoring="sigmoid", shared_width=None,
                 stacked=False, score_bias=False):
        super().__init__()
        if scoring not in SCORINGS:
            raise ValueError(f"scoring must be one of {sorted(SCORINGS)}")
        self.held, self.top_k, self.scale = tuple(held), top_k, scale
        self.normalize, self.scoring = normalize, scoring
        self.gate = nn.Linear(hidden, routed, bias_attr=False)
        # expert i here is routed expert held[0] + i
        self.stacked, count = stacked, self.held[1]
        if stacked:
            self.gate_up = self.create_parameter(
                [count, hidden, 2 * width],
                default_initializer=I.XavierNormal(hidden, 2 * width))
            self.down = self.create_parameter(
                [count, width, hidden],
                default_initializer=I.XavierNormal(width, hidden))
        else:     # only the SwiGLUs' weights are used
            self.experts = nn.LayerList([SwiGLU(hidden, width)
                                         for _ in range(count)])
        self.shared_experts = SwiGLU(hidden, shared_width or width)
        self.score_correction_bias = (self.create_parameter(
            [routed], default_initializer=I.Constant(0.0))
            if score_bias else None)

    def expert_weights(self):
        """(gate_up, down) as `routed_experts` takes them."""
        if self.stacked:
            return self.gate_up._value, self.down._value
        return ([e.gate_up_proj.weight._value for e in self.experts],
                [e.down_proj.weight._value for e in self.experts])

    def forward(self, m, active=None):
        shape = m.shape
        flat = m._value.reshape(-1, shape[-1])
        routed, counts = routed_experts(
            flat, self.gate.weight._value, *self.expert_weights(),
            held=self.held,
            top_k=self.top_k, scale=self.scale, normalize=self.normalize,
            scoring=self.scoring, active=active,
            bias=(None if self.score_correction_bias is None
                  else self.score_correction_bias._value))
        with jax.named_scope("moe.shared"):
            shared = self.shared_experts(m)
        f = routed.reshape(shape) + shared._value.astype(jnp.float32)
        return Tensor(f.astype(m._value.dtype)), counts
