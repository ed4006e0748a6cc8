"""The plain reference of the window / full attention decoder with a per-head
output gate and routed experts beside a shared one (Laguna-S-2.1's block): the
forward pass in straightforward `jax.numpy`, float32, matmuls at
`jax.default_matmul_precision("highest")`.  No kernels, no cache, no ring, no
sorting, and no code shared with `paddle_tpu.models`; the family module hands
it the model's weights (any float type; cast here).

Written from the equations (layer l: N_l query heads, a window W_l or none;
Nkv K/V heads of d lanes; rms(x) = x / sqrt(mean(x^2) + eps)):

  n   = rms(x) g_in
  q   = n W_q [N_l x d]    k = n W_k [Nkv x d]    v = n W_v [Nkv x d]
  g   = sigmoid(n W_g) [N_l]
  q,k = rope_l(q, k, position)
  a_h = softmax_j(q_h . k_{h // (N_l / Nkv), j} / sqrt(d) + mask_l(i, j)) v
        mask: j <= i, and j > i - W_l where the layer has a window
  x   = x + concat_h(g_h a_h) W_o
  m   = rms(x) g_post
  dense:   x = x + W_d(silu(W_g m) * (W_u m))
  sparse:  s = softmax(m W_r) over all experts;  T = top-k(s);
           w_e = scale s_e / sum_T s
           x = x + sum_{e in T, e held} w_e E_e(m) + E_shared(m)
  logits = rms(x_L) g H

rope_l, from the layer's `rope` entry (r = d x partial_rotary_factor rotated
lanes, the FIRST r of a head; the rest pass through unrotated and unscaled):
pos_i = theta^(2i/r) for the r/2 pairs; "default": inv_freq_i = 1 / pos_i;
"yarn": corr(b) = r ln(original / (2 pi b)) / (2 ln theta), low =
floor(corr(beta_fast)), high = ceil(corr(beta_slow)), ramp_i = clip((i - low)
/ (high - low), 0, 1), inv_freq_i = (1 - ramp_i) / pos_i + ramp_i / (factor
pos_i), and cos and sin are multiplied by attention_factor.  Worked in float64
on the host and rounded to float32 once.  Pairs are adjacent lanes (2i, 2i+1)
(Hugging Face's code rotates halves: a fixed permutation of W_q's and W_k's
rotated columns, which only a loader of real checkpoints applies).

The expert layer takes the same `held = (first, count)` range as the program:
the router scores ALL experts and the weights are normalised over the k chosen
wherever they live, but only experts first .. first + count - 1 are computed;
what the absent ones would add is left out, exactly as the program leaves it
out.  Each held expert is applied to every token and weighted by w_e (zero
where the token did not choose it): no sorting, no capacity, nothing dropped.

So that 8,320 positions fit beside a serving engine on one chip, a layer is
computed in pieces, each a small jitted function that casts only the weights it
multiplies: attention per K/V group (the N_l / Nkv query heads that share one
K/V head) and in blocks of `Q_BLOCK` queries against ALL keys up to the block
(the window is a mask, never a shorter key range), the dense FFN in column
chunks, the experts one at a time.

Weights layout (`weights`): "embed" [V, h]; "norm" [h]; "head" [h, V];
"layers": a list of dicts with "g_in", "g_post", "w_qkv" [h, (N_l + 2 Nkv) d]
(q's columns, then k's, then v's: the served layout, so that no second copy
is made), "w_g" [h, N_l], "w_o" [N_l d, h], and either "w_gate_up" [h, 2F] and
"w_down" [F, h] (dense), or "w_router" [h, E], "shared" and "experts" (a
(w_gate_up, w_down) pair, and a list of them for the held experts in order).
`sizes`: {"layers": [{"heads", "window" (or None), "rope": {...}}, ...],
"kv_heads", "head_dim", "eps", "top_k", "scale", "normalize", "held"} and
optionally the CONTROLS, each of which computes something else on purpose and
must not compare equal: "dtype" "bfloat16" (EVERYTHING, router and softmax
too, in bfloat16 at default precision: the lower-precision twin the cell's
limits must tell apart), "softmax_dtype" / "router_dtype" "bfloat16" (only
the attention softmax, or only the router, in bfloat16), "ignore_window" (the
sliding layers see all keys), "no_gate" (g = 1).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256       # queries per attention piece: 9 heads x 256 x 8,320 scores
COL_CHUNK = 4096    # dense-FFN columns per piece


def _prec(dt):
    return "highest" if dt == jnp.float32 else "default"


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _now(x):
    """Wait for one piece before the next is asked for: a piece's output is
    allocated when it is dispatched, and unbounded dispatch of a layer's
    pieces held gigabytes beside a resident engine (reference_mla_moe)."""
    return jax.block_until_ready(x)


# ---------------------------------------------------------------------- rope

def inv_freq(rope: dict, head_dim: int):
    """(inv_freq float32 [r / 2], attention_factor, r) of one layer's rope
    entry, from the module docstring's formulas."""
    r = int(round(head_dim * float(rope.get("partial_rotary_factor", 1.0))))
    theta = float(rope["rope_theta"])
    pos = np.array([theta ** (2.0 * i / r) for i in range(r // 2)], np.float64)
    if rope.get("rope_type", "default") == "default":
        return (1.0 / pos).astype(np.float32), 1.0, r
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}: default or yarn")
    factor = float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])
    corr = lambda b: (r * math.log(original / (2 * math.pi * b))  # noqa: E731
                      / (2 * math.log(theta)))
    low = max(math.floor(corr(float(rope.get("beta_fast", 32)))), 0)
    high = min(math.ceil(corr(float(rope.get("beta_slow", 1)))), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = (1.0 - ramp) / pos + ramp / (factor * pos)
    af = rope.get("attention_factor")
    return (inv.astype(np.float32),
            0.1 * math.log(factor) + 1.0 if af is None else float(af), r)


def _rope(x, inv, af, r):
    """x [S, n, d], positions 0..S-1: the first r lanes rotated in adjacent
    pairs (2i, 2i+1), cos and sin times af; the rest as they are."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    c = (jnp.cos(ang) * jnp.float32(af))[:, None, :]
    s = (jnp.sin(ang) * jnp.float32(af))[:, None, :]
    xf = x[..., :r].astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    turned = jnp.stack([even * c - odd * s, odd * c + even * s],
                       axis=-1).reshape(xf.shape).astype(x.dtype)
    return jnp.concatenate([turned, x[..., r:]], axis=-1)


# ----------------------------------------------------------------- attention

@functools.partial(jax.jit, static_argnames=("eps", "dt"))
def _normed(x, g, *, eps, dt):
    return _rms(x, g, eps).astype(dt)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "d", "dt"))
def _project(n, w_qkv, w_g, *, heads, kv_heads, d, dt):
    with jax.default_matmul_precision(_prec(dt)):
        s = n.shape[0]
        qkv = n @ w_qkv.astype(dt)
        q = qkv[:, :heads * d].reshape(s, heads, d)
        k = qkv[:, heads * d:(heads + kv_heads) * d].reshape(s, kv_heads, d)
        v = qkv[:, (heads + kv_heads) * d:].reshape(s, kv_heads, d)
        gate = jax.nn.sigmoid((n @ w_g.astype(dt)).astype(jnp.float32))
        return q, k, v, gate.astype(dt)


@functools.partial(jax.jit, static_argnames=("window", "q_block", "dt", "sdt"))
def _attend_group(q, k, v, *, window, q_block, dt, sdt):
    """One K/V head and the query heads that share it: q [S, G, d], k and v
    [S, d] -> [S, G, d].  Scores, mask and softmax in `sdt`."""
    with jax.default_matmul_precision(_prec(dt)):
        s, _g, d = q.shape
        out = []
        for at in range(0, s, q_block):
            upto = min(s, at + q_block)
            score = (jnp.einsum("qgd,sd->gqs", q[at:upto], k[:upto])
                     / jnp.sqrt(jnp.asarray(d, dt))).astype(sdt)
            i = (at + jnp.arange(upto - at))[:, None]
            j = jnp.arange(upto)[None, :]
            seen = j <= i
            if window is not None:
                seen = seen & (j > i - window)
            prob = jax.nn.softmax(jnp.where(seen[None], score, -jnp.inf), -1)
            out.append(jnp.einsum("gqs,sd->qgd", prob.astype(dt), v[:upto]))
        return jnp.concatenate(out, axis=0)


@functools.partial(jax.jit, static_argnames=("dt",))
def _gate_and_project(x, ctx, gate, w_o, *, dt):
    """x + concat_h(g_h a_h) W_o; ctx [S, N, d], gate [S, N]."""
    with jax.default_matmul_precision(_prec(dt)):
        gated = (ctx * gate[:, :, None]).reshape(ctx.shape[0], -1)
        return x + gated @ w_o.astype(dt)


# ----------------------------------------------------------------------- FFN

@functools.partial(jax.jit, static_argnames=("dt",))
def _swiglu(m, w_gate, w_up, w_down, *, dt):
    with jax.default_matmul_precision(_prec(dt)):
        return (jax.nn.silu(m @ w_gate.astype(dt))
                * (m @ w_up.astype(dt))) @ w_down.astype(dt)


def _ffn(m, w_gate_up, w_down, dt, chunk=COL_CHUNK):
    """W_d(silu(W_g m) * (W_u m)), `chunk` of the F columns at a time."""
    width = w_down.shape[0]
    f = jnp.zeros((m.shape[0], w_down.shape[1]), m.dtype)
    for at in range(0, width, chunk):
        to = min(width, at + chunk)
        f = _now(f + _swiglu(m, w_gate_up[:, at:to],
                             w_gate_up[:, width + at:width + to],
                             w_down[at:to], dt=dt))
    return f


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "normalize", "dt"))
def _route(m, w_router, *, top_k, scale, normalize, dt):
    """Softmax scores of ALL experts, the k chosen and their weights: [S, E]
    weights (zero where not chosen), the router LOGITS' gap between the k-th
    and the (k+1)-th expert, and the k + 1 best experts in order."""
    with jax.default_matmul_precision(_prec(dt)):
        logits = m.astype(dt) @ w_router.astype(dt)
        score = jax.nn.softmax(logits, axis=-1)
        top_s, top_i = jax.lax.top_k(score, top_k + 1)
        w = top_s[:, :top_k].astype(jnp.float32)
        if normalize:
            w = w / jnp.sum(w, -1, keepdims=True)
        w = w * scale
        dense = jnp.zeros(score.shape, jnp.float32).at[
            jnp.arange(m.shape[0])[:, None], top_i[:, :top_k]].set(w)
        top_l = jnp.take_along_axis(logits, top_i, axis=1).astype(jnp.float32)
        return dense, top_l[:, top_k - 1] - top_l[:, top_k], top_i


# --------------------------------------------------------------------- layers

def _layer(x, w, geometry, sizes, dt, probe):
    eps, d, kv_heads = sizes["eps"], sizes["head_dim"], sizes["kv_heads"]
    heads = geometry["heads"]
    window = None if sizes.get("ignore_window") else geometry["window"]
    sdt = jnp.dtype(sizes.get("softmax_dtype", dt))
    n = _normed(x, w["g_in"], eps=eps, dt=dt)
    q, k, v, gate = _project(n, w["w_qkv"], w["w_g"], heads=heads,
                             kv_heads=kv_heads, d=d, dt=dt)
    inv, af, r = inv_freq(geometry["rope"], d)
    q, k = _rope(q, inv, af, r), _rope(k, inv, af, r)
    if sizes.get("no_gate"):
        gate = jnp.ones_like(gate)
    group = heads // kv_heads
    ctx = [_now(_attend_group(q[:, g * group:(g + 1) * group], k[:, g], v[:, g],
                              window=window, q_block=Q_BLOCK, dt=dt, sdt=sdt))
           for g in range(kv_heads)]
    a = _now(_gate_and_project(x, jnp.concatenate(ctx, axis=1), gate, w["w_o"],
                               dt=dt))
    m = _normed(a, w["g_post"], eps=eps, dt=dt)
    if "w_router" not in w:
        return _now(a + _ffn(m, w["w_gate_up"], w["w_down"], dt))
    first, count = sizes["held"]
    rdt = jnp.dtype(sizes.get("router_dtype", dt))
    weight, gap, top = _route(m, w["w_router"], top_k=sizes["top_k"],
                              scale=sizes["scale"],
                              normalize=sizes["normalize"], dt=rdt)
    if probe is not None:
        edge = top[:, -2:]        # the k-th and the (k+1)-th expert
        held_edge = ((edge >= first) & (edge < first + count)).any(-1)
        probe.append((gap, held_edge, top[:, :-1], m))
    f = _ffn(m, *w["shared"], dt)
    for e, expert in enumerate(w["experts"]):
        f = _now(f + (weight[:, first + e, None]
                      * _ffn(m, *expert, dt)).astype(f.dtype))
    return _now(a + f)


def hidden(weights, sizes, ids, probe=None):
    """x_L of ONE sequence `ids` [S] -> [S, h].  `probe`, a list, receives
    per expert layer (gap [S], held_edge [S], chosen [S, k], m [S, h]): the
    router logits' gap between each token's k-th and (k+1)-th expert, whether
    either of the two is held, the k experts chosen, and the router's input."""
    dt = jnp.dtype(sizes.get("dtype", "float32"))
    x = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(dt)
    for w, geometry in zip(weights["layers"], sizes["layers"]):
        x = _layer(x, w, geometry, sizes, dt, probe)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "dt"))
def _head(x, norm, head, *, eps, dt):
    with jax.default_matmul_precision(_prec(dt)):
        return (_rms(x, norm, eps) @ head.astype(dt)).astype(jnp.float32)


def _seen(ids, positions, to=128):
    """`ids` without the tail behind the last position asked for (causal:
    nothing there is seen), cut at the next multiple of `to` so that the
    pieces compile for a few lengths, not for every length."""
    n = min(len(ids), -(-(max(int(p) for p in positions) + 1) // to) * to)
    return jnp.asarray(ids)[:n]


def logits_at(weights, sizes, ids, positions):
    """Reference logits [len(positions), V] of ONE sequence `ids` [S] at the
    given positions (each row predicts the token after that position)."""
    dt = jnp.dtype(sizes.get("dtype", "float32"))
    x = hidden(weights, sizes, _seen(ids, positions))[jnp.asarray(positions)]
    return _head(x, weights["norm"], weights["head"], eps=sizes["eps"], dt=dt)


def logits_and_near_ties(weights, sizes, ids, positions, tau):
    """`logits_at`, and for each position whether ITS OWN routing is a near
    tie in some expert layer: the router logits of its k-th and (k+1)-th
    expert lie within `tau` of each other and one of the two is held, so
    rounding in the program's hidden state may put a different expert's
    output into this token's result."""
    dt = jnp.dtype(sizes.get("dtype", "float32"))
    probe = []
    at = jnp.asarray(positions)
    x = hidden(weights, sizes, _seen(ids, positions), probe)[at]
    tie = jnp.zeros(len(positions), bool)
    for gap, held_edge, _chosen, _m in probe:
        tie = tie | ((gap[at] < tau) & held_edge[at])
    lg = _head(x, weights["norm"], weights["head"], eps=sizes["eps"], dt=dt)
    return lg, tie


def window_attention(q, k, v, lens, window):
    """What a decode step's attention must return for given inputs: q [B, N,
    d] (one query a row, at position lens[b] - 1), k and v [B, S, Nkv, d]
    (each sequence's rows in order of POSITION; the first lens[b] are live),
    `window` W or None -> [B, N, d]; float32 at highest precision whatever
    the inputs' type."""
    with jax.default_matmul_precision("highest"):
        q = jnp.asarray(q).astype(jnp.float32)
        k = jnp.asarray(k).astype(jnp.float32)
        v = jnp.asarray(v).astype(jnp.float32)
        b, n, d = q.shape
        nkv = k.shape[2]
        qg = q.reshape(b, nkv, n // nkv, d)
        score = jnp.einsum("bkgd,bskd->bkgs", qg, k) / jnp.sqrt(jnp.float32(d))
        j = jnp.arange(k.shape[1])[None, :]
        lens = jnp.asarray(lens)[:, None]
        seen = j < lens
        if window is not None:
            seen = seen & (j >= lens - window)
        prob = jax.nn.softmax(jnp.where(seen[:, None, None, :], score,
                                        -jnp.inf), -1)
        return jnp.einsum("bkgs,bskd->bkgd", prob, v).reshape(b, n, d)
