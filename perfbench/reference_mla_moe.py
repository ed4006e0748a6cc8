"""The plain reference of the latent-attention decoder with routed experts, a
shared expert and sandwich norms (openPangu-Ultra-MoE-718B's block): the
forward pass in straightforward `jax.numpy`, float32, matmuls at
`jax.default_matmul_precision("highest")`.  No kernels, no cache, no sorting,
no absorbed attention, and no code shared with `paddle_tpu.models`; the family
module hands it the model's weights (any float type; cast here).

Written from the equations (h hidden, N heads, r_q / r latent ranks, d_n / d_r
/ d_v head parts; rms(x) = x / sqrt(mean(x^2) + eps)):

  n      = rms(x) g_in
  c_q    = rms(n W_qa) g_qa
  q_i    = c_q W_qb -> (q_nope_i [d_n], rope(q_rope_i) [d_r])
  (c', k_r) = n W_kva ;  c = rms(c') g_kva ;  k_rope = rope(k_r)
  (k_nope_i, v_i) = c W_kvb
  p_i    = causal softmax((q_nope_i.k_nope_i + q_rope_i.k_rope) / sqrt(d_n + d_r))
  a      = x + rms(concat_i(p_i v_i) W_o) g_post_attn
  m      = rms(a) g_pre_mlp
  dense layer:   f = W_d(silu(W_g m) * (W_u m))
  expert layer:  s = sigmoid(m W_r);  T = top-k(s);  w_e = scale s_e / sum_T s
                 f = sum_{e in T, e held} w_e E_e(m) + E_shared(m)
  x'     = a + rms(f) g_post_mlp
  logits = rms(x_L) g H

The expert layer takes the same `held = (first, count)` range as the program:
the router scores ALL experts and the weights are normalised over the k chosen
wherever they live, but only experts first .. first + count - 1 are computed;
what the absent ones would add is left out, exactly as the program leaves it
out.  Each held expert is applied to every token and weighted by w_e (zero
where the token did not choose it): no sorting, no capacity, nothing dropped.

Departures from the published code, none of which changes a shape: rope pairs
adjacent lanes (2i, 2i+1) where Hugging Face's code rotates halves (a fixed
permutation of W_qb's and W_kva's rope columns, which only a loader of real
checkpoints applies); sigmoid scoring with no expert groups and no score bias
(the configuration file's `assumed`); no rope scaling; the next-token-
prediction module is not part of the main model's logits and is not computed.

So that 8,200 positions fit beside a serving engine on one chip, a layer is
computed in pieces, each a small jitted function that casts only the weights it
multiplies: attention per group of `head_group` heads and in blocks of `q_block`
queries (no [S, S] matrix for more than a block), the dense FFN in column
chunks, the experts one at a time.

Weights layout (`weights`): "embed" [V, h]; "norm" [h]; "head" [h, V];
"layers": a list of dicts with "g_in", "g_qa", "g_kva", "g_pre_mlp", and with
`sandwich_norm` "g_post_attn" and "g_post_mlp" (absent or None: no such norm), "w_qa" [h, r_q], "w_qb" [r_q, N(d_n+d_r)], "w_kva"
[h, r+d_r], "w_kvb" [r, N(d_n+d_v)], "w_o" [N d_v, h], and either "w_gate_up"
[h, 2F] (the gate's columns, then the up projection's: the served layout, so
that no second copy of the weights is made) and "w_down" [F, h] (dense), or
"w_router" [h, E], "shared" and "experts" (a (w_gate_up, w_down) pair, and a
list of them for the held experts in order).  `sizes`: {"heads", "nope",
"rope", "v", "eps", "theta", "top_k", "scale", "normalize", "held", and
optionally "dtype"}: "dtype"
"bfloat16" computes EVERYTHING (router and softmax too) in bfloat16 at default
precision — the lower-precision twin that the cell's limit must tell apart.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HEAD_GROUP = 16     # heads per attention piece: 0.41 GB of temporaries at
Q_BLOCK = 256       # 8,320 positions (32 and 512 queries a block: 1.36 GB)
COL_CHUNK = 4096    # dense-FFN columns per piece


def _prec(dt):
    return "highest" if dt == jnp.float32 else "default"


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x [S, n, d], positions 0..S-1; pairs (2i, 2i+1)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([even * c - odd * s, odd * c + even * s],
                     axis=-1).reshape(x.shape).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "rank", "dt"))
def _latents(x, g_in, w_qa, g_qa, w_kva, g_kva, *, eps, theta, rank, dt):
    """n, c_q, c and the roped shared key of every token."""
    with jax.default_matmul_precision(_prec(dt)):
        n = _rms(x, g_in, eps)
        c_q = _rms(n @ w_qa.astype(dt), g_qa, eps)
        kva = n @ w_kva.astype(dt)
        c = _rms(kva[:, :rank], g_kva, eps)
        k_rope = _rope(kva[:, None, rank:], theta)[:, 0]
        return c_q, c, k_rope


@functools.partial(jax.jit, static_argnames=("nope", "rope", "v", "theta",
                                             "q_block", "dt"))
def _attention_group(c_q, c, k_rope, w_qb, w_kvb, w_o, *, nope, rope, v,
                     theta, q_block, dt):
    """The heads of one group: their part of concat_i(p_i v_i) W_o, [S, h].
    w_qb [r_q, G(d_n+d_r)], w_kvb [r, G(d_n+d_v)], w_o [G d_v, h]."""
    with jax.default_matmul_precision(_prec(dt)):
        s = c.shape[0]
        q = (c_q @ w_qb.astype(dt)).reshape(s, -1, nope + rope)
        kv = (c @ w_kvb.astype(dt)).reshape(s, -1, nope + v)
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], theta)
        k_nope, val = kv[..., :nope], kv[..., nope:]
        out = []
        for at in range(0, s, q_block):
            upto = min(s, at + q_block)
            score = (jnp.einsum("qgd,sgd->gqs", q_nope[at:upto], k_nope[:upto])
                     + jnp.einsum("qgd,sd->gqs", q_rope[at:upto], k_rope[:upto])
                     ) / jnp.sqrt(jnp.asarray(nope + rope, dt))
            seen = (jnp.arange(upto)[None, :]
                    <= (at + jnp.arange(upto - at))[:, None])
            prob = jax.nn.softmax(jnp.where(seen[None], score, -jnp.inf), -1)
            out.append(jnp.einsum("gqs,sgd->qgd", prob, val[:upto]))
        ctx = jnp.concatenate(out, axis=0).reshape(s, -1)
        return ctx @ w_o.astype(dt)


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


def _now(x):
    """Wait for one piece before the next is asked for.  A piece's output is
    allocated when it is DISPATCHED, and the host dispatches a whole layer's
    pieces in milliseconds: unbounded, the pieces of 8,320 positions held 4.6
    GiB beside a serving engine's 10.7 (the cell's peak, 15.3-15.5 of 15.75
    GiB); one at a time they hold what the docstring says."""
    return jax.block_until_ready(x)


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "normalize", "dt"))
def _route(m, w_router, *, top_k, scale, normalize, dt):
    """Scores of ALL experts, the k chosen and their weights: [S, E] weights
    (zero where not chosen), the router logits' gap between the k-th and the
    (k+1)-th expert, and the k + 1 best experts in order."""
    with jax.default_matmul_precision(_prec(dt)):
        logits = m @ w_router.astype(dt)
        score = jax.nn.sigmoid(logits)
        top_s, top_i = jax.lax.top_k(score, top_k + 1)
        w = top_s[:, :top_k].astype(jnp.float32)
        if normalize:
            w = w / jnp.sum(w, -1, keepdims=True)
        w = w * scale
        dense = jnp.zeros(score.shape, jnp.float32).at[
            jnp.arange(m.shape[0])[:, None], top_i[:, :top_k]].set(w)
        top_l = jnp.take_along_axis(logits, top_i, axis=1).astype(jnp.float32)
        return dense, top_l[:, top_k - 1] - top_l[:, top_k], top_i


def _layer(x, w, sizes, dt, probe):
    eps, theta = sizes["eps"], sizes["theta"]
    nope, rope, v = sizes["nope"], sizes["rope"], sizes["v"]
    c_q, c, k_rope = _latents(
        x, w["g_in"], w["w_qa"], w["g_qa"], w["w_kva"], w["g_kva"],
        eps=eps, theta=theta, rank=w["w_kvb"].shape[0], dt=dt)
    attn = jnp.zeros_like(x)
    for g0 in range(0, sizes["heads"], HEAD_GROUP):
        g1 = min(sizes["heads"], g0 + HEAD_GROUP)
        attn = _now(attn + _attention_group(
            c_q, c, k_rope, w["w_qb"][:, g0 * (nope + rope):g1 * (nope + rope)],
            w["w_kvb"][:, g0 * (nope + v):g1 * (nope + v)],
            w["w_o"][g0 * v:g1 * v], nope=nope, rope=rope, v=v, theta=theta,
            q_block=Q_BLOCK, dt=dt))
    if w.get("g_post_attn") is not None:      # sandwich_norm
        attn = _rms(attn, w["g_post_attn"], eps)
    a = x + attn
    m = _rms(a, w["g_pre_mlp"], eps)
    if "w_router" not in w:
        f = _ffn(m, w["w_gate_up"], w["w_down"], dt)
    else:
        first, count = sizes["held"]
        weight, gap, top = _route(
            m, w["w_router"], top_k=sizes["top_k"], scale=sizes["scale"],
            normalize=sizes["normalize"], dt=dt)
        if probe is not None:
            edge = top[:, -2:]        # the k-th and the (k+1)-th expert
            held_edge = ((edge >= first) & (edge < first + count)).any(-1)
            probe.append((gap, held_edge, top[:, :-1], m))
        f = _ffn(m, *w["shared"], dt)
        for e, expert in enumerate(w["experts"]):
            f = _now(f + (weight[:, first + e, None]
                          * _ffn(m, *expert, dt)).astype(f.dtype))
    if w.get("g_post_mlp") is not None:
        f = _rms(f, w["g_post_mlp"], eps)
    return _now(a + f)


def hidden(weights, sizes, ids, probe=None):
    """x_L of ONE sequence `ids` [S] -> [S, h].  `probe`, a list, receives
    per expert layer (gap [S], held_edge [S], chosen [S, k], m [S, h]): the
    router logits' gap between each token's k-th and (k+1)-th expert, whether
    either of the two is held, the k experts chosen, and the router's input."""
    dt = jnp.dtype(sizes.get("dtype", "float32"))
    x = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(dt)
    for w in weights["layers"]:
        x = _layer(x, w, sizes, dt, probe)
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
    output into this token's result.  (Other tokens' near ties reach a
    position only through attention, one key among thousands.)"""
    dt = jnp.dtype(sizes.get("dtype", "float32"))
    probe = []
    at = jnp.asarray(positions)
    x = hidden(weights, sizes, _seen(ids, positions), probe)[at]
    tie = jnp.zeros(len(positions), bool)
    for gap, held_edge, _chosen, _m in probe:
        tie = tie | ((gap[at] < tau) & held_edge[at])
    lg = _head(x, weights["norm"], weights["head"], eps=sizes["eps"], dt=dt)
    return lg, tie


def absorbed_attention(q, rows, lens, rank, width):
    """What a decode step's attention must return for given inputs: q [B, N,
    r + d_r], rows [B, S, r + d_r] (each sequence's cache rows in order; the
    first `lens[b]` are live), -> sum_s softmax_s(q . row(s) / sqrt(width))
    row(s)[:rank], [B, N, rank]; float32 at highest precision whatever the
    inputs' type."""
    with jax.default_matmul_precision("highest"):
        q = jnp.asarray(q).astype(jnp.float32)
        rows = jnp.asarray(rows).astype(jnp.float32)
        score = jnp.einsum("bnr,bsr->bns", q, rows) / jnp.sqrt(jnp.float32(width))
        seen = jnp.arange(rows.shape[1])[None, :] < jnp.asarray(lens)[:, None]
        prob = jax.nn.softmax(jnp.where(seen[:, None, :], score, -jnp.inf), -1)
        return jnp.einsum("bns,bsr->bnr", prob, rows[..., :rank])
