"""The plain reference of the decoder with attention in a convolved latent, a
one-token value shift, an MLP router carried across depth, top-1 of a few
wide experts with a skip choice, and scaled residuals (ZAYA1-8B's block): the
forward pass in straightforward `jax.numpy`, float32, matmuls at
`jax.default_matmul_precision("highest")`.  No kernels, no cache, no state, no
sorting, no scan over layers, and no code shared with `paddle_tpu.models`; the
family module hands it the model's weights (any float type; cast here).

Written from the equations (E hidden, Hq query heads on Hkv K/V heads of d
lanes, G = Hq / Hkv, C = (Hq + Hkv) d; rms(x) = x / sqrt(mean(x^2) + eps);
everything at a position t < 0 is zero):

  n    = rms(x) g_a
  z    = n W_qk = [q~ ; k~]
  c_t  = a0 z_{t-1} + a1 z_t + b1              depthwise, kernel 2
  u_t  = B0 c_{t-1} + B1 c_t + b2              grouped, kernel 2, a group a head
  q[h] = u^q[h] + (q~[h] + k~[h // G]) / 2
  k[j] = u^k[j] + (mean_{h // G = j} q~[h] + k~[j]) / 2
  q[h] = sqrt(d) q[h] / |q[h]|       k[j] = exp(tau_j) sqrt(d) k[j] / |k[j]|
  q, k = rope(q, k, t): the first r = d x partial_rotary_factor lanes, adjacent
         pairs (2i, 2i+1), inv_freq_i = theta^(-2i/r); the rest pass through
  v_t  = [n_t W_v1 ; n_{t-1} W_v2]             first half of the K/V heads the
                                               current token's, second the previous
  o[h] = sum_{s <= t} softmax_s(q_t[h] . k_s[h // G] / sqrt(d)) v_s[h // G]
  x    = s_r (x + b_r) + s_o (concat_h(o[h]) W_o + b_o)
  m    = rms(x) g_m
  r^l  = m W_dn + b_dn + gamma^l r^{l-1}        (r^{-1} = 0)
  e    = W_3 gelu(W_2 gelu(W_1 (rms(r^l) g_r) + b_1) + b_2)     [experts + 1]
  p    = softmax(e);   c = argmax(p + beta)
  y    = p[c] E_c(m) if c < experts and c is held;  p[c] m if c = experts
         (skip);  0 otherwise;      E_e(m) = W_d^e (silu(W_g^e m) * (W_u^e m))
  x    = s_r' (x + b_r') + s_o' (y + b_o')
  logits = rms(x_L) g Emb^T

DEPARTURE RISKS.  The config fixes every width, the two kernel sizes, heads,
rope, experts and top-1, the router's width, the tied table and eps.  The
papers name the rest and these equations give each a FORM, which is this
repository's reading and may differ from the released weights' code: the
convolutions' grouping (depthwise, then one group a head) and that both are
over [q~ ; k~] together; the q-k mean and where it enters; the L2 norm at
sqrt(d) with a learned temperature on k only; the value shift's split by K/V
head; the router MLP's depth (two hidden layers), its exact (erf) GELU, its
norm; where the carry enters (before the norm, scaled by a learned vector);
the balancing bias (added to the probabilities, selection only); the skip
choice (one extra router output) and what a skipped token yields (its own
normed input times the skip probability); the scaled residual's four vectors.
Rope pairs adjacent lanes (Hugging Face's code rotates halves: a fixed
permutation of the rotated columns, which only a loader of real checkpoints
applies).

The expert sublayer takes the same `held = (first, count)` range as the
program: the router chooses among ALL experts and skip, and only experts
first .. first + count - 1 are computed; what an absent expert would add is
left out, as the program leaves it out; the skip choice every share computes
alike.  Each held expert is applied to every token and weighted by p[c] where
the token chose it (zero elsewhere): no sorting, nothing dropped.

So that 8,320 positions fit beside a serving engine on one chip, a layer is
computed in pieces, each a small jitted function that casts only the weights
it multiplies: attention a K/V head at a time in blocks of `Q_BLOCK` queries,
the experts one at a time, the tied head in chunks of `VOCAB_CHUNK` rows of the
table and for the positions asked only.

Weights layout (`weights`): "embed" [V, E]; "norm" [E]; "layers": a list of
dicts with "g_a", "g_m" [E]; "w_qk" [E, C]; "w_v" [E, Hkv d] (W_v1's columns,
then W_v2's); "w_o" [Hq d, E]; "conv0_w" [2, C] (a0, a1), "conv0_b" [C];
"conv1_w" [2, Hq + Hkv, d, d] (B0, B1; y = x B), "conv1_b" [C]; "tau" [Hkv];
"attn_res" and "mlp_res" (s_r, b_r, s_o, b_o); "router": {"down_w" [E, R],
"down_b", "gamma", "norm_g" [R], "w1", "w2" [R, R], "b1", "b2" [R], "w3"
[R, experts + 1], "beta" [experts + 1]}; "experts": the held experts'
(w_gate_up [E, 2F], w_down [F, E]) pairs in order.
`sizes`: {"heads", "kv_heads", "head_dim", "eps", "theta", "rotary", "experts",
"held"} and optionally the CONTROLS, each of which computes something else on
purpose and must not compare equal: "dtype" "bfloat16" (EVERYTHING in
bfloat16 at default precision), "router_dtype" "bfloat16" (only the router),
"no_conv0" (c = z), "no_conv1" (u = c), "no_shift" (the second half of v from
n_t), "no_carry" (r^l without gamma r^{l-1}), "skip_zero" (a skipped token
yields 0), "no_balance" (beta = 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256          # queries per attention piece
VOCAB_CHUNK = 32768    # rows of the table per piece of the head


def _prec(dt):
    return "highest" if dt == jnp.float32 else "default"


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _now(x):
    """Wait for one piece before the next is asked for (a piece's output is
    allocated when it is dispatched: reference_mla_moe)."""
    return jax.block_until_ready(x)


def _before(x):
    """x_{t-1} beside x_t along the first axis, zeros at t = 0."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def _rope(x, theta, r):
    """x [S, n, d], positions 0..S-1: the first r lanes rotated in adjacent
    pairs; frequencies in float64 on the host, rounded once."""
    inv = jnp.asarray(np.array([theta ** (-2.0 * i / r) for i in range(r // 2)],
                               np.float64).astype(np.float32))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x[..., :r].astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    turned = jnp.stack([even * c - odd * s, odd * c + even * s],
                       axis=-1).reshape(xf.shape).astype(x.dtype)
    return jnp.concatenate([turned, x[..., r:]], axis=-1)


# ----------------------------------------------------------------- attention

@functools.partial(jax.jit, static_argnames=("eps", "dt"))
def _normed(x, g, *, eps, dt):
    return _rms(x, g, eps).astype(dt)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "d", "theta", "rotary", "dt", "no_conv0", "no_conv1",
    "no_shift"))
def _latent(n, w, *, heads, kv_heads, d, theta, rotary, dt, no_conv0, no_conv1,
            no_shift):
    """q [S, Hq, d], k and v [S, Hkv, d] of one sequence's normed input."""
    with jax.default_matmul_precision(_prec(dt)):
        s = n.shape[0]
        groups, g = heads + kv_heads, heads // kv_heads
        z = n @ w["w_qk"].astype(dt)
        a, b1 = w["conv0_w"].astype(dt), w["conv0_b"].astype(dt)
        c = z if no_conv0 else a[0] * _before(z) + a[1] * z + b1
        if no_conv1:
            u = c.reshape(s, groups, d)
        else:
            bw = w["conv1_w"].astype(dt)
            mix = lambda x, m: jnp.einsum(  # noqa: E731
                "sgi,gio->sgo", x.reshape(s, groups, d), m)
            u = (mix(_before(c), bw[0]) + mix(c, bw[1])
                 + w["conv1_b"].astype(dt).reshape(groups, d))
        zq = z[:, :heads * d].reshape(s, kv_heads, g, d)
        zk = z[:, heads * d:].reshape(s, kv_heads, 1, d)
        q = u[:, :heads] + ((zq + zk) / 2).reshape(s, heads, d)
        k = u[:, heads:] + (zq.mean(axis=2) + zk[:, :, 0]) / 2
        unit = lambda x: x / jnp.sqrt(jnp.sum(  # noqa: E731
            jnp.square(x.astype(jnp.float32)), -1, keepdims=True)).astype(dt)
        root = jnp.sqrt(jnp.asarray(d, dt))
        q = unit(q) * root
        k = unit(k) * root * jnp.exp(w["tau"].astype(dt))[:, None]
        q, k = _rope(q, theta, rotary), _rope(k, theta, rotary)
        v12 = (n @ w["w_v"].astype(dt)).reshape(s, 2, kv_heads // 2, d)
        shifted = v12[:, 1] if no_shift else _before(v12[:, 1])
        return q, k, jnp.concatenate([v12[:, 0], shifted], axis=1)


@functools.partial(jax.jit, static_argnames=("q_block", "dt"))
def _attend_group(q, k, v, *, q_block, dt):
    """One K/V head and the query heads that share it: q [S, G, d], k and v
    [S, d] -> [S, G, d]; causal, all keys up to each query."""
    with jax.default_matmul_precision(_prec(dt)):
        s, _g, d = q.shape
        out = []
        for at in range(0, s, q_block):
            upto = min(s, at + q_block)
            score = (jnp.einsum("qgd,sd->gqs", q[at:upto], k[:upto])
                     / jnp.sqrt(jnp.asarray(d, dt)))
            i = (at + jnp.arange(upto - at))[:, None]
            seen = jnp.arange(upto)[None, :] <= i
            prob = jax.nn.softmax(jnp.where(seen[None], score, -jnp.inf), -1)
            out.append(jnp.einsum("gqs,sd->qgd", prob.astype(dt), v[:upto]))
        return jnp.concatenate(out, axis=0)


@functools.partial(jax.jit, static_argnames=("dt",))
def _residual(x, f, res, *, dt):
    s_r, b_r, s_o, b_o = (t.astype(dt) for t in res)
    return s_r * (x + b_r) + s_o * (f + b_o)


@functools.partial(jax.jit, static_argnames=("dt",))
def _project_out(ctx, w_o, *, dt):
    with jax.default_matmul_precision(_prec(dt)):
        return ctx.reshape(ctx.shape[0], -1) @ w_o.astype(dt)


# ------------------------------------------------------------ expert sublayer

@functools.partial(jax.jit, static_argnames=("eps", "dt", "no_carry",
                                             "no_balance"))
def _route(m, r_prev, w, *, eps, dt, no_carry, no_balance):
    """p [S, experts + 1], the choice [S], the router state r [S, R], and the
    margin between the first and the second of p + beta with the two."""
    with jax.default_matmul_precision(_prec(dt)):
        t = lambda a: a.astype(dt)  # noqa: E731
        r = t(m) @ t(w["down_w"]) + t(w["down_b"])
        if not no_carry:
            r = r + t(w["gamma"]) * r_prev.astype(dt)
        h = _rms(r, w["norm_g"], eps)
        h = jax.nn.gelu(h @ t(w["w1"]) + t(w["b1"]), approximate=False)
        h = jax.nn.gelu(h @ t(w["w2"]) + t(w["b2"]), approximate=False)
        p = jax.nn.softmax(h @ t(w["w3"]), axis=-1)
        select = p if no_balance else p + t(w["beta"])
        top, idx = jax.lax.top_k(select.astype(jnp.float32), 2)
        return (p.astype(jnp.float32), idx[:, 0], r.astype(jnp.float32),
                top[:, 0] - top[:, 1], idx)


@functools.partial(jax.jit, static_argnames=("dt",))
def _expert(m, w_gate_up, w_down, *, dt):
    with jax.default_matmul_precision(_prec(dt)):
        f = w_down.shape[0]
        gu = m @ w_gate_up.astype(dt)
        return (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w_down.astype(dt)


# --------------------------------------------------------------------- layers

def _layer(x, r_prev, w, sizes, dt, probe):
    eps, d = sizes["eps"], sizes["head_dim"]
    heads, kv_heads = sizes["heads"], sizes["kv_heads"]
    n = _normed(x, w["g_a"], eps=eps, dt=dt)
    q, k, v = _latent(
        n, {key: w[key] for key in ("w_qk", "w_v", "conv0_w", "conv0_b",
                                    "conv1_w", "conv1_b", "tau")},
        heads=heads, kv_heads=kv_heads, d=d, theta=sizes["theta"],
        rotary=sizes["rotary"], dt=dt, no_conv0=bool(sizes.get("no_conv0")),
        no_conv1=bool(sizes.get("no_conv1")),
        no_shift=bool(sizes.get("no_shift")))
    g = heads // kv_heads
    ctx = [_now(_attend_group(q[:, j * g:(j + 1) * g], k[:, j], v[:, j],
                              q_block=Q_BLOCK, dt=dt))
           for j in range(kv_heads)]
    a = _project_out(jnp.concatenate(ctx, axis=1), w["w_o"], dt=dt)
    x = _now(_residual(x, a, w["attn_res"], dt=dt))

    m = _normed(x, w["g_m"], eps=eps, dt=dt)
    rdt = jnp.dtype(sizes.get("router_dtype", dt))
    p, chosen, r, gap, top2 = _route(
        m, r_prev, w["router"], eps=eps, dt=rdt,
        no_carry=bool(sizes.get("no_carry")),
        no_balance=bool(sizes.get("no_balance")))
    first, count = sizes["held"]
    skip = sizes["experts"]
    if probe is not None:
        held_edge = (((top2 >= first) & (top2 < first + count))
                     | (top2 == skip)).any(-1)
        probe.append((gap, held_edge, chosen[:, None], m, r_prev))
    weight = jnp.take_along_axis(p, chosen[:, None], axis=1)      # [S, 1]
    y = jnp.zeros(m.shape, jnp.float32)
    if not sizes.get("skip_zero"):
        y = jnp.where(chosen[:, None] == skip,
                      weight * m.astype(jnp.float32), 0.0)
    for e, (w_gate_up, w_down) in enumerate(w["experts"]):
        mine = jnp.where(chosen[:, None] == first + e, weight, 0.0)
        y = _now(y + mine * _expert(m, w_gate_up, w_down,
                                    dt=dt).astype(jnp.float32))
    return _now(_residual(x, y.astype(dt), w["mlp_res"], dt=dt)), r


def hidden(weights, sizes, ids, probe=None):
    """x_L of ONE sequence `ids` [S] -> [S, E].  `probe`, a list, receives
    per layer (gap [S], held_edge [S], chosen [S, 1], m [S, E], r_prev
    [S, R]): the margin between the first and the second of the router's
    selection scores, whether either of the two is an expert held here or
    skip, the outcome chosen, and the router's two inputs."""
    dt = jnp.dtype(sizes.get("dtype", "float32"))
    x = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(dt)
    width = weights["layers"][0]["router"]["gamma"].shape[0]
    r = jnp.zeros((x.shape[0], width), jnp.float32)
    for w in weights["layers"]:
        x, r = _layer(x, r, w, sizes, dt, probe)
    return x


@functools.partial(jax.jit, static_argnames=("dt",))
def _head_chunk(x, rows, *, dt):
    with jax.default_matmul_precision(_prec(dt)):
        return (x @ rows.astype(dt).T).astype(jnp.float32)


def _head(x, norm, embed, *, eps, dt):
    """rms(x) g Emb^T for the rows of x given, a chunk of the table at a
    time: the table in float32 whole would be 2.1 GB."""
    x = _normed(x, norm, eps=eps, dt=dt)
    return jnp.concatenate(
        [_now(_head_chunk(x, embed[at:at + VOCAB_CHUNK], dt=dt))
         for at in range(0, embed.shape[0], VOCAB_CHUNK)], axis=-1)


def _seen(ids, positions, to=128):
    """`ids` without the tail behind the last position asked for (causal:
    nothing there is seen), cut at the next multiple of `to` so that the
    pieces compile for a few lengths, not for every length."""
    n = min(len(ids), -(-(max(int(p) for p in positions) + 1) // to) * to)
    return jnp.asarray(ids)[:n]


def logits_at(weights, sizes, ids, positions):
    """Reference logits [len(positions), V] of ONE sequence `ids` [S] at the
    given positions (each row predicts the token after that position)."""
    return logits_and_near_ties(weights, sizes, ids, positions, None)[0]


def logits_and_near_ties(weights, sizes, ids, positions, tau):
    """`logits_at`, and for each position whether ITS OWN routing is a near
    tie in some layer: the router's first and second selection scores lie
    within `tau` of each other and one of the two is an expert held here or
    skip, so rounding in the program's hidden state may hand this token
    another outcome's result (all False where `tau` is None)."""
    dt = jnp.dtype(sizes.get("dtype", "float32"))
    probe = None if tau is None else []
    at = jnp.asarray(positions)
    x = hidden(weights, sizes, _seen(ids, positions), probe)[at]
    tie = jnp.zeros(len(positions), bool)
    for gap, held_edge, *_rest in probe or ():
        tie = tie | ((gap[at] < tau) & held_edge[at])
    lg = _head(x, weights["norm"], weights["embed"], eps=sizes["eps"], dt=dt)
    return lg, tie


def conv_tail(z, w, *, no_conv0=False):
    """What the two convolutions must return for a whole sequence's z [S, C]
    (float32, highest): u [S, groups, d], for comparisons on the same
    inputs across a block boundary."""
    with jax.default_matmul_precision("highest"):
        z = jnp.asarray(z).astype(jnp.float32)
        a = jnp.asarray(w["conv0_w"]).astype(jnp.float32)
        c = z if no_conv0 else (a[0] * _before(z) + a[1] * z
                                + jnp.asarray(w["conv0_b"]).astype(jnp.float32))
        bw = jnp.asarray(w["conv1_w"]).astype(jnp.float32)
        groups, d = bw.shape[1], bw.shape[2]
        mix = lambda x, m: jnp.einsum(  # noqa: E731
            "sgi,gio->sgo", x.reshape(x.shape[0], groups, d), m)
        return (mix(_before(c), bw[0]) + mix(c, bw[1])
                + jnp.asarray(w["conv1_b"]).astype(jnp.float32).reshape(groups, d))


def causal_attention(q, k, v, lens):
    """What a decode step's attention must return for given inputs: q [B, N,
    d] (one query a row, at position lens[b] - 1), k and v [B, S, Nkv, d]
    (each sequence's rows in order of position; the first lens[b] are live)
    -> [B, N, d]; float32 at highest precision whatever the inputs' type."""
    with jax.default_matmul_precision("highest"):
        q = jnp.asarray(q).astype(jnp.float32)
        k = jnp.asarray(k).astype(jnp.float32)
        v = jnp.asarray(v).astype(jnp.float32)
        b, n, d = q.shape
        nkv = k.shape[2]
        qg = q.reshape(b, nkv, n // nkv, d)
        score = jnp.einsum("bkgd,bskd->bkgs", qg, k) / jnp.sqrt(jnp.float32(d))
        seen = jnp.arange(k.shape[1])[None, :] < jnp.asarray(lens)[:, None]
        prob = jax.nn.softmax(jnp.where(seen[:, None, None, :], score,
                                        -jnp.inf), -1)
        return jnp.einsum("bkgs,bskd->bkgd", prob, v).reshape(b, n, d)
