"""The plain reference of the latent-attention decoder with a learned sparse
indexer on its full layers, a second latent geometry over a sliding window on
the others, headwise gates and routed experts beside a shared one
(dots3-note-prev's block): the forward pass in straightforward `jax.numpy`,
float32, matmuls at `jax.default_matmul_precision("highest")`.  No kernels,
no cache, no ring, no absorbed attention, no sorting of experts, and no code
shared with `paddle_tpu.models`; the family module hands it the model's
weights (any float type; cast here).

Written from the equations (layer l of type full or sliding, each type with
its own N heads, ranks r_q / r, head parts d_n / d_r / d_v and rope theta;
rms(x) = x / sqrt(mean(x^2) + eps)):

  n       = rms(x) g_in
  c_q     = rms(n W_qa) g_qa . sqrt(h / r_q)
  q_i     = c_q W_qb -> (q_nope_i [d_n], rope(q_rope_i) [d_r])
  (c', k_r) = n W_kva ;  c = rms(c') g_kva . sqrt(h / r) ;  k_rope = rope(k_r)
  (k_nope_i, v_i) = c W_kvb
  full layers, the indexer (H_I heads of d_I, k = top_k):
    qI_j  = c_q WI_q,j ;  kI_s = LayerNorm(n_s WI_k)   (rope on the first
            d_r lanes of both; LayerNorm eps 1e-6)
    w_t,j = (n_t WI_w)_j / sqrt(H_I d_I)
    I_t,s = sum_j w_t,j relu(qI_t,j . kI_s),  s <= t
    S_t   = the k positions s <= t of largest I_t,s (all of them while t < k)
  p_i     = softmax over s in S_t (full) or t - W < s <= t (sliding) of
            (q_nope_i . k_nope_i,s + q_rope_i . k_rope_s) / sqrt(d_n + d_r)
  a       = x + concat_i(sigmoid(n W_g)_i . sum_s p_i,s v_i,s) W_o
  m       = rms(a) g_post
  dense:   f = W_d(silu(W_g m) * (W_u m))
  experts: s = sigmoid(m W_r);  T = top-k of (s + b);  w_e = scale s_e / sum_T s
           f = sum_{e in T, e held} w_e E_e(m) + E_shared(m)
  x'      = a + f ;   logits = rms(x_L) g H

Rope pairs adjacent lanes (2i, 2i+1), as the program's does (a fixed
permutation of the rotated columns of a published checkpoint's halves).  The
indexer's selection is `jax.lax.top_k` of the float32 scores: the exact
top-k.  The expert layer takes the same `held = (first, count)` range as the
program: the router scores ALL experts and the weights are normalised over
the k chosen wherever they live, but only the held experts are computed;
each is applied to every token and weighted by w_e (zero where not chosen).

So that 8,320 positions fit beside a serving engine on one chip, a layer is
computed in pieces, each a small jitted function that casts only the weights
it multiplies: the indexer's scores and choice in blocks of `I_BLOCK`
queries, attention per group of `HEAD_GROUP` heads and in blocks of `Q_BLOCK`
queries against the keys up to the block under the layer's mask (the
selection, or the window's band), the dense FFN in column chunks, the
experts one at a time.

Weights layout (`weights`): "embed" [V, h]; "norm" [h]; "head" [h, V];
"layers": dicts with "g_in", "g_post", "w_qa" [h, r_q], "g_qa", "w_qb"
[r_q, N(d_n+d_r)], "w_kva" [h, r+d_r], "g_kva", "w_kvb" [r, N(d_n+d_v)],
"w_o" [N d_v, h], "w_g" [h, N]; full layers also "wi_q" [r_q, H_I d_I],
"wi_k" [h, d_I], "gi_k" / "bi_k" [d_I] (the LayerNorm), "wi_w" [h, H_I];
then either "w_gate_up" [h, 2F] and "w_down" [F, h] (dense) or "w_router"
[h, E], "bias" [E], "shared" and "experts" ((w_gate_up, w_down) pairs of the
held experts in order).  `sizes`: {"layers": [{"kind": "full" | "sliding",
"heads", "nope", "rope", "v", "theta", "window" (or None)}, ...], "index":
{"heads", "dim", "top_k"}, "lora_rescale", "eps", "top_k", "scale",
"normalize", "held"} and optionally the CONTROLS, each of which computes
something else on purpose and must not compare equal: "dtype" "bfloat16"
(EVERYTHING in bfloat16 at default precision), "router_dtype" /
"index_dtype" "bfloat16" (only the router, or only the indexer's scores),
"dense_index" (the full layers attend every s <= t), "no_bias" (the router
chooses by s alone), "ignore_window", "no_gate".
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HEAD_GROUP = 16     # heads a piece of attention
Q_BLOCK = 256       # queries a piece of attention: 16 x 256 x 8,320 scores
I_BLOCK = 128       # queries a piece of the indexer: 128 x 64 x 8,320 dots
COL_CHUNK = 4096    # dense-FFN columns a piece


def _prec(dt):
    return "highest" if dt == jnp.float32 else "default"


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _now(x):
    """Wait for one piece before the next is asked for (a piece's output is
    allocated when it is dispatched)."""
    return jax.block_until_ready(x)


def _rope(x, theta):
    """x [S, n, d], positions 0..S-1, every lane in adjacent pairs."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([even * c - odd * s, odd * c + even * s],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _rope_first(x, r, theta):
    return jnp.concatenate([_rope(x[..., :r], theta), x[..., r:]], -1)


# ----------------------------------------------------------------- attention

@functools.partial(jax.jit, static_argnames=("eps", "theta", "rescale", "dt"))
def _latents(x, g_in, w_qa, g_qa, w_kva, g_kva, *, eps, theta, rescale, dt):
    """n, c_q, c and the roped shared key of every token."""
    with jax.default_matmul_precision(_prec(dt)):
        h, r_q, r = x.shape[-1], w_qa.shape[1], g_kva.shape[0]
        n = _rms(x, g_in, eps)
        c_q = _rms(n @ w_qa.astype(dt), g_qa, eps)
        kva = n @ w_kva.astype(dt)
        c = _rms(kva[:, :r], g_kva, eps)
        if rescale:
            c_q = c_q * jnp.asarray(math.sqrt(h / r_q), dt)
            c = c * jnp.asarray(math.sqrt(h / r), dt)
        k_rope = _rope(kva[:, None, r:], theta)[:, 0]
        return n, c_q, c, k_rope


@functools.partial(jax.jit, static_argnames=("heads", "dim", "rot", "theta",
                                             "dt"))
def _indexer(n, c_q, wi_q, wi_k, gi_k, bi_k, wi_w, *, heads, dim, rot, theta,
             dt):
    """The indexer's queries [S, H_I, d_I], weights [S, H_I] and keys
    [S, d_I]."""
    with jax.default_matmul_precision(_prec(dt)):
        s = n.shape[0]
        q = _rope_first((c_q.astype(dt) @ wi_q.astype(dt)).reshape(s, heads, dim),
                        rot, theta)
        k = n.astype(dt) @ wi_k.astype(dt)
        mean = jnp.mean(k, -1, keepdims=True)
        var = jnp.mean(jnp.square(k - mean), -1, keepdims=True)
        k = ((k - mean) * jax.lax.rsqrt(var + 1e-6) * gi_k.astype(dt)
             + bi_k.astype(dt))
        k = _rope_first(k[:, None], rot, theta)[:, 0]
        w = (n.astype(dt) @ wi_w.astype(dt)) / jnp.asarray(
            math.sqrt(heads * dim), dt)
        return q, w, k


@functools.partial(jax.jit, static_argnames=("top_k", "dt"))
def _choose(q, w, k, t0, *, top_k, dt):
    """One block of queries q [Q, H, d], w [Q, H] at positions t0.. against
    ALL the keys k [S, d] (S > top_k; the causal mask hides the later ones,
    and every block compiles once): (chosen [Q, S] bool, the relative gap
    between each query's top_k-th and (top_k + 1)-th score; inf where the
    query has no (top_k + 1)-th)."""
    with jax.default_matmul_precision(_prec(dt)):
        score = jnp.einsum("qhs,qh->qs",
                           jax.nn.relu(jnp.einsum("qhd,sd->qhs", q.astype(dt),
                                                  k.astype(dt))),
                           w.astype(dt)).astype(jnp.float32)
        t = t0 + jnp.arange(q.shape[0])
        seen = jnp.arange(k.shape[0])[None, :] <= t[:, None]
        vals, at = jax.lax.top_k(jnp.where(seen, score, -jnp.inf), top_k + 1)
        rows = jnp.arange(q.shape[0])[:, None]
        chosen = jnp.zeros(seen.shape, bool).at[rows, at[:, :top_k]].set(True)
        gap = jnp.where(t >= top_k,
                        (vals[:, top_k - 1] - vals[:, top_k])
                        / jnp.abs(vals[:, top_k - 1]), jnp.inf)
        return chosen & seen, gap


@functools.partial(jax.jit, static_argnames=("nope", "rope", "v", "theta",
                                             "q_block", "dt"))
def _attention_group(c_q, c, k_rope, gate, allowed, w_qb, w_kvb, w_o, *, nope,
                     rope, v, theta, q_block, dt):
    """The heads of one group: their part of concat_i(g_i o_i) W_o, [S, h].
    allowed [S, S] bool: the keys each query sees."""
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
            prob = jax.nn.softmax(
                jnp.where(allowed[None, at:upto, :upto], score, -jnp.inf), -1)
            out.append(jnp.einsum("gqs,sgd->qgd", prob, val[:upto]))
        ctx = jnp.concatenate(out, axis=0) * gate[:, :, None].astype(dt)
        return ctx.reshape(s, -1) @ w_o.astype(dt)


@functools.partial(jax.jit, static_argnames=("dt",))
def _gates(n, w_g, *, dt):
    with jax.default_matmul_precision(_prec(dt)):
        return jax.nn.sigmoid((n @ w_g.astype(dt)).astype(jnp.float32)
                              ).astype(dt)


@functools.partial(jax.jit, static_argnames=("s", "window"))
def _band(*, s, window):
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    seen = j <= i
    return seen if window is None else seen & (j > i - window)


def _selection(n, c_q, w, geometry, sizes, dt, probe):
    """The full layer's [S, S] selection (the indexer's choice, in blocks of
    queries) and each query's relative gap at the choice's edge; the
    indexer's own inputs, its selection and the gaps into `probe`."""
    ix = sizes["index"]
    idt = jnp.dtype(sizes.get("index_dtype", dt))
    s, top_k = n.shape[0], ix["top_k"]
    q, wt, k = _indexer(n, c_q, w["wi_q"], w["wi_k"], w["gi_k"], w["bi_k"],
                        w["wi_w"], heads=ix["heads"], dim=ix["dim"],
                        rot=geometry["rope"], theta=geometry["theta"], dt=idt)
    allowed = _band(s=s, window=None)
    gaps = jnp.full((s,), jnp.inf)
    if sizes.get("dense_index") or s <= top_k:
        chosen_all = allowed
    else:
        rows = []
        for at in range(0, s, I_BLOCK):
            upto = min(s, at + I_BLOCK)
            if upto <= top_k:        # every query sees at most top_k keys
                rows.append(allowed[at:upto])
                continue
            chosen, gap = _choose(q[at:upto], wt[at:upto], k, jnp.int32(at),
                                  top_k=top_k, dt=idt)
            rows.append(chosen)
            gaps = gaps.at[at:upto].set(gap)
        chosen_all = _now(jnp.concatenate(rows, axis=0))
    if probe is not None:
        probe.append((q, wt, k, chosen_all, gaps))
    return chosen_all, gaps


# ----------------------------------------------------------------------- FFN

@functools.partial(jax.jit, static_argnames=("dt",))
def _swiglu(m, w_gate, w_up, w_down, *, dt):
    with jax.default_matmul_precision(_prec(dt)):
        return (jax.nn.silu(m @ w_gate.astype(dt))
                * (m @ w_up.astype(dt))) @ w_down.astype(dt)


def _ffn(m, w_gate_up, w_down, dt, chunk=COL_CHUNK):
    width = w_down.shape[0]
    f = jnp.zeros((m.shape[0], w_down.shape[1]), m.dtype)
    for at in range(0, width, chunk):
        to = min(width, at + chunk)
        f = _now(f + _swiglu(m, w_gate_up[:, at:to],
                             w_gate_up[:, width + at:width + to],
                             w_down[at:to], dt=dt))
    return f


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "normalize",
                                             "dt"))
def _route(m, w_router, bias, *, top_k, scale, normalize, dt):
    """Sigmoid scores of ALL experts, chosen by score + bias: [S, E] weights
    (zero where not chosen, the unbiased scores normalised over the chosen),
    the gap between the k-th and the (k+1)-th choice value, and the k + 1
    best experts in order."""
    with jax.default_matmul_precision(_prec(dt)):
        score = jax.nn.sigmoid(m.astype(dt) @ w_router.astype(dt)
                               ).astype(jnp.float32)
        pick, top_i = jax.lax.top_k(score + bias.astype(jnp.float32), top_k + 1)
        w = jnp.take_along_axis(score, top_i[:, :top_k], axis=1)
        if normalize:
            w = w / jnp.sum(w, -1, keepdims=True)
        dense = jnp.zeros(score.shape, jnp.float32).at[
            jnp.arange(m.shape[0])[:, None], top_i[:, :top_k]].set(w * scale)
        return dense, pick[:, top_k - 1] - pick[:, top_k], top_i


# --------------------------------------------------------------------- layers

def _layer(x, w, geometry, sizes, dt, probe, index_probe, index_gaps,
           attn_probe=None):
    eps = sizes["eps"]
    nope, rope, v = geometry["nope"], geometry["rope"], geometry["v"]
    n, c_q, c, k_rope = _latents(
        x, w["g_in"], w["w_qa"], w["g_qa"], w["w_kva"], w["g_kva"], eps=eps,
        theta=geometry["theta"], rescale=bool(sizes["lora_rescale"]), dt=dt)
    gate = _gates(n, w["w_g"], dt=dt)
    if sizes.get("no_gate"):
        gate = jnp.ones_like(gate)
    gaps = None
    if geometry["kind"] == "full":
        allowed, gaps = _selection(n, c_q, w, geometry, sizes, dt,
                                   index_probe)
    else:
        window = None if sizes.get("ignore_window") else geometry["window"]
        allowed = _band(s=x.shape[0], window=window)
    attn = jnp.zeros_like(x)
    for g0 in range(0, geometry["heads"], HEAD_GROUP):
        g1 = min(geometry["heads"], g0 + HEAD_GROUP)
        attn = _now(attn + _attention_group(
            c_q, c, k_rope, gate[:, g0:g1], allowed,
            w["w_qb"][:, g0 * (nope + rope):g1 * (nope + rope)],
            w["w_kvb"][:, g0 * (nope + v):g1 * (nope + v)],
            w["w_o"][g0 * v:g1 * v], nope=nope, rope=rope, v=v,
            theta=geometry["theta"], q_block=Q_BLOCK, dt=dt))
    if gaps is not None and index_gaps is not None:
        index_gaps.append(gaps)
    if attn_probe is not None:
        attn_probe(x, attn)
    a = x + attn
    m = _rms(a, w["g_post"], eps)
    if "w_router" not in w:
        return _now(a + _ffn(m, w["w_gate_up"], w["w_down"], dt))
    first, count = sizes["held"]
    rdt = jnp.dtype(sizes.get("router_dtype", dt))
    bias = jnp.zeros_like(w["bias"]) if sizes.get("no_bias") else w["bias"]
    weight, gap, top = _route(m, w["w_router"], bias, top_k=sizes["top_k"],
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


def hidden(weights, sizes, ids, probe=None, index_probe=None, depth=None,
           index_gaps=None, attn_probe=None):
    """x after `depth` layers (all of them where None) of ONE sequence `ids`
    [S] -> [S, h].  `probe`, a list, receives per expert layer (gap [S],
    held_edge [S], chosen [S, k], m [S, h]): the gap between each token's
    k-th and (k+1)-th choice value (score + bias), whether either of the two
    experts is held, the k experts chosen and the router's input.
    `index_probe` receives per full layer (qI [S, H_I, d_I], w [S, H_I], kI
    [S, d_I], chosen [S, S] bool, gap [S]): the indexer's own inputs, its
    selection, and each query's relative gap between its top_k-th and
    (top_k + 1)-th score; `index_gaps` the gaps alone.  `attn_probe`, a
    callable, is called once a layer with (i, x [S, h], attn [S, h]): the
    layer's index, its input and its attention sublayer's output
    concat_i(g_i o_i) W_o, while the layer is computed."""
    dt = jnp.dtype(sizes.get("dtype", "float32"))
    x = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(dt)
    layers = list(zip(weights["layers"], sizes["layers"]))[:depth]
    for i, (w, geometry) in enumerate(layers):
        seen = (None if attn_probe is None
                else functools.partial(attn_probe, i))
        x = _layer(x, w, geometry, sizes, dt, probe, index_probe, index_gaps,
                   seen)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "dt"))
def _head(x, norm, head, *, eps, dt):
    with jax.default_matmul_precision(_prec(dt)):
        return (_rms(x, norm, eps) @ head.astype(dt)).astype(jnp.float32)


def _seen(ids, positions, to=128):
    """`ids` without the tail behind the last position asked for, cut at the
    next multiple of `to` so that the pieces compile for a few lengths."""
    n = min(len(ids), -(-(max(int(p) for p in positions) + 1) // to) * to)
    return jnp.asarray(ids)[:n]


def logits_at(weights, sizes, ids, positions):
    """Reference logits [len(positions), V] of ONE sequence `ids` [S] at the
    given positions (each row predicts the token after that position)."""
    dt = jnp.dtype(sizes.get("dtype", "float32"))
    x = hidden(weights, sizes, _seen(ids, positions))[jnp.asarray(positions)]
    return _head(x, weights["norm"], weights["head"], eps=sizes["eps"], dt=dt)


def logits_and_near_ties(weights, sizes, ids, positions, tau, index_tau=0.0):
    """`logits_at`, and for each position whether ITS OWN routing or
    selection is a near tie: in some expert layer the choice values of its
    k-th and (k+1)-th expert lie within `tau` of each other with one of the
    two held here, or in some full layer its top_k-th and (top_k + 1)-th
    indexer scores lie within `index_tau` of each other (relative)."""
    dt = jnp.dtype(sizes.get("dtype", "float32"))
    probe, index_gaps = [], []
    at = jnp.asarray(positions)
    x = hidden(weights, sizes, _seen(ids, positions), probe,
               index_gaps=index_gaps)[at]
    tie = jnp.zeros(len(positions), bool)
    for gap, held_edge, _chosen, _m in probe:
        tie = tie | ((gap[at] < tau) & held_edge[at])
    for gaps in index_gaps:
        tie = tie | (gaps[at] < index_tau)
    lg = _head(x, weights["norm"], weights["head"], eps=sizes["eps"], dt=dt)
    return lg, tie
