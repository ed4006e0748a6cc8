"""The plain reference of the dense GQA decoder: forward pass and mean
cross-entropy in straightforward `jax.numpy`, float32, matmuls at
`jax.default_matmul_precision("highest")`.  No kernels, no cache, no batching
tricks, and no code shared with `paddle_tpu.models`; a family module hands it
the model's weights (any float type; they are cast to float32 here).

Written from the equations:

  x_0   = E[ids]
  a_l   = x_l + Wo . Attn(rope(Wq n1), rope(Wk n1), Wv n1),  n1 = rms(x_l) g1
  x_l+1 = a_l + Wd . (silu(Wg n2) * (Wu n2)),                n2 = rms(a_l) g2
  logits = rms(x_L) g . H

rms(x) = x / sqrt(mean(x^2) + eps).  Attention is causal softmax(QK^T /
sqrt(d)) V with each K/V head shared by `heads / kv_heads` query heads.
rope rotates the lane pairs (2i, 2i+1) of every head by the angle
pos * theta^(-2i/d), as in the RoFormer paper's equation 34.

Departures from the published checkpoints' code, neither of which changes a
shape: Hugging Face's implementation rotates the two HALVES of a head instead
of adjacent pairs — the same function under a fixed permutation of each
head's lanes in Wq and Wk, which only a loader of real checkpoints has to
apply; and InternLM2's fused `wqkv` is three matrices here.

Weights layout (`weights`): "embed" [V, h]; "layers": a list of dicts with
"wq" [h, N*d], "wk", "wv" [h, Nkv*d], "wo" [N*d, h], "w_gate", "w_up" [h, f],
"w_down" [f, h], "ln1", "ln2" [h]; "norm" [h]; "head" [h, V].
`sizes`: {"heads", "kv_heads", "head_dim", "eps", "theta"}.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: [B, S, N, d]; positions 0..S-1."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * c - odd * s, odd * c + even * s],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim",
                                             "eps", "theta"))
def layer(x, w, *, heads, kv_heads, head_dim, eps, theta):
    """One decoder layer on x [B, S, h] (float32)."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        b, s, _ = x.shape
        n1 = _rms(x, w["ln1"], eps)
        q = _rope((n1 @ w["wq"]).reshape(b, s, heads, head_dim), theta)
        k = _rope((n1 @ w["wk"]).reshape(b, s, kv_heads, head_dim), theta)
        v = (n1 @ w["wv"]).reshape(b, s, kv_heads, head_dim)
        group = heads // kv_heads
        q = q.reshape(b, s, kv_heads, group, head_dim)
        score = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / jnp.sqrt(F32(head_dim))
        causal = jnp.tril(jnp.ones((s, s), bool))
        prob = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), axis=-1)
        ctx = jnp.einsum("bkgqs,bskd->bqkgd", prob, v).reshape(b, s, -1)
        a = x + ctx @ w["wo"]
        n2 = _rms(a, w["ln2"], eps)
        return a + (jax.nn.silu(n2 @ w["w_gate"]) * (n2 @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm.astype(F32), eps) @ head.astype(F32)


def hidden(weights, sizes, ids):
    x = jnp.take(weights["embed"], ids, axis=0).astype(F32)
    for w in weights["layers"]:
        x = layer(x, w, **sizes)
    return x


def logits_at(weights, sizes, ids, positions):
    """Reference logits [len(positions), V] of ONE sequence `ids` [S] at the
    given positions (each row predicts the token after that position)."""
    x = hidden(weights, sizes, jnp.asarray(ids)[None, :])
    x = x[0, jnp.asarray(positions), :]
    return _head(x, weights["norm"], weights["head"], eps=sizes["eps"])


def mean_cross_entropy(weights, sizes, ids, labels, rows=1024):
    """Mean over all positions of -log softmax(logits)[label]; the vocabulary
    projection is taken `rows` positions at a time, so the logits of a long
    batch never exist at once."""
    x = hidden(weights, sizes, jnp.asarray(ids))
    x = x.reshape(-1, x.shape[-1])
    y = jnp.asarray(labels).reshape(-1)
    total = 0.0
    for at in range(0, x.shape[0], rows):
        lg = _head(x[at:at + rows], weights["norm"], weights["head"],
                   eps=sizes["eps"])
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, y[at:at + rows, None], axis=-1)[:, 0]
        total += float(jnp.sum(lse - picked))
    return total / x.shape[0]
