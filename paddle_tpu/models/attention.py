"""The pieces of attention that the served models share: the rotary angles
and rotation, the one plain (causal, optionally windowed) attention of their
prefill, and the headwise sigmoid gate.  Every model module imports them
from here; none reaches into another model's module for them.

Rope pairs adjacent lanes (2i, 2i+1), as ops/paged_attention
.rope_rotate_chunk does for every model.  The angles of the positions a
program touches are computed inside it: a table over every position would be
megabytes of constants in each compiled program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from paddle_tpu.ops import paged_attention as pa

__all__ = ["rope_at", "rope_at_freq", "rotate", "causal_attention",
           "gate_heads"]


def rope_at(positions, dim, theta):
    """cos and sin [P, dim / 2] (float32) of the rope angles of the int32
    positions [P]: rows of the table `rope_rotate_chunk` indexes."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def rope_at_freq(positions, inv_freq, attention_factor):
    """cos and sin [P, rot / 2] (float32) of the int32 positions [P] for
    given inverse frequencies (a scaled rope's), times the attention factor:
    rows of the table `rope_rotate_chunk` indexes."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    f = jnp.float32(attention_factor)
    return jnp.cos(ang) * f, jnp.sin(ang) * f


def rotate(x, cos, sin, at, rot):
    """x [B, T, n, d]: its first `rot` lanes rotated by the rows `at` [B, T]
    of cos / sin, the rest untouched."""
    if rot == x.shape[-1]:
        return pa.rope_rotate_chunk(x, cos, sin, at)
    return jnp.concatenate(
        [pa.rope_rotate_chunk(x[..., :rot], cos, sin, at), x[..., rot:]], -1)


def causal_attention(q, k, v, window=None, block=512):
    """Causal (and windowed) grouped attention for q / k [B, S, N or Nkv, d]
    and v [B, S, Nkv, d_v] (d_v may differ from d, as latent attention's
    does): the flash kernel on a TPU; elsewhere plain XLA in query blocks over
    the keys a block can see (never an [S, S] score matrix)."""
    from paddle_tpu import ops as _ops

    if _ops.use_pallas():
        return _ops.flash_attention(q, k, v, causal=True, window=window)
    b, s, n, d = q.shape
    nkv = k.shape[2]
    qg = q.reshape(b, s, nkv, n // nkv, d)
    scale = 1.0 / math.sqrt(d)
    outs = []
    for at in range(0, s, block):
        upto = min(s, at + block)
        lo = 0 if window is None else max(0, at - window + 1)
        score = jnp.einsum("bqkgd,bskd->bkgqs", qg[:, at:upto], k[:, lo:upto],
                           preferred_element_type=jnp.float32) * scale
        qi = (at + jnp.arange(upto - at))[:, None]
        kj = (lo + jnp.arange(upto - lo))[None, :]
        ok = kj <= qi
        if window is not None:
            ok = ok & (kj > qi - window)
        p = jax.nn.softmax(jnp.where(ok, score, -1e30), axis=-1)
        outs.append(jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype),
                               v[:, lo:upto],
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, axis=1).reshape(
        b, s, n, v.shape[-1]).astype(q.dtype)


def gate_heads(gate_proj, n, o):
    """The headwise gate: o [B, T, N, d] (raw) times sigmoid(n W_g) [B, T, N],
    one gate a head, the sigmoid and the product in float32, back in o's
    type.  `gate_proj` is the layer's W_g (an `nn.Linear` of h -> N)."""
    with jax.named_scope("attn.gate"):
        g = jax.nn.sigmoid(gate_proj(n)._value.astype(jnp.float32))
        return (o.astype(jnp.float32) * g[..., None]).astype(o.dtype)
