"""Fused RMSNorm / LayerNorm Pallas kernels.

Reference: paddle.incubate.nn.functional.fused_rms_norm / fused_layer_norm
(paddle/phi/kernels/fusion/gpu/fused_layernorm_kernel.cu).  TPU-native: one
VMEM-resident rowwise kernel computing fp32 statistics and the scaled output
in a single pass; backward is analytic jnp (XLA fuses it into the surrounding
backward graph).  Supports the reference's residual-add fusion
(`fused_layer_norm(x, residual=...)` adds before normalizing and returns the
pre-norm sum as well).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops import _pl_utils
from paddle_tpu.ops._pl_utils import imap


def _rows_block(total_rows, hidden=1024, dtype=None):
    # 1. autotune cache (per device kind; ops/autotune.py)
    from paddle_tpu.ops import autotune as _at

    tuned = _at.lookup("rms_rows", {
        "rows": total_rows, "hidden": hidden,
        "dtype": jnp.dtype(dtype).name if dtype is not None else "bfloat16"})
    if tuned:
        br = int(tuned["rows_block"])
        if 0 < br <= total_rows and total_rows % br == 0:
            return br
    # 2. analytic default: bound the double-buffered VMEM footprint — the
    # kernel holds the block in f32 (4B) for the reduction, so keep
    # br*hidden*4 around <=4MB, and br a multiple of 8 (f32 sublane).
    cap = max(8, (4 << 20) // max(1, hidden * 4))
    cap -= cap % 8 or 0
    return min(max(cap, 8), 256, total_rows)


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + jnp.float32(eps))
    o_ref[:] = (x * inv * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + jnp.float32(eps))
    o_ref[:] = (xc * inv * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _pallas_rows(kernel, x2d, params, out_dtype, rows_block=None):
    rows, hidden = x2d.shape
    br = rows_block or _rows_block(rows, hidden, x2d.dtype)
    if rows % br:
        # the largest multiple of 8 under the cap that tiles the rows
        # (8192 rows of 7680: the cap is 136, the block 128); a single
        # block only for small or ragged row counts that nothing tiles
        br = next((b for b in range(br - br % 8, 7, -8) if rows % b == 0),
                  rows)
    grid = (rows // br,)
    in_specs = [pl.BlockSpec((br, hidden), imap(lambda i: (i, 0)))]
    in_specs += [pl.BlockSpec((hidden,), imap(lambda i: (0,))) for _ in params]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, hidden), imap(lambda i: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((rows, hidden), out_dtype),
        interpret=_pl_utils.interpret(),
        name="rms_norm_fwd",
    )(x2d, *params)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms(x2d, w, eps):
    return _pallas_rows(functools.partial(_rms_kernel, eps=eps), x2d, (w,), x2d.dtype)


def _rms_fwd(x2d, w, eps):
    return _rms(x2d, w, eps), (x2d, w)


def _rms_bwd(eps, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32) * w.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    # d/dx [x * inv]: inv * g - x * (x.g) * inv^3 / H
    h = x.shape[-1]
    dot = jnp.sum(gf * xf, axis=-1, keepdims=True)
    dx = (gf * inv - xf * dot * inv**3 / h).astype(x.dtype)
    dw = jnp.sum(g.astype(jnp.float32) * (xf * inv), axis=0).astype(w.dtype)
    return dx, dw


_rms.defvjp(_rms_fwd, _rms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln(x2d, w, b, eps):
    return _pallas_rows(functools.partial(_ln_kernel, eps=eps), x2d, (w, b), x2d.dtype)


def _ln_fwd(x2d, w, b, eps):
    return _ln(x2d, w, b, eps), (x2d, w)


def _ln_bwd(eps, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    h = x.shape[-1]
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = xc * inv
    gf = g.astype(jnp.float32)
    gw = gf * w.astype(jnp.float32)
    dx = inv * (gw - jnp.mean(gw, axis=-1, keepdims=True) - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    dw = jnp.sum(gf * xhat, axis=0).astype(w.dtype)
    db = jnp.sum(gf, axis=0).astype(w.dtype)
    return dx.astype(x.dtype), dw, db


_ln.defvjp(_ln_fwd, _ln_bwd)


def fused_rms_norm(x, weight, *, epsilon=1e-6, residual=None):
    """RMSNorm over the last axis; optional fused residual add.

    Returns `out` or `(out, x_plus_residual)` when residual is given —
    matching the reference wrapper's contract
    (python/paddle/incubate/nn/functional/fused_rms_norm.py).
    """
    if residual is not None:
        x = x + residual
    shape = x.shape
    out = _rms(x.reshape(-1, shape[-1]), weight, float(epsilon)).reshape(shape)
    if residual is not None:
        return out, x
    return out


def fused_layer_norm(x, weight, bias, *, epsilon=1e-5, residual=None):
    if residual is not None:
        x = x + residual
    shape = x.shape
    if bias is None:
        bias = jnp.zeros(shape[-1], dtype=x.dtype)
    out = _ln(x.reshape(-1, shape[-1]), weight, bias, float(epsilon)).reshape(shape)
    if residual is not None:
        return out, x
    return out
