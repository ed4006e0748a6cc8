"""Fused SwiGLU (silu(x) * gate) Pallas kernel.

Reference: paddle.incubate.nn.functional.swiglu (fused in
paddle/phi/kernels/fusion/gpu; used by LLaMA MLP).  Elementwise VPU kernel
with fp32 math and analytic backward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops import _pl_utils
from paddle_tpu.ops._pl_utils import imap


def _swiglu_kernel(x_ref, y_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)
    y = y_ref[:].astype(jnp.float32)
    o_ref[:] = (x * jax.nn.sigmoid(x) * y).astype(o_ref.dtype)


def _swiglu_apply(x2d, y2d, rows_block=None, cols_block=None):
    rows, cols = x2d.shape
    br, bc = rows_block, cols_block
    if br is None or bc is None:
        # autotune cache first (per device kind; ops/autotune.py)
        from paddle_tpu.ops import autotune as _at

        tuned = _at.lookup("swiglu", {"rows": rows, "cols": cols,
                                      "dtype": x2d.dtype.name})
        if tuned:
            tr, tc = int(tuned["rows_block"]), int(tuned["cols_block"])
            if rows % tr == 0 and cols % tc == 0:
                br, bc = br or tr, bc or tc
    if br is None:
        br = min(256, rows)
    if rows % br:
        br = rows
    # Tile the lane dim too: a (br, cols) block at large intermediate sizes
    # (e.g. 8192x5632) needs >16MB of double-buffered VMEM and fails to
    # allocate.  Elementwise kernel, so any 128-multiple tile is valid;
    # fall back to the full width only when cols has no such divisor.
    if bc is None or cols % bc:
        bc = cols
        for cand in (2048, 1024, 512, 256, 128):
            if cols % cand == 0:
                bc = cand
                break
    return pl.pallas_call(
        _swiglu_kernel,
        grid=(rows // br, cols // bc),
        in_specs=[
            pl.BlockSpec((br, bc), imap(lambda i, j: (i, j))),
            pl.BlockSpec((br, bc), imap(lambda i, j: (i, j))),
        ],
        out_specs=pl.BlockSpec((br, bc), imap(lambda i, j: (i, j))),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x2d.dtype),
        interpret=_pl_utils.interpret(),
        name="swiglu_fwd",
    )(x2d, y2d)


@jax.custom_vjp
def _swiglu(x, y):
    shape = x.shape
    return _swiglu_apply(x.reshape(-1, shape[-1]), y.reshape(-1, shape[-1])).reshape(shape)


def _swiglu_fwd(x, y):
    return _swiglu(x, y), (x, y)


def _swiglu_bwd(res, g):
    x, y = res
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    sig = jax.nn.sigmoid(xf)
    silu = xf * sig
    dsilu = sig * (1.0 + xf * (1.0 - sig))
    return (gf * yf * dsilu).astype(x.dtype), (gf * silu).astype(y.dtype)


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def swiglu(x, y=None):
    """swiglu(x, y) = silu(x) * y; if y is None, x is split in half on the
    last axis (reference semantics)."""
    if y is None:
        x, y = jnp.split(x, 2, axis=-1)
    return _swiglu(x, y)
