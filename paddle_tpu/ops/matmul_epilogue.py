"""Fused matmul + bias + activation Pallas kernel (the matmul-epilogue
fusion family).

Reference capability: CINN fusion groups / epilogue fusion
(paddle/cinn/hlir/framework/op_lowering_impl.cc — matmul+bias+act chains),
phi fused kernels like fused_gemm_epilogue.

TPU shape: a blocked MXU matmul accumulating in f32 VMEM scratch; the
epilogue (bias add + gelu/silu/relu) runs on the final K step on the
accumulator while it is still in VMEM — the intermediate [M, N] pre-
activation never round-trips HBM.  Tiles come from the measured autotune
cache (ops/autotune.py, kernel "matmul_epilogue") with VMEM-safe analytic
defaults; shapes the grid cannot tile cleanly fall back to plain XLA
(which fuses simple epilogues well — the kernel exists for the cases it
does not, and for tile control).  So does exact-erf GELU: Mosaic has no
lowering for erf, so that epilogue is XLA's on every backend and
MatmulEpiloguePattern does not fuse it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops import _pl_utils
from paddle_tpu.ops._pl_utils import imap
from jax.experimental.pallas import tpu as pltpu

__all__ = ["matmul_bias_act"]

_ACTS = {
    "none": lambda v: v,
    "relu": lambda v: jnp.maximum(v, 0.0),
    "gelu": lambda v: jax.nn.gelu(v, approximate=False),
    "gelu_tanh": lambda v: jax.nn.gelu(v, approximate=True),
    "silu": lambda v: v * jax.nn.sigmoid(v),
}


def _kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, act, k_steps, has_bias):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(
        x_ref[:], w_ref[:], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        r = acc_ref[:]
        if has_bias:
            r = r + b_ref[:].astype(jnp.float32)
        o_ref[:] = _ACTS[act](r).astype(o_ref.dtype)


def _pick_tiles(M, K, N, dtype):
    from paddle_tpu.ops import autotune as _at

    tuned = _at.lookup("matmul_epilogue", {
        "m": M, "k": K, "n": N, "dtype": jnp.dtype(dtype).name})
    if tuned:
        bm, bk, bn = int(tuned["bm"]), int(tuned["bk"]), int(tuned["bn"])
        if M % bm == 0 and K % bk == 0 and N % bn == 0:
            return bm, bk, bn

    def best(total, cands):
        for c in cands:
            if total % c == 0:
                return c
        return None

    # MXU-friendly defaults; the f32 accumulator block (bm x bn) plus the
    # double-buffered inputs must sit in VMEM: 256x256x4B acc = 256KB.
    bm = best(M, (256, 128, 64, 32, 16, 8))
    bn = best(N, (256, 128))
    bk = best(K, (512, 256, 128))
    if bm is None or bn is None or bk is None:
        return None
    return bm, bk, bn


# The epilogues the kernel takes.  Exact-erf GELU is not among them: the
# TPU compiler cannot lower it ("Unimplemented primitive in Pallas TPU
# lowering: erfc"), so it is XLA's on every backend.  The one place that
# knows; MatmulEpiloguePattern asks here before it fuses.
FUSIBLE_ACTS = frozenset(_ACTS) - {"gelu"}


def _fused_2d(x2d, w, bias, act, tiles=None):
    M, K = x2d.shape
    N = w.shape[1]
    if act not in FUSIBLE_ACTS:
        return None
    tiles = tiles or _pick_tiles(M, K, N, x2d.dtype)
    if tiles is None:
        return None
    bm, bk, bn = tiles
    has_bias = bias is not None
    # the bias rides as [1, N]: Mosaic refuses a 1-D operand blocked at
    # bn ("XLA layout does not match Mosaic layout")
    b = (bias if has_bias else jnp.zeros((N,), x2d.dtype)).reshape(1, N)
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_kernel, act=act, k_steps=grid[2], has_bias=has_bias),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), imap(lambda i, j, k: (i, k))),
            pl.BlockSpec((bk, bn), imap(lambda i, j, k: (k, j))),
            pl.BlockSpec((1, bn), imap(lambda i, j, k: (0, j))),
        ],
        out_specs=pl.BlockSpec((bm, bn), imap(lambda i, j, k: (i, j))),
        out_shape=jax.ShapeDtypeStruct((M, N), x2d.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=_pl_utils.interpret(),
        name="matmul_epilogue",
    )(x2d, w, b)


def _replay(x2d, w, bias, act):
    """The epilogue math in plain XLA — the fallback path AND the backward
    replay (one definition of the semantics)."""
    r = jnp.matmul(x2d, w)
    if bias is not None:
        r = r + bias
    return _ACTS[act](r.astype(jnp.float32)).astype(x2d.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _mm_epilogue(x2d, w, bias, act):
    out = _fused_2d(x2d, w, bias, act)
    if out is None:
        out = _replay(x2d, w, bias, act)
    return out


def _mm_fwd(x2d, w, bias, act):
    return _mm_epilogue(x2d, w, bias, act), (x2d, w, bias)


def _mm_bwd(act, res, g):
    x2d, w, bias = res
    if bias is None:
        _, vjp = jax.vjp(lambda xa, wa: _replay(xa, wa, None, act), x2d, w)
        dx, dw = vjp(g)
        return dx, dw, None
    _, vjp = jax.vjp(lambda xa, wa, ba: _replay(xa, wa, ba, act), x2d, w, bias)
    return vjp(g)


_mm_epilogue.defvjp(_mm_fwd, _mm_bwd)


def matmul_bias_act(x, weight, bias=None, activation="none"):
    """act(x @ weight + bias) with the epilogue fused into the matmul.

    x: [..., K]; weight: [K, N]; bias: [N] or None;
    activation: none | relu | gelu | gelu_tanh | silu.
    """
    if activation not in _ACTS:
        raise ValueError(f"unknown activation {activation!r}; have {sorted(_ACTS)}")
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    out = _mm_epilogue(x2d, weight, bias, activation)
    return out.reshape(shape[:-1] + (weight.shape[1],))
