"""Flash attention as a Pallas TPU kernel.

Capability parity with the reference's flash-attention integration
(paddle/phi/kernels/gpu/flash_attn_kernel.cu + python wrapper
paddle.nn.functional.flash_attention) but implemented TPU-first: blockwise
online-softmax attention tiled for the MXU, Q/K/V blocks staged through VMEM
by the Pallas pipeline, fp32 accumulation, logsumexp saved for the backward.

Layout convention: public entry takes Paddle's [B, S, N, H]; kernels run in
[B, N, S, H].  GQA (num_kv_heads < num_heads) is handled in the forward with a
BlockSpec index map (no materialized repeat); the backward materializes the
repeat and reduces dK/dV over the head group.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu._core import compile_cache
from paddle_tpu.ops import _pl_utils
from paddle_tpu.ops import autotune as _at
from paddle_tpu.ops._pl_utils import imap
from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _mask_val():
    # Explicit f32: under global x64 a bare Python float becomes an f64
    # constant inside the kernel trace, which Mosaic cannot lower (infinite
    # recursion in its f64->f32 conversion helper).  tests/test_ops_pallas.py
    # scans every kernel jaxpr for 64-bit types to keep this class of bug out.
    return jnp.float32(DEFAULT_MASK_VALUE)


def _block_sizes(seq_q, seq_k, head_dim=128, dtype=None, causal=False, *,
                 v_dim=None, window=None, kernel="flash_fwd", default=None):
    """(block_q, block_k) for one of the three kernels, in precedence order
    (reference phi/kernels/autotune/cache.h consults its config cache the
    same way):

    1. explicit FLAGS_flash_block_q/_k override, for every kernel — invalid
       values WARN loudly and fall through (VERDICT r3 #10: no silent
       fallbacks);
    2. the per-device-kind autotune table (ops/autotune.py,
       ops/tuned/<device>.json) under `kernel` for this (seq, head_dim,
       dtype, causal) signature (and `window`, where the forward takes
       one: a windowed row meets few key blocks, so its best tile differs).
       Its v5e entries were measured on the chip (PRs 30 and 31), on the
       kernels whose products take bfloat16 operands, at the shapes the
       benchmark's cells run; each backward kernel has entries of its own
       (`flash_bwd_dq`, `flash_bwd_dkv`);
    3. `default`: the forward's tile, for a backward kernel the table does
       not know; else 128 x 128, the untuned shapes' tile: safe for every
       type and length the kernels take, and 3 to 4.7 times slower than the
       tuned tile at every shape PR 30 measured (PERF.md section 6).
    """
    import warnings

    from paddle_tpu._core import flags as _flags

    def _fallback(seq):
        return min(128, seq)

    def invalid(bq, bk):
        return _at.validate_flash_tile(bq, bk, seq_q, seq_k, head_dim,
                                       dtype=dtype, v_dim=v_dim)

    # 1. explicit flags
    fq, fk = int(_flags.flag("FLAGS_flash_block_q")), int(_flags.flag("FLAGS_flash_block_k"))
    if fq > 0 or fk > 0:
        bq = min(fq, seq_q) if fq > 0 else _fallback(seq_q)
        bk = min(fk, seq_k) if fk > 0 else _fallback(seq_k)
        reason = invalid(bq, bk)
        if reason is None:
            return bq, bk
        warnings.warn(
            f"flash_attention: FLAGS_flash_block_q/_k=({fq},{fk}) invalid "
            f"for seq=({seq_q},{seq_k}), head_dim={head_dim}: {reason}; "
            "using the autotune cache / 128x128 default instead",
            stacklevel=3,
        )

    # 2. autotune cache
    key = _at.flash_key(seq_q, seq_k, head_dim,
                        dtype if dtype is not None else "bfloat16", causal,
                        v_dim, window)
    tuned = _at.lookup(kernel, key)
    if tuned:
        bq, bk = int(tuned["block_q"]), int(tuned["block_k"])
        reason = invalid(bq, bk)
        if reason is None:
            return bq, bk
        warnings.warn(
            f"flash_attention: cached tile ({bq},{bk}) for {kernel} {key} is "
            f"invalid on this device: {reason}; using the 128x128 default "
            "(re-run `python -m paddle_tpu.ops.autotune`)",
            stacklevel=3,
        )

    # 3. default
    return default or (_fallback(seq_q), _fallback(seq_k))


# Mosaic's own stack scratch on top of the pipeline's blocks (observed
# <= 16 KiB when compiling for a v5e; the dkv kernel at exactly 16 MiB of
# blocks was refused at "16.01M").
_MOSAIC_SCRATCH = 32 << 10
_LANE_F32 = 128 * 4  # one lse / delta row, lane-padded


def _require_vmem(kernel, seq_name, seq, row_bytes, block, block_row_bytes):
    """Mosaic branch only: these kernels keep one head's WHOLE sequence
    resident in VMEM (K and V in the forward and dq kernels; Q, dO, lse
    and delta in the dk/dv kernel) and Pallas double-buffers every block,
    so past a length the chip's compiler refuses the kernel with a
    scoped-VMEM allocation dump.  Name the limit instead.  A `window`
    does not lift it: the windowed forward visits only the key blocks its
    window reaches, but K and V are still resident whole.  No switch to
    the O(S^2) reference: a caller that needs longer sequences shards
    them (context_parallel_llama) until a streaming-K/V kernel exists."""
    budget = _at._VMEM_BUDGET
    need = 2 * (seq * row_bytes + block * block_row_bytes) + _MOSAIC_SCRATCH
    if need <= budget:
        return
    # the longest at the untuned 128-row tile: a property of the kernel and
    # the widths, not of the tile this call happened to bring (a backward
    # kernel with no entry of its own inherits the forward's, which may be
    # 512 or 1,024 rows)
    longest = (((budget - _MOSAIC_SCRATCH) // 2
                - min(block, 128) * block_row_bytes) // row_bytes)
    raise ValueError(
        f"flash_attention ({kernel} kernel): {seq_name}={seq} needs "
        f"{need / (1 << 20):.2f} MiB of VMEM for its whole-sequence blocks "
        f"(double-buffered) but Mosaic's scoped-VMEM limit is "
        f"{budget >> 20} MiB; the longest {seq_name} this kernel "
        f"compiles for at these widths is {longest // 128 * 128} "
        "(with or without a window: K and V stay resident whole). "
        "Shard the sequence (context parallelism) or shorten it.")


# ---------------------------------------------------------------------------
# What the matrix unit is fed
# ---------------------------------------------------------------------------


def _operand_dtype(*blocks):
    """The type of every matrix product's operands, from the blocks' own.
    bfloat16 blocks go to the matrix unit as they are stored, one pass with
    float32 accumulation, whatever matmul precision the caller has set; the
    float32 factors made inside a kernel (the probabilities `p`, the score
    gradients `ds`) are rounded to it once before their product, as the
    published modelling code of the served configurations does
    (`softmax(..., dtype=float32).to(query.dtype)`).  Anything else
    (float32, float16, a mix) is multiplied in float32 at the caller's
    precision, as ever: about six passes at "highest"; at jax's default a
    v5e gives a float32 product one pass too (PR 30 measured the two
    operand types equally fast at every tile: PERF.md section 6), so on
    the chip this states the products' precision more than it changes it.
    Maxima, row sums (from the float32 `p`), lse, delta, exponentials and
    every accumulator are float32 either way."""
    if all(b.dtype == jnp.bfloat16 for b in blocks):
        return jnp.bfloat16
    return jnp.float32


# contracted axes of (a, b): a b^T, a b, a^T b
_NT, _NN, _TN = (1, 1), (1, 0), (0, 0)


def _dot(a, b, axes):
    # two bfloat16 values multiply exactly in float32: one pass IS full
    # precision, and Mosaic refuses bfloat16 operands at any other setting
    # ("Bad lhs type" under jax_default_matmul_precision=highest).  float32
    # operands keep the caller's precision, as ever.
    precision = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return jax.lax.dot_general(a, b, (((axes[0],), (axes[1],)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _causal_mask(s, row0, col0, window=None):
    """Hide s[r, c] where key col0 + c lies after query row0 + r or, with
    a `window`, at or before row0 + r - window (a query sees itself and
    the window - 1 keys before it)."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = rows >= cols
    if window is not None:
        seen = seen & (cols > rows - jnp.int32(window))
    return jnp.where(seen, s, _mask_val())


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, block_k,
                window=None):
    # q_ref: [bq, H]; k_ref: [S, H]; v_ref: [S, Hv]; o_ref: [bq, Hv];
    # lse_ref: [bq, 128].  Hv == H everywhere but latent attention's prefill
    # (q/k 192 = nope 128 + rope 64, v 128: models/mla_moe.py)
    bq, head_dim = q_ref.shape[0], v_ref.shape[1]
    seq_k = k_ref.shape[0]
    qi = pl.program_id(2)  # q-block index
    mxu = _operand_dtype(q_ref, k_ref, v_ref)
    scale = jnp.float32(scale)
    q = q_ref[:].astype(mxu)
    if mxu == jnp.float32:
        q = q * scale  # float32 operands: scaled before the product, as ever

    num_kv = seq_k // block_k
    # bottom-right causal alignment for Sq != Sk (the kv-cache/decode
    # convention; matches flash_attention_reference's tril(k=Sk-Sq))
    row0 = seq_k - pl.num_programs(2) * bq + qi * bq  # first (aligned) q row
    if causal:
        # only kv blocks whose start <= last (aligned) q row
        num_kv_dyn = jnp.minimum(
            jnp.int32(row0 + bq + block_k - 1) // jnp.int32(block_k), num_kv)
    else:
        num_kv_dyn = jnp.int32(num_kv)
    # with a window, start at the first kv block the block's FIRST row still
    # reaches (keys > row0 - window): the blocks before it are never read
    first_kv = jnp.int32(0) if window is None else (
        jnp.maximum(jnp.int32(row0 - window + 1), 0) // jnp.int32(block_k))

    def body(j, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(mxu)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(mxu)
        s = _dot(q, k, _NT)  # [bq, bk]
        if mxu != jnp.float32:
            s = s * scale  # the raw blocks were multiplied: scale the sums
        if causal:
            s = _causal_mask(s, row0, j * block_k, window)
        m_cur = jnp.max(s, axis=1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + _dot(p.astype(mxu), v, _NN)
        return acc, m_new, l_new

    acc0 = jnp.zeros((bq, head_dim), jnp.float32)
    m0 = jnp.full((bq, 1), DEFAULT_MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(first_kv, num_kv_dyn, body, (acc0, m0, l0))

    l_safe = jnp.where(l == 0.0, jnp.float32(1.0), l)
    o_ref[:] = (acc / l_safe).astype(o_ref.dtype)
    lse = (m + jnp.log(l_safe)).astype(jnp.float32)  # [bq, 1]
    lse_ref[:] = jnp.broadcast_to(lse, lse_ref.shape)


def _fwd(q, k, v, scale, causal, block_q, block_k, window=None):
    # q: [B, N, Sq, H]; k: [B, Nkv, Sk, H]; v: [B, Nkv, Sk, Hv]
    batch, num_heads, seq_q, head_dim = q.shape
    num_kv_heads, seq_k, v_dim = k.shape[1], k.shape[2], v.shape[3]
    group = num_heads // num_kv_heads
    grid = (batch, num_heads, seq_q // block_q)
    interpret = _pl_utils.interpret()
    if not interpret:
        row = (_at.lane_padded(head_dim) + _at.lane_padded(v_dim)) * q.dtype.itemsize
        _require_vmem("forward", "seq_k", seq_k, row, block_q,
                      row + _LANE_F32)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_k=block_k, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, head_dim), imap(lambda b, n, i: (b, n, i, 0))),
            pl.BlockSpec((None, None, seq_k, head_dim), imap(lambda b, n, i: (b, n // group, 0, 0))),
            pl.BlockSpec((None, None, seq_k, v_dim), imap(lambda b, n, i: (b, n // group, 0, 0))),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, v_dim), imap(lambda b, n, i: (b, n, i, 0))),
            pl.BlockSpec((None, None, block_q, 128), imap(lambda b, n, i: (b, n, i, 0))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape[:3] + (v_dim,), q.dtype),
            jax.ShapeDtypeStruct((batch, num_heads, seq_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, scale, causal, block_k):
    bq, head_dim = q_ref.shape
    seq_k = k_ref.shape[0]
    qi = pl.program_id(2)
    mxu = _operand_dtype(q_ref, k_ref, v_ref, do_ref)
    q = q_ref[:].astype(mxu)
    do = do_ref[:].astype(mxu)
    lse = lse_ref[:, :1]  # [bq, 1]
    delta = delta_ref[:, :1]  # [bq, 1]
    scale = jnp.float32(scale)

    num_kv = seq_k // block_k
    row0 = seq_k - pl.num_programs(2) * bq + qi * bq  # bottom-right alignment
    if causal:
        num_kv_dyn = jnp.minimum(
            jnp.int32(row0 + bq + block_k - 1) // jnp.int32(block_k), num_kv)
    else:
        num_kv_dyn = jnp.int32(num_kv)

    def body(j, dq):
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(mxu)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(mxu)
        s = _dot(q, k, _NT) * scale
        if causal:
            s = _causal_mask(s, row0, j * block_k)
        p = jnp.exp(s - lse)
        dp = _dot(do, v, _NT)
        ds = p * (dp - delta) * scale
        return dq + _dot(ds.astype(mxu), k, _NN)

    dq = jax.lax.fori_loop(jnp.int32(0), num_kv_dyn, body, jnp.zeros((bq, head_dim), jnp.float32))
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, scale, causal, block_q):
    bk, head_dim = k_ref.shape
    seq_q = q_ref.shape[0]
    ki = pl.program_id(2)
    mxu = _operand_dtype(q_ref, k_ref, v_ref, do_ref)
    k = k_ref[:].astype(mxu)
    v = v_ref[:].astype(mxu)
    scale = jnp.float32(scale)

    num_q = seq_q // block_q
    q_off = pl.num_programs(2) * bk - seq_q  # bottom-right alignment
    if causal:
        # q blocks whose last aligned row precedes this kv block start
        # contribute nothing
        start_q = jnp.maximum(jnp.int32(ki * bk) - jnp.int32(q_off),
                              jnp.int32(0)) // jnp.int32(block_q)
    else:
        start_q = jnp.int32(0)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.ds(i * block_q, block_q), :].astype(mxu)
        do = do_ref[pl.ds(i * block_q, block_q), :].astype(mxu)
        lse = lse_ref[pl.ds(i * block_q, block_q), :1]
        delta = delta_ref[pl.ds(i * block_q, block_q), :1]
        s = _dot(q, k, _NT) * scale
        if causal:
            s = _causal_mask(s, q_off + i * block_q, ki * bk)
        p = jnp.exp(s - lse)  # [bq_blk, bk]
        dv = dv + _dot(p.astype(mxu), do, _TN)
        dp = _dot(do, v, _NT)
        ds = p * (dp - delta) * scale
        dk = dk + _dot(ds.astype(mxu), q, _TN)
        return dk, dv

    dk0 = jnp.zeros((bk, head_dim), jnp.float32)
    dv0 = jnp.zeros((bk, head_dim), jnp.float32)
    dk, dv = jax.lax.fori_loop(start_q, jnp.int32(num_q), body, (dk0, dv0))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _bwd_dq(q, k, v, do, lse_b, delta_b, scale, causal, block_q, block_k):
    # k, v: one head per query head (the caller repeats a KV group)
    batch, num_heads, seq_q, head_dim = q.shape
    seq_k = k.shape[2]
    interpret = _pl_utils.interpret()
    if not interpret:
        row = _at.lane_padded(head_dim) * q.dtype.itemsize
        _require_vmem("backward dq", "seq_k", seq_k, 2 * row, block_q,
                      3 * row + 2 * _LANE_F32)
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, block_k=block_k),
        grid=(batch, num_heads, seq_q // block_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, head_dim), imap(lambda b, n, i: (b, n, i, 0))),
            pl.BlockSpec((None, None, seq_k, head_dim), imap(lambda b, n, i: (b, n, 0, 0))),
            pl.BlockSpec((None, None, seq_k, head_dim), imap(lambda b, n, i: (b, n, 0, 0))),
            pl.BlockSpec((None, None, block_q, head_dim), imap(lambda b, n, i: (b, n, i, 0))),
            pl.BlockSpec((None, None, block_q, 128), imap(lambda b, n, i: (b, n, i, 0))),
            pl.BlockSpec((None, None, block_q, 128), imap(lambda b, n, i: (b, n, i, 0))),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, head_dim), imap(lambda b, n, i: (b, n, i, 0))),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse_b, delta_b)


def _bwd_dkv(q, k, v, do, lse_b, delta_b, scale, causal, block_q, block_k):
    batch, num_heads, seq_q, head_dim = q.shape
    seq_k = k.shape[2]
    interpret = _pl_utils.interpret()
    if not interpret:
        row = _at.lane_padded(head_dim) * q.dtype.itemsize
        _require_vmem("backward dk/dv", "seq_q", seq_q,
                      2 * row + 2 * _LANE_F32, block_k, 4 * row)
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q),
        grid=(batch, num_heads, seq_k // block_k),
        in_specs=[
            pl.BlockSpec((None, None, seq_q, head_dim), imap(lambda b, n, j: (b, n, 0, 0))),
            pl.BlockSpec((None, None, block_k, head_dim), imap(lambda b, n, j: (b, n, j, 0))),
            pl.BlockSpec((None, None, block_k, head_dim), imap(lambda b, n, j: (b, n, j, 0))),
            pl.BlockSpec((None, None, seq_q, head_dim), imap(lambda b, n, j: (b, n, 0, 0))),
            pl.BlockSpec((None, None, seq_q, 128), imap(lambda b, n, j: (b, n, 0, 0))),
            pl.BlockSpec((None, None, seq_q, 128), imap(lambda b, n, j: (b, n, 0, 0))),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_k, head_dim), imap(lambda b, n, j: (b, n, j, 0))),
            pl.BlockSpec((None, None, block_k, head_dim), imap(lambda b, n, j: (b, n, j, 0))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse_b, delta_b)


def _row_stats(out, lse, do):
    """lse and delta = sum(out * dO) per query row [B, N, Sq], float32 and
    broadcast over 128 lanes: the form both backward kernels read."""
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    return tuple(jnp.broadcast_to(x[..., None], (*x.shape, 128)).astype(jnp.float32)
                 for x in (lse, delta))


def _bwd(q, k, v, out, lse, do, scale, causal, block_q, block_k):
    """dq, dk, dv.  `block_q` / `block_k` are the forward's tile: each
    backward kernel runs the tile the table holds for IT at this shape
    (its best differs from the forward's), and the forward's where the
    table holds none."""
    batch, num_heads, seq_q, head_dim = q.shape
    num_kv_heads, seq_k = k.shape[1], k.shape[2]
    group = num_heads // num_kv_heads
    if group > 1:
        k_rep = jnp.repeat(k, group, axis=1)
        v_rep = jnp.repeat(v, group, axis=1)
    else:
        k_rep, v_rep = k, v

    lse_b, delta_b = _row_stats(out, lse, do)

    def tile(kernel):
        return _block_sizes(seq_q, seq_k, head_dim, q.dtype, causal,
                            kernel=kernel, default=(block_q, block_k))

    args = (q, k_rep, v_rep, do, lse_b, delta_b, scale, causal)
    dq = _bwd_dq(*args, *tile("flash_bwd_dq"))
    dk_rep, dv_rep = _bwd_dkv(*args, *tile("flash_bwd_dkv"))

    if group > 1:
        dk = dk_rep.reshape(batch, num_kv_heads, group, seq_k, head_dim).sum(axis=2).astype(k.dtype)
        dv = dv_rep.reshape(batch, num_kv_heads, group, seq_k, head_dim).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_rep, dv_rep
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper (operates in [B, N, S, H])
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bnsh(q, k, v, scale, causal, block_q, block_k, window=None):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k, window)
    return out


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k, window):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k, window)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, window, res, do):
    if window is not None:
        raise NotImplementedError(
            "flash_attention: the backward kernels take no window (window="
            f"{window}): only serving's prefill runs windowed layers today; "
            "train them through flash_attention_reference(window=) or add "
            "the window to _bwd_dq_kernel / _bwd_dkv_kernel (ROADMAP R4)")
    q, k, v, out, lse = res
    return _bwd(q, k, v, out, lse, do, scale, causal, block_q, block_k)


_flash_bnsh.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _ragged(seq_q, seq_k, block_q, block_k):
    """Lengths the kernels cannot tile: not a multiple of the block, or a
    (short-sequence) block off the 8-row sublane grid, which Mosaic refuses
    ("cannot statically prove that index ... is a multiple of 8")."""
    return bool(seq_q % block_q or seq_k % block_k
                or block_q % 8 or block_k % 8)


def flash_attention(q, k, v, *, causal=False, scale=None, window=None):
    """Blockwise flash attention.  q/k/v: [B, S, N, H] (paddle layout).
    v may have a width of its own ([B, S, N, Hv]; the output takes it):
    that case is forward-only, the backward kernels keep one width.

    `window` W (causal only): a query sees itself and the W - 1 keys
    before it.  The forward's loop over key blocks starts at the first
    block the window reaches, so a sliding layer's prefill at 8,192 with
    W = 512 does about an eighth of the products; K and V are still
    resident in VMEM whole, so a window does NOT lift the whole-sequence
    limit (`_require_vmem`).  Forward only: differentiating a windowed
    call raises NotImplementedError by name.

    Lengths the kernels cannot tile (see _ragged): causal self-attention
    (Sq == Sk, e.g. a prompt of any length) is zero-padded to a multiple of
    128 and sliced back — exact, since a padded key sits after every real
    query and the causal mask hides it.  Padding keys would change a
    non-causal softmax, and padding a cross-length chunk would move its
    bottom-right alignment, so those fall back to the full-softmax
    reference with a warning.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if window is not None:
        if not causal:
            raise ValueError("flash_attention: window= needs causal=True")
        window = int(window)
        if window < 1:
            raise ValueError(f"flash_attention: window={window} must be >= 1")
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    seq_q, seq_k = qt.shape[2], kt.shape[2]

    def blocks(sq, sk):
        return _block_sizes(sq, sk, head_dim=qt.shape[-1], dtype=qt.dtype,
                            causal=causal, v_dim=vt.shape[-1], window=window)

    block_q, block_k = blocks(seq_q, seq_k)
    pad = 0
    if _ragged(seq_q, seq_k, block_q, block_k) and causal and seq_q == seq_k:
        pad = -seq_q % 128
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        qt, kt, vt = (jnp.pad(x, widths) for x in (qt, kt, vt))
        block_q, block_k = blocks(seq_q + pad, seq_k + pad)
    if _ragged(seq_q + pad, seq_k + pad, block_q, block_k):
        # the full O(S^2)-memory reference — fine for tests, a cliff in
        # real use
        import warnings

        warnings.warn(
            f"flash_attention: seq lengths ({seq_q}, {seq_k}) are not "
            f"multiples of the ({block_q}, {block_k}) block; falling back to "
            "full-softmax attention (O(S^2) memory). Pad sequences to a "
            "multiple of 128 for the Pallas kernel.",
            stacklevel=2,
        )
        return flash_attention_reference(q, k, v, causal=causal, scale=scale,
                                         window=window)
    # the counter that says which products this trace asked for
    compile_cache.count("flash_bf16_operand_traces"
                        if _operand_dtype(qt, kt, vt) == jnp.bfloat16
                        else "flash_f32_operand_traces")
    if vt.shape[-1] != qt.shape[-1]:
        out, _ = _fwd(qt, kt, vt, float(scale), bool(causal), block_q, block_k,
                      window)
    else:
        out = _flash_bnsh(qt, kt, vt, float(scale), bool(causal), block_q,
                          block_k, window)
    return jnp.swapaxes(out[:, :, :seq_q], 1, 2)


def flash_attention_reference(q, k, v, *, causal=False, scale=None,
                              window=None):
    """Pure-jnp oracle with identical semantics ([B, S, N, H] layout)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    group = qt.shape[1] // kt.shape[1]
    if group > 1:
        kt = jnp.repeat(kt, group, axis=1)
        vt = jnp.repeat(vt, group, axis=1)
    logits = jnp.einsum("bnqh,bnkh->bnqk", qt, kt) * scale
    if causal:
        qlen, klen = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), bool), k=klen - qlen)
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((qlen, klen), bool),
                                    k=klen - qlen - int(window))
        logits = jnp.where(mask, logits, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnqk,bnkh->bnqh", probs, vt)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)
