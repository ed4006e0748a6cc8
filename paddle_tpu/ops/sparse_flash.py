"""Flash attention's forward over a per-query selection of keys, as a Pallas
TPU kernel: the full layers' prefill of a learned sparse indexer
(models/dsa_moe.py) attends each query's chosen keys without a float32
score matrix in HBM.

The selection arrives as a KEEP MASK packed 32 keys to an int32 word, one
row of words a query.  Keys go in groups of `GROUP` = 4,096; key s is bit
m = (s % 4096) // 128 of word (s // 4096) * 128 + s % 128.  So the 128
words of a group are one row of lanes, and the keep bits of any 128
consecutive keys of it are ONE shift of that row: a key tile of the kernel
unpacks in registers with a shift and an `and` per 128 lanes, no gather.
A prompt of S keys takes `words_for(S)` words a query (256 at 8,192: 1 KB,
where a float32 score row is 32 KB).

The kernel (`sparse_flash_forward`) is the causal forward of
ops/flash_attention.py with the causal mask replaced by the keep bits:
q [B, N, R, d], K [B, N, Sk, d] and V [B, N, Sk, d_v] (d_v may differ from
d) resident a head at a time, an online softmax in VMEM over key tiles,
float32 statistics, the products' operands in the blocks' own type.  Which
key tiles a query tile visits is a scalar-prefetched table: the tiles in
which some query of the tile keeps a key, in key order (`_kept_tiles`).
Causality lives in the mask (a query keeps no later key), so the tiles
past the diagonal are never visited and the kernel takes no row offset.
It is forward-only: serving's prefill is its one caller, and nothing
trains through a learned selection.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import _pl_utils
from paddle_tpu.ops import autotune as _at
from paddle_tpu.ops._pl_utils import imap
from paddle_tpu.ops.flash_attention import (_MOSAIC_SCRATCH, _NN, _NT, _dot,
                                            _mask_val, _operand_dtype)

__all__ = ["GROUP", "KERNEL", "words_for", "pack_keep", "tiles", "fits",
           "sparse_flash_forward"]

GROUP = 4096      # keys a row of 128 words covers (32 bits x 128 lanes)
_LANES = 128
KERNEL = "sparse_flash_fwd"   # its name in the tile table and on the device


def words_for(n_keys: int) -> int:
    """Words a query row of a mask over `n_keys` keys holds."""
    return -(-n_keys // GROUP) * _LANES


def _or(x, axis):
    return jax.lax.reduce(x, jnp.int32(0), jax.lax.bitwise_or, (axis,))


def pack_keep(keep):
    """keep [..., n] bool -> int32 words [..., words_for(n)] (module
    docstring's layout; keys past n are not kept)."""
    n = keep.shape[-1]
    groups = -(-n // GROUP)
    lead = keep.shape[:-1]
    keep = jnp.pad(keep, [(0, 0)] * len(lead) + [(0, groups * GROUP - n)])
    bits = keep.reshape(lead + (groups, 32, _LANES)).astype(jnp.int32)
    shifts = jnp.arange(32, dtype=jnp.int32)[:, None]
    return _or(bits << shifts, len(lead) + 1).reshape(lead + (groups * _LANES,))


def _kept_tiles(words, block_q, block_k, n_k):
    """(count [B * nq], tiles [B * nq * n_k]) int32: for each query tile the
    number of key tiles in which one of its queries keeps a key, and those
    tiles' indices in key order ahead of the rest."""
    b, r, wd = words.shape
    nq = r // block_q
    rows = _or(words.reshape(b, nq, block_q, wd), 2)            # [b, nq, wd]
    group = _or(rows.reshape(b, nq, wd // _LANES, _LANES), 3)   # bit m: chunk m
    chunk = (group[..., None] >> jnp.arange(32, dtype=jnp.int32)) & 1
    per = block_k // _LANES
    kept = jnp.max(chunk.reshape(b, nq, -1, per), axis=-1)[..., :n_k] > 0
    j = jnp.arange(n_k, dtype=jnp.int32)
    order = jnp.argsort(jnp.where(kept, j, n_k + j), axis=-1)
    count = jnp.sum(kept, axis=-1, dtype=jnp.int32)
    return count.reshape(-1), order.astype(jnp.int32).reshape(-1)


def _kernel(count_ref, tiles_ref, q_ref, k_ref, v_ref, w_ref, o_ref, *,
            scale, block_k, n_q, n_k):
    # q_ref [bq, d]; k_ref [Sk, d]; v_ref [Sk, d_v]; w_ref [bq, W] int32;
    # o_ref [bq, d_v]
    bq, v_dim = q_ref.shape[0], v_ref.shape[1]
    at = pl.program_id(0) * n_q + pl.program_id(2)
    mxu = _operand_dtype(q_ref, k_ref, v_ref)
    scale = jnp.float32(scale)
    q = q_ref[:].astype(mxu)
    if mxu == jnp.float32:
        q = q * scale
    chunks = block_k // _LANES

    def body(j, carry):
        acc, m_prev, l_prev = carry
        start = pl.multiple_of(tiles_ref[at * n_k + j] * block_k, block_k)
        k = k_ref[pl.ds(start, block_k), :].astype(mxu)
        v = v_ref[pl.ds(start, block_k), :].astype(mxu)
        s = _dot(q, k, _NT)  # [bq, bk]
        if mxu != jnp.float32:
            s = s * scale
        # the tile's 128 words and its first bit: a tile lies in one group
        words = w_ref[:, pl.ds(pl.multiple_of(start // GROUP * _LANES,
                                              _LANES), _LANES)]
        first = (start % GROUP) // _LANES
        keep = jnp.concatenate(
            [(words >> (first + c)) & 1 for c in range(chunks)], axis=1)
        s = jnp.where(keep != 0, s, _mask_val())
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + _dot(p.astype(mxu), v, _NN)
        return acc, m_new, l_new

    # a row's first visited tile may keep none of ITS keys: its statistics
    # then start from the mask value, and the first kept key's alpha,
    # exp(mask - m), is exactly 0 and clears them.  A row that keeps no key
    # at all (a padding row) ends at the mask value and reads zeros.
    acc0 = jnp.zeros((bq, v_dim), jnp.float32)
    m0 = jnp.full((bq, 1), _mask_val(), jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, count_ref[at], body, (acc0, m0, l0))
    kept = m > _mask_val()
    out = acc / jnp.where(kept, l, jnp.float32(1.0))
    o_ref[:] = jnp.where(kept, out, jnp.float32(0.0)).astype(o_ref.dtype)


def _vmem_need(seq_k, head_dim, v_dim, itemsize, block_q, block_k):
    """The kernel's VMEM, counted as flash_attention._require_vmem counts the
    causal forward's, with the mask's block and the tile's scores."""
    row = (_at.lane_padded(head_dim) + _at.lane_padded(v_dim)) * itemsize
    words = words_for(seq_k) * 4
    return (2 * (seq_k * row + block_q * (row + words))
            + 2 * block_q * block_k * 4 + _MOSAIC_SCRATCH)


def _valid(block_q, block_k, seq_k, head_dim, v_dim, dtype):
    if block_q % 8 or block_k % _LANES or GROUP % block_k:
        return False
    seq_k = -(-seq_k // block_k) * block_k
    return _vmem_need(seq_k, head_dim, v_dim, jnp.dtype(dtype).itemsize,
                      block_q, block_k) <= _at._VMEM_BUDGET


def _untuned(rows, seq_k):
    return min(256, -(-rows // 8) * 8), (512 if seq_k >= 2048 else _LANES)


def tiles(rows, seq_k, head_dim, v_dim, dtype):
    """(block_q, block_k): the tile table's entry for this shape (`KERNEL`,
    under `autotune.flash_key` of (rows, keys)) where it holds a valid one;
    else 256 x 512, cut to the rows and keys of a small call.  block_k is a
    multiple of 128 that divides `GROUP`."""
    tuned = _at.lookup(KERNEL, _at.flash_key(rows, seq_k, head_dim, dtype,
                                             True, v_dim))
    if tuned:
        bq, bk = int(tuned["block_q"]), int(tuned["block_k"])
        if _valid(bq, bk, seq_k, head_dim, v_dim, dtype):
            return bq, bk
    return _untuned(rows, seq_k)


def fits(seq_k, head_dim, v_dim, dtype) -> bool:
    """Whether the kernel holds a head's K and V of `seq_k` keys whole at
    its untuned tile (the causal forward's limit: no streaming of K and V;
    a tuned tile is valid or not taken)."""
    return _valid(*_untuned(seq_k, seq_k), seq_k, head_dim, v_dim, dtype)


def sparse_flash_forward(q, k, v, words, *, scale, block_q=None,
                         block_k=None):
    """softmax(scale q K^T, over each query's kept keys) V.  q [B, N, R, d];
    k [B, N, Sk, d]; v [B, N, Sk, d_v]; words [B, R, words_for(Sk)] the
    keep mask (`pack_keep`), shared by the heads; a query that keeps no
    key reads zeros.  Returns [B, N, R, d_v] in q's type.  The tile comes
    from `tiles` unless given."""
    batch, heads, rows, head_dim = q.shape
    seq_k, v_dim = k.shape[2], v.shape[3]
    if words.shape != (batch, rows, words_for(seq_k)):
        raise ValueError(f"sparse_flash_forward: mask {words.shape} for "
                         f"{batch} x {rows} queries over {seq_k} keys "
                         f"wants {(batch, rows, words_for(seq_k))}")
    if block_q is None or block_k is None:
        block_q, block_k = tiles(rows, seq_k, head_dim, v_dim, q.dtype)
    r_pad, k_pad = -rows % block_q, -seq_k % block_k
    if r_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, r_pad), (0, 0)))
        words = jnp.pad(words, ((0, 0), (0, r_pad), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
    out = _call(q, k, v, words, scale=float(scale), block_q=int(block_q),
                block_k=int(block_k), interpret=_pl_utils.interpret())
    return out[:, :, :rows]


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "block_k",
                                             "interpret"))
def _call(q, k, v, words, *, scale, block_q, block_k, interpret):
    """The kernel's call, a jitted function of its own, as
    ops/paged_attention._paged_decode_call: the full layers of a prefill
    program share one trace and one lowering of it."""
    batch, heads, rows, head_dim = q.shape
    seq_k, v_dim, wd = k.shape[2], v.shape[3], words.shape[2]
    n_q, n_k = rows // block_q, seq_k // block_k
    count, order = _kept_tiles(words, block_q, block_k, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k, n_q=n_q,
                          n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, heads, n_q),
            in_specs=[
                pl.BlockSpec((None, None, block_q, head_dim),
                             imap(lambda b, n, i, *_: (b, n, i, 0))),
                pl.BlockSpec((None, None, seq_k, head_dim),
                             imap(lambda b, n, i, *_: (b, n, 0, 0))),
                pl.BlockSpec((None, None, seq_k, v_dim),
                             imap(lambda b, n, i, *_: (b, n, 0, 0))),
                pl.BlockSpec((None, block_q, wd),
                             imap(lambda b, n, i, *_: (b, i, 0))),
            ],
            out_specs=pl.BlockSpec((None, None, block_q, v_dim),
                                   imap(lambda b, n, i, *_: (b, n, i, 0)))),
        out_shape=jax.ShapeDtypeStruct((batch, heads, rows, v_dim), q.dtype),
        interpret=interpret,
        name=KERNEL,
    )(count, order, q, k, v, words)
