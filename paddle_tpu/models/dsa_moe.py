"""Latent attention with a learned sparse indexer on the full layers, a second
latent geometry over a sliding window on the others, a headwise gate on
both, and routed experts beside a shared one (the block of dots3-note-prev).

Notation: h hidden; rms(x) = x / sqrt(mean(x^2) + eps); layers are pre-norm.
A FULL layer (`layer_types[i] == "full_attention"`) has N heads, query and
key-value latent ranks r_q and r, head parts d_n / d_r / d_v and rope theta;
a SLIDING layer has its own N', r_q', r', d_n', d_r', d_v', theta' (the
config's `swa_*` keys) and sees the last W positions (`sliding_window_size`):

    n       = rms(x) g_in
    c_q     = rms(n W_qa) g_qa . s_q         s_q = sqrt(h / r_q)
    q_i     = c_q W_qb -> (q_nope_i [d_n], rope(q_rope_i) [d_r])
    (c', k_r) = n W_kva ;  c = rms(c') g_kva . s_kv ,  s_kv = sqrt(h / r)
    k_rope  = rope(k_r)                      the cache row: (c, k_rope)
    (k_nope_i, v_i) = c W_kvb
    full layers, the indexer (H_I heads of d_I, top k = `index_topk`):
      qI_j  = c_q WI_q,j  [d_I]              rope on its first d_r lanes
      kI_s  = LayerNorm(n_s WI_k) [d_I]      rope likewise; cached, d_I a token
      w_t,j = (n_t WI_w)_j . H_I^-1/2 . d_I^-1/2
      I_t,s = sum_j w_t,j relu(qI_t,j . kI_s),   s <= t
      S_t   = the k positions s <= t of largest I_t,s (all s <= t while
              t < k): the EXACT top-k
    p_i     = softmax over s in S_t (full) or t - W < s <= t (sliding) of
              (q_nope_i . k_nope_i,s + q_rope_i . k_rope_s) / sqrt(d_n + d_r)
    o_i     = sum_s p_i,s v_i,s ;  g = sigmoid(n W_g) [N]   one gate a head
    a       = x + concat_i(g_i o_i) W_o
    m       = rms(a) g_post
    dense layer (the first `first_k_dense_replace`): f = W_d(silu(W_g m) * (W_u m))
    expert layer: s = sigmoid(m W_r) in float32; T = top-k of (s + b), b the
              correction bias (noaux_tc: it moves the choice, not the
              weights); w_e = scale . s_e / sum_T s
              f = sum_{e in T, e held} w_e E_e(m) + E_shared(m)
    x'      = a + f ;   logits = rms(x_L) g W_head

Each layer's attention is models/mla_moe.LatentAttention built from ITS
geometry (`DsaMoeConfig.geometry`): the sliding layers' with a `window` (their
cache row lies in a window class's ring, read whole by the absorbed form),
the full layers' with the indexer beside it (`SparseLatentAttention`).

THE CACHE has two classes (models/contract.py).  The full layers are PAGED
with two pools: "latent", one (c, k_rope) row a token in `pool_width` lanes,
and "index", the indexer's key kI (d_I values a token).  The sliding layers
are a WINDOW class of W positions with one pool, "wlatent": the latent row
of their own geometry in a ring of blocks a slot.  PREFILL materialises
per-head K/V from the latent: the sliding layers run the flash kernel with
`window=`, the full layers each query over the indexer's selection
(`sparse_attention`: on a TPU a Pallas flash forward over a packed keep
mask, ops/sparse_flash.py).  DECODE is absorbed: a full
layer scores the row's live indexer keys (every page of its table), takes
the exact top-k (`index_select`) and reads only those latent rows
(ops/paged_attention.paged_selected_attention: a gather by page table and
slot); a sliding layer reads its ring (`absorbed_attention(window=)`).

The indexer's scores are float32: its queries and weights float32, its
products at the precision that keeps a float32 query exact against a key
that the cache holds in the model's type (`index_scores`).  The router is
models/experts.route with the correction bias.  Both are functions of
their own, so that a comparison can hand them the reference's inputs
(perfbench/families/dsa_moe.py).  Rope pairs adjacent lanes (2i, 2i+1), as
everywhere here.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu._core import compile_cache
from paddle_tpu._core.tensor import Tensor
from paddle_tpu.models.contract import (CacheClass, CacheSpec, PoolSpec,
                                        ServingContract)
from paddle_tpu.models.experts import RoutedExperts, SwiGLU, add_counts
from paddle_tpu.models.attention import rope_at, rotate
from paddle_tpu.models.mla_moe import LatentAttention, MlaMoeConfig
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops import sparse_flash

__all__ = ["DsaMoeConfig", "DsaMoeForCausalLM", "DsaMoeModel",
           "index_scores", "index_select", "select_words", "sparse_attention",
           "sparse_attention_kernel", "sparse_attention_xla", "dsa_moe_tiny",
           "FULL", "SLIDING"]

FULL, SLIDING = "full_attention", "sliding_attention"

# queries a block of the full layers' prefill (the indexer's scores, its
# selection and the masked attention of one block are live at once)
Q_BLOCK = 128


@dataclass
class DsaMoeConfig:
    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824          # the leading dense layers' FFN
    moe_intermediate_size: int = 1536       # one expert's FFN
    num_hidden_layers: int = 46
    first_k_dense_replace: int = 1
    # per layer; None: full, full, then sliding x 3, full repeated
    layer_types: tuple | None = None
    # the full layers' latent attention
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 80000000.0
    # the sliding layers' own
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    sliding_window_size: int = 513
    # the full layers' indexer
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    apply_mla_qkv_lora_rescale: bool = True
    n_routed_experts: int = 256             # the router's outputs
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 524288
    rms_norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # (first, count): the contiguous range of routed experts THIS model
    # holds and computes; None holds all of them
    held_experts: tuple | None = None

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = tuple(
                FULL if i < 2 or (i - 1) % 4 == 0 else SLIDING
                for i in range(n))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != n:
            raise ValueError(f"layer_types has {len(self.layer_types)} "
                             f"entries for {n} layers")
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"layer_types knows {FULL!r} and {SLIDING!r}")
        if not 0 < self.qk_rope_head_dim <= self.index_head_dim:
            raise ValueError("the indexer rotates the first qk_rope_head_dim "
                             "lanes of its index_head_dim")

    @property
    def held(self) -> tuple:
        return self.held_experts or (0, self.n_routed_experts)

    def geometry(self, kind) -> MlaMoeConfig:
        """The latent attention of one layer type, as
        models/mla_moe.LatentAttention takes it."""
        swa = "swa_" if kind == SLIDING else ""
        return MlaMoeConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_attention_heads=getattr(self, swa + "num_attention_heads"),
            q_lora_rank=getattr(self, swa + "q_lora_rank"),
            kv_lora_rank=getattr(self, swa + "kv_lora_rank"),
            qk_nope_head_dim=getattr(self, swa + "qk_nope_head_dim"),
            qk_rope_head_dim=getattr(self, swa + "qk_rope_head_dim"),
            v_head_dim=getattr(self, swa + "v_head_dim"),
            rope_theta=getattr(self, swa + "rope_theta"),
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps, dtype=self.dtype)


# --------------------------------------------------------------- the indexer

def index_scores(q, w, k):
    """I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s]) in float32: q [B, T, H,
    d] and w [B, T, H] (float32), k [B, S, d] -> [B, T, S].  The products at
    HIGH precision: a float32 query against a key held in bfloat16 is then
    exact (on a TPU three bfloat16 passes, of which the key's low part is
    zero), and against a float32 key within 2^-16 of it."""
    f32 = jnp.float32
    with jax.named_scope("dsa.index"):
        dots = jnp.einsum("bthd,bsd->bths", q.astype(f32), k.astype(f32),
                          precision=jax.lax.Precision.HIGH,
                          preferred_element_type=f32)
        return jnp.einsum("bths,bth->bts", jax.nn.relu(dots), w.astype(f32),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=f32)


def index_select(scores, allowed, top_k):
    """The EXACT top-k (`jax.lax.top_k`) of scores [..., S] over the
    `allowed` positions: (positions [..., k'] int32, valid [..., k'] bool),
    k' = min(top_k, S); where fewer than k' positions are allowed the rest
    are not valid."""
    k = min(top_k, scores.shape[-1])
    with jax.named_scope("dsa.select"):
        _, at = jax.lax.top_k(jnp.where(allowed, scores, -jnp.inf), k)
        ok = jnp.take_along_axis(jnp.broadcast_to(allowed, scores.shape),
                                 at, axis=-1)
    return at.astype(jnp.int32), ok


class Indexer(nn.Layer):
    """The learned sparse indexer of the full layers (DeepSeek-V3.2's
    form): H_I query heads from the query latent c_q, ONE key a token from
    the normed input through a LayerNorm (the key the cache keeps), and a
    weight a head from the normed input."""

    def __init__(self, c: DsaMoeConfig):
        super().__init__()
        self.heads, self.dim = c.index_n_heads, c.index_head_dim
        self.rotary, self.theta = c.qk_rope_head_dim, c.rope_theta
        self.wq = nn.Linear(c.q_lora_rank, self.heads * self.dim,
                            bias_attr=False)
        self.wk = nn.Linear(c.hidden_size, self.dim, bias_attr=False)
        self.k_norm = nn.LayerNorm(self.dim, epsilon=1e-6)
        self.weights_proj = nn.Linear(c.hidden_size, self.heads,
                                      bias_attr=False)

    def forward(self, n, c_q, pos):
        """n: Tensor [B, T, h]; c_q [B, T, r_q] (raw); pos [B, T] ->
        (q [B, T, H_I, d_I] float32, w [B, T, H_I] float32, k [B, T, d_I]
        in the model's type: what the cache keeps and the scores read)."""
        f32 = jnp.float32
        b, t = pos.shape
        with jax.named_scope("dsa.index"):
            cos, sin = rope_at(pos.reshape(-1), self.rotary, self.theta)
            at = jnp.arange(b * t, dtype=jnp.int32).reshape(b, t)
            q = jnp.einsum("btr,rj->btj", c_q, self.wq.weight._value,
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=f32)
            q = rotate(q.reshape(b, t, self.heads, self.dim), cos, sin,
                              at, self.rotary)
            k = jnp.einsum("bth,hd->btd", n._value, self.wk.weight._value,
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=f32)
            k = self.k_norm(Tensor(k))._value.astype(f32)
            k = rotate(k[:, :, None], cos, sin, at, self.rotary)[:, :, 0]
            w = jnp.einsum("bth,hj->btj", n._value,
                           self.weights_proj.weight._value,
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=f32)
            w = w * f32(1.0 / math.sqrt(self.heads * self.dim))
        return q, w, k.astype(n._value.dtype)


def sparse_attention(q, k, v, q_idx, w_idx, k_idx, top_k, block=Q_BLOCK):
    """Prefill attention of a full layer: query t attends the k = `top_k`
    positions s <= t of the indexer's largest scores (all s <= t while
    t < k).  q / k [B, S, N, d], v [B, S, N, d_v]; the indexer's q_idx
    [B, S, H_I, d_I], w_idx [B, S, H_I], k_idx [B, S, d_I].  Queries go in
    spans of max(k, block), whose indexer scores meet only the keys up to
    the span's end, and each span in blocks of `block` queries (a lax.map):
    a block's scores and selection are float32.  Returns [B, S, N, d_v] in
    q's type.

    Two forms, one selection, chosen by platform and shape and counted at
    trace time by the form taken (`sparse_prefill_kernel_traces` /
    `sparse_prefill_xla_traces`): on a TPU, where a head's K and V fit the
    kernel, `sparse_attention_kernel` (the Pallas forward
    ops/sparse_flash.py over a packed keep mask); elsewhere
    `sparse_attention_xla`."""
    from paddle_tpu import ops

    b, s, heads, d = q.shape
    if ops.use_pallas() and sparse_flash.fits(s, d, v.shape[-1], q.dtype):
        compile_cache.count("sparse_prefill_kernel_traces")
        return sparse_attention_kernel(q, k, v, q_idx, w_idx, k_idx, top_k,
                                       block)
    compile_cache.count("sparse_prefill_xla_traces")
    return sparse_attention_xla(q, k, v, q_idx, w_idx, k_idx, top_k, block)


def _total_order(x):
    """float32 -> int32 keys in `jax.lax.top_k`'s order, a total one: -0.0
    sorts below +0.0, where `==` calls them equal."""
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


def select_words(scores, allowed, top_k):
    """`index_select`'s selection as a packed keep mask
    (ops/sparse_flash.pack_keep) over the last axis of scores [..., S]
    (float32): int32 words [..., words_for(S)].  Built without a scatter:
    the k-th largest allowed score is the row's threshold; every allowed
    position above it is kept, and of those AT it the ones `jax.lax.top_k`
    took, which are the lowest positions (its ties go to the lower index):
    all up to the highest position it returned at the threshold.  Scores
    compare in top_k's own total order (`_total_order`)."""
    k = min(top_k, scores.shape[-1])
    with jax.named_scope("dsa.select"):
        masked = jnp.where(allowed, scores, -jnp.inf)
        vals, at = jax.lax.top_k(masked, k)
        key, ranked = _total_order(masked), _total_order(vals)
        edge = ranked[..., -1:]
        last = jnp.max(jnp.where(ranked == edge, at, -1), axis=-1,
                       keepdims=True)
        pos = jnp.arange(scores.shape[-1], dtype=jnp.int32)
        keep = allowed & ((key > edge) | ((key == edge) & (pos <= last)))
        return sparse_flash.pack_keep(keep)


def sparse_attention_kernel(q, k, v, q_idx, w_idx, k_idx, top_k,
                            block=Q_BLOCK):
    """`sparse_attention` through the Pallas kernels.  A first span whose
    queries each see at most k keys selects nothing: it is the causal
    `flash_attention`.  Every later query's selection is a row of packed
    keep bits (`select_words`, a block of `block` queries at a time over
    its span's keys), and ONE call of ops/sparse_flash.sparse_flash_forward
    attends them all over the prompt's keys."""
    from paddle_tpu.ops.flash_attention import flash_attention

    b, s, heads, d = q.shape
    scale = 1.0 / math.sqrt(d)
    block = min(block, s)
    span = -(-max(top_k, block) // block) * block
    dense = min(s, span) if min(s, span) <= top_k else 0
    outs = []
    if dense:
        outs.append(flash_attention(q[:, :dense], k[:, :dense],
                                    v[:, :dense], causal=True, scale=scale))
    if dense < s:
        width = sparse_flash.words_for(s)
        words = []
        for lo in range(dense, s, span):
            hi = min(s, lo + span)

            def one(args, k_i=k_idx[:, :hi], hi=hi):
                qib, wib, t0 = args
                w = select_words(index_scores(qib, wib, k_i),
                                 _seen(t0, block, hi)[None], top_k)
                return jnp.pad(w, ((0, 0), (0, 0), (0, width - w.shape[-1])))

            words.append(_over_blocks(one, lo, hi, block, q_idx, w_idx))
        o = sparse_flash.sparse_flash_forward(
            jnp.swapaxes(q[:, dense:], 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), jnp.concatenate(words, axis=1),
            scale=scale)
        outs.append(jnp.swapaxes(o, 1, 2))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def sparse_attention_xla(q, k, v, q_idx, w_idx, k_idx, top_k, block=Q_BLOCK):
    """`sparse_attention` in XLA: the form off a TPU, and the oracle of the
    kernel's.  A block's selection is scattered into a [B, block, keys]
    mask, and its scores over every key to its span's end, the masked
    softmax and the product with V are float32 in HBM."""
    b, s, heads, d = q.shape
    scale = 1.0 / math.sqrt(d)
    block = min(block, s)
    span = -(-max(top_k, block) // block) * block
    outs = []
    for lo in range(0, s, span):
        hi = min(s, lo + span)

        def one(args, keys=k[:, :hi], vals=v[:, :hi], k_i=k_idx[:, :hi],
                hi=hi):
            qb, qib, wib, t0 = args
            seen = _seen(t0, block, hi)
            if hi > top_k:
                at, ok = index_select(index_scores(qib, wib, k_i),
                                      seen[None], top_k)
                with jax.named_scope("dsa.select"):
                    rows = jnp.arange(block)[None, :, None]
                    picked = jnp.zeros((b, block, hi), bool).at[
                        jnp.arange(b)[:, None, None], rows, at].set(ok)
                    seen = picked & seen[None]
            else:
                seen = jnp.broadcast_to(seen[None], (b, block, hi))
            score = jnp.einsum("bqnd,bsnd->bnqs", qb, keys,
                               preferred_element_type=jnp.float32) * scale
            p = jax.nn.softmax(jnp.where(seen[:, None], score, -1e30), -1)
            return jnp.einsum("bnqs,bsnd->bqnd", p.astype(vals.dtype), vals,
                              preferred_element_type=jnp.float32
                              ).astype(q.dtype)

        outs.append(_over_blocks(one, lo, hi, block, q, q_idx, w_idx))
    return jnp.concatenate(outs, axis=1)


def _seen(t0, block, hi):
    """[block, hi]: query t0 + r sees key s <= t0 + r."""
    t = t0 + jnp.arange(block, dtype=jnp.int32)
    return jnp.arange(hi, dtype=jnp.int32)[None, :] <= t[:, None]


def _over_blocks(one, lo, hi, block, *xs):
    """`one((x_block, ..., t0))` over the blocks of `block` queries of
    lo..hi (a lax.map; the last block zero-padded) of each x [B, S, ...]:
    its results [B, hi - lo, ...]."""
    n = -(-(hi - lo) // block)

    def cut(x):
        x = x[:, lo:hi]
        if n * block > hi - lo:
            x = jnp.pad(x, [(0, 0), (0, n * block - (hi - lo))]
                        + [(0, 0)] * (x.ndim - 2))
        return jnp.moveaxis(
            x.reshape((x.shape[0], n, block) + x.shape[2:]), 1, 0)

    starts = lo + block * jnp.arange(n, dtype=jnp.int32)
    o = jax.lax.map(one, (*map(cut, xs), starts))
    return jnp.moveaxis(o, 0, 1).reshape(
        (o.shape[1], n * block) + o.shape[3:])[:, :hi - lo]


def selected_pairs(n, top_k):
    """(pairs scored, pairs selected) of a causal prompt of n tokens (traced
    int32): sum over t < n of t + 1 and of min(t + 1, k)."""
    n = jnp.asarray(n, jnp.int32)
    k = jnp.int32(top_k)
    head = jnp.minimum(n, k)
    return n * (n + 1) // 2, head * (head + 1) // 2 + (n - head) * k


# ----------------------------------------------------------------- layers

class SparseLatentAttention(LatentAttention):
    """A full layer's latent attention (the full geometry, the gate, the
    rescale) with the indexer beside it: prefill attends each query's
    selection, decode reads only the selected latent rows."""

    def __init__(self, config: DsaMoeConfig):
        super().__init__(config.geometry(FULL), gate=True,
                         lora_rescale=config.apply_mla_qkv_lora_rescale)
        self.indexer = Indexer(config)
        self.top_k = config.index_topk

    def prefill(self, n):
        """(Tensor [B, S, h], latent rows [B, S, 1, pool_width], index rows
        [B, S, 1, d_I])."""
        b, s = n.shape[0], n.shape[1]
        with jax.named_scope("mla.prefill"):
            pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            q_nope, q_rope, row, c_q = self._project(n, pos)
            q_i, w_i, k_i = self.indexer(n, c_q, pos)
            o = sparse_attention(*self._heads(q_nope, q_rope, row),
                                 q_i, w_i, k_i, self.top_k)
            out = self._out(n, o)
        return out, row[:, :, None, :], k_i[:, :, None, :]

    def decode(self, n, pool, index_pool, table, lens):
        """One new token a row: its latent row and indexer key written
        through the block table, the live keys scored, the exact top-k
        taken and only those latent rows read.  Returns (Tensor [B, 1, h],
        pool, index_pool, (indexer keys gathered a row, latent rows read
        [B])): what this layer read, counted from the gather's width and
        the selection itself."""
        c = self.config
        with jax.named_scope("mla.sparse.decode"):
            pos = (lens - 1)[:, None]
            q_nope, q_rope, row, c_q = self._project(n, pos)
            q_i, w_i, k_i = self.indexer(n, c_q, pos)
            pool = pa.paged_write_chunk(pool, row[:, :, None, :], table, pos)
            index_pool = pa.paged_write_chunk(index_pool, k_i[:, :, None, :],
                                              table, pos)
            keys = pa.paged_gather(index_pool, table)[:, 0]   # [B, P, d_I]
            live = (jnp.arange(keys.shape[1], dtype=jnp.int32)[None, :]
                    < lens[:, None])
            at, ok = index_select(index_scores(q_i, w_i, keys)[:, 0], live,
                                  self.top_k)
            w_k, w_v = self._w_kvb()
            q = self._absorb(q_nope, q_rope, w_k, row.dtype)
            q = jnp.pad(q, ((0, 0), (0, 0), (0, pool.shape[-1] - q.shape[-1])))
            with jax.named_scope("dsa.select"):
                o_lat = pa.paged_selected_attention(
                    q, pool, table, at, ok, rank=c.kv_lora_rank,
                    scale=1.0 / math.sqrt(self.q_head))
            out = self._expand(n, o_lat, w_v, row.dtype)
        return out, pool, index_pool, (keys.shape[1],
                                       jnp.sum(ok, -1, dtype=jnp.int32))


class DsaMoeDecoderLayer(nn.Layer):
    def __init__(self, config: DsaMoeConfig, index: int):
        super().__init__()
        c, h = config, config.hidden_size
        self.sliding = c.layer_types[index] == SLIDING
        self.dense = index < c.first_k_dense_replace
        self.self_attn = (
            LatentAttention(c.geometry(SLIDING), window=c.sliding_window_size,
                            gate=True, lora_rescale=c.apply_mla_qkv_lora_rescale)
            if self.sliding else SparseLatentAttention(c))
        f = c.moe_intermediate_size
        self.mlp = (SwiGLU(h, c.intermediate_size) if self.dense
                    else RoutedExperts(
                        h, f, routed=c.n_routed_experts, held=c.held,
                        top_k=c.num_experts_per_tok,
                        scale=c.routed_scaling_factor,
                        normalize=c.norm_topk_prob, scoring="sigmoid",
                        shared_width=f * c.n_shared_experts, stacked=True,
                        score_bias=True))
        self.input_layernorm = nn.RMSNorm(h, c.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(h, c.rms_norm_eps)

    def finish(self, x, attn_out, active=None):
        """Everything after the attention sublayer: (x', counts or None)."""
        a = x + attn_out
        m = self.post_attention_layernorm(a)
        if self.dense:
            return a + self.mlp(m), None
        f, counts = self.mlp(m, active)
        return a + f, counts


class DsaMoeModel(nn.Layer):
    def __init__(self, config: DsaMoeConfig):
        super().__init__()
        self.config = config
        bf16 = config.dtype == "bfloat16"

        def made(layer):
            # cast as built: the whole model in float32 first would be
            # twice what a chip sized for the bfloat16 weights holds
            if bf16:
                layer.to(dtype="bfloat16")
            return layer

        self.embed_tokens = made(nn.Embedding(config.vocab_size,
                                              config.hidden_size))
        self.layers = nn.LayerList([made(DsaMoeDecoderLayer(config, i))
                                    for i in range(config.num_hidden_layers)])
        self.norm = made(nn.RMSNorm(config.hidden_size, config.rms_norm_eps))

    def forward_prefill(self, input_ids, n_real=None):
        """The whole prompt through every layer: (hidden after the final
        norm, per-layer cache rows (full: latent and index rows; sliding:
        latent rows), expert counts summed over the expert layers).
        n_real: traced count of real (not right-padding) tokens, whose rows
        alone are counted."""
        h = self.embed_tokens(input_ids)
        active = None
        if n_real is not None:
            b, s = input_ids.shape[0], input_ids.shape[1]
            active = jnp.broadcast_to(jnp.arange(s) < n_real, (b, s)).reshape(-1)
        rows, totals = [], None
        for layer in self.layers:
            out, *kept = layer.self_attn.prefill(layer.input_layernorm(h))
            h, counts = layer.finish(h, out, active)
            rows.append(tuple(kept))
            totals = add_counts(totals, counts)
        return self.norm(h), rows, totals

    def forward(self, input_ids):
        return self.forward_prefill(input_ids)[0]


class DsaMoeForCausalLM(nn.Layer):
    def __init__(self, config: DsaMoeConfig):
        super().__init__()
        self.config = config
        self.model = DsaMoeModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)
        if config.dtype == "bfloat16":
            self.lm_head.to(dtype="bfloat16")

    def forward(self, input_ids, labels=None):
        logits = self.lm_head(self.model(input_ids))
        if labels is not None:
            loss = F.cross_entropy(
                logits.astype("float32").reshape([-1, self.config.vocab_size]),
                labels.reshape([-1]), ignore_index=-100)
            return loss, logits
        return logits

    def serving_contract(self) -> "DsaMoeServing":
        """What `serving.GenerationEngine` asks of this model."""
        return DsaMoeServing(self)


class DsaMoeServing(ServingContract):
    """The model contract (models/contract.py): TWO cache classes.  The full
    layers are a paged class with pools "latent" (the full geometry's row,
    `pool_width` lanes) and "index" (the indexer's key); the sliding layers
    a window class of `sliding_window_size` positions with one pool,
    "wlatent" (the sliding geometry's row).  `decode` takes one table a
    class, the pools as [latent, index, wlatent], each a list over its
    class's layers, and walks the layers unrolled."""

    def __init__(self, lm: DsaMoeForCausalLM):
        cfg = lm.config
        self.lm = lm
        self.max_positions = cfg.max_position_embeddings
        self.top_k = cfg.index_topk
        dtype = "bfloat16" if cfg.dtype == "bfloat16" else "float32"
        full = tuple(i for i, t in enumerate(cfg.layer_types) if t == FULL)
        sliding = tuple(i for i, t in enumerate(cfg.layer_types)
                        if t == SLIDING)
        classes = []
        if full:
            classes.append(CacheClass(full, (
                PoolSpec("latent", 1, cfg.geometry(FULL).pool_width, dtype),
                PoolSpec("index", 1, cfg.index_head_dim, dtype))))
        if sliding:
            classes.append(CacheClass(sliding, (
                PoolSpec("wlatent", 1, cfg.geometry(SLIDING).pool_width,
                         dtype),), window=cfg.sliding_window_size))
        self.spec = CacheSpec.of(classes)
        # each class's first pool in `pools`, and model layer -> (its
        # class's index, its index within the class)
        self._first = [sum(len(c.pools) for c in classes[:i])
                       for i in range(len(classes))]
        self._where = [next((c, cls.layers.index(i))
                            for c, cls in enumerate(classes) if i in cls.layers)
                       for i in range(cfg.num_hidden_layers)]
        self._n_full = len(full)

    def forward_cached(self, ids, caches, offset, n_real=None):
        if offset:
            raise NotImplementedError(
                "sparse / window latent attention prefills a whole prompt at "
                "once: no prefix to attend yet (prefix cache and chunked "
                "prefill refuse a window class at construction)")
        h, rows, totals = self.lm.model.forward_prefill(ids, n_real)
        aux = {} if totals is None else {
            "moe_prefill_assignments": totals["assignments"],
            "moe_prefill_held_assignments": totals["held"]}
        if self._n_full:
            scored, chosen = selected_pairs(
                ids.shape[1] if n_real is None else n_real, self.top_k)
            aux.update(dsa_prefill_pairs_scored=scored * self._n_full,
                       dsa_prefill_pairs_selected=chosen * self._n_full)
        return h, [tuple(Tensor(r) for r in layer) for layer in rows], aux

    def decode(self, tokens, pools, tables, lens, active=None, **kv_only):
        if kv_only:
            raise NotImplementedError(
                f"sparse / window latent attention decode has no "
                f"{sorted(kv_only)}")
        model = self.lm.model
        if not self.spec.per_class_tables:
            tables = (tables,)
        pools = [list(p) for p in pools]
        h = model.embed_tokens(Tensor(tokens))
        totals = read = None
        for layer, (c, j) in zip(model.layers, self._where):
            p, n = self._first[c], layer.input_layernorm(h)
            if layer.sliding:
                out, pools[p][j] = layer.self_attn.decode(
                    n, pools[p][j], tables[c], lens)
            else:
                out, pools[p][j], pools[p + 1][j], read = (
                    layer.self_attn.decode(n, pools[p][j], pools[p + 1][j],
                                           tables[c], lens))
            h, counts = layer.finish(h, out, active)
            totals = add_counts(totals, counts)
        # what this token step's attention read and what was live, once a
        # step and class (every layer of a class reads the same): the full
        # layers' indexer keys as gathered (the table's whole width a row)
        # and latent rows as selected, counted from the last full layer
        if active is None:
            active = jnp.ones(lens.shape, bool)
        zero = jnp.int32(0)
        live = scored = sparse = w_read = w_live = zero
        for cls, table in zip(self.spec.classes, tables):
            if cls.window is None:
                live = jnp.sum(jnp.where(active, lens, 0)).astype(jnp.int32)
                width, rows = read
                scored = jnp.sum(active, dtype=jnp.int32) * width
                sparse = jnp.sum(jnp.where(active, rows, 0)).astype(jnp.int32)
            else:
                w_read, w_live = pa.window_positions(
                    table, pa.pool_block_size(pools[-1][0]), lens, cls.window,
                    active)
        aux = {"attn_positions_read": sparse + w_read,
               "attn_positions_live": live + w_live,
               "attn_full_positions_read": sparse,
               "attn_window_positions_read": w_read,
               "attn_window_positions_live": w_live,
               "attn_indexer_positions_read": scored,
               "attn_sparse_positions_read": sparse}
        if totals is not None:
            aux.update({
                "moe_assignments": totals["assignments"],
                "moe_held_assignments": totals["held"],
                "moe_peak_expert_assignments": totals["peak"],
                "moe_experts_touched": totals["touched"],
                "moe_layer_steps": totals["layer_steps"]})
        return model.norm(h), pools, aux

    def logits(self, h):
        return self.lm.lm_head(h)


def dsa_moe_tiny(**kw) -> DsaMoeConfig:
    """A CPU-test size with every mechanism present: a dense full layer, a
    full layer with experts, three sliding layers with experts (one period);
    4 full heads over a 16-wide latent, 3 sliding heads over a 32-wide one
    of their own widths, a window of 9, an indexer of 4 heads x 16 choosing
    12 positions, 8 routed experts top-3 with the correction bias and a
    shared one."""
    base = dict(
        vocab_size=256, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=48, num_hidden_layers=5,
        layer_types=(FULL, FULL, SLIDING, SLIDING, SLIDING),
        num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_theta=10000.0, swa_num_attention_heads=3, swa_q_lora_rank=24,
        swa_kv_lora_rank=32, swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
        swa_v_head_dim=16, swa_rope_theta=100.0, sliding_window_size=9,
        index_n_heads=4, index_head_dim=16, index_topk=12,
        n_routed_experts=8, num_experts_per_tok=3, max_position_embeddings=512,
        dtype="float32")
    base.update(kw)
    return DsaMoeConfig(**base)
