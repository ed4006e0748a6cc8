"""Latent-attention decoder with routed experts, a shared expert and sandwich
norms (the block of openPangu-Ultra-MoE / DeepSeek-V3's family).

One layer, from the published config's keys (h hidden, N heads, r_q / r the
query / key-value latent ranks, d_n / d_r the no-position and rope parts of
a query or key head, d_v the value head; rms(x) = x / sqrt(mean(x^2) + eps)):

    n      = rms(x) g_in
    c_q    = rms(n W_qa) g_qa                                  [r_q]
    q_i    = c_q W_qb -> (q_nope_i [d_n], rope(q_rope_i) [d_r])   per head
    (c', k_r) = n W_kva                     [r], [d_r]: ONE per token
    c      = rms(c') g_kva ;  k_rope = rope(k_r)   <- the cache row (c, k_rope)
    (k_nope_i, v_i) = c W_kvb                       [d_n], [d_v]  per head
    p_i    = causal softmax((q_nope_i.k_nope_i + q_rope_i.k_rope)/sqrt(d_n+d_r))
    a      = x + rms(concat_i(p_i v_i) W_o) g_post_attn      (sandwich norm)
    m      = rms(a) g_pre_mlp
    dense layer:   f = W_d(silu(W_g m) * (W_u m))
    expert layer:  s = sigmoid(m W_r) in float32;  T = top-k(s);
                   w_e = scale * s_e / sum_{e' in T} s_e'
                   f = sum_{e in T} w_e E_e(m) + E_shared(m)
    x'     = a + rms(f) g_post_mlp

Attention has the two paths deployments run.  PREFILL materialises per-head
K and V from `c` and runs the flash kernel with q/k width d_n + d_r and v
width d_v (ops/flash_attention.py takes the two widths); it writes (c,
k_rope), r + d_r values a token, to the cache.  DECODE runs the absorbed
form against the paged latent pool: q~_i = q_nope_i W_kvb^K_i [r], score =
q~_i.c + q_rope_i.k_rope, output (sum_s p c) W_kvb^V_i — all N query heads
read one shared row, whose first r lanes are also the value.  On a TPU that
read is the Pallas kernel `paged_decode` in its shared-row case: each row's
own live pages straight from the pool, one copy a page
(ops/paged_attention.paged_shared_row_attention; the pool's row is
`pool_width` wide, r + d_r rounded up to whole 128-lane rows).

The routed-expert layer (`routed_experts`, models/experts.py, shared with
models/window_moe.py) is DROPLESS and is told which contiguous range of
experts it holds (`held = (first, count)`): it routes over all
`n_routed_experts`, normalises over the k chosen wherever they live,
computes only its own experts' part (plus the shared expert) and passes
that partial result on — one chip's share of an expert-parallel layer,
without the exchange.  Nothing stands in for the absent chips.

Rope pairs adjacent lanes (2i, 2i+1), as ops/paged_attention.rope_rotate_chunk
does for every model here; no rope scaling.  The angles of the positions a
program touches are computed inside it (`_rope_at`): a table over all 131,072
positions would be 33 MB of constants in every compiled program.  The extra
next-token-prediction module of the published model is not part of the main
model and is not built.

The router's scores (`route`) and the decode attention's softmax
(`absorbed_attention`) are computed in float32, as the published inference
code has them; both are functions of their own so that a comparison can hand
them the reference's inputs (perfbench/families/mla_moe.py,
chip_smoke.py --mla-moe-logits).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu._core.tensor import Tensor
from paddle_tpu.models.contract import CacheSpec, PoolSpec, ServingContract
# the expert layer is shared with models/window_moe.py (one implementation);
# `route`, `routed_experts` and `EXPERT_TILE` stay importable from here
from paddle_tpu.models.experts import (EXPERT_TILE, RoutedExperts,  # noqa: F401
                                       SwiGLU, add_counts, route,
                                       routed_experts)
from paddle_tpu.ops import paged_attention as pa

__all__ = ["MlaMoeConfig", "MlaMoeForCausalLM", "MlaMoeModel", "route",
           "routed_experts", "absorbed_attention", "mla_moe_tiny"]

@dataclass
class MlaMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432          # the leading dense layers' FFN
    moe_intermediate_size: int = 2048       # one expert's FFN
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3          # leading dense layers
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256             # the router's outputs
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    sandwich_norm: bool = True
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25600000.0
    dtype: str = "bfloat16"
    # (first, count): the contiguous range of routed experts THIS model
    # holds and computes; None holds all of them
    held_experts: tuple | None = None

    @property
    def held(self) -> tuple:
        return self.held_experts or (0, self.n_routed_experts)

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """A token's row as the POOL holds it: `latent_width` rounded up to
        whole 128-lane rows (576 -> 640, the last 64 lanes zero).  Width-
        minor, a TPU's HBM tiling pads the row to that anyway, and Mosaic
        takes a page by DMA only where the row is whole lane tiles (a slice
        of a 576-wide ref is refused); rounded here, the pool is one
        `ops.paged_attention.reads_own_pages` admits as it admits a K pool
        of one head."""
        return -(-self.latent_width // 128) * 128


# ------------------------------------------------------------------ layers

class LatentAttention(nn.Layer):
    def __init__(self, config: MlaMoeConfig):
        super().__init__()
        c = config
        self.config = c
        self.q_head = c.qk_nope_head_dim + c.qk_rope_head_dim
        self.q_a_proj = nn.Linear(c.hidden_size, c.q_lora_rank, bias_attr=False)
        self.q_a_layernorm = nn.RMSNorm(c.q_lora_rank, c.rms_norm_eps)
        self.q_b_proj = nn.Linear(c.q_lora_rank,
                                  c.num_attention_heads * self.q_head,
                                  bias_attr=False)
        self.kv_a_proj = nn.Linear(c.hidden_size, c.latent_width,
                                   bias_attr=False)
        self.kv_a_layernorm = nn.RMSNorm(c.kv_lora_rank, c.rms_norm_eps)
        self.kv_b_proj = nn.Linear(
            c.kv_lora_rank,
            c.num_attention_heads * (c.qk_nope_head_dim + c.v_head_dim),
            bias_attr=False)
        self.o_proj = nn.Linear(c.num_attention_heads * c.v_head_dim,
                                c.hidden_size, bias_attr=False)

    def _project(self, n, pos):
        """n: Tensor [B, T, h]; pos [B, T].  Returns raw arrays: q_nope
        [B, T, N, d_n], q_rope [B, T, N, d_r] (rotated) and the cache row
        [B, T, pool_width] = (rms(c') g, rope(k_r), zeros)."""
        c = self.config
        b, t = n.shape[0], n.shape[1]
        cos, sin = _rope_at(pos.reshape(-1), c.qk_rope_head_dim, c.rope_theta)
        at = jnp.arange(b * t, dtype=jnp.int32).reshape(b, t)
        q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(n)))._value
        q = q.reshape(b, t, c.num_attention_heads, self.q_head)
        q_nope, q_rope = q[..., :c.qk_nope_head_dim], q[..., c.qk_nope_head_dim:]
        q_rope = pa.rope_rotate_chunk(q_rope, cos, sin, at)
        kv_a = self.kv_a_proj(n)._value
        latent = self.kv_a_layernorm(Tensor(kv_a[..., :c.kv_lora_rank]))._value
        k_rope = pa.rope_rotate_chunk(
            kv_a[:, :, None, c.kv_lora_rank:], cos, sin, at)[:, :, 0]
        pad = jnp.zeros((b, t, c.pool_width - c.latent_width), latent.dtype)
        return q_nope, q_rope, jnp.concatenate([latent, k_rope, pad], axis=-1)

    def _w_kvb(self):
        c = self.config
        w = self.kv_b_proj.weight._value.reshape(
            c.kv_lora_rank, c.num_attention_heads,
            c.qk_nope_head_dim + c.v_head_dim)
        return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]

    def prefill(self, n):
        """Causal self-attention over a whole prompt, per-head K/V
        materialised from the latent: (Tensor [B, S, h] before the
        sandwich norm, cache rows [B, S, 1, pool_width])."""
        c = self.config
        b, s = n.shape[0], n.shape[1]
        heads = c.num_attention_heads
        with jax.named_scope("mla.prefill"):
            pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            q_nope, q_rope, row = self._project(n, pos)
            latent = row[..., :c.kv_lora_rank]
            k_rope = row[..., c.kv_lora_rank:c.latent_width]
            kv = self.kv_b_proj(Tensor(latent))._value.reshape(
                b, s, heads, c.qk_nope_head_dim + c.v_head_dim)
            k = jnp.concatenate(
                [kv[..., :c.qk_nope_head_dim],
                 jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, heads, c.qk_rope_head_dim))], axis=-1)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            o = _causal_attention(q, k, kv[..., c.qk_nope_head_dim:])
            out = self.o_proj(Tensor(o.reshape(b, s, heads * c.v_head_dim)))
        return out, row[:, :, None, :]

    def decode(self, n, pool, tables, lens):
        """One new token a row against the paged latent pool, absorbed
        form.  n: Tensor [B, 1, h]; pool [num_blocks, 1, bs, pool_width];
        lens [B] INCLUDING this token.  Returns (Tensor [B, 1, h], pool)."""
        c = self.config
        with jax.named_scope("mla.decode"):
            pos = (lens - 1)[:, None]
            q_nope, q_rope, row = self._project(n, pos)
            pool = pa.paged_write_chunk(pool, row[:, :, None, :], tables, pos)
            w_k, w_v = self._w_kvb()                    # [r, N, d_n], [r, N, d_v]
            q_lat = jnp.einsum("bnd,rnd->bnr", q_nope[:, 0], w_k,
                               preferred_element_type=jnp.float32)
            q = jnp.concatenate([q_lat.astype(row.dtype), q_rope[:, 0]], -1)
            o_lat = absorbed_attention(q, pool, tables, lens,
                                       rank=c.kv_lora_rank, width=self.q_head)
            o = jnp.einsum("bnr,rnd->bnd", o_lat.astype(row.dtype), w_v,
                           preferred_element_type=jnp.float32).astype(row.dtype)
            out = self.o_proj(Tensor(o.reshape(o.shape[0], 1, -1)))
        return out, pool


def _rope_at(positions, dim, theta):
    """cos and sin [P, dim / 2] (float32) of the rope angles of the int32
    positions [P]: rows of the table `rope_rotate_chunk` indexes."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def absorbed_attention(q, pool, tables, lens, *, rank, width):
    """Decode attention over the paged latent pool, absorbed form: q [B, N,
    r + d_r] (q~ = q_nope W_kvb^K beside the rotated rope part), pool
    [num_blocks, 1, bs, r + d_r or wider] (`pool_width`: the lanes past q's
    are zero, and meet zeros), tables [B, W], lens [B] -> sum_s p(s) c(s),
    [B, N, r] float32 (the caller applies W_kvb^V).  All N heads read one
    shared row, whose first `rank` lanes are also the value.  Scores
    (divided by sqrt(width), the q/k head width), their maximum, exponent
    and sum are float32; the probabilities meet the rows in the rows' type
    (bfloat16 passes of the matrix unit, float32 sums).  Which form runs it
    (the Pallas kernel over each row's own pages, or XLA's gather and two
    einsums) is `ops.paged_attention.paged_shared_row_attention`'s to say,
    by what it sees in the pool."""
    if pool.shape[-1] > q.shape[-1]:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pool.shape[-1] - q.shape[-1])))
    return pa.paged_shared_row_attention(q, pool, tables, lens, rank=rank,
                                         scale=1.0 / math.sqrt(width))


def _causal_attention(q, k, v, block=512):
    """Causal softmax(q k^T / sqrt(width)) v for q/k [B, S, N, d] and v
    [B, S, N, d_v]: the flash kernel on a TPU; elsewhere plain XLA in query
    blocks (never an [S, S] score matrix for more than `block` queries)."""
    from paddle_tpu import ops as _ops

    if _ops.use_pallas():
        return _ops.flash_attention(q, k, v, causal=True)
    s = q.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    outs = []
    for at in range(0, s, block):
        qb = q[:, at:at + block]
        upto = min(s, at + block)
        score = jnp.einsum("bqnd,bsnd->bnqs", qb, k[:, :upto],
                           preferred_element_type=jnp.float32) * scale
        ok = (jnp.arange(upto)[None, :]
              <= (at + jnp.arange(qb.shape[1]))[:, None])
        p = jax.nn.softmax(jnp.where(ok, score, -1e30), axis=-1)
        outs.append(jnp.einsum("bnqs,bsnd->bqnd", p.astype(v.dtype),
                               v[:, :upto],
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


class MlaMoeDecoderLayer(nn.Layer):
    """One layer: latent attention, then a dense FFN (the leading layers) or
    routed experts; with `sandwich_norm`, a norm after each sublayer too."""

    def __init__(self, config: MlaMoeConfig, dense: bool):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.dense = dense
        self.self_attn = LatentAttention(config)
        f = config.moe_intermediate_size
        self.mlp = (SwiGLU(h, config.intermediate_size) if dense
                    else RoutedExperts(
                        h, f, routed=config.n_routed_experts, held=config.held,
                        top_k=config.num_experts_per_tok,
                        scale=config.routed_scaling_factor,
                        normalize=config.norm_topk_prob, scoring="sigmoid",
                        shared_width=f * config.n_shared_experts))
        self.input_layernorm = nn.RMSNorm(h, eps)
        self.pre_mlp_layernorm = nn.RMSNorm(h, eps)
        if config.sandwich_norm:
            self.post_attn_norm = nn.RMSNorm(h, eps)
            self.post_mlp_norm = nn.RMSNorm(h, eps)
        else:
            self.post_attn_norm = self.post_mlp_norm = None

    def finish(self, x, attn_out, active=None):
        """Everything after the attention sublayer: (x', counts or None)."""
        if self.post_attn_norm is not None:
            attn_out = self.post_attn_norm(attn_out)
        a = x + attn_out
        m = self.pre_mlp_layernorm(a)
        counts = None
        if self.dense:
            f = self.mlp(m)
        else:
            f, counts = self.mlp(m, active)
        if self.post_mlp_norm is not None:
            f = self.post_mlp_norm(f)
        return a + f, counts


class MlaMoeModel(nn.Layer):
    def __init__(self, config: MlaMoeConfig):
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
        self.layers = nn.LayerList([
            made(MlaMoeDecoderLayer(config, i < config.first_k_dense_replace))
            for i in range(config.num_hidden_layers)])
        self.norm = made(nn.RMSNorm(config.hidden_size, config.rms_norm_eps))

    def forward_prefill(self, input_ids, n_real=None):
        """The whole prompt through every layer: (hidden after the final
        norm, per-layer cache rows [B, S, 1, r + d_r], expert counts summed
        over the expert layers).  n_real: traced count of real (not
        right-padding) tokens, whose rows alone are counted."""
        h = self.embed_tokens(input_ids)
        active = None
        if n_real is not None:
            b, s = input_ids.shape[0], input_ids.shape[1]
            active = jnp.broadcast_to(jnp.arange(s) < n_real, (b, s)).reshape(-1)
        rows, totals = [], None
        for layer in self.layers:
            out, row = layer.self_attn.prefill(layer.input_layernorm(h))
            h, counts = layer.finish(h, out, active)
            rows.append(row)
            totals = add_counts(totals, counts)
        return self.norm(h), rows, totals

    def forward(self, input_ids):
        return self.forward_prefill(input_ids)[0]


class MlaMoeForCausalLM(nn.Layer):
    def __init__(self, config: MlaMoeConfig):
        super().__init__()
        self.config = config
        self.model = MlaMoeModel(config)
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

    def serving_contract(self) -> "MlaMoeServing":
        """What `serving.GenerationEngine` asks of this model."""
        return MlaMoeServing(self)


class MlaMoeServing(ServingContract):
    """The model contract (models/contract.py): ONE pool a layer, a token's
    row the latent and the rope key (r + d_r values, rounded up to whole
    128-lane rows: `MlaMoeConfig.pool_width`), no V pool.  The
    layers are not alike (a dense FFN ahead of the expert layers), so the
    decode step walks them unrolled and the pools are carried as a list."""

    def __init__(self, lm: MlaMoeForCausalLM):
        cfg = lm.config
        self.lm = lm
        self.max_positions = cfg.max_position_embeddings
        self.spec = CacheSpec(cfg.num_hidden_layers, (PoolSpec(
            "latent", 1, cfg.pool_width,
            "bfloat16" if cfg.dtype == "bfloat16" else "float32"),))

    def forward_cached(self, ids, caches, offset, n_real=None):
        if offset:
            raise NotImplementedError(
                "latent attention prefills a whole prompt at once: no "
                "prefix to attend yet (prefix cache and chunked prefill "
                "refuse this model's pool at construction)")
        h, rows, totals = self.lm.model.forward_prefill(ids, n_real)
        aux = {} if totals is None else {
            "moe_prefill_assignments": totals["assignments"],
            "moe_prefill_held_assignments": totals["held"]}
        return h, [(Tensor(r),) for r in rows], aux

    def decode(self, tokens, pools, tables, lens, active=None, **kv_only):
        if kv_only:
            raise NotImplementedError(
                f"latent-attention decode has no {sorted(kv_only)}")
        model = self.lm.model
        h = model.embed_tokens(Tensor(tokens))
        new, totals = [], None
        for layer, pool in zip(model.layers, pools[0]):
            out, pool = layer.self_attn.decode(layer.input_layernorm(h),
                                               pool, tables, lens)
            h, counts = layer.finish(h, out, active)
            new.append(pool)
            totals = add_counts(totals, counts)
        # what this token step's attention read and what was live, once a
        # step (every layer reads the same pages; `absorbed_attention`'s
        # XLA form gathers the table's whole width: no ladder)
        pool = pools[0][0]
        read, live = pa.attn_positions(tables, pa.pool_block_size(pool), lens,
                                       active, pool=pool, ladder=False)
        aux = {"attn_positions_read": read, "attn_positions_live": live}
        if totals is not None:
            aux.update({
                "moe_assignments": totals["assignments"],
                "moe_held_assignments": totals["held"],
                "moe_peak_expert_assignments": totals["peak"],
                "moe_experts_touched": totals["touched"],
                "moe_layer_steps": totals["layer_steps"]})
        return model.norm(h), [new], aux

    def logits(self, h):
        return self.lm.lm_head(h)


def mla_moe_tiny(**kw) -> MlaMoeConfig:
    """A CPU-test size with every mechanism present: 1 dense + 2 expert
    layers, 8 routed experts top-2 and a shared one, 4 latent heads."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=48, num_hidden_layers=3,
                first_k_dense_replace=1, num_attention_heads=4,
                q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
                num_experts_per_tok=2, max_position_embeddings=512,
                rope_theta=10000.0, dtype="float32")
    base.update(kw)
    return MlaMoeConfig(**base)
