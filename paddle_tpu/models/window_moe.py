"""Decoder with window and full attention mixed, head counts by layer type, a
per-head output gate, and routed experts beside a shared one (the block of
Laguna-S-2.1, poolside's family).

Layer `l` has a type t(l) from `layer_types` ("full_attention" |
"sliding_attention"), N_l query heads from `num_attention_heads_per_layer`,
`num_key_value_heads` K/V heads of `head_dim`, and an FFN kind from
`mlp_layer_types` ("dense" | "sparse"); rms(x) = x / sqrt(mean(x^2) + eps):

    n   = rms(x) g_in
    q   = n W_q [N_l x d]     k = n W_k [Nkv x d]     v = n W_v [Nkv x d]
    g   = sigmoid(n W_g) [N_l]               one gate a query head (headwise
                                             gated attention, arXiv:2505.06708)
    q,k = rope_t(q, k, position)             by layer type: `rope_parameters`
    a_h = softmax_j(q_h . k_{h // (N_l / Nkv), j} / sqrt(d) + mask_t(i, j)) v
          mask: j <= i;  sliding layers also j > i - W  (a query sees itself
          and the W - 1 positions before it)
    x   = x + concat_h(g_h a_h) W_o
    m   = rms(x) g_post
    dense:   x = x + W_d(silu(W_g m) * (W_u m))
    sparse:  s = softmax(m W_r) over ALL experts, float32;  T = top-k(s);
             w_e = scale * s_e / sum_T s;
             x = x + sum_{e in T, e held} w_e E_e(m) + E_shared(m)
    logits = rms(x_L) g W_head

Rope by layer type (`rope_inv_freq`): "default" is plain rope; "yarn" blends
each frequency pair between its own and `factor` times slower by where its
wavelength stands against `original_max_position_embeddings`, and multiplies
cos and sin by `attention_factor`.  `partial_rotary_factor` rotates only the
FIRST d x factor lanes of a head; the rest pass through unrotated and
unscaled.  Pairs are adjacent lanes (2i, 2i+1), as ops/paged_attention
.rope_rotate_chunk has them for every model here.  The angles of the
positions a program touches are computed inside it from the d/2 frequencies
(a table over 1,048,576 positions would be 268 MB of constants in every
compiled program).

THE CACHE has two lifetimes (models/contract.py): full layers keep every
position (a PAGED class, pools "k" / "v"), sliding layers only the last W (a
WINDOW class, pools "wk" / "wv": a ring of blocks a slot).  Prefill runs the
flash kernel, with `window=` on the sliding layers; decode reads the paged
pools through the request's block table and the rings through the slot's
ring table (ops/paged_attention.py).  The layers are not alike, so the decode
step walks them unrolled and the pools are carried as lists.

The expert layer is models/experts.py's, shared with models/mla_moe.py, here
with softmax scoring; `held_experts = (first, count)` is this chip's share.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu._core.tensor import Tensor
from paddle_tpu.models.contract import (CacheClass, CacheSpec, PoolSpec,
                                        ServingContract)
from paddle_tpu.models.experts import RoutedExperts, SwiGLU, add_counts
from paddle_tpu.ops import paged_attention as pa

__all__ = ["WindowMoeConfig", "WindowMoeForCausalLM", "WindowMoeModel",
           "rope_inv_freq", "window_moe_tiny", "FULL", "SLIDING"]

FULL, SLIDING = "full_attention", "sliding_attention"


def _laguna_rope():
    return {
        FULL: {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 128.0,
               "original_max_position_embeddings": 8192, "beta_fast": 32.0,
               "beta_slow": 1.0, "attention_factor": 1.4852030263919618,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000.0,
                  "partial_rotary_factor": 1.0},
    }


@dataclass
class WindowMoeConfig:
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288          # a dense layer's FFN
    moe_intermediate_size: int = 1024       # one routed expert's FFN
    shared_expert_intermediate_size: int = 1024
    num_hidden_layers: int = 48
    # per layer; None: full, sliding, sliding, sliding repeated / 48 heads on
    # full layers and 72 on sliding ones / layer 0 dense, the rest sparse
    layer_types: tuple | None = None
    num_attention_heads_per_layer: tuple | None = None
    mlp_layer_types: tuple | None = None
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    num_experts: int = 256                  # the router's outputs
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    router_scoring: str = "softmax"
    rope_parameters: dict = field(default_factory=_laguna_rope)
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    # (first, count): the contiguous range of routed experts THIS model
    # holds and computes; None holds all of them
    held_experts: tuple | None = None

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = tuple(FULL if i % 4 == 0 else SLIDING
                                     for i in range(n))
        if self.num_attention_heads_per_layer is None:
            self.num_attention_heads_per_layer = tuple(
                48 if t == FULL else 72 for t in self.layer_types)
        if self.mlp_layer_types is None:
            self.mlp_layer_types = tuple("dense" if i == 0 else "sparse"
                                         for i in range(n))
        for name in ("layer_types", "num_attention_heads_per_layer",
                     "mlp_layer_types"):
            value = tuple(getattr(self, name))
            setattr(self, name, value)
            if len(value) != n:
                raise ValueError(f"{name} has {len(value)} entries for "
                                 f"{n} layers")
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"layer_types knows {FULL!r} and {SLIDING!r}: "
                             f"{sorted(set(self.layer_types))}")
        if set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError("mlp_layer_types knows 'dense' and 'sparse'")
        for heads in self.num_attention_heads_per_layer:
            if heads % self.num_key_value_heads:
                raise ValueError(f"{heads} query heads do not group over "
                                 f"{self.num_key_value_heads} K/V heads")

    @property
    def held(self) -> tuple:
        return self.held_experts or (0, self.num_experts)


# ---------------------------------------------------------------------- rope

def rope_inv_freq(params: dict, head_dim: int):
    """(inv_freq float32 [rotary / 2], attention_factor, rotary lanes) of one
    layer type's `rope_parameters` entry.  "default": inv_freq_i =
    theta^(-2i/r) over the r = head_dim x partial_rotary_factor rotated
    lanes.  "yarn": with pos_i = theta^(2i/r) and corr(b) = r ln(original /
    (2 pi b)) / (2 ln theta), low = floor(corr(beta_fast)), high =
    ceil(corr(beta_slow)), ramp_i = clip((i - low) / (high - low), 0, 1):
    inv_freq_i = (1 - ramp_i) / pos_i + ramp_i / (factor pos_i); cos and sin
    are multiplied by `attention_factor` (0.1 ln(factor) + 1 where the
    config gives none).  Worked in float64 on the host, rounded once."""
    kind = params.get("rope_type", "default")
    rot = int(round(head_dim * float(params.get("partial_rotary_factor", 1.0))))
    if rot < 2 or rot % 2 or rot > head_dim:
        raise ValueError(f"partial_rotary_factor leaves {rot} of {head_dim} "
                         "lanes: an even count within the head is needed")
    theta = float(params["rope_theta"])
    i = np.arange(rot // 2, dtype=np.float64)
    pos = theta ** (2.0 * i / rot)
    if kind == "default":
        return (1.0 / pos).astype(np.float32), 1.0, rot
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: 'default' and 'yarn' are built")
    factor = float(params["factor"])
    orig = float(params["original_max_position_embeddings"])

    def corr(beta):
        return rot * math.log(orig / (2 * math.pi * beta)) / (2 * math.log(theta))

    low = max(math.floor(corr(float(params.get("beta_fast", 32.0)))), 0)
    high = min(math.ceil(corr(float(params.get("beta_slow", 1.0)))), rot - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = (1.0 - ramp) / pos + ramp / (factor * pos)
    af = params.get("attention_factor")
    af = 0.1 * math.log(factor) + 1.0 if af is None else float(af)
    return inv.astype(np.float32), af, rot


def _rope_at(positions, inv_freq, attention_factor):
    """cos and sin [P, rot / 2] (float32) of the int32 positions [P], times
    the attention factor: rows of the table `rope_rotate_chunk` indexes."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    f = jnp.float32(attention_factor)
    return jnp.cos(ang) * f, jnp.sin(ang) * f


def _rotate(x, cos, sin, at, rot):
    """x [B, T, n, d]: its first `rot` lanes rotated by the rows `at` [B, T]
    of cos / sin, the rest untouched."""
    if rot == x.shape[-1]:
        return pa.rope_rotate_chunk(x, cos, sin, at)
    return jnp.concatenate(
        [pa.rope_rotate_chunk(x[..., :rot], cos, sin, at), x[..., rot:]], -1)


# ----------------------------------------------------------------- attention

def _causal_attention(q, k, v, window=None, block=512):
    """Causal (and windowed) grouped attention for q [B, S, N, d] and k / v
    [B, S, Nkv, d]: the flash kernel on a TPU; elsewhere plain XLA in query
    blocks over the keys a block can see (never an [S, S] score matrix)."""
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
    return jnp.concatenate(outs, axis=1).reshape(b, s, n, d).astype(q.dtype)


class GatedAttention(nn.Layer):
    """One layer's attention: grouped heads, its layer type's rope and mask,
    and a sigmoid gate a query head on the attention output."""

    def __init__(self, config: WindowMoeConfig, index: int):
        super().__init__()
        c = config
        self.heads = c.num_attention_heads_per_layer[index]
        self.kv_heads, self.head_dim = c.num_key_value_heads, c.head_dim
        self.sliding = c.layer_types[index] == SLIDING
        self.window = c.sliding_window if self.sliding else None
        self.scope = "attn.window" if self.sliding else "attn.full"
        self.inv_freq, self.attention_factor, self.rotary = rope_inv_freq(
            c.rope_parameters[c.layer_types[index]], c.head_dim)
        width = (self.heads + 2 * self.kv_heads) * c.head_dim
        self.qkv_proj = nn.Linear(c.hidden_size, width, bias_attr=False)
        self.gate_proj = nn.Linear(c.hidden_size, self.heads, bias_attr=False)
        self.o_proj = nn.Linear(self.heads * c.head_dim, c.hidden_size,
                                bias_attr=False)

    def _project(self, n, pos):
        """n: Tensor [B, T, h]; pos [B, T] -> q [B, T, N, d] and k, v
        [B, T, Nkv, d] (raw arrays), q and k rotated."""
        b, t = n.shape[0], n.shape[1]
        d, nq, nkv = self.head_dim, self.heads, self.kv_heads
        qkv = self.qkv_proj(n)._value
        q = qkv[..., :nq * d].reshape(b, t, nq, d)
        k = qkv[..., nq * d:(nq + nkv) * d].reshape(b, t, nkv, d)
        v = qkv[..., (nq + nkv) * d:].reshape(b, t, nkv, d)
        cos, sin = _rope_at(pos.reshape(-1), self.inv_freq,
                            self.attention_factor)
        at = jnp.arange(b * t, dtype=jnp.int32).reshape(b, t)
        return (_rotate(q, cos, sin, at, self.rotary),
                _rotate(k, cos, sin, at, self.rotary), v)

    def _gated_out(self, n, o):
        """o [B, T, N, d] (raw) -> Tensor [B, T, h]: each head times its
        gate (float32 sigmoid of the normed input's projection), then W_o."""
        with jax.named_scope("attn.gate"):
            g = jax.nn.sigmoid(self.gate_proj(n)._value.astype(jnp.float32))
            o = (o.astype(jnp.float32) * g[..., None]).astype(o.dtype)
        return self.o_proj(Tensor(o.reshape(o.shape[0], o.shape[1], -1)))

    def prefill(self, n):
        """Self-attention over a whole prompt: (Tensor [B, S, h], k rows,
        v rows [B, S, Nkv, d]: what the cache keeps, k rotated)."""
        b, s = n.shape[0], n.shape[1]
        with jax.named_scope(self.scope + ".prefill"):
            pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            q, k, v = self._project(n, pos)
            o = _causal_attention(q, k, v, self.window)
        return self._gated_out(n, o), k, v

    def decode(self, n, k_pool, v_pool, table, lens):
        """One new token a row.  n: Tensor [B, 1, h]; the pools and `table`
        of this layer's cache class (a block table, or the slots' rings);
        lens [B] INCLUDING this token.  Returns (Tensor, k_pool, v_pool)."""
        with jax.named_scope(self.scope + ".decode"):
            pos = (lens - 1)[:, None]
            q, k, v = self._project(n, pos)
            if self.sliding:
                k_pool = pa.ring_write_chunk(k_pool, k, table, pos)
                v_pool = pa.ring_write_chunk(v_pool, v, table, pos)
                o = pa.paged_window_attention(q, k_pool, v_pool, table, lens,
                                              self.window)
            else:
                k_pool = pa.paged_write_chunk(k_pool, k, table, pos)
                v_pool = pa.paged_write_chunk(v_pool, v, table, pos)
                o = pa.paged_chunk_attention(q, k_pool, v_pool, table, lens)
        return self._gated_out(n, o), k_pool, v_pool


class WindowMoeDecoderLayer(nn.Layer):
    def __init__(self, config: WindowMoeConfig, index: int):
        super().__init__()
        c, h = config, config.hidden_size
        self.dense = c.mlp_layer_types[index] == "dense"
        self.self_attn = GatedAttention(c, index)
        self.mlp = (SwiGLU(h, c.intermediate_size) if self.dense
                    else RoutedExperts(
                        h, c.moe_intermediate_size, routed=c.num_experts,
                        held=c.held, top_k=c.num_experts_per_tok,
                        scale=c.moe_routed_scaling_factor,
                        normalize=c.norm_topk_prob, scoring=c.router_scoring,
                        shared_width=c.shared_expert_intermediate_size,
                        stacked=True))
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


class WindowMoeModel(nn.Layer):
    def __init__(self, config: WindowMoeConfig):
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
        self.layers = nn.LayerList([made(WindowMoeDecoderLayer(config, i))
                                    for i in range(config.num_hidden_layers)])
        self.norm = made(nn.RMSNorm(config.hidden_size, config.rms_norm_eps))

    def forward_prefill(self, input_ids, n_real=None):
        """The whole prompt through every layer: (hidden after the final
        norm, per-layer (k, v) cache rows [B, S, Nkv, d], expert counts
        summed over the expert layers).  n_real: traced count of real (not
        right-padding) tokens, whose rows alone are counted."""
        h = self.embed_tokens(input_ids)
        active = None
        if n_real is not None:
            b, s = input_ids.shape[0], input_ids.shape[1]
            active = jnp.broadcast_to(jnp.arange(s) < n_real, (b, s)).reshape(-1)
        rows, totals = [], None
        for layer in self.layers:
            out, k, v = layer.self_attn.prefill(layer.input_layernorm(h))
            h, counts = layer.finish(h, out, active)
            rows.append((k, v))
            totals = add_counts(totals, counts)
        return self.norm(h), rows, totals

    def forward(self, input_ids):
        return self.forward_prefill(input_ids)[0]


class WindowMoeForCausalLM(nn.Layer):
    def __init__(self, config: WindowMoeConfig):
        super().__init__()
        self.config = config
        self.model = WindowMoeModel(config)
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

    def serving_contract(self) -> "WindowMoeServing":
        """What `serving.GenerationEngine` asks of this model."""
        return WindowMoeServing(self)


class WindowMoeServing(ServingContract):
    """The model contract (models/contract.py): TWO cache classes.  The full
    layers are a paged class with pools "k" and "v"; the sliding layers a
    window class of `sliding_window` positions with pools "wk" and "wv"
    (rings).  `decode` takes one table a class and the pools as
    [k, v, wk, wv], each a list over its class's layers."""

    def __init__(self, lm: WindowMoeForCausalLM):
        cfg = lm.config
        self.lm = lm
        self.max_positions = cfg.max_position_embeddings
        dtype = "bfloat16" if cfg.dtype == "bfloat16" else "float32"
        full = tuple(i for i, t in enumerate(cfg.layer_types) if t == FULL)
        sliding = tuple(i for i, t in enumerate(cfg.layer_types)
                        if t == SLIDING)

        def pools(*names):
            return tuple(PoolSpec(n, cfg.num_key_value_heads, cfg.head_dim,
                                  dtype) for n in names)

        classes = []
        if full:
            classes.append(CacheClass(full, pools("k", "v")))
        if sliding:
            classes.append(CacheClass(sliding, pools("wk", "wv"),
                                      window=cfg.sliding_window))
        self.spec = CacheSpec.of(classes)
        # model layer -> (its class's index, its index within the class)
        self._where = [next((c, cls.layers.index(i))
                            for c, cls in enumerate(classes) if i in cls.layers)
                       for i in range(cfg.num_hidden_layers)]

    def forward_cached(self, ids, caches, offset, n_real=None):
        if offset:
            raise NotImplementedError(
                "window / full attention prefills a whole prompt at once: "
                "no prefix to attend yet (prefix cache and chunked prefill "
                "refuse a window class at construction)")
        h, rows, totals = self.lm.model.forward_prefill(ids, n_real)
        aux = {} if totals is None else {
            "moe_prefill_assignments": totals["assignments"],
            "moe_prefill_held_assignments": totals["held"]}
        return h, [(Tensor(k), Tensor(v)) for k, v in rows], aux

    def decode(self, tokens, pools, tables, lens, active=None, **kv_only):
        if kv_only:
            raise NotImplementedError(
                f"window / full attention decode has no {sorted(kv_only)}")
        model = self.lm.model
        if not self.spec.per_class_tables:
            tables = (tables,)
        # pools[2c], pools[2c + 1]: class c's K and V lists, rebound layer
        # by layer as the walk writes them
        pools = [list(p) for p in pools]
        h = model.embed_tokens(Tensor(tokens))
        totals = None
        for layer, (c, j) in zip(model.layers, self._where):
            out, pools[2 * c][j], pools[2 * c + 1][j] = layer.self_attn.decode(
                layer.input_layernorm(h), pools[2 * c][j], pools[2 * c + 1][j],
                tables[c], lens)
            h, counts = layer.finish(h, out, active)
            totals = add_counts(totals, counts)
        # what this token step's attention read and what was live, once a
        # step and class (every layer of a class reads the same)
        bs = pa.pool_block_size(pools[0][0])
        f_read = f_live = w_read = w_live = jnp.int32(0)
        for c, (cls, table) in enumerate(zip(self.spec.classes, tables)):
            if cls.window is None:
                f_read, f_live = pa.attn_positions(table, bs, lens, active,
                                                   pool=pools[2 * c][0])
            else:
                w_read, w_live = pa.window_positions(table, bs, lens,
                                                     cls.window, active)
        aux = {"attn_positions_read": f_read + w_read,
               "attn_positions_live": f_live + w_live,
               "attn_full_positions_read": f_read,
               "attn_window_positions_read": w_read,
               "attn_window_positions_live": w_live}
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


def window_moe_tiny(**kw) -> WindowMoeConfig:
    """A CPU-test size with every mechanism present: a dense full layer, three
    sliding layers and a full one with experts (one period), 4 / 6 query
    heads over 2 K/V heads (groups of 2 and 3), window 8, 8 routed experts
    top-3 by softmax and a shared one, YaRN on half the lanes of the full
    layers."""
    base = dict(
        vocab_size=256, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=48, shared_expert_intermediate_size=48,
        num_hidden_layers=5,
        layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
        num_attention_heads_per_layer=(4, 6, 6, 6, 4),
        mlp_layer_types=("dense", "sparse", "sparse", "sparse", "sparse"),
        num_key_value_heads=2, head_dim=16, sliding_window=8, num_experts=8,
        num_experts_per_tok=3, max_position_embeddings=512,
        rope_parameters={
            FULL: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
                   "original_max_position_embeddings": 16, "beta_fast": 4.0,
                   "beta_slow": 1.0, "attention_factor": None,
                   "partial_rotary_factor": 0.5},
            SLIDING: {"rope_type": "default", "rope_theta": 100.0,
                      "partial_rotary_factor": 1.0}},
        dtype="float32")
    base.update(kw)
    return WindowMoeConfig(**base)
