"""LLaMA-family decoder (flagship model).

Capability target: the reference trains LLaMA-2 via PaddleNLP on fleet hybrid
parallel (BASELINE.json north star).  Architecture built on this framework's nn
API; TPU-first choices:
- bfloat16 parameters/activations by default, fp32 RMSNorm statistics;
- rotary embeddings computed once and gathered (no per-step trig);
- attention via scaled_dot_product_attention → XLA fused attention or the
  Pallas flash kernel;
- shapes chosen MXU-friendly (head_dim multiple of 128 recommended at scale).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu._core.tensor import Tensor
from paddle_tpu.models.contract import CacheSpec, PoolSpec, ServingContract
from paddle_tpu.tensor._ops_common import apply

__all__ = [
    "LlamaConfig",
    "LlamaForCausalLM",
    "LlamaModel",
    "LlamaDecoderLayer",
    "shard_llama",
    "LLAMA_TP_COL_TARGETS",
    "LLAMA_TP_ROW_TARGETS",
    "pipeline_llama",
    "context_parallel_llama",
    "llama_tiny",
    "llama_7b",
]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # parallel hints consumed by the distributed layer (tp/sp shardings)
    tensor_parallel_degree: int = 1
    sequence_parallel: bool = False
    use_recompute: bool = False
    # recompute tier inside each block (reference recompute_granularity):
    # "full" | "full_attn" | "core_attn"
    recompute_granularity: str = "full"
    # run the decoder stack as ONE jax.lax.scan over stacked per-layer
    # weights (nn.LayerStack): trace/compile cost becomes O(1) in depth.
    # FLAGS_scan_layers forces this on for every model built afterwards.
    fuse_layer_stack: bool = False


def _rope_tables(head_dim: int, max_len: int, theta: float):
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [max_len, head_dim/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def _rope_rotate(qv, kv, c_t, s_t):
    """Rotate-half on [B, S, N, H] given pre-sliced cos/sin [S, H/2]."""
    c_t = c_t[None, :, None, :]
    s_t = s_t[None, :, None, :]

    def rot(x):
        x1 = x[..., 0::2]
        x2 = x[..., 1::2]
        xr1 = x1 * c_t - x2 * s_t
        xr2 = x2 * c_t + x1 * s_t
        return jnp.stack([xr1, xr2], axis=-1).reshape(x.shape)

    return rot(qv).astype(qv.dtype), rot(kv).astype(kv.dtype)


def apply_rotary_pos_emb(q, k, cos, sin, position_offset=0):
    """Rotate half formulation on [B, S, N, H] tensors (reference fused_rope
    kernel paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu — here one
    fused XLA elementwise chain; a Pallas variant lives in paddle_tpu.ops).

    position_offset may be a Tensor (traced — e.g. a sequence-parallel
    rank's shard offset); the table slice then lowers to dynamic_slice."""
    from paddle_tpu._core.tensor import Tensor as _T

    if isinstance(position_offset, _T):
        def _rope_dyn(qv, kv, c, s, off):
            import jax.lax as _lax

            S = qv.shape[1]
            c_t = _lax.dynamic_slice_in_dim(c, off, S, 0)
            s_t = _lax.dynamic_slice_in_dim(s, off, S, 0)
            return _rope_rotate(qv, kv, c_t, s_t)

        return apply("rotary_pos_emb", _rope_dyn, q, k, cos, sin, position_offset)

    def _rope(qv, kv, c, s):
        S = qv.shape[1]
        return _rope_rotate(
            qv, kv,
            c[position_offset : position_offset + S],
            s[position_offset : position_offset + S],
        )

    return apply("rotary_pos_emb", _rope, q, k, cos, sin)


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.hidden_size = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        bias = False
        self.q_proj = nn.Linear(self.hidden_size, self.num_heads * self.head_dim, bias_attr=bias)
        self.k_proj = nn.Linear(self.hidden_size, self.num_kv_heads * self.head_dim, bias_attr=bias)
        self.v_proj = nn.Linear(self.hidden_size, self.num_kv_heads * self.head_dim, bias_attr=bias)
        self.o_proj = nn.Linear(self.num_heads * self.head_dim, self.hidden_size, bias_attr=bias)

    def forward(self, hidden_states, rope_cos, rope_sin, attn_mask=None, kv_cache=None, position_offset=0):
        b, s, _ = hidden_states.shape
        q = self.q_proj(hidden_states).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(hidden_states).reshape([b, s, self.num_kv_heads, self.head_dim])
        v = self.v_proj(hidden_states).reshape([b, s, self.num_kv_heads, self.head_dim])
        sep_ax = None
        if getattr(self, "_sep_mode", None):
            # one gate for BOTH the rope offset and the attention branch:
            # rope offsets and ring exchange must engage together
            from paddle_tpu.distributed.communication import current_axis_scope

            ax = current_axis_scope().get("sep")
            if ax is not None and (attn_mask is not None or kv_cache is not None):
                # silently skipping the sep path would make each rank compute
                # plain local attention with offset-0 rope -> wrong logits
                raise ValueError(
                    "context-parallel ('sep') attention supports neither "
                    "attn_mask nor kv_cache: drop them inside the sep axis "
                    "scope, or run this layer without context parallelism"
                )
            sep_ax = ax
        if sep_ax is not None:
            # sequence sharded over 'sep': this shard's tokens sit at global
            # positions rank*s .. rank*s + s, so the rope tables must be
            # sliced at the rank offset (dynamic under tracing)
            import jax.lax as _lax

            rope_len = int(rope_cos.shape[0])

            def _sep_off(z, ax=sep_ax, s=s, rope_len=rope_len):
                from paddle_tpu.distributed.shard_map_compat import axis_size

                w = axis_size(ax)
                if s * w > rope_len:
                    raise ValueError(
                        f"context parallelism: global sequence {s * w} "
                        f"exceeds the rope table ({rope_len} positions); "
                        "raise max_position_embeddings"
                    )
                return (z + _lax.axis_index(ax) * s).astype(jnp.int32)

            base = (
                position_offset
                if isinstance(position_offset, Tensor)
                else paddle.full([], int(position_offset), "int32")
            )
            position_offset = apply("sep_pos_offset", _sep_off, base)
        q, k = apply_rotary_pos_emb(q, k, rope_cos, rope_sin, position_offset)
        if kv_cache is not None:
            k = paddle.concat([kv_cache[0], k], axis=1)
            v = paddle.concat([kv_cache[1], v], axis=1)
            new_cache = (k, v)
        else:
            new_cache = None
        if self.num_kv_heads != self.num_heads:
            rep = self.num_heads // self.num_kv_heads
            k = paddle.repeat_interleave(k, rep, axis=2)
            v = paddle.repeat_interleave(v, rep, axis=2)
        # multi-token chunk on a non-empty cache (chunked prefill /
        # speculative verify) is safe: both attention paths are
        # bottom-right aligned for Sq != Sk, so chunk token i attends to
        # the cache plus chunk positions <= i
        if sep_ax is not None:
            # context parallelism (context_parallel_llama): the sequence is
            # sharded over the 'sep' axis — ring/Ulysses attention exchange
            # K/V shards over ICI instead of materializing the full sequence
            from paddle_tpu.distributed.fleet.meta_parallel.segment_parallel import (
                sep_attention,
            )

            out = sep_attention(q, k, v, causal=True, mode=self._sep_mode)
        else:
            # empty-cache prefill is causal; a cached single-token
            # decode attends to everything it has
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask,
                is_causal=(kv_cache is None) or s > 1
            )
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        out = self.o_proj(out)
        if new_cache is not None:
            return out, new_cache
        return out


class LlamaMLP(nn.Layer):
    """SwiGLU MLP — gate/up fused into one matmul (MXU-friendly)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.gate_up_proj = nn.Linear(config.hidden_size, 2 * config.intermediate_size, bias_attr=False)
        self.down_proj = nn.Linear(config.intermediate_size, config.hidden_size, bias_attr=False)
        self.intermediate_size = config.intermediate_size

    def forward(self, x):
        gate_up = self.gate_up_proj(x)
        gate, up = paddle.split(gate_up, 2, axis=-1)
        from paddle_tpu import ops as _ops

        if _ops.use_pallas():
            import paddle_tpu.incubate.nn.functional as _FF

            return self.down_proj(_FF.swiglu(gate, up))
        return self.down_proj(F.silu(gate) * up)


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self._use_recompute = config.use_recompute

    def forward(self, hidden_states, rope_cos, rope_sin, attn_mask=None, kv_cache=None, position_offset=0):
        residual = hidden_states
        h = self.input_layernorm(hidden_states)
        new_cache = None
        if kv_cache is not None:
            h, new_cache = self.self_attn(
                h, rope_cos, rope_sin, attn_mask, kv_cache=kv_cache, position_offset=position_offset
            )
        else:
            from paddle_tpu.nn.layer.stack import current_recompute_tier

            if current_recompute_tier() == "full_attn":
                # recompute_granularity="full_attn": exactly the attention
                # sublayer rematerializes in backward (nested jax.checkpoint
                # via fleet.recompute); MLP/norm residuals stay saved
                from paddle_tpu.distributed.fleet.recompute import recompute

                h = recompute(self.self_attn, h, rope_cos, rope_sin, attn_mask)
            else:
                h = self.self_attn(h, rope_cos, rope_sin, attn_mask)
        h = residual + h
        residual = h
        h2 = self.post_attention_layernorm(h)
        h2 = self.mlp(h2)
        out = residual + h2
        if new_cache is not None:
            return out, new_cache
        return out


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        from paddle_tpu._core import flags as _flags

        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        blocks = [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)]
        if config.fuse_layer_stack or _flags.flag("FLAGS_scan_layers"):
            # one scanned block instead of N unrolled ones: trace + XLA
            # compile cost is O(1) in depth (docs/SCAN_LAYERS.md)
            self.layers = nn.LayerStack(
                blocks,
                recompute=(config.recompute_granularity
                           if config.use_recompute else None),
                needs_rng=False,  # no stochastic sublayers in the block
            )
        else:
            self.layers = nn.LayerList(blocks)
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        head_dim = config.hidden_size // config.num_attention_heads
        cos, sin = _rope_tables(head_dim, config.max_position_embeddings, config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)
        if config.dtype == "bfloat16":
            self.to(dtype="bfloat16")
            # rope tables stay fp32 for precision
            self.rope_cos._bind(cos)
            self.rope_sin._bind(sin)

    def forward(self, input_ids, attn_mask=None):
        from paddle_tpu.distributed.fleet.meta_parallel import PipelineStack

        if getattr(self, "_pp_full", False):
            # full-model pipeline: embedding rides the first stage and
            # norm+head the last (reference SegmentLayers pp_layers.py:92);
            # the stack consumes token ids and emits logits
            return self.layers(input_ids, self.rope_cos, self.rope_sin, attn_mask)
        h = self.embed_tokens(input_ids)
        if isinstance(self.layers, (PipelineStack, nn.LayerStack)):
            h = self.layers(h, self.rope_cos, self.rope_sin, attn_mask)
        else:
            gran = self.config.recompute_granularity
            for layer in self.layers:
                if self.config.use_recompute and self.training:
                    if gran == "full":
                        from paddle_tpu.distributed.fleet.recompute import recompute

                        h = recompute(layer, h, self.rope_cos, self.rope_sin, attn_mask)
                    else:
                        # sub-layer tiers: the block itself remats its
                        # attention (full_attn) or its attention core
                        # (core_attn) under this scope
                        from paddle_tpu.nn.layer.stack import recompute_tier_scope

                        with recompute_tier_scope(gran):
                            h = layer(h, self.rope_cos, self.rope_sin, attn_mask)
                else:
                    h = layer(h, self.rope_cos, self.rope_sin, attn_mask)
        return self.norm(h)


def _proj_lora(proj, x, ad, name, slots, scaling):
    """A target projection's raw output, plus its gathered per-row LoRA
    delta when the adapter pack covers it (nn/lora.py lora_delta).  x is
    the projection's input Tensor; returns a raw [B, T, out] array."""
    out = proj(x)._value
    if ad is not None and name in ad:
        from paddle_tpu.nn.lora import lora_delta

        out = out + lora_delta(x._value, *ad[name], slots, scaling)
    return out


def _mlp_paged(mlp, x, ad, slots, scaling):
    """layer.mlp(x) with optional LoRA deltas on gate_up/down — mirrors
    LlamaMLP.forward so the no-adapter decode program is unchanged."""
    if ad is None or ("mlp.gate_up_proj" not in ad
                      and "mlp.down_proj" not in ad):
        return mlp(x)
    gate_up = Tensor(_proj_lora(mlp.gate_up_proj, x, ad, "mlp.gate_up_proj",
                                slots, scaling))
    gate, up = paddle.split(gate_up, 2, axis=-1)
    from paddle_tpu import ops as _ops

    if _ops.use_pallas():
        import paddle_tpu.incubate.nn.functional as _FF

        act = _FF.swiglu(gate, up)
    else:
        act = F.silu(gate) * up
    return Tensor(_proj_lora(mlp.down_proj, act, ad, "mlp.down_proj",
                             slots, scaling))


def _decode_layer_paged(layer, h, cos, sin, kc, vc, tables, lens,
                        ad=None, slots=None, scaling=None):
    """One decoder layer on one new token against the paged KV pools.

    h: Tensor [B, 1, D]; kc/vc: [num_blocks, Nkv, bs, H] pools (raw arrays);
    tables: [B, max_blocks]; lens: [B] lengths INCLUDING this token.
    Returns (Tensor h', kc', vc').

    ad/slots/scaling: optional multi-tenant LoRA state — ad maps target
    paths to THIS layer's slot-stacked (A [S, in, r], B [S, r, out]);
    slots [B] picks each batch row's adapter slot and scaling [B] its
    alpha/rank, so mixed-adapter batches decode in this ONE program
    (slot 0 gathers zeros — the exact base-model identity; nn/lora.py).
    """
    from paddle_tpu.ops import paged_attention as pa

    attn = layer.self_attn
    residual = h
    x = layer.input_layernorm(h)
    b = int(x.shape[0])
    n, nkv, hd = attn.num_heads, attn.num_kv_heads, attn.head_dim
    qv = _proj_lora(attn.q_proj, x, ad, "self_attn.q_proj", slots,
                    scaling).reshape(b, n, hd)
    kv_ = _proj_lora(attn.k_proj, x, ad, "self_attn.k_proj", slots,
                     scaling).reshape(b, nkv, hd)
    vv = _proj_lora(attn.v_proj, x, ad, "self_attn.v_proj", slots,
                    scaling).reshape(b, nkv, hd)
    pos = lens - 1
    qv = pa.rope_rotate_by_position(qv, cos, sin, pos)
    kv_ = pa.rope_rotate_by_position(kv_, cos, sin, pos)
    kc = pa.paged_write(kc, kv_, tables, pos)
    vc = pa.paged_write(vc, vv, tables, pos)
    o = pa.paged_decode_attention(qv, kc, vc, tables, lens)
    out = Tensor(_proj_lora(attn.o_proj, Tensor(o.reshape(b, 1, n * hd)),
                            ad, "self_attn.o_proj", slots, scaling))
    h = residual + out
    residual = h
    h2 = layer.post_attention_layernorm(h)
    h2 = _mlp_paged(layer.mlp, h2, ad, slots, scaling)
    return residual + h2, kc, vc


def _decode_layer_paged_chunk(layer, h, cos, sin, kc, vc, tables, lens,
                              ad=None, slots=None, scaling=None):
    """One decoder layer on a T-token chunk against the paged KV pools
    (speculative verify / chunked paged decode).

    h: Tensor [B, T, D]; lens: [B] lengths INCLUDING all T chunk tokens.
    Chunk token j sits at global position lens - T + j.  Returns
    (Tensor h', kc', vc').  ad/slots/scaling as in _decode_layer_paged."""
    from paddle_tpu.ops import paged_attention as pa

    attn = layer.self_attn
    residual = h
    x = layer.input_layernorm(h)
    b, t = int(x.shape[0]), int(x.shape[1])
    n, nkv, hd = attn.num_heads, attn.num_kv_heads, attn.head_dim
    qv = _proj_lora(attn.q_proj, x, ad, "self_attn.q_proj", slots,
                    scaling).reshape(b, t, n, hd)
    kv_ = _proj_lora(attn.k_proj, x, ad, "self_attn.k_proj", slots,
                     scaling).reshape(b, t, nkv, hd)
    vv = _proj_lora(attn.v_proj, x, ad, "self_attn.v_proj", slots,
                    scaling).reshape(b, t, nkv, hd)
    pos = lens[:, None] - t + jnp.arange(t, dtype=jnp.int32)[None, :]  # [B,T]
    qv = pa.rope_rotate_chunk(qv, cos, sin, pos)
    kv_ = pa.rope_rotate_chunk(kv_, cos, sin, pos)
    kc = pa.paged_write_chunk(kc, kv_, tables, pos)
    vc = pa.paged_write_chunk(vc, vv, tables, pos)
    o = pa.paged_chunk_attention(qv, kc, vc, tables, lens)
    out = Tensor(_proj_lora(attn.o_proj, Tensor(o.reshape(b, t, n * hd)),
                            ad, "self_attn.o_proj", slots, scaling))
    h = residual + out
    residual = h
    h2 = layer.post_attention_layernorm(h)
    h2 = _mlp_paged(layer.mlp, h2, ad, slots, scaling)
    return residual + h2, kc, vc


def _decode_layers_paged(layers, h, cos, sin, kpools, vpools, tables, lens,
                         chunk=False, adapters=None, slots=None,
                         scaling=None):
    """Run every decoder layer's paged decode step over per-layer pools.

    ``layers`` is either a LayerList (unrolled view loop — the program
    traces N layer bodies) or an ``nn.LayerStack`` (the pools stack on a
    leading layer axis INSIDE this trace and thread through ONE
    ``lax.scan`` as per-layer state — trace and XLA compile are O(1) in
    depth, closing the decode half of docs/SCAN_LAYERS.md).

    kpools/vpools: lists of per-layer pool arrays [num_blocks, Nkv, bs, H]
    — or, on the LayerStack path, optionally ONE stacked [N, ...] array
    each (see _pool_carry): macro-step inner loops pass the stacked form
    so the N-pool concat is paid once per dispatch, not once per token.
    ``chunk`` selects the T-token variant (speculative verify / macro-step
    internals share it).  Returns (h, pools) in the layout given.

    adapters/slots/scaling: multi-tenant LoRA — ``adapters`` maps target
    paths to slot-stacked (A [L, S, in, r], B [L, S, r, out]) with a
    LEADING LAYER AXIS; on the LayerStack path the pack rides the decode
    scan as extra per-layer xs, on the view loop each layer indexes its
    slice.  slots [B] / scaling [B] are per-batch-row (nn/lora.py).
    """
    from paddle_tpu.ops import paged_attention as pa

    step = _decode_layer_paged_chunk if chunk else _decode_layer_paged
    if isinstance(layers, nn.LayerStack):
        # per-layer form is a list/tuple; anything else (a raw stacked
        # array or a stacked QuantPool pytree) is the carry form
        stacked_in = not isinstance(kpools, (list, tuple))
        k_state = kpools if stacked_in else pa.pool_stack(kpools)
        v_state = vpools if stacked_in else pa.pool_stack(vpools)
        if adapters is None:
            h, k_state, v_state = layers.decode_scan(
                lambda layer, hh, kc, vc: step(
                    layer, hh, cos, sin, kc, vc, tables, lens),
                h, k_state, v_state)
        else:
            h, k_state, v_state = layers.decode_scan(
                lambda layer, hh, kc, vc, ad: step(
                    layer, hh, cos, sin, kc, vc, tables, lens,
                    ad=ad, slots=slots, scaling=scaling),
                h, k_state, v_state, extra=adapters)
        if stacked_in:
            return h, k_state, v_state
        n = len(layers)
        return (h, [pa.pool_index(k_state, i) for i in range(n)],
                [pa.pool_index(v_state, i) for i in range(n)])
    import jax

    new_k, new_v = [], []
    for li, layer in enumerate(layers):
        ad_l = (None if adapters is None else
                jax.tree_util.tree_map(lambda a: a[li], adapters))
        h, kc, vc = step(layer, h, cos, sin, kpools[li], vpools[li],
                         tables, lens, ad=ad_l, slots=slots, scaling=scaling)
        new_k.append(kc)
        new_v.append(vc)
    return h, new_k, new_v


def _pool_carry(layers, kpools, vpools):
    """Per-layer pool lists -> the cheapest loop-carry form: ONE stacked
    [N, ...] pool each for a LayerStack (the macro-step scan then carries
    2 buffers instead of 2N and the decode_scan consumes them directly —
    no per-token stack/unstack), the lists unchanged for the view loop.
    Stacking is leaf-wise so quantized pools (QuantPool payload + scales)
    ride the same path."""
    from paddle_tpu.ops import paged_attention as pa

    if isinstance(layers, nn.LayerStack):
        return pa.pool_stack(kpools), pa.pool_stack(vpools)
    return list(kpools), list(vpools)


def _pool_unpack(layers, kpools, vpools):
    """Inverse of _pool_carry: back to per-layer lists for the host."""
    from paddle_tpu.ops import paged_attention as pa

    if isinstance(layers, nn.LayerStack):
        n = len(layers)
        return ([pa.pool_index(kpools, i) for i in range(n)],
                [pa.pool_index(vpools, i) for i in range(n)])
    return list(kpools), list(vpools)


def _empty_caches(config: "LlamaConfig", batch):
    """Per-layer empty naive KV caches (one constructor for generate /
    beam search / speculative decode)."""
    nkv = config.num_key_value_heads
    head_dim = config.hidden_size // config.num_attention_heads
    return [
        (paddle.zeros([batch, 0, nkv, head_dim], dtype=config.dtype),
         paddle.zeros([batch, 0, nkv, head_dim], dtype=config.dtype))
        for _ in range(config.num_hidden_layers)
    ]


def _model_forward_cached(model: "LlamaModel", input_ids, caches, position_offset=0):
    """Thread per-layer naive KV caches (prefill or decode)."""
    h = model.embed_tokens(input_ids)
    new_caches = []
    for layer, c in zip(model.layers, caches):
        h, nc = layer(h, model.rope_cos, model.rope_sin, None, kv_cache=c, position_offset=position_offset)
        new_caches.append(nc)
    return model.norm(h), new_caches


class LlamaServing(ServingContract):
    """The model contract (models/contract.py) of the dense GQA decoder: a
    K pool and a V pool a layer, `num_key_value_heads` rows of `head_dim`.
    Every method is the function the engine called directly before the
    contract existed, so streams and programs are unchanged."""

    def __init__(self, lm: "LlamaForCausalLM"):
        cfg = lm.config
        self.lm = lm
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        dtype = "bfloat16" if cfg.dtype == "bfloat16" else "float32"
        self.spec = CacheSpec(cfg.num_hidden_layers, tuple(
            PoolSpec(n, cfg.num_key_value_heads, head_dim, dtype)
            for n in ("k", "v")))

    @property
    def max_positions(self) -> int:
        return int(self.lm.model.rope_cos.shape[0])

    def forward_cached(self, ids, caches, offset, n_real=None):
        h, caches = _model_forward_cached(self.lm.model, ids, caches, offset)
        return h, caches, {}

    def decode(self, tokens, pools, tables, lens, active=None, **kv_only):
        model = self.lm.model
        h = model.embed_tokens(Tensor(tokens))
        h, kps, vps = _decode_layers_paged(
            model.layers, h, model.rope_cos._value, model.rope_sin._value,
            pools[0], pools[1], tables, lens, **kv_only)
        # what this token step's attention read and what was live, once a
        # step (every layer reads the same pages)
        from paddle_tpu.ops import paged_attention as pa

        k0 = pools[0][0] if isinstance(pools[0], (list, tuple)) else pools[0]
        read, live = pa.attn_positions(
            tables, pa.pool_block_size(k0), lens, active, pool=k0)
        return model.norm(h), [kps, vps], {
            "attn_positions_read": read, "attn_positions_live": live}

    def logits(self, h):
        return self.lm._logits(h)

    def pool_carry(self, pools):
        return list(_pool_carry(self.lm.model.layers, *pools))

    def pool_unpack(self, pools):
        return list(_pool_unpack(self.lm.model.layers, *pools))

    def shard(self, mesh, mp_axis):
        shard_llama(self.lm, mesh, mp_axis=mp_axis)

    def adapter_layers(self):
        return self.lm.model.layers


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias_attr=False)
            if config.dtype == "bfloat16":
                self.lm_head.to(dtype="bfloat16")

    def forward(self, input_ids, labels=None, attn_mask=None):
        if getattr(self.model, "_pp_full", False):
            logits = self.model(input_ids, attn_mask)  # stack already applied norm+head
        else:
            h = self.model(input_ids, attn_mask)
            logits = self._logits(h)
        if labels is not None:
            loss = F.cross_entropy(
                logits.astype("float32").reshape([-1, self.config.vocab_size]),
                labels.reshape([-1]),
                ignore_index=-100,
            )
            return loss, logits
        return logits

    def _logits(self, h):
        if self.lm_head is not None:
            return self.lm_head(h)
        return paddle.matmul(h, self.model.embed_tokens.weight, transpose_y=True)

    def serving_contract(self) -> LlamaServing:
        """What `serving.GenerationEngine` asks of this model."""
        return LlamaServing(self)

    @paddle.no_grad()
    def _speculative_decode(self, input_ids, max_new_tokens, draft_model, K):
        """Draft-and-verify greedy decoding (speculative decoding,
        Leviathan et al.; the serving tier beyond the reference repo).

        The draft proposes K tokens autoregressively; the target verifies
        all of them in ONE chunked forward over its cache (K+1 query
        tokens against cache+K keys — the bottom-right-aligned
        cross-length attention path).  Greedy acceptance: the longest
        prefix where the target's argmax agrees, then the target's own
        token at the first disagreement — so the output is EXACTLY the
        target's plain greedy decode, in ~1/(mean_accepted+1) target
        forwards.  Caches are naive (concat) so rejected tail entries
        trim with a slice.
        """
        import jax.numpy as jnp  # noqa: F811 — module alias shadow-safe

        cfg = self.config
        if draft_model.config.vocab_size != cfg.vocab_size:
            raise ValueError("draft and target must share a vocabulary")
        b, s0 = int(input_ids.shape[0]), int(input_ids.shape[1])
        self._spec_stats = {"target_forwards": 0, "draft_forwards": 0,
                            "accepted": 0, "proposed": 0}

        def _trim(caches, n):
            return [(Tensor(k._value[:, :n]), Tensor(v._value[:, :n]))
                    for k, v in caches]

        import numpy as np

        prompt = [int(t) for t in np.asarray(input_ids._value)[0]]

        # target prefill: cache covers the prompt; first token from the
        # last logit
        h, t_caches = _model_forward_cached(
            self.model, input_ids, _empty_caches(self.config, b), 0)
        self._spec_stats["target_forwards"] += 1
        first = int(jnp.argmax(
            self._logits(h[:, -1:, :])._value[0, -1, :]))
        out = [first]
        # draft prefill over the same prompt
        _, d_caches = _model_forward_cached(
            draft_model.model, input_ids,
            _empty_caches(draft_model.config, b), 0)
        self._spec_stats["draft_forwards"] += 1
        d_len = s0  # draft cache length (cache position p holds full[p])

        while len(out) < max_new_tokens:
            full = prompt + out
            base = len(full) - 1  # both caches must cover full[:base]
            # draft catch-up: one chunk over whatever the last round's
            # acceptance left unconsumed (incl. the bonus token)
            if d_len < base:
                _, d_caches = _model_forward_cached(
                    draft_model.model,
                    paddle.to_tensor([full[d_len:base]], dtype="int32"),
                    d_caches, d_len)
                self._spec_stats["draft_forwards"] += 1
                d_len = base
            k_prop = min(K, max_new_tokens - len(out))
            # ---- draft proposes k_prop tokens after `out[-1]` ----------
            proposals = []
            d_tok = out[-1]
            for j in range(k_prop):
                dh, d_caches = _model_forward_cached(
                    draft_model.model,
                    paddle.to_tensor([[d_tok]], dtype="int32"),
                    d_caches, d_len)
                self._spec_stats["draft_forwards"] += 1
                d_len += 1
                d_tok = int(jnp.argmax(
                    draft_model._logits(dh)._value[0, -1, :]))
                proposals.append(d_tok)
            # ---- target verifies the whole chunk in ONE forward --------
            chunk = [out[-1]] + proposals
            h, t_caches = _model_forward_cached(
                self.model,
                paddle.to_tensor([chunk], dtype="int32"),
                t_caches, base)
            self._spec_stats["target_forwards"] += 1
            preds = jnp.argmax(self._logits(h)._value[0], axis=-1)
            # preds[i] = target's next token after chunk[i]
            accepted = 0
            while accepted < k_prop and int(preds[accepted]) == proposals[accepted]:
                accepted += 1
            self._spec_stats["proposed"] += k_prop
            self._spec_stats["accepted"] += accepted
            # accepted proposals, then the target's own token at the first
            # disagreement (or the bonus token when everything matched)
            new = proposals[:accepted] + [int(preds[accepted])]
            out.extend(new[: max_new_tokens - len(out)])
            # trusted cache = prompt + out[:-1]: chunk[0..accepted-1] were
            # appended beyond `base`; the rejected tail trims away
            keep = base + accepted + 1
            t_caches = _trim(t_caches, keep)
            d_caches = _trim(d_caches, min(d_len, keep))
            d_len = min(d_len, keep)

        return paddle.to_tensor(
            np.asarray(out, np.int32)[None][:, :max_new_tokens])

    @paddle.no_grad()
    def _beam_search(self, input_ids, max_new_tokens, num_beams, length_penalty=0.0):
        """Beam search over the naive cache path (the reference generate()'s
        decode_strategy="beam_search", python/paddle generation lineage).

        TPU-native shape discipline: the beam frontier is a FIXED [B*K]
        batch — expand once after prefill, then each step scores [B, K*V],
        takes top-K, and reorders the caches by beam index (a gather on the
        batch axis); every step has identical shapes."""
        import jax

        cfg = self.config
        b, s0 = int(input_ids.shape[0]), int(input_ids.shape[1])
        K = int(num_beams)
        n_layers = cfg.num_hidden_layers
        nkv = cfg.num_key_value_heads
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        V = cfg.vocab_size

        empty = _empty_caches(cfg, b)
        h, caches = _model_forward_cached(self.model, input_ids, empty, 0)
        logp = jax.nn.log_softmax(
            self._logits(h[:, -1:, :])._value[:, -1, :].astype(jnp.float32), -1)

        # first step: per sequence, the K best first tokens seed the beams
        scores, first = jax.lax.top_k(logp, K)           # [B, K]
        beams = first[:, :, None].astype(jnp.int32)      # [B, K, 1]
        # expand caches to the beam frontier: [B, ...] -> [B*K, ...]
        def expand(t):
            v = t._value
            return Tensor(jnp.repeat(v, K, axis=0))
        caches = [(expand(k), expand(v)) for k, v in caches]

        for step in range(1, max_new_tokens):
            tok = Tensor(beams[:, :, -1].reshape(b * K, 1))
            h, caches = _model_forward_cached(self.model, tok, caches,
                                              s0 + step - 1)
            lp = jax.nn.log_softmax(
                self._logits(h)._value[:, -1, :].astype(jnp.float32), -1)
            total = scores.reshape(b * K, 1) + lp        # [B*K, V]
            total = total.reshape(b, K * V)
            scores, flat = jax.lax.top_k(total, K)       # [B, K]
            beam_idx = flat // V                         # [B, K] source beam
            tok_idx = (flat % V).astype(jnp.int32)
            beams = jnp.concatenate(
                [jnp.take_along_axis(beams, beam_idx[:, :, None], axis=1),
                 tok_idx[:, :, None]], axis=2)
            # reorder the beam-expanded caches by the winning source beams
            gather = (jnp.arange(b)[:, None] * K + beam_idx).reshape(-1)
            caches = [
                (Tensor(jnp.take(k._value, gather, axis=0)),
                 Tensor(jnp.take(v._value, gather, axis=0)))
                for k, v in caches
            ]

        if length_penalty:
            # no EOS termination in this path, so every beam has the same
            # length and a shared positive divisor cannot reorder them —
            # accepted for reference-signature parity, surfaced as a no-op
            import warnings

            warnings.warn(
                "length_penalty has no effect without EOS-terminated beams "
                "(all beams share length max_new_tokens)", stacklevel=2)
            scores = scores / (float(max_new_tokens) ** float(length_penalty))
        best = jnp.argmax(scores, axis=1)                # [B]
        out = jnp.take_along_axis(beams, best[:, None, None], axis=1)[:, 0, :]
        return Tensor(out)

    @paddle.no_grad()
    def generate(self, input_ids, max_new_tokens=16, cache: str = "paged",
                 block_size: int = 16, do_sample: bool = False,
                 temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                 seed=None, decode_strategy=None, num_beams: int = 1,
                 length_penalty: float = 0.0, draft_model=None,
                 num_speculative_tokens: int = 4, decode_chunk=None):
        """Incremental decode (serving path): greedy by default; sampling
        with temperature / top-k / top-p via do_sample=True (the reference
        generate()'s decode_strategy="sampling" surface,
        python/paddle/generation lineage).

        cache="naive": per-layer concat caches (reference use_cache
        semantics; shapes grow each step, eager).
        cache="paged": block-pooled KV (reference block_multihead_attention,
        paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu):
        static shapes, so every decode step reuses ONE compiled program —
        sampling runs INSIDE it (jax.random.categorical, per-step fold_in).

        decode_chunk (paged only; None -> FLAGS_decode_chunk): macro-step
        decoding — D tokens advance per dispatch inside ONE compiled
        program (a lax.scan over the single-token step with donated
        pools), so the host round-trip and device sync amortize over D
        tokens.  Token streams are BIT-IDENTICAL for every D (greedy and
        sampled: each inner step folds the same per-step counter); the
        max_new_tokens % D tail runs through a second cached chunk size.
        """
        import numpy as np

        import jax

        if decode_strategy is not None:
            if decode_strategy not in ("sampling", "greedy_search", "beam_search"):
                raise ValueError(
                    f"decode_strategy must be 'sampling', 'greedy_search' or "
                    f"'beam_search', got {decode_strategy!r}")
            do_sample = decode_strategy == "sampling"
        if num_beams > 1:
            if do_sample:
                raise ValueError(
                    "num_beams > 1 is deterministic beam search; drop "
                    "do_sample/decode_strategy='sampling' (beam-sampling "
                    "is not implemented)")
            if draft_model is not None:
                raise ValueError(
                    "draft_model (speculative decoding) is greedy-only; "
                    "drop num_beams")
            # beam frontier runs on the naive cache path (growing shapes);
            # cache=/block_size= do not apply here
            return self._beam_search(input_ids, max_new_tokens,
                                     num_beams=num_beams,
                                     length_penalty=length_penalty)
        if draft_model is not None:
            if do_sample:
                raise ValueError(
                    "speculative decoding is greedy-only here (sampling "
                    "needs rejection-sampling acceptance; drop do_sample)")
            if int(input_ids.shape[0]) != 1:
                raise ValueError(
                    "speculative decoding supports batch size 1 at the "
                    "model-level API (per-row acceptance lengths diverge)")
            return self._speculative_decode(
                input_ids, max_new_tokens, draft_model,
                int(num_speculative_tokens))
        # decode_strategy='beam_search' with num_beams=1 IS greedy search
        if do_sample and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        # validated BEFORE the (expensive) prefill; an explicit bad value
        # is loud everywhere, a bad FLAGS_decode_chunk clamps to 1 (the
        # same rule GenerationEngine applies)
        if decode_chunk is not None and int(decode_chunk) < 1:
            raise ValueError(
                f"decode_chunk must be >= 1, got {decode_chunk}")
        base_key = None
        if do_sample:
            # derive the key lazily: greedy decode must not advance the
            # global RNG stream (seed-reproducibility of existing scripts)
            if seed is not None:
                base_key = jax.random.PRNGKey(int(seed))
            else:
                from paddle_tpu._core import random as _rng

                base_key = _rng.next_key()

        def _select(logits2d, step):
            """[B, V] raw logits -> [B] next ids (greedy or sampled)."""
            if not do_sample:
                return jnp.argmax(logits2d, axis=-1)
            lg = logits2d.astype(jnp.float32) / jnp.float32(max(temperature, 1e-6))
            if top_k and top_k > 0:
                kth = jax.lax.top_k(lg, min(int(top_k), lg.shape[-1]))[0][:, -1:]
                lg = jnp.where(lg < kth, -jnp.inf, lg)
            if top_p < 1.0:
                sort = jnp.sort(lg, axis=-1)[:, ::-1]
                probs = jax.nn.softmax(sort, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                # keep the smallest prefix with mass >= top_p (always >= 1)
                keep = cum - probs < jnp.float32(top_p)
                cutoff = jnp.min(jnp.where(keep, sort, jnp.inf), axis=-1, keepdims=True)
                lg = jnp.where(lg < cutoff, -jnp.inf, lg)
            return jax.random.categorical(jax.random.fold_in(base_key, step), lg, axis=-1)

        cfg = self.config
        b, s0 = int(input_ids.shape[0]), int(input_ids.shape[1])
        n_layers = cfg.num_hidden_layers
        nkv = cfg.num_key_value_heads
        head_dim = cfg.hidden_size // cfg.num_attention_heads

        # prefill with naive caches (causal), collect per-layer K/V
        empty = _empty_caches(cfg, b)
        h, caches = _model_forward_cached(self.model, input_ids, empty, 0)
        next_tok = Tensor(
            _select(self._logits(h[:, -1:, :])._value[:, -1, :], 0)
            .astype(jnp.int32)[:, None])
        out_tokens = [next_tok]

        if cache == "naive":
            cur = caches
            for step in range(1, max_new_tokens):
                h, cur = _model_forward_cached(self.model, next_tok, cur, s0 + step - 1)
                next_tok = Tensor(
                    _select(self._logits(h)._value[:, -1, :], step)
                    .astype(jnp.int32)[:, None])
                out_tokens.append(next_tok)
            return paddle.concat(out_tokens, axis=1)

        if cache != "paged":
            raise ValueError(f"cache must be 'naive' or 'paged', got {cache!r}")

        # ---- paged: pour prefill K/V into block pools -------------------
        max_len = s0 + max_new_tokens
        blocks_per_seq = -(-max_len // block_size)
        num_blocks = b * blocks_per_seq
        # seq i owns blocks [i*bps, (i+1)*bps) — a trivial allocator; real
        # serving shares the pool across requests via these same tables
        tables = jnp.asarray(
            np.arange(num_blocks, dtype=np.int32).reshape(b, blocks_per_seq)
        )
        pools = []
        pad = blocks_per_seq * block_size - s0
        for (k, v) in caches:
            kc = jnp.moveaxis(k._value, 1, 2)  # [B, Nkv, S, H]
            vc = jnp.moveaxis(v._value, 1, 2)
            kc = jnp.pad(kc, ((0, 0), (0, 0), (0, pad), (0, 0)))
            vc = jnp.pad(vc, ((0, 0), (0, 0), (0, pad), (0, 0)))
            # [B, Nkv, bps*bs, H] -> [B*bps, Nkv, bs, H] pool layout
            kc = kc.reshape(b, nkv, blocks_per_seq, block_size, head_dim)
            vc = vc.reshape(b, nkv, blocks_per_seq, block_size, head_dim)
            pools.append(
                (
                    jnp.moveaxis(kc, 1, 2).reshape(num_blocks, nkv, block_size, head_dim),
                    jnp.moveaxis(vc, 1, 2).reshape(num_blocks, nkv, block_size, head_dim),
                )
            )

        state = list(self.state_dict().values())

        def run_chunk(state_vals, kpools, vpools, tok, lens, step0, d):
            # step_once is defined INSIDE the traced function: lax.scan
            # caches the traced body jaxpr by the body's identity, so a
            # shared body object would serve one trace's closed-over bound
            # weights (tracers) to the next trace (the tail chunk)
            def step_once(carry, _):
                """One decode token — the scan body shared by every chunk
                size (bit-identical streams across D by construction)."""
                tok, kps, vps, lens, step_i = carry
                lens = lens + 1  # the new token occupies slot lens (0-based)
                hh = self.model.embed_tokens(Tensor(tok))
                hh, kps, vps = _decode_layers_paged(
                    self.model.layers, hh, self.model.rope_cos._value,
                    self.model.rope_sin._value, kps, vps, tables, lens)
                hh = self.model.norm(hh)
                logits = self._logits(hh)
                nxt = (_select(logits._value[:, -1, :], step_i)
                       .astype(tok.dtype)[:, None])
                return (nxt, kps, vps, lens, step_i + 1), nxt[:, 0]

            originals = [t._value for t in state]
            try:
                for t, v in zip(state, state_vals):
                    t._bind(v)
                with paddle.no_grad():
                    (tok, kpools, vpools, lens, _), toks = jax.lax.scan(
                        step_once, (tok, kpools, vpools, lens, step0),
                        None, length=d)
            finally:
                for t, v in zip(state, originals):
                    t._bind(v)
            return toks, tok, kpools, vpools, lens

        if decode_chunk is None:
            from paddle_tpu._core import flags as _flags

            D = max(1, int(_flags.flag("FLAGS_decode_chunk")))
        else:
            D = int(decode_chunk)
        # one executable per chunk size: the main D plus (at most) one tail
        jit_chunk = jax.jit(run_chunk, static_argnums=(6,),
                            donate_argnums=(1, 2))
        # carry form ONCE for the whole decode: a LayerStack's pools ride
        # as one stacked [N, ...] buffer each across every dispatch (the
        # per-layer lists never round-trip, so no per-dispatch restack)
        kpools, vpools = _pool_carry(
            self.model.layers, [k for k, _ in pools], [v for _, v in pools])
        lens = jnp.full((b,), s0, jnp.int32)
        tok = next_tok._value
        state_vals = [t._value for t in state]
        step = 1
        while step < max_new_tokens:
            d = min(D, max_new_tokens - step)
            toks, tok, kpools, vpools, lens = jit_chunk(
                state_vals, kpools, vpools, tok, lens, jnp.int32(step), d)
            out_tokens.append(Tensor(toks.T))  # [d, B] -> [B, d]
            step += d
        return paddle.concat(out_tokens, axis=1)


# Megatron TP kinds of the per-layer target projections — the ONE
# classification shared by shard_llama's placement walk and
# nn.lora.AdapterPack.place_over_mesh, so a serving adapter's low-rank
# factors always ride the same axis split as their base projection
# (column-parallel output dims vs row-parallel input dims).
LLAMA_TP_COL_TARGETS = ("self_attn.q_proj", "self_attn.k_proj",
                        "self_attn.v_proj", "mlp.gate_up_proj")
LLAMA_TP_ROW_TARGETS = ("self_attn.o_proj", "mlp.down_proj")


def shard_llama(model: "LlamaForCausalLM", mesh, mp_axis: str = "mp"):
    """Apply Megatron-style tensor-parallel placements to a LlamaForCausalLM.

    Capability parity with building the model from fleet mpu layers
    (reference python/paddle/distributed/fleet/layers/mpu/mp_layers.py:
    VocabParallelEmbedding :47, ColumnParallelLinear :333,
    RowParallelLinear :540) — TPU-native, the layer code is unchanged and the
    parallelism lives entirely in NamedSharding placements; GSPMD inserts the
    identity/allreduce/split/gather collectives mp_ops.py spells out by hand.

    Linear weights here are [in_features, out_features]:
      column-parallel (q/k/v, gate_up, lm_head) → Shard(1) on mp
      row-parallel (o_proj, down_proj)          → Shard(0) on mp
      vocab-parallel embedding                  → Shard(0) on mp
      norms                                     → replicated
    """
    from paddle_tpu.distributed.auto_parallel import Replicate, Shard, shard_tensor

    if mp_axis not in mesh.dim_names:
        return model
    axis_idx = mesh.dim_names.index(mp_axis)

    def place(n_dims_placement):
        pl = [Replicate()] * mesh.ndim
        pl[axis_idx] = n_dims_placement
        return pl

    def shard_param(layer, name, placement):
        p = layer._parameters.get(name)
        if p is None:
            return
        layer._parameters[name] = shard_tensor(p, mesh, place(placement), stop_gradient=p.stop_gradient)

    shard_param(model.model.embed_tokens, "weight", Shard(0))
    if isinstance(model.model.layers, nn.LayerStack):
        from paddle_tpu.nn.layer.stack import shard_stacked_params

        shard_stacked_params(
            model.model.layers, mesh, place,
            col_keys=LLAMA_TP_COL_TARGETS, row_keys=LLAMA_TP_ROW_TARGETS)
    else:
        for blk in model.model.layers:
            for col in (blk.self_attn.q_proj, blk.self_attn.k_proj, blk.self_attn.v_proj, blk.mlp.gate_up_proj):
                shard_param(col, "weight", Shard(1))
                shard_param(col, "bias", Shard(0))
            for row in (blk.self_attn.o_proj, blk.mlp.down_proj):
                shard_param(row, "weight", Shard(0))
    if model.lm_head is not None:
        shard_param(model.lm_head, "weight", Shard(1))
    return model


class _LlamaHead(nn.Layer):
    """Last pipeline stage: final RMSNorm + lm head — the layers the
    reference's SegmentLayers places on the last stage (fleet
    pp_layers.py:92)."""

    def __init__(self, norm, lm_head):
        super().__init__()
        self.norm = norm
        self.lm_head = lm_head

    def forward(self, h):
        return self.lm_head(self.norm(h))


def pipeline_llama(model: "LlamaForCausalLM", mesh, pp_axis: str = "pp",
                   num_microbatches=None, use_recompute: bool = False,
                   include_edges: bool = True, schedule: str = "1F1B",
                   num_virtual_stages: int = 1):
    """Convert the decoder stack to a pipelined stack over the 'pp' mesh axis
    (reference: PipelineLayer partition, fleet pp_layers.py:237).  Apply AFTER
    shard_llama (TP placements transfer to the stacked weights) and BEFORE
    creating the optimizer (parameters are replaced by stacked ones).

    include_edges=True pipelines the FULL model: the embedding becomes the
    first stage's extra layer and norm+lm_head the last stage's (reference
    SegmentLayers non-uniform cut, pp_layers.py:92), so token ids enter the
    pipeline and logits leave it."""
    from paddle_tpu.distributed.fleet.meta_parallel import PipelineStack

    if pp_axis not in mesh.dim_names:
        return model
    if isinstance(model.model.layers, nn.LayerStack):
        raise ValueError(
            "pipeline_llama: the decoder stack is a fused LayerStack "
            "(fuse_layer_stack/FLAGS_scan_layers); pipeline parallelism "
            "partitions per-layer modules — build the model with "
            "fuse_layer_stack=False to pipeline it")
    first = last = None
    if include_edges and model.lm_head is None:
        # tied embeddings would need the embedding weight on both edge
        # stages; keep the (previous, still-correct) trunk-only pipeline
        import warnings

        warnings.warn(
            "pipeline_llama: tie_word_embeddings=True cannot place the "
            "embedding on both edge stages; falling back to the trunk-only "
            "pipeline (embedding/head replicated outside the pp region)",
            stacklevel=2,
        )
        include_edges = False
    if include_edges:
        first = model.model.embed_tokens
        last = _LlamaHead(model.model.norm, model.lm_head)
    model.model.layers = PipelineStack(
        list(model.model.layers),
        mesh,
        pp_axis=pp_axis,
        num_microbatches=num_microbatches,
        use_recompute=use_recompute,
        schedule=schedule,
        num_virtual_stages=num_virtual_stages,
        first_stage=first,
        last_stage=last,
    )
    if include_edges:
        self_model = model.model
        self_model._pp_full = True
    return model


def context_parallel_llama(model: "LlamaForCausalLM", mode: str = "ring"):
    """Switch every attention layer to sequence-parallel attention
    (ring or Ulysses over the 'sep' mesh axis — reference SEP hybrid axis +
    the ring/all-to-all context-parallel recipes).  Inside an SPMD region
    with 'sep' in scope each rank holds a contiguous sequence shard: rope
    offsets become rank-relative and K/V shards rotate over ICI
    (ops/ring_attention.py).  Outside any sep scope the layers fall back to
    ordinary causal attention, so the same model object serves both."""
    if mode not in ("ring", "ulysses"):
        raise ValueError(f"mode must be ring|ulysses, got {mode!r}")
    for blk in model.model.layers:
        blk.self_attn._sep_mode = mode
    return model


def llama_tiny(**kw) -> LlamaConfig:
    cfg = dict(
        vocab_size=1024,
        hidden_size=256,
        intermediate_size=688,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=4,
        max_position_embeddings=512,
    )
    cfg.update(kw)
    return LlamaConfig(**cfg)


def llama_7b(**kw) -> LlamaConfig:
    cfg = dict(
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=11008,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=32,
        max_position_embeddings=4096,
    )
    cfg.update(kw)
    return LlamaConfig(**cfg)
