"""Decoder whose attention runs in a compressed latent mixed by causal
convolutions, with a one-token value shift, an MLP router whose state is
carried across depth, top-1 of a few wide experts with a skip choice, and
scaled residuals (the block of ZAYA1-8B, Zyphra's family: "Compressed
Convolutional Attention", arXiv:2510.04476; the ZAYA1 report,
arXiv:2511.17127).

One layer (E hidden, Hq query heads on Hkv K/V heads of d lanes, G = Hq / Hkv,
C = (Hq + Hkv) d channels; rms(x) = x / sqrt(mean(x^2) + eps); everything at a
position t < 0 is zero):

    n    = rms(x) g_a
    z    = n W_qk = [q~ ; k~]                                  [C]
    c_t  = a0 z_{t-1} + a1 z_t + b1         depthwise causal conv, kernel 2
    u_t  = B0 c_{t-1} + B1 c_t + b2         grouped causal conv, kernel 2, one
                                            group a head ([d, d] a group)
    q[h] = u^q[h] + (q~[h] + k~[h // G]) / 2                    the q-k mean
    k[j] = u^k[j] + (mean_{h // G = j} q~[h] + k~[j]) / 2
    q[h] = sqrt(d) q[h] / |q[h]|      k[j] = exp(tau_j) sqrt(d) k[j] / |k[j]|
    q, k = rope(q, k, t)                    the first d x partial_rotary_factor
                                            lanes, adjacent pairs
    v_t  = [n_t W_v1 ; n_{t-1} W_v2]        the value shift: the first half of
                                            the K/V heads is the current
                                            token's, the second the previous
    o[h] = sum_{s <= t} softmax_s(q_t[h] . k_s[h // G] / sqrt(d)) v_s[h // G]
    x    = s_r (x + b_r) + s_o (concat_h(o[h]) W_o + b_o)       scaled residual

    m    = rms(x) g_m
    r^l  = m W_dn + b_dn + gamma^l r^{l-1}  [R]; the CARRY across depth, zero
                                            at the first layer run
    e    = W_3 gelu(W_2 gelu(W_1 (rms(r^l) g_r) + b_1) + b_2)   [experts + 1]
    p    = softmax(e);  c = argmax(p + beta)         beta: selection only
    y    = p[c] E_c(m) if c is an expert held here; p[skip] m if c is the
           skip choice (the last index); 0 otherwise
    x    = s_r' (x + b_r') + s_o' (y + b_o')
    logits = rms(x_L) g Emb^T               (tied)

The router (`route_mlp`) is float32 at highest precision, as
`experts.route` is and for the same reason: a top-1 choice flips on the
rounding of a bfloat16 product.  The experts' loop is models/experts.py's
(`expert_loop`), shared with models/mla_moe.py and models/window_moe.py.

THE CACHE has two lifetimes (models/contract.py): K and V are PAGED (after
the convolutions the attention is plain GQA: prefill runs the flash kernel,
decode `ops.paged_attention.paged_chunk_attention`), and what the
convolutions and the value shift need of the previous token is STATE A SLOT,
three pools a layer: "cca_z" (z_{t-1}), "cca_c" (c_{t-1}) and "cca_v2"
(n_{t-1} W_v2, so that decode never keeps n_{t-1}).

All layers are alike, so they are ONE scanned `nn.LayerStack` whose carry is
(x, r): a program holds one layer's body whatever the depth.  The experts'
weights are two parameters of the MODEL with every layer's held experts
along one leading axis ([layers x held, ...]): a layer's loop indexes
`layer x held + e` inside the pass that runs, so an expert nobody chose is
never read (a per-layer slice of a stack would be cut, whole, in front of
the loop).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu._core import random as rng
from paddle_tpu._core.tensor import Parameter, Tensor
from paddle_tpu.models.contract import (CacheClass, CacheSpec, PoolSpec,
                                        ServingContract)
from paddle_tpu.models.experts import add_counts, expert_loop
from paddle_tpu.models.attention import (causal_attention, rope_at_freq,
                                         rotate)
from paddle_tpu.models.window_moe import rope_inv_freq
from paddle_tpu.nn import initializer as I
from paddle_tpu.ops import paged_attention as pa

__all__ = ["CcaMoeConfig", "CcaMoeForCausalLM", "CcaMoeModel", "route_mlp",
           "cca_moe_tiny"]

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclass
class CcaMoeConfig:
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2                  # the depthwise convolution's kernel
    cca_time1: int = 2                  # the grouped convolution's kernel
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0
    num_experts: int = 16               # the router has one output more: skip
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # (first, count): the contiguous range of experts THIS model holds and
    # computes; None holds all of them
    held_experts: tuple | None = None

    def __post_init__(self):
        if (self.cca_time0, self.cca_time1) != (2, 2):
            raise ValueError("the convolutions are built for kernels of 2 (one "
                             "previous token each, which is what a slot keeps)")
        if self.num_experts_per_tok != 1:
            raise ValueError("the router is top-1 (the weight is the chosen "
                             "probability itself)")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads do not group over the K/V heads")
        if self.num_key_value_heads % 2:
            raise ValueError("the value shift splits the K/V heads in halves")

    @property
    def held(self) -> tuple:
        return self.held_experts or (0, self.num_experts)

    @property
    def channels(self) -> int:
        """Width of z = [q~ ; k~], the latent the convolutions mix."""
        return ((self.num_attention_heads + self.num_key_value_heads)
                * self.head_dim)


# ---------------------------------------------------------------- the router

def route_mlp(m, r_prev, w, *, eps):
    """The router: m [T, h] (the expert sublayer's normed input), r_prev
    [T, R] float32 (the router state of the layer before; zeros at the first
    layer run), `w` the router's arrays by name -> (chosen [T, 1] int32 among
    experts + 1 outcomes, the last of them "skip"; weight [T, 1] float32, the
    chosen outcome's probability itself; r [T, R] float32, handed to the next
    layer).  Float32 with every product at highest precision, whatever the
    types handed in; `beta` moves the choice and never the weight."""
    with jax.named_scope("moe.router_mlp"):
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        dot = lambda a, b: jnp.dot(a, f32(b), precision=_HIGHEST)  # noqa: E731
        r = dot(f32(m), w["down_w"]) + f32(w["down_b"]) \
            + f32(w["gamma"]) * r_prev
        n = r * jax.lax.rsqrt(jnp.mean(r * r, -1, keepdims=True) + eps)
        a = jax.nn.gelu(dot(n * f32(w["norm_g"]), w["w1"]) + f32(w["b1"]),
                        approximate=False)
        a = jax.nn.gelu(dot(a, w["w2"]) + f32(w["b2"]), approximate=False)
        p = jax.nn.softmax(dot(a, w["w3"]), axis=-1)
        chosen = jnp.argmax(p + f32(w["beta"]), axis=-1).astype(jnp.int32)
        weight = jnp.take_along_axis(p, chosen[:, None], axis=1)
        return chosen[:, None], weight, r


class RouterMLP(nn.Layer):
    """The router's weights (`route_mlp` computes with them)."""

    def __init__(self, hidden, width, outcomes):
        super().__init__()
        self.down = nn.Linear(hidden, width)
        self.gamma = self.create_parameter(
            [width], default_initializer=I.Constant(1.0))
        self.norm = nn.RMSNorm(width)
        self.fc1 = nn.Linear(width, width)
        self.fc2 = nn.Linear(width, width)
        self.out = nn.Linear(width, outcomes, bias_attr=False)
        self.balance = self.create_parameter(
            [outcomes], default_initializer=I.Constant(0.0))

    def arrays(self) -> dict:
        return {"down_w": self.down.weight._value,
                "down_b": self.down.bias._value, "gamma": self.gamma._value,
                "norm_g": self.norm.weight._value,
                "w1": self.fc1.weight._value, "b1": self.fc1.bias._value,
                "w2": self.fc2.weight._value, "b2": self.fc2.bias._value,
                "w3": self.out.weight._value, "beta": self.balance._value}


# ----------------------------------------------------------------- the layer

class ScaledResidual(nn.Layer):
    """x, f -> s_r (x + b_r) + s_o (f + b_o): four learned vectors."""

    def __init__(self, hidden):
        super().__init__()
        one, zero = I.Constant(1.0), I.Constant(0.0)
        self.res_scale = self.create_parameter([hidden], default_initializer=one)
        self.res_bias = self.create_parameter([hidden], default_initializer=zero)
        self.out_scale = self.create_parameter([hidden], default_initializer=one)
        self.out_bias = self.create_parameter([hidden], default_initializer=zero)

    def forward(self, x, f):
        """x: Tensor; f: raw array of x's shape (any float type)."""
        f32 = lambda p: p._value.astype(jnp.float32)  # noqa: E731
        y = (f32(self.res_scale) * (x._value.astype(jnp.float32)
                                    + f32(self.res_bias))
             + f32(self.out_scale) * (f.astype(jnp.float32)
                                      + f32(self.out_bias)))
        return Tensor(y.astype(x._value.dtype))


class ConvAttention(nn.Layer):
    """The attention sublayer up to W_o: projections into the latent, the
    two causal convolutions, the q-k mean, the norms with a temperature,
    rope, the value shift."""

    def __init__(self, config: CcaMoeConfig):
        super().__init__()
        c = config
        self.heads, self.kv_heads, self.d = (c.num_attention_heads,
                                             c.num_key_value_heads, c.head_dim)
        ch, h = c.channels, c.hidden_size
        self.inv_freq, _af, self.rotary = rope_inv_freq(
            {"rope_type": "default", "rope_theta": c.rope_theta,
             "partial_rotary_factor": c.partial_rotary_factor}, c.head_dim)
        self.qk_proj = nn.Linear(h, ch, bias_attr=False)        # z = [q~ ; k~]
        # [W_v1 | W_v2]: the current token's half, then the half that the
        # NEXT token uses
        self.v_proj = nn.Linear(h, self.kv_heads * self.d, bias_attr=False)
        self.o_proj = nn.Linear(self.heads * self.d, h, bias_attr=False)
        self.conv0_weight = self.create_parameter(          # (a0, a1)
            [2, ch], default_initializer=I.Normal(0.0, math.sqrt(0.5)))
        self.conv0_bias = self.create_parameter(
            [ch], default_initializer=I.Constant(0.0))
        groups = self.heads + self.kv_heads
        self.conv1_weight = self.create_parameter(          # (B0, B1)
            [2, groups, self.d, self.d],
            default_initializer=I.XavierNormal(2 * self.d, self.d))
        self.conv1_bias = self.create_parameter(
            [ch], default_initializer=I.Constant(0.0))
        self.temperature = self.create_parameter(           # tau
            [self.kv_heads], default_initializer=I.Constant(0.0))

    def project(self, n, prev, pos):
        """n: Tensor [B, T, h]; pos [B, T].  `prev`: (z, c, v2) of the position
        BEFORE each of the T, [B, T, C], [B, T, C] and [B, T, Hkv / 2, d] (a
        decode step hands the slot's state), or None for a whole sequence,
        whose own values shifted by one stand there (zeros before its
        first).  Returns raw arrays: q [B, T, Hq, d] and k [B, T, Hkv, d]
        (normed, rotated), v [B, T, Hkv, d], and these positions' own
        (z, c, v2)."""
        b, t = n.shape[0], n.shape[1]
        nq, nkv, d = self.heads, self.kv_heads, self.d
        dt = n._value.dtype
        f32 = jnp.float32

        def before(x, given):      # x_{t-1} beside x_t, zeros at t = 0
            if prev is not None:
                return given
            return jnp.pad(x, [(0, 0), (1, 0)] + [(0, 0)] * (x.ndim - 2))[:, :-1]

        with jax.named_scope("cca.conv"):
            z = self.qk_proj(n)._value                              # [B, T, C]
            a = self.conv0_weight._value.astype(f32)
            c = (a[0] * before(z, prev and prev[0]).astype(f32)
                 + a[1] * z.astype(f32)
                 + self.conv0_bias._value.astype(f32)).astype(dt)
            w = self.conv1_weight._value
            grouped = lambda x, w_g: jnp.einsum(  # noqa: E731
                "btgi,gio->btgo", x.reshape(b, t, nq + nkv, d), w_g,
                preferred_element_type=f32)
            u = (grouped(before(c, prev and prev[1]), w[0]) + grouped(c, w[1])
                 + self.conv1_bias._value.astype(f32).reshape(nq + nkv, d))
            zq = z[..., :nq * d].astype(f32).reshape(b, t, nkv, nq // nkv, d)
            zk = z[..., nq * d:].astype(f32).reshape(b, t, nkv, 1, d)
            q = u[:, :, :nq] + ((zq + zk) / 2).reshape(b, t, nq, d)
            k = u[:, :, nq:] + (jnp.mean(zq, axis=3) + zk[:, :, :, 0]) / 2
            unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
                jnp.sum(x * x, -1, keepdims=True))
            q = (unit(q) * math.sqrt(d)).astype(dt)
            k = (unit(k) * math.sqrt(d) * jnp.exp(
                self.temperature._value.astype(f32))[:, None]).astype(dt)
            cos, sin = rope_at_freq(pos.reshape(-1), self.inv_freq, 1.0)
            at = jnp.arange(b * t, dtype=jnp.int32).reshape(b, t)
            q = rotate(q, cos, sin, at, self.rotary)
            k = rotate(k, cos, sin, at, self.rotary)
            v12 = self.v_proj(n)._value.reshape(b, t, 2, nkv // 2, d)
            v = jnp.concatenate(
                [v12[:, :, 0], before(v12[:, :, 1], prev and prev[2])], axis=2)
        return q, k, v, (z, c, v12[:, :, 1])

    def prefill(self, n, n_real=None):
        """Self-attention over a whole prompt: (o [B, S, Hq d] raw, k rows,
        v rows [B, S, Hkv, d], the state (z, c, v2) after position
        n_real - 1 (the last where n_real is None), as the cache class has
        it: [B, 1, heads, width] each)."""
        b, s = n.shape[0], n.shape[1]
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        q, k, v, state = self.project(n, None, pos)
        with jax.named_scope("cca.attend"):
            o = causal_attention(q, k, v)
        last = s - 1 if n_real is None else n_real - 1
        # [B, 1, heads, width] each: z and c are one "head" of C channels
        z, c, v2 = (jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
                    for x in state)
        return o.reshape(b, s, -1), k, v, (z[:, :, None], c[:, :, None], v2)

    def decode(self, n, k_pool, v_pool, state, table, lens, active):
        """One new token a row.  n: Tensor [B, 1, h]; the paged K and V
        pools and their block table; state (z, c, v2): row b the state of
        row b's slot, [B, 1, C], [B, 1, C], [B, Hkv / 2, d]; lens [B]
        INCLUDING this token.  Returns (o [B, 1, Hq d] raw, k_pool, v_pool,
        state): the state rewritten for ACTIVE rows only."""
        z_prev, c_prev, v2_prev = state
        pos = (lens - 1)[:, None]
        q, k, v, (z, c, v2) = self.project(
            n, (z_prev, c_prev, v2_prev[:, None]), pos)
        with jax.named_scope("cca.attend"):
            k_pool = pa.paged_write_chunk(k_pool, k, table, pos)
            v_pool = pa.paged_write_chunk(v_pool, v, table, pos)
            o = pa.paged_chunk_attention(q, k_pool, v_pool, table, lens)
        if active is None:
            state = (z, c, v2[:, 0])
        else:
            keep = active[:, None, None]
            state = (jnp.where(keep, z, z_prev), jnp.where(keep, c, c_prev),
                     jnp.where(keep, v2[:, 0], v2_prev))
        return o.reshape(o.shape[0], 1, -1), k_pool, v_pool, state


class CcaMoeDecoderLayer(nn.Layer):
    """One layer but for its experts' weights, which the model holds for
    all layers (`CcaMoeModel.expert_gate_up` / `expert_down`)."""

    def __init__(self, config: CcaMoeConfig):
        super().__init__()
        c, h = config, config.hidden_size
        self.config = c
        self.input_layernorm = nn.RMSNorm(h, c.rms_norm_eps)
        self.self_attn = ConvAttention(c)
        self.attn_residual = ScaledResidual(h)
        self.post_attention_layernorm = nn.RMSNorm(h, c.rms_norm_eps)
        self.router = RouterMLP(h, c.router_hidden_size, c.num_experts + 1)
        self.mlp_residual = ScaledResidual(h)

    def experts(self, x, r_prev, gate_up, down, base, active=None):
        """The expert sublayer: x Tensor [B, T, h], r_prev [B * T, R]
        float32; `gate_up` / `down` every layer's held experts, this
        layer's from entry `base` on.  Returns (x', r, counts)."""
        c = self.config
        m = self.post_attention_layernorm(x)
        flat = m._value.reshape(-1, m.shape[-1])
        chosen, weight, r = route_mlp(flat, r_prev, self.router.arrays(),
                                      eps=c.rms_norm_eps)
        y, counts = expert_loop(flat, chosen, weight, gate_up, down,
                                held=c.held, active=active,
                                routed=c.num_experts, base=base)
        # the skip choice: the token's own normed input, weighted like an
        # expert's output, and no expert read
        y = y + jnp.where(chosen == c.num_experts,
                          weight * flat.astype(jnp.float32), 0.0)
        return self.mlp_residual(x, y.reshape(m.shape)), r, counts

    def forward_prefill(self, x, r_prev, gate_up, down, base, n_real, active):
        o, k, v, state = self.self_attn.prefill(self.input_layernorm(x), n_real)
        x = self.attn_residual(x, self.self_attn.o_proj(Tensor(o))._value)
        x, r, counts = self.experts(x, r_prev, gate_up, down, base, active)
        return x, r, (k, v) + state, counts

    def forward_decode(self, x, r_prev, gate_up, down, base, k_pool, v_pool,
                       state, table, lens, active):
        o, k_pool, v_pool, state = self.self_attn.decode(
            self.input_layernorm(x), k_pool, v_pool, state, table, lens,
            active)
        x = self.attn_residual(x, self.self_attn.o_proj(Tensor(o))._value)
        x, r, counts = self.experts(x, r_prev, gate_up, down, base, active)
        return x, r, k_pool, v_pool, state, counts


# ----------------------------------------------------------------- the model

def _stacked_normal(shape, std, dtype):
    """N(0, std^2) of `shape` in `dtype`, made one leading entry at a time
    inside one program: all of it in float32 first would be 8.6 GB for a
    stack that is 4.3 GB of bfloat16."""
    keys = jax.random.split(rng.next_key(), shape[0])
    one = lambda k: (jax.random.normal(k, shape[1:], jnp.float32)  # noqa: E731
                     * std).astype(dtype)
    return jax.jit(lambda ks: jax.lax.map(one, ks))(keys)


class CcaMoeModel(nn.Layer):
    def __init__(self, config: CcaMoeConfig):
        super().__init__()
        c = self.config = config
        dt = jnp.bfloat16 if c.dtype == "bfloat16" else jnp.float32

        def made(layer):
            if c.dtype == "bfloat16":
                layer.to(dtype="bfloat16")
            return layer

        self.embed_tokens = made(nn.Embedding(c.vocab_size, c.hidden_size))
        self.layers = nn.LayerStack(
            [made(CcaMoeDecoderLayer(c)) for _ in range(c.num_hidden_layers)])
        self.norm = made(nn.RMSNorm(c.hidden_size, c.rms_norm_eps))
        # every layer's held experts, layer-major: layer l's expert
        # held[0] + e is entry l * count + e
        n, h, f = c.num_hidden_layers * c.held[1], c.hidden_size, \
            c.moe_intermediate_size
        self.expert_gate_up = Parameter(_stacked_normal(
            (n, h, 2 * f), math.sqrt(2.0 / (h + 2 * f)), dt))
        self.expert_down = Parameter(_stacked_normal(
            (n, f, h), math.sqrt(2.0 / (f + h)), dt))

    def _carry0(self, h):
        rows = h.shape[0] * h.shape[1]
        return (h._value, jnp.zeros((rows, self.config.router_hidden_size),
                                    jnp.float32))

    def forward_prefill(self, input_ids, n_real=None):
        """The whole prompt through every layer: (hidden after the final
        norm, per layer (k, v) rows [N, B, S, Hkv, d] and the state (z, c,
        v2) after the last real position [N, B, 1, ...], expert counts
        summed over the layers).  n_real: traced count of real (not
        right-padding) tokens; only their rows are counted."""
        h = self.embed_tokens(input_ids)
        count = self.config.held[1]
        active = None
        if n_real is not None:
            b, s = input_ids.shape[0], input_ids.shape[1]
            active = jnp.broadcast_to(jnp.arange(s) < n_real, (b, s)).reshape(-1)
        gate_up, down = self.expert_gate_up._value, self.expert_down._value

        def body(layer, carry, index):
            x, r, counts = carry
            x, r, rows, new = layer.forward_prefill(
                Tensor(x), r, gate_up, down, index * count, n_real, active)
            return (x._value, r, add_counts(counts, new)), rows

        (x, _r, counts), rows = self.layers.scan(
            body, self._carry0(h) + (_zero_counts(),), self._layer_index())
        return self.norm(Tensor(x)), rows, counts

    def forward_decode(self, tokens, pools, table, lens, active=None):
        """One token a row: pools (k, v, z, c, v2), each ONE array with the
        layers in front.  K and V ride the scan's CARRY as one flat pool of
        layers x blocks pages each (a view: nothing moves), layer l reading
        and writing through the block table shifted by l pools' worth of
        pages: the write and the kernel's page reads are in place, and no
        layer's pool is ever cut out or put back.  The state a slot, a few
        KB a layer and row, rides as per-layer slices.  Returns (hidden
        after the final norm, pools, expert counts)."""
        h = self.embed_tokens(Tensor(tokens))
        count = self.config.held[1]
        gate_up, down = self.expert_gate_up._value, self.expert_down._value
        k, v, *state = pools
        per_layer = k.shape[1]                   # pages of one layer's pool
        flat = lambda p: p.reshape((-1,) + p.shape[2:])  # noqa: E731

        def body(layer, carry, xs):
            x, r, counts, kf, vf = carry
            index, own = xs
            x, r, kf, vf, own, new = layer.forward_decode(
                Tensor(x), r, gate_up, down, index * count, kf, vf, own,
                table + index * per_layer, lens, active)
            return (x._value, r, add_counts(counts, new), kf, vf), own

        (x, _r, counts, kf, vf), state = self.layers.scan(
            body, self._carry0(h) + (_zero_counts(), flat(k), flat(v)),
            (self._layer_index(), tuple(state)))
        return (self.norm(Tensor(x)),
                [kf.reshape(k.shape), vf.reshape(v.shape)] + list(state),
                counts)

    def _layer_index(self):
        return jnp.arange(self.config.num_hidden_layers, dtype=jnp.int32)

    def forward(self, input_ids):
        return self.forward_prefill(input_ids)[0]


def _zero_counts():
    return {k: jnp.int32(0) for k in ("assignments", "held", "peak", "touched",
                                      "layer_steps", "skipped")}


class CcaMoeForCausalLM(nn.Layer):
    def __init__(self, config: CcaMoeConfig):
        super().__init__()
        self.config = config
        self.model = CcaMoeModel(config)

    def _logits(self, h):
        return paddle.matmul(h, self.model.embed_tokens.weight,
                             transpose_y=True)

    def forward(self, input_ids):
        return self._logits(self.model(input_ids))

    def serving_contract(self) -> "CcaMoeServing":
        """What `serving.GenerationEngine` asks of this model."""
        return CcaMoeServing(self)


class CcaMoeServing(ServingContract):
    """The model contract (models/contract.py): every layer is in TWO cache
    classes, a PAGED one (pools "k" and "v") and a STATE one (pools "cca_z",
    "cca_c", "cca_v2": what the convolutions and the value shift keep of
    the previous token, a slot), both STACKED: the engine holds each pool
    as one array with the layers in front, the form the scanned stack
    computes on.  `decode` takes the pools as [k, v, cca_z, cca_c, cca_v2]
    and (block table, None) as its tables."""

    def __init__(self, lm: CcaMoeForCausalLM):
        cfg = lm.config
        self.lm = lm
        self.max_positions = cfg.max_position_embeddings
        dtype = "bfloat16" if cfg.dtype == "bfloat16" else "float32"
        layers = tuple(range(cfg.num_hidden_layers))
        kv, d = cfg.num_key_value_heads, cfg.head_dim
        self.spec = CacheSpec.of([
            CacheClass(layers, (PoolSpec("k", kv, d, dtype),
                                PoolSpec("v", kv, d, dtype)), stacked=True),
            CacheClass(layers, (PoolSpec("cca_z", 1, cfg.channels, dtype),
                                PoolSpec("cca_c", 1, cfg.channels, dtype),
                                PoolSpec("cca_v2", kv // 2, d, dtype)),
                       slot_state=True, stacked=True)])

    def forward_cached(self, ids, caches, offset, n_real=None):
        if offset:
            raise NotImplementedError(
                "convolutional attention prefills a whole prompt at once: "
                "its state a slot is the state after the LAST position, so "
                "there is no prefix to continue from (prefix cache and "
                "chunked prefill refuse a state class at construction)")
        h, rows, counts = self.lm.model.forward_prefill(ids, n_real)
        aux = {"moe_prefill_assignments": counts["assignments"],
               "moe_prefill_held_assignments": counts["held"],
               "moe_prefill_skipped": counts["skipped"]}
        n = self.spec.n_layers
        return h, [tuple(Tensor(p[i]) for p in rows) for i in range(n)], aux

    def decode(self, tokens, pools, tables, lens, active=None, **kv_only):
        if kv_only:
            raise NotImplementedError(
                f"convolutional attention decode has no {sorted(kv_only)}")
        table = tables[0]           # the paged class's; the state class's is None
        k0 = pools[0][0]            # one layer's K pool
        h, pools, counts = self.lm.model.forward_decode(
            tokens, pools, table, lens, active)
        # what this token step's attention read and what was live, once a
        # step (every layer reads the same pages)
        read, live = pa.attn_positions(table, pa.pool_block_size(k0), lens,
                                       active, pool=k0)
        return h, pools, {
            "attn_positions_read": read, "attn_positions_live": live,
            "moe_assignments": counts["assignments"],
            "moe_held_assignments": counts["held"],
            "moe_peak_expert_assignments": counts["peak"],
            "moe_experts_touched": counts["touched"],
            "moe_layer_steps": counts["layer_steps"],
            "moe_skipped": counts["skipped"]}

    def logits(self, h):
        return self.lm._logits(h)

    def pool_carry(self, pools):
        return [p[0] for p in pools]

    def pool_unpack(self, pools):
        return [[p] for p in pools]


def cca_moe_tiny(**kw) -> CcaMoeConfig:
    """A CPU-test size with every mechanism present: 3 layers, 4 query heads
    on 2 K/V heads of 16 lanes (groups of 2), half of each head rotated, 8
    experts top-1 with the skip choice, a router state of 16."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                num_experts=8, moe_intermediate_size=48, router_hidden_size=16,
                max_position_embeddings=512, rope_theta=10000.0,
                dtype="float32")
    base.update(kw)
    return CcaMoeConfig(**base)
