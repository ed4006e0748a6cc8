"""Latent attention with a learned sparse indexer on the full layers, a second
latent geometry over a sliding window on the others, headwise gates, sigmoid
experts chosen with a correction bias beside a shared one (dots3-note-prev's
block): the program runs it through `paddle_tpu.models.dsa_moe`, served by
the same `GenerationEngine` as the other configurations, its cache in two
classes (pages for the full layers' latent rows and indexer keys, a ring a
slot for the sliding layers' latent rows).  The reference is
`perfbench.reference_dsa_moe`; operations and bytes are in
`perfbench.roofline_dsa_moe`, whose functions this module registers with
`perfbench.roofline.FUNCTIONS` when it is imported (the harness imports the
family before it reads any metric).

A configuration of this family is ONE CHIP'S SHARE of a deployment (its
`share`): `n_routed_experts` counts the experts held here, a contiguous range
starting at `share.first_expert`, while the router keeps `published
.n_routed_experts` outputs; `vocab_size` is the slice of the vocabulary held
here; `layer_types` is kept as published and its first `num_hidden_layers`
entries are run.

A program without `paddle_tpu.models.dsa_moe` fails this module's import at
once, before any weight is made.
"""

from __future__ import annotations

import importlib.util

from perfbench import roofline, roofline_dsa_moe
from perfbench.families.mla_moe import perturb_norms
from perfbench.families.window_moe import spread_gates

if importlib.util.find_spec("paddle_tpu.models.dsa_moe") is None:
    raise ImportError("this program has no paddle_tpu.models.dsa_moe: the "
                      "dsa_moe family cannot run on it")

REFERENCE = "perfbench.reference_dsa_moe"
_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}
# every key of a configuration file this family reads or knowingly ignores
_KNOWN = {
    # the file's own
    "name", "family", "source", "torch_dtype", "reduced", "published", "share",
    "deployment", "assumed",
    # the model's, read by model_config
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "first_k_dense_replace", "layer_types",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "rope_theta", "swa_num_attention_heads",
    "swa_q_lora_rank", "swa_kv_lora_rank", "swa_qk_nope_head_dim",
    "swa_qk_rope_head_dim", "swa_v_head_dim", "swa_rope_theta",
    "sliding_window_size", "index_n_heads", "index_head_dim", "index_topk",
    "apply_mla_qkv_lora_rescale", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
    "max_position_embeddings", "rms_norm_eps",
    # checked against the one value the model implements
    "model_type", "attention_bias", "attention_gate_type", "hidden_act",
    "moe_layer_freq", "num_key_value_heads", "rope_scaling", "scoring_func",
    "swa_attention_gate_type", "swa_num_key_value_heads",
    "tie_word_embeddings", "topk_method",
}

roofline.FUNCTIONS.setdefault("dsa_moe_decode_token_step_min_s",
                              roofline_dsa_moe.decode_token_step_min_s)
roofline.FUNCTIONS.setdefault("dsa_moe_prefill_min_s",
                              roofline_dsa_moe.prefill_min_s)

# the correction bias's spread: about a third of the spread of the sigmoid
# scores it is added to, so that the choice by s + b parts from the choice by
# s alone for most tokens and a router that leaves b out cannot compare equal
BIAS_SPREAD = 0.05


def held_experts(cfg: dict) -> tuple:
    """(first, count) of the routed experts this chip holds."""
    return cfg.get("share", {}).get("first_expert", 0), cfg["n_routed_experts"]


def routed_experts(cfg: dict) -> int:
    return roofline_dsa_moe.routed_experts(cfg)


def check(cfg: dict):
    """Refuse what the program's model cannot express instead of running
    something else under the configuration's name: a key this family does
    not know raises, and so does a known key with a value whose mechanism
    the model lacks."""
    unknown = sorted(set(cfg) - _KNOWN)
    if unknown:
        raise ValueError(f"keys this family does not implement: {unknown}")
    n = cfg["num_hidden_layers"]
    if cfg.get("attention_bias"):
        raise ValueError("DsaMoeConfig has no projection bias")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("DsaMoeConfig's FFNs are SwiGLU (silu)")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("DsaMoeForCausalLM has an untied head")
    for key in ("attention_gate_type", "swa_attention_gate_type"):
        if cfg.get(key, "headwise") != "headwise":
            raise ValueError(f"{key}: the gate is one sigmoid a query head "
                             "('headwise'); no other gate is built")
    for heads, kv in (("num_attention_heads", "num_key_value_heads"),
                      ("swa_num_attention_heads", "swa_num_key_value_heads")):
        if cfg.get(kv, cfg[heads]) != cfg[heads]:
            raise ValueError("latent attention has one latent row a token, "
                             "shared by all heads: no grouped K/V heads")
    if cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("the router scores by sigmoid")
    if cfg.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("the router chooses by score + correction bias "
                         "(noaux_tc), in one group")
    if cfg.get("moe_layer_freq", 1) != 1:
        raise ValueError("every layer after the dense ones has experts")
    if cfg.get("rope_scaling") is not None:
        raise ValueError("no rope scaling is built")
    if len(cfg["layer_types"]) < n:
        raise ValueError(f"layer_types has {len(cfg['layer_types'])} entries "
                         f"for {n} layers")
    if cfg["torch_dtype"] not in _DTYPES:
        raise ValueError(f"unknown dtype {cfg['torch_dtype']!r}")
    first, count = held_experts(cfg)
    if first + count > routed_experts(cfg):
        raise ValueError(f"held experts {first}..{first + count} lie outside "
                         f"the router's {routed_experts(cfg)}")


def model_config(cfg: dict):
    """The program's config for this chip's share."""
    from paddle_tpu.models.dsa_moe import DsaMoeConfig

    check(cfg)
    n = cfg["num_hidden_layers"]
    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "first_k_dense_replace",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "swa_num_attention_heads", "swa_q_lora_rank", "swa_kv_lora_rank",
            "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
            "sliding_window_size", "index_n_heads", "index_head_dim",
            "index_topk", "apply_mla_qkv_lora_rescale", "n_shared_experts",
            "num_experts_per_tok", "norm_topk_prob", "max_position_embeddings",
            "rms_norm_eps")
    return DsaMoeConfig(
        **{k: cfg[k] for k in same}, num_hidden_layers=n,
        layer_types=tuple(cfg["layer_types"][:n]),
        rope_theta=float(cfg["rope_theta"]),
        swa_rope_theta=float(cfg["swa_rope_theta"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        n_routed_experts=routed_experts(cfg),
        dtype=_DTYPES[cfg["torch_dtype"]], held_experts=held_experts(cfg))


def draw_bias(model, seed: int):
    """Each expert layer's correction bias ~ N(0, BIAS_SPREAD^2) from the
    seed: the model builds it at zero, where a router that left it out would
    compare equal."""
    import jax.numpy as jnp
    import numpy as np

    draw = np.random.default_rng([int(seed) % (2 ** 63), 17])
    for name, p in model.state_dict().items():
        if name.endswith("score_correction_bias"):
            p._bind(jnp.asarray(BIAS_SPREAD * draw.standard_normal(p.shape),
                                p._value.dtype))


def build(cfg: dict, seed: int, training: bool):
    """The program's model with weights made on the default device from the
    seed by the model's own initialiser (norm gains: `perturb_norms`; the
    attention gates: `spread_gates`; the correction bias: `draw_bias`)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.dsa_moe import DsaMoeForCausalLM

    if training:
        raise ValueError("no cell trains this family: at 16 bytes a parameter "
                         "no cut within the floors fits one chip")
    paddle.seed(seed)
    # the chip's own generator makes 4.1 B seeded values at memory speed
    # (families/mla_moe.py); the reference reads the SAME arrays
    with jax.default_prng_impl("rbg"):
        model = DsaMoeForCausalLM(model_config(cfg))
    perturb_norms(model, seed)
    spread_gates(model, seed)
    draw_bias(model, seed)
    model.eval()
    return model


def _sizes_of(c) -> dict:
    from paddle_tpu.models.dsa_moe import SLIDING

    def layer(kind):
        g = c.geometry(kind)
        sliding = kind == SLIDING
        return {"kind": "sliding" if sliding else "full",
                "heads": g.num_attention_heads, "nope": g.qk_nope_head_dim,
                "rope": g.qk_rope_head_dim, "v": g.v_head_dim,
                "theta": float(g.rope_theta),
                "window": c.sliding_window_size if sliding else None}

    return {"layers": [layer(kind) for kind in c.layer_types],
            "index": {"heads": c.index_n_heads, "dim": c.index_head_dim,
                      "top_k": c.index_topk},
            "lora_rescale": bool(c.apply_mla_qkv_lora_rescale),
            "eps": float(c.rms_norm_eps), "top_k": c.num_experts_per_tok,
            "scale": float(c.routed_scaling_factor),
            "normalize": bool(c.norm_topk_prob), "held": tuple(c.held)}


def reference_sizes(cfg: dict) -> dict:
    return _sizes_of(model_config(cfg))


class _Experts:
    """The held experts' (w_gate_up, w_down) pairs in order, each cut from the
    model's stacks when the reference asks for it and dropped when it moves
    on: never a second copy of all of them."""

    def __init__(self, gate_up, down):
        self._gate_up, self._down = gate_up, down

    def __len__(self):
        return self._gate_up.shape[0]

    def __iter__(self):
        return ((self._gate_up[e], self._down[e]) for e in range(len(self)))


def reference_weights(model) -> dict:
    """The model's weights in the reference's layout.  They ALIAS the
    model's arrays (gate and up stay fused, as the reference takes them; the
    experts one at a time from their stacks): no second copy is made."""
    sd = {k: v._value for k, v in model.state_dict().items()}
    layers = []
    for i, layer in enumerate(model.model.layers):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        w = {"g_in": sd[p + "input_layernorm.weight"],
             "g_post": sd[p + "post_attention_layernorm.weight"],
             "w_qa": sd[a + "q_a_proj.weight"],
             "g_qa": sd[a + "q_a_layernorm.weight"],
             "w_qb": sd[a + "q_b_proj.weight"],
             "w_kva": sd[a + "kv_a_proj.weight"],
             "g_kva": sd[a + "kv_a_layernorm.weight"],
             "w_kvb": sd[a + "kv_b_proj.weight"],
             "w_o": sd[a + "o_proj.weight"], "w_g": sd[a + "gate_proj.weight"]}
        if not layer.sliding:
            x = a + "indexer."
            w.update(wi_q=sd[x + "wq.weight"], wi_k=sd[x + "wk.weight"],
                     gi_k=sd[x + "k_norm.weight"], bi_k=sd[x + "k_norm.bias"],
                     wi_w=sd[x + "weights_proj.weight"])

        def ffn(q):
            return (sd[q + "gate_up_proj.weight"], sd[q + "down_proj.weight"])

        if layer.dense:
            w["w_gate_up"], w["w_down"] = ffn(p + "mlp.")
        else:
            w["w_router"] = sd[p + "mlp.gate.weight"]
            w["bias"] = sd[p + "mlp.score_correction_bias"]
            w["shared"] = ffn(p + "mlp.shared_experts.")
            w["experts"] = _Experts(sd[p + "mlp.gate_up"], sd[p + "mlp.down"])
        layers.append(w)
    return {"embed": sd["model.embed_tokens.weight"], "layers": layers,
            "norm": sd["model.norm.weight"], "head": sd["lm_head.weight"]}


def routing_agreement(model, weights, sizes, ids, reference, route=None) -> tuple:
    """(share, pairs): over the (token, expert layer) pairs of ONE sequence
    `ids`, the share for which the PROGRAM's router (`models.experts.route`
    with sigmoid scoring and the layer's correction bias, the function its
    macro-step and its prefill program call), handed the REFERENCE's own
    router input m of that layer, chooses the experts the reference chooses.
    Both see the same numbers, so rounding upstream of the router plays no
    part.  With `sizes["dtype"]` (or `sizes["router_dtype"]`) lowered it is
    the reference that is run in the lower type.  `route`: another router
    with the program's signature, in its place (a control)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if route is None:
        from paddle_tpu.models.experts import route

    c = model.config
    pick = jax.jit(lambda m, w, b: route(
        m, w, top_k=c.num_experts_per_tok, scale=c.routed_scaling_factor,
        normalize=c.norm_topk_prob, scoring="sigmoid", bias=b)[0])
    probe = []
    reference.hidden(weights, sizes, ids, probe)
    layers = [layer for layer in model.model.layers if not layer.dense]
    same = [np.asarray((jnp.sort(pick(m, layer.mlp.gate.weight._value,
                                      layer.mlp.score_correction_bias._value),
                                 -1) == jnp.sort(chosen, -1)).all(-1))
            for layer, (_gap, _edge, chosen, m) in zip(layers, probe)]
    same = np.concatenate(same)
    return float(same.mean()), int(same.size)


def index_agreement(model, weights, sizes, ids, reference, block=128) -> tuple:
    """(share, positions): over the queries of ONE sequence `ids` that have
    more than `index_topk` positions to choose from, in every full layer,
    the share of the reference's selected positions that the PROGRAM's
    indexer choice (`models.dsa_moe.index_scores` then `index_select`, the
    functions its decode step and its prefill program call), handed the
    REFERENCE's own indexer queries, weights and keys, selects too.  Both
    see the same numbers, so rounding upstream of the indexer plays no part;
    with `sizes["index_dtype"]` (or `sizes["dtype"]`) lowered it is the
    reference whose scores are computed in the lower type."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.dsa_moe import index_scores, index_select

    top_k = model.config.index_topk

    @jax.jit
    def agree(q, w, k, chosen, t0):
        t = t0 + jnp.arange(q.shape[0], dtype=jnp.int32)
        seen = jnp.arange(k.shape[0], dtype=jnp.int32)[None, :] <= t[:, None]
        at, ok = index_select(index_scores(q[None], w[None], k[None])[0],
                              seen, top_k)
        hit = jnp.take_along_axis(chosen, at, axis=-1) & ok
        return jnp.sum(jnp.where(t >= top_k, jnp.sum(hit, -1), 0))

    probe = []
    depth = 1 + max(i for i, g in enumerate(sizes["layers"])
                    if g["kind"] == "full")
    reference.hidden(weights, sizes, ids, index_probe=probe, depth=depth)
    hits = total = 0
    for q, w, k, chosen, _gap in probe:
        s = q.shape[0]
        for lo in range(0, s, block):
            hi = min(s, lo + block)
            if hi <= top_k:
                continue
            hits += int(agree(q[lo:hi], w[lo:hi], k, chosen[lo:hi],
                              jnp.int32(lo)))
        total += max(0, s - top_k) * top_k
    if not total:
        raise ValueError(f"a prompt of {len(ids)} tokens leaves the indexer "
                         f"nothing to choose (top {top_k})")
    return hits / total, total


_READERS = {}       # id(model) -> (model, {(kind, block size): reader})


def _layer_reader(layer, block_size, window):
    """A jitted function (values, x [S, h] float32, t [B] int32) -> [B, h]
    float32: the PROGRAM's attention sublayer of `layer`'s kind at the
    positions t, its decode path as the macro-step runs it.  The layer's
    input norm and attention (`values`: their parameters, in
    `state_dict` order, of this layer or another of its kind) see x, the
    reference's input of the layer, in the model's type; each row's cache
    holds the rows the program's own projections make of x[:t] (a paged
    class's pages of `block_size` positions, a window class's ring of
    ceil((W - 1) / block_size) + 1 blocks, laid out as ring_write_chunk
    lays them), and the row of position t is written and read by the
    layer's `decode`: the indexer's scores and exact top-k and the gather
    of the selected rows on a full layer, the absorbed read over the ring
    on a sliding one, then W_kvb^V, the gate and W_o."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu._core.autograd import no_grad
    from paddle_tpu._core.tensor import Tensor

    attn, norm = layer.self_attn, layer.input_layernorm
    state = (list(norm.state_dict().values())
             + list(attn.state_dict().values()))
    dtype = norm.weight._value.dtype
    bs = block_size

    def read(values, x, t):
        originals = [p._value for p in state]
        try:
            for p, v in zip(state, values):
                p._bind(v)
            with no_grad():
                s, b = x.shape[0], t.shape[0]
                n = norm(Tensor(x[None].astype(dtype)))
                pos = jnp.arange(s, dtype=jnp.int32)[None]
                _q_nope, _q_rope, row, c_q = attn._project(n, pos)
                row = row[0]
                n_t = Tensor(n._value[0, t][:, None])
                lens = t + 1
                if layer.sliding:
                    ring = -(-(window - 1) // bs) + 1
                    span = ring * bs
                    r = jnp.arange(span, dtype=jnp.int32)[None, :]
                    # the position ring index r holds: the latest p <= t
                    # with p % span == r (none yet where p < 0)
                    p = t[:, None] - (t[:, None] - r) % span
                    pool = jnp.where((p >= 0)[..., None],
                                     row[jnp.maximum(p, 0)], 0)
                    pool = pool.reshape(b * ring, 1, bs, row.shape[-1])
                    tables = (jnp.arange(b, dtype=jnp.int32)[:, None] * ring
                              + jnp.arange(ring, dtype=jnp.int32)[None, :])
                    out, _ = attn.decode(n_t, pool, tables, lens)
                else:
                    _q, _w, key = attn.indexer(n, c_q, pos)
                    pages = -(-s // bs)

                    def paged(rows):
                        rows = jnp.pad(rows, ((0, pages * bs - s), (0, 0)))
                        seen = (jnp.arange(pages * bs)[None, :]
                                < t[:, None])[..., None]
                        return jnp.where(seen, rows[None], 0).reshape(
                            b * pages, 1, bs, rows.shape[-1])

                    tables = (jnp.arange(b, dtype=jnp.int32)[:, None] * pages
                              + jnp.arange(pages, dtype=jnp.int32)[None, :])
                    out, *_ = attn.decode(n_t, paged(row), paged(key[0]),
                                          tables, lens)
                return out._value[:, 0].astype(jnp.float32)
        finally:
            for p, v in zip(state, originals):
                p._bind(v)

    return jax.jit(read), state


def attention_agreement(model, weights, sizes, ids, reference, positions,
                        block_size=128) -> list:
    """[(layer, kind, readings)]: for each layer of ONE sequence `ids`, the
    PROGRAM's attention sublayer on its decode path (`_layer_reader`),
    handed the REFERENCE's own input of that layer, against the
    reference's attention sublayer output, at each of `positions`: max |
    program - reference| over the hidden width in standard deviations of
    the reference's row.  Both see the same input, so what upstream layers
    round plays no part: the reading is that layer's own projections,
    cache write, selection or window, read, gate and output."""
    import jax.numpy as jnp
    import numpy as np

    t = jnp.asarray(positions, jnp.int32)
    if int(t.max()) >= len(ids):
        raise ValueError(f"positions up to {int(t.max())} in a sequence of "
                         f"{len(ids)}")
    layers = model.model.layers
    window = model.config.sliding_window_size
    # one program a layer kind (its layers have the same shapes), kept with
    # the model so that a second call compiles nothing
    readers = _READERS.setdefault(id(model), (model, {}))[1]
    out = []

    def compare(i, x, attn):
        layer = layers[i]
        kind = "sliding" if layer.sliding else "full"
        if (kind, block_size) not in readers:
            readers[kind, block_size] = _layer_reader(layer, block_size,
                                                      window)
        read, _state = readers[kind, block_size]
        values = ([p._value for p in layer.input_layernorm.state_dict()
                   .values()]
                  + [p._value for p in layer.self_attn.state_dict().values()])
        got = np.asarray(read(values, x.astype(jnp.float32), t))
        want = np.asarray(attn[t], np.float32)
        err = np.abs(got - want).max(-1) / want.std(-1)
        out.append((i, kind, [float(e) for e in err]))

    reference.hidden(weights, sizes, ids, attn_probe=compare)
    return out
