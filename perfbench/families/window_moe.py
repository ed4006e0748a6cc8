"""Window and full attention mixed, head counts by layer type, a per-head
output gate, routed experts beside a shared one (Laguna-S-2.1's block;
poolside's family): the program runs it through `paddle_tpu.models.window_moe`,
served by the same `GenerationEngine` as the other configurations, its cache
in two classes (pages for the full layers, a ring a slot for the sliding
ones).  The reference is `perfbench.reference_window_moe`; operations and
bytes are in `perfbench.roofline_window_moe`, whose functions this module
registers with `perfbench.roofline.FUNCTIONS` when it is imported (the harness
imports the family before it reads any metric).

A configuration of this family is ONE CHIP'S SHARE of a deployment (its
`share`): `num_experts` counts the experts held here, a contiguous range
starting at `share.first_expert`, while the router keeps `published
.num_experts` outputs; `vocab_size` is the slice of the vocabulary held here;
the per-layer lists are kept as published and the first `num_hidden_layers`
entries of each are run.

`paddle_tpu.models.window_moe` is imported inside the functions that need it:
a program without that module fails in `build` at once, before any weight is
made.
"""

from __future__ import annotations

from perfbench import roofline, roofline_window_moe
from perfbench.families.mla_moe import perturb_norms

REFERENCE = "perfbench.reference_window_moe"
_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}
_ROPE_KEYS = {"rope_type", "rope_theta", "partial_rotary_factor", "factor",
              "original_max_position_embeddings", "beta_fast", "beta_slow",
              "attention_factor"}
# every key of a configuration file this family reads or knowingly ignores
_KNOWN = {
    # the file's own
    "name", "family", "source", "torch_dtype", "reduced", "published", "share",
    "deployment", "assumed",
    # the model's, read by model_config
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_key_value_heads", "head_dim", "max_position_embeddings",
    "rms_norm_eps", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "norm_topk_prob", "sliding_window", "rope_parameters", "layer_types",
    "mlp_layer_types", "num_attention_heads_per_layer",
    "moe_routed_scaling_factor",
    # checked against the one value the model implements
    "model_type", "num_attention_heads", "attention_bias", "hidden_act",
    "decoder_sparse_step", "mlp_only_layers", "tie_word_embeddings", "gating",
    "gating_types", "moe_apply_router_weight_on_input",
    "moe_router_logit_softcapping",
}

roofline.FUNCTIONS.setdefault("swa_moe_decode_token_step_min_s",
                              roofline_window_moe.decode_token_step_min_s)
roofline.FUNCTIONS.setdefault("swa_moe_prefill_min_s",
                              roofline_window_moe.prefill_min_s)


def held_experts(cfg: dict) -> tuple:
    """(first, count) of the routed experts this chip holds."""
    return cfg.get("share", {}).get("first_expert", 0), cfg["num_experts"]


def routed_experts(cfg: dict) -> int:
    return roofline_window_moe.routed_experts(cfg)


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
        raise ValueError("WindowMoeConfig has no projection bias")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("WindowMoeConfig's FFNs are SwiGLU (silu)")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("WindowMoeForCausalLM has an untied head")
    if cfg.get("gating", "per-head") != "per-head" or any(
            g != "per_head" for g in cfg.get("gating_types", [])[:n]):
        raise ValueError("the attention gate is one sigmoid a query head "
                         "(gating 'per-head'); no other gating is built")
    if cfg.get("moe_apply_router_weight_on_input"):
        raise ValueError("router weights are applied to the experts' outputs")
    if cfg.get("moe_router_logit_softcapping"):
        raise ValueError("the router has no logit soft cap")
    if cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("every layer after the dense ones has experts "
                         "(decoder_sparse_step 1)")
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        if len(cfg[key]) < n:
            raise ValueError(f"{key} has {len(cfg[key])} entries for {n} layers")
    dense = [i for i, k in enumerate(cfg["mlp_layer_types"][:n]) if k == "dense"]
    if dense != [i for i in cfg.get("mlp_only_layers", dense) if i < n]:
        raise ValueError(f"mlp_only_layers {cfg['mlp_only_layers']} and "
                         f"mlp_layer_types disagree (dense layers {dense})")
    full_heads = {h for t, h in zip(cfg["layer_types"][:n],
                                    cfg["num_attention_heads_per_layer"][:n])
                  if t == "full_attention"}
    if "num_attention_heads" in cfg and full_heads - {cfg["num_attention_heads"]}:
        raise ValueError("num_attention_heads is the full layers' head count")
    for kind, rope in cfg["rope_parameters"].items():
        if kind not in ("full_attention", "sliding_attention"):
            raise ValueError(f"rope_parameters for unknown layer type {kind!r}")
        if set(rope) - _ROPE_KEYS:
            raise ValueError(f"rope keys not implemented: "
                             f"{sorted(set(rope) - _ROPE_KEYS)}")
        if rope.get("rope_type", "default") not in ("default", "yarn"):
            raise ValueError(f"rope_type {rope['rope_type']!r} is not built")
    if cfg["torch_dtype"] not in _DTYPES:
        raise ValueError(f"unknown dtype {cfg['torch_dtype']!r}")
    first, count = held_experts(cfg)
    if first + count > routed_experts(cfg):
        raise ValueError(f"held experts {first}..{first + count} lie outside "
                         f"the router's {routed_experts(cfg)}")


def model_config(cfg: dict):
    """The program's config for this chip's share."""
    from paddle_tpu.models.window_moe import WindowMoeConfig

    check(cfg)
    n = cfg["num_hidden_layers"]
    return WindowMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg["shared_expert_intermediate_size"],
        num_hidden_layers=n, layer_types=tuple(cfg["layer_types"][:n]),
        num_attention_heads_per_layer=tuple(
            cfg["num_attention_heads_per_layer"][:n]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"][:n]),
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], sliding_window=cfg["sliding_window"],
        num_experts=routed_experts(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        moe_routed_scaling_factor=cfg["moe_routed_scaling_factor"],
        router_scoring="softmax",
        rope_parameters={k: dict(v) for k, v in cfg["rope_parameters"].items()},
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], dtype=_DTYPES[cfg["torch_dtype"]],
        held_experts=held_experts(cfg))


def spread_gates(model, seed: int):
    """W_g ~ N(0, (1.5 / sqrt(h))^2) from the seed: the gates' inputs then
    have a standard deviation of about 1.5 and the gates spread over (0.1,
    0.9), where a gate that is left out (g = 1) or misplaced cannot compare
    equal.  The model's own initialiser leaves them within (0.3, 0.7)."""
    import jax.numpy as jnp
    import numpy as np

    draw = np.random.default_rng([int(seed) % (2 ** 63), 11])
    for name, p in model.state_dict().items():
        if name.endswith("gate_proj.weight"):
            w = draw.standard_normal(p.shape) * (1.5 / np.sqrt(p.shape[0]))
            p._bind(jnp.asarray(w, p._value.dtype))


def build(cfg: dict, seed: int, training: bool):
    """The program's model with weights made on the default device from the
    seed by the model's own initialiser (norm gains: `perturb_norms`; the
    attention gates: `spread_gates`)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.window_moe import WindowMoeForCausalLM

    if training:
        raise ValueError("no cell trains this family: at 14 bytes a parameter "
                         "the floor's 8 experts a layer is a deployment "
                         "nobody runs (EP32)")
    paddle.seed(seed)
    # the chip's own generator makes 1.7 B seeded values at memory speed
    # (families/mla_moe.py); the reference reads the SAME arrays
    with jax.default_prng_impl("rbg"):
        model = WindowMoeForCausalLM(model_config(cfg))
    perturb_norms(model, seed)
    spread_gates(model, seed)
    model.eval()
    return model


def _sizes_of(c) -> dict:
    return {"layers": [
                {"heads": heads,
                 "window": (c.sliding_window if kind == "sliding_attention"
                            else None),
                 "rope": dict(c.rope_parameters[kind])}
                for kind, heads in zip(c.layer_types,
                                       c.num_attention_heads_per_layer)],
            "kv_heads": c.num_key_value_heads, "head_dim": c.head_dim,
            "eps": float(c.rms_norm_eps), "top_k": c.num_experts_per_tok,
            "scale": float(c.moe_routed_scaling_factor),
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
    model's arrays (q, k, v stay fused, and gate and up, as the reference
    takes them; the experts one at a time from their stacks): no second
    copy is made."""
    sd = {k: v._value for k, v in model.state_dict().items()}
    layers = []
    for i, layer in enumerate(model.model.layers):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        w = {"g_in": sd[p + "input_layernorm.weight"],
             "g_post": sd[p + "post_attention_layernorm.weight"],
             "w_qkv": sd[a + "qkv_proj.weight"],
             "w_g": sd[a + "gate_proj.weight"], "w_o": sd[a + "o_proj.weight"]}

        def ffn(q):
            return (sd[q + "gate_up_proj.weight"], sd[q + "down_proj.weight"])

        if layer.dense:
            w["w_gate_up"], w["w_down"] = ffn(p + "mlp.")
        else:
            w["w_router"] = sd[p + "mlp.gate.weight"]
            w["shared"] = ffn(p + "mlp.shared_experts.")
            w["experts"] = _Experts(sd[p + "mlp.gate_up"], sd[p + "mlp.down"])
        layers.append(w)
    return {"embed": sd["model.embed_tokens.weight"], "layers": layers,
            "norm": sd["model.norm.weight"], "head": sd["lm_head.weight"]}


def routing_agreement(model, weights, sizes, ids, reference, route=None) -> tuple:
    """(share, pairs): over the (token, expert layer) pairs of ONE sequence
    `ids`, the share for which the PROGRAM's router (`models.experts.route`
    with this model's softmax scoring, the function its macro-step and its
    prefill program call), handed the REFERENCE's own router input m of that
    layer, chooses the experts the reference chooses.  Both see the same
    numbers, so rounding upstream of the router plays no part: a float32
    router at highest precision agrees on every pair but an exact tie.  With
    `sizes["dtype"]` (or `sizes["router_dtype"]`) lowered it is the reference
    that is run in the lower type.  `route`: another router with the
    program's signature, in its place (a control)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if route is None:
        from paddle_tpu.models.experts import route

    c = model.config
    pick = jax.jit(lambda m, w: route(
        m, w, top_k=c.num_experts_per_tok, scale=c.moe_routed_scaling_factor,
        normalize=c.norm_topk_prob, scoring=c.router_scoring)[0])
    probe = []
    reference.hidden(weights, sizes, ids, probe)
    layers = [layer for layer in model.model.layers if not layer.dense]
    same = [np.asarray((jnp.sort(pick(m, layer.mlp.gate.weight._value), -1)
                        == jnp.sort(chosen, -1)).all(-1))
            for layer, (_gap, _edge, chosen, m) in zip(layers, probe)]
    same = np.concatenate(same)
    return float(same.mean()), int(same.size)
