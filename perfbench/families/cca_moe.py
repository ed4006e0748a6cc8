"""Attention in a convolved latent with a one-token value shift, an MLP router
carried across depth, top-1 of a few wide experts with a skip choice, scaled
residuals (ZAYA1-8B's block; Zyphra's family): the program runs it through
`paddle_tpu.models.cca_moe`, served by the same `GenerationEngine` as the other
configurations, its cache in two classes (pages for K and V, state a slot for
what the convolutions and the shift keep of the previous token).  The
reference is `perfbench.reference_cca_moe`; operations and bytes are in
`perfbench.roofline_cca_moe`, whose functions this module registers with
`perfbench.roofline.FUNCTIONS` when it is imported (the harness imports the
family before it reads any metric).

A configuration of this family holds every layer WHOLE (all `num_experts`
experts, the whole vocabulary); `share.first_expert` / `share.held_experts`
may name a contiguous range of experts instead, which the CPU tests use.

`paddle_tpu.models.cca_moe` is imported inside the functions that need it, but
for one line at the top that makes a program WITHOUT that module fail the
family's import at once, before any weight is made.
"""

from __future__ import annotations

import importlib.util

from perfbench import roofline, roofline_cca_moe
from perfbench.families.mla_moe import perturb_norms

if importlib.util.find_spec("paddle_tpu.models.cca_moe") is None:
    raise ImportError("this program has no paddle_tpu.models.cca_moe: the "
                      "cca_moe family cannot run on it")

REFERENCE = "perfbench.reference_cca_moe"
_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}
# every key of a configuration file this family reads or knowingly ignores
_KNOWN = {
    # the file's own
    "name", "family", "source", "torch_dtype", "reduced", "published", "share",
    "deployment", "assumed",
    # the model's, read by model_config
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "cca_time0", "cca_time1",
    "partial_rotary_factor", "rope_parameters", "num_experts",
    "num_experts_per_tok", "moe_intermediate_size", "router_hidden_size",
    "max_position_embeddings", "rms_norm_eps",
    # checked against the one value the model implements
    "model_type", "attention_bias", "hidden_act", "layer_types",
    "lm_head_bias", "sliding_window", "tie_word_embeddings",
}

roofline.FUNCTIONS.setdefault("cca_moe_decode_token_step_min_s",
                              roofline_cca_moe.decode_token_step_min_s)
roofline.FUNCTIONS.setdefault("cca_moe_prefill_min_s",
                              roofline_cca_moe.prefill_min_s)


def held_experts(cfg: dict) -> tuple:
    """(first, count) of the experts this chip holds."""
    share = cfg.get("share", {})
    return (share.get("first_expert", 0),
            share.get("held_experts", cfg["num_experts"]))


def routed_experts(cfg: dict) -> int:
    return cfg["num_experts"]


def check(cfg: dict):
    """Refuse what the program's model cannot express instead of running
    something else under the configuration's name."""
    unknown = sorted(set(cfg) - _KNOWN)
    if unknown:
        raise ValueError(f"keys this family does not implement: {unknown}")
    n = cfg["num_hidden_layers"]
    if cfg.get("attention_bias") or cfg.get("lm_head_bias"):
        raise ValueError("CcaMoeConfig has no projection or head bias")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the experts are SwiGLU (silu)")
    if not cfg.get("tie_word_embeddings", True):
        raise ValueError("CcaMoeForCausalLM ties its head to the embedding")
    if cfg.get("sliding_window") is not None:
        raise ValueError("every layer attends all positions (no window)")
    if any(t != "hybrid" for t in cfg.get("layer_types", [])[:n]):
        raise ValueError("every layer is 'hybrid' (attention, then experts)")
    if len(cfg.get("layer_types", [None] * n)) < n:
        raise ValueError(f"layer_types has fewer than {n} entries")
    rope = cfg["rope_parameters"]["hybrid"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not built")
    if float(rope["partial_rotary_factor"]) != float(
            cfg.get("partial_rotary_factor", rope["partial_rotary_factor"])):
        raise ValueError("partial_rotary_factor and rope_parameters disagree")
    if cfg["torch_dtype"] not in _DTYPES:
        raise ValueError(f"unknown dtype {cfg['torch_dtype']!r}")
    first, count = held_experts(cfg)
    if first + count > cfg["num_experts"]:
        raise ValueError(f"held experts {first}..{first + count} lie outside "
                         f"the router's {cfg['num_experts']}")


def model_config(cfg: dict):
    """The program's config for this configuration."""
    from paddle_tpu.models.cca_moe import CcaMoeConfig

    check(cfg)
    rope = cfg["rope_parameters"]["hybrid"]
    return CcaMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], cca_time0=cfg["cca_time0"],
        cca_time1=cfg["cca_time1"],
        partial_rotary_factor=float(rope["partial_rotary_factor"]),
        rope_theta=float(rope["rope_theta"]), num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        router_hidden_size=cfg["router_hidden_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], dtype=_DTYPES[cfg["torch_dtype"]],
        held_experts=held_experts(cfg))


# what the model's own initialiser leaves at 1 or at 0, where a term that is
# left out or misplaced would compare equal: (name's end, mean, spread)
_DRAWS = (
    ("res_scale", 1.0, 0.1), ("out_scale", 1.0, 0.1),
    ("res_bias", 0.0, 0.01), ("out_bias", 0.0, 0.01),
    ("conv0_bias", 0.0, 0.3), ("conv1_bias", 0.0, 0.3),
    ("temperature", 0.0, 0.3), ("router.gamma", 0.7, 0.1),
    ("router.down.bias", 0.0, 0.1), ("router.fc1.bias", 0.0, 0.1),
    ("router.fc2.bias", 0.0, 0.1), ("router.balance", 0.0, 0.05),
)
# the router's last matrix drawn this many times wider than the initialiser's,
# and CENTRED (each outcome's weights sum to zero over the hidden units): a
# trained top-1 router is decisive (its chosen probability is the weight of
# the expert's output) and balanced (that is what its balancing bias is
# trained for); an initialiser's is a coin with 17 sides at 0.06 each, and
# widened uncentred it hands nearly every token to the same two experts,
# because the hidden units' GELU activations share a positive mean (the
# second hidden layer's matrix is centred too, for the same reason).  With
# both, 512 tokens spread over all 17 outcomes at every depth and 32 rows
# touch about 12 of 16 experts (13.7 were the router uniform)
ROUTER_SHARPNESS = 6.0
# the table's standard deviation.  The residual stream starts at the token's
# own row and every sublayer adds a few tenths to it, so at 2.0 the stream
# stays the TOKEN'S through all the layers: the router sees different tokens
# differently at every depth (at the initialiser's 0.003 the stream is the
# attention's running average, alike for every token, and two experts serve
# all), and the map from weights to logits is well conditioned (with the
# stream carried by peaked attention instead, leaving out 5% of the skip
# choices moved every logit by 4 sigma, and so did bfloat16: my chip run, PR
# 34; at 2.0 the mechanisms left out move a row by 0.8-1.9 sigma and a
# bfloat16 reference by 0.36: CPU, 12 layers at the published widths).  The
# head is tied, so a stream that is the token's own row would predict the
# token itself for ever: the FINAL norm's gain is drawn N(0, 1), signs and
# all, which makes the next token a seeded function of the stream instead
EMBEDDING_SCALE = 2.0


def perturb(model, seed: int):
    """Norm gains (`perturb_norms`; the final norm's N(0, 1)), every scale
    near 1 and every bias, tau, gamma and beta away from 0, a table that
    carries the stream and a decisive, balanced router, all from the seed."""
    import jax.numpy as jnp
    import numpy as np

    perturb_norms(model, seed)
    draw = np.random.default_rng([int(seed) % (2 ** 63), 13])
    for name, p in model.state_dict().items():
        for end, mean, spread in _DRAWS:
            if name.endswith(end):
                p._bind(jnp.asarray(
                    mean + spread * draw.standard_normal(p.shape),
                    p._value.dtype))
        if name == "model.norm.weight":
            p._bind(jnp.asarray(draw.standard_normal(p.shape), p._value.dtype))
        if name.endswith("embed_tokens.weight"):
            w = p._value
            p._bind((w * (EMBEDDING_SCALE / jnp.std(w.astype(jnp.float32)))
                     ).astype(w.dtype))
        if name.endswith(("router.fc2.weight", "router.out.weight")):
            w = p._value.astype(jnp.float32)             # [..., in, out]
            w = w - w.mean(axis=-2, keepdims=True)
            if name.endswith("out.weight"):
                w = w * ROUTER_SHARPNESS
            p._bind(w.astype(p._value.dtype))


def build(cfg: dict, seed: int, training: bool):
    """The program's model with weights made on the default device from the
    seed by the model's own initialiser, then `perturb`."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.cca_moe import CcaMoeForCausalLM

    if training:
        raise ValueError("no cell trains this family: training with experts "
                         "is ROADMAP.md R1's")
    paddle.seed(seed)
    # the chip's own generator makes 3.9 B seeded values at memory speed
    # (families/mla_moe.py); the reference reads the SAME arrays
    with jax.default_prng_impl("rbg"):
        model = CcaMoeForCausalLM(model_config(cfg))
    perturb(model, seed)
    model.eval()
    return model


def _sizes_of(c) -> dict:
    return {"heads": c.num_attention_heads, "kv_heads": c.num_key_value_heads,
            "head_dim": c.head_dim, "eps": float(c.rms_norm_eps),
            "theta": float(c.rope_theta),
            "rotary": int(round(c.head_dim * c.partial_rotary_factor)),
            "experts": c.num_experts, "held": tuple(c.held)}


def reference_sizes(cfg: dict) -> dict:
    return _sizes_of(model_config(cfg))


class _Experts:
    """One layer's held experts' (w_gate_up, w_down) pairs in order, each cut
    from the model's stacks when the reference asks for it and dropped when
    it moves on: never a second copy of all of them."""

    def __init__(self, gate_up, down, base, count):
        self._gate_up, self._down = gate_up, down
        self._base, self._count = base, count

    def __len__(self):
        return self._count

    def __iter__(self):
        return ((self._gate_up[self._base + e], self._down[self._base + e])
                for e in range(self._count))


def reference_weights(model) -> dict:
    """The model's weights in the reference's layout.  The table and the
    experts ALIAS the model's arrays (the experts cut one at a time from
    their stacks); a layer's other weights, 6 M values, are that layer's
    slices of the scanned stack."""
    sd = {k: v._value for k, v in model.state_dict().items()}
    count = model.config.held[1]
    layers = []
    for i in range(model.config.num_hidden_layers):
        at = lambda key, i=i: sd["model.layers." + key][i]  # noqa: E731
        res = lambda p: tuple(at(f"{p}.{k}") for k in (  # noqa: E731
            "res_scale", "res_bias", "out_scale", "out_bias"))
        layers.append({
            "g_a": at("input_layernorm.weight"),
            "g_m": at("post_attention_layernorm.weight"),
            "w_qk": at("self_attn.qk_proj.weight"),
            "w_v": at("self_attn.v_proj.weight"),
            "w_o": at("self_attn.o_proj.weight"),
            "conv0_w": at("self_attn.conv0_weight"),
            "conv0_b": at("self_attn.conv0_bias"),
            "conv1_w": at("self_attn.conv1_weight"),
            "conv1_b": at("self_attn.conv1_bias"),
            "tau": at("self_attn.temperature"),
            "attn_res": res("attn_residual"), "mlp_res": res("mlp_residual"),
            "router": {
                "down_w": at("router.down.weight"),
                "down_b": at("router.down.bias"), "gamma": at("router.gamma"),
                "norm_g": at("router.norm.weight"),
                "w1": at("router.fc1.weight"), "b1": at("router.fc1.bias"),
                "w2": at("router.fc2.weight"), "b2": at("router.fc2.bias"),
                "w3": at("router.out.weight"), "beta": at("router.balance")},
            "experts": _Experts(sd["model.expert_gate_up"],
                                sd["model.expert_down"], i * count, count)})
    return {"embed": sd["model.embed_tokens.weight"], "layers": layers,
            "norm": sd["model.norm.weight"]}


def routing_agreement(model, weights, sizes, ids, reference, route=None) -> tuple:
    """(share, pairs): over the (token, layer) pairs of ONE sequence `ids`,
    the share for which the PROGRAM's router (`models.cca_moe.route_mlp`, the
    function its macro-step and its prefill program call), handed the
    REFERENCE's own router inputs of that layer (m and the carried r), makes
    the reference's choice.  Both see the same numbers, so rounding upstream
    of the router plays no part.  With `sizes["dtype"]` (or
    `sizes["router_dtype"]`) lowered it is the reference that is run in the
    lower type.  `route`: another router with the program's signature, in
    its place (a control)."""
    import jax
    import numpy as np

    if route is None:
        from paddle_tpu.models.cca_moe import route_mlp as route

    eps = model.config.rms_norm_eps
    pick = jax.jit(lambda m, r, w: route(m, r, w, eps=eps)[0])
    probe = []
    reference.hidden(weights, sizes, ids, probe)
    same = [np.asarray((pick(m, r_prev, w["router"]) == chosen).all(-1))
            for w, (_gap, _edge, chosen, m, r_prev)
            in zip(weights["layers"], probe)]
    same = np.concatenate(same)
    return float(same.mean()), int(same.size)
