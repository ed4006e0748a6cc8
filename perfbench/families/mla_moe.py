"""Latent-attention decoder with routed experts, a shared expert and sandwich
norms (openPangu-Ultra-MoE-718B's block; DeepSeek-V3's family): the program
runs it through `paddle_tpu.models.mla_moe`, served by the same
`GenerationEngine` as the dense configurations.  The reference is
`perfbench.reference_mla_moe`; operations and bytes are in
`perfbench.roofline_mla_moe`, whose functions this module registers with
`perfbench.roofline.FUNCTIONS` when it is imported (the harness imports the
family before it reads any metric).

A configuration of this family is ONE CHIP'S SHARE of a deployment (its
`share`): `n_routed_experts` counts the experts held here, a contiguous range
starting at `share.first_expert`, while the router keeps `published
.n_routed_experts` outputs; `vocab_size` is the slice of the vocabulary held
here.
"""

from __future__ import annotations

from perfbench import roofline, roofline_mla_moe

REFERENCE = "perfbench.reference_mla_moe"
_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}

roofline.FUNCTIONS.setdefault("mla_moe_decode_token_step_min_s",
                              roofline_mla_moe.decode_token_step_min_s)


def check(cfg: dict):
    """Refuse what the program's model cannot express instead of running
    something else under the configuration's name."""
    if cfg.get("attention_bias"):
        raise ValueError("MlaMoeConfig has no projection bias")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("MlaMoeConfig's FFNs are SwiGLU (silu)")
    if cfg.get("num_key_value_heads", cfg["num_attention_heads"]) \
            != cfg["num_attention_heads"]:
        raise ValueError("latent attention has one latent row a token, "
                         "shared by all heads: no grouped K/V heads")
    if cfg.get("num_nextn_predict_layers"):
        raise ValueError("the next-token-prediction module is not built "
                         "(list num_nextn_predict_layers under reduced, 0)")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("MlaMoeForCausalLM has an untied head")
    if cfg["torch_dtype"] not in _DTYPES:
        raise ValueError(f"unknown dtype {cfg['torch_dtype']!r}")
    routed = roofline_mla_moe.routed_experts(cfg)
    first = cfg.get("share", {}).get("first_expert", 0)
    if first + cfg["n_routed_experts"] > routed:
        raise ValueError(f"held experts {first}..{first + cfg['n_routed_experts']}"
                         f" lie outside the router's {routed}")


def model_config(cfg: dict):
    """The program's config for this chip's share."""
    from paddle_tpu.models.mla_moe import MlaMoeConfig

    check(cfg)
    return MlaMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        n_routed_experts=roofline_mla_moe.routed_experts(cfg),
        n_shared_experts=cfg["n_shared_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        sandwich_norm=cfg["sandwich_norm"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        dtype=_DTYPES[cfg["torch_dtype"]],
        held_experts=(cfg.get("share", {}).get("first_expert", 0),
                      cfg["n_routed_experts"]))


def perturb_norms(model, seed: int):
    """Norm gains of 1 + 0.1 N(0, 1) from the seed: the initialiser leaves
    them at exactly 1, where a gain applied in the wrong place or left out
    would compare equal."""
    import jax.numpy as jnp
    import numpy as np

    draw = np.random.default_rng([int(seed) % (2 ** 63), 7])
    for name, p in model.state_dict().items():
        if name.endswith("norm.weight"):
            g = 1.0 + 0.1 * draw.standard_normal(p.shape)
            p._bind(jnp.asarray(g, p._value.dtype))


def build(cfg: dict, seed: int, training: bool):
    """The program's model with weights made on the default device from the
    seed by the model's own initialiser (norm gains: `perturb_norms`)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.mla_moe import MlaMoeForCausalLM

    if training:
        raise ValueError("no cell trains this family: at 14 bytes a parameter "
                         "its smallest admissible cut is three chips' worth")
    import jax

    paddle.seed(seed)
    # 4.9 B seeded values at the cell's size: the chip's own generator makes
    # them at memory speed, the default counter-based one took 50 s of a run's
    # set-up (PERF.md section 6, PR 27).  Seeded and repeatable on one kind
    # of device, which is all a run compares: the reference reads the SAME
    # arrays.
    with jax.default_prng_impl("rbg"):
        model = MlaMoeForCausalLM(model_config(cfg))
    perturb_norms(model, seed)
    model.eval()
    return model


def _sizes_of(c) -> dict:
    return {"heads": c.num_attention_heads, "nope": c.qk_nope_head_dim,
            "rope": c.qk_rope_head_dim, "v": c.v_head_dim,
            "eps": float(c.rms_norm_eps), "theta": float(c.rope_theta),
            "top_k": c.num_experts_per_tok,
            "scale": float(c.routed_scaling_factor),
            "normalize": bool(c.norm_topk_prob), "held": tuple(c.held)}


def reference_sizes(cfg: dict) -> dict:
    return _sizes_of(model_config(cfg))


def reference_weights(model) -> dict:
    """The model's weights in the reference's layout.  They ALIAS the
    model's arrays (gate and up stay fused, as the reference takes them): a
    second copy of 9.8 GB would not fit beside the first."""
    sd = {k: v._value for k, v in model.state_dict().items()}
    layers = []
    for i, layer in enumerate(model.model.layers):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        w = {"g_in": sd[p + "input_layernorm.weight"],
             "g_pre_mlp": sd[p + "pre_mlp_layernorm.weight"],
             "g_post_attn": sd.get(p + "post_attn_norm.weight"),
             "g_post_mlp": sd.get(p + "post_mlp_norm.weight"),
             "w_qa": sd[a + "q_a_proj.weight"],
             "g_qa": sd[a + "q_a_layernorm.weight"],
             "w_qb": sd[a + "q_b_proj.weight"],
             "w_kva": sd[a + "kv_a_proj.weight"],
             "g_kva": sd[a + "kv_a_layernorm.weight"],
             "w_kvb": sd[a + "kv_b_proj.weight"],
             "w_o": sd[a + "o_proj.weight"]}

        def ffn(q):
            return (sd[q + "gate_up_proj.weight"], sd[q + "down_proj.weight"])

        if layer.dense:
            w["w_gate_up"], w["w_down"] = ffn(p + "mlp.")
        else:
            w["w_router"] = sd[p + "mlp.gate.weight"]
            w["shared"] = ffn(p + "mlp.shared_experts.")
            w["experts"] = [ffn(f"{p}mlp.experts.{e}.")
                            for e in range(len(layer.mlp.experts))]
        layers.append(w)
    return {"embed": sd["model.embed_tokens.weight"], "layers": layers,
            "norm": sd["model.norm.weight"], "head": sd["lm_head.weight"]}


def routing_agreement(model, weights, sizes, ids, reference, route=None) -> tuple:
    """(share, pairs): over the (token, expert layer) pairs of ONE sequence
    `ids`, the share for which the PROGRAM's router — `models.mla_moe.route`,
    the function its macro-step and its prefill program call — handed the
    REFERENCE's own router input m of that layer, chooses the experts the
    reference chooses.  Both see the same numbers, so rounding upstream of
    the router plays no part: a float32 router at highest precision agrees
    on every pair but an exact tie, and one run in bfloat16 (or a float32
    product left at the TPU's default bfloat16 passes) parts from it on
    about three pairs in ten, because a token's k-th and (k+1)-th scores lie
    within bfloat16's rounding of each other that often.  With
    `sizes["dtype"]` lowered it is the reference that is run in the lower
    type, and the share falls as far.  `route`: another router with the
    program's signature, in its place (a control)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if route is None:
        from paddle_tpu.models.mla_moe import route

    c = model.config
    pick = jax.jit(lambda m, w: route(m, w, top_k=c.num_experts_per_tok,
                                      scale=c.routed_scaling_factor,
                                      normalize=c.norm_topk_prob)[0])
    probe = []
    reference.hidden(weights, sizes, ids, probe)
    layers = [layer for layer in model.model.layers if not layer.dense]
    same = [np.asarray((jnp.sort(pick(m, layer.mlp.gate.weight._value), -1)
                        == jnp.sort(chosen, -1)).all(-1))
            for layer, (_gap, _edge, chosen, m) in zip(layers, probe)]
    same = np.concatenate(same)
    return float(same.mean()), int(same.size)
