"""Dense decoder with grouped-query attention, RMS norm, rotary positions and
a SwiGLU MLP (Mistral-7B, InternLM2, ...): the program runs it through
`paddle_tpu.models.llama.LlamaConfig`, so every kernel, the norm, the rotary
code and the MLP are shared between such configurations.  The reference is
`perfbench.reference`.
"""

from __future__ import annotations

REFERENCE = "perfbench.reference"
_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}


def check(cfg: dict):
    """Refuse what the program's model cannot express instead of running
    something else under the configuration's name."""
    if cfg.get("head_dim") not in (None, cfg["hidden_size"]
                                   // cfg["num_attention_heads"]):
        raise ValueError("LlamaConfig has no head_dim apart from "
                         "hidden_size / num_attention_heads")
    if cfg.get("sliding_window") not in (None, 0):
        raise ValueError("LlamaConfig has no sliding window")
    if cfg.get("bias") or cfg.get("attention_bias"):
        raise ValueError("LlamaConfig has no projection bias")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("LlamaConfig's MLP is SwiGLU (silu)")
    if cfg["torch_dtype"] not in _DTYPES:
        raise ValueError(f"unknown dtype {cfg['torch_dtype']!r}")


def build(cfg: dict, seed: int, training: bool):
    """The program's model with weights made on the default device from the
    seed by the model's own initialiser."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    check(cfg)
    paddle.seed(seed)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=_DTYPES[cfg["torch_dtype"]]))
    if not training:
        model.eval()
    return model


def loss_fn(model, ids, labels):
    return model(ids, labels=labels)[0]


def reference_sizes(cfg: dict) -> dict:
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"])}


def reference_weights(model) -> dict:
    """The model's weights in the reference's layout (device arrays in the
    served type; the reference casts).  They alias the model's own arrays:
    read them before a train step donates and overwrites its state."""
    import jax.numpy as jnp

    sd = {k: v._value for k, v in model.state_dict().items()}
    layers = []
    for i in range(model.config.num_hidden_layers):
        p = f"model.layers.{i}."
        gate_up = sd[p + "mlp.gate_up_proj.weight"]
        f = gate_up.shape[1] // 2
        layers.append({
            "wq": sd[p + "self_attn.q_proj.weight"],
            "wk": sd[p + "self_attn.k_proj.weight"],
            "wv": sd[p + "self_attn.v_proj.weight"],
            "wo": sd[p + "self_attn.o_proj.weight"],
            "w_gate": jnp.array(gate_up[:, :f]),
            "w_up": jnp.array(gate_up[:, f:]),
            "w_down": sd[p + "mlp.down_proj.weight"],
            "ln1": sd[p + "input_layernorm.weight"],
            "ln2": sd[p + "post_attention_layernorm.weight"],
        })
    embed = sd["model.embed_tokens.weight"]
    head = embed.T if model.lm_head is None else sd["lm_head.weight"]
    return {"embed": embed, "layers": layers,
            "norm": sd["model.norm.weight"], "head": head}
