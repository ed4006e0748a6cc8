"""Operations and bytes of the latent-attention / routed-expert family
(`families/mla_moe.py`), from a configuration file's sizes: what the ALGORITHM
requires, as `perfbench/roofline.py` counts for the dense family.  Parameters
are the matrices (norm gains, a few thousand values a layer, are left out).

`cfg` is one chip's share: `n_routed_experts` experts HELD of
`published.n_routed_experts` routed, `vocab_size` rows of the vocabulary held.
"""

from __future__ import annotations

from perfbench.roofline import peaks


def attention_params(cfg) -> int:
    """q_a, q_b, kv_a (latent and the shared rope key), kv_b, o."""
    h, n = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (h * rq + rq * n * (dn + dr) + h * (r + dr) + r * n * (dn + dv)
            + n * dv * h)


def expert_params(cfg) -> int:
    """One expert (routed or shared): gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def routed_experts(cfg) -> int:
    """The router's outputs: all the routed experts of the deployment."""
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def dense_layer_params(cfg) -> int:
    return attention_params(cfg) + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_layer_fixed_params(cfg) -> int:
    """What every token step multiplies in an expert layer whatever the
    routing: attention, the shared experts, the router."""
    return (attention_params(cfg) + cfg["n_shared_experts"] * expert_params(cfg)
            + cfg["hidden_size"] * routed_experts(cfg))


def expert_layer_params(cfg) -> int:
    """Fixed part plus the held experts."""
    return expert_layer_fixed_params(cfg) + cfg["n_routed_experts"] * expert_params(cfg)


def layer_counts(cfg):
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def model_params(cfg) -> int:
    """Held here: the layers, the embedding and the (untied) head."""
    dense, expert = layer_counts(cfg)
    return (dense * dense_layer_params(cfg) + expert * expert_layer_params(cfg)
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def latent_bytes_per_token(cfg, bytes_per_value=2) -> int:
    """The cache row (latent and rope key) of one token over all layers."""
    return (cfg["num_hidden_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per_value)


def decode_token_step_min_s(cfg, facts, device_kind) -> float:
    """Least seconds of one decode token step (one token for each of
    `facts["rows"]` resident rows).

    Bytes it must read: every matrix outside the routed experts once (the
    embedding is a gather of `rows` rows, not a pass over the table), the
    weights of the held experts that received at least one token
    (`facts["moe_touched_per_layer_step"]`, the program's own count, a mean
    over the window's expert-layer steps), and the latent row of every live
    token (`facts["live_kv_tokens"]`).  FLOPs: 2 per multiplied parameter
    per row — the routed experts by the assignments they received
    (`facts["moe_held_per_layer_step"]`) — plus the absorbed attention's
    2 x heads x ((r + d_r) + r) per live token per layer.  The bound is the
    larger of bytes over HBM bandwidth and FLOPs over the bf16 peak."""
    pk = peaks(device_kind)
    dense, expert = layer_counts(cfg)
    h, rows = cfg["hidden_size"], facts["rows"]
    fixed = (dense * dense_layer_params(cfg)
             + expert * expert_layer_fixed_params(cfg)
             + cfg["vocab_size"] * h)                      # the head
    by = 2 * (fixed + rows * h
              + expert * facts["moe_touched_per_layer_step"] * expert_params(cfg))
    by += facts["live_kv_tokens"] * latent_bytes_per_token(cfg)
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    fl = 2.0 * (fixed * rows
                + expert * facts["moe_held_per_layer_step"] * expert_params(cfg))
    fl += (2.0 * cfg["num_attention_heads"] * ((r + dr) + r)
           * cfg["num_hidden_layers"] * facts["live_kv_tokens"])
    return max(by / pk["hbm_bytes_s"], fl / pk["flops_bf16"])
