"""Operations and bytes of the family with attention in a convolved latent, an
MLP router carried across depth and top-1 experts with a skip choice
(`families/cca_moe.py`), from a configuration file's sizes: what the ALGORITHM
requires, as `perfbench/roofline.py` counts for the dense family.  Unlike the
other families' counts, every parameter is counted, vectors too (the issue
that defined the configuration reckons 207,583,763 a layer at the published
widths, and the test holds this module to the built model's own count).

`cfg` holds every layer whole: `num_experts` experts of `moe_intermediate_size`
(`share.held_experts` of them where a share is stated), the whole vocabulary,
a tied head.
"""

from __future__ import annotations

from perfbench.roofline import peaks


def held_experts(cfg) -> int:
    return cfg.get("share", {}).get("held_experts", cfg["num_experts"])


def channels(cfg) -> int:
    """Width of z = [q~ ; k~], what the convolutions mix."""
    return ((cfg["num_attention_heads"] + cfg["num_key_value_heads"])
            * cfg["head_dim"])


def attention_matrices(cfg) -> int:
    """W_qk, [W_v1 | W_v2], W_o."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return (h * channels(cfg) + h * cfg["num_key_value_heads"] * d
            + cfg["num_attention_heads"] * d * h)


def conv_params(cfg) -> int:
    """Depthwise (a0, a1, b1), grouped (B0, B1: a [d, d] a head each) and b2."""
    c, d = channels(cfg), cfg["head_dim"]
    return 3 * c + 2 * (c // d) * d * d + c


def router_params(cfg) -> int:
    """W_dn and b_dn, gamma, g_r, W_1, b_1, W_2, b_2, W_3 (no bias), beta."""
    h, r, out = cfg["hidden_size"], cfg["router_hidden_size"], \
        cfg["num_experts"] + 1
    return h * r + r + r + r + 2 * (r * r + r) + r * out + out


def vector_params(cfg) -> int:
    """Two norm gains, the two scaled residuals' four vectors each, tau."""
    return 10 * cfg["hidden_size"] + cfg["num_key_value_heads"]


def expert_params(cfg) -> int:
    """One expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_fixed_params(cfg) -> int:
    """What every token meets in a layer whatever the routing."""
    return (attention_matrices(cfg) + conv_params(cfg) + router_params(cfg)
            + vector_params(cfg))


def layer_params(cfg) -> int:
    return layer_fixed_params(cfg) + held_experts(cfg) * expert_params(cfg)


def model_params(cfg) -> int:
    """The layers, the table (tied: once) and the final norm."""
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def weight_bytes(cfg, bytes_per_param=2) -> int:
    return model_params(cfg) * bytes_per_param


def kv_bytes_per_token(cfg, bytes_per_value=2) -> int:
    """K and V of one token over all layers (the paged class)."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * bytes_per_value)


def state_bytes_per_slot(cfg, bytes_per_value=2) -> int:
    """z_{t-1}, c_{t-1} and n_{t-1} W_v2 of one slot over all layers (the
    state class)."""
    return cfg["num_hidden_layers"] * bytes_per_value * (
        2 * channels(cfg) + cfg["num_key_value_heads"] // 2 * cfg["head_dim"])


def fixed_params(cfg) -> int:
    """Everything a token step reads outside the experts: the layers' fixed
    part, the final norm and the table once (the head; the embedding rows are
    a gather)."""
    return (cfg["num_hidden_layers"] * layer_fixed_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def multiplied_params(cfg) -> int:
    """Of a layer's fixed part, what a token is MULTIPLIED with (2 FLOPs a
    parameter): the matrices of attention, the grouped convolution, the
    router; vectors and the depthwise taps cost a FLOP or two a value and
    are left out."""
    h, r = cfg["hidden_size"], cfg["router_hidden_size"]
    c, d = channels(cfg), cfg["head_dim"]
    return (attention_matrices(cfg) + 2 * (c // d) * d * d
            + h * r + 2 * r * r + r * (cfg["num_experts"] + 1))


def prefill_flops(cfg, s: int, held_share: float = 1.0) -> float:
    """FLOPs the prefill of ONE prompt of s tokens requires: 2 per multiplied
    parameter per token (an expert for `held_share` of the tokens a layer:
    the rest chose skip, or an expert not held; the head once, for the last
    position), plus attention's QK^T and PV over the causal pairs."""
    layers = cfg["num_hidden_layers"]
    per_token = layers * (multiplied_params(cfg)
                          + held_share * expert_params(cfg))
    fl = 2.0 * per_token * s + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    fl += (layers * 2 * 2.0 * cfg["num_attention_heads"] * cfg["head_dim"]
           * (s * (s + 1) // 2))
    return fl


def prefill_min_s(cfg, facts, device_kind) -> float:
    """Least seconds of the MEAN admission among `facts["admitted_prompt_
    lens"]` (the prompts admitted in the traced part of the window): its
    FLOPs at the bf16 peak (an 8,192-token prompt is 7 TFLOP against 7.7 GB
    of weights: compute-bound)."""
    lens = facts["admitted_prompt_lens"]
    fl = sum(prefill_flops(cfg, s, facts.get("moe_prefill_held_share", 1.0))
             for s in lens) / len(lens)
    return fl / peaks(device_kind)["flops_bf16"]


def decode_token_step_min_s(cfg, facts, device_kind) -> float:
    """Least seconds of one decode token step (one token for each of
    `facts["rows"]` resident rows).

    Bytes it must read: everything outside the experts once (the table once,
    as the tied head; the embedding is a gather of `rows` rows), the weights
    of the held experts that received at least one token (`facts["moe_touched_
    per_layer_step"]`, the program's own count, a mean over the window's
    layer steps), K and V of every live token (`facts["live_kv_tokens"]`) and
    the rows' state a slot, read and written.  FLOPs: 2 per multiplied
    parameter per row (an expert by the assignments it received, `facts["moe_
    held_per_layer_step"]`; the head for every row) plus attention's 2 x 2 x
    heads x d per live position per layer.  The larger of bytes over HBM
    bandwidth and FLOPs over the bf16 peak."""
    pk = peaks(device_kind)
    h, layers, rows = cfg["hidden_size"], cfg["num_hidden_layers"], facts["rows"]
    by = 2 * (fixed_params(cfg) + rows * h
              + layers * facts["moe_touched_per_layer_step"] * expert_params(cfg))
    by += facts["live_kv_tokens"] * kv_bytes_per_token(cfg)
    by += 2 * rows * state_bytes_per_slot(cfg)
    fl = 2.0 * (rows * (layers * multiplied_params(cfg)
                        + h * cfg["vocab_size"])
                + layers * facts["moe_held_per_layer_step"] * expert_params(cfg))
    fl += (layers * 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
           * facts["live_kv_tokens"])
    return max(by / pk["hbm_bytes_s"], fl / pk["flops_bf16"])
