"""Operations and bytes of the window / full attention family with routed
experts (`families/window_moe.py`), from a configuration file's sizes: what the
ALGORITHM requires, as `perfbench/roofline.py` counts for the dense family.
Parameters are the matrices (norm gains, a few thousand values a layer, are
left out).

`cfg` is one chip's share: `num_experts` experts HELD of `published
.num_experts` routed, `vocab_size` rows of the vocabulary held, the first
`num_hidden_layers` entries of the per-layer lists (`layer_types`,
`num_attention_heads_per_layer`, `mlp_layer_types`).
"""

from __future__ import annotations

from perfbench.roofline import peaks

SLIDING = "sliding_attention"


def layers(cfg) -> list:
    """(sliding, query heads, sparse) of each layer that is run."""
    n = cfg["num_hidden_layers"]
    return [(t == SLIDING, heads, kind == "sparse") for t, heads, kind in zip(
        cfg["layer_types"][:n], cfg["num_attention_heads_per_layer"][:n],
        cfg["mlp_layer_types"][:n])]


def attention_params(cfg, heads: int) -> int:
    """q, k, v, the per-head gate, o."""
    h, d, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    return h * (heads + 2 * kv) * d + h * heads + heads * d * h


def expert_params(cfg) -> int:
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"]


def routed_experts(cfg) -> int:
    """The router's outputs: all the routed experts of the deployment."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def layer_fixed_params(cfg, heads: int, sparse: bool) -> int:
    """What every token multiplies in a layer whatever the routing:
    attention, and the dense FFN or the shared expert and the router."""
    h = cfg["hidden_size"]
    if not sparse:
        return attention_params(cfg, heads) + 3 * h * cfg["intermediate_size"]
    return (attention_params(cfg, heads) + shared_expert_params(cfg)
            + h * routed_experts(cfg))


def layer_params(cfg, heads: int, sparse: bool) -> int:
    """Fixed part plus the held experts."""
    return (layer_fixed_params(cfg, heads, sparse)
            + (cfg["num_experts"] * expert_params(cfg) if sparse else 0))


def expert_layers(cfg) -> int:
    return sum(sparse for _s, _h, sparse in layers(cfg))


def model_params(cfg) -> int:
    """Held here: the layers, the embedding and the (untied) head."""
    return (sum(layer_params(cfg, heads, sparse)
                for _s, heads, sparse in layers(cfg))
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def weight_bytes(cfg, bytes_per_param=2) -> int:
    return model_params(cfg) * bytes_per_param


def kv_bytes_per_position(cfg, sliding: bool, bytes_per_value=2) -> int:
    """K and V of one position over one cache class's layers: the paged
    class (full layers; every token of a row is live) or the window class
    (sliding layers; min(len, W) positions of a row are live)."""
    n = sum(1 for s, _h, _e in layers(cfg) if s == sliding)
    return n * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_value


def fixed_params(cfg) -> int:
    """Every matrix a token step multiplies outside the routed experts,
    the head included (the embedding row is looked up, not multiplied)."""
    return (sum(layer_fixed_params(cfg, heads, sparse)
                for _s, heads, sparse in layers(cfg))
            + cfg["vocab_size"] * cfg["hidden_size"])


def causal_pairs(s: int, window=None) -> int:
    """(query, key) pairs a causal layer scores over a prompt of s tokens:
    s (s + 1) / 2, or with a window sum_i min(i + 1, W)."""
    if window is None or s <= window:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def prefill_flops(cfg, s: int, held_share: float | None = None) -> float:
    """FLOPs the prefill of ONE prompt of s tokens requires: 2 per multiplied
    parameter per token (the routed experts by the assignments that chose a
    held expert, `held_share` of tokens x top-k a sparse layer; an even
    router's held / routed where no count is given; the head once, for the
    last position), plus attention's QK^T and PV over the pairs each layer's
    mask admits (a sliding layer's are `causal_pairs(s, W)`, never s^2/2)."""
    if held_share is None:
        held_share = cfg["num_experts"] / routed_experts(cfg)
    h, d = cfg["hidden_size"], cfg["head_dim"]
    per_token = sum(layer_fixed_params(cfg, heads, sparse)
                    for _s, heads, sparse in layers(cfg))
    per_token += (expert_layers(cfg) * cfg["num_experts_per_tok"] * held_share
                  * expert_params(cfg))
    fl = 2.0 * per_token * s + 2.0 * h * cfg["vocab_size"]
    for sliding, heads, _e in layers(cfg):
        fl += 2 * 2.0 * heads * d * causal_pairs(
            s, cfg["sliding_window"] if sliding else None)
    return fl


def prefill_min_s(cfg, facts, device_kind) -> float:
    """Least seconds of the MEAN admission among `facts["admitted_prompt_
    lens"]` (the prompts admitted in the traced part of the window): its
    FLOPs at the bf16 peak.  Compute-bound: an 8,192-token prompt is 10 TFLOP
    against 3.4 GB of weights."""
    lens = facts["admitted_prompt_lens"]
    fl = sum(prefill_flops(cfg, s, facts.get("moe_prefill_held_share"))
             for s in lens) / len(lens)
    return fl / peaks(device_kind)["flops_bf16"]


def decode_token_step_min_s(cfg, facts, device_kind) -> float:
    """Least seconds of one decode token step (one token for each of
    `facts["rows"]` resident rows).

    Bytes it must read: every matrix outside the routed experts once (the
    embedding is a gather of `rows` rows), the weights of the held experts
    that received at least one token (`facts["moe_touched_per_layer_step"]`,
    the program's own count, a mean over the window's expert-layer steps),
    K and V of every live token on the paged layers (`facts["live_kv_
    tokens"]`) and of the live window positions on the sliding ones
    (`facts["live_window_positions"]`: the program's count of min(len, W)
    summed over the rows, a mean per token step).  FLOPs: 2 per multiplied
    parameter per row (the routed experts by the assignments they received,
    `facts["moe_held_per_layer_step"]`) plus attention's 2 x 2 x heads x d
    per live position per layer.  The larger of bytes over HBM bandwidth
    and FLOPs over the bf16 peak."""
    pk = peaks(device_kind)
    h, d, rows = cfg["hidden_size"], cfg["head_dim"], facts["rows"]
    experts = expert_layers(cfg)
    by = 2 * (fixed_params(cfg) + rows * h
              + experts * facts["moe_touched_per_layer_step"] * expert_params(cfg))
    by += facts["live_kv_tokens"] * kv_bytes_per_position(cfg, False)
    by += facts["live_window_positions"] * kv_bytes_per_position(cfg, True)
    fl = 2.0 * (fixed_params(cfg) * rows
                + experts * facts["moe_held_per_layer_step"] * expert_params(cfg))
    for sliding, heads, _e in layers(cfg):
        fl += 4.0 * heads * d * (facts["live_window_positions"] if sliding
                                 else facts["live_kv_tokens"])
    return max(by / pk["hbm_bytes_s"], fl / pk["flops_bf16"])
