"""Operations and bytes of the latent-attention family with a learned sparse
indexer on its full layers and a latent window on the others
(`families/dsa_moe.py`), from a configuration file's sizes: what the
ALGORITHM requires, as `perfbench/roofline.py` counts for the dense family.
Parameters are every value the model holds: the matrices, the norms' gains
(and the indexer's LayerNorm bias) and the router's correction bias.

`cfg` is one chip's share: `n_routed_experts` experts HELD of `published
.n_routed_experts` routed, `vocab_size` rows of the vocabulary held, the first
`num_hidden_layers` entries of `layer_types`.
"""

from __future__ import annotations

from perfbench.roofline import peaks

SLIDING = "sliding_attention"


def layers(cfg) -> list:
    """(sliding, experts) of each layer that is run."""
    n = cfg["num_hidden_layers"]
    return [(t == SLIDING, i >= cfg["first_k_dense_replace"])
            for i, t in enumerate(cfg["layer_types"][:n])]


def geometry(cfg, sliding: bool) -> dict:
    """The latent attention of one layer type: heads, r_q, r, d_n, d_r, d_v."""
    p = "swa_" if sliding else ""
    return {k: cfg[p + key] for k, key in (
        ("heads", "num_attention_heads"), ("rq", "q_lora_rank"),
        ("r", "kv_lora_rank"), ("dn", "qk_nope_head_dim"),
        ("dr", "qk_rope_head_dim"), ("dv", "v_head_dim"))}


def indexer_params(cfg) -> int:
    """The indexer's query (from the query latent), key (its LayerNorm's
    gain and bias with it) and head weights."""
    h, hi, di = cfg["hidden_size"], cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * hi * di + h * di + 2 * di + h * hi


def attention_params(cfg, sliding: bool) -> int:
    """q_a, q_b, kv_a (latent and the shared rope key), kv_b, o, the
    headwise gate, the two latent norms; a full layer's indexer besides."""
    h, g = cfg["hidden_size"], geometry(cfg, sliding)
    n = g["heads"]
    own = (h * g["rq"] + g["rq"] * n * (g["dn"] + g["dr"]) + h * (g["r"] + g["dr"])
           + g["r"] * n * (g["dn"] + g["dv"]) + n * g["dv"] * h + h * n
           + g["rq"] + g["r"])
    return own if sliding else own + indexer_params(cfg)


def expert_params(cfg) -> int:
    """One expert (routed or shared): gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def routed_experts(cfg) -> int:
    """The router's outputs: all the routed experts of the deployment."""
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def layer_fixed_params(cfg, sliding: bool, experts: bool) -> int:
    """What every token multiplies in a layer whatever the routing:
    attention, the two norms in front of the sublayers, and the dense FFN or
    the shared experts and the router (its correction bias with it)."""
    h = cfg["hidden_size"]
    if not experts:
        return (attention_params(cfg, sliding) + 2 * h
                + 3 * h * cfg["intermediate_size"])
    return (attention_params(cfg, sliding) + 2 * h
            + cfg["n_shared_experts"] * expert_params(cfg)
            + (h + 1) * routed_experts(cfg))


def expert_layers(cfg) -> int:
    return sum(e for _s, e in layers(cfg))


def model_params(cfg) -> int:
    """Held here: the layers (the held experts with them), the embedding, the
    final norm and the (untied) head."""
    return (sum(layer_fixed_params(cfg, s, e) for s, e in layers(cfg))
            + expert_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg)
            + (2 * cfg["vocab_size"] + 1) * cfg["hidden_size"])


def weight_bytes(cfg, bytes_per_param=2) -> int:
    return model_params(cfg) * bytes_per_param


def fixed_params(cfg) -> int:
    """Everything a token step reads outside the routed experts, the head and
    the final norm included (the embedding row is looked up, not
    multiplied)."""
    return (sum(layer_fixed_params(cfg, s, e) for s, e in layers(cfg))
            + (cfg["vocab_size"] + 1) * cfg["hidden_size"])


def row_bytes(cfg, sliding: bool, bytes_per_value=2) -> int:
    """One position's latent row (c, k_rope) in one layer."""
    g = geometry(cfg, sliding)
    return (g["r"] + g["dr"]) * bytes_per_value


def index_key_bytes(cfg, bytes_per_value=2) -> int:
    return cfg["index_head_dim"] * bytes_per_value


def counts(cfg) -> tuple:
    """(full layers, sliding layers)."""
    sliding = sum(s for s, _e in layers(cfg))
    return len(layers(cfg)) - sliding, sliding


def selected_pairs(s: int, top_k: int) -> int:
    """(query, key) pairs a full layer attends over a prompt of s tokens:
    sum over t < s of min(t + 1, k)."""
    head = min(s, top_k)
    return head * (head + 1) // 2 + (s - head) * top_k


def window_pairs(s: int, window: int) -> int:
    """(query, key) pairs a sliding layer attends: sum_t min(t + 1, W)."""
    return selected_pairs(s, window)


def prefill_flops(cfg, s: int, held_share: float | None = None) -> float:
    """FLOPs the prefill of ONE prompt of s tokens requires: 2 per multiplied
    parameter per token (the routed experts by the assignments that chose a
    held expert, `held_share` of tokens x top-k a layer; an even router's
    held / routed where no count is given; the head once, for the last
    position), the indexer's 2 H_I d_I over every causal pair of a full
    layer, and attention's QK^T and PV, 2 N (d_n + d_r + d_v) a pair, over
    the pairs each layer's mask admits: min(t + 1, index_topk) keys a query
    on a full layer, the window's band on a sliding one."""
    if held_share is None:
        held_share = cfg["n_routed_experts"] / routed_experts(cfg)
    h = cfg["hidden_size"]
    per_token = sum(layer_fixed_params(cfg, sl, e) for sl, e in layers(cfg))
    per_token += (expert_layers(cfg) * cfg["num_experts_per_tok"] * held_share
                  * expert_params(cfg))
    fl = 2.0 * per_token * s + 2.0 * h * cfg["vocab_size"]
    for sliding, _e in layers(cfg):
        g = geometry(cfg, sliding)
        per_pair = 2.0 * g["heads"] * (g["dn"] + g["dr"] + g["dv"])
        if sliding:
            fl += per_pair * window_pairs(s, cfg["sliding_window_size"])
        else:
            fl += per_pair * selected_pairs(s, cfg["index_topk"])
            fl += (2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]
                   * s * (s + 1) // 2)
    return fl


def prefill_min_s(cfg, facts, device_kind) -> float:
    """Least seconds of the MEAN admission among `facts["admitted_prompt_
    lens"]` (the prompts admitted in the traced part of the window): its
    FLOPs at the bf16 peak (an 8,192-token prompt is about 20 TFLOP against
    8.2 GB of weights)."""
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
    and in each full layer the indexer key of every live position
    (`facts["live_index_positions"]`) and the latent row of every SELECTED
    position (`facts["selected_positions"]`), in each sliding layer the
    latent row of every live window position (`facts["live_window_
    positions"]`: min(len, W) summed over the rows), each the program's
    count, a mean per token step.  FLOPs: 2 per multiplied parameter per row
    (the routed experts by the assignments they received, `facts["moe_held_
    per_layer_step"]`), the indexer's 2 H_I d_I per live position and the
    absorbed attention's 2 N ((r + d_r) + r) per position read.  The larger
    of bytes over HBM bandwidth and FLOPs over the bf16 peak."""
    pk = peaks(device_kind)
    h, rows = cfg["hidden_size"], facts["rows"]
    full, sliding = counts(cfg)
    experts = expert_layers(cfg)
    by = 2 * (fixed_params(cfg) + rows * h
              + experts * facts["moe_touched_per_layer_step"] * expert_params(cfg))
    by += full * (facts["live_index_positions"] * index_key_bytes(cfg)
                  + facts["selected_positions"] * row_bytes(cfg, False))
    by += sliding * facts["live_window_positions"] * row_bytes(cfg, True)
    fl = 2.0 * (fixed_params(cfg) * rows
                + experts * facts["moe_held_per_layer_step"] * expert_params(cfg))
    fl += full * 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"] \
        * facts["live_index_positions"]
    for is_sliding, n, positions in (
            (False, full, facts["selected_positions"]),
            (True, sliding, facts["live_window_positions"])):
        g = geometry(cfg, is_sliding)
        fl += n * 2.0 * g["heads"] * (2 * g["r"] + g["dr"]) * positions
    return max(by / pk["hbm_bytes_s"], fl / pk["flops_bf16"])
