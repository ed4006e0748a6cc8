"""Peaks of the chips the benchmark knows, and operations and bytes from shapes.

The peaks are the published ones (Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip), keyed by
`jax.devices()[0].device_kind`.  A device that is not in the table is an
error, never a default.  The program keeps a table of its own
(`paddle_tpu/device/peaks.py`, FLOP/s only); this one is the yardstick's and
is not to be edited by a PR that claims a gain.

The counts are what the ALGORITHM requires, from the configuration's sizes:
recomputed operations do not count, causal attention counts the lower
triangle only, and the embedding lookup is a gather, not a matmul.  `cfg` is a
configuration file's dict (Hugging Face key names).
"""

from __future__ import annotations

PEAKS = {
    # device_kind: published peaks of ONE chip
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, 'TPU v5e'"},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                "hbm_bytes": 16e9,
                "source": "Google Cloud documentation, 'TPU v5e'"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add it to perfbench/roofline.py with its source")
    return PEAKS[device_kind]


# ------------------------------------------------------------ parameters

def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_params(cfg) -> int:
    """Parameters of one dense GQA decoder layer: q and o projections, k and
    v projections, gate/up/down, two norm vectors."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * head_dim(cfg)
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    return 2 * h * q + 2 * h * kv + 3 * h * f + 2 * h


def model_params(cfg) -> int:
    """All parameters: layers, embedding, final norm and (untied) head."""
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    head = 0 if cfg.get("tie_word_embeddings") else v * h
    return cfg["num_hidden_layers"] * layer_params(cfg) + v * h + h + head


def matmul_params(cfg) -> int:
    """Parameters that a token is multiplied with: every layer matrix and the
    vocabulary projection (the embedding row is looked up, not multiplied)."""
    h = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * (layer_params(cfg) - 2 * h)
            + cfg["vocab_size"] * h)


def weight_bytes(cfg, bytes_per_param=2) -> int:
    return model_params(cfg) * bytes_per_param


def kv_bytes_per_token(cfg, bytes_per_value=2) -> int:
    """K and V of one token over all layers."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * head_dim(cfg) * bytes_per_value)


# ---------------------------------------------------------- train: FLOPs

def train_flops_per_token(cfg, seq: int) -> float:
    """Forward + backward FLOPs one trained token requires: 6 per matmul
    parameter, plus causal attention (QK^T and PV, a token at position t
    meets t+1 keys, (seq+1)/2 on average; 2 FLOPs a multiply-add, two
    products, backward twice the forward)."""
    attn_width = cfg["num_attention_heads"] * head_dim(cfg)
    attn = cfg["num_hidden_layers"] * 3 * 2 * 2 * attn_width * (seq + 1) / 2
    return 6.0 * matmul_params(cfg) + attn


def train_step_min_s(cfg, facts, device_kind) -> float:
    """Least seconds one train step could take: its required FLOPs at peak."""
    tokens = facts["batch"] * facts["seq"]
    return (tokens * train_flops_per_token(cfg, facts["seq"])
            / peaks(device_kind)["flops_bf16"])


# ---------------------------------------------------------- decode: bytes

def decode_token_step_min_s(cfg, facts, device_kind) -> float:
    """Least seconds one decode step (one token for every resident row) could
    take: it must read every weight once and the K/V of every live token once
    (`facts["live_kv_tokens"]`: mean over the traced steps of the tokens
    resident in the batch).  Memory-bound at these batch sizes, so the bound
    is bytes over HBM bandwidth; the FLOP bound (2 per matmul parameter per
    row) is returned if it is ever the larger."""
    pk = peaks(device_kind)
    by = weight_bytes(cfg) + facts["live_kv_tokens"] * kv_bytes_per_token(cfg)
    fl = 2.0 * matmul_params(cfg) * facts["rows"]
    return max(by / pk["hbm_bytes_s"], fl / pk["flops_bf16"])


FUNCTIONS = {
    "train_step_min_s": train_step_min_s,
    "decode_token_step_min_s": decode_token_step_min_s,
}
