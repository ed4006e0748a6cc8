"""The lowered programs of the serving engines, for comparing two trees.

Lowers the decode macro-step and one prefill program of five small engines
(dense GQA, latent attention at pangu's geometry, window / full attention at
laguna's, convolved latent at zaya's, sparse / window latent attention at
dots3's widths with a top-256 indexer, so that its 1,024-token prefill runs
the sparse forward) for a DESCRIBED v5e — no chip
attached, Mosaic steered on, the v5e's tile table — and writes one line a
program: its name and the sha256 of its StableHLO text, each Mosaic kernel's
body printed without its source locations (a kernel body carries the file
and line of every op, which moving a function changes and nothing else).
Run it once on each tree, then compare:

    JAX_PLATFORMS=cpu python tools/lowered_programs.py --tree <checkout A> --out a.json
    JAX_PLATFORMS=cpu python tools/lowered_programs.py --tree <checkout B> --out b.json
    python tools/lowered_programs.py --compare a.json b.json

`--tree` is the checkout whose `paddle_tpu` is imported (this one if not
given); `--compare` exits 1 when a program differs and prints which.  A few
minutes on a CPU for the ten programs.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _engines():
    import paddle_tpu as paddle
    from paddle_tpu.models.cca_moe import CcaMoeConfig, CcaMoeForCausalLM
    from paddle_tpu.models.dsa_moe import DsaMoeForCausalLM, dsa_moe_tiny
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    from paddle_tpu.models.mla_moe import MlaMoeForCausalLM, mla_moe_tiny
    from paddle_tpu.models.window_moe import (WindowMoeForCausalLM,
                                              window_moe_tiny)

    made = (
        ("dense", 16, 128, lambda: LlamaForCausalLM(llama_tiny(
            dtype="bfloat16", vocab_size=512, num_attention_heads=16,
            num_key_value_heads=8, hidden_size=256))),
        ("latent", 128, 1024, lambda: MlaMoeForCausalLM(mla_moe_tiny(
            hidden_size=256, num_hidden_layers=2, num_attention_heads=128,
            q_lora_rank=64, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128,
            max_position_embeddings=16384, dtype="bfloat16",
            held_experts=(2, 4)))),
        ("window", 128, 1024, lambda: WindowMoeForCausalLM(window_moe_tiny(
            hidden_size=256, num_attention_heads_per_layer=(48, 72, 72, 72, 48),
            num_key_value_heads=8, head_dim=128, sliding_window=512,
            max_position_embeddings=16384, dtype="bfloat16",
            held_experts=(2, 6)))),
        ("cca", 128, 1024, lambda: CcaMoeForCausalLM(CcaMoeConfig(
            num_hidden_layers=2, vocab_size=4096, num_experts=4))),
        ("sparse", 128, 1024, lambda: DsaMoeForCausalLM(dsa_moe_tiny(
            hidden_size=256, num_attention_heads=128, q_lora_rank=64,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, swa_num_attention_heads=64, swa_q_lora_rank=64,
            swa_kv_lora_rank=512, swa_qk_nope_head_dim=192,
            swa_qk_rope_head_dim=64, swa_v_head_dim=128,
            sliding_window_size=513, index_n_heads=64, index_head_dim=128,
            index_topk=256, max_position_embeddings=16384, dtype="bfloat16",
            held_experts=(2, 4)))),
    )
    for name, block_size, prompt, build in made:
        paddle.seed(0)
        model = build()
        model.eval()
        yield name, model, block_size, prompt


def _mosaic_body(b64: str) -> str:
    """A Mosaic kernel's serialized module, printed without locations."""
    from jax._src.lib.mlir import ir

    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(b64))
        return module.operation.get_asm(enable_debug_info=False)


def normalized(text: str) -> str:
    """StableHLO text with each Mosaic body replaced by the hash of its
    location-free form (the rest of its configuration kept)."""
    def one(match):
        cfg = json.loads(match.group(1).replace("\\22", '"'))
        body = cfg["custom_call_config"].pop("body")
        digest = hashlib.sha256(_mosaic_body(body).encode()).hexdigest()
        return f"MOSAIC<{digest}|{json.dumps(cfg, sort_keys=True)}>"

    return re.sub(r'backend_config = "(\{\\22custom_call_config.*?\})"', one,
                  text)


def lower_all(tree: str) -> dict:
    """{program: sha256 of its normalized text} for the engines of `tree`."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, tree)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu import serving
    from paddle_tpu.ops import _pl_utils, autotune

    _pl_utils.on_tpu = lambda: True
    autotune.device_kind_slug = lambda device=None: "tpu_v5_lite"
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree_of_arrays):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree_of_arrays)

    out = {}
    with jax.default_matmul_precision("default"):
        for name, model, bs, s in _engines():
            eng = serving.GenerationEngine(model, max_batch=8, block_size=bs,
                                           num_blocks=8 * 67)
            lowered = {"step": eng._build_step(8).lower(
                *described(eng._step_avals()))}
            lowered["prefill"] = eng._prefill_program(s, 0).lower(
                described([t._value for t in eng._state]),
                jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=one), None)
            for kind, low in lowered.items():
                text = normalized(low.as_text())
                out[f"{name}.{kind}"] = hashlib.sha256(
                    text.encode()).hexdigest()
                print(f"{name}.{kind} {out[f'{name}.{kind}'][:16]}",
                      flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=HERE)
    ap.add_argument("--out")
    ap.add_argument("--compare", nargs=2, metavar=("A", "B"))
    a = ap.parse_args(argv)
    if a.compare:
        with open(a.compare[0]) as f:
            left = json.load(f)
        with open(a.compare[1]) as f:
            right = json.load(f)
        differ = sorted(k for k in set(left) | set(right)
                        if left.get(k) != right.get(k))
        for k in sorted(set(left) | set(right)):
            print(k, "DIFFERS" if k in differ else "equal")
        return 1 if differ else 0
    hashes = lower_all(os.path.abspath(a.tree))
    if a.out:
        with open(a.out, "w") as f:
            json.dump(hashes, f, indent=1, sort_keys=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
