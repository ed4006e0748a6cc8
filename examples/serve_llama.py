"""Serving: paged-KV incremental decode + weight-only int8 head."""

import os
import sys

if "--cpu" in sys.argv:  # CPU smoke on a machine that has a TPU
    sys.argv.remove("--cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny


def main():
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny(dtype="float32"))
    model.eval()
    prompt = paddle.to_tensor(np.random.default_rng(0).integers(0, 1024, (2, 12)).astype(np.int32))
    out = model.generate(prompt, max_new_tokens=16, cache="paged", block_size=16)
    print("generated:", np.asarray(out._value))


if __name__ == "__main__":
    main()
