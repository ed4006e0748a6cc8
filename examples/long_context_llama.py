"""Long-context LLaMA with context parallelism (ring attention over 'sep').

The sequence is sharded over the 'sep' mesh axis: each rank holds a
contiguous chunk, rope tables are sliced at the rank's global offset, and
K/V shards rotate around the ring over ICI — O(S_local) attention memory
per chip instead of O(S).

Virtual 4-device mesh:  python examples/long_context_llama.py
On a real pod slice drop the jax_platforms override.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"

import numpy as np


def main():
    import jax

    # the CPU backend unless explicitly asked for the TPU: this example's
    # virtual device mesh is a host-platform feature
    if "--tpu" not in sys.argv:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu._core.tensor import Tensor
    from paddle_tpu.distributed.communication import collective_axis_scope
    from paddle_tpu.models.llama import (
        LlamaForCausalLM,
        context_parallel_llama,
        llama_tiny,
    )

    paddle.seed(0)
    W = 4  # sep degree
    cfg = llama_tiny(max_position_embeddings=4096, dtype="float32")
    model = context_parallel_llama(LlamaForCausalLM(cfg), mode="ring")
    model.eval()
    state = list(model.state_dict().values())

    B, S = 1, 2048  # global sequence; each rank sees S/W = 512 tokens
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (B, S)).astype(np.int32)

    mesh = Mesh(np.array(jax.devices()[:W]), ("sep",))

    def body(ids_local, *vals):
        originals = [t._value for t in state]
        try:
            for t, v in zip(state, vals):
                t._bind(v)
            with paddle.no_grad(), collective_axis_scope({"sep": "sep"}):
                return model(Tensor(ids_local))._value
        finally:
            for t, v in zip(state, originals):
                t._bind(v)

    f = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "sep"),) + tuple(P() for _ in state),
        out_specs=P(None, "sep", None), check_vma=False,
    ))
    logits = f(jnp.asarray(ids), *[t._value for t in state])
    print(f"context-parallel logits: {logits.shape} over {W} sequence shards "
          f"({S // W} tokens/chip), finite={bool(jnp.isfinite(logits).all())}")


if __name__ == "__main__":
    main()
