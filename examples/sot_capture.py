"""SOT bytecode capture: guards, graph breaks, replay, fallback.

python examples/sot_capture.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    # the CPU backend unless explicitly asked for the TPU
    if "--tpu" not in sys.argv:
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.jit import to_static
    from paddle_tpu.jit.sot import sot_stats

    @to_static(mode="sot")
    def policy(x, n):
        # python loop: unrolled at capture time, no graph break
        for _ in range(n):
            x = paddle.tanh(x * 1.5)
        # TENSOR predicate: graph break — the prefix segment executes,
        # the branch concretizes, capture resumes per decision path
        if x.sum() > 0:
            return x * 2.0
        return x - 1.0

    t = paddle.to_tensor(np.float32([0.5, 1.0, -0.2]))
    print("positive path:", np.asarray(policy(t, 3)._value))
    print("negative path:", np.asarray(policy(-t, 3)._value))
    print("replay (cached segments):", np.asarray(policy(t, 3)._value))
    print("stats:", sot_stats())


if __name__ == "__main__":
    main()
