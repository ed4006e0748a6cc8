"""paddle_tpu.ops — Pallas TPU kernel library.

This package is the TPU-native analog of the reference's fused CUDA kernels
(paddle/phi/kernels/fusion/gpu/: fused_rope_kernel.cu, fused_layernorm_kernel.cu,
fused_rms_norm .. and paddle/phi/kernels/gpu/flash_attn_kernel.cu).  Each op
ships two implementations:

- a Pallas TPU kernel (MXU/VPU-tiled, VMEM-resident, custom VJP), used when
  running on TPU hardware;
- a pure jax/jnp reference with identical semantics, used on CPU test meshes
  and as the numerics oracle (Pallas kernels are additionally unit-tested in
  interpreter mode against it).

Dispatch is `use_pallas()`: TPU backend by default, overridable via the flag
`FLAGS_use_pallas` (paddle_tpu.set_flags) for A/B benchmarking.  Under a
multi-device mesh (`jax.set_mesh`, which ShardedTrainStep enters) the default
is the XLA implementation: GSPMD refuses to split a Mosaic kernel ("Mosaic
kernels cannot be automatically partitioned") and libtpu registers no custom
partitioner, so until the kernels are shard_mapped the partitioned program
keeps plain XLA ops.  The mesh is part of jax's trace key, so one process can
run both.

This library also plays the role of the reference's KPS tier
(paddle/phi/kernels/primitive/, Backend::KPS — the "write once, run
per-backend" kernel-authoring primitives): Pallas IS the portable
kernel-authoring layer on the XLA stack (same kernel source lowers to TPU
Mosaic or interpret-mode CPU; GPU Triton lowering exists upstream), so no
separate primitive API is reproduced.
"""

from __future__ import annotations

import jax

from paddle_tpu._core import flags as _flags
from paddle_tpu.ops import _pl_utils

_flags.define_flag("FLAGS_use_pallas", "auto", "auto|true|false — Pallas kernel dispatch")
_flags.define_flag("FLAGS_flash_block_q", 0,
                   "flash attention q-block rows override; 0 = consult the "
                   "autotune cache, then the 128 default")
_flags.define_flag("FLAGS_flash_block_k", 0,
                   "flash attention k-block rows override; 0 = consult the "
                   "autotune cache, then the 128 default")
_flags.define_flag("FLAGS_use_autotune_cache", True,
                   "consult ops/tuned/<device_kind>.json for Pallas tile configs")
_flags.define_flag("FLAGS_autotune_cache_dir", "",
                   "where `python -m paddle_tpu.ops.autotune` saves tuned tiles "
                   "(empty = the package's ops/tuned/ seed directory)")


def use_pallas() -> bool:
    v = str(_flags.flag("FLAGS_use_pallas")).lower()
    if v in ("true", "1"):
        return True
    if v in ("false", "0"):
        return False
    return _pl_utils.on_tpu() and jax.sharding.get_abstract_mesh().size <= 1


from .flash_attention import flash_attention, flash_attention_reference  # noqa: E402,F401
from .fused_norm import fused_rms_norm, fused_layer_norm  # noqa: E402,F401
from .fused_rope import fused_rotary_position_embedding  # noqa: E402,F401
from .swiglu import swiglu  # noqa: E402,F401
from .matmul_epilogue import matmul_bias_act  # noqa: E402,F401
from .ring_attention import ring_attention, ulysses_attention  # noqa: E402,F401
