"""`jax.shard_map` and `jax.lax.axis_size` under the names this package
imports them by (pyproject.toml pins jax>=0.7).  The one adaptation left:
callers pass ``axis_names=None`` / ``check_vma=None`` for "jax's default".
"""

from __future__ import annotations

import jax
from jax.lax import axis_size

__all__ = ["shard_map", "axis_size"]


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=None, **kwargs):
    if axis_names is not None:
        kwargs["axis_names"] = axis_names
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
