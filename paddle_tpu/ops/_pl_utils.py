"""Shared pallas helpers.

Mosaic requires every index-map component to be i32 (mixed-width index
tuples are rejected, and in this jax version a 64->32-bit convert inside
Mosaic lowering recurses forever).  `imap` wraps an index map so every
component is cast to int32; together with the framework-wide no-64-bit
policy (_core/dtype.py) this keeps kernel traces Mosaic-cleanly 32-bit —
enforced by the jaxpr scan in tests/test_ops_pallas.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def on_tpu() -> bool:
    """True when jax's default backend is a TPU.  The one place the ops
    tier asks: `use_pallas()` defaults to it, and `interpret()` hands its
    negation to every pallas_call.  Tests that compile kernels for a
    described (not attached) chip monkeypatch THIS function."""
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    """pallas_call's `interpret=`: Mosaic on a TPU, the Pallas interpreter
    everywhere else."""
    return not on_tpu()


def imap(fn):
    def wrapped(*idx):
        out = fn(*idx)
        if not isinstance(out, tuple):
            out = (out,)
        return tuple(jnp.int32(v) for v in out)

    return wrapped
