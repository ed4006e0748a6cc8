"""Device API (reference: python/paddle/device/__init__.py:265 set_device,
cuda stream/event API).  Streams don't exist on the XLA path — ordering is
owned by the compiler — so Stream/Event are compatibility no-ops that still
give correct synchronize() semantics via jax block_until_ready."""

from __future__ import annotations

import jax

from paddle_tpu._core.place import (  # noqa: F401
    CPUPlace,
    CustomPlace,
    Place,
    TPUPlace,
    device_count,
    get_device,
    is_compiled_with_tpu,
    set_device,
)

__all__ = [
    "set_device",
    "get_device",
    "get_all_device_type",
    "get_available_device",
    "device_count",
    "synchronize",
    "hard_sync",
    "time_step_ms",
    "Stream",
    "Event",
    "current_stream",
    "stream_guard",
    "is_compiled_with_tpu",
    "IS_WINDOWS",
]

IS_WINDOWS = False


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_all_custom_device_type():
    return [t for t in get_all_device_type() if t not in ("cpu", "tpu")]


def synchronize(device=None):
    """Block until all launched device work completes: enqueue a trivial
    computation per addressable device and read it back — each device
    executes its stream in order, so the readback implies all previously
    enqueued work finished.
    """
    import jax.numpy as jnp

    jax.effects_barrier()
    for d in jax.local_devices():
        with jax.default_device(d):
            hard_sync(jnp.zeros(8) + 1.0)


def hard_sync(x):
    """Device barrier by readback: fetch one element of `x` to the host.

    The device runs its stream in order, so fetching the last enqueued
    value implies everything enqueued before it has completed.  On a
    locally attached TPU `jax.block_until_ready` waits for the device just
    as well — the same llama_7b-width train step timed 70.7 ms under
    either on a v5e (chip_smoke.py, PR 21) — so new timing code uses
    `block_until_ready`; this one remains for callers that hold a Tensor
    or a pytree and for transports whose ready-future settles at dispatch.

    Accepts a jax array, a Tensor-like with `._value`, or any pytree;
    syncs on the last leaf and returns `x` unchanged.
    """
    leaf = x._value if hasattr(x, "_value") else x
    device_leaves = [
        l for l in jax.tree_util.tree_leaves(leaf)
        if isinstance(l, jax.Array) and l.size
    ]
    if device_leaves:
        # one element of EVERY device leaf (leaves may live on different
        # devices); host numpy / zero-size leaves must not satisfy the
        # barrier
        jax.device_get([l.ravel()[:1] for l in device_leaves])
    return x


def time_step_ms(fn, args=(), *, inner=10, samples=2):
    """Steady-state per-call wall ms of a compiled step function.

    The public timing primitive for benchmarks: each sample readback-syncs
    (`hard_sync`) batches of `inner` and `2*inner` back-to-back calls and
    differences the totals, so the fixed dispatch + readback cost cancels;
    returns the MIN over `samples` — a host noise spike can only inflate a
    sample, so min is the faithful steady-state estimate."""
    from paddle_tpu.ops.autotune import _time_fn

    return min(
        _time_fn(fn, args, warmup=0, iters=1, inner=inner)
        for _ in range(samples)
    )


class Stream:
    """Compatibility stream object; XLA schedules internally."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        jax.effects_barrier()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        jax.effects_barrier()


_current = Stream()


def current_stream(device=None):
    return _current


class stream_guard:
    def __init__(self, stream):
        self.stream = stream

    def __enter__(self):
        return self.stream

    def __exit__(self, *exc):
        return False


class cuda:
    """Namespace shim: the reference exposes paddle.device.cuda.*; here those
    map to the single accelerator's stats."""

    Stream = Stream
    Event = Event

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def synchronize(device=None):
        synchronize()

    @staticmethod
    def current_stream(device=None):
        return _current

    @staticmethod
    def max_memory_allocated(device=None):
        stats = jax.local_devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0)

    @staticmethod
    def memory_allocated(device=None):
        stats = jax.local_devices()[0].memory_stats() or {}
        return stats.get("bytes_in_use", 0)

    @staticmethod
    def max_memory_reserved(device=None):
        stats = jax.local_devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0)

    @staticmethod
    def memory_reserved(device=None):
        stats = jax.local_devices()[0].memory_stats() or {}
        return stats.get("bytes_limit", 0)

    @staticmethod
    def empty_cache():
        pass


# ------------------------------------------------------------- memory stats
# Reference: paddle/fluid/memory/stats.h peak trackers surfaced as
# paddle.device.cuda.max_memory_allocated etc.  TPU-native: PJRT device
# memory_stats plus live-buffer accounting.

def memory_stats(device=None):
    d = jax.devices()[0] if device is None else device
    try:
        return dict(d.memory_stats() or {})
    except Exception:
        return {}


def memory_allocated(device=None):
    st = memory_stats(device)
    if "bytes_in_use" in st:
        return int(st["bytes_in_use"])
    return int(sum(v.nbytes for v in jax.live_arrays()))


def max_memory_allocated(device=None):
    st = memory_stats(device)
    return int(st.get("peak_bytes_in_use", memory_allocated(device)))


def max_memory_reserved(device=None):
    st = memory_stats(device)
    return int(st.get("bytes_reserved", st.get("bytes_limit", 0)))


def empty_cache():
    pass  # XLA/PJRT owns the arena; freeing is GC-driven

from .plugin import (  # noqa: F401,E402
    load_custom_device_plugin,
    registered_custom_devices,
    scan_custom_device_plugins,
)


# ----------------------------------------------------- compile-flag predicates
def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    return False


def is_compiled_with_distribute():
    """Distributed support is built in (jax.distributed + GSPMD)."""
    return True


def is_compiled_with_custom_device(device_type):
    """True when a PJRT plugin backend of this name is registered
    (reference: custom-device runtime query)."""
    import jax

    try:
        return any(d.platform == device_type for d in jax.devices(device_type))
    except RuntimeError:
        return False


def get_available_custom_device():
    """Devices of registered PJRT PLUGIN backends (reference:
    paddle.device.get_available_custom_device) — builtin cpu/tpu are not
    custom devices."""
    import jax

    from .plugin import registered_custom_devices

    out = []
    for plat in registered_custom_devices():
        try:
            out.extend(f"{d.platform}:{d.id}" for d in jax.devices(plat))
        except RuntimeError:
            pass
    return out


def get_cudnn_version():
    """No cuDNN on this backend (reference returns None when not compiled
    with CUDA)."""
    return None


def set_stream(stream=None):
    """Streams are XLA-managed on TPU; accepted for API compat, returns the
    previous (None) stream like the reference's setter contract."""
    return None


class XPUPlace:
    def __init__(self, *a, **k):
        raise RuntimeError("XPU backend is not available in paddle_tpu (TPU-native build)")


class IPUPlace:
    def __init__(self, *a, **k):
        raise RuntimeError("IPU backend is not available in paddle_tpu (TPU-native build)")

__all__ += [
    "is_compiled_with_cuda", "is_compiled_with_rocm", "is_compiled_with_xpu",
    "is_compiled_with_ipu", "is_compiled_with_cinn", "is_compiled_with_distribute",
    "is_compiled_with_custom_device", "get_available_custom_device",
    "get_cudnn_version", "set_stream", "XPUPlace", "IPUPlace",
]
