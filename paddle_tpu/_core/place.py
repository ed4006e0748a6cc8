"""Device placement.

Capability parity with the reference's Place hierarchy
(paddle/phi/common/place.h:31, python/paddle/device/__init__.py:265) mapped
onto jax.Device.  On TPU there are no manual streams — XLA schedules — so a
Place is just (device_kind, index) resolving to a jax.Device.
"""

from __future__ import annotations

import threading

import jax

__all__ = [
    "Place",
    "TPUPlace",
    "CPUPlace",
    "CUDAPlace",
    "CUDAPinnedPlace",
    "CustomPlace",
    "set_device",
    "get_device",
    "get_default_device",
    "is_compiled_with_tpu",
    "device_count",
]


class Place:
    """Base place: a logical device slot."""

    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self) -> jax.Device:
        devs = _devices_for(self.device_type)
        if not devs:
            raise RuntimeError(f"No devices of type {self.device_type!r} available")
        return devs[self.device_id % len(devs)]

    def is_tpu_place(self):
        return self.device_type == "tpu"

    def is_cpu_place(self):
        return self.device_type == "cpu"

    # GPU never exists in this framework; kept for API-shape compatibility.
    def is_gpu_place(self):
        return False


class TPUPlace(Place):
    device_type = "tpu"


class CPUPlace(Place):
    device_type = "cpu"


class CustomPlace(Place):
    """Any other PJRT backend (pluggable-device analog of the reference's
    CustomPlace, paddle/phi/common/place.h)."""

    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = device_type


class CUDAPlace(Place):
    """API-compat alias: reference code written against paddle.CUDAPlace(i)
    (paddle/phi/common/place.h GPUPlace) runs unchanged — the i-th
    accelerator here is the i-th device of the default (TPU) backend."""

    device_type = "accel"

    def jax_device(self) -> jax.Device:
        devs = jax.devices()
        return devs[self.device_id % len(devs)]


class CUDAPinnedPlace(CPUPlace):
    """API-compat alias: pinned host memory is a CUDA-transfer concept; on
    TPU/PJRT host staging is managed by the runtime, so this is CPUPlace."""


def _accel_type() -> str:
    return jax.default_backend()


def _devices_for(device_type: str):
    try:
        return jax.devices(device_type)
    except RuntimeError:
        return []


_state = threading.local()


def _parse(device: str) -> Place:
    device = device.lower()
    if ":" in device:
        kind, _, idx = device.partition(":")
        idx = int(idx)
    else:
        kind, idx = device, 0
    kind = {"gpu": "tpu", "xpu": "tpu", "cuda": "tpu"}.get(kind, kind)
    if kind == "cpu":
        return CPUPlace(idx)
    if kind == "tpu":
        return TPUPlace(idx)
    return CustomPlace(kind, idx)


def set_device(device) -> Place:
    """paddle.set_device equivalent (reference python/paddle/device/__init__.py:265)."""
    place = device if isinstance(device, Place) else _parse(str(device))
    _state.place = place
    return place


def get_default_device() -> Place:
    place = getattr(_state, "place", None)
    if place is None:
        accel = _accel_type()
        place = CPUPlace(0) if accel == "cpu" else (
            TPUPlace(0) if accel == "tpu" else CustomPlace(accel, 0)
        )
        _state.place = place
    return place


def get_device() -> str:
    p = get_default_device()
    return f"{p.device_type}:{p.device_id}"


def is_compiled_with_tpu() -> bool:
    return len(_devices_for("tpu")) > 0


def device_count(device_type: str | None = None) -> int:
    if device_type is None:
        device_type = get_default_device().device_type
    return len(_devices_for(device_type))
