"""Places and device management.

Reference parity: paddle/fluid/platform/place.h (Place tagged union) and
python/paddle/device (set_device/get_device). TPU-first redesign: a Place wraps
a jax.Device; `TPUPlace` is the accelerator place, `CPUPlace` the host. There is
no DeviceContext/stream pool — XLA/PJRT owns streams; ordering is program order
inside jitted computations.
"""
from __future__ import annotations

import threading

import jax

__all__ = [
    "Place",
    "CPUPlace",
    "TPUPlace",
    "CUDAPlace",
    "set_device",
    "get_device",
    "device_count",
    "is_compiled_with_tpu",
    "get_all_devices",
]


class Place:
    """Identifies a physical device; wraps a jax.Device."""

    kind = "undefined"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    @property
    def jax_device(self):
        devs = _devices_of_kind(self.kind)
        if not devs:
            raise RuntimeError(f"no {self.kind} devices available")
        return devs[self.device_id % len(devs)]

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.kind, self.device_id))

    def __repr__(self):
        return f"Place({self.kind}:{self.device_id})"


class CPUPlace(Place):
    kind = "cpu"


class TPUPlace(Place):
    """The accelerator place — the point of this framework (BASELINE.json north star)."""

    kind = "tpu"


# Alias so reference-style scripts using CUDAPlace keep working: on this stack the
# accelerator is the TPU.
CUDAPlace = TPUPlace


def _devices_of_kind(kind):
    devs = jax.devices()
    if kind == "cpu":
        return [d for d in devs if d.platform == "cpu"] or jax.devices("cpu")
    return [d for d in devs if d.platform == "tpu"]


_state = threading.local()


def _default_place() -> Place:
    devs = jax.devices()
    if devs and devs[0].platform == "tpu":
        return TPUPlace(0)
    return CPUPlace(0)


def _current_place() -> Place:
    place = getattr(_state, "place", None)
    if place is None:
        place = _default_place()
        _state.place = place
    return place


def set_device(device) -> Place:
    """paddle.device.set_device parity. Accepts 'tpu', 'tpu:0', 'cpu', 'gpu:0'
    (gpu maps to the accelerator), or a Place."""
    if isinstance(device, Place):
        _state.place = device
        return device
    name = str(device).lower()
    idx = 0
    if ":" in name:
        name, sidx = name.split(":", 1)
        idx = int(sidx)
    if name in ("cpu",):
        place = CPUPlace(idx)
    elif name in ("tpu", "gpu", "cuda", "xpu", "npu"):
        place = TPUPlace(idx)
    else:
        raise ValueError(f"unknown device {device!r}")
    _state.place = place
    try:
        jax.config.update("jax_default_device", place.jax_device)
    except RuntimeError:
        pass
    return place


def get_device() -> str:
    p = _current_place()
    return f"{p.kind}:{p.device_id}"


def device_count(kind: str = "tpu") -> int:
    return len(_devices_of_kind(kind))


def get_all_devices():
    return jax.devices()


def is_compiled_with_tpu() -> bool:
    return device_count("tpu") > 0


def is_compiled_with_cuda() -> bool:  # reference-API shim; the accelerator is TPU
    return is_compiled_with_tpu()
