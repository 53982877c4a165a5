"""Device placement.

The reference models devices as Place objects (paddle/phi/common/place.h,
python surface paddle.CPUPlace/CUDAPlace/CustomPlace) routed through a
DeviceManager (paddle/phi/backends/device_manager.h:134). On TPU the device
inventory is owned by the XLA/PJRT client, so Place is a thin, hashable
handle that resolves to a `jax.Device`. The global default place is what
creation ops use, mirroring `paddle.device.set_device`
(/root/reference/python/paddle/device/__init__.py:62).
"""
from __future__ import annotations

import threading
from typing import Optional

import jax


def on_tpu() -> bool:
    """THE device test: the default backend is a TPU. Everything that
    switches behavior on the accelerator (Pallas kernels, the PRNG
    implementation, bench sizes) asks here and nowhere else. A backend
    that fails to start raises — it is not reported as "no TPU"."""
    return jax.default_backend() == "tpu"


class Place:
    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        if self.device_type == "cpu":
            return "Place(cpu)"
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"

    # reference-compat alias: on this framework the accelerator is always TPU
    is_gpu_place = is_tpu_place
    is_custom_place = is_tpu_place


def CPUPlace() -> Place:
    return Place("cpu", 0)


def TPUPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


# Accept Fleet-style scripts that ask for an accelerator by its CUDA name.
def CUDAPinnedPlace() -> Place:
    """Pinned-host-memory place (reference CUDAPinnedPlace): on TPU, host
    staging buffers are managed by PJRT; maps to the host place."""
    return Place("cpu", 0)


def CUDAPlace(device_id: int = 0) -> Place:
    return TPUPlace(device_id)


def XPUPlace(device_id: int = 0) -> Place:
    return TPUPlace(device_id)


def IPUPlace() -> Place:
    return TPUPlace(0)


CustomPlace = TPUPlace

_state = threading.local()


def get_device() -> str:
    place = getattr(_state, "place", None)
    if place is None:
        place = Place("tpu" if on_tpu() else "cpu", 0)
        _state.place = place
    if place.device_type == "cpu":
        return "cpu"
    return f"{place.device_type}:{place.device_id}"


def set_device(device: str) -> Place:
    """set_device("tpu"), set_device("tpu:1"), set_device("cpu").

    Accepts "gpu"/"cuda"/"xpu" as aliases for "tpu" so reference launch
    scripts run unchanged.
    """
    name, _, idx = device.partition(":")
    name = name.lower()
    if name in ("gpu", "cuda", "xpu", "npu", "custom"):
        name = "tpu"
    if name not in ("cpu", "tpu"):
        raise ValueError(f"unsupported device {device!r}")
    place = Place(name, int(idx) if idx else 0)
    _state.place = place
    return place


def get_default_place() -> Place:
    get_device()
    return _state.place


def to_jax_device(place: Optional[Place]) -> Optional["jax.Device"]:
    """Resolve a Place to a concrete jax.Device (None = framework default)."""
    if place is None:
        place = get_default_place()
    if place.device_type == "cpu":
        devs = jax.devices("cpu")
    else:
        # when the default backend is the CPU (the test suite's simulation
        # mode, chosen in the open by tests/conftest.py) a TPU place maps
        # onto its devices; a backend that fails to start is an error
        devs = jax.devices()
    if not devs:
        raise RuntimeError(f"no jax devices for place {place}")
    return devs[min(place.device_id, len(devs) - 1)]


def place_of(array) -> Place:
    """Best-effort Place for a jax.Array (sharded arrays report device 0)."""
    try:
        dev = next(iter(array.devices()))
    except Exception:
        return get_default_place()
    if dev.platform == "cpu":
        return Place("cpu", dev.id)
    return Place("tpu", dev.id)
