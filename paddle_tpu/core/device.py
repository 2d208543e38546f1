"""Device API — analog of python/paddle/device/__init__.py:355 (set_device).

On TPU there is exactly one native accelerator; "places" map onto jax
devices. `set_device('tpu')`/`set_device('cpu')` select the default jax
device used for newly created tensors. Unlike the reference's
DeviceContextPool (paddle/fluid/platform/device_context.h:353), there is
no per-stream context to manage: XLA/PJRT owns streams and ordering.
"""
from __future__ import annotations

import jax

_current_place = None


class Place:
    """A device place, e.g. Place('tpu', 0). Analog of phi::Place."""

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def jax_device(self):
        """The jax device this place names. Raises when the process
        has no such device: asking for a TPU on a host without one is
        an error, never a quiet CPU."""
        devs = jax.devices(self.device_type)
        if self.device_id >= len(devs):
            raise ValueError(
                f"{self!r}: only {len(devs)} {self.device_type} "
                f"device(s) present")
        return devs[self.device_id]


def platform() -> str:
    """Platform of the default backend as JAX reports it ("tpu",
    "cpu", ...). The ONE place kernels ask where they run — and it
    does not catch: a backend that cannot be asked raises here rather
    than answering "not a TPU" and sending the caller down the
    interpreter or a dense path."""
    return jax.devices()[0].platform


def on_tpu() -> bool:
    return platform() == "tpu"


def pallas_interpret() -> bool:
    """Pallas kernels run under the interpreter on the CPU backend
    (the CI path) and nowhere else."""
    return platform() == "cpu"


def _parse(device: str) -> Place:
    device = device.lower()
    if ":" in device:
        kind, idx = device.split(":", 1)
        return Place(kind, int(idx))
    return Place(device, 0)


def set_device(device: str) -> Place:
    """Select the default device; analog of paddle.device.set_device
    (python/paddle/device/__init__.py:355)."""
    global _current_place
    place = _parse(device)
    place.jax_device()  # raises when the device is absent
    _current_place = place
    return place


def get_device() -> str:
    p = get_place()
    return f"{p.device_type}:{p.device_id}"


def get_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = Place(platform(), 0)
    return _current_place


def default_jax_device():
    return get_place().jax_device()


def is_compiled_with_cuda() -> bool:  # API parity; this build has zero CUDA
    return False


def device_count() -> int:
    return len(jax.devices())
