"""paddle.device analog (python/paddle/device/__init__.py)."""
from paddle_tpu.core.device import (
    Place,
    default_jax_device,
    device_count,
    get_device,
    get_place,
    is_compiled_with_cuda,
    on_tpu,
    set_device,
)


def is_compiled_with_tpu() -> bool:
    return on_tpu()


def synchronize():
    """Block until all pending device work completes — analog of
    device.cuda.synchronize; PJRT equivalent is draining async dispatch."""
    import jax

    (jax.device_put(0.0) + 0).block_until_ready()


cuda = None  # no CUDA in this build (paddle.device.cuda parity stub)

from paddle_tpu.device.memory import (  # noqa: E402
    max_memory_allocated,
    max_memory_reserved,
    memory_allocated,
    memory_reserved,
    memory_stats,
    reset_peak_memory_stats,
)

__all__ = [
    "set_device", "get_device", "get_place", "device_count", "Place",
    "is_compiled_with_cuda", "is_compiled_with_tpu", "synchronize",
    "memory_stats", "memory_allocated", "max_memory_allocated",
    "memory_reserved", "max_memory_reserved", "reset_peak_memory_stats",
]
