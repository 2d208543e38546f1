"""Device memory introspection — analog of paddle/fluid/memory/stats.h
(Stat/StatRegistry, memory_allocated/max_memory_allocated) and
python/paddle/device/cuda/__init__.py (max_memory_allocated etc.).

Two sources, chosen by the device's platform and never mixed:
- On an accelerator, the PJRT allocator's per-device statistics
  (device.memory_stats(): bytes_in_use, peak_bytes_in_use ...). A
  backend that reports none raises; nothing else is ever returned
  under the device's name.
- On the CPU backend, which has no allocator statistics, live-array
  accounting: the sum of nbytes of jax.live_arrays() on the device,
  with a process-local high-water mark advanced at every query
  (memory_stats/max_memory_allocated/record_peak — NOT automatically
  during training steps: a per-step live_arrays() walk in the hot path
  would cost more than it tells). The `source` field says which; the
  live-array view counts resident arrays only — in-program activation
  temps are visible through program_memory() instead.

For the true in-program peak (activations + temps inside one XLA
executable — what HBM pressure actually is on TPU), use
`program_memory(compiled)` over a compiled/lowered step; bench.py
prints it per model row.
"""
from __future__ import annotations

__all__ = [
    "memory_stats", "memory_allocated", "max_memory_allocated",
    "memory_reserved", "max_memory_reserved", "reset_peak_memory_stats",
    "record_peak", "program_memory",
]

# process-local high-water marks per CPU device ({device_key: peak_bytes})
_peaks: dict = {}


def _device(device=None):
    import jax

    if device is None:
        from paddle_tpu.core.device import default_jax_device

        return default_jax_device()
    if isinstance(device, int):
        return jax.devices()[device]
    if isinstance(device, str):
        from paddle_tpu.core.device import _parse

        return _parse(device).jax_device()
    return device


def _live_bytes(dev) -> int:
    import jax

    total = 0
    for a in jax.live_arrays():
        try:
            if dev in a.devices():
                # addressable shard bytes on this device
                total += sum(s.data.nbytes for s in a.addressable_shards
                             if s.device == dev)
        except Exception:
            continue
    return total


def record_peak(device=None) -> int:
    """Sample current usage and advance the high-water mark (called by
    the compiled-step dispatchers; callable any time)."""
    dev = _device(device)
    cur = memory_allocated(dev)
    key = str(dev)
    if cur > _peaks.get(key, 0):
        _peaks[key] = cur
    return cur


def _allocator_stats(dev) -> dict:
    raw = dev.memory_stats()
    if not raw:
        raise RuntimeError(
            f"{dev} ({dev.platform}) reports no allocator statistics")
    return raw


def memory_stats(device=None) -> dict:
    """All counters for `device` as a dict (paddle.device.cuda
    .memory_stats analog): the allocator's on an accelerator,
    live-array accounting on the CPU (`source` says which)."""
    dev = _device(device)
    if dev.platform != "cpu":
        raw = _allocator_stats(dev)
        return {
            "source": "pjrt",
            "allocated_bytes": raw.get("bytes_in_use", 0),
            "peak_allocated_bytes": raw.get("peak_bytes_in_use", 0),
            "reserved_bytes": raw.get("bytes_reserved",
                                      raw.get("bytes_in_use", 0)),
            "peak_reserved_bytes": raw.get("peak_bytes_reserved",
                                           raw.get("peak_bytes_in_use", 0)),
            "largest_free_block_bytes": raw.get(
                "largest_free_block_bytes"),
            "raw": raw,
        }
    cur = _live_bytes(dev)
    key = str(dev)
    if cur > _peaks.get(key, 0):
        _peaks[key] = cur
    return {
        "source": "live_arrays",
        "allocated_bytes": cur,
        "peak_allocated_bytes": _peaks[key],
        "reserved_bytes": cur,
        "peak_reserved_bytes": _peaks[key],
        "largest_free_block_bytes": None,
        "raw": None,
    }


def memory_allocated(device=None) -> int:
    """Bytes currently allocated on `device`
    (paddle.device.cuda.memory_allocated analog)."""
    dev = _device(device)
    if dev.platform != "cpu":
        return int(_allocator_stats(dev)["bytes_in_use"])
    return _live_bytes(dev)


def max_memory_allocated(device=None) -> int:
    """Peak allocated bytes since process start / last reset
    (paddle.device.cuda.max_memory_allocated analog)."""
    return int(memory_stats(device)["peak_allocated_bytes"])


def memory_reserved(device=None) -> int:
    return int(memory_stats(device)["reserved_bytes"])


def max_memory_reserved(device=None) -> int:
    return int(memory_stats(device)["peak_reserved_bytes"])


def reset_peak_memory_stats(device=None) -> None:
    """Reset the CPU live-array high-water mark (an allocator's peak
    lasts as long as the allocator and cannot be reset from here)."""
    _peaks[str(_device(device))] = 0


def program_memory(compiled) -> dict:
    """Peak HBM of ONE compiled XLA program: argument/output/temp/gen
    sizes from compiled.memory_analysis() — temps are the activation
    working set, the number the reference's memory profiler reports per
    iteration. Accepts a jax Compiled (from .lower().compile()) or
    anything exposing memory_analysis()."""
    out = {"argument_bytes": None, "output_bytes": None,
           "temp_bytes": None, "generated_code_bytes": None,
           "total_bytes": None}
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return out
    if ma is None:
        return out
    get = lambda n: getattr(ma, n, None)
    out["argument_bytes"] = get("argument_size_in_bytes")
    out["output_bytes"] = get("output_size_in_bytes")
    out["temp_bytes"] = get("temp_size_in_bytes")
    out["generated_code_bytes"] = get("generated_code_size_in_bytes")
    alias = get("alias_size_in_bytes") or 0
    parts = [out["argument_bytes"], out["output_bytes"],
             out["temp_bytes"], out["generated_code_bytes"]]
    if all(p is not None for p in parts):
        # aliased buffers (donated params) are counted in both argument
        # and output size; subtract one copy
        out["total_bytes"] = sum(parts) - alias
    return out
