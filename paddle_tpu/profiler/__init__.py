"""Profiler — analog of python/paddle/profiler/ (profiler.py:344).

Host spans (RecordEvent, the analog of platform/profiler/event_tracing.h)
are recorded into a ring buffer and exported as chrome://tracing JSON
(ChromeTracingLogger analog). Device-side timing comes from jax.profiler
(XPlane/TensorBoard) when a trace dir is given (`PADDLE_TPU_TRACE_DIR`) —
the CUPTI analog on TPU. Every RecordEvent is also a
`jax.profiler.TraceAnnotation`, so the host spans lie in that trace (or
in any other `jax.profiler` trace that is running) beside the device
lines, on one clock.
"""
from .profiler import (
    Profiler,
    ProfilerState,
    ProfilerTarget,
    RecordEvent,
    export_chrome_tracing,
    make_scheduler,
)
from .profiler_statistic import SortedKeys, StatisticData
from .utils import SummaryView

__all__ = [
    "Profiler", "RecordEvent", "ProfilerState", "ProfilerTarget",
    "make_scheduler", "export_chrome_tracing", "SummaryView",
    "SortedKeys", "StatisticData",
]
