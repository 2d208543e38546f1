from __future__ import annotations

import contextlib
import enum
import itertools
import json
import os
import sys
import threading
import time
from typing import Callable, Iterable, List, Optional


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(enum.Enum):
    CPU = 0
    TPU = 1


class _HostEventRecorder:
    """Ring-buffer host span recorder (host_event_recorder.h analog)."""

    def __init__(self):
        self.events: List[dict] = []
        # reentrant: a garbage collection can start inside `record`
        # (it allocates under the lock), and its `host.gc` span ends by
        # recording itself on the same thread
        self._lock = threading.RLock()
        self.enabled = False

    def record(self, name, start_us, end_us, tid, cat="host"):
        if not self.enabled:
            return
        with self._lock:
            self.events.append(
                {"name": name, "ph": "X", "ts": start_us, "dur": end_us - start_us,
                 "pid": os.getpid(), "tid": tid, "cat": cat})

    def drain(self):
        with self._lock:
            out = self.events
            self.events = []
        return out

    def peek(self):
        """Non-destructive copy of the buffered spans — the tracing
        timeline merge (`observability.tracing.export_timeline`) reads
        the stream without stealing it from a recording Profiler."""
        with self._lock:
            return [dict(e) for e in self.events]


_recorder = _HostEventRecorder()


class RecordEvent:
    """Analog of paddle.profiler.RecordEvent (event_tracing.h RecordEvent).

    The one bridge between the program's host spans and the profilers:
    a span goes to the `_HostEventRecorder` (while a `Profiler` records)
    AND, as a `jax.profiler.TraceAnnotation`, onto the clock of whatever
    `jax.profiler` trace is running — the one `Profiler` starts itself
    or an outside one (the benchmark's traced slice) — where the device
    lines live. Outside a profiler session the annotation is a no-op of
    well under a microsecond. A process that has not imported jax (a
    dataloader worker) does not import it for a span.

    Names are constants: what varies per step (request ids, lane
    counts) belongs in the flight recorder or the `TraceRecorder`, never
    in a name — it would split one row of a reduction into thousands.
    """

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._start = None
        self._annotation = None

    def begin(self):
        profiler = sys.modules.get("jax.profiler")
        if profiler is not None:
            self._annotation = profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self._start = time.perf_counter_ns() // 1000

    def end(self):
        if self._start is not None:
            if _recorder.enabled:
                _recorder.record(self.name, self._start,
                                 time.perf_counter_ns() // 1000,
                                 threading.get_ident() % 100000)
            self._start = None
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Analog of paddle.profiler.make_scheduler."""
    cycle = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= cycle * repeat:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


# monotonic export sequence: two exports within the same wall-clock
# second (scheduler cycles faster than 1 Hz, tests) must land in two
# files — `{name}_{epoch}.json` alone silently overwrites the first
_export_seq = itertools.count()


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"worker_{os.getpid()}"
        path = os.path.join(
            dir_name,
            f"{name}_{int(time.time())}_{next(_export_seq):04d}.json")
        prof._export_path = path
        prof.export(path)

    return handler


class Profiler:
    """Analog of paddle.profiler.Profiler (profiler.py:344). Also starts a
    jax.profiler trace (XPlane) when `timer_only=False` and a trace dir is
    set via on_trace_ready=export_chrome_tracing(dir)."""

    def __init__(self, *, targets: Optional[Iterable] = None, scheduler=None,
                 on_trace_ready=None, record_shapes=False, profile_memory=False,
                 timer_only=False, with_flops=False):
        self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        # ProfilerTarget.TPU => sync-timed op spans (each dispatch
        # blocks until outputs are ready, approximating device time —
        # the CUPTI-attribution analog; see profiler_statistic.py)
        self._sync_ops = any(t == ProfilerTarget.TPU
                             for t in (targets or []))
        self.step_num = 0
        self._state = ProfilerState.CLOSED
        self._events: List[dict] = []
        self._jax_trace_dir = None
        self._jax_tracing = False
        self._export_path = None
        self._step_t0 = None
        self._step_times = []
        self._trace_ready_fired = False

    # -- lifecycle ---------------------------------------------------------
    def _set_recording(self, on: bool):
        """Toggle the span sinks together: host RecordEvents and the
        per-op dispatch span hook (device-sync when targets say TPU)."""
        from paddle_tpu.ops.dispatch import OpStats

        _recorder.enabled = on
        if on and not self._timer_only:
            OpStats.span_hook = self._op_span
            OpStats.sync_spans = self._sync_ops
        else:
            OpStats.span_hook = None
            OpStats.sync_spans = False

    def _op_span(self, name, start_us, end_us, synced):
        # op spans feed the operator summary; sync-timed ones carry
        # device attribution (see profiler_statistic.py)
        _recorder.record(name, start_us, end_us,
                         threading.get_ident() % 100000,
                         cat="device" if synced else "op")

    def start(self):
        # first, so that a trace that cannot start leaves nothing on
        self._maybe_start_device_trace()
        self._state = (self._scheduler(self.step_num)
                       if self._scheduler else ProfilerState.RECORD)
        self._set_recording(self._state in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN))
        self._step_t0 = time.perf_counter()
        return self

    def stop(self):
        self._set_recording(False)
        self._events.extend(_recorder.drain())
        self._maybe_stop_device_trace()
        if self._on_trace_ready and not self._trace_ready_fired:
            self._on_trace_ready(self)
        self._trace_ready_fired = False
        self._state = ProfilerState.CLOSED

    def step(self, num_frames: int = 1):
        now = time.perf_counter()
        if self._step_t0 is not None:
            self._step_times.append(now - self._step_t0)
        self._step_t0 = now
        self.step_num += num_frames
        if self._scheduler:
            new_state = self._scheduler(self.step_num)
            if new_state != self._state:
                if new_state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
                    self._set_recording(True)
                    self._trace_ready_fired = False  # new record window
                elif self._state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
                    self._events.extend(_recorder.drain())
                    self._set_recording(False)
                    if new_state == ProfilerState.CLOSED and self._on_trace_ready:
                        # fired here; stop() must not export a duplicate
                        self._on_trace_ready(self)
                        self._trace_ready_fired = True
                self._state = new_state

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- device trace ------------------------------------------------------
    def _maybe_start_device_trace(self):
        """A trace that cannot start says so: the error goes through."""
        d = os.environ.get("PADDLE_TPU_TRACE_DIR")
        if self._timer_only or not d:
            return
        import jax

        jax.profiler.start_trace(d)
        self._jax_tracing = True

    def _maybe_stop_device_trace(self):
        if self._jax_tracing:
            import jax

            self._jax_tracing = False
            jax.profiler.stop_trace()

    # -- export / summary --------------------------------------------------
    def export(self, path: str, format: str = "json"):
        self._events.extend(_recorder.drain())
        with open(path, "w") as f:
            json.dump({"traceEvents": self._events}, f)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Aggregated statistics report (profiler_statistic.py analog):
        per-name calls/total/avg/max for host spans and op dispatches,
        with device-time attribution when targets included TPU. Prints
        the table and returns the StatisticData for programmatic use."""
        from .profiler_statistic import (
            SortedKeys, StatisticData, build_table,
        )

        self._events.extend(_recorder.drain())
        data = StatisticData(self._events, self._step_times)
        if sorted_by is None:
            # sync-timed profiles put all op time in the device column;
            # sorting them by (all-zero) CPU totals would scramble the
            # table
            sorted_by = (SortedKeys.DeviceTotal if self._sync_ops
                         else SortedKeys.CPUTotal)
        table = build_table(
            data, sorted_by=sorted_by,
            op_detail=op_detail, time_unit=time_unit)
        print("---- profiler summary ----\n" + table)
        return data
