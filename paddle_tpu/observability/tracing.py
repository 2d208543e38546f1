"""Request-scoped tracing + step-phase timeline — the span half of
serving observability (metrics.py answers "how much / how often"; this
module answers "where did request X's 40 ms go").

Four host-side pieces, shared by the engine and the fleet (the fourth
process-wide):

- **TraceRecorder**: a thread-safe bounded ring of Chrome
  trace-event-format spans. Every span carries a `trace_id` (one per
  request, minted at intake and riding the disaggregated handoff
  across replicas) plus its own `span_id`/`parent_id`, so one Perfetto
  timeline shows a request crossing engines. The ring is bounded
  (`capacity` spans, oldest dropped first, drops counted) so
  steady-state serving never grows memory without bound.
- **PhaseTimer**: exclusive-time accounting for the named host phases
  one `engine.step()` decomposes into (`STEP_PHASES`). Nested phases
  PAUSE their parent, so per-phase totals partition the step wall
  exactly — the serial-host tax of ROADMAP item 3 becomes a number
  (`engine_step_host_gap_seconds{phase=…}`) instead of an assertion.
  The engine opens each phase inside a `profiler.RecordEvent` named
  `engine.<phase>`, which is how the phases reach a `jax.profiler`
  trace (the device's clock); this module stays off jax.
- **FlightRecorder**: a bounded ring of recent request-lifecycle
  events (queued/admit/first_token/stall/finish/handoff/…) — the
  postmortem `drain()`'s leak audit attaches to its exception.
- **HostPauses**: the process's pauses that no phase owns — every
  garbage collection (inside a `host.gc` span) and every stage of a
  JAX compile — counted on the default registry and kept in small
  rings, so that a stalled step (`StallDetector`, `stall_owner`) can
  name its owner: a collection, a compile, the device or a phase.

Clock policy: every timestamp is `time.perf_counter_ns() // 1000` —
the SAME monotonic microsecond clock `profiler.RecordEvent` stamps its
spans with, so `export_timeline` can merge a TraceRecorder stream and
the profiler's `_HostEventRecorder` stream onto one coherent timeline
without offset juggling (single-process fleets share the clock;
cross-HOST merges go through `tools/merge_timelines.py --align-start`,
which normalizes each file's epoch).

House invariant: tracing is HOST-SIDE ONLY. Nothing in this module
ever becomes a compiled-program argument, so a tracing-enabled engine
runs byte-identical programs to a disabled one (the `sampling=False`
precedent, held trivially by construction). No jax imports — importing
this module must never initialize a backend.
"""
from __future__ import annotations

import atexit
import gc
import itertools
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager

from .metrics import get_registry

__all__ = [
    "STEP_PHASES", "TraceRecorder", "PhaseTimer", "FlightRecorder",
    "new_trace_id", "now_us", "merge_trace_events", "export_timeline",
    "profiler_host_events", "HOST_GC_SPAN", "HostPauses",
    "install_host_pause_hooks", "host_pauses", "StallDetector",
    "is_stall", "stall_owner",
]

#: The named host phases one `engine.step()` decomposes into. Every
#: phase is host work between (or around) compiled dispatches:
#: - schedule:      admission loop, lane scan, growth allocation
#: - prefix_lookup: prefix-cache chain walk at admission
#: - adapter_swap:  adapter-page acquire (incl. host->device swap-in)
#: - draft_propose: speculative drafter proposal (host-side)
#: - dispatch:      building host args + issuing a compiled step
#: - device_wait:   blocking on device results (block_until_ready
#:                  discipline — the only phase that is device time)
#: - accept_walk:   greedy draft-acceptance walk over verify output
#: - sample_walk:   rejection-sampling acceptance walk (sampled lanes)
#: - cow:           copy-on-write block promotion
#: - finish:        token emission, TTFT/TPOT accounting, retirement
#: - state_alloc:   a row of the slots' fixed-size state taken and
#:                  zeroed at admission (models with recurrent layers)
#: - state_free:    that row given back at retirement
STEP_PHASES = ("schedule", "prefix_lookup", "adapter_swap",
               "draft_propose", "dispatch", "device_wait",
               "accept_walk", "sample_walk", "cow", "finish",
               "state_alloc", "state_free")

_trace_seq = itertools.count(1)


def now_us():
    """Monotonic microseconds — the shared span clock (see module
    docstring for the cross-stream merge policy)."""
    return time.perf_counter_ns() // 1000


def new_trace_id():
    """Process-unique request trace id. Deliberately NOT random: the
    pid prefix keeps ids unique across processes (multi-host fleets)
    while the counter keeps single-process test traces deterministic."""
    return f"{os.getpid():x}-{next(_trace_seq):x}"


class TraceRecorder:
    """Thread-safe bounded ring of Chrome trace-event spans.

    Events are plain dicts in the trace-event JSON schema ("X" duration
    spans, "i" instants), timestamped by `now_us()`. The ring holds the
    newest `capacity` events; `dropped` counts evictions so a truncated
    export is visible, never silent.
    """

    def __init__(self, capacity=4096, process_name="engine"):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.process_name = process_name
        self._events = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._span_seq = itertools.count(1)
        self.total_recorded = 0

    @property
    def dropped(self):
        """Events evicted by the ring bound (recorded - retained)."""
        with self._lock:
            return self.total_recorded - len(self._events)

    def new_span_id(self):
        return next(self._span_seq)

    def _push(self, ev):
        with self._lock:
            self._events.append(ev)
            self.total_recorded += 1

    def add_span(self, name, start_us, end_us, *, trace_id=None,
                 parent_id=None, tid=0, cat="engine", args=None):
        """Record one completed span; returns its span id (usable as
        another span's `parent_id`)."""
        sid = self.new_span_id()
        a = {"span_id": sid}
        if trace_id is not None:
            a["trace_id"] = trace_id
        if parent_id is not None:
            a["parent_id"] = parent_id
        if args:
            a.update(args)
        self._push({"name": name, "ph": "X", "ts": int(start_us),
                    "dur": max(int(end_us) - int(start_us), 0),
                    "pid": os.getpid(), "tid": int(tid), "cat": cat,
                    "args": a})
        return sid

    def add_instant(self, name, ts_us=None, *, trace_id=None, tid=0,
                    cat="engine", args=None):
        """Record a zero-duration marker (finish reasons, sheds,
        first-token ticks)."""
        a = {}
        if trace_id is not None:
            a["trace_id"] = trace_id
        if args:
            a.update(args)
        self._push({"name": name, "ph": "i", "s": "t",
                    "ts": int(now_us() if ts_us is None else ts_us),
                    "pid": os.getpid(), "tid": int(tid), "cat": cat,
                    "args": a})

    @contextmanager
    def span(self, name, *, trace_id=None, parent_id=None, tid=0,
             cat="engine", args=None):
        t0 = now_us()
        try:
            yield
        finally:
            self.add_span(name, t0, now_us(), trace_id=trace_id,
                          parent_id=parent_id, tid=tid, cat=cat,
                          args=args)

    def snapshot(self):
        """Non-destructive copy of the retained events, oldest first."""
        with self._lock:
            return [dict(e) for e in self._events]

    def clear(self):
        with self._lock:
            self._events.clear()
            self.total_recorded = 0


class PhaseTimer:
    """Exclusive-time phase accounting for one scheduler iteration.

    `phase(name)` is a reentrant-by-stack context manager: entering a
    nested phase PAUSES the enclosing one, so `totals()` values are
    disjoint and sum to (at most) the step's wall time — the property
    that makes a window's device fraction (device_wait's seconds over
    `engine_step_seconds_total`) a real fraction instead of
    double-counting nested sections.

    Thread-confined: each thread owns its own stack AND accumulator
    (the async engine core runs drafter proposals on a helper thread
    while the step thread is inside its own phases — a phase recorded
    off the step thread must neither pause the step thread's active
    phase nor fold its overlapped seconds into the step thread's
    totals, or phase sums would exceed step wall time and the device
    fraction would stop being a fraction).
    `reset()` and `totals()` operate on the calling thread's clock
    only; no locks needed because no state is shared.
    """

    def __init__(self):
        self._tls = threading.local()

    def _state(self):
        tls = self._tls
        if not hasattr(tls, "acc"):
            tls.acc = {}
            tls.stack = []             # [name, slice_start] frames
        return tls.acc, tls.stack

    def reset(self):
        acc, stack = self._state()
        self._tls.acc = {}
        stack.clear()
        return acc

    @contextmanager
    def phase(self, name):
        acc, stack = self._state()
        now = time.perf_counter()
        if stack:                      # pause the enclosing phase
            outer = stack[-1]
            acc[outer[0]] = acc.get(outer[0], 0.0) + now - outer[1]
        stack.append([name, now])
        try:
            yield
        finally:
            acc, stack = self._state()
            frame = stack.pop()
            now = time.perf_counter()
            acc[frame[0]] = acc.get(frame[0], 0.0) + now - frame[1]
            if stack:                  # resume the enclosing phase
                stack[-1][1] = now

    def totals(self):
        """phase -> accumulated exclusive seconds since last reset,
        for the CALLING thread's clock."""
        return dict(self._state()[0])


class FlightRecorder:
    """Bounded ring of recent request-lifecycle events — the engine's
    black box. Always on (a handful of dict appends per request, far
    off any hot path), bounded so steady-state serving never grows it,
    and formatted into `drain()`'s leak-audit exception so a failed
    audit arrives WITH the recent history that explains it."""

    def __init__(self, capacity=256):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._events = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.total_recorded = 0

    def record(self, event, req_id=None, **detail):
        ev = {"t_us": now_us(), "event": event}
        if req_id is not None:
            ev["req_id"] = req_id
        if detail:
            ev.update(detail)
        with self._lock:
            self._events.append(ev)
            self.total_recorded += 1

    def dump(self):
        """Retained events, oldest first (JSON-able dicts)."""
        with self._lock:
            return [dict(e) for e in self._events]

    def format(self, limit=None):
        """Human-readable tail for exception messages."""
        rows = self.dump()
        if limit is not None:
            rows = rows[-limit:]
        head = (f"flight recorder ({len(rows)} of "
                f"{self.total_recorded} events, newest last):")
        lines = [head]
        for e in rows:
            extra = " ".join(f"{k}={e[k]}" for k in e
                             if k not in ("t_us", "event", "req_id"))
            rid = f" req={e['req_id']!r}" if "req_id" in e else ""
            lines.append(f"  [{e['t_us']}us] {e['event']}{rid}"
                         + (f" {extra}" if extra else ""))
        return "\n".join(lines)


#: The span round every garbage collection, on the profiler's clock:
#: innermost wherever a collection interrupts, so the device's idle
#: time under it is filed under this name and not under the phase.
HOST_GC_SPAN = "host.gc"

#: `jax.monitoring` duration events -> the `stage` label of
#: `host_compile_*`. The persistent cache's retrieval runs INSIDE
#: `backend_compile`, so it is counted apart and left out of
#: `HostPauses.compile_seconds`.
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}

#: Entries kept of the newest collections and compile stages.
PAUSE_RING = 256

#: The stall rule (`is_stall`, `stall_owner`): a step is a stall when
#: its wall is at least STALL_RATIO times the median of the last
#: STALL_WINDOW steps AND at least STALL_MIN_EXCESS_S longer than it;
#: a collection or a compile owns it when its seconds inside the step
#: cover STALL_PAUSE_SHARE of that excess.
STALL_WINDOW = 64
STALL_RATIO = 4.0
STALL_MIN_EXCESS_S = 0.05
STALL_PAUSE_SHARE = 0.5


class HostPauses:
    """The process's host pauses that belong to no phase of the
    program: garbage collections and JAX compiles. One instance a
    process (`host_pauses()`), installed once (`install()`, idempotent)
    by whatever builds a `GenerationEngine` or a `TrainStep`, and
    always on from then.

    - GC: a `gc.callbacks` hook opens the `host.gc` span
      (`profiler.RecordEvent`) at a collection's start and closes it at
      its stop; it adds to `host_gc_pauses_total{generation}` and
      `host_gc_pause_seconds_total{generation}` and keeps the longest
      in `host_gc_pause_max_seconds` (the default registry).
    - Compiles: a `jax.monitoring` duration listener adds each stage of
      COMPILE_EVENTS to `host_compiles_total{stage}` and
      `host_compile_seconds_total{stage}`.

    Beside the counters, plain totals since installation (the registry
    may be reset; a step's share is a difference of these) and two
    bounded rings, `gcs` of `(t_us, generation, seconds)` and
    `compiles` of `(t_us, fun_name, stage, seconds)`, so that a stall
    can name the collections and the functions compiled inside it.
    """

    def __init__(self):
        self.installed = False
        self.gc_seconds = 0.0
        self.compile_seconds = 0.0
        self.gcs = deque(maxlen=PAUSE_RING)
        self.compiles = deque(maxlen=PAUSE_RING)
        self._gc_t0 = None

    def install(self):
        """Hook the collector and JAX's compile events (once). Called
        where jax is already loaded: this module never imports it at
        import time."""
        if self.installed:
            return self
        from jax import monitoring

        from paddle_tpu.profiler import RecordEvent

        reg = get_registry()
        pauses = reg.counter(
            "host_gc_pauses_total",
            "Garbage collections of this process, by generation "
            "(each inside a `host.gc` span).", labelnames=("generation",))
        seconds = reg.counter(
            "host_gc_pause_seconds_total",
            "Seconds this process spent in garbage collections, by "
            "generation.", labelnames=("generation",))
        self._m_gc = [(pauses.labels(generation=g),
                       seconds.labels(generation=g)) for g in (0, 1, 2)]
        self._m_gc_max = reg.gauge(
            "host_gc_pause_max_seconds",
            "The longest garbage collection of this process so far.")
        compiles = reg.counter(
            "host_compiles_total",
            "JAX compile stages this process ran (trace, lowering, "
            "backend compile, persistent-cache retrieval).",
            labelnames=("stage",))
        compile_s = reg.counter(
            "host_compile_seconds_total",
            "Seconds of JAX compile stages, by stage (a cache "
            "retrieval is inside its backend_compile's seconds).",
            labelnames=("stage",))
        self._m_compile = {s: (compiles.labels(stage=s),
                               compile_s.labels(stage=s))
                           for s in COMPILE_EVENTS.values()}
        self._gc_span = RecordEvent(HOST_GC_SPAN)
        gc.callbacks.append(self._on_gc)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        # no collection is clocked while the interpreter is torn down
        atexit.register(self._uninstall_gc)
        self.installed = True
        return self

    def _uninstall_gc(self):
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase, info):
        # collections never nest, and a collection runs on one thread
        # from its start to its stop: one span object serves them all
        if phase == "start":
            self._gc_span.begin()
            self._gc_t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._gc_t0
        self._gc_span.end()
        gen = info["generation"]
        self.gc_seconds += dt
        self.gcs.append((now_us(), gen, dt))
        n, s = self._m_gc[gen]
        n.inc()
        s.inc(dt)
        self._m_gc_max.set_max(dt)

    def _on_duration(self, event, seconds, **kwargs):
        stage = COMPILE_EVENTS.get(event)
        if stage is None:
            return
        self.compiles.append((now_us(), kwargs.get("fun_name"), stage,
                              seconds))
        if stage != "cache_retrieval":        # inside backend_compile
            self.compile_seconds += seconds
        n, s = self._m_compile[stage]
        n.inc()
        s.inc(seconds)

    def since(self, t_us):
        """`(generations of the collections, names of the functions
        compiled)` from `t_us` on (as far as the rings reach)."""
        return ([g for t, g, _ in self.gcs if t >= t_us],
                sorted({f for t, f, _, _ in self.compiles
                        if t >= t_us and f}))


_HOST_PAUSES = HostPauses()


def host_pauses():
    """The process's one `HostPauses` (installed or not)."""
    return _HOST_PAUSES


def install_host_pause_hooks():
    """Install the process's GC and compile hooks, once; returns the
    `HostPauses` they feed."""
    return _HOST_PAUSES.install()


def is_stall(wall, median):
    """Whether a step of `wall` seconds stalled against the `median`
    of the steps before it (the module's STALL_* constants)."""
    return wall >= STALL_RATIO * median \
        and wall - median >= STALL_MIN_EXCESS_S


def stall_owner(wall, median, phases, gc_s=0.0, compile_s=0.0):
    """Who owns a stalled step: `gc` or `compile` where that pause's
    seconds inside the step cover STALL_PAUSE_SHARE of its excess over
    the median (the larger of the two where both do); else the phase
    with the most exclusive seconds, `device_wait` named `device`."""
    excess = wall - median
    seconds, pause = max((gc_s, "gc"), (compile_s, "compile"))
    if seconds > 0 and seconds >= STALL_PAUSE_SHARE * excess:
        return pause
    if not phases:
        return "other"
    phase = max(phases, key=phases.get)
    return "device" if phase == "device_wait" else phase


class StallDetector:
    """The walls of the last STALL_WINDOW steps; `observe(wall)`
    returns their median where this step is a stall (`is_stall`), else
    None. Nothing is judged until the window is full, and a step under
    STALL_MIN_EXCESS_S is never sorted for."""

    def __init__(self):
        self._walls = deque(maxlen=STALL_WINDOW)

    def observe(self, wall):
        walls, median = self._walls, None
        if wall >= STALL_MIN_EXCESS_S and len(walls) == STALL_WINDOW:
            s = sorted(walls)
            mid = STALL_WINDOW // 2
            median = (s[mid - 1] + s[mid]) / 2
            if not is_stall(wall, median):
                median = None
        walls.append(wall)
        return median


def profiler_host_events():
    """Non-destructive peek at the profiler's `_HostEventRecorder`
    stream (the `engine.step`/`engine.prefill`/`engine.decode` and
    `engine.<phase>` spans `RecordEvent` emits while a Profiler
    records — the same names the `TraceRecorder` gives the phases).
    Lazy import: the profiler package is stdlib-only too, but tracing
    must stay importable standalone."""
    from paddle_tpu.profiler.profiler import _recorder

    return _recorder.peek()


def merge_trace_events(groups):
    """Merge named event streams onto one timeline: `groups` is an
    iterable of (process_name, events). Each group is re-pidded to a
    stable small integer (1, 2, …) with a `process_name` metadata
    event, so Perfetto renders one track group per engine/replica/
    profiler stream — events share the monotonic clock (module
    docstring), so no timestamp shifting happens here."""
    out = []
    for pid, (pname, events) in enumerate(groups, start=1):
        out.append({"name": "process_name", "ph": "M", "pid": pid,
                    "tid": 0, "args": {"name": pname}})
        for ev in events:
            e = dict(ev)
            e["pid"] = pid
            out.append(e)
    return out


def export_timeline(path, groups):
    """Write merged `groups` (see `merge_trace_events`) as one Chrome
    trace-event / Perfetto JSON file. Returns the event count."""
    events = merge_trace_events(groups)
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return len(events)
