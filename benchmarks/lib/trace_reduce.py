"""From a profiler trace to numbers: busy union, idle share, the time
of the events that match a pattern, the heaviest operations, and the
idle gaps named by what the host was doing.

The arithmetic works on plain tuples `(name, start_ns, dur_ns)` so that
it can be checked on a small synthetic list; `load_xplane` turns the
`.xplane.pb` that `jax.profiler` writes into those tuples with nothing
but JAX (`jax.profiler.ProfileData`).

What a TPU v5e trace looks like (JAX 0.9.0, seen by hand in PR 25): one
plane per chip named `/device:TPU:<n>`, with the lines `Steps`,
`XLA Modules` (one event per executed program, named after the jitted
function, e.g. `jit_step_fn(...)`), `XLA Ops` (one event per HLO
instruction that ran; a Pallas kernel is a custom call whose event name
carries the kernel's name) and `XLA TraceMe`; host threads are lines of
the plane `/host:CPU`, where `jax.profiler.TraceAnnotation` spans land.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = re.compile(r"^/host:")
#: the span the harness puts round the traced slice of the window
SLICE_SPAN = "bench.trace_slice"


def union_intervals(events):
    """Merged, sorted `[start, end)` intervals of the events."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    out = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def busy_ns(events, window=None):
    """Nanoseconds in which at least one event ran, clipped to
    `window=(start, end)` where given."""
    total = 0
    for s, e in union_intervals(events):
        if window is not None:
            s, e = max(s, window[0]), min(e, window[1])
        if e > s:
            total += e - s
    return total


def window_of(events):
    """First start to last end of the events."""
    if not events:
        return None
    return (min(s for _, s, _ in events),
            max(s + d for _, s, d in events))


def self_times(events):
    """`(name, self_ns)` per event: its duration less what the events
    nested inside it cover (an HLO `while` or `call` contains the
    instructions of its body on the same line)."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []          # stack of [name, end, self]
    for name, s, d in order:
        while stack and s >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    while stack:
        done = stack.pop()
        out.append((done[0], done[2]))
    return out


def pattern_ns(events, patterns):
    """Summed duration of the outermost events whose name matches any
    of the regular expressions (an event nested in a matching one is
    not counted twice)."""
    rx = [re.compile(p) for p in patterns]
    hit = [e for e in events if any(r.search(e[0]) for r in rx)]
    return busy_ns(hit), len(hit)


_HLO = re.compile(r"^%?(?P<inst>\S+) = (?P<out>.*?) (?P<op>[\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


@functools.lru_cache(maxsize=1 << 16)
def op_group(name):
    """An `XLA Ops` event is named by its whole HLO instruction. Group
    by what it is, not by its number: the operation and its output
    types (the same fusion of every layer falls into one row); a custom
    call keeps its kernel's name where the text carries one."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    out = _LAYOUT.sub("", m["out"])
    kernel = re.search(r'kernel_name\W+([\w\-\.]+)', name)
    if kernel:
        return f"{m['op']} {kernel[1]} {out}"[:120]
    return f"{m['op']} {out}"[:120]


def top_ops(events, n=10):
    """`[[group, seconds], ...]`: self time summed by `op_group`,
    heaviest first."""
    agg = {}
    for name, ns in self_times(events):
        name = op_group(name)
        agg[name] = agg.get(name, 0) + ns
    rows = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


def innermost_segments(events):
    """Nested spans of ONE thread -> sorted, disjoint `(start, end,
    name)`: at every instant the innermost span that covers it."""
    out, stack, cursor = [], [], 0       # stack of (name, end)

    def emit(upto):
        nonlocal cursor
        if stack and upto > cursor:
            out.append((cursor, upto, stack[-1][0]))
        cursor = max(cursor, upto)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        if stack:
            emit(s)
        cursor = max(cursor, s)
        stack.append((name, s + d))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def idle_gaps(events, host_events, window=None, n=10):
    """`[[host_span_name, seconds], ...]`: the device's idle time inside
    the window, summed by the innermost span of the driving host thread
    that covers each gap's midpoint (`(no host span)` where none does),
    longest first."""
    window = window or window_of(events)
    if not window:
        return []
    segments = innermost_segments(host_events)
    starts = [s[0] for s in segments]
    agg, prev = {}, window[0]

    def gap(s, e):
        i = bisect.bisect_right(starts, (s + e) / 2) - 1
        name = segments[i][2] if i >= 0 and segments[i][1] > (s + e) / 2 \
            else "(no host span)"
        agg[name] = agg.get(name, 0) + (e - s)

    for s, e in union_intervals(events):
        s, e = max(s, window[0]), min(e, window[1])
        if e <= s:
            continue
        if s > prev:
            gap(prev, s)
        prev = max(prev, e)
    if window[1] > prev:
        gap(prev, window[1])
    rows = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path):
    """-> `{"devices": {plane: {line: [(name, start_ns, dur_ns)]}},
    "host": [(name, start_ns, dur_ns)]}` from one `.xplane.pb`; `host`
    is the one thread that drives the program."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events]
            devices[plane.name] = lines
        elif HOST_PLANE.match(plane.name):
            # the thread that drives the program: the one that carries
            # the harness's own span round the slice
            for line in plane.lines:
                evs = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                       for ev in line.events]
                if any(e[0] == SLICE_SPAN for e in evs):
                    host = evs
    return {"devices": devices, "host": host}


def reduce_trace(trace, chips=1):
    """What the result line needs from a loaded trace: `busy_s` and
    `window_s` averaged over the chips used, the ops of the fullest
    chip for the per-layer readers, and the breakdown."""
    per_dev = []
    for name in sorted(trace["devices"])[:chips]:
        ops = trace["devices"][name].get(OPS_LINE, [])
        if ops:
            per_dev.append(ops)
    if not per_dev:
        return None
    # the traced slice is the harness's own host span where the trace
    # has it (same clock as the device lines), else the ops' extent
    window = next(((s, s + d) for n, s, d in trace["host"]
                   if n == SLICE_SPAN and d > 0), None) \
        or window_of([e for ops in per_dev for e in ops])
    busy = [busy_ns(ops, window) for ops in per_dev]
    ops0 = per_dev[0]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (window[1] - window[0]) / 1e9,
        "window": window,
        "ops": ops0,
        "modules": trace["devices"][sorted(trace["devices"])[0]]
        .get(MODULES_LINE, []),
        "breakdown": {
            "device_ops": top_ops(ops0),
            "idle_gaps": idle_gaps(ops0, trace["host"], window),
        },
    }
