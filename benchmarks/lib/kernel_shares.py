"""A kernel's share of its roofline where the work is counted by the
ENGINE'S OWN counters over the traced slice (`lib/readers.KERNEL_WORK`
counts a GPT-2 block from shapes and may not be edited): the least time
the chip could take for the work (the larger of FLOPs over peak and bytes
over bandwidth) over the device time of the events that match the
metric's `patterns`, in percent. None where there is no trace, no peak,
no matching event or nothing counted: never 0.

`busy_share` is the other question about a kernel: not how near its
bound it runs, but how much of the device's busy time it takes — for
work a better program would not do at all, a share of a roofline reads
well while the time is lost.
"""
from __future__ import annotations

import importlib

from . import trace_reduce


def architecture_counts(facts):
    return importlib.import_module(
        f"benchmarks.lib.counts_{facts['cfg']['architecture']}")


def slice_counter(facts, name):
    return (facts.get("counters", {}).get("slice") or {}).get(name)


def share(params, facts, work):
    """`work` is `(flops, bytes)` of the traced slice, or None."""
    tr, peaks = facts.get("trace"), facts.get("peaks")
    if not tr or not peaks or work is None:
        return None
    ns, hits = trace_reduce.pattern_ns(tr["ops"], params["patterns"])
    if not hits or ns <= 0:
        return None
    flops, nbytes = work
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)


def busy_share(params, facts):
    """Device time of the events that match `patterns` over the busy
    time of the traced slice, in percent."""
    tr = facts.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    ns, hits = trace_reduce.pattern_ns(tr["ops"], params["patterns"])
    if not hits or ns <= 0:
        return None
    return 100.0 * ns / 1e9 / tr["busy_s"]
