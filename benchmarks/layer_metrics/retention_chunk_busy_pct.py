"""`retention_chunk_busy_pct`: device time of the sub-chunk loops (the
HLO `while`s) that ran INSIDE the engine's prefill program over the
traced slice's busy time. An `XLA Ops` event is named by its instruction
alone (`metadata`, which names the program, is not part of it), so the
loops are held to the program by time: an event counts where it starts
inside an `XLA Modules` event whose name matches the data file's
`module`. None where there is no trace, no such module event or no such
loop in one: never 0."""
from __future__ import annotations

import bisect
import re

from benchmarks.lib import trace_reduce


def read(params, facts):
    tr = facts.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    program = re.compile(params["module"])
    runs = trace_reduce.union_intervals(
        [m for m in tr.get("modules") or () if program.search(m[0])])
    starts = [s for s, _ in runs]

    def inside(event):
        i = bisect.bisect_right(starts, event[1]) - 1
        return i >= 0 and event[1] < runs[i][1]

    ns, hits = trace_reduce.pattern_ns(
        [e for e in tr["ops"] if inside(e)], params["patterns"])
    if not hits or ns <= 0:
        return None
    return 100.0 * ns / 1e9 / tr["busy_s"]
