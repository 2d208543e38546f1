"""Readers of the program's own host spans, as far as a reader can see
them: of a traced run's host line `facts["trace"]` keeps
`breakdown.idle_gaps`, the ten largest rows of `[span name, seconds of
device idle time filed under it]` (`trace_reduce.idle_gaps`: a gap goes
to the innermost span of the driving thread that covers its midpoint).

The spans are the program's (`paddle_tpu.profiler.RecordEvent`, on the
profiler's clock): `engine.<phase>` round every phase of an engine step,
`engine.step` round the iteration, `trainstep.*` round TrainStep's host
stages. A program without them (the parent of the PR that brought them)
leaves no such row, and the reader returns None: the metric is then left
out of the line.
"""
from __future__ import annotations


def idle_under(params, facts):
    """Percent of the traced slice in which the device idled while the
    host was inside one of `params["spans"]` (innermost).

    None where there is no trace or where no row at all starts with
    `params["prefix"]` (the program writes no such spans); 0.0 where
    such rows exist and none of this metric's is among the ten largest.
    """
    tr = facts.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    rows = tr.get("breakdown", {}).get("idle_gaps") or []
    if not any(name.startswith(params["prefix"]) for name, _ in rows):
        return None
    spans = set(params["spans"])
    return 100.0 * sum(s for name, s in rows if name in spans) \
        / tr["window_s"]
