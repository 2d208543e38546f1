"""What the program says of its own host pauses (ISSUE 37), as
`lib/program.py` reads its path statistics: a program that wraps every
garbage collection in a `host.gc` span (`paddle_tpu.observability.
tracing.install_host_pause_hooks`, installed when an engine or a
`TrainStep` is built), and the readers of that span.

A program without the hook (the parent of the PR that brought it) has
no such span, so a trace without a `host.gc` row means nothing there:
the readers return None, and the metric is left out of the line.
Imports of `paddle_tpu` happen inside the functions.
"""
from __future__ import annotations

import importlib


def hooks_installed():
    """Whether this process's program clocks its collections in
    `host.gc` spans: False where the program has no such hook."""
    try:
        tracing = importlib.import_module(
            "paddle_tpu.observability.tracing")
    except ImportError:
        return False
    pauses = getattr(tracing, "host_pauses", None)
    return bool(pauses is not None and pauses().installed)


def idle_under_pauses(params, facts):
    """Percent of the traced slice in which the device idled while the
    host was inside one of `params["spans"]` (`breakdown.idle_gaps`,
    innermost span at each gap's midpoint). 0.0 where the trace has no
    such row and the program has the hook; None where there is no
    trace or no hook."""
    tr = facts.get("trace")
    if not tr or not tr.get("window_s") or not hooks_installed():
        return None
    spans = set(params["spans"])
    rows = tr.get("breakdown", {}).get("idle_gaps") or []
    return 100.0 * sum(s for name, s in rows if name in spans) \
        / tr["window_s"]
