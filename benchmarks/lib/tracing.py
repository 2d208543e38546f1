"""The traced slice: a few steps (or a second or two of serving) under
`jax.profiler`, taken right AFTER the measured window has closed, with
the load still running, so that the profiler's own cost never falls
into the window's numbers. Spans recorded here are the harness's own,
around its calls into the program (`jax.profiler.TraceAnnotation`, the
same clock as the device lines).
"""
from __future__ import annotations

import contextlib
import shutil
import tempfile

from . import trace_reduce


class Tracer:
    def __init__(self, enabled, chips=1):
        self.enabled = bool(enabled)
        self.chips = chips
        self.active = False
        self.reduced = None

    def span(self, name):
        if not self.active:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def slice(self):
        """Profile the body; afterwards `self.reduced` holds what
        `trace_reduce.reduce_trace` made of it (None: nothing ran on a
        device line)."""
        import jax

        out = tempfile.mkdtemp(prefix="bench_trace_")    # under TMPDIR
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(out, profiler_options=options)
        self.active = True
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.SLICE_SPAN):
                yield
        finally:
            self.active = False
            jax.profiler.stop_trace()
            try:
                self.reduced = trace_reduce.reduce_trace(
                    trace_reduce.load_xplane(
                        trace_reduce.find_xplane(out)), self.chips)
            finally:
                shutil.rmtree(out, ignore_errors=True)
