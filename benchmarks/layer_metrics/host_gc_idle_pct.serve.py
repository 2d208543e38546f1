"""`host_gc_idle_pct.serve`: the traced slice's device idle time filed
under the program's `host.gc` span, over the slice
(`lib/program_pauses.idle_under_pauses`); None where the program has no
such span."""
from benchmarks.lib.program_pauses import idle_under_pauses as read  # noqa: F401
