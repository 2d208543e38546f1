"""`engine_idle_dispatch_pct`: `lib/span_readers.idle_under` over the spans `engine_idle_dispatch_pct.json` lists."""
from benchmarks.lib.span_readers import idle_under as read  # noqa: F401
