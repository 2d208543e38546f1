"""`engine_idle_finish_pct`: `lib/span_readers.idle_under` over the spans `engine_idle_finish_pct.json` lists."""
from benchmarks.lib.span_readers import idle_under as read  # noqa: F401
