"""`moe_prefill_experts_busy_pct`: device time of `moe_grouped_matmul`
inside the engine's prefill chunks over the traced slice's busy time."""
from benchmarks.lib.kernel_shares import busy_share as read  # noqa: F401
