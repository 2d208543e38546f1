"""`moe_experts_roofline`: weights of the experts touched and the
assignments' rows of the traced decode steps (the larger of their bytes
and FLOPs) over the device time of `moe_grouped_matmul`."""
from benchmarks.lib import kernel_shares


def read(params, facts):
    touched = kernel_shares.slice_counter(facts, "moe_experts_touched")
    held = kernel_shares.slice_counter(facts, "moe_assignments_held")
    work = kernel_shares.architecture_counts(facts).moe_experts_work(
        facts["cfg"], touched, held) if touched else None
    return kernel_shares.share(params, facts, work)
