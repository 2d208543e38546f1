"""`mla_decode_roofline`: the cached rows the traced decode steps' walks
covered — each read once a layer, scored and summed by every head (the
larger of their bytes and FLOPs) — over the device time of
`mla_paged_decode`. None where the program has no such counter or kernel
(a parent that lacks them)."""
from benchmarks.lib import kernel_shares


def read(params, facts):
    rows = kernel_shares.slice_counter(facts, "mla_context_rows")
    counts = kernel_shares.architecture_counts(facts)
    work = counts.mla_decode_work(facts["cfg"], rows) \
        if rows and hasattr(counts, "mla_decode_work") else None
    return kernel_shares.share(params, facts, work)
