"""`retention_decode_roofline`: state bytes read and written of the live
lanes of the traced decode steps over the device time of
`power_retention_decode`."""
from benchmarks.lib import kernel_shares


def read(params, facts):
    lanes = kernel_shares.slice_counter(facts, "decode_live_lanes")
    work = kernel_shares.architecture_counts(facts).retention_decode_work(
        facts["cfg"], lanes) if lanes else None
    return kernel_shares.share(params, facts, work)
