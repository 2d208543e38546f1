"""The kinds of reader a per-layer metric's data file may name.

`layer_metrics/<metric>.json` says `{"reader": <kind>, ...parameters}`;
a reader gets that dict and the run's `facts` (what the driver counted,
the chip's peaks, and — in a traced run — the reduced trace) and returns
the value, or None where it finds nothing to read: the harness then
leaves the metric out of the line. It never returns 0 for a share of a
roofline or of a peak. A metric that needs arithmetic none of these has
brings `layer_metrics/<metric>.py` with a `read(params, facts)` of its
own, which the harness finds by the metric's name.
"""
from __future__ import annotations

from . import counts, trace_reduce


def _get(facts, dotted):
    cur = facts
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def fact(params, facts):
    """A number the driver counted, by its dotted path, times `scale`."""
    v = _get(facts, params["fact"])
    return v * params.get("scale", 1) if v else None   # 0: not reported


def ratio(params, facts):
    a, b = _get(facts, params["numerator"]), _get(facts,
                                                 params["denominator"])
    if a is None or not b:
        return None
    return a / b * params.get("scale", 1)


def mfu(params, facts):
    """Required FLOPs of the whole window over its whole time, as a
    share of the chip's bf16 peak (times the chips used), in percent."""
    flops, secs = facts.get("flops_required"), facts.get("window_s")
    if not flops or not secs or not facts.get("peaks"):
        return None
    peak = facts["peaks"]["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * flops / secs / peak


def device_idle(params, facts):
    tr = facts.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def kernel_roofline(params, facts):
    """Least time the chip could take for the slice's required work
    (the larger of FLOPs over peak and bytes over bandwidth) over the
    device time of the events that match `patterns`, in percent."""
    tr = facts.get("trace")
    if not tr or not facts.get("peaks"):
        return None
    ns, hits = trace_reduce.pattern_ns(tr["ops"], params["patterns"])
    if not hits or ns <= 0:
        return None
    work = KERNEL_WORK[params["count"]](facts)
    if work is None:
        return None
    flops, nbytes = work
    peaks = facts["peaks"]
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)


def _attention_train(facts):
    t, steps = facts["traffic"], facts.get("slice_steps")
    if not steps:
        return None
    return (steps * counts.attention_train_flops_per_step(
                facts["cfg"], t["batch"], t["seq"]),
            steps * counts.attention_train_bytes_per_step(
                facts["cfg"], t["batch"], t["seq"]))


def _paged_decode(facts):
    """Decode attention is bytes-bound: every decode step reads the
    keys and values of every live context once."""
    tokens = facts.get("slice_context_tokens")
    if not tokens:
        return None
    return (4 * tokens * facts["cfg"]["hidden_size"]
            * facts["cfg"]["num_layers"],
            tokens * counts.kv_bytes_per_token(facts["cfg"]))


KERNEL_WORK = {"attention_train": _attention_train,
               "paged_decode": _paged_decode}

READERS = {"fact": fact, "ratio": ratio, "mfu": mfu,
           "device_idle": device_idle, "kernel_roofline": kernel_roofline}
