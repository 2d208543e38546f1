"""What `drivers/serve_closed_stateful.py` takes from the program beside
`lib/program.py` (which may not be edited): weights bound a leaf at a
time, the engine's own counters of a model with recurrent state and
sparse experts, and the path statistics of the scan and the expert
product. Imports of `paddle_tpu` happen inside the functions.
"""
from __future__ import annotations


def bind_weights_leafwise(model, seed, spec, dtype):
    """Give every parameter the seeded array of its name, ONE LEAF AT A
    TIME: each array is made, bound and the one it replaces dropped
    before the next is made, so set-up never holds two copies of the
    weights. A name or a shape that does not match is an error."""
    from . import weights

    named = dict(model.named_parameters())
    names = [s[0] for s in spec]
    if set(named) != set(names):
        raise ValueError(
            "parameter names differ: program only "
            f"{sorted(set(named) - set(names))[:5]}, reference only "
            f"{sorted(set(names) - set(named))[:5]}")
    for i, (name, shape, _) in enumerate(spec):
        p = named[name]
        (a,) = weights.make_leaves(seed, spec, [i], dtype)
        if tuple(p.shape) != tuple(shape) or p._array.dtype != a.dtype:
            raise ValueError(f"{name}: program {p.shape} "
                             f"{p._array.dtype}, seeded {shape} {a.dtype}")
        p._in_place_update(a)


def engine_counters(engine):
    """The engine's running totals -> (`sums`: decode steps and the
    model's summed decode-step counters, `highs`: its high-water marks),
    the kinds as the model's serving spec names them."""
    totals = engine.step_counter_totals
    kinds = dict(engine.spec.step_counters)
    sums = {k: v for k, v in totals.items() if kinds[k] == "sum"}
    sums["decode_steps"] = engine.decode_steps
    return sums, {k: v for k, v in totals.items() if kinds[k] == "max"}


def state_rows_used(engine):
    return engine.cache.state_rows_used


def state_rows_total(engine):
    return engine.cache.state_rows


def state_pool_bytes(engine):
    return engine.cache.state_nbytes()


def kernel_path_stats(reset=False):
    """`{"ssm": {...}, "moe": {...}}`: which form of the scan's decode
    step and of the expert product was traced."""
    from paddle_tpu.distributed import moe
    from paddle_tpu.ops import ssm

    if reset:
        ssm.reset_ssm_path_stats()
        moe.reset_moe_path_stats()
    return {"ssm": dict(ssm.SSM_PATH_STATS),
            "moe": dict(moe.MOE_PATH_STATS)}
