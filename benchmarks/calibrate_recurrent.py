"""`calibrate.py` for a cell of the `serve_closed_recurrent` driver: reads,
on the chip and at the cell's own size and load, what the limits of its
comparison are set from (PERF.md gives the readings beside each limit).
Every set of numbers comes from `driver.numbers()` and goes through
`check.judge` with the limits of the cell's traffic file, as a benchmark
run's do (`calibrate.py` itself may not be edited, reads
`token_logit_gaps()` alone and knows none of the numbers this driver
adds).

    python3 benchmarks/calibrate_recurrent.py --workload <cell> \
        --first-seed 1000 --runs sound,control,carry_dropped,\
row_not_zeroed,state_bf16 [--seconds 20] [--mass 1] [--trace 1]

One process, one seed a run (`--first-seed` + its place in `--runs`):

* `sound` — the program as it is;
* `control` — the program as it is, and beside its numbers the control's:
  the reference in the nearest precision below the configuration's
  (`--control fp8`) put in the program's place;
* three faults PLANTED IN THE PROGRAM before the engine is built, each
  what this mechanism can get wrong and no other model's comparison
  would see: `carry_dropped` (every prefill chunk starts from nought: the
  decode steps see the prompt's last chunk alone), `row_not_zeroed` (a
  slot's state rows are handed out as the last request left them),
  `state_bf16` (the state and the normaliser rounded to bfloat16 after
  every chunk and every decode step).

`--mass 1` adds, for the FIRST run's first checked request, what share of the
retention's weight `a_tj` on the served tokens lies on keys older than
one prefill chunk, read from the reference's own score matrix: the part
of the answer that only a carried state can give.

`--trace 1` takes the first run's traced slice as a benchmark run with
`--trace 1` does and adds the cell's per-layer metrics as their readers
give them, and the names of the programs the slice ran.

Each run is one JSON line on standard output and in
`chiprun_out/calibrate_<cell>.jsonl`. Not part of a benchmark run.
"""
import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

FAULTS = ("carry_dropped", "row_not_zeroed", "state_bf16")


@contextlib.contextmanager
def planted(fault):
    """The program with `fault` in it, for engines built inside."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.engine import PagedKVCache
    from paddle_tpu.ops import retention
    from paddle_tpu.ops.pallas import retention as kernel

    saved = [(retention, "power_retention_chunk"),
             (kernel, "retention_decode_update"),
             (retention, "_state_step_xla"),
             (PagedKVCache, "allocate_state")]
    saved = [(o, n, getattr(o, n)) for o, n in saved]
    chunk, update, step_xla, allocate = (s[2] for s in saved)

    def low(x):
        return x.astype(jnp.bfloat16).astype(x.dtype)

    if fault == "carry_dropped":
        retention.power_retention_chunk = \
            lambda q, k, v, log_g, state, norm, *rest: chunk(
                q, k, v, log_g, jnp.zeros_like(state),
                jnp.zeros_like(norm), *rest)
    elif fault == "row_not_zeroed":
        def allocate_state(self):
            self._zero_row = lambda arrays, row: arrays
            return allocate(self)

        PagedKVCache.allocate_state = allocate_state
    elif fault == "state_bf16":
        def rounded_chunk(*args):
            y, state, norm = chunk(*args)
            return y, low(state), low(norm)

        def rounded_rows(pool, layer, rows):
            # a row at a time, in place: the pool leaves no room for a
            # second copy of twenty rows
            return jax.lax.fori_loop(
                0, rows.shape[0], lambda i, p: p.at[layer, rows[i]].set(
                    low(p[layer, rows[i]])), pool)

        def rounded_update(pool, layer, rows, *rest, **kw):
            y, pool = update(pool, layer, rows, *rest, **kw)
            return y, rounded_rows(pool, layer, rows)

        def rounded_step(pool, layer, rows, *rest):
            y, pool = step_xla(pool, layer, rows, *rest)
            return y, rounded_rows(pool, layer, rows)

        retention.power_retention_chunk = rounded_chunk
        kernel.retention_decode_update = rounded_update
        retention._state_step_xla = rounded_step
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    try:
        yield
    finally:
        for owner, name, value in saved:
            setattr(owner, name, value)


def mass_older_than(driver, span):
    """For the first checked request: the share of the retention's weight
    on the served tokens that lies on keys `span` and more tokens back,
    from the reference's score matrix (float32). -> the mean over layers,
    heads and served tokens, and each (layer, KV head)'s own mean."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import weights

    ctx, cfg, ref = driver.ctx, driver.cfg, driver.ctx.reference
    rid = driver.sample()[0]
    seq = np.asarray(driver.results[rid], np.int32)
    plen = len(driver.issued[rid]["prompt"])
    model = ref.build(cfg, ctx.reference_common.MM["f32"])
    index = model.index()
    batch = {"input_ids": jnp.asarray(seq[None, :-1])}
    rep = cfg["num_attention_heads"] // cfg["num_key_value_heads"]

    def leaves(seg):
        return tuple(weights.make_leaves(
            ctx.seed, model.spec, [index[n] for n in seg.leaves],
            driver.dtype))

    @jax.jit
    def share(p, x):
        p = [a.astype(jnp.float32) for a in p]
        q, k, _, log_g = ref.projections(
            p[1:9], ref.rms_norm(x, p[0], cfg["rms_norm_eps"]), cfg,
            ctx.reference_common.MM["f32"])
        a = ref.retention_weights(q, k, log_g)[0, :, plen - 1:]
        t = jnp.arange(plen - 1, a.shape[-1])[:, None]
        old = jnp.arange(a.shape[-1])[None, :] <= t - span
        part = jnp.sum(jnp.where(old, a, 0.0), -1) / (jnp.sum(a, -1)
                                                      + ref.EPS)
        return jnp.mean(part.reshape(-1, rep, part.shape[-1]), (1, 2))

    x, heads = None, []
    for seg in model.segments:
        p = leaves(seg)
        if x is not None:
            heads.append(np.asarray(share(p, x)))
        x = ctx.reference_stepwise._fwd(seg.fn)(p, x, batch)
    heads = np.stack(heads)                       # [layers, kv_heads]
    return {"request": int(rid), "span": int(span),
            "mean_pct": 100 * float(heads.mean()),
            "heads_over_tenth": int((heads > 0.1).sum()),
            "heads": int(heads.size),
            "by_head_pct": np.round(100 * heads, 2).tolist()}


def main(argv=None):
    from benchmarks.calibrate import verdict
    from benchmarks.lib import harness, program, registry
    from benchmarks.lib.tracing import Tracer

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--runs", default="sound,control," + ",".join(FAULTS))
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--mass", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--require-tpu", type=int, choices=(0, 1), default=1)
    ap.add_argument("--bench-dir", default=None)
    args = ap.parse_args(argv)

    bench, cell, traffic, config = harness.load_cell(
        args.workload, args.bench_dir, args.bench_dir)
    devs = harness.device_facts(cell["chips"], bool(args.require_tpu))
    peaks = None
    if args.require_tpu:
        program.enable_compile_cache()
        peaks = registry.peaks(devs[0].device_kind, args.bench_dir)
    limits = traffic["limits"]
    os.makedirs("chiprun_out", exist_ok=True)
    log = open(f"chiprun_out/calibrate_{args.workload}.jsonl", "a")

    for k, run in enumerate(args.runs.split(",")):
        seed = args.first_seed + k
        t0 = time.perf_counter()
        driver = harness.make_driver(cell, traffic, config, seed,
                                     args.bench_dir)
        tracer = Tracer(args.trace and k == 0, cell["chips"])
        with planted(run if run in FAULTS else None):
            driver.setup()
            counted = driver.window(args.seconds, tracer)
        e2e, facts = driver.end_to_end(), driver.facts()
        driver.free()
        numbers, detail = driver.numbers()
        row = {"workload": args.workload, "seed": seed, "run": run,
               "numbers": numbers, "detail": detail,
               "verdict": verdict(numbers, limits),
               "counted": counted, "end_to_end": e2e}
        if run == "control":
            gap, _ = driver.token_logit_gaps(mm=args.control, served=False)
            row["control"] = {"token_logit_gap": gap,
                              **driver.gap_numbers}
            row["control_verdict"] = verdict(row["control"], limits)
        if tracer.reduced:
            facts.update(peaks=peaks, chips=cell["chips"],
                         trace=tracer.reduced,
                         memory_peak_bytes=harness.memory_peak(devs))
            row["per_layer"] = {
                m["name"]: harness.read_layer_metric(m["name"], facts,
                                                     args.bench_dir)
                for m in registry.metrics_for(bench, "per_layer",
                                              args.workload)}
            row["programs"] = sorted({m[0] for m in
                                      tracer.reduced["modules"]})
        if args.mass and k == 0:
            row["mass_older_than_chunk"] = mass_older_than(
                driver, traffic["engine"]["prefill_chunk"])
        row["seconds"] = time.perf_counter() - t0
        line = json.dumps(row)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()
        del driver
    log.close()


if __name__ == "__main__":
    main()
