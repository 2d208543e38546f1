"""`calibrate.py` for a cell of the `serve_closed_sessions` driver: reads,
on the chip and at the cell's own size, what the limits of its comparison
are set from (PERF.md gives the readings beside each limit):

* the program's numbers on several seeds (the lower reading is their
  largest): both logit gaps, the guards;
* the control's (`--control fp8` for a bf16 cell) and the three planted
  faults' (`drivers/serve_closed_sessions.FAULTS`), each the reference put
  in the program's place, which each have to fail one of the limits.

    python3 benchmarks/calibrate_sessions.py --workload <cell> --seeds 6 \
        --control-seeds 2 --first-seed 1000 [--seconds 8]

One process reads every seed. Each seed's readings are one JSON line on
standard output and in `chiprun_out/calibrate_<cell>.jsonl`. Not part of
a benchmark run (`calibrate.py` itself may not be edited, and knows
neither the second gap nor the faults).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    from benchmarks.calibrate import verdict
    from benchmarks.lib import harness, program, registry
    from benchmarks.lib.tracing import Tracer

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()

    _, cell, traffic, config = harness.load_cell(args.workload)
    harness.device_facts(cell["chips"], require_tpu=True)
    program.enable_compile_cache()
    faults = registry.load_module("drivers", traffic["driver"]).FAULTS
    limits = traffic["limits"]
    os.makedirs("chiprun_out", exist_ok=True)
    log = open(f"chiprun_out/calibrate_{args.workload}.jsonl", "a")

    def gaps(driver, **kw):
        gap, n = driver.token_logit_gaps(**kw)
        return {"token_logit_gap": gap,
                "token_logit_gap_mean": driver.gap_mean}, n

    for k in range(args.seeds):
        seed = args.first_seed + k
        t0 = time.perf_counter()
        driver = harness.make_driver(cell, traffic, config, seed)
        driver.setup()
        t_setup = time.perf_counter() - t0
        counted = driver.window(args.seconds, Tracer(False))
        e2e, facts = driver.end_to_end(), driver.facts()
        guards = driver.guards()
        driver.free()
        t1 = time.perf_counter()
        numbers, n = gaps(driver)
        t_ref = time.perf_counter() - t1
        row = {"workload": args.workload, "seed": seed,
               "program": {**numbers, **guards,
                           "wrong_answers": driver.wrong_answers()},
               "served_tokens_compared": n, "counted": counted,
               "end_to_end": e2e,
               "facts": {k_: facts[k_] for k_ in (
                   "engine_step_ms_median", "ttft_p50_ms", "tokens",
                   "engine_steps", "requests_finished", "pool_blocks",
                   "pool_blocks_used_mean", "pool_blocks_used_max",
                   "prompt_tokens", "prompt_tokens_hit")}}
        planted = {}
        if k < args.control_seeds:
            planted["control"], _ = gaps(driver, mm=args.control,
                                         served=False)
            for fault in faults:
                planted[fault], _ = gaps(driver, served=False, fault=fault)
        row["verdict"] = {"program": verdict(row["program"], limits)}
        for name, read in planted.items():
            row[name] = read
            row["verdict"][name] = verdict(read, limits)
        row["seconds"] = {"setup": t_setup, "reference": t_ref,
                          "all": time.perf_counter() - t0}
        line = json.dumps(row)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()
        del driver
    log.close()


if __name__ == "__main__":
    main()
