"""Reads, on the chip and at a cell's own size, what the limits of
`correct` are set from (PERF.md gives the readings beside each limit):

* the program's numbers on a dozen seeds or more (the lower reading is
  their largest),
* the control's: the reference put in the program's place and computed
  in the nearest precision below the configuration's (`--control fp8`
  for a bf16 cell), which has to fail one of the cell's numbers,
* for a training cell, the fault "half of the batch left out, the mean
  taken over the rest", planted in the reference put in its place.

    python3 benchmarks/calibrate.py --workload <cell> --seeds 12 \
        --control-seeds 3 --first-seed 1000 [--seconds 8]

One process reads every seed (set-up is most of a run's cost). Every
set of numbers goes through the cell's own comparison (`check.judge`
with the limits of its traffic file): `verdict` says whether the program
came out correct and the control and the fault did not, and by which
numbers. Each seed's readings are one JSON line on standard output and in
`chiprun_out/calibrate_<cell>.jsonl`. Not part of a benchmark run.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def verdict(numbers, limits):
    """The cell's own comparison over these numbers: `correct`, and the
    names that failed (only the limits of numbers that were read)."""
    from benchmarks.lib import check

    ok, checks = check.judge(
        numbers, {k: v for k, v in limits.items() if k in numbers})
    return {"correct": ok,
            "fails": [k for k, c in checks.items()
                      if not c["value"] <= c["limit"]]}


def main():
    from benchmarks.lib import check, harness, program
    from benchmarks.lib.tracing import Tracer

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()

    _, cell, traffic, config = harness.load_cell(args.workload)
    harness.device_facts(cell["chips"], require_tpu=True)
    program.enable_compile_cache()
    limits = traffic["limits"]
    os.makedirs("chiprun_out", exist_ok=True)
    log = open(f"chiprun_out/calibrate_{args.workload}.jsonl", "a")

    for k in range(args.seeds):
        seed = args.first_seed + k
        t0 = time.perf_counter()
        driver = harness.make_driver(cell, traffic, config, seed)
        driver.setup()
        t_setup = time.perf_counter() - t0
        row = {"workload": args.workload, "seed": seed}
        with_control = k < args.control_seeds
        planted = {}
        if traffic["driver"] == "train":
            driver.free()
            t1 = time.perf_counter()
            ref = driver.reference_numbers()
            t_ref = time.perf_counter() - t1
            row["program"], row["worst_leaf"] = check.train_numbers(
                driver.prog, ref)
            row["loss"] = {"program": driver.prog["loss"],
                           "reference": ref["loss"]}
            if with_control:
                planted["control"], _ = check.train_numbers(
                    driver.reference_numbers(mm=args.control), ref)
                planted["half_batch"], _ = check.train_numbers(
                    driver.reference_numbers(half_batch=True), ref)
        else:
            counted = driver.window(args.seconds, Tracer(False))
            e2e = driver.end_to_end()
            facts = driver.facts()
            driver.free()
            t1 = time.perf_counter()
            gap, n = driver.token_logit_gaps()
            t_ref = time.perf_counter() - t1
            row["program"] = {"token_logit_gap": gap,
                              "wrong_answers": driver.wrong_answers(),
                              **driver.guards()}
            row["served_tokens_compared"] = n
            row["counted"], row["end_to_end"] = counted, e2e
            row["facts"] = {k_: facts[k_] for k_ in (
                "engine_step_ms_median", "ttft_p50_ms", "tokens",
                "engine_steps", "requests_finished", "pool_blocks",
                "pool_blocks_used_mean", "pool_blocks_used_max")}
            if with_control:
                cgap, _ = driver.token_logit_gaps(mm=args.control,
                                                  served=False)
                planted["control"] = {"token_logit_gap": cgap}
        row["verdict"] = {"program": verdict(row["program"], limits)}
        for name, numbers in planted.items():
            row[name] = numbers
            row["verdict"][name] = verdict(numbers, limits)
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
