"""One run of one cell: set-up, the measured window, the numbers, the
comparison, the result line. `run.py` is the command; tests call
`run_cell` in-process with `require_tpu=False`.

    python benchmarks/run.py --workload <cell> --seed <n>
                             --seconds <s> --trace <0|1>
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
import types

from . import check, readers, registry
from .tracing import Tracer

class NoAccelerator(SystemExit):
    pass


def say(msg):
    print(f"[bench +{time.perf_counter() - T0:.1f}s] {msg}",
          file=sys.stderr, flush=True)


T0 = time.perf_counter()


def device_facts(chips, require_tpu):
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise NoAccelerator(f"benchmark: JAX found no TPU "
                            f"(platform={d0.platform}); no result")
    if len(devs) < chips:
        raise NoAccelerator(f"benchmark: the cell asks for {chips} "
                            f"chip(s), JAX reports {len(devs)}; no result")
    return devs[:chips]


def memory_peak(devs):
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        say(f"memory_stats of device {d.id}: {st}")
        # the allocator's peak, and what XLA reserved beside it for the
        # programs' temporaries (BERT-base at 64 x 512: 1.3 GB of
        # arrays and 9.5 GB of temporaries, PR 25)
        peaks.append(int(st.get("peak_bytes_in_use", 0))
                     + int(st.get("peak_bytes_reserved", 0)))
    return max(peaks)


def read_layer_metric(name, facts, bench_dir):
    """The metric's own reader: `layer_metrics/<name>.py` where the
    metric brings one, else the kind its data file names."""
    params = registry.find("layer_metrics", name, bench_dir)
    if registry.find_module("layer_metrics", name, bench_dir):
        return registry.load_module("layer_metrics", name,
                                    bench_dir).read(params, facts)
    return readers.READERS[params["reader"]](params, facts)


def load_cell(workload, repo_dir=None, bench_dir=None):
    """-> (BENCHMARK.json, the cell's entry, its traffic file, its
    configuration file), each found by the name the one before gives."""
    bench = registry.load_benchmark(repo_dir)
    cell = registry.cell(bench, workload)
    traffic = registry.find("traffic", cell["traffic"], bench_dir)
    config = registry.config_file(bench, cell["config"], repo_dir)
    return bench, cell, traffic, config


def make_driver(cell, traffic, config, seed, bench_dir=None):
    """The cell's driver (`drivers/<traffic's driver>.py`, class `Cell`),
    with the configuration's plain reference."""
    ctx = types.SimpleNamespace(
        seed=int(seed), config=config, traffic=traffic,
        chips=cell["chips"], bench_dir=bench_dir,
        reference=importlib.import_module(
            f"benchmarks.reference.{config['reference']}"),
        reference_common=importlib.import_module(
            "benchmarks.reference.common"),
        reference_stepwise=importlib.import_module(
            "benchmarks.reference.stepwise"))
    return registry.load_module("drivers", traffic["driver"],
                                bench_dir).Cell(ctx)


def run_cell(workload, seed, seconds, trace, t_start=None,
             require_tpu=True, repo_dir=None, bench_dir=None,
             out=sys.stdout):
    """Runs the cell and prints the result line; returns the result."""
    t_start = T0 if t_start is None else t_start
    bench, cell, traffic, config = load_cell(workload, repo_dir, bench_dir)
    chips = cell["chips"]

    devs = device_facts(chips, require_tpu)
    d0 = devs[0]
    if require_tpu:
        peaks = registry.peaks(d0.device_kind, bench_dir)
        from . import program

        say(f"compile cache: {program.enable_compile_cache()}")
    else:                     # a rehearsal reads no share of a peak
        peaks = None
    driver = make_driver(cell, traffic, config, seed, bench_dir)

    say(f"cell {workload}: config {cell['config']}, traffic "
        f"{cell['traffic']}, seed {seed}, device {d0.device_kind}")
    driver.setup()
    setup_s = time.perf_counter() - t_start
    say(f"set-up done in {setup_s:.1f}s; window of {seconds}s")
    tracer = Tracer(trace, chips)
    counted = driver.window(float(seconds), tracer)
    peak_bytes = memory_peak(devs)
    say(f"window closed: {counted}; peak {peak_bytes / 1e9:.2f} GB")

    facts = driver.facts()
    facts.update(peaks=peaks, chips=chips, trace=tracer.reduced,
                 memory_peak_bytes=peak_bytes)
    if trace:
        wanted = registry.metrics_for(bench, "per_layer", workload)
        values = {}
        for m in wanted:
            v = read_layer_metric(m["name"], facts, bench_dir)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = dict(driver.end_to_end(), setup_s=setup_s)
        say(f"end to end, all that the driver reads: {json.dumps(e2e)}")
        values = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                  for m in registry.metrics_for(bench, "end_to_end",
                                                workload)}

    # the comparison: after the window, the peak read, the state freed
    driver.free()
    t_ref = time.perf_counter()
    numbers, detail = driver.numbers()
    correct, checks = check.judge(numbers, traffic["limits"])
    say(f"reference and comparison took "
        f"{time.perf_counter() - t_ref:.1f}s; detail: {json.dumps(detail)}")

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": chips, "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), **counted, "metrics": values,
              "device": device}
    if trace and tracer.reduced:
        device["busy_s"] = tracer.reduced["busy_s"]
        device["window_s"] = tracer.reduced["window_s"]
        result["breakdown"] = tracer.reduced["breakdown"]
    result["checks"] = checks                 # last, each beside its limit
    for name, c in checks.items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r}"
              f"{'' if c['value'] <= c['limit'] else '  <-- FAILS'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None, t_start=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    run_cell(args.workload, args.seed, args.seconds, args.trace,
             t_start=t_start)
