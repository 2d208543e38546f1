"""Finds the benchmark's data files by the names `BENCHMARK.json` uses.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under `benchmarks/`:

    configs/<config>.json          sizes, source, what was assumed
    traffic/<traffic>.json         the driver kind and its parameters
    layer_metrics/<metric>.json    which reader, which patterns/counts
    drivers/<driver>.py            the loop a traffic file's `driver` names
    constructors/<constructor>.py  the model a config's `constructor` names
    peaks.json                     the chips' published peaks

A later PR adds files and entries in `BENCHMARK.json`; no code here names
a cell, a configuration, a metric, a driver or a constructor.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent      # benchmarks/
REPO_DIR = BENCH_DIR.parent


class BenchmarkDataError(Exception):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def load_json(path):
    path = Path(path)
    if not path.is_file():
        raise BenchmarkDataError(f"no such benchmark data file: {path}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(repo_dir=None):
    return load_json(Path(repo_dir or REPO_DIR) / "BENCHMARK.json")


def find(kind, name, bench_dir=None):
    """`kind` is `traffic` or `layer_metrics`; a file under `bench_dir`
    (the tests' own data) wins over the benchmark's."""
    for root in (bench_dir, BENCH_DIR):
        if root and (Path(root) / kind / f"{name}.json").is_file():
            return load_json(Path(root) / kind / f"{name}.json")
    raise BenchmarkDataError(
        f"no {kind}/{name}.json under {bench_dir or BENCH_DIR}")


_MODULES = {}


def find_module(kind, name, bench_dir=None):
    """The path of `<kind>/<name>.py` (`drivers`, `constructors`, or a
    per-layer metric's own reader under `layer_metrics`), or None."""
    for root in (bench_dir, BENCH_DIR):
        if root and (Path(root) / kind / f"{name}.py").is_file():
            return Path(root) / kind / f"{name}.py"
    return None


def load_module(kind, name, bench_dir=None):
    """`<kind>/<name>.py`, imported by its path: a file dropped in is
    found by the name a data file gives, with no table to edit."""
    path = find_module(kind, name, bench_dir)
    if path is None:
        raise BenchmarkDataError(
            f"no {kind}/{name}.py under {bench_dir or BENCH_DIR}")
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def cell(bench, name):
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchmarkDataError(
        f"no workload {name!r} in BENCHMARK.json (has: "
        f"{[w['name'] for w in bench['workloads']]})")


def config_file(bench, name, repo_dir=None):
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(Path(repo_dir or REPO_DIR) / c["file"])
    raise BenchmarkDataError(f"no config {name!r} in BENCHMARK.json")


def metrics_for(bench, section, cell_name):
    """The metrics of `end_to_end` or `per_layer` that this cell reports:
    those with no `workloads` key, and those that list the cell."""
    return [m for m in bench[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def peaks(device_kind, bench_dir=None):
    table = load_json(Path(bench_dir or BENCH_DIR) / "peaks.json")
    if device_kind not in table or device_kind.startswith("_"):
        raise BenchmarkDataError(
            f"device_kind {device_kind!r} is not in peaks.json; add its "
            "published peaks with their source (no default is assumed)")
    return table[device_kind]
