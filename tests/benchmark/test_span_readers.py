"""The readers of the program's host spans (`lib/span_readers.py`) and
the three metrics that use them, on synthetic `facts` and synthetic
trace tuples (CPU; nothing here is a device number)."""
import json
from pathlib import Path

import pytest

from benchmarks.lib import harness, registry, span_readers, trace_reduce

BENCH = Path(__file__).resolve().parent.parent.parent / "benchmarks"
METRICS = ["engine_idle_schedule_pct", "engine_idle_dispatch_pct",
           "engine_idle_finish_pct"]
PARAMS = {"prefix": "engine.", "spans": ["engine.finish",
                                         "engine.accept_walk"]}


def facts_with(rows, window_s=2.0):
    return {"trace": {"window_s": window_s, "busy_s": 1.0,
                      "breakdown": {"device_ops": [], "idle_gaps": rows}}}


@pytest.mark.parametrize("rows,expect", [
    # this metric's rows present: their seconds over the slice
    ([["engine.finish", 0.05], ["engine.dispatch", 0.03],
      ["engine.accept_walk", 0.01], ["bench.engine_step", 0.002]], 3.0),
    # the program writes the spans, none of this metric's among the ten
    ([["engine.dispatch", 0.03], ["np.asarray(jax.Array)", 0.02]], 0.0),
    # no `engine.` row at all: a program without these spans
    ([["bench.engine_step", 0.12], ["np.asarray(jax.Array)", 0.002]],
     None),
    ([], None),
])
def test_idle_under_on_synthetic_rows(rows, expect):
    got = span_readers.idle_under(PARAMS, facts_with(rows))
    assert got == pytest.approx(expect) if expect is not None \
        else got is None


@pytest.mark.parametrize("facts", [
    {}, {"trace": None},
    {"trace": {"window_s": 0.0, "breakdown": {"idle_gaps": [
        ["engine.finish", 0.1]]}}}])
def test_idle_under_without_a_trace_reads_nothing(facts):
    assert span_readers.idle_under(PARAMS, facts) is None


@pytest.mark.parametrize("metric", METRICS)
def test_each_metric_reads_its_own_spans_through_the_harness(metric):
    """The harness finds `<metric>.py` by the metric's name; the three
    lists are disjoint, so one row moves one metric."""
    params = registry.find("layer_metrics", metric)
    assert params["prefix"] == "engine."
    others = set()
    for m in METRICS:
        if m != metric:
            others |= set(registry.find("layer_metrics", m)["spans"])
    assert not set(params["spans"]) & others
    rows = [[params["spans"][0], 0.04], ["engine.step", 0.01],
            ["engine.device_wait", 0.01]]
    got = harness.read_layer_metric(metric, facts_with(rows), None)
    assert got == pytest.approx(2.0)
    # on a program without the spans (this PR's parent): left out
    old = facts_with([["bench.engine_step", 0.12]])
    assert harness.read_layer_metric(metric, old, None) is None
    assert harness.read_layer_metric(metric, {"trace": None}, None) is None


def test_benchmark_json_lists_the_three_on_the_serve_cell():
    bench = registry.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for metric in METRICS:
        m = by_name[metric]
        assert m["workloads"] == ["gpt1p3b_serve_chat"]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            ("%", "lower", "device_trace", "serve_tokens_per_s")
        assert m["layer"] == by_name["engine_step_ms"]["layer"]
        assert json.loads((BENCH / "layer_metrics"
                           / f"{metric}.json").read_text())["spans"]


MS = 1_000_000


@pytest.mark.parametrize("inner,expect,reads", [
    # a gap inside engine.finish inside bench.engine_step
    ([("engine.finish", 60 * MS, 30 * MS)], "engine.finish", 6.0),
    # JAX's own span innermost where it covers the gap (and then no
    # `engine.` row is left among the rows: nothing to read)
    ([("engine.finish", 60 * MS, 30 * MS),
      ("np.asarray(jax.Array)", 70 * MS, 10 * MS)],
     "np.asarray(jax.Array)", None),
    # no phase covers the gap: the engine's own residue
    ([("engine.finish", 20 * MS, 5 * MS)], "engine.step", 0.0),
])
def test_a_gap_is_filed_under_the_innermost_span(inner, expect, reads):
    ops = [("%fusion.1 = f32[8] fusion(...)", 0, 72 * MS),
           ("%fusion.2 = f32[8] fusion(...)", 78 * MS, 22 * MS)]
    host = [("bench.trace_slice", 0, 100 * MS),
            ("bench.engine_step", 10 * MS, 85 * MS),
            ("engine.step", 11 * MS, 83 * MS)] + inner
    rows = trace_reduce.idle_gaps(ops, host, (0, 100 * MS))
    assert rows == [[expect, pytest.approx(0.006)]]
    facts = facts_with(rows, window_s=0.1)
    got = span_readers.idle_under(
        {"prefix": "engine.", "spans": ["engine.finish"]}, facts)
    assert got == pytest.approx(reads) if reads is not None \
        else got is None
