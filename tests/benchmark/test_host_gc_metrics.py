"""The two per-layer metrics that read the program's `host.gc` span
(ISSUE 37), on synthetic `facts` (CPU; nothing here is a device number):
a reading where a row is there, 0.0 where none is and the program has the
hook, None with no trace or on a program without the hook."""
import pytest

from benchmarks.lib import harness, program_pauses, registry

METRICS = ["host_gc_idle_pct.train", "host_gc_idle_pct.serve"]
CELLS = {
    "host_gc_idle_pct.train": ["gpt1p3b_train", "bert_base_finetune"],
    "host_gc_idle_pct.serve": ["gpt1p3b_serve_chat", "gpt1p3b_serve_longctx",
                               "nemotron3s_serve_chat",
                               "brumby14b_serve_docgen"],
}


@pytest.fixture
def hooked():
    from paddle_tpu.observability.tracing import install_host_pause_hooks

    install_host_pause_hooks()
    assert program_pauses.hooks_installed()


def facts_with(rows, window_s=2.0):
    return {"trace": {"window_s": window_s, "busy_s": 1.9,
                      "breakdown": {"device_ops": [], "idle_gaps": rows}}}


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("rows,expect", [
    ([["host.gc", 0.04], ["engine.dispatch", 0.03],
      ["np.asarray(jax.Array)", 0.01]], 2.0),
    ([["engine.dispatch", 0.03], ["bench.train_step", 0.01]], 0.0),
    ([], 0.0),
])
def test_a_reading_and_nought_with_the_hook(hooked, metric, rows, expect):
    got = harness.read_layer_metric(metric, facts_with(rows), None)
    assert got == pytest.approx(expect)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("facts", [{}, {"trace": None},
                                   facts_with([["host.gc", 0.1]], 0.0)])
def test_nothing_without_a_trace(hooked, metric, facts):
    assert harness.read_layer_metric(metric, facts, None) is None


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_on_a_program_without_the_hook(monkeypatch, metric):
    """The parent of ISSUE 37 has no `host.gc` span: its trace's lack of
    a row is no reading, so the metric is left out of its line."""
    import paddle_tpu.observability.tracing as tracing

    monkeypatch.delattr(tracing, "host_pauses")
    assert not program_pauses.hooks_installed()
    assert harness.read_layer_metric(
        metric, facts_with([["engine.dispatch", 0.1]]), None) is None


def test_benchmark_json_lists_the_six_cells():
    bench = registry.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-2:]] == METRICS
    for metric, cells in CELLS.items():
        m = by_name[metric]
        assert m["workloads"] == cells
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "%", "lower", "device_trace",
            "host runtime (observability/tracing.py)")
        params = registry.find("layer_metrics", metric)
        assert (params["reader"], params["spans"]) == ("device_idle",
                                                       ["host.gc"])
    assert by_name["host_gc_idle_pct.train"]["moves"] == "train_tokens_per_s"
    assert by_name["host_gc_idle_pct.serve"]["moves"] == "tpot_p95_ms"
    # every cell listed reports the end-to-end metric the share moves
    for metric, cells in CELLS.items():
        for cell in cells:
            assert by_name[metric]["moves"] in {
                m["name"] for m in registry.metrics_for(
                    bench, "end_to_end", cell)}
    # the latent cell's list is pinned with == by its own test
    assert "pangu_ultra_serve_docqa" not in CELLS["host_gc_idle_pct.serve"]
