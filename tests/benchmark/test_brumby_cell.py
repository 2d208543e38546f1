"""The `serve_closed_recurrent` driver and what the `brumby` configuration
brings to the benchmark, rehearsed on the CPU at a tiny size from
`data/brumby/` (a BENCHMARK.json, a configuration and a traffic file of
this test's own): the cell end to end in-process, the lower-precision
control and a planted fault (a chunk that drops its carried state) shown
to fail, the architecture's count functions against the published
numbers, the new readers on synthetic facts. No device metric's value is
named here.
"""
import io
import json
from pathlib import Path

import pytest

from benchmarks.lib import check, counts_brumby as counts, harness, registry

HERE = Path(__file__).resolve().parent
DATA = HERE / "data" / "brumby"
REPO = HERE.parent.parent
CELL = "tiny_brumby_serve"


@pytest.fixture(scope="module")
def runs():
    cache = {}

    def get(trace):
        if trace not in cache:
            out = io.StringIO()
            cache[trace] = harness.run_cell(
                CELL, 2147489777, 0.4, trace, require_tpu=False,
                repo_dir=DATA, bench_dir=DATA, out=out)
            assert json.loads(out.getvalue().strip().splitlines()[-1]) \
                == json.loads(json.dumps(cache[trace]))
        return cache[trace]

    return get


def test_cell_end_to_end_is_correct_and_reports_its_metrics(runs):
    r = runs(0)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                 "setup_s"}
    assert set(r["checks"]) == {"token_logit_gap", "token_logit_gap_mean",
                                "token_logit_gap_request_mean",
                                "token_logit_gap_over_half_pct",
                                "token_logit_gap_p99",
                                "wrong_answers", "compiles_in_window",
                                "unexpected_kernel_path"}
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]


def test_traced_run_reports_the_state_and_no_pool_and_no_share_of_a_peak(
        runs):
    """On the CPU no device line and no peak: the roofline, the busy share
    and `serve_mfu` are left out, never 0; and with no pool the `ratio`
    reader finds a zero denominator and leaves `kv_pool_fill_pct` out."""
    r = runs(1)
    assert r["correct"] is True
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert {"engine_step_ms", "engine_decode_lanes", "ttft_p50_ms",
            "state_pool_fill_pct"} <= set(m)
    assert not {"serve_mfu", "retention_decode_roofline",
                "retention_chunk_busy_pct", "kv_pool_fill_pct"} & set(m)
    assert 0 < m["state_pool_fill_pct"] <= 75.0     # 3 clients, 4 slots


def _driver():
    _, cell, mix, config = harness.load_cell(CELL, DATA, DATA)
    return harness.make_driver(cell, mix, config, 9, DATA), mix


def test_facts_carry_both_kinds_of_counter_and_the_retentions_path():
    driver, mix = _driver()
    driver.setup()
    driver.window(0.2, harness.Tracer(False), min_finished=20)
    facts = driver.facts()
    window = facts["counters"]["window"]
    assert window["decode_steps"] > 0
    assert 0 < window["decode_live_lanes"] <= 3 * window["decode_steps"]
    # every prompt row of a request that finished was computed by a chunk
    done = [r for r in driver.issued.values()
            if r["phase"] == "window" and "t_done" in r
            and r["t_done"] <= driver.t1]
    assert window["prefill_rows_computed"] >= sum(
        len(r["prompt"]) for r in done) > 0
    assert window["moe_expert_steps"] == 0
    assert facts["counters"]["kernel_paths"]["retention"] == {
        "xla": 2, "pallas": 0}                  # one trace, two layers
    assert facts["pool_blocks"] == 0 and facts["pool_blocks_used_max"] == 0
    assert facts["state_rows"] == 4 and facts["state_pool_bytes"] == \
        2 * 5 * 2 * 192 * (16 + 1) * 4
    assert facts["flops_required"] == sum(
        counts.serve_request_flops(driver.cfg, len(r["prompt"]), r["new"])
        for r in driver.issued.values()
        if r.get("t_done") is not None
        and driver.t0 <= r["t_done"] <= driver.t1) > 0
    assert driver.guards()["unexpected_kernel_path"] == 0
    driver.mix = dict(mix, expect_retention_path="pallas")  # not what ran
    assert driver.guards()["unexpected_kernel_path"] == 1 + 2
    driver.free()


GAPS = ("token_logit_gap", "token_logit_gap_mean",
        "token_logit_gap_request_mean", "token_logit_gap_p99")
OFTEN = "token_logit_gap_over_half_pct"


def _served(fault=None):
    """The tiny cell served, with one of `calibrate_recurrent`'s faults
    planted in the program where one is named."""
    from benchmarks import calibrate_recurrent

    driver, mix = _driver()
    with calibrate_recurrent.planted(fault):
        driver.setup()
        driver.window(0.2, harness.Tracer(False), min_finished=60)
    driver.free()
    return driver, {k: mix["limits"][k] for k in GAPS}


def _gaps(driver, **kw):
    largest, n = driver.token_logit_gaps(**kw)
    return {"token_logit_gap": largest, **driver.gap_numbers}, n


def test_serving_control_fails():
    """The served tokens pass the cell's comparison; the fp8 reference's
    first choices do not."""
    driver, limits = _served()
    sound, n = _gaps(driver)
    assert driver.gap_witness["request_means"] and \
        driver.gap_witness["gaps_over_one"] == 0
    control, _ = _gaps(driver, mm="fp8", served=False)
    assert n >= 100
    assert check.judge(sound, limits)[0]
    ok, checks = check.judge(control, limits)
    assert not ok and all(c["value"] > 10 * c["limit"]
                          for c in checks.values())


@pytest.mark.parametrize("fault, times", [
    ("carry_dropped", 10), ("row_not_zeroed", 10), ("state_bf16", 3)])
def test_a_fault_planted_in_the_program_fails_the_cells_comparison(
        fault, times):
    """What this mechanism can get wrong, planted as
    `calibrate_recurrent.py` plants it on the chip. Prompts of up to 36
    tokens cross two chunk edges of 16, and with the gates' bias spread
    what a chunk forgets, or what the last request left in a row, reaches
    the served tokens; a state rounded to bfloat16 moves few tokens far,
    so it is the largest gap that finds it. And the program is whole
    again afterwards."""
    from paddle_tpu.inference.engine import PagedKVCache
    from paddle_tpu.ops import retention

    whole = (retention.power_retention_chunk, retention._state_step_xla,
             PagedKVCache.allocate_state)
    driver, limits = _served(fault)
    assert whole == (retention.power_retention_chunk,
                     retention._state_step_xla,
                     PagedKVCache.allocate_state)
    faulty, n = _gaps(driver)
    assert n >= 100
    ok, checks = check.judge(faulty, limits)
    assert not ok
    assert checks["token_logit_gap"]["value"] > \
        times * checks["token_logit_gap"]["limit"]


def test_the_calibration_reads_a_run_through_the_cells_own_comparison(
        tmp_path, monkeypatch, capsys):
    """`calibrate_recurrent.py` as the chip runs it, at the tiny size: a
    sound run's numbers are the driver's own (`numbers()`), judged by the
    cell's limits; the control beside it fails them; the share of the
    retention's weight that lies further back than a chunk is a share."""
    from benchmarks import calibrate_recurrent

    monkeypatch.chdir(tmp_path)
    calibrate_recurrent.main([
        "--workload", CELL, "--bench-dir", str(DATA), "--require-tpu", "0",
        "--runs", "control", "--seconds", "0.5", "--mass", "1",
        "--first-seed", "2147489778"])
    (line,) = capsys.readouterr().out.strip().splitlines()
    row = json.loads(line)
    assert (tmp_path / "chiprun_out" / f"calibrate_{CELL}.jsonl") \
        .read_text().strip() == line
    assert row["run"] == "control" and row["seed"] == 2147489778
    assert set(GAPS) | {OFTEN} <= set(row["numbers"])
    assert row["verdict"] == {"correct": True, "fails": []}
    assert row["control_verdict"]["correct"] is False
    assert set(row["control_verdict"]["fails"]) == set(GAPS) | {OFTEN}
    mass = row["mass_older_than_chunk"]
    assert mass["span"] == 16 and mass["heads"] == 4
    assert 0 <= mass["mean_pct"] <= 100
    assert "per_layer" not in row       # no device line on the CPU


def test_one_bad_request_cannot_hide_in_the_mean():
    """Four requests of 250 tokens, one of which goes wrong late (its
    last 40 tokens half a logit off): the mean over all the tokens stays
    under a limit three times the sound mean, the request's own mean does
    not."""
    import numpy as np

    driver_module = registry.load_module("drivers",
                                         "serve_closed_recurrent")
    rng = np.random.default_rng(0)
    sound = [np.abs(rng.normal(0, 0.02, 250)) for _ in range(4)]
    base = driver_module.gap_numbers(sound)
    bad = [g.copy() for g in sound]
    bad[2][-40:] += 0.5
    read = driver_module.gap_numbers(bad)
    assert read["token_logit_gap_mean"] < 3 * base["token_logit_gap_mean"]
    assert read["token_logit_gap_request_mean"] > \
        5 * base["token_logit_gap_request_mean"]
    assert read["token_logit_gap_p99"] > 5 * base["token_logit_gap_p99"]
    # 40 of 1,000 tokens over half a logit where none was
    assert (base[OFTEN], read[OFTEN]) == (0.0, 4.0)
    assert np.isnan(driver_module.gap_numbers([])["token_logit_gap_p99"])


# -- the architecture's counts ---------------------------------------------------

def published_cfg():
    return registry.load_json(
        REPO / "benchmarks" / "configs" / "brumby-14b-base-l8.json")


def test_counts_reproduce_the_published_parameters():
    cfg = published_cfg()
    # by hand: q and o 2 x 5120 x 5120, k and v 2 x 5120 x 1024, the gate
    # 5120 x 8 + 8, MLP 3 x 5120 x 17408, gains 2 x 5120 + 2 x 128
    assert counts.layer_params(cfg) == \
        52_428_800 + 10_485_760 + 40_968 + 267_386_880 + 10_496 \
        == 330_352_904
    whole = counts.total_params(
        cfg, cfg["published"]["num_hidden_layers"], cfg["vocab_size"])
    assert whole == 14_769_945_920 and round(whole / 1e9, 2) == 14.77
    held = counts.total_params(cfg, cfg["num_hidden_layers"],
                               cfg["vocab_size"])
    assert held == 4_198_652_992 and round(held * 2 / 1e9, 2) == 8.40
    assert counts.experts_held_all_layers(cfg) == 0
    assert counts.state_width(cfg) == cfg["state_width"] == 8_256


def test_kernel_work_and_request_flops_by_hand():
    cfg = published_cfg()
    # a lane a layer: 8 KV heads x 8,256 x 128 float32, in and out; each
    # element one multiply-add for the update and one a query head (5)
    flops, nbytes = counts.retention_decode_work(cfg, 10)
    assert nbytes == 10 * 8 * 2 * 33_816_576
    assert flops == 10 * 8 * 2 * 6 * 8 * 8_256 * 128
    assert round(2 * 33_816_576 / 1e6, 1) == 67.6
    tiny = registry.load_json(DATA / "configs" / "tiny-brumby.json")
    # hidden 64: q and o 2 x 64 x 64, k and v 2 x 64 x 32, gate 64 x 2,
    # MLP 3 x 64 x 96; D = 16 x 17 / 2 = 136; 2 query heads a KV head
    assert counts.layer_matmul_params(tiny) == 8_192 + 4_096 + 128 + 18_432
    assert counts.retention_flops_per_token(tiny) == 2 * 3 * 2 * 136 * 16
    # a prompt of 3 and 2 new tokens: 4 tokens fed, the head twice
    assert counts.serve_request_flops(tiny, 3, 2) == \
        4 * 2 * (2 * 30_848 + 26_112) + 2 * 120 * 64 * 2


# -- the readers ----------------------------------------------------------------

def _facts(ops, slice_counters, modules=()):
    return {"cfg": published_cfg(), "chips": 1,
            "peaks": {"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9},
            "trace": {"ops": ops, "window_s": 1.0, "busy_s": 0.5,
                      "modules": list(modules)},
            "counters": {"slice": slice_counters, "window": {}}}


def _read(metric, facts):
    return harness.read_layer_metric(metric, facts, None)


def test_readers_on_a_synthetic_trace():
    kernel = ('%engine_decode_step.7 = (f32[20,8,5,128]{3,2,1,0}, '
              'f32[8,21,8,8704,128]{4,3,2,1,0}) custom-call(...), '
              'custom_call_target="tpu_custom_call",\n'
              'frontend_attributes={kernel_metadata={"kernel_name":'
              '"power_retention_decode"}}')
    loop = ('%while.4 = (s32[]{:T(128)}, f32[8,8704,128]{2,1,0}, '
            'f32[8,8704]{1,0}) while(%tuple.1), condition=%cond, '
            'body=%body')
    inside = '%fusion.9 = f32[64,8,5,8704]{3,2,1,0} fusion(...)'
    other = kernel.replace("power_retention_decode", "ssm_decode_update")
    # events are (name, start ns, duration ns); the loop's body lies
    # inside the loop's own event and is not counted twice
    ops = [(kernel, 0, 20_000_000), (loop, 30_000_000, 10_000_000),
           (inside, 31_000_000, 2_000_000), (other, 50_000_000, 5_000_000),
           (loop, 60_000_000, 7_000_000)]
    # the programs that ran: the second loop lies in a decode step, and
    # is not the chunk's
    modules = [("jit_engine_decode_step(123)", 0, 25_000_000),
               ("jit_engine_prefill_chunk(456)", 28_000_000, 15_000_000),
               ("jit_engine_decode_step(123)", 45_000_000, 30_000_000)]
    facts = _facts(ops, {"decode_live_lanes": 20}, modules)
    # 20 lanes x 8 layers x 67.6 MB = 10.8 GB at 819 GB/s = 13.2 ms of 20
    assert _read("retention_decode_roofline", facts) == pytest.approx(
        100 * (20 * 8 * 2 * 33_816_576 / 819e9) / 0.020)
    # the loop is 10 ms of the 500 ms the device was busy
    assert _read("retention_chunk_busy_pct", facts) == pytest.approx(2.0)
    # nothing counted, no trace, no matching event: None, never 0
    assert _read("retention_decode_roofline", _facts(ops, {})) is None
    assert _read("retention_decode_roofline",
                 dict(facts, trace=None)) is None
    assert _read("retention_decode_roofline", _facts(
        [ops[3]], {"decode_live_lanes": 20})) is None
    assert _read("retention_chunk_busy_pct",
                 _facts([ops[0]], {}, modules)) is None
    # no program of the chunk's name in the trace: silent, not unscoped
    assert _read("retention_chunk_busy_pct",
                 _facts(ops, {}, [modules[0], modules[2]])) is None
    assert _read("retention_chunk_busy_pct", _facts(ops, {})) is None
    assert _read("retention_chunk_busy_pct",
                 dict(facts, trace=None)) is None
    # no pool: a zero denominator, and the metric is left out
    assert _read("kv_pool_fill_pct", {"pool_blocks_used_mean": 0.0,
                                      "pool_blocks": 0}) is None


def test_benchmark_json_names_the_cell_and_its_files():
    bench = registry.load_benchmark()
    cell = registry.cell(bench, "brumby14b_serve_docgen")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "brumby-14b-base-l8", "docgen_closed20", 1)
    (entry,) = [c for c in bench["configs"]
                if c["name"] == cell["config"]]
    cfg = registry.load_json(REPO / entry["file"])
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        (row,) = [r for r in map(json.loads, catalog.read_text().splitlines())
                  if r["source_url"] == entry["source"]]
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
    mix = registry.find("traffic", cell["traffic"])
    assert mix["driver"] == "serve_closed_recurrent"
    assert (mix["clients"], mix["engine"]["num_slots"],
            mix["requests_drawn"]) == (20, 20, 20)
    assert mix["expect_retention_path"] == "pallas"
    assert "num_blocks" not in mix["engine"]
    names = {m["name"] for m in registry.metrics_for(
        bench, "per_layer", cell["name"])}
    # held with >=, so that a later PR can add a metric to the cell
    assert names >= {"retention_decode_roofline",
                     "retention_chunk_busy_pct", "state_pool_fill_pct",
                     "serve_mfu", "engine_step_ms", "engine_decode_lanes",
                     "device_idle_pct.serve", "ttft_p95_ms", "ttft_p50_ms"}
    # no pool to fill, no paged kernel to read
    assert not names & {"kv_pool_fill_pct", "paged_attn_roofline"}
    assert {m["name"] for m in registry.metrics_for(
        bench, "end_to_end", cell["name"])} == {
            "serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
