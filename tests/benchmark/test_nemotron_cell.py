"""The `serve_closed_stateful` driver and what the `nemotron_h`
configuration brings to the benchmark, rehearsed on the CPU at a tiny size
from `data/nemotron/` (a BENCHMARK.json, a configuration and a traffic
file of this test's own): the driver end to end in-process, the
lower-precision control shown to fail, the architecture's count functions
against hand-worked and published numbers, the new readers on synthetic
facts. No device metric's value is named here.
"""
import io
import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.lib import (check, counts_nemotron_h as counts, harness,
                            kernel_shares, registry)

HERE = Path(__file__).resolve().parent
DATA = HERE / "data" / "nemotron"
REPO = HERE.parent.parent
CELL = "tiny_nemotron_serve"
PUBLISHED = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
             "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@pytest.fixture(scope="module")
def runs():
    cache = {}

    def get(trace):
        if trace not in cache:
            out = io.StringIO()
            cache[trace] = harness.run_cell(
                CELL, 5, 0.4, trace, require_tpu=False, repo_dir=DATA,
                bench_dir=DATA, out=out)
            assert json.loads(out.getvalue().strip().splitlines()[-1]) \
                == json.loads(json.dumps(cache[trace]))
        return cache[trace]

    return get


def test_driver_end_to_end_is_correct_and_reports_its_metrics(runs):
    r = runs(0)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                 "setup_s"}
    assert set(r["checks"]) == {"token_logit_gap", "wrong_answers",
                                "compiles_in_window",
                                "unexpected_kernel_path",
                                "prefix_hit_tokens"}
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]


def test_traced_run_reports_the_counters_and_no_share_of_a_peak(runs):
    """On the CPU no device line and no peak: the two rooflines and
    `serve_mfu` are left out, never 0; what the engine counts is there."""
    r = runs(1)
    assert r["correct"] is True
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert {"engine_step_ms", "engine_decode_lanes", "ttft_p50_ms",
            "moe_tokens_per_expert", "state_pool_fill_pct"} <= set(m)
    assert not {"serve_mfu", "ssm_decode_roofline",
                "moe_experts_roofline"} & set(m)
    # 3 clients on 4 slots; an expert held takes a lane's token at most
    # once: about 3 x 5 / 16 an expert, a layer and a step, never over 3
    assert 0 < m["state_pool_fill_pct"] <= 75.0
    assert 0 < m["moe_tokens_per_expert"] <= 3


def _driver():
    _, cell, mix, config = harness.load_cell(CELL, DATA, DATA)
    return harness.make_driver(cell, mix, config, 9, DATA), mix


def test_facts_carry_the_engines_counters_and_the_architectures_flops():
    driver, mix = _driver()
    driver.setup()
    driver.window(0.2, harness.Tracer(False), min_finished=20)
    facts = driver.facts()
    window = facts["counters"]["window"]
    assert window["decode_steps"] > 0
    assert 0 < window["decode_live_lanes"] <= 3 * window["decode_steps"]
    assert 0 < window["moe_experts_touched"] <= \
        window["moe_assignments_held"]
    assert facts["counters"]["kernel_paths"] == {
        "ssm": {"xla": 2, "pallas": 0}, "moe": {"xla": 4, "pallas": 0}}
    assert facts["state_rows"] == 4 and facts["state_pool_bytes"] > 0
    done = [r for r in driver.issued.values()
            if r.get("t_done") is not None
            and driver.t0 <= r["t_done"] <= driver.t1]
    assert facts["flops_required"] == sum(
        counts.serve_request_flops(driver.cfg, len(r["prompt"]), r["new"])
        for r in done) > 0
    assert driver.guards()["unexpected_kernel_path"] == 0
    driver.mix = dict(mix, expect_moe_path="pallas")     # not what ran
    assert driver.guards()["unexpected_kernel_path"] == 1 + 4
    driver.free()


def _sound_and_control(monkeypatch=None):
    driver, mix = _driver()
    if monkeypatch is not None:      # the fault: rows handed out as left
        from paddle_tpu.inference.engine import PagedKVCache

        monkeypatch.setattr(PagedKVCache, "allocate_state",
                            lambda self: self._free_rows.pop()
                            if self._free_rows else None)
    driver.setup()
    driver.window(0.2, harness.Tracer(False), min_finished=60)
    driver.free()
    limits = {"token_logit_gap": mix["limits"]["token_logit_gap"]}
    return driver, limits


def test_serving_control_fails():
    """The served tokens pass the cell's comparison; the fp8 reference's
    first choices do not."""
    driver, limits = _sound_and_control()
    sound, n = driver.token_logit_gaps()
    control, _ = driver.token_logit_gaps(mm="fp8", served=False)
    assert n >= 100
    assert check.judge({"token_logit_gap": sound}, limits)[0]
    assert not check.judge({"token_logit_gap": control}, limits)[0]
    assert control > 3 * max(sound, limits["token_logit_gap"])


def test_a_state_row_left_unzeroed_fails_the_cells_comparison(monkeypatch):
    """With `a_log_init_std` the heads remember: what the slot's last
    tenant left in the row reaches the next request's served tokens."""
    driver, limits = _sound_and_control(monkeypatch)
    faulty, n = driver.token_logit_gaps()
    assert n >= 100
    assert not check.judge({"token_logit_gap": faulty}, limits)[0]
    assert faulty > 100 * limits["token_logit_gap"]


# -- the architecture's counts ---------------------------------------------------

def published_cfg():
    cfg = registry.load_json(
        REPO / "benchmarks" / "configs"
        / "nemotron-3-super-120b-a12b-ep4.json")
    return cfg


def test_counts_reproduce_the_published_parameters():
    cfg = published_cfg()
    assert (PUBLISHED.count("M"), PUBLISHED.count("*"),
            PUBLISHED.count("E")) == (40, 8, 40) and len(PUBLISHED) == 88
    assert cfg["published"]["hybrid_override_pattern"] == PUBLISHED
    # by hand: M = 4096 x (8192 + 10240 + 128) + 8192 x 4096 matmul
    # + conv 4 x 10240 + 10240 + 3 x 128 + norm 8192 + gain 4096
    assert counts.layer_params(cfg, "M", 0) == \
        76_021_760 + 33_554_432 + 40_960 + 10_240 + 384 + 8_192 + 4_096 \
        == 109_640_064
    # * = 2 x 4096 x 4096 + 2 x 4096 x 256 + gain
    assert counts.layer_params(cfg, "*", 0) == \
        33_554_432 + 2_097_152 + 4_096 == 35_655_680
    # E outside its experts: router 4096 x 512 + 512, down and up
    # 2 x 4096 x 1024, shared 2 x 4096 x 5376, gain
    assert counts.layer_params(cfg, "E", 0) == \
        2_097_152 + 512 + 8_388_608 + 44_040_192 + 4_096 == 54_530_560
    assert counts.expert_params(cfg) == 2 * 1024 * 2688 == 5_505_024
    whole = counts.total_params(cfg, PUBLISHED, 512, 131_072)
    assert round(whole / 1e9, 2) == 120.67
    active = sum(counts.layer_params(cfg, k, 22) for k in PUBLISHED) \
        + 2 * 131_072 * 4096 + 4096
    assert round(active / 1e9, 2) == 12.77
    held = counts.total_params(cfg, cfg["hybrid_override_pattern"],
                               cfg["n_routed_experts"], cfg["vocab_size"])
    assert held == 4_648_163_712 and round(held * 2 / 1e9, 2) == 9.30


def test_serve_request_flops_by_hand_at_a_tiny_size():
    cfg = registry.load_json(DATA / "configs" / "tiny-nemotron.json")
    # pattern ME*EM, hidden 64; M: inner 64, conv 64 + 2 x 2 x 16 = 128,
    # 8 heads -> 64 x (64 + 128 + 8) + 64 x 64 = 16,896
    assert counts.layer_matmul_params(cfg, "M") == 16_896
    # *: q 64 x 64, k and v 64 x 32, out 64 x 64
    assert counts.layer_matmul_params(cfg, "*") == 12_288
    # E: router 64 x 16, down + up 2 x 64 x 32, shared 2 x 64 x 64,
    # 5 x 4 / 16 = 1.25 experts of 2 x 32 x 48 here
    assert counts.expected_experts_here(cfg) == 1.25
    assert counts.layer_matmul_params(cfg, "E", 1.25) == \
        1_024 + 4_096 + 8_192 + 1.25 * 3_072 == 17_152
    assert counts.scan_flops_per_token(cfg) == 5 * 8 * 8 * 16 == 5_120
    # a prompt of 3 and 2 new tokens: 4 tokens fed; the one * layer sees
    # 1 + 2 + 3 + 4 = 10 keys at 4 x 4 heads x 16; the head twice
    body = 2 * (2 * 16_896 + 12_288 + 2 * 17_152) + 2 * 5_120
    assert counts.serve_request_flops(cfg, 3, 2) == \
        body * 4 + 4 * 64 * 10 + 2 * 120 * 64 * 2


def test_kernel_work_by_hand():
    cfg = published_cfg()
    # a lane a step: 5 M layers x 128 x 64 x 128 float32, in and out
    flops, nbytes = counts.ssm_decode_work(cfg, 10)
    assert nbytes == 10 * 5 * 2 * 4_194_304 and flops == 10 * 5 * 5_242_880
    flops, nbytes = counts.moe_experts_work(cfg, experts_touched=640,
                                            assignments=3520)
    assert flops == 2 * 3520 * 5_505_024
    assert nbytes == 2 * (640 * 5_505_024 + 3520 * 2 * (1024 + 2688))


# -- the readers ----------------------------------------------------------------

def _facts(ops, slice_counters):
    return {"cfg": published_cfg(), "chips": 1,
            "peaks": {"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9},
            "trace": {"ops": ops, "window_s": 1.0, "busy_s": 0.5},
            "counters": {"slice": slice_counters, "window": {}}}


def _read(metric, facts):
    return harness.read_layer_metric(metric, facts, None)


def test_roofline_readers_on_a_synthetic_trace():
    ssm_call = ('%engine_decode_step.7 = f32[128,128,64]{2,1,0} '
                'custom-call(...), custom_call_target="tpu_custom_call",\n'
                'frontend_attributes={kernel_metadata={"kernel_name":'
                '"ssm_decode_update"}}')
    moe_call = ssm_call.replace("ssm_decode_update", "moe_grouped_matmul")
    other = ssm_call.replace("engine_decode_step", "engine_prefill_chunk")
    # events are (name, start ns, duration ns)
    ops = [(ssm_call, 0, 10_000_000), (moe_call, 20_000_000, 20_000_000),
           (other, 50_000_000, 5_000_000)]
    facts = _facts(ops, {"decode_live_lanes": 128,
                         "moe_experts_touched": 640,
                         "moe_assignments_held": 3520})
    # 128 lanes x 5 layers x 8 MiB = 5.37 GB at 819 GB/s = 6.55 ms of 10
    assert _read("ssm_decode_roofline", facts) == pytest.approx(
        100 * (128 * 5 * 2 * 4_194_304 / 819e9) / 0.010)
    # 640 experts x 11 MB + rows = 7.10 GB = 8.67 ms of 20
    want = 2 * (640 * 5_505_024 + 3520 * 2 * 3712) / 819e9 / 0.020
    assert _read("moe_experts_roofline", facts) == pytest.approx(100 * want)
    # nothing counted, no trace, no matching event: None, never 0
    assert _read("ssm_decode_roofline", _facts(ops, {})) is None
    assert _read("moe_experts_roofline",
                 dict(facts, trace=None)) is None
    assert _read("ssm_decode_roofline", _facts([ops[2]], {
        "decode_live_lanes": 128})) is None
    assert kernel_shares.share({"patterns": ["x"]}, facts, None) is None
    # the chunk's product is 5 ms of the 500 ms the device was busy
    chunk_moe = moe_call.replace("engine_decode_step",
                                 "engine_prefill_chunk")
    facts = _facts(ops + [(chunk_moe, 60_000_000, 5_000_000)], {})
    assert _read("moe_prefill_experts_busy_pct", facts) == \
        pytest.approx(1.0)
    assert _read("moe_prefill_experts_busy_pct", _facts(ops, {})) is None
    assert _read("moe_prefill_experts_busy_pct",
                 dict(facts, trace=None)) is None


def test_counter_readers():
    cfg = published_cfg()
    facts = {"cfg": cfg, "state_rows": 128, "state_rows_used_mean": 96.0,
             "counters": {"window": {
                 "moe_assignments_held": 704 * 5 * 10,
                 "moe_expert_steps":
                     10 * counts.experts_held_all_layers(cfg)}}}
    assert _read("state_pool_fill_pct", facts) == 75.0
    assert _read("moe_tokens_per_expert", facts) == 5.5
    assert _read("moe_tokens_per_expert",
                 dict(facts, counters={"window": {}})) is None


def test_benchmark_json_names_the_cell_and_its_files():
    bench = registry.load_benchmark()
    cell = registry.cell(bench, "nemotron3s_serve_chat")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-super-120b-a12b-ep4", "chat_closed128", 1)
    (entry,) = [c for c in bench["configs"]
                if c["name"] == cell["config"]]
    cfg = registry.load_json(REPO / entry["file"])
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        (row,) = [r for r in map(json.loads, catalog.read_text().splitlines())
                  if r["source_url"] == entry["source"]]
        for key, value in row["config"].items():
            if isinstance(value, (int, float)) and not isinstance(value, bool) \
                    and key not in cfg["reduced"]:
                assert cfg[key] == value, key
    mix = registry.find("traffic", cell["traffic"])
    assert mix["driver"] == "serve_closed_stateful"
    assert (mix["clients"], mix["engine"]["num_slots"],
            mix["requests_drawn"]) == (128, 128, 128)
    names = {m["name"] for m in registry.metrics_for(
        bench, "per_layer", cell["name"])}
    assert {"ssm_decode_roofline", "moe_experts_roofline",
            "moe_prefill_experts_busy_pct", "moe_tokens_per_expert",
            "state_pool_fill_pct", "kv_pool_fill_pct",
            "serve_mfu"} <= names
    # its count is 2 x layers x hidden a token: wrong for grouped KV heads
    assert "paged_attn_roofline" not in names
