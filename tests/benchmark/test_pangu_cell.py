"""The `serve_closed_sessions` driver and what the `pangu_ultra_moe`
configuration brings to the benchmark, rehearsed on the CPU at a tiny size
from `data/pangu/` (a BENCHMARK.json, a configuration and a traffic file
of this test's own): the driver end to end in-process with its documents
served from the prefix cache, the lower-precision control and the three
planted faults shown to fail, the architecture's count functions against
the published numbers, the two new readers on made-up facts. No device
metric's value is named here.
"""
import io
import json
from pathlib import Path

import pytest

from benchmarks.lib import (check, counts_pangu_ultra_moe as counts,
                            harness, registry)

HERE = Path(__file__).resolve().parent
DATA = HERE / "data" / "pangu"
REPO = HERE.parent.parent
CELL = "tiny_pangu_docqa"


@pytest.fixture(scope="module")
def runs():
    cache = {}

    def get(trace):
        if trace not in cache:
            out = io.StringIO()
            cache[trace] = harness.run_cell(
                CELL, 2147489101, 0.5, trace, require_tpu=False,
                repo_dir=DATA, bench_dir=DATA, out=out)
            assert json.loads(out.getvalue().strip().splitlines()[-1]) \
                == json.loads(json.dumps(cache[trace]))
        return cache[trace]

    return get


def test_driver_end_to_end_is_correct_and_reports_its_metrics(runs):
    r = runs(0)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                 "setup_s"}
    assert set(r["checks"]) == {
        "token_logit_gap", "token_logit_gap_mean", "wrong_answers",
        "compiles_in_window", "unexpected_kernel_path",
        "document_blocks_missed"}
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]


def test_traced_run_reports_the_hits_and_no_share_of_a_peak(runs):
    """On the CPU no device line and no peak: the rooflines and
    `serve_mfu` are left out, never 0; what the engine counts is there."""
    r = runs(1)
    assert r["correct"] is True
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert {"engine_step_ms", "engine_decode_lanes", "kv_pool_fill_pct",
            "ttft_p50_ms", "moe_tokens_per_expert",
            "prefix_hit_share_pct"} <= set(m)
    assert not {"serve_mfu", "mla_decode_roofline",
                "moe_experts_roofline"} & set(m)
    # documents of 24-60 tokens in blocks of 8 under questions of 4-12:
    # most of a prompt is a hit, never all of it
    assert 50 < m["prefix_hit_share_pct"] < 100
    assert 0 < m["moe_tokens_per_expert"] <= 3


def _driver(seed=9):
    _, cell, mix, config = harness.load_cell(CELL, DATA, DATA)
    return harness.make_driver(cell, mix, config, seed, DATA), mix


@pytest.fixture(scope="module")
def served():
    """One set-up and window, its engine freed: what the comparisons
    below read."""
    driver, mix = _driver()
    driver.setup()
    driver.window(0.2, harness.Tracer(False), min_finished=30)
    facts, guards = driver.facts(), driver.guards()
    driver.free()
    return driver, mix, facts, guards


def test_every_client_holds_one_document_and_later_turns_hit_it(served):
    driver, mix, facts, guards = served
    bs = mix["engine"]["block_size"]
    assert sorted(len(d) for d in driver.documents) == [28, 38, 52]
    by_client = {}
    for r in driver.issued.values():
        by_client.setdefault(r["client"], []).append(r)
        doc = driver.documents[r["client"]]
        assert r["prompt"][:len(doc)].tolist() == doc.tolist()
        assert len(doc) + 4 <= len(r["prompt"]) <= len(doc) + 12
    assert set(by_client) == {0, 1, 2}
    for client, requests in by_client.items():
        full = len(driver.documents[client]) // bs * bs
        assert requests[0]["hit_tokens"] == 0          # prefilled cold
        assert all(r["hit_tokens"] >= full for r in requests[1:]
                   if "hit_tokens" in r)
        # the questions differ: a hit never reaches past the document
        assert all(r["hit_tokens"] <= full + bs for r in requests[1:]
                   if "hit_tokens" in r)
    assert guards["document_blocks_missed"] == 0
    assert facts["document_blocks_missed"] == 0
    assert 0 < facts["prompt_tokens_hit"] < facts["prompt_tokens"]


def test_facts_carry_the_counters_and_only_the_work_the_cache_left(served):
    driver, mix, facts, guards = served
    window = facts["counters"]["window"]
    assert window["decode_steps"] > 0
    assert 0 < window["decode_live_lanes"] <= 3 * window["decode_steps"]
    assert window["mla_context_rows"] > 24 * window["decode_live_lanes"]
    assert 0 < window["moe_experts_touched"] <= \
        window["moe_assignments_held"]
    assert facts["counters"]["latent_paths"]["decode"] == {
        "dense": 3, "pallas": 0}
    assert facts["counters"]["kernel_paths"]["moe"] == {"xla": 4,
                                                        "pallas": 0}
    done = driver._window_done()
    assert facts["flops_required"] == sum(
        counts.serve_request_flops(driver.cfg, len(r["prompt"]), r["new"],
                                   r["hit_tokens"]) for r in done) > 0
    assert facts["flops_required"] < sum(
        counts.serve_request_flops(driver.cfg, len(r["prompt"]), r["new"])
        for r in done)
    assert guards["unexpected_kernel_path"] == 0
    driver.mix = dict(mix, expect_latent_path="pallas")   # not what ran
    assert driver.guards()["unexpected_kernel_path"] == 1 + 3
    driver.mix = mix


def test_an_evicted_document_is_counted_block_by_block(served):
    driver, mix, _, _ = served
    r = next(r for r in driver.issued.values()
             if r["phase"] == "window" and r.get("hit_tokens"))
    kept = r["hit_tokens"]
    r["hit_tokens"] = 8                     # all but one block lost
    assert driver.guards()["document_blocks_missed"] == \
        len(driver.documents[r["client"]]) // 8 - 1
    r["hit_tokens"] = kept


def _limits(mix):
    return {k: mix["limits"][k]
            for k in ("token_logit_gap", "token_logit_gap_mean")}


def _judge(driver, mix, **kw):
    gap, n = driver.token_logit_gaps(**kw)
    numbers = {"token_logit_gap": gap,
               "token_logit_gap_mean": driver.gap_mean}
    return check.judge(numbers, _limits(mix))[0], numbers, n


def test_the_served_tokens_pass_and_the_control_fails(served):
    driver, mix, _, _ = served
    ok, sound, n = _judge(driver, mix)
    assert ok and n >= 20
    ok, control, _ = _judge(driver, mix, mm="fp8", served=False)
    assert not ok
    assert control["token_logit_gap"] > 3 * max(
        sound["token_logit_gap"], mix["limits"]["token_logit_gap"])


@pytest.mark.parametrize("fault", ["skipped_page", "no_key_rotation",
                                   "late_blocks"])
def test_a_planted_fault_fails_the_cells_comparison(served, fault):
    """A cached page left out of a walk, the rotation left off the cached
    keys, a document's blocks mapped one block late: each moves the
    reference's own first choices past a limit."""
    driver, mix, _, _ = served
    ok, numbers, n = _judge(driver, mix, served=False, fault=fault)
    assert n >= 20 and not ok, numbers


# -- the architecture's counts ---------------------------------------------------

@pytest.fixture(scope="module")
def real():
    bench = registry.load_benchmark(REPO)
    return registry.config_file(bench, "openpangu-ultra-moe-718b-ep32",
                                REPO)


@pytest.mark.parametrize("what,want", [
    ("attention_params", 196_575_232),
    ("expert_params", 47_185_920),
    ("dense_mlp_params", 424_673_280),
    ("router_params", 1_966_080),
    ("norm_params", 32_768),
    ("held_params", 3_409_190_400),
    ("cache_bytes_per_token", 5_760),
    ("plain_heads_cache_bytes_per_token", 409_600),
    ("experts_held_all_layers", 32),
])
def test_counts_reproduce_the_configurations_numbers(real, what, want):
    assert getattr(counts, what)(real) == want


def test_counts_reproduce_the_published_totals(real):
    whole, active = counts.published_params(real)
    assert round(whole / 1e9) == 719 and 39 <= active / 1e9 <= 41
    assert counts.layer_params(real, False, 0) == 621_281_280
    assert counts.layer_params(real, True, 8) == 623_247_360
    assert f"{counts.held_params(real):,}" in real["deployment"]["held"]
    assert counts.expected_experts_here(real) == 0.25


def test_the_configuration_keeps_every_published_width(real):
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if '"openPangu-Ultra-MoE-718B"' in line)
    assert real["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if real.get(k) != v}
    assert differs == set(real["reduced"]) == set(real["reduced_why"])
    for key in real["reduced"]:
        assert real["published"][key] == row["config"][key]


def test_a_cached_prefix_takes_its_work_out_of_the_count(real):
    cold = counts.serve_request_flops(real, 6200, 140)
    warm = counts.serve_request_flops(real, 6200, 140, 6144)
    body = 2 * counts.token_matmul_params(real)
    # 6,144 tokens less through the body, and their pairs among
    # themselves; the pairs of the tokens that are computed stay
    pairs = 6144 * 6145 // 2
    assert cold - warm == 6144 * body + pairs * 5 \
        * counts.attention_pair_flops(real)
    assert warm < cold / 20


# -- the two new readers ------------------------------------------------------------

def _facts(real, **kw):
    return dict({"cfg": real, "chips": 1,
                 "peaks": {"bf16_flops_per_s": 197e12,
                           "hbm_bytes_per_s": 819e9}}, **kw)


def test_mla_decode_roofline_reads_the_counter_and_the_kernels_time(real):
    op = ('%engine_decode_step.3 = bf16[96,128,512] custom-call(...), '
          'custom_call_target="tpu_custom_call", '
          'kernel_metadata={"kernel_name": "mla_paged_decode"}')
    rows = 500_000
    facts = _facts(real, counters={"slice": {"mla_context_rows": rows}},
                   trace={"ops": [[op, 0, 5_000_000]], "busy_s": 0.02,
                          "window_s": 0.02})
    value = harness.read_layer_metric("mla_decode_roofline", facts, None)
    flops, nbytes = counts.mla_decode_work(real, rows)
    assert flops == rows * 5 * 2 * 128 * (576 + 512)
    assert nbytes == rows * 5 * 1152
    assert value == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 5e-3)
    assert 0 < value < 100
    # nothing counted, no such kernel, no trace: left out, never 0
    for lacking in (dict(facts, counters={"slice": {}}),
                    dict(facts, trace={"ops": [["%fusion.1 = ...", 0, 9]],
                                       "busy_s": 1, "window_s": 1}),
                    dict(facts, trace=None)):
        assert harness.read_layer_metric("mla_decode_roofline", lacking,
                                         None) is None


def test_prefix_hit_share_reads_the_drivers_two_counts(real):
    facts = _facts(real, prompt_tokens=1000, prompt_tokens_hit=970)
    assert harness.read_layer_metric("prefix_hit_share_pct", facts,
                                     None) == pytest.approx(97.0)
    assert harness.read_layer_metric("prefix_hit_share_pct",
                                     _facts(real), None) is None


def test_the_cell_is_on_the_lists_the_issue_names():
    bench = registry.load_benchmark(REPO)
    cell = "pangu_ultra_serve_docqa"
    entry = registry.cell(bench, cell)
    assert entry["chips"] == 1 and entry["traffic"] == "docqa_closed96"
    e2e = {m["name"] for m in registry.metrics_for(bench, "end_to_end",
                                                   cell)}
    assert e2e == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    layer = {m["name"] for m in registry.metrics_for(bench, "per_layer",
                                                     cell)}
    assert layer == {"engine_step_ms", "engine_decode_lanes",
                     "kv_pool_fill_pct", "serve_mfu",
                     "device_idle_pct.serve", "ttft_p95_ms", "ttft_p50_ms",
                     "moe_experts_roofline", "moe_tokens_per_expert",
                     "mla_decode_roofline", "prefix_hit_share_pct"}
    mix = registry.find("traffic", entry["traffic"])
    assert mix["clients"] == mix["engine"]["num_slots"] == 96
    assert mix["document_tokens"] == [4096, 8192]
    assert mix["prompt_tokens"] == [64, 256]
    assert mix["new_tokens"] == [96, 192]
    assert mix["requests_drawn"] == 96 and mix["warm_finished"] == 192
    assert mix["engine"]["block_size"] == 64
    assert mix["engine"]["max_model_len"] == 8704
    assert "expect_no_prefix_hits" not in mix
    assert mix["expect_latent_path"] == mix["expect_moe_path"] == "pallas"
