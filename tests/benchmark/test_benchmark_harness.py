"""The benchmark harness (benchmarks/), rehearsed on the CPU at a tiny
size from this directory's own data files (`data/`): both drivers end to
end in-process, the data-driven lookup, the trace reduction on a
synthetic event list, the count functions against hand-worked numbers,
the references against the repo's models, and the comparison that
decides `correct` shown to fail: under the lower-precision control and
with the timed path broken underneath.

Nothing here names a device metric's value: a CPU run gives counts and
correctness, never a speed. No TPU library is loaded at import.
"""
import io
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmarks.lib import (check, counts, harness, program, readers,
                            registry, trace_reduce, traffic)

HERE = Path(__file__).resolve().parent
DATA = HERE / "data"
REPO = HERE.parent.parent
TRAIN_CELLS = ["tiny_gpt_train", "tiny_bert_finetune"]


def run(workload, trace=0, seed=3, seconds=0.3):
    out = io.StringIO()
    result = harness.run_cell(workload, seed, seconds, trace,
                              require_tpu=False, repo_dir=DATA,
                              bench_dir=DATA, out=out)
    lines = out.getvalue().strip().splitlines()
    assert json.loads(lines[-1]) == json.loads(json.dumps(result))
    return result


@pytest.fixture(scope="module")
def runs():
    cache = {}

    def get(workload, trace=0):
        if (workload, trace) not in cache:
            cache[workload, trace] = run(workload, trace)
        return cache[workload, trace]

    return get


# -- both drivers, end to end ------------------------------------------------

@pytest.mark.parametrize("workload", TRAIN_CELLS + ["tiny_gpt_serve"])
def test_driver_end_to_end_reports_every_declared_metric(runs, workload):
    r = runs(workload)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"          # the compared numbers, last
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    bench = registry.load_benchmark(DATA)
    want = {m["name"] for m in registry.metrics_for(bench, "end_to_end",
                                                    workload)}
    assert set(r["metrics"]) == want and "setup_s" in want
    for m in r["metrics"].values():
        assert np.isfinite(m["value"]) and m["value"] > 0 and m["unit"]
    assert r["device"]["platform"] == "cpu"         # named, never hidden
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("workload,expect", [
    ("tiny_gpt_train", set()),
    ("tiny_gpt_serve", {"engine_step_ms", "engine_decode_lanes",
                        "kv_pool_fill_pct",
                        "ttft_p95_ms", "ttft_p50_ms"})])
def test_traced_run_reports_only_what_its_readers_found(runs, workload,
                                                        expect):
    """On the CPU no operation runs on a device line and no peak is
    known: shares of a peak or a roofline are left out, never 0."""
    r = runs(workload, trace=1)
    assert set(r["metrics"]) >= expect
    assert not {"train_mfu", "serve_mfu", "flash_attn_roofline",
                "paged_attn_roofline", "device_idle_pct.train",
                "device_idle_pct.serve"} & set(r["metrics"])
    assert r["correct"] is True


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "gpt1p3b_train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""                   # no result line
    assert "no TPU" in p.stderr


# -- driven by data ------------------------------------------------------------

def test_files_dropped_in_are_found_by_name(tmp_path):
    for kind in ("configs", "traffic", "layer_metrics"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / "new-model.json").write_text(
        json.dumps({"hidden_size": 8}))
    (tmp_path / "traffic" / "new_mix.json").write_text(
        json.dumps({"driver": "serve_closed", "clients": 2}))
    (tmp_path / "layer_metrics" / "new_metric.json").write_text(
        json.dumps({"reader": "ratio", "numerator": "tokens",
                    "denominator": "engine_steps", "scale": 2}))
    (tmp_path / "layer_metrics" / "own_reader.json").write_text(
        json.dumps({"offset": 1}))
    (tmp_path / "layer_metrics" / "own_reader.py").write_text(
        "def read(params, facts):\n"
        "    return facts['tokens'] + params['offset']\n")
    bench = {"configs": [{"name": "new-model",
                          "file": "configs/new-model.json"}],
             "workloads": [{"name": "new_cell", "config": "new-model",
                            "traffic": "new_mix", "chips": 1}],
             "per_layer": [{"name": "new_metric",
                            "workloads": ["new_cell"]},
                           {"name": "elsewhere", "workloads": ["other"]},
                           {"name": "everywhere"}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    bench = registry.load_benchmark(tmp_path)
    cell = registry.cell(bench, "new_cell")
    assert registry.config_file(bench, cell["config"], tmp_path) == \
        {"hidden_size": 8}
    assert registry.find("traffic", cell["traffic"], tmp_path)["clients"] \
        == 2
    assert [m["name"] for m in registry.metrics_for(
        bench, "per_layer", "new_cell")] == ["new_metric", "everywhere"]
    facts = {"tokens": 30, "engine_steps": 10}
    assert harness.read_layer_metric("new_metric", facts, tmp_path) == 6
    assert harness.read_layer_metric("own_reader", facts, tmp_path) == 31
    # the benchmark's own files stay reachable beside the new ones
    assert registry.find("layer_metrics", "train_mfu", tmp_path)
    with pytest.raises(registry.BenchmarkDataError):
        registry.find("traffic", "no_such_mix", tmp_path)
    with pytest.raises(registry.BenchmarkDataError):
        registry.cell(bench, "no_such_cell")


def test_a_driver_and_a_constructor_dropped_in_are_found_by_name(tmp_path):
    """A later PR's new driver or architecture is a file of its own:
    the traffic file's `driver` and the configuration's `constructor`
    name it, and no table in the harness is edited."""
    for kind in ("drivers", "constructors"):
        (tmp_path / kind).mkdir()
    (tmp_path / "drivers" / "serve_open.py").write_text(
        "class Cell:\n"
        "    def __init__(self, ctx):\n"
        "        self.rate = ctx.traffic['rate']\n")
    (tmp_path / "constructors" / "new_arch.py").write_text(
        "class Model:\n"
        "    def __init__(self, cfg):\n"
        "        self.cfg, self.mode, self.dtype = cfg, None, None\n"
        "    def eval(self):\n"
        "        self.mode = 'eval'\n"
        "    def to(self, dtype):\n"
        "        self.dtype = dtype\n"
        "def build(cfg):\n"
        "    return Model(cfg)\n")
    cfg = {"constructor": "new_arch", "reference": "gpt2",
           "dtype": "bfloat16"}
    driver = harness.make_driver({"chips": 1}, {"driver": "serve_open",
                                               "rate": 7}, cfg, 1, tmp_path)
    assert driver.rate == 7
    model = program.build_model(cfg, tmp_path)
    assert (model.cfg, model.mode, model.dtype) == (cfg, "eval",
                                                    "bfloat16")
    # the benchmark's own stay reachable beside the new ones
    assert registry.load_module("drivers", "train", tmp_path).Cell
    assert registry.load_module("constructors", "gpt_causal_lm",
                                tmp_path).build
    with pytest.raises(registry.BenchmarkDataError):
        registry.load_module("drivers", "no_such_driver", tmp_path)
    with pytest.raises(registry.BenchmarkDataError):
        program.build_model({"constructor": "no_such_arch"}, tmp_path)


def test_peaks_raise_on_an_unknown_device_kind():
    assert registry.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(registry.BenchmarkDataError):
            registry.peaks(kind)


def test_benchmark_json_names_files_that_exist():
    bench = registry.load_benchmark()
    for w in bench["workloads"]:
        registry.config_file(bench, w["config"])
        mix = registry.find("traffic", w["traffic"])
        assert registry.find_module("drivers", mix["driver"])
        assert "limits" in mix
        for section in ("end_to_end", "per_layer"):
            assert registry.metrics_for(bench, section, w["name"])
    for c in bench["configs"]:
        cfg = registry.config_file(bench, c["name"])
        assert registry.find_module("constructors", cfg["constructor"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for m in bench["per_layer"]:
        params = registry.find("layer_metrics", m["name"])
        assert params["reader"] in readers.READERS
        assert m["workloads"], m["name"]    # every metric lists its cells


def test_every_seed_draws_the_same_sizes_in_another_order():
    mix = registry.find("traffic", "chat_closed96")
    n = mix["requests_drawn"]
    lo_p, hi_p = mix["prompt_tokens"]
    lo_n, hi_n = mix["new_tokens"]
    per_seed = []
    for seed in (1, 2 ** 31 + 5):           # the driver's seeds are large
        stream = traffic.requests(mix, 50257, seed)
        reqs = [next(stream) for _ in range(n)]
        assert all(lo_p <= len(p) <= hi_p and lo_n <= m <= hi_n
                   and p.dtype == np.int32 and p.max() < 50257
                   for p, m in reqs)
        per_seed.append([(len(p), m) for p, m in reqs])
    assert per_seed[0] != per_seed[1]
    assert sorted(per_seed[0]) == sorted(per_seed[1]) == \
        sorted(traffic.sizes(mix))
    a = next(traffic.requests(mix, 50257, 7))
    b = next(traffic.requests(mix, 50257, 7))
    assert (a[0] == b[0]).all() and a[1] == b[1]    # same seed, same input


# -- the trace reduction, on a synthetic event list ------------------------------

EVENTS = [("fusion.1", 0, 100), ("while.2", 150, 300),
          ("paged_decode_kernel", 200, 100),      # nested in while.2
          ("fusion.1", 320, 50),                  # nested in while.2
          ("copy.3", 430, 40), ("copy.3", 460, 40)]    # overlapping
HOST = [("bench.engine_step", 90, 70), ("bench.trace_slice", 0, 1000),
        ("PjitFunction(step)", 100, 30)]


def test_busy_union_idle_share_and_pattern_time():
    assert trace_reduce.union_intervals(EVENTS) == [[0, 100], [150, 500]]
    assert trace_reduce.busy_ns(EVENTS) == 100 + 350
    assert trace_reduce.busy_ns(EVENTS, (50, 200)) == 50 + 50
    assert trace_reduce.window_of(EVENTS) == (0, 500)
    assert trace_reduce.window_of([]) is None
    ns, hits = trace_reduce.pattern_ns(EVENTS, [r"paged_.*kernel"])
    assert (ns, hits) == (100, 1)
    ns, hits = trace_reduce.pattern_ns(EVENTS, [r"^copy", r"while"])
    assert (ns, hits) == (350, 3)           # overlap counted once
    assert trace_reduce.pattern_ns(EVENTS, [r"nothing"]) == (0, 0)


def test_self_times_top_ops_and_longest_gaps():
    selfs = dict()
    for name, ns in trace_reduce.self_times(EVENTS):
        selfs[name] = selfs.get(name, 0) + ns
    # copy.3 laps over the end of while.2 and over itself: what an
    # event covers of an earlier one is taken from the earlier one
    assert selfs == {"fusion.1": 150, "while.2": 130,
                     "paged_decode_kernel": 100, "copy.3": 70}
    top = trace_reduce.top_ops(EVENTS, n=2)
    assert [t[0] for t in top] == ["fusion.1", "while.2"]
    assert top[0][1] == pytest.approx(150e-9)
    assert trace_reduce.innermost_segments(HOST) == [
        (0, 90, "bench.trace_slice"), (90, 100, "bench.engine_step"),
        (100, 130, "PjitFunction(step)"), (130, 160, "bench.engine_step"),
        (160, 1000, "bench.trace_slice")]
    gaps = trace_reduce.idle_gaps(EVENTS, HOST, (0, 1000))
    # 100-150 falls in the innermost host span that covers its middle,
    # 500-1000 only in the slice's own span
    assert gaps == [["bench.trace_slice", pytest.approx(500e-9)],
                    ["PjitFunction(step)", pytest.approx(50e-9)]]
    reduced = trace_reduce.reduce_trace(
        {"devices": {"/device:TPU:0": {"XLA Ops": EVENTS}}, "host": HOST})
    assert reduced["window_s"] == pytest.approx(1000e-9)
    assert reduced["busy_s"] == pytest.approx(450e-9)
    assert trace_reduce.reduce_trace({"devices": {}, "host": HOST}) is None


def test_roofline_reader_never_reads_zero_or_counts_too_high():
    cfg = registry.config_file(registry.load_benchmark(),
                               "cerebras-gpt-1.3b")
    facts = {"cfg": cfg, "peaks": registry.peaks("TPU v5 lite"),
             "slice_context_tokens": 32 * 400 * 100,
             "trace": {"ops": [("paged_decode_kernel", 0, int(400e6))],
                       "busy_s": 0.4, "window_s": 1.0}}
    params = {"reader": "kernel_roofline", "count": "paged_decode",
              "patterns": ["paged_decode"]}
    share = readers.kernel_roofline(params, facts)
    bytes_needed = 32 * 400 * 100 * 196608
    assert share == pytest.approx(100 * bytes_needed / 819e9 / 0.4)
    assert 0 < share < 100
    assert readers.kernel_roofline(
        dict(params, patterns=["absent"]), facts) is None
    assert readers.kernel_roofline(params, dict(facts, trace=None)) is None
    assert readers.device_idle({}, facts) == pytest.approx(60.0)
    assert readers.device_idle({}, {"trace": None}) is None
    assert readers.mfu({}, {"flops_required": 0, "window_s": 1}) is None


# -- the count functions, against hand-worked numbers ----------------------------

def test_counts_match_hand_worked_numbers():
    bench = registry.load_benchmark()
    gpt = registry.config_file(bench, "cerebras-gpt-1.3b")
    # 24 x (4 x 2048^2 + 2 x 2048 x 8192) + 50304 x 2048
    assert counts.matmul_params(gpt) == 24 * 50331648 + 103022592
    assert counts.matmul_params(gpt) == pytest.approx(1.311e9, rel=1e-3)
    # 6 N + causal attention 6 x S x hidden x layers
    assert counts.train_flops_per_token(gpt, 2048) == \
        6 * 1310982144 + 6 * 2048 * 2048 * 24
    assert counts.train_flops_per_token(gpt, 2048) == \
        pytest.approx(8.47e9, rel=1e-3)
    assert counts.kv_bytes_per_token(gpt) == 196608
    # the trainer's configuration holds half of the vocabulary layer
    vp2 = registry.config_file(bench, "cerebras-gpt-1.3b-vp2")
    assert vp2["vocab_size_run"] * vp2["deployment"][
        "chips_sharing_a_layer"] == gpt["vocab_size_run"]
    assert counts.train_flops_per_token(vp2, 2048) == \
        6 * (24 * 50331648 + 25152 * 2048) + 6 * 2048 * 2048 * 24
    assert counts.train_flops_per_token(vp2, 2048) == \
        pytest.approx(8.16e9, rel=1e-3)
    bert = registry.config_file(bench, "bert-base-uncased")
    assert counts.matmul_params(bert) == 12 * (4 * 768 ** 2
                                               + 2 * 768 * 3072)
    assert counts.train_flops_per_token(bert, 512) == \
        pytest.approx(0.566e9, rel=2e-3)
    # attention alone, a step: causal is half of 4 B S^2 hidden, x3
    assert counts.attention_train_flops_per_step(gpt, 2, 2048) == \
        3 * 2 * 2 * 2048 ** 2 * 2048 * 24
    assert counts.attention_train_flops_per_step(bert, 64, 512) == \
        3 * 4 * 64 * 512 ** 2 * 768 * 12
    # one request of 3 prompt and 2 new tokens feeds 4 tokens
    h, layers = 2048, 24
    assert counts.serve_request_flops(gpt, 3, 2) == \
        2 * 24 * 50331648 * 4 + 4 * h * layers * 10 + 2 * 103022592 * 2


# -- the references against the repo's models, float32, tiny ---------------------

def _program_and_reference(config_name):
    import jax.numpy as jnp

    from benchmarks.lib import weights
    from benchmarks.reference import common, stepwise

    cfg = registry.config_file(registry.load_benchmark(DATA), config_name,
                               DATA)
    ref = __import__(f"benchmarks.reference.{cfg['reference']}",
                     fromlist=["build"])
    model = program.build_model(cfg)
    program.bind_weights(model, weights.make_all(
        5, ref.param_spec(cfg), jnp.float32))
    return cfg, model, ref, common, stepwise


@pytest.mark.parametrize("config_name,shape", [("tiny-gpt", (3, 24)),
                                               ("tiny-bert", (4, 32))])
def test_reference_forward_matches_the_repos_model(config_name, shape):
    import jax.numpy as jnp

    cfg, model, ref, common, stepwise = _program_and_reference(
        config_name)
    ids = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], shape, dtype=np.int32)
    want = np.asarray(model(program.to_tensor(ids))._array)
    got = np.asarray(stepwise.logits_of(
        ref.build(cfg, common.mm_f32), 5, ids, jnp.float32))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    # the lower-precision control is a different answer, not a copy
    low = np.asarray(stepwise.logits_of(
        ref.build(cfg, common.mm_fp8), 5, ids, jnp.float32))
    assert np.abs(low - want).max() > 50 * np.abs(got - want).max()


# -- `correct` shown to fail -----------------------------------------------------

def _driver(workload, seed=3):
    _, cell, mix, cfg = harness.load_cell(workload, DATA, DATA)
    return harness.make_driver(cell, mix, cfg, seed, DATA), mix


@pytest.mark.parametrize("workload", TRAIN_CELLS)
def test_training_control_and_half_batch_fault_fail(workload):
    """The reference put in the program's place, in the precision below
    the cell's (bf16 under this float32 cell), and with half of the batch
    left out: each has to fail one of the cell's numbers."""
    driver, mix = _driver(workload)
    driver.setup()
    driver.free()
    ref = driver.reference_numbers()
    sound, _ = check.train_numbers(driver.prog, ref)
    limits = {k: v for k, v in mix["limits"].items() if k in sound}
    ok, _ = check.judge(sound, limits)
    assert ok and len(limits) == 5
    for planted in (dict(mm="bf16"), dict(half_batch=True)):
        numbers, _ = check.train_numbers(
            driver.reference_numbers(**planted), ref)
        ok, checks = check.judge(numbers, limits)
        assert not ok, (planted, checks)
        worst = max(numbers[k] / max(sound[k], 1e-9) for k in numbers)
        assert worst > 3, (planted, numbers, sound)


def test_serving_control_fails():
    """The served tokens pass the cell's comparison; the fp8 reference's
    first choices, put through the same `judge`, do not. The window
    stays open until 150 requests have finished, so that a slow machine
    compares as many tokens as a fast one."""
    driver, mix = _driver("tiny_gpt_serve")
    driver.setup()
    driver.window(0.2, harness.Tracer(False), min_finished=150)
    driver.free()
    limits = {"token_logit_gap": mix["limits"]["token_logit_gap"]}
    sound, n = driver.token_logit_gaps()
    control, _ = driver.token_logit_gaps(mm="fp8", served=False)
    assert n >= 200
    assert check.judge({"token_logit_gap": sound}, limits)[0]
    ok, checks = check.judge({"token_logit_gap": control}, limits)
    assert not ok, checks
    assert control > 3 * max(sound, limits["token_logit_gap"])


def test_idle_leaves_are_left_out_by_the_reference_gradient():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    assert check.idle_leaves(ref) == {"c"}
    prog = {"a": 1.0, "b": 2.2, "c": 5.0}
    gap, where = check.worst_leaf_gap(prog, ref, skip={"c"})
    assert where == "b" and gap == pytest.approx(0.1)
    # against the median leaf where the leaf's own norm is smaller
    gap, where = check.worst_leaf_gap({"a": 1, "b": 2, "c": 0.5}, ref)
    assert where == "c" and gap == pytest.approx(0.5)
    with pytest.raises(KeyError):
        check.judge({}, {"listed_but_not_read": 1.0})
    assert check.judge({"read_but_not_listed": 9.0}, {}) == (True, {})
    assert check.judge({"x": float("nan")}, {"x": 1.0})[0] is False


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, fault):
    """The rest of a run, with the program broken underneath."""
    if fault == "state_unchanged":
        real = program.build_trainer

        def build(model, opt_cfg):
            step, opt = real(model, opt_cfg)

            class Frozen:
                _jitted = None

                def __call__(self, *a, **k):
                    import jax.numpy as jnp

                    # copies: the step donates what it is given
                    params = [jnp.array(p._array, copy=True)
                              for p in model.parameters()]
                    state = {k_: (jnp.array(v._array, copy=True)
                                  if hasattr(v, "_array") else v)
                             for k_, v in opt.state_dict().items()}
                    loss = step(*a, **k)
                    if opt._step_count > 1:     # keeps step 1's moments
                        for p, old in zip(model.parameters(), params):
                            p._in_place_update(old)
                        opt.set_state_dict(state)
                    Frozen._jitted = step._jitted
                    return loss

            return Frozen(), opt

        monkeypatch.setattr(program, "build_trainer", build)
        workload = "tiny_gpt_train"
    elif fault == "half_batch":
        real = program.to_tensor
        monkeypatch.setattr(
            program, "to_tensor",
            lambda a: real(np.concatenate([a[:len(a) // 2]] * 2)))
        workload = "tiny_bert_finetune"
    else:
        real = program.build_engine

        def build(model, engine_cfg):
            engine = real(model, engine_cfg)
            pop = engine.pop_results

            def altered():
                out = pop()
                for tokens in out.values():
                    tokens[-2] = (tokens[-2] + 1) % 120
                return out

            engine.pop_results = altered
            return engine

        monkeypatch.setattr(program, "build_engine", build)
        workload = "tiny_gpt_serve"
    r = run(workload, seed=9)
    assert r["correct"] is False
    failing = [k for k, c in r["checks"].items()
               if not c["value"] <= c["limit"]]
    assert failing and "compiles_in_window" not in failing
