"""Packaging + CI bench regression gate (VERDICT r3 missing #4).

Reference analogs: tools/check_op_benchmark_result.py,
tools/ci_model_benchmark.sh, setup.py (packaging).
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GATE = os.path.join(REPO, "tools", "check_bench_result.py")


def _run(args):
    return subprocess.run([sys.executable, GATE] + args,
                          capture_output=True, text=True, timeout=120)


def _bench_lines(path, rows):
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def test_gate_passes_within_threshold(tmp_path):
    base = {"m1": {"metric": "m1", "value": 100.0, "unit": "x/s"}}
    (tmp_path / "base.json").write_text(json.dumps(base))
    _bench_lines(tmp_path / "cur.jsonl",
                 [{"metric": "m1", "value": 95.0, "unit": "x/s"}])
    res = _run(["--bench", str(tmp_path / "cur.jsonl"),
                "--baseline", str(tmp_path / "base.json")])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "bench gate ok" in res.stdout


def test_gate_fails_on_regression(tmp_path):
    base = {"m1": {"metric": "m1", "value": 100.0, "unit": "x/s"}}
    (tmp_path / "base.json").write_text(json.dumps(base))
    _bench_lines(tmp_path / "cur.jsonl",
                 [{"metric": "m1", "value": 80.0, "unit": "x/s"}])
    res = _run(["--bench", str(tmp_path / "cur.jsonl"),
                "--baseline", str(tmp_path / "base.json")])
    assert res.returncode == 1
    assert "REGRESSION GATE FAILED" in res.stdout
    assert "+20.0% regression" in res.stdout


def test_gate_fails_on_missing_or_failed_row(tmp_path):
    base = {"m1": {"metric": "m1", "value": 100.0},
            "m2": {"metric": "m2", "value": 10.0}}
    (tmp_path / "base.json").write_text(json.dumps(base))
    _bench_lines(tmp_path / "cur.jsonl",
                 [{"metric": "m1_FAILED", "value": 0, "unit": "error"},
                  {"metric": "m1", "value": 0, "unit": "error"}])
    res = _run(["--bench", str(tmp_path / "cur.jsonl"),
                "--baseline", str(tmp_path / "base.json")])
    assert res.returncode == 1
    assert "m2: missing" in res.stdout
    assert "m1: current run FAILED" in res.stdout


def test_gate_update_writes_baseline(tmp_path):
    _bench_lines(tmp_path / "cur.jsonl",
                 [{"metric": "m1", "value": 50.0, "unit": "x/s"}])
    res = _run(["--bench", str(tmp_path / "cur.jsonl"),
                "--baseline", str(tmp_path / "new.json"), "--update"])
    assert res.returncode == 0
    data = json.loads((tmp_path / "new.json").read_text())
    assert data["m1"]["value"] == 50.0


def test_gate_opbench_mode(tmp_path):
    base = {"op_a": {"op": "op_a", "ms": 1.0}}
    (tmp_path / "base.json").write_text(json.dumps(base))
    (tmp_path / "cur.json").write_text(json.dumps(
        {"op_a": {"op": "op_a", "ms": 2.0}}))
    res = _run(["--opbench", str(tmp_path / "cur.json"),
                "--baseline", str(tmp_path / "base.json")])
    assert res.returncode == 1
    assert "+100%" in res.stdout


def test_repo_baseline_is_current_format():
    """The committed BENCH_BASELINE.json gates the committed metric
    names — a renamed bench row must update the baseline too."""
    with open(os.path.join(REPO, "BENCH_BASELINE.json")) as f:
        base = json.load(f)
    for m in ("gpt_1p3b_train_tokens_per_sec_per_chip",
              "bert_base_finetune_tokens_per_sec_per_chip",
              "resnet50_train_images_per_sec_per_chip"):
        assert m in base
        assert base[m]["value"] > 0


def test_pyproject_packaging_metadata():
    """pip install -e . consumes this file; validate it statically
    (no network in the test env)."""
    try:
        import tomllib                   # 3.11+
    except ModuleNotFoundError:
        import tomli as tomllib          # the 3.10 backport

    with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
        meta = tomllib.load(f)
    assert meta["project"]["name"] == "paddle-tpu"
    assert "jax" in meta["project"]["dependencies"]
    inc = meta["tool"]["setuptools"]["packages"]["find"]["include"]
    assert "paddle_tpu*" in inc
    from setuptools import find_packages

    pkgs = find_packages(where=REPO, include=["paddle_tpu*"])
    assert "paddle_tpu" in pkgs and "paddle_tpu.distributed" in pkgs


def test_gate_floor_row_absolute_pass_condition(tmp_path):
    """VERDICT r5 next #8a: a row with a decided 'floor' is gated on
    clearing that absolute throughput, not on the relative drop vs its
    own best-ever value (the ResNet go/no-go shape)."""
    base = {"r": {"metric": "r", "value": 2435.0, "unit": "images/s",
                  "floor": 2350.0}}
    (tmp_path / "base.json").write_text(json.dumps(base))
    # 2360 is a >3% drop vs 2435 BUT clears the floor: pass
    _bench_lines(tmp_path / "cur.jsonl",
                 [{"metric": "r", "value": 2360.0, "unit": "images/s"}])
    res = _run(["--bench", str(tmp_path / "cur.jsonl"),
                "--baseline", str(tmp_path / "base.json"),
                "--threshold", "0.02"])
    assert res.returncode == 0, res.stdout + res.stderr
    # below the floor fails regardless of threshold
    _bench_lines(tmp_path / "cur.jsonl",
                 [{"metric": "r", "value": 2300.0, "unit": "images/s"}])
    res = _run(["--bench", str(tmp_path / "cur.jsonl"),
                "--baseline", str(tmp_path / "base.json"),
                "--threshold", "0.50"])
    assert res.returncode == 1
    assert "below the decided floor" in res.stdout


def test_gate_update_preserves_floor(tmp_path):
    base = {"r": {"metric": "r", "value": 2435.0, "floor": 2350.0}}
    (tmp_path / "base.json").write_text(json.dumps(base))
    _bench_lines(tmp_path / "cur.jsonl",
                 [{"metric": "r", "value": 2500.0, "unit": "images/s"}])
    res = _run(["--bench", str(tmp_path / "cur.jsonl"),
                "--baseline", str(tmp_path / "base.json"), "--update"])
    assert res.returncode == 0
    data = json.loads((tmp_path / "base.json").read_text())
    assert data["r"]["value"] == 2500.0 and data["r"]["floor"] == 2350.0
    # a partial run MISSING the floored row must not erase the decision
    _bench_lines(tmp_path / "cur.jsonl",
                 [{"metric": "other", "value": 1.0, "unit": "x/s"}])
    res = _run(["--bench", str(tmp_path / "cur.jsonl"),
                "--baseline", str(tmp_path / "base.json"), "--update"])
    assert res.returncode == 0
    data = json.loads((tmp_path / "base.json").read_text())
    assert data["r"]["floor"] == 2350.0 and data["r"]["value"] == 2500.0
    assert data["other"]["value"] == 1.0


def test_repo_resnet_row_carries_decided_floor():
    """The committed baseline encodes the ResNet go/no-go decision."""
    with open(os.path.join(REPO, "BENCH_BASELINE.json")) as f:
        base = json.load(f)
    assert base["resnet50_train_images_per_sec_per_chip"]["floor"] == 2350.0


def test_pending_smoke_flags_unadopted_opbench_rows():
    """--pending smoke (ISSUE 4 satellite): the suite rows added by
    PRs 1-18 stay VISIBLY pending until a TPU `bench_ops.py --save`
    refresh adopts them — the gate must keep saying so, loudly."""
    res = _run(["--pending", os.path.join(REPO, "OPBENCH.json")])
    assert res.returncode == 0, res.stdout + res.stderr  # report-only
    for row in ("gpt_decode_kv_350m", "gpt_engine_offered_load",
                "paged_attention_decode_sweep",
                "gpt_engine_offered_load_pallas",
                "gpt_engine_prefix_cache",
                "gpt_engine_speculative",
                "gpt_engine_offered_load_mp2",
                "gpt_engine_offered_load_int8",
                "gpt_fleet_offered_load",
                "gpt_engine_multitenant_lora", "gpt_engine_sampling",
                "conv_fused_sweep", "resnet50_fused_block",
                "conv_fused_bwd_sweep", "resnet50_fused_block_train",
                "gpt_engine_host_gap", "gpt_engine_async_overlap"):
        assert f"PENDING: {row}" in res.stdout, res.stdout
    assert "pending row(s) not gated" in res.stdout
    # --strict turns the report into a failure
    res = _run(["--pending", os.path.join(REPO, "OPBENCH.json"),
                "--strict"])
    assert res.returncode == 1
