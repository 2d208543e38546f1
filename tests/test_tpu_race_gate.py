"""Tier-1 tpu-race gate: the analyzer runs self-clean over the whole
codebase against the committed baseline, the TPU203 zombie-write rule
demonstrably fires on the broken depth-2 pipe shape (and passes the
fixed form), the TPU2xx namespace stays disjoint from tpu-lint's
TPU0xx and tpu-verify's TPU1xx, the introspect effect tables name
real framework methods, and importing the race package touches no JAX
backend."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

import paddle_tpu.analysis.race as R
from paddle_tpu.analysis.race.cli import DEFAULT_BASELINE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = Path(__file__).parent / "fixtures" / "tpu_race"

GATE_PATHS = [os.path.join(REPO, "paddle_tpu")] + sorted(
    str(p) for p in Path(REPO).glob("bench*.py")) + [
    os.path.join(REPO, "tools")]


@pytest.fixture(scope="module")
def repo_analysis():
    """One analysis of the whole repo shared by the gate assertions."""
    baseline = R.load_baseline(DEFAULT_BASELINE)
    return baseline, R.analyze_paths(GATE_PATHS, baseline=baseline)


def test_repo_is_race_clean_against_baseline(repo_analysis):
    """THE gate: any non-baselined TPU2xx finding in paddle_tpu/,
    bench*.py or tools/ fails tier-1. Hold the lock, annotate the
    caller contract with `# guarded-by:`, or fix the ordering — a
    baseline entry is the exceptional last resort."""
    _baseline, res = repo_analysis
    new = res.new_findings()
    assert new == [], "non-baselined tpu-race findings:\n" + "\n".join(
        f.render() for f in new)
    assert res.parse_errors == []
    # the gate must actually cover the codebase, not an empty glob
    assert len(res.files) > 185


def test_baseline_is_small_and_justified(repo_analysis):
    baseline, res = repo_analysis  # load_baseline raises if unjustified
    assert len(baseline) <= 5, (
        "tpu-race baseline grew past 5 entries — fix the concurrency "
        "instead of grandfathering it")
    for e in baseline.values():
        assert len(str(e["justification"]).strip()) >= 20, \
            f"baseline justification for {e['id']} is too thin"
    # no stale entries: every baselined id still matches a finding
    assert res.stale_baseline == []


def test_tpu203_fires_on_broken_depth2_pipe_and_passes_fixed():
    """The zombie-proofing gate for async pipe depth > 1 (ROADMAP
    item 3): freeing the previous iteration's blocks BEFORE waiting on
    its dispatch must fire; the complete-then-free ordering must not.
    The fixtures model the engine's loop-carried depth-2 shape."""
    broken, _ = R.analyze_file(str(FIXTURES / "tpu203_pos.py"))
    assert [(f.rule, f.line) for f in broken] == [("TPU203", 17)], \
        [f.render() for f in broken]
    assert "zombie" in broken[0].message
    fixed, _ = R.analyze_file(str(FIXTURES / "tpu203_neg.py"))
    assert fixed == [], [f.render() for f in fixed]


def test_tpu203_holds_the_ahead_orders_invariant():
    """Since PR 29 the engine launches step N+1 before it reads step
    N. The invariant (DESIGN_DECISIONS r21, restated): a lane is
    released only after the LAST step dispatched over it has
    completed. The gate passes the order itself (wait for N, release
    N's lanes, N+1 outstanding) and still refuses a release between a
    dispatch and that dispatch's completion: step N's lanes before the
    wait on N, and step N+1's lanes while it runs."""
    broken, _ = R.analyze_file(str(FIXTURES / "tpu203_ahead_pos.py"))
    assert [(f.rule, f.line) for f in broken] == \
        [("TPU203", 20), ("TPU203", 23)], [f.render() for f in broken]
    sound, _ = R.analyze_file(str(FIXTURES / "tpu203_ahead_neg.py"))
    assert sound == [], [f.render() for f in sound]


def test_rule_id_namespaces_are_disjoint():
    """One registry test over all four analysis tiers: tpu-lint
    TPU0xx, tpu-verify TPU1xx, tpu-race TPU2xx, tpu-shard TPU3xx — no
    id collisions, each tier inside its own hundred-block."""
    from paddle_tpu.analysis import all_rule_ids
    from paddle_tpu.analysis.race.rules import all_race_rule_ids
    from paddle_tpu.analysis.shard.rules import all_shard_rule_ids
    from paddle_tpu.analysis.trace.rules import all_trace_rule_ids

    tiers = {
        "lint": (set(all_rule_ids()), 0),
        "trace": (set(all_trace_rule_ids()), 100),
        "race": (set(all_race_rule_ids()), 200),
        "shard": (set(all_shard_rule_ids()), 300),
    }
    for name, (ids, base) in tiers.items():
        assert ids, name
        assert all(base <= int(r[3:]) <= base + 99 for r in ids), name
    names = sorted(tiers)
    for i, a in enumerate(names):
        for b in names[i + 1:]:
            assert not (tiers[a][0] & tiers[b][0]), (a, b)


def test_introspect_effect_tables_name_real_methods():
    """The dispatch/release tables TPU203 consumes must track the real
    framework surface (the ENGINE_STEP_DONATION pattern): every name
    is a callable on the class that declares it, and the classes
    reference the table rather than restating the strings."""
    from paddle_tpu.adapters.pool import PagedAdapterPool
    from paddle_tpu.inference.engine import (GenerationEngine,
                                             PagedKVCache)
    from paddle_tpu.jit import introspect as I

    by_name = {"PagedKVCache": PagedKVCache,
               "PagedAdapterPool": PagedAdapterPool}
    assert sorted(by_name) == sorted(I.ALLOCATOR_RELEASE_EFFECTS)
    for cls_name, methods in I.ALLOCATOR_RELEASE_EFFECTS.items():
        cls = by_name[cls_name]
        assert cls.RACE_RELEASE_METHODS == methods
        for m in methods:
            assert callable(getattr(cls, m)), (cls_name, m)
    assert GenerationEngine.RACE_DISPATCH_METHODS \
        == I.ENGINE_DISPATCH_EFFECTS
    for m in I.ENGINE_DISPATCH_EFFECTS:
        assert callable(getattr(GenerationEngine, m)), m
    assert GenerationEngine.RACE_COMPLETE_CALLS == I.STEP_COMPLETE_CALLS
    assert "jax.block_until_ready" in I.STEP_COMPLETE_CALLS
    # the serial completes sync via host conversion, not an explicit
    # block_until_ready — the table must cover that path too
    assert "numpy.asarray" in I.STEP_COMPLETE_CALLS


def test_race_import_has_no_backend_init_and_no_jax_use():
    """Importing + running the race analyzer must not initialize a JAX
    backend: pure AST work over introspect metadata, safe in
    pre-device CI stages."""
    code = (
        "import paddle_tpu.analysis.race as R\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, 'import initialized a backend'\n"
        "src = ('import threading\\n'\n"
        "       'class W:\\n'\n"
        "       '    def __init__(self):\\n'\n"
        "       '        self.n = 0\\n'\n"
        "       '        threading.Thread(target=self._w).start()\\n'\n"
        "       '    def _w(self):\\n'\n"
        "       '        self.n += 1\\n'\n"
        "       '    def step(self):\\n'\n"
        "       '        return self.n\\n')\n"
        "findings, _ = R.analyze_file('snippet.py', src)\n"
        "assert [f.rule for f in findings] == ['TPU201'], findings\n"
        "assert not xla_bridge._backends, 'analysis touched a backend'\n"
        "print('RACE_SMOKE_OK')\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "RACE_SMOKE_OK" in res.stdout


def test_cli_acceptance_command_exits_zero():
    """The ISSUE acceptance command, verbatim."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpu_race.py"),
         os.path.join(REPO, "paddle_tpu"),
         os.path.join(REPO, "bench_ops.py"),
         os.path.join(REPO, "tools")],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "tpu-race clean" in res.stdout
