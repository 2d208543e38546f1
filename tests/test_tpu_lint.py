"""tpu-lint unit tests: per-rule fixtures (exact file:line), inline
suppressions, baseline round-trip, stable finding IDs, CLI output."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import paddle_tpu.analysis as A
from paddle_tpu.analysis.findings import assign_ids

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = Path(__file__).parent / "fixtures" / "tpu_lint"
LINT = os.path.join(REPO, "tools", "tpu_lint.py")


def analyze(name):
    findings, _mod = A.analyze_file(str(FIXTURES / name))
    return assign_ids(findings)


def hits(findings, rule):
    """(line, suppressed) pairs for one rule, in line order."""
    return [(f.line, f.suppressed) for f in findings if f.rule == rule]


# -- per-rule fixtures: >=1 positive and >=1 negative, exact lines --------

@pytest.mark.parametrize("rule,pos,neg,lines", [
    ("TPU001", "tpu001_pos.py", "tpu001_neg.py", [8, 9, 10, 16]),
    ("TPU002", "tpu002_pos.py", "tpu002_neg.py", [6, 16]),
    ("TPU003", "tpu003_pos.py", "tpu003_neg.py", [6, 13]),
    # the PR-15 sampling-step key-fold pattern: a folded per-slot key
    # consumed twice fires; fold_in-per-draw (ops/sampling.py) passes
    ("TPU003", "tpu003_sampling_pos.py", "tpu003_sampling_neg.py",
     [10]),
    ("TPU004", "tpu004_pos.py", "tpu004_neg.py", [8, 14]),
    ("TPU005", "tpu005_pos.py", "tpu005_neg.py", [10, 11]),
    ("TPU006", "tpu006_pos.py", "tpu006_neg.py", [3, 9]),
    ("TPU007", "tpu007_pos.py", "tpu007_neg.py", [8]),
    ("TPU008", "tpu008_pos.py", "tpu008_neg.py", [9]),
])
def test_rule_fixture(rule, pos, neg, lines):
    findings = analyze(pos)
    assert hits(findings, rule) == [(ln, False) for ln in lines], \
        [f.render() for f in findings]
    # the positive fixture must not trip OTHER rules (fixture isolation)
    assert {f.rule for f in findings} == {rule}
    neg_findings = analyze(neg)
    assert hits(neg_findings, rule) == [], \
        [f.render() for f in neg_findings]


def test_shard_map_bodies_are_traced_contexts():
    """ISSUE 8 satellite: a callable staged through
    `jax.shard_map` is a traced context for the
    jit-reachability walker — host syncs (TPU001) and eager
    collectives (TPU007) inside the body are findings, while the
    mesh-level `jax.lax.psum`/`all_gather` the sharded serving engine
    actually uses never misfire."""
    findings = analyze("shard_map_pos.py")
    assert hits(findings, "TPU001") == [(6, False)], \
        [f.render() for f in findings]
    assert {f.rule for f in findings} == {"TPU001"}
    findings = analyze("shard_map_tpu007_pos.py")
    assert hits(findings, "TPU007") == [(8, False)], \
        [f.render() for f in findings]
    assert {f.rule for f in findings} == {"TPU007"}
    neg = analyze("shard_map_neg.py")
    assert not neg, [f.render() for f in neg]


def test_unparseable_file_is_reported_not_skipped():
    findings = analyze("unparseable.py")
    assert [f.rule for f in findings] == ["TPU000"]
    assert "unparseable" in findings[0].message


# -- suppressions ---------------------------------------------------------

def test_inline_suppression_same_line_only():
    findings = analyze("suppressed.py")
    assert hits(findings, "TPU005") == [(8, True), (14, False)]


# -- stable finding ids ---------------------------------------------------

def test_finding_ids_survive_line_shifts():
    src = (FIXTURES / "tpu003_pos.py").read_text()
    base, _ = A.analyze_file("k.py", src)
    assign_ids(base)
    shifted, _ = A.analyze_file("k.py", "# a comment\n\n" + src)
    assign_ids(shifted)
    assert [f.id for f in base] == [f.id for f in shifted]
    assert [f.line + 2 for f in base] == [f.line for f in shifted]


def test_finding_ids_change_when_the_hazard_line_changes():
    src = (FIXTURES / "tpu003_pos.py").read_text()
    base, _ = A.analyze_file("k.py", src)
    assign_ids(base)
    edited, _ = A.analyze_file(
        "k.py", src.replace("jax.random.uniform(key, (2,))",
                            "jax.random.uniform(key, (3,))"))
    assign_ids(edited)
    assert base[0].id != edited[0].id  # grandfathering invalidated


def test_tpu004_resolves_introspect_donation_constants():
    """The framework's own donation idiom — `donate_argnums=
    introspect.TRAINSTEP_DONATE_ARGNUMS if flag else ()`, possibly via
    a local variable — must stay visible to TPU004 (the analyzer reads
    the metadata, not a literal)."""
    src = (
        "import jax\n"
        "from paddle_tpu.jit import introspect\n"
        "def run(params, accums, bufs, x, flag, step_fn):\n"
        "    donate = introspect.TRAINSTEP_DONATE_ARGNUMS if flag "
        "else ()\n"
        "    step = jax.jit(step_fn, donate_argnums=donate)\n"
        "    out = step(params, accums, bufs, x)\n"
        "    return params\n")
    findings, _ = A.analyze_file("donate.py", src)
    assert [(f.rule, f.line) for f in findings] == [("TPU004", 7)], \
        [f.render() for f in findings]
    # direct keyword form, no intermediate variable
    src2 = (
        "import jax\n"
        "from paddle_tpu.jit import introspect\n"
        "def run(grads, x, acc_fn):\n"
        "    acc = jax.jit(acc_fn, "
        "donate_argnums=introspect.ACCUM_DONATE_ARGNUMS)\n"
        "    out = acc(grads, x)\n"
        "    return grads\n")
    findings2, _ = A.analyze_file("donate2.py", src2)
    assert [(f.rule, f.line) for f in findings2] == [("TPU004", 6)], \
        [f.render() for f in findings2]


def test_relative_imports_resolve_in_package_init():
    """A relative import in a package __init__.py resolves against the
    PACKAGE, not its parent — a TPU007 hazard reached through
    `from .collective import all_reduce` must not slip the gate."""
    src = ("import jax\n"
           "from .collective import all_reduce\n"
           "@jax.jit\n"
           "def step(x):\n"
           "    return all_reduce(x)\n")
    findings, _ = A.analyze_file(
        "paddle_tpu/distributed/__init__.py", src)
    assert [(f.rule, f.line) for f in findings] == [("TPU007", 5)], \
        [f.render() for f in findings]


def test_finding_ids_in_lambdas_survive_line_shifts():
    src = ("import jax, time\n"
           "f = jax.jit(lambda x: x + time.time())\n")
    base, _ = A.analyze_file("lam.py", src)
    assign_ids(base)
    assert [f.rule for f in base] == ["TPU005"]
    shifted, _ = A.analyze_file("lam.py", "# c\n# c\n" + src)
    assign_ids(shifted)
    assert [f.id for f in base] == [f.id for f in shifted]


# -- baseline round-trip --------------------------------------------------

def test_baseline_round_trip(tmp_path):
    res = A.analyze_paths([str(FIXTURES / "tpu001_pos.py")])
    assert len(res.new_findings()) == 4
    bpath = tmp_path / "baseline.json"
    A.write_baseline(str(bpath), res.new_findings())
    # skeleton entries have empty justifications: loader must refuse
    with pytest.raises(A.BaselineError, match="justification"):
        A.load_baseline(str(bpath))
    doc = json.loads(bpath.read_text())
    for e in doc["entries"]:
        e["justification"] = "test grandfathering"
    doc["entries"].append({"id": "TPU009:deadbeef00", "rule": "TPU009",
                           "path": "gone.py",
                           "justification": "stale on purpose"})
    bpath.write_text(json.dumps(doc))
    baseline = A.load_baseline(str(bpath))
    res2 = A.analyze_paths([str(FIXTURES / "tpu001_pos.py")],
                           baseline=baseline)
    assert res2.new_findings() == []
    assert sum(1 for f in res2.findings if f.baselined) == 4
    assert res2.stale_baseline == ["TPU009:deadbeef00"]


def test_baseline_accepts_bare_list_form(tmp_path):
    bpath = tmp_path / "list.json"
    bpath.write_text(json.dumps([
        {"id": "TPU001:0000000000",
         "justification": "list-form baseline entry for the loader"}]))
    baseline = A.load_baseline(str(bpath))
    assert "TPU001:0000000000" in baseline


# -- CLI ------------------------------------------------------------------

def _run_lint(args, cwd=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, LINT] + args, env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=cwd)


def test_finding_ids_do_not_depend_on_cwd(tmp_path):
    """The committed baseline must hold from ANY invocation directory:
    paths in finding IDs are repo-root-relative, not cwd-relative."""
    res = _run_lint([os.path.join(REPO, "paddle_tpu", "core",
                                  "pylayer.py")], cwd=str(tmp_path))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "2 baselined" in res.stdout


def test_cli_json_format_and_exit_code():
    res = _run_lint([str(FIXTURES / "tpu002_pos.py"),
                     "--baseline", "none", "--format", "json"])
    assert res.returncode == 1
    doc = json.loads(res.stdout)
    assert [f["line"] for f in doc["findings"]] == [6, 16]
    assert all(f["rule"] == "TPU002" for f in doc["findings"])
    assert doc["files"] == 1
    res = _run_lint([str(FIXTURES / "tpu002_neg.py"),
                     "--baseline", "none"])
    assert res.returncode == 0, res.stdout + res.stderr


def test_cli_stats_reports_counts_and_unparseable():
    res = _run_lint([str(FIXTURES), "--baseline", "none", "--stats"])
    assert res.returncode == 1
    out = res.stdout
    assert "files analyzed: 23" in out
    assert "UNPARSEABLE files: 1" in out
    assert "unparseable.py" in out
    # per-rule counts visible (no silent skips); the shard_map
    # fixtures add one TPU001 and one TPU007 hit
    # the PR-15 sampling fixtures add one TPU003 hit
    for rule, n in [("TPU001", 5), ("TPU002", 2), ("TPU003", 3),
                    ("TPU004", 2), ("TPU005", 4), ("TPU006", 2),
                    ("TPU007", 2), ("TPU008", 1)]:
        assert any(line.startswith(rule) and line.rstrip().endswith(str(n))
                   for line in out.splitlines()), (rule, n, out)
    assert "suppressed inline: 1" in out


def test_cli_list_rules_covers_all_eight():
    res = _run_lint(["--list-rules"])
    assert res.returncode == 0
    for rule in ["TPU00%d" % i for i in range(1, 9)]:
        assert rule in res.stdout
