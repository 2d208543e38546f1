"""tpu-race unit tests: per-rule fixtures (exact file:line), inline
suppressions, baseline round-trip, stable finding IDs, branch-fork
effect modeling, the fixed/annotated real-file regressions, and the
CLI surface."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import paddle_tpu.analysis.race as R
from paddle_tpu.analysis.findings import assign_ids

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = Path(__file__).parent / "fixtures" / "tpu_race"
RACE = os.path.join(REPO, "tools", "tpu_race.py")


def analyze(name):
    findings, _mod = R.analyze_file(str(FIXTURES / name))
    return assign_ids(findings)


def hits(findings, rule):
    """(line, suppressed) pairs for one rule, in line order."""
    return [(f.line, f.suppressed) for f in findings if f.rule == rule]


# -- per-rule fixtures: >=1 positive and >=1 negative, exact lines --------

@pytest.mark.parametrize("rule,pos,neg,lines", [
    ("TPU201", "tpu201_pos.py", "tpu201_neg.py", [11]),
    ("TPU202", "tpu202_pos.py", "tpu202_neg.py", [16, 31]),
    ("TPU203", "tpu203_pos.py", "tpu203_neg.py", [17]),
    ("TPU203", "tpu203_ahead_pos.py", "tpu203_ahead_neg.py", [20, 23]),
    ("TPU204", "tpu204_pos.py", "tpu204_neg.py", [20, 24, 28]),
    ("TPU205", "tpu205_pos.py", "tpu205_neg.py", [15]),
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


def test_unparseable_file_is_reported_not_skipped():
    findings = analyze("unparseable.py")
    assert [f.rule for f in findings] == ["TPU200"]
    assert "unparseable" in findings[0].message


# -- suppressions ---------------------------------------------------------

def test_inline_suppression_same_line_only():
    findings = analyze("suppressed.py")
    assert hits(findings, "TPU202") == [(15, True), (18, False)]


def test_race_tag_does_not_leak_into_tpu_lint_suppressions():
    """`# tpu-race: disable=...` must not suppress tpu-lint findings
    and vice versa — the tags are separate namespaces."""
    from paddle_tpu.analysis.findings import parse_suppressions
    src = ("x = 1  # tpu-race: disable=TPU202\n"
           "y = 2  # tpu-lint: disable=TPU005\n")
    assert parse_suppressions(src) == {2: {"TPU005"}}
    assert parse_suppressions(src, tag="tpu-race") == {1: {"TPU202"}}


# -- branch-fork effect modeling (the engine false-positive shapes) -------

def test_early_return_arm_does_not_leak_its_dispatch():
    """The `step()` shape: an `if` arm that RETURNS after dispatching
    (async core) must not make the serial fall-through path's
    allocations read as free-before-complete."""
    src = (
        "class E:\n"
        "    def step(self):\n"
        "        if self.async_core:\n"
        "            return self._step_async()\n"
        "        return self.cache.allocate(1)\n"
        "    def _step_async(self):\n"
        "        self._dispatch_ahead()\n"
        "    def _dispatch_ahead(self):\n"
        "        pass\n")
    findings, _ = R.analyze_file("e.py", src)
    assert [f for f in findings if f.rule == "TPU203"] == [], \
        [f.render() for f in findings]


def test_exclusive_if_arms_do_not_see_each_others_dispatch():
    """The `_dispatch_ahead()` shape: a dispatch on the spec arm and a
    release on the else arm are exclusive, not ordered. The linear
    `bad()` ordering is the positive control — same calls, one path."""
    src = (
        "class E:\n"
        "    def go(self, spec):\n"
        "        if spec:\n"
        "            self._spec_dispatch()\n"
        "        else:\n"
        "            self.pool.release(1)\n"
        "    def bad(self):\n"
        "        self._spec_dispatch()\n"
        "        self.pool.release(1)\n")
    findings, _ = R.analyze_file("e.py", src)
    assert [(f.rule, f.line) for f in findings] == [("TPU203", 9)], \
        [f.render() for f in findings]


def test_conditional_complete_is_pessimistic():
    """A complete wrapped in `if` (not the early-return guard idiom)
    leaves a no-complete path — the release after the merge fires."""
    src = (
        "import jax\n"
        "class E:\n"
        "    def f(self, x, b):\n"
        "        self._plain_dispatch(x)\n"
        "        if self.flag:\n"
        "            jax.block_until_ready(x)\n"
        "        self.cache.free(b)\n"
        "    def _plain_dispatch(self, x):\n"
        "        pass\n")
    findings, _ = R.analyze_file("e.py", src)
    assert [(f.rule, f.line) for f in findings] == [("TPU203", 7)], \
        [f.render() for f in findings]


# -- which dispatch a wait completes (the ahead order's invariant) --------

_AHEAD = (
    "import jax\n"
    "class E:\n"
    "    def step(self, work):\n"
    "        prev = self._inflight\n"
    "        self._inflight = self._plain_dispatch(work)\n"
    "        if prev is None:\n"
    "            return\n"
    "        {wait}\n"
    "        for i, slot in zip(prev.runnable, prev.slots):\n"
    "            self._finish(slot)\n"
    "{tail}"
    "    def _finish(self, slot):\n"
    "        self.cache.free(slot.blocks)\n"
    "    def _plain_dispatch(self, work):\n"
    "        return work\n")


@pytest.mark.parametrize("wait,tail,lines", [
    # the ahead order: wait for step N, release ITS lanes with N+1 out
    ("jax.block_until_ready(prev.out)", "", []),
    # a wait on something else completes no record held under a name
    ("jax.block_until_ready(work)", "", [10]),
    # no wait at all
    ("pass", "", [10]),
    # the wait on N does not cover the lanes of N+1, still running
    ("jax.block_until_ready(prev.out)",
     "        self.cache.free(self._inflight.slots[0].blocks)\n", [11]),
    # nor a release that draws its lanes from no record
    ("jax.block_until_ready(prev.out)",
     "        self.cache.free(work)\n", [11]),
    # a wait on the NEWER step completes the older one too (one stream)
    ("jax.block_until_ready(self._inflight.out)", "", []),
])
def test_a_release_needs_the_wait_on_the_step_its_lanes_last_rode(
        wait, tail, lines):
    """The invariant TPU203 holds since the ahead order (r21 as
    restated): release after the LAST step dispatched over the lane has
    completed. The record a wait completes and the record a release
    draws its lanes from are followed by name through assignments, loop
    targets and spliced calls' parameters."""
    findings, _ = R.analyze_file(
        "e.py", _AHEAD.format(wait=wait, tail=tail))
    assert [(f.rule, f.line) for f in findings] == \
        [("TPU203", ln) for ln in lines], [f.render() for f in findings]


def test_engine_ahead_order_is_clean_and_a_planted_early_release_fires():
    """The engine's own `_step_ahead` passes; the same source with a
    release planted between the launch of step N+1 and the wait on
    step N (step N's lanes), or drawn from step N+1's record, fires."""
    rel = "paddle_tpu/inference/engine.py"
    src, findings = _analyze_repo_file(rel)
    assert [f for f in findings if f.rule == "TPU203"] == [], \
        [f.render() for f in findings]
    anchor = ("            if prev is not None:\n"
              "                progressed += self._plain_complete(prev)\n")
    assert src.count(anchor) == 1
    for record in ("prev", "self._inflight"):
        planted = src.replace(
            anchor,
            f"            for slot in {record}.slots:\n"
            "                self._release(slot)\n" + anchor)
        fired, _ = R.analyze_file(os.path.join(REPO, rel), planted)
        assert {f.rule for f in fired} == {"TPU203"}, record


def test_getattr_default_lock_idiom_is_a_lock():
    """`with getattr(self, "_lock", threading.Lock()):` (core/random)
    still names the lock for the discipline rules."""
    src = (
        "import threading\n"
        "class G:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def seed(self, s):\n"
        "        with getattr(self, '_lock', threading.Lock()):\n"
        "            self._seed = s\n"
        "    def reseed(self, s):\n"
        "        with self._lock:\n"
        "            self._seed = s\n")
    findings, _ = R.analyze_file("g.py", src)
    assert findings == [], [f.render() for f in findings]


# -- real-file regressions for the first self-run's findings --------------

def _analyze_repo_file(rel):
    path = os.path.join(REPO, rel)
    src = Path(path).read_text()
    findings, _ = R.analyze_file(path, src)
    return src, findings


def test_ssd_table_lru_touch_is_locked_regression():
    """PR-19 true positive: SSDSparseTable._touch mutated the LRU
    OrderedDict without _db_lock while _maybe_evict popped it under
    the lock (table ops run on PS rpc handler threads). Fixed by
    locking _touch; dropping the lock must re-fire TPU202."""
    rel = "paddle_tpu/distributed/ps/table.py"
    src, findings = _analyze_repo_file(rel)
    assert [f for f in findings if f.rule == "TPU202"] == [], \
        [f.render() for f in findings]
    unlocked = src.replace(
        "        with self._db_lock:\n"
        "            self._lru.pop(i, None)\n"
        "            self._lru[i] = None",
        "        self._lru.pop(i, None)\n"
        "        self._lru[i] = None")
    assert unlocked != src, "table.py _touch no longer matches"
    broken, _ = R.analyze_file(rel, unlocked)
    assert any(f.rule == "TPU202" and "_lru" in f.message
               for f in broken), [f.render() for f in broken]


@pytest.mark.parametrize("rel", [
    "paddle_tpu/observability/metrics.py",
    "paddle_tpu/distributed/launch/elastic.py",
])
def test_guarded_by_annotations_are_load_bearing(rel):
    """metrics._zero / elastic._prune are caller-holds-lock helpers:
    clean WITH the guarded-by annotations, TPU202 findings without
    them — the annotations assert a real contract, not decoration."""
    src, findings = _analyze_repo_file(rel)
    assert "# guarded-by: _lock" in src
    assert [f for f in findings if f.rule == "TPU202"] == [], \
        [f.render() for f in findings]
    stripped = src.replace("# guarded-by: _lock", "")
    broken, _ = R.analyze_file(rel, stripped)
    assert any(f.rule == "TPU202" for f in broken)


# -- stable finding ids ---------------------------------------------------

def test_finding_ids_survive_line_shifts():
    src = (FIXTURES / "tpu202_pos.py").read_text()
    base, _ = R.analyze_file("k.py", src)
    assign_ids(base)
    shifted, _ = R.analyze_file("k.py", "# a comment\n\n" + src)
    assign_ids(shifted)
    assert [f.id for f in base] == [f.id for f in shifted]
    assert [f.line + 2 for f in base] == [f.line for f in shifted]


def test_finding_ids_change_when_the_hazard_line_changes():
    src = (FIXTURES / "tpu202_pos.py").read_text()
    base, _ = R.analyze_file("k.py", src)
    assign_ids(base)
    edited, _ = R.analyze_file(
        "k.py", src.replace("self._total = 0.0\n\n\nclass TwoLocks",
                            "self._total = -0.0\n\n\nclass TwoLocks"))
    assign_ids(edited)
    assert base[0].id != edited[0].id  # grandfathering invalidated


# -- baseline round-trip --------------------------------------------------

def test_baseline_round_trip(tmp_path):
    res = R.analyze_paths([str(FIXTURES / "tpu202_pos.py")])
    assert len(res.new_findings()) == 2
    bpath = tmp_path / "baseline.json"
    R.write_baseline(str(bpath), res.new_findings())
    # skeleton entries have empty justifications: loader must refuse
    with pytest.raises(R.BaselineError, match="justification"):
        R.load_baseline(str(bpath))
    doc = json.loads(bpath.read_text())
    for e in doc["entries"]:
        e["justification"] = "test grandfathering"
    doc["entries"].append({"id": "TPU209:deadbeef00", "rule": "TPU209",
                           "path": "gone.py",
                           "justification": "stale on purpose"})
    bpath.write_text(json.dumps(doc))
    baseline = R.load_baseline(str(bpath))
    res2 = R.analyze_paths([str(FIXTURES / "tpu202_pos.py")],
                           baseline=baseline)
    assert res2.new_findings() == []
    assert sum(1 for f in res2.findings if f.baselined) == 2
    assert res2.stale_baseline == ["TPU209:deadbeef00"]


# -- CLI ------------------------------------------------------------------

def _run_race(args, cwd=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, RACE] + args, env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=cwd)


def test_cli_json_format_and_exit_code():
    res = _run_race([str(FIXTURES / "tpu204_pos.py"),
                     "--baseline", "none", "--format", "json"])
    assert res.returncode == 1
    doc = json.loads(res.stdout)
    assert [f["line"] for f in doc["findings"]] == [20, 24, 28]
    assert all(f["rule"] == "TPU204" for f in doc["findings"])
    assert doc["files"] == 1
    res = _run_race([str(FIXTURES / "tpu204_neg.py"),
                     "--baseline", "none"])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "tpu-race clean" in res.stdout


def test_cli_stats_reports_counts_and_unparseable():
    res = _run_race([str(FIXTURES), "--baseline", "none", "--stats"])
    assert res.returncode == 1
    out = res.stdout
    assert "files analyzed: 14" in out
    assert "UNPARSEABLE files: 1" in out
    assert "unparseable.py" in out
    for rule, n in [("TPU200", 1), ("TPU201", 1), ("TPU202", 4),
                    ("TPU203", 3), ("TPU204", 3), ("TPU205", 1)]:
        assert any(line.startswith(rule)
                   and line.rstrip().endswith(str(n))
                   for line in out.splitlines()), (rule, n, out)
    assert "suppressed inline: 1" in out


def test_cli_list_rules_covers_all_six():
    res = _run_race(["--list-rules"])
    assert res.returncode == 0
    for rule in ["TPU20%d" % i for i in range(6)]:
        assert rule in res.stdout
