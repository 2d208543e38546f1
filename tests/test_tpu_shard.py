"""tpu-shard unit tests: per-rule golden fixtures (a minimal traced
program that FIRES each TPU30x rule and a minimal one that must NOT,
with the exact finding anchor file:line asserted), byte-drift snapshot
round-trip + stale detection, finding-ID stability under line shifts,
suppression-tag disjointness against the sibling tiers (both
directions), the CLI's json/stats modes through its program-injection
seam, and the no-backend import smoke.

Fixtures build TracedProgram records from tiny local shard_map
functions exactly the way the harvester does; contracts anchor at the
committed fixture files under tests/fixtures/tpu_shard/ so the
file-level suppression scan reads real text.
"""
import json
import os
import subprocess
import sys

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.analysis.findings import (Finding, assign_ids,
                                          parse_suppressions)
from paddle_tpu.analysis.shard import (analyze_programs,
                                       compare_snapshot,
                                       load_shard_baseline,
                                       snapshot_of,
                                       write_shard_baseline)
from paddle_tpu.analysis.shard.cli import main as shard_main
from paddle_tpu.analysis.shard.model import (build_record,
                                             parse_main_shardings)
from paddle_tpu.analysis.shard.rules import (check_tpu301, check_tpu302,
                                             check_tpu303, check_tpu304,
                                             check_tpu305)
from paddle_tpu.analysis.trace.contracts import (CollectiveBudget,
                                                 TraceContract)
from paddle_tpu.analysis.trace.rules import TracedProgram
from paddle_tpu.jit.introspect import AxisCollectiveBudget

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CLEAN_AT = "tests/fixtures/tpu_shard/clean_step.py"
BROKEN_AT = "tests/fixtures/tpu_shard/broken_step.py"
SUPPRESSED_AT = "tests/fixtures/tpu_shard/suppressed_step.py"
FOREIGN_AT = "tests/fixtures/tpu_shard/foreign_tags.py"

#: fixture serving geometry the payload bounds evaluate over
GEOM = dict(tokens=2, hidden=8)


def _budget(axes=(("mp", "ici"),), entries=(
        ("mp", "all_gather", 0, 1, "tokens * hidden * 4"),
        ("mp", "psum", 0, 1, "tokens * hidden * 4"))):
    return AxisCollectiveBudget(axes=axes, entries=entries)


def _contract(**kw):
    kw.setdefault("name", "fixture_step")
    kw.setdefault("declared_at", BROKEN_AT)
    kw.setdefault("collective_budget", _budget())
    return TraceContract(**kw)


def _mesh(axis="mp", n=2):
    return jax.sharding.Mesh(np.array(jax.devices()[:n]), (axis,))


def shard_prog(fn, args, contract, mp=2, num_layers=1,
               in_shardings=None, out_shardings=None, declared_in=None,
               declared_out=None, geometry=GEOM):
    """Build a TracedProgram the way the harvester does — make_jaxpr +
    jit(...).lower — plus the declared-layout/geometry fields the
    tpu-shard tier consumes."""
    kw = {}
    if in_shardings is not None:
        kw["in_shardings"] = in_shardings
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    lowered = jax.jit(fn, **kw).lower(*args)
    return TracedProgram(
        contract=contract, config="fixture", mp=mp,
        num_layers=num_layers, jaxpr=jax.make_jaxpr(fn)(*args),
        lowered_text=lowered.as_text(), donated_leaves=0,
        declared_in_specs=declared_in, declared_out_specs=declared_out,
        geometry=dict(geometry) if geometry else None)


def _gather_fn(n_gathers, axis="mp"):
    def body(x):
        for _ in range(n_gathers):
            x = jax.lax.all_gather(x, axis, axis=0,
                                   tiled=True).reshape(2, -1)[0]
        return x

    return shard_map(body, mesh=_mesh(axis), in_specs=(P(axis),),
                     out_specs=P(axis), check_vma=False)


def _rec(fn, args, contract, **kw):
    return build_record(shard_prog(fn, args, contract, **kw))


# -- TPU301 undeclared-resharding ---------------------------------------

def test_tpu301_positive_count_exceeded():
    prog = shard_prog(_gather_fn(2), (jnp.ones((4,)),), _contract())
    found = check_tpu301(build_record(prog))
    assert [(f.rule, f.path, f.line) for f in found] \
        == [("TPU301", BROKEN_AT, 1)]
    assert "all_gather crosses axis 'mp' 2x" in found[0].message \
        and "allowed 1" in found[0].message


def test_tpu301_positive_bytes_exceed_payload_cap():
    """Count inside the budget but the moved bytes outgrow the
    declared payload bound: one 8-byte-shard gather against a
    2-byte bound (cap = 1 x 2 x 1 peer)."""
    c = _contract(collective_budget=_budget(entries=(
        ("mp", "all_gather", 0, 1, "tokens"),)))
    found = check_tpu301(_rec(_gather_fn(1), (jnp.ones((4,)),), c))
    assert [(f.rule, f.path, f.line) for f in found] \
        == [("TPU301", BROKEN_AT, 1)]
    assert "moves 8 bytes" in found[0].message \
        and "caps 2" in found[0].message


def test_tpu301_positive_undeclared_axis():
    c = _contract(collective_budget=_budget(
        axes=(("dp", "ici"),), entries=()))
    found = check_tpu301(_rec(_gather_fn(1), (jnp.ones((4,)),), c))
    assert [f.rule for f in found] == ["TPU301"]
    assert "mesh axis 'mp' which the budget does not declare" \
        in found[0].message


def test_tpu301_positive_no_axis_budget():
    """A legacy count-only CollectiveBudget declares no axes — every
    collective is an undeclared resharding under the per-axis gate."""
    c = _contract(collective_budget=CollectiveBudget(
        fixed=(("all_gather", 1),)))
    found = check_tpu301(_rec(_gather_fn(1), (jnp.ones((4,)),), c))
    assert [f.rule for f in found] == ["TPU301"]
    assert "declares no per-axis collective budget" in found[0].message


def test_tpu301_negative_within_budget():
    c = _contract(declared_at=CLEAN_AT)
    rec = _rec(_gather_fn(1), (jnp.ones((4,)),), c)
    assert check_tpu301(rec) == []
    # and per-layer budgets scale with the layer count
    c = _contract(declared_at=CLEAN_AT, collective_budget=_budget(
        entries=(("mp", "all_gather", 1, 0, "tokens * hidden * 4"),)))
    rec = _rec(_gather_fn(3), (jnp.ones((4,)),), c, num_layers=3)
    assert check_tpu301(rec) == []


def test_tpu301_negative_no_collectives():
    rec = _rec(lambda x: x * 2.0, (jnp.ones((4,)),),
               _contract(declared_at=CLEAN_AT))
    assert check_tpu301(rec) == []


# -- TPU302 replicated-large-buffer -------------------------------------

def test_tpu302_positive_sharded_plan_lowered_replicated():
    """A 4 KiB buffer the declared layout shards over mp but the
    lowering pinned `{replicated}` — every chip pays full HBM."""
    mesh = _mesh()
    prog = shard_prog(
        lambda w: w + 1.0, (jnp.ones((16, 64)),), _contract(),
        in_shardings=(NamedSharding(mesh, P()),),
        declared_in=(("mp", None),))
    found = check_tpu302(build_record(prog))
    assert [(f.rule, f.path, f.line) for f in found] \
        == [("TPU302", BROKEN_AT, 1)]
    assert "4096 bytes" in found[0].message \
        and "declared P('mp', None)" in found[0].message \
        and "lowered replicated" in found[0].message


def test_tpu302_negative_lowered_sharded_as_declared():
    mesh = _mesh()
    prog = shard_prog(
        lambda w: w + 1.0, (jnp.ones((16, 64)),),
        _contract(declared_at=CLEAN_AT),
        in_shardings=(NamedSharding(mesh, P("mp")),),
        declared_in=(("mp", None),))
    rec = build_record(prog)
    assert check_tpu302(rec) == []
    assert check_tpu303(rec) == []     # and the layout matches too


def test_tpu302_negative_small_buffer_replicates_by_design():
    mesh = _mesh()
    prog = shard_prog(
        lambda w: w + 1.0, (jnp.ones((4,)),),      # 16 bytes
        _contract(declared_at=CLEAN_AT),
        in_shardings=(NamedSharding(mesh, P()),), declared_in=((),))
    assert check_tpu302(build_record(prog)) == []


# -- TPU303 pspec-layout drift ------------------------------------------

def test_tpu303_positive_sharded_on_wrong_dim():
    mesh = _mesh()
    prog = shard_prog(
        lambda w: w + 1.0, (jnp.ones((16, 64)),), _contract(),
        in_shardings=(NamedSharding(mesh, P(None, "mp")),),
        declared_in=(("mp", None),))
    found = check_tpu303(build_record(prog))
    assert [(f.rule, f.path, f.line) for f in found] \
        == [("TPU303", BROKEN_AT, 1)]
    assert "expects split 2x1" in found[0].message \
        and "lowered split 1x2" in found[0].message


def test_tpu303_positive_declared_replicated_lowered_sharded():
    mesh = _mesh()
    prog = shard_prog(
        lambda w: w + 1.0, (jnp.ones((16, 64)),), _contract(),
        in_shardings=(NamedSharding(mesh, P("mp")),),
        declared_in=((),))
    found = check_tpu303(build_record(prog))
    assert [f.rule for f in found] == ["TPU303"]
    assert "expects replicated" in found[0].message


def test_tpu303_negative_plan_matches_lowering():
    mesh = _mesh()
    prog = shard_prog(
        lambda w, s: w * s, (jnp.ones((16, 64)), jnp.ones((64,))),
        _contract(declared_at=CLEAN_AT),
        in_shardings=(NamedSharding(mesh, P("mp")),
                      NamedSharding(mesh, P())),
        declared_in=(("mp", None), ()))
    assert check_tpu303(build_record(prog)) == []


def test_tpu303_skips_undeclared_and_host_leaves():
    prog = shard_prog(
        lambda w, t: w * t, (jnp.ones((16, 64)), jnp.ones((64,))),
        _contract(declared_at=CLEAN_AT),
        declared_in=(None, None))     # host args: no declared layout
    assert check_tpu303(build_record(prog)) == []


# -- TPU304 axis-unsafe collective shape --------------------------------

def test_tpu304_positive_payload_scales_with_mesh():
    """The gathered GLOBAL payload (16 bytes) lands above a bound
    declared over serving geometry only (tokens = 2 bytes) — the
    signature of a payload that grows with axis size."""
    c = _contract(collective_budget=_budget(entries=(
        ("mp", "all_gather", 0, 1, "tokens"),)))
    found = check_tpu304(_rec(_gather_fn(1), (jnp.ones((4,)),), c))
    assert [(f.rule, f.path, f.line) for f in found] \
        == [("TPU304", BROKEN_AT, 1)]
    assert "16-byte global payload" in found[0].message \
        and "declared bound 2" in found[0].message


def test_tpu304_negative_payload_within_bound():
    rec = _rec(_gather_fn(1), (jnp.ones((4,)),),
               _contract(declared_at=CLEAN_AT))
    assert check_tpu304(rec) == []


# -- TPU305 dcn-hostile collective --------------------------------------

def _pp_budget():
    return _budget(axes=(("pp", "dcn"),), entries=(
        ("pp", "all_gather", 0, 1, "tokens * hidden * 4"),))


def test_tpu305_positive_per_token_over_dcn():
    c = _contract(collective_budget=_pp_budget(), per_token=True)
    found = check_tpu305(
        _rec(_gather_fn(1, axis="pp"), (jnp.ones((4,)),), c))
    assert [(f.rule, f.path, f.line) for f in found] \
        == [("TPU305", BROKEN_AT, 1)]
    assert "slow axis 'pp'" in found[0].message \
        and "per-token step" in found[0].message


def test_tpu305_positive_on_device_loop_body():
    def body(x):
        def step(c, _):
            return c + jax.lax.psum(x, "pp"), None
        out, _ = jax.lax.scan(step, x, None, length=2)
        return out

    fn = shard_map(body, mesh=_mesh("pp"), in_specs=(P("pp"),),
                   out_specs=P("pp"), check_vma=False)
    c = _contract(collective_budget=_budget(
        axes=(("pp", "dcn"),),
        entries=(("pp", "psum", 2, 0, "tokens * hidden * 4"),)))
    found = check_tpu305(_rec(fn, (jnp.ones((4,)),), c))
    assert {f.rule for f in found} == {"TPU305"}
    assert "on-device loop body" in found[0].message


def test_tpu305_negative_per_admission_prefill():
    """Same DCN crossing from a per-admission program (per_token
    False, not in a loop): tolerable, TPU305 stays quiet."""
    c = _contract(declared_at=CLEAN_AT,
                  collective_budget=_pp_budget())
    found = check_tpu305(
        _rec(_gather_fn(1, axis="pp"), (jnp.ones((4,)),), c))
    assert found == []


def test_tpu305_negative_fast_ici_axis():
    c = _contract(declared_at=CLEAN_AT, per_token=True)
    rec = _rec(_gather_fn(1), (jnp.ones((4,)),), c)
    assert check_tpu305(rec) == []


# -- TPU300 drift snapshot + parse errors -------------------------------

def _clean_prog():
    return shard_prog(_gather_fn(1), (jnp.ones((4,)),),
                      _contract(declared_at=CLEAN_AT))


def test_shard_baseline_round_trip(tmp_path):
    prog = _clean_prog()
    path = str(tmp_path / "SHARD_BASELINE.json")
    assert write_shard_baseline(path, [build_record(prog)]) == 1
    res = analyze_programs([prog], shard_baseline=path)
    assert res.new_findings() == [] and res.stale_shard_baseline == []


def test_shard_baseline_drift_missing_and_stale():
    prog = _clean_prog()
    rec = build_record(prog)
    base = snapshot_of([rec])
    # exact totals -> clean
    drift, stale = compare_snapshot([rec], base)
    assert drift == [] and stale == []
    # any byte movement fails loudly
    mutated = json.loads(json.dumps(base))
    mutated[rec.key]["axes"]["mp"]["all_gather"]["moved_bytes"] += 8
    drift, _ = compare_snapshot([rec], mutated)
    assert [(f.rule, f.path, f.line) for f in drift] \
        == [("TPU300", CLEAN_AT, 1)]
    assert "drifted" in drift[0].message \
        and "mp/all_gather 1x/16B -> 1x/8B" in drift[0].message
    # a program with no entry fails; a ghost entry is reported stale
    drift, stale = compare_snapshot([rec], {"ghost[cfg]": {"axes": {}}})
    assert [f.rule for f in drift] == ["TPU300"]
    assert "no SHARD_BASELINE.json entry" in drift[0].message
    assert stale == ["ghost[cfg]"]


def test_unparseable_lowering_is_tpu300():
    prog = _clean_prog()
    prog.lowered_text = "not a module"
    prog.declared_in_specs = (("mp",),)
    res = analyze_programs([prog], shard_baseline=None)
    rules = [f.rule for f in res.findings]
    assert "TPU300" in rules
    f = next(f for f in res.findings if f.rule == "TPU300")
    assert "did not parse" in f.message and f.path == CLEAN_AT


def test_tpu300_drift_is_never_grandfatherable():
    """A drift finding's stable ID hashes the program key, not the
    drift content — a findings-baseline entry would mask every FUTURE
    drift too, so analyze_programs refuses to honor one (it surfaces
    stale and the finding stays live)."""
    prog = _clean_prog()
    rec = build_record(prog)
    mutated = json.loads(json.dumps(snapshot_of([rec])))
    mutated[rec.key]["axes"]["mp"]["all_gather"]["count"] += 1
    res = analyze_programs([prog], shard_baseline=mutated)
    drift = [f for f in res.findings if f.rule == "TPU300"]
    assert len(drift) == 1
    baseline = {drift[0].id: {"id": drift[0].id,
                              "justification": "x" * 20}}
    res = analyze_programs([prog], baseline=baseline,
                           shard_baseline=mutated)
    drift = [f for f in res.findings if f.rule == "TPU300"]
    assert drift and not drift[0].baselined
    assert drift[0] in res.new_findings()
    assert res.stale_baseline == sorted(baseline)


def test_findings_baseline_grandfathers_tpu301(tmp_path):
    prog = shard_prog(_gather_fn(2), (jnp.ones((4,)),), _contract())
    res = analyze_programs([prog], shard_baseline=None)
    assert [f.rule for f in res.new_findings()] == ["TPU301"]
    baseline = {f.id: {"id": f.id, "justification": "fixture: " * 3}
                for f in res.new_findings()}
    res = analyze_programs([prog], baseline=baseline,
                           shard_baseline=None)
    assert res.new_findings() == [] \
        and [f.baselined for f in res.findings] == [True]


# -- IDs, suppressions, tag disjointness --------------------------------

def test_finding_ids_stable_under_line_shifts():
    """IDs hash the line-free identity (rule|path|qualname|source|
    occurrence) — moving the anchor line must not orphan a baseline
    entry."""
    def ids(line):
        fs = [Finding(rule="TPU303", path=BROKEN_AT, line=line, col=0,
                      qualname="fixture_step", source="fixture",
                      message="m")]
        return [f.id for f in assign_ids(fs)]

    assert ids(1) == ids(500)
    # and the end-to-end path is deterministic across reruns
    one = analyze_programs([_clean_prog(),
                            shard_prog(_gather_fn(2), (jnp.ones((4,)),),
                                       _contract())],
                           shard_baseline=None)
    two = analyze_programs([shard_prog(_gather_fn(2), (jnp.ones((4,)),),
                                       _contract()), _clean_prog()],
                           shard_baseline=None)
    assert [f.id for f in one.findings] == [f.id for f in two.findings]


def test_inline_suppression_tpu_shard_tag():
    prog = shard_prog(_gather_fn(2), (jnp.ones((4,)),),
                      _contract(declared_at=SUPPRESSED_AT))
    res = analyze_programs([prog], shard_baseline=None)
    tpu301 = [f for f in res.findings if f.rule == "TPU301"]
    assert tpu301 and all(f.suppressed for f in tpu301)
    assert res.new_findings() == []


def test_sibling_tier_tags_do_not_suppress_shard_findings():
    """foreign_tags.py line 1 disables TPU301 under the tpu-lint tag
    (and tpu-race on line 2) — the tpu-shard scan must not honor
    either."""
    prog = shard_prog(_gather_fn(2), (jnp.ones((4,)),),
                      _contract(declared_at=FOREIGN_AT))
    res = analyze_programs([prog], shard_baseline=None)
    assert [f.rule for f in res.new_findings()] == ["TPU301"]


def test_shard_tag_invisible_to_sibling_tiers():
    """Direction two of the disjointness: a `# tpu-shard: disable=`
    line parses under the tpu-shard tag ONLY — the tpu-lint and
    tpu-race parsers must not see it (and vice versa)."""
    src = ("# tpu-shard: disable=TPU301\n"
           "# tpu-lint: disable=TPU019\n"
           "# tpu-race: disable=TPU201\n")
    assert parse_suppressions(src, tag="tpu-shard") == {1: {"TPU301"}}
    assert parse_suppressions(src, tag="tpu-lint") == {2: {"TPU019"}}
    assert parse_suppressions(src, tag="tpu-race") == {3: {"TPU201"}}


def test_contract_waiver_suppresses_shard_rule():
    c = _contract(waive=(("TPU301", "fixture: proving waiver "
                          "plumbing for the shard tier"),))
    prog = shard_prog(_gather_fn(2), (jnp.ones((4,)),), c)
    res = analyze_programs([prog], shard_baseline=None)
    tpu301 = [f for f in res.findings if f.rule == "TPU301"]
    assert tpu301 and all(f.suppressed for f in tpu301)


# -- signature parser ---------------------------------------------------

def test_parse_main_shardings_decodes_counts():
    text = ('module @x { sdy.mesh @mesh = <["dp"=4, "mp"=2]> '
            'func.func public @main('
            '%arg0: tensor<2x9x8x4x8xi8> {sdy.sharding = '
            '#sdy.sharding<@mesh, [{}, {}, {}, {"mp"}, {}]>}, '
            '%arg1: tensor<32x64xf32> {sdy.sharding = '
            '#sdy.sharding<@mesh, [{}, {}]>}, '
            '%arg2: tensor<4xi32>) -> (tensor<2x32xf32>, '
            'tensor<8xbf16> {jax.result_info = "result[1]", '
            'sdy.sharding = #sdy.sharding<@mesh, [{"mp"}]>}) { } }')
    args, results = parse_main_shardings(text)
    assert [(a[0], a[3]) for a in args] == [
        ((2, 9, 8, 4, 8), (1, 1, 1, 2, 1)),
        ((32, 64), ()), ((4,), None)]
    assert args[0][2] == 2 * 9 * 8 * 4 * 8       # i8 bytes
    assert [(r[0], r[3]) for r in results] == [
        ((2, 32), None), ((8,), (2,))]
    assert results[1][2] == 16                   # bf16 bytes


# -- CLI (through the program-injection seam) ---------------------------

def _cli(args, programs, capsys):
    code = shard_main(args, programs=programs)
    out = capsys.readouterr().out
    return code, out


def test_cli_clean_and_finding_exit_codes(capsys, tmp_path):
    clean, broken = _clean_prog(), shard_prog(
        _gather_fn(2), (jnp.ones((4,)),), _contract())
    code, out = _cli(["--shard-baseline", "none"], [clean], capsys)
    assert code == 0 and "tpu-shard clean: 1 programs" in out
    code, out = _cli(["--shard-baseline", "none"], [broken], capsys)
    assert code == 1 and "TPU301" in out


def test_cli_json_and_stats(capsys):
    prog = shard_prog(_gather_fn(2), (jnp.ones((4,)),), _contract())
    code, out = _cli(["--format", "json", "--shard-baseline", "none"],
                     [prog], capsys)
    assert code == 1
    doc = json.loads(out)
    assert [f["rule"] for f in doc["findings"]] == ["TPU301"]
    assert doc["programs"] == [prog.key]
    code, out = _cli(["--stats", "--shard-baseline", "none"], [prog],
                     capsys)
    assert code == 1 and "programs analyzed: 1" in out \
        and "TPU301 undeclared-resharding" in out


def test_cli_shard_baseline_round_trip(capsys, tmp_path):
    prog = _clean_prog()
    path = str(tmp_path / "snap.json")
    code, out = _cli(["--write-shard-baseline", path], [prog], capsys)
    assert code == 0 and "snapshotted 1 programs" in out
    assert set(load_shard_baseline(path)) == {prog.key}
    code, out = _cli(["--shard-baseline", path], [prog], capsys)
    assert code == 0 and "clean" in out
    # drift: same program, one more gather
    drifted = shard_prog(
        _gather_fn(2), (jnp.ones((4,)),),
        _contract(declared_at=CLEAN_AT, collective_budget=_budget(
            entries=(("mp", "all_gather", 0, 2,
                      "tokens * hidden * 4"),))))
    code, out = _cli(["--shard-baseline", path], [drifted], capsys)
    assert code == 1 and "TPU300" in out and "drifted" in out


def test_cli_path_filter_and_usage_errors(capsys):
    progs = [_clean_prog(),
             shard_prog(_gather_fn(2), (jnp.ones((4,)),), _contract())]
    # only the broken program's declaring file selected -> 1 finding
    code, out = _cli([os.path.join(REPO, BROKEN_AT),
                      "--shard-baseline", "none"], progs, capsys)
    assert code == 1 and "TPU301" in out
    # only the clean one -> clean over exactly 1 program
    code, out = _cli([os.path.join(REPO, CLEAN_AT),
                      "--shard-baseline", "none"], progs, capsys)
    assert code == 0 and "clean: 1 programs" in out
    assert shard_main(["definitely/not/a/path.py"], programs=progs) == 2
    assert shard_main(["--baseline", "/nonexistent.json"],
                      programs=progs) == 2
    assert shard_main(["--shard-baseline", "/nonexistent.json"],
                      programs=progs) == 2


def test_cli_list_rules(capsys):
    assert shard_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ("TPU300", "TPU301", "TPU302", "TPU303", "TPU304",
                 "TPU305"):
        assert rule in out


# -- import smoke -------------------------------------------------------

def test_shard_import_has_no_backend_init():
    """Importing the shard tier (and its rule table) must not
    initialize a JAX backend — only the harvest may."""
    code = (
        "import paddle_tpu.analysis.shard as S\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, 'import initialized a backend'\n"
        "assert len(S.SHARD_RULES) == 6\n"
        "assert S.SUPPRESS_TAG == 'tpu-shard'\n"
        "print('SHARD_SMOKE_OK')\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "SHARD_SMOKE_OK" in res.stdout
