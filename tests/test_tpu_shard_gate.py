"""Tier-1 tpu-shard gate: the full 38-program harvest runs self-clean
against the committed SHARD_BASELINE.json through the real CLI, the
two flagship rules (TPU301 undeclared-resharding, TPU302
replicated-large-buffer) are proven against deliberately broken
programs built on a REAL mp=2 engine (so the gate's green is known to
be falsifiable), the per-axis budget table in jit.introspect is pinned
to the live class surfaces it claims to describe, and the four
analysis CLIs' rule namespaces stay mutually disjoint end to end.
"""
import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.analysis.trace as T
from paddle_tpu.analysis.shard.core import DEFAULT_SHARD_BASELINE
from paddle_tpu.analysis.shard.model import build_record, eval_payload
from paddle_tpu.analysis.shard.rules import check_tpu301, check_tpu302
from paddle_tpu.jit import introspect

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CLI_TOOLS = ("tpu_lint", "tpu_verify", "tpu_race", "tpu_shard")


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.fixture(scope="module")
def tiny_mp2_engine():
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import GenerationEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig.tiny(vocab=64, hidden=32,
                                          layers=2, heads=4, seq=32))
    model.eval()
    return GenerationEngine(model, num_slots=2, block_size=8,
                            attention_backend="dense", mp_degree=2,
                            donate=True)


def _decode_args(eng):
    S, MB = eng.num_slots, eng.max_blocks
    return (eng._state_arrays(), eng.cache.kpool, eng.cache.vpool,
            jnp.asarray(np.zeros((S, 1), np.int32)),
            jnp.asarray(np.zeros(S, np.int32)),
            jnp.asarray(np.zeros((S, MB), np.int32)))


def _decode_prog(eng, fn, geometry=None):
    from paddle_tpu.analysis.trace.harvest import _geometry

    args = _decode_args(eng)
    return T.TracedProgram(
        contract=T.get_contract("engine_decode_step"),
        config="dense,K=0,mp=2", mp=2, num_layers=2,
        jaxpr=jax.make_jaxpr(fn)(*args), lowered_text="",
        donated_leaves=0,
        geometry=geometry or _geometry(eng, 2, eng.num_slots))


def test_cli_acceptance_command_exits_zero():
    """THE gate, and the ISSUE acceptance command verbatim: the CLI
    harvests the full contract matrix and runs every TPU3xx rule plus
    the byte-drift comparison self-clean against the committed
    SHARD_BASELINE.json."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpu_shard.py"),
         os.path.join(REPO, "paddle_tpu")],
        env=_env(), capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "tpu-shard clean: 38 programs" in res.stdout


def test_shard_baseline_is_committed_and_covers_the_matrix():
    """The committed snapshot has one entry per harvested program:
    every sharded (mp=2) engine step moves bytes over 'mp' only, in
    the three declared kinds; every mp=1 / conv / COW program pins an
    EMPTY axes map (growing a collective where none existed is drift
    too). The CLI acceptance test above proves the live harvest
    matches these totals exactly."""
    with open(DEFAULT_SHARD_BASELINE) as f:
        snap = json.load(f)["programs"]
    assert len(snap) == 38
    moving = {k for k, v in snap.items() if v["axes"]}
    assert len(moving) == 12
    for key in moving:
        assert "mp=2" in key, key
        assert set(snap[key]["axes"]) == {"mp"}
        assert set(snap[key]["axes"]["mp"]) <= \
            {"all_gather", "psum", "pmax"}
        for v in snap[key]["axes"]["mp"].values():
            assert v["count"] > 0 and v["moved_bytes"] > 0
    # the COW copy is sharded but collective-free; conv and mp=1
    # programs have no mesh at all
    for key in set(snap) - moving:
        assert "mp=2" not in key or key.startswith("engine_cow_copy")


def test_tpu301_fires_on_an_extra_all_gather(tiny_mp2_engine):
    """Deliberate break #1: one accidental extra all-gather appended
    to the mp=2 decode step busts the per-axis count (9 = 4/layer x 2
    layers + 1 fixed) and TPU301 names the axis; the real step — with
    its live geometry, so the BYTE caps are exercised too — passes."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    eng = tiny_mp2_engine
    extra = shard_map(
        lambda t: jax.lax.all_gather(t, "mp", axis=0, tiled=True),
        mesh=eng.mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False)

    def broken_step(*a):
        nxt, kp, vp = eng._decode_pure(*a)
        return extra(nxt)[: nxt.shape[0]], kp, vp

    found = check_tpu301(build_record(_decode_prog(eng, broken_step)))
    assert [f.rule for f in found] == ["TPU301"]
    assert "all_gather crosses axis 'mp' 10x" in found[0].message
    assert "allowed 9" in found[0].message
    clean = build_record(_decode_prog(eng, eng._decode_pure))
    assert check_tpu301(clean) == []
    # the clean step's byte totals sit under the budget caps with the
    # REAL payload bounds evaluated (not just vacuously skipped)
    assert clean.axis_totals["mp"]["all_gather"]["moved_bytes"] > 0


def test_tpu302_fires_when_a_pool_lowers_replicated(tiny_mp2_engine):
    """Deliberate break #2: pinning a paged KV pool's in_sharding to
    replicated while the declared layout truth (pool_pspec) says
    head-sharded — every chip would silently pay mp x its HBM share.
    The engine's own sharding passes the same check."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    eng = tiny_mp2_engine
    # a host-side stand-in with the pool's exact geometry (the real
    # committed pool already carries its sharding, which jit would
    # rightly refuse to override)
    pool = np.zeros(eng.cache.kpool.shape, eng.cache.kpool.dtype)
    declared = (tuple(eng.cache.pool_pspec()),)

    def prog(sharding):
        lowered = jax.jit(lambda k: k + 1.0,
                          in_shardings=(sharding,)).lower(pool)
        return T.TracedProgram(
            contract=T.get_contract("engine_decode_step"),
            config="dense,K=0,mp=2", mp=2, num_layers=2,
            jaxpr=jax.make_jaxpr(lambda k: k + 1.0)(pool),
            lowered_text=lowered.as_text(), donated_leaves=0,
            declared_in_specs=declared)

    broken = prog(NamedSharding(eng.mesh, P()))
    found = check_tpu302(build_record(broken))
    assert [f.rule for f in found] == ["TPU302"]
    assert "lowered replicated" in found[0].message
    fixed = prog(NamedSharding(eng.mesh, eng.cache.pool_pspec()))
    rec = build_record(fixed)
    assert check_tpu302(rec) == []
    from paddle_tpu.analysis.shard.rules import check_tpu303
    assert check_tpu303(rec) == []


def test_axis_budget_table_pins_real_surfaces(tiny_mp2_engine):
    """The ONE per-axis budget table (introspect) is what the model
    module exports, what the engine contracts resolve to, and its
    rows describe the live mesh: axis 'mp' on ICI, kinds that are
    real collective primitives, payload bounds that evaluate to
    positive byte counts over the real harvest geometry — and the
    merged count view reproduces the legacy TPU104 budget exactly."""
    from paddle_tpu.analysis.trace.contracts import resolve_budget
    from paddle_tpu.analysis.trace.harvest import _geometry
    from paddle_tpu.analysis.trace.rules import COLLECTIVE_PRIMS
    from paddle_tpu.models import gpt

    budget = introspect.GPT_SERVING_AXIS_BUDGET
    assert gpt.GPT_SERVING_COLLECTIVES is budget
    for step in ("engine_decode_step", "engine_verify_step",
                 "engine_prefill_chunk"):
        assert resolve_budget(T.get_contract(step)) is budget
    assert budget.axis_names() == ("mp",)
    assert budget.link_of("mp") == "ici"
    assert budget.slow_axes() == ()
    assert set(budget.kinds()) <= COLLECTIVE_PRIMS
    geom = _geometry(tiny_mp2_engine, 2, tiny_mp2_engine.num_slots)
    for kind in budget.kinds():
        bounds = budget.payload_bounds("mp", kind)
        assert bounds, kind
        assert all(eval_payload(b, geom) > 0 for b in bounds), kind
    # the TPU104 count surface, unchanged through the refactor: 9
    # gathers (4/layer x 2 + 1 lm-head), 1 psum, 2 pmax at L=2
    assert budget.allowed("all_gather", 2) == 9
    assert budget.allowed("psum", 2) == 1
    assert budget.allowed("pmax", 2) == 2
    assert dict(budget.per_layer) == {"all_gather": 4, "pmax": 1}
    assert dict(budget.fixed) == {"all_gather": 1, "psum": 1}


def test_per_token_contracts_mark_the_decode_loop():
    """TPU305's latency classification rides the contract: the
    decode/verify steps (the per-generated-token host loop body) are
    per_token; prefills and the COW copy run per admission."""
    for step, hot in (("engine_decode_step", True),
                      ("engine_verify_step", True),
                      ("engine_prefill_chunk", False),
                      ("engine_cow_copy", False)):
        assert T.get_contract(step).per_token is hot, step


@pytest.fixture(scope="module")
def cli_rule_ids():
    """rule-id set per analysis CLI, straight from `--list-rules`."""
    out = {}
    for tool in _CLI_TOOLS:
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", tool + ".py"),
             "--list-rules"],
            env=_env(), capture_output=True, text=True, timeout=300)
        assert res.returncode == 0, (tool, res.stdout + res.stderr)
        ids = {line.split()[0] for line in res.stdout.splitlines()
               if line.strip().startswith("TPU")}
        assert ids, tool
        out[tool] = ids
    return out


@pytest.mark.parametrize("a,b",
                         list(itertools.combinations(_CLI_TOOLS, 2)))
def test_cli_rule_namespaces_mutually_disjoint(cli_rule_ids, a, b):
    """End-to-end namespace disjointness: what the four CLIs actually
    ADVERTISE (not just the registries) never collides — a suppression
    or baseline entry can always be attributed to exactly one tier."""
    assert not (cli_rule_ids[a] & cli_rule_ids[b]), (a, b)


def test_tpu_shard_advertises_the_tpu3xx_block(cli_rule_ids):
    ids = cli_rule_ids["tpu_shard"]
    assert ids == {"TPU300", "TPU301", "TPU302", "TPU303", "TPU304",
                   "TPU305"}
