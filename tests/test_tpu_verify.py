"""tpu-verify unit tests: per-rule golden fixtures (a minimal traced
program that FIRES each TPU1xx rule and a minimal one that must NOT),
contract waiver semantics, drift-snapshot comparison, finding-ID
stability, and the no-backend import smoke.

The fixtures build TracedProgram records directly from tiny local
functions — the rules are pure functions over (jaxpr, lowered text,
arg leaves), so they are provable without constructing engines.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.analysis.trace import (CollectiveBudget, TraceContract,
                                       TracedProgram, check_program,
                                       compare_snapshot, snapshot_of)
from paddle_tpu.analysis.trace.rules import (check_tpu101, check_tpu102,
                                             check_tpu103, check_tpu104,
                                             check_tpu105, check_tpu106)
from paddle_tpu.analysis.findings import assign_ids

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def trace_prog(fn, args, contract, mp=1, num_layers=1):
    """Build a TracedProgram for a fixture fn exactly the way the
    harvester does (make_jaxpr + jit(...).lower with the contract's
    donation)."""
    closed = jax.make_jaxpr(fn)(*args)
    lowered = jax.jit(
        fn, donate_argnums=contract.donate_argnums).lower(*args)
    donated = sum(len(jax.tree_util.tree_leaves(args[i]))
                  for i in contract.donate_argnums)
    leaves = [(jax.tree_util.keystr(p), leaf) for p, leaf in
              jax.tree_util.tree_flatten_with_path(args)[0]]
    return TracedProgram(
        contract=contract, config="fixture", mp=mp,
        num_layers=num_layers, jaxpr=closed,
        lowered_text=lowered.as_text(), donated_leaves=donated,
        arg_leaves=leaves)


def _contract(**kw):
    kw.setdefault("name", "fixture_step")
    kw.setdefault("declared_at", "tests/test_tpu_verify.py")
    return TraceContract(**kw)


def _mesh(n=2):
    return jax.sharding.Mesh(np.array(jax.devices()[:n]), ("mp",))


# -- TPU101 donation-actually-applied -----------------------------------

def test_tpu101_positive_dropped_alias():
    """Donating a buffer whose 'updated' output changed dtype: jax
    silently drops the alias (the installed JAX says nothing at
    lowering) — the rule turns that into a failure."""
    def step(pool, tok):
        return tok.sum(), (pool + 1.0).astype(jnp.bfloat16)

    c = _contract(donate_argnums=(0,))
    prog = trace_prog(step, (jnp.zeros((4, 8)), jnp.ones((3,))), c)
    found = check_tpu101(prog)
    assert [f.rule for f in found] == ["TPU101"]
    assert "donation was dropped" in found[0].message


def test_tpu101_negative_pinned_alias():
    def step(pool, tok):
        return tok.sum(), pool + 1.0

    c = _contract(donate_argnums=(0,))
    prog = trace_prog(step, (jnp.zeros((4, 8)), jnp.ones((3,))), c)
    assert prog.lowered_text.count("tf.aliasing_output") == 1
    assert check_tpu101(prog) == []


def test_tpu101_skipped_without_declared_donation():
    def step(pool):
        return pool * 2.0

    prog = trace_prog(step, (jnp.zeros((4,)),), _contract())
    assert check_tpu101(prog) == []


# -- TPU102 baked-large-constant ----------------------------------------

def test_tpu102_positive_closure_captured_weight():
    baked = jnp.asarray(np.ones((64, 64), np.float32))   # 16 KiB

    def step(x):
        return x @ baked

    prog = trace_prog(step, (jnp.ones((2, 64)),),
                      _contract(max_const_bytes=4096))
    found = check_tpu102(prog)
    assert [f.rule for f in found] == ["TPU102"]
    assert "16384 bytes" in found[0].message


def test_tpu102_negative_weight_as_argument():
    def step(x, w):
        return x @ w

    prog = trace_prog(
        step, (jnp.ones((2, 64)), jnp.ones((64, 64))),
        _contract(max_const_bytes=4096))
    assert check_tpu102(prog) == []


# -- TPU103 accumulation-dtype ------------------------------------------

def test_tpu103_positive_bf16_accumulation():
    def step(a, b):
        # jnp.sum auto-upcasts bf16 computation, so the genuine
        # narrow-accumulation hazard is raw lax usage: this reduce
        # specializes to a bf16 reduce_sum
        return a @ b, jax.lax.reduce(b, np.array(0, "bfloat16"),
                                     jax.lax.add, (0, 1))

    prog = trace_prog(
        step, (jnp.ones((4, 8), jnp.bfloat16),
               jnp.ones((8, 4), jnp.bfloat16)), _contract())
    rules = sorted(f.message.split(" ")[0] for f in check_tpu103(prog))
    assert rules == ["dot_general", "reduce_sum"]


def test_tpu103_negative_fp32_accumulation():
    def step(a, b):
        d = jnp.einsum("ij,jk->ik", a, b,
                       preferred_element_type=jnp.float32)
        return d, jnp.sum(b, dtype=jnp.float32)

    prog = trace_prog(
        step, (jnp.ones((4, 8), jnp.bfloat16),
               jnp.ones((8, 4), jnp.bfloat16)), _contract())
    assert check_tpu103(prog) == []


def test_tpu103_fp32_operands_never_flagged():
    def step(a, b):
        return a @ b

    prog = trace_prog(step, (jnp.ones((4, 8)), jnp.ones((8, 4))),
                      _contract())
    assert check_tpu103(prog) == []


def _jaxpr_prog(fn, args, contract=None):
    """TracedProgram from make_jaxpr alone (no lowering) — for
    fixtures whose exotic dtype combinations the CPU backend need not
    compile; TPU103 reads only the jaxpr."""
    return TracedProgram(
        contract=contract or _contract(), config="fixture", mp=1,
        num_layers=1, jaxpr=jax.make_jaxpr(fn)(*args),
        lowered_text="", donated_leaves=0)


def _int8_dot(a, b, accum):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=accum)


def test_tpu103_int8_positive_narrow_accumulation():
    """The quantized-serving contract (ISSUE 11): an int8 dot_general
    accumulating in bf16 — or staying int8 — fires; quantization
    already spent the narrow bits once, the accumulator must not
    spend them again."""
    a = jnp.ones((4, 8), jnp.int8)
    b = jnp.ones((8, 4), jnp.int8)
    found = check_tpu103(_jaxpr_prog(
        lambda x, y: _int8_dot(x, y, jnp.bfloat16), (a, b)))
    assert [f.rule for f in found] == ["TPU103"]
    assert "int8/int8" in found[0].message \
        and "bfloat16" in found[0].message
    found = check_tpu103(_jaxpr_prog(
        lambda x, y: _int8_dot(x, y, None), (a, b)))  # stays int8
    assert [f.rule for f in found] == ["TPU103"]


def test_tpu103_int8_negative_wide_accumulation():
    """int8 operands accumulating in fp32 (the engine's dequantized
    matmuls' pinned policy) or exact int32 pass."""
    a = jnp.ones((4, 8), jnp.int8)
    b = jnp.ones((8, 4), jnp.int8)
    for accum in (jnp.float32, jnp.int32):
        prog = _jaxpr_prog(lambda x, y: _int8_dot(x, y, accum), (a, b))
        assert check_tpu103(prog) == [], accum
    # int32 token ids are NOT narrow — reductions over them are fine
    ids = jnp.ones((16,), jnp.int32)
    assert check_tpu103(_jaxpr_prog(lambda x: jnp.sum(x), (ids,))) \
        == []


# -- TPU104 collective-budget -------------------------------------------

def _gather_fn(n_gathers):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def body(x):
        for _ in range(n_gathers):
            x = jax.lax.all_gather(x, "mp", axis=0,
                                   tiled=True).reshape(2, -1)[0]
        return x

    return shard_map(body, mesh=_mesh(), in_specs=(P("mp"),),
                     out_specs=P("mp"), check_vma=False)


def test_tpu104_positive_budget_exceeded():
    c = _contract(collective_budget=CollectiveBudget(
        fixed=(("all_gather", 1),)))
    prog = trace_prog(_gather_fn(2), (jnp.ones((4,)),), c, mp=2)
    found = check_tpu104(prog)
    assert [f.rule for f in found] == ["TPU104"]
    assert "all_gather appears 2x" in found[0].message \
        and "allowed 1" in found[0].message


def test_tpu104_negative_within_budget():
    c = _contract(collective_budget=CollectiveBudget(
        fixed=(("all_gather", 1),)))
    prog = trace_prog(_gather_fn(1), (jnp.ones((4,)),), c, mp=2)
    assert check_tpu104(prog) == []


def test_tpu104_unsharded_step_allows_no_collectives():
    """At mp=1 the budget is zero regardless of the declaration."""
    c = _contract(collective_budget=CollectiveBudget(
        fixed=(("all_gather", 8),)))
    prog = trace_prog(_gather_fn(1), (jnp.ones((4,)),), c, mp=1)
    found = check_tpu104(prog)
    assert [f.rule for f in found] == ["TPU104"]
    assert "unsharded steps run no collectives" in found[0].message


def test_tpu104_per_layer_budget_scales_with_layers():
    c = _contract(collective_budget=CollectiveBudget(
        per_layer=(("all_gather", 1),)))
    prog = trace_prog(_gather_fn(3), (jnp.ones((4,)),), c, mp=2,
                      num_layers=3)
    assert check_tpu104(prog) == []
    prog.num_layers = 2
    assert len(check_tpu104(prog)) == 1


# -- TPU105 trace-key instability ---------------------------------------

def test_tpu105_positive_python_scalar_and_weak_leaf():
    def step(x, s):
        return x * s

    prog = trace_prog(step, (jnp.ones((4,)), 2.5), _contract())
    found = check_tpu105(prog)
    assert [f.rule for f in found] == ["TPU105"]
    assert "python float" in found[0].message
    # the weak-typed-array branch: a scalar laundered through
    # jnp.asarray keeps weak_type=True and must still fire
    weak = jnp.asarray(2.5)
    assert weak.aval.weak_type
    prog = trace_prog(step, (jnp.ones((4,)), weak), _contract())
    found = check_tpu105(prog)
    assert [f.rule for f in found] == ["TPU105"]
    assert "weak-typed leaf" in found[0].message


def test_tpu105_negative_strong_typed_args():
    def step(x, s):
        return x * s

    prog = trace_prog(
        step, (jnp.ones((4,)), jnp.float32(2.5)), _contract())
    assert check_tpu105(prog) == []


# -- TPU106 host-callback-in-compiled-step ------------------------------

def test_tpu106_positive_pure_callback():
    def step(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) * 2,
            jax.ShapeDtypeStruct((4,), jnp.float32), x)

    prog = trace_prog(step, (jnp.ones((4,)),), _contract())
    found = check_tpu106(prog)
    assert [f.rule for f in found] == ["TPU106"]
    assert "pure_callback" in found[0].message


def test_tpu106_negative_pure_program():
    def step(x):
        return x * 2.0

    prog = trace_prog(step, (jnp.ones((4,)),), _contract())
    assert check_tpu106(prog) == []


def test_tpu106_contract_opt_in_allows_callbacks():
    def step(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) * 2,
            jax.ShapeDtypeStruct((4,), jnp.float32), x)

    prog = trace_prog(step, (jnp.ones((4,)),),
                      _contract(allow_host_callbacks=True))
    assert check_tpu106(prog) == []


# -- waivers, IDs, drift snapshot ---------------------------------------

def test_contract_waiver_suppresses_with_justification():
    def step(a, b):
        return a @ b

    c = _contract(waive=(("TPU103", "fixture: proving waiver "
                          "plumbing, not a real accumulation"),))
    prog = trace_prog(
        step, (jnp.ones((4, 8), jnp.bfloat16),
               jnp.ones((8, 4), jnp.bfloat16)), c)
    found = check_program(prog)
    tpu103 = [f for f in found if f.rule == "TPU103"]
    assert tpu103 and all(f.suppressed for f in tpu103)


def test_contract_waiver_requires_justification():
    c = _contract(waive=(("TPU103", "   "),))
    with pytest.raises(ValueError, match="justification"):
        c.waived("TPU103")


def test_finding_ids_stable_across_reruns():
    def step(x, s):
        return x * s

    def one():
        prog = trace_prog(step, (jnp.ones((4,)), 2.5), _contract())
        return assign_ids(check_tpu105(prog))[0].id

    assert one() == one()


def test_snapshot_drift_and_stale_detection():
    def step(pool, x):
        return x.sum(), pool + 1.0

    c = _contract(donate_argnums=(0,))
    prog = trace_prog(step, (jnp.zeros((4, 8)), jnp.ones((3,))), c)
    base = snapshot_of([prog])
    drift, stale = compare_snapshot([prog], base)
    assert drift == [] and stale == []
    # any op-count change fails loudly
    mutated = {k: dict(v, ops=dict(v["ops"], add=99))
               for k, v in base.items()}
    drift, _ = compare_snapshot([prog], mutated)
    assert [f.rule for f in drift] == ["TPU100"]
    assert "drifted" in drift[0].message
    # a program missing from the baseline fails; a baseline entry no
    # current program matches is reported stale
    drift, stale = compare_snapshot([prog], {"ghost[cfg]": {}})
    assert [f.rule for f in drift] == ["TPU100"]
    assert "no TRACE_BASELINE.json entry" in drift[0].message
    assert stale == ["ghost[cfg]"]


def test_tpu100_drift_is_never_grandfatherable():
    """A TPU100 finding's stable ID hashes the program key, not the
    drift content — so a findings-baseline entry for it would mask
    every FUTURE drift of that program too. The baseline application
    must refuse to honor such an entry (it surfaces as stale), and
    the drift finding stays live."""
    from paddle_tpu.analysis.trace import (TraceResult,
                                           apply_findings_baseline)

    def step(pool, x):
        return x.sum(), pool + 1.0

    c = _contract(donate_argnums=(0,))
    prog = trace_prog(step, (jnp.zeros((4, 8)), jnp.ones((3,))), c)
    base_snap = snapshot_of([prog])
    mutated = {k: dict(v, const_bytes=v["const_bytes"] + 1)
               for k, v in base_snap.items()}
    drift, _ = compare_snapshot([prog], mutated)
    res = TraceResult()
    res.findings = assign_ids(drift + check_tpu105(
        trace_prog(step, (jnp.zeros((4, 8)), 1.0), c)))
    fake_baseline = {f.id: {"id": f.id, "justification": "x" * 20}
                     for f in res.findings}
    stale = apply_findings_baseline(res, fake_baseline)
    tpu100 = [f for f in res.findings if f.rule == "TPU100"]
    tpu105 = [f for f in res.findings if f.rule == "TPU105"]
    assert tpu100 and not any(f.baselined for f in tpu100)
    assert tpu105 and all(f.baselined for f in tpu105)
    assert [i for i in stale] == [f.id for f in tpu100]
    assert tpu100[0] in res.new_findings()


def test_trace_import_has_no_backend_init():
    """ISSUE satellite: importing analysis.trace (and the contract-
    declaring builder modules) must not initialize a JAX backend —
    only invoking harvest may."""
    code = (
        "import paddle_tpu.analysis.trace as T\n"
        "import paddle_tpu.inference.engine\n"
        "import paddle_tpu.ops.paged_attention\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, 'import initialized a backend'\n"
        "assert len(T.registered_contracts()) == 5\n"
        "assert len(T.all_trace_rule_ids()) == 7\n"
        "print('TRACE_SMOKE_OK')\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "TRACE_SMOKE_OK" in res.stdout
