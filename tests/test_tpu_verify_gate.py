"""Tier-1 tpu-verify gate: every registered compiled engine program,
abstractly traced over the full {dense,pallas} x K in {0,4} x
mp in {1,2} matrix on CPU, passes its declared trace contract and
matches the committed TRACE_BASELINE.json — and the two flagship
rules (TPU101 donation aliasing, TPU104 collective budget) are proven
against deliberately broken programs, so the gate's green is known to
be falsifiable.

conftest forces --xla_force_host_platform_device_count=8, so the REAL
mp=2 shard_map programs trace on a virtual device mesh.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.analysis.trace as T
from paddle_tpu.jit import introspect

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def matrix_result():
    """One harvest+verify of the full matrix shared by the gate
    assertions (the committed TRACE_BASELINE.json is the default
    drift reference)."""
    return T.verify_matrix()


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


def test_matrix_is_contract_clean(matrix_result):
    """THE gate: any TPU1xx finding (or TRACE_BASELINE drift) on any
    program of the full config matrix fails tier-1. Fix the program,
    or (exceptionally) add a justified waiver/baseline entry."""
    res = matrix_result
    new = res.new_findings()
    assert new == [], "tpu-verify findings:\n" + "\n".join(
        f.render() for f in new)
    # the matrix must actually cover the serving stack: the 16
    # backend/K/kv-divergent decode/verify steps plus the 8 per-
    # (mp, kv_dtype) backend-invariant programs, every contract seen
    # — the kv=int8 half is the PR-11 quantized serving config (int8
    # per-block-scaled KV pools + int8 weights) — plus the 3 PR-13
    # adapter-threaded programs (LORA_CONFIGS: a plain fp mp=1
    # decode + its prefill chunk, and the composed
    # pallas/K=4/mp=2/int8 verify step) — plus the 3 PR-15
    # sampling-threaded programs (SAMPLING_CONFIGS: a plain fp mp=1
    # sampled decode + its sampled prefill chunk, and the composed
    # pallas/K=4/mp=2/int8 rejection-sampling verify step) — plus the
    # 4 PR-14 fused Pallas conv programs (both kernel families x
    # stride) — plus the 4 PR-16 backward programs (the train-mode
    # custom_vjp grad jaxprs, both families x stride; TPU103 must
    # walk the fused dInput/dWeight kernels too)
    assert len(res.programs) == 38
    assert sum(",int8" in p.config for p in res.programs) == 14
    assert sum(",lora" in p.config for p in res.programs) == 3
    assert sum(",sampling" in p.config for p in res.programs) == 3
    assert sum(p.contract.name.startswith("conv_bn_relu")
               for p in res.programs) == 8
    names = {p.contract.name for p in res.programs}
    assert names == {"engine_decode_step", "engine_verify_step",
                     "engine_prefill_chunk",
                     "engine_cow_copy", "conv_bn_relu_1x1",
                     "conv_bn_relu_3x3", "conv_bn_relu_1x1_bwd",
                     "conv_bn_relu_3x3_bwd"}
    assert res.stale_trace_baseline == []


def test_trace_baseline_is_committed_and_exact(matrix_result):
    """The committed TRACE_BASELINE.json matches the live snapshot
    key-for-key and count-for-count (drift would have produced TPU100
    findings above; this pins the file itself)."""
    base = T.load_trace_baseline(T.DEFAULT_TRACE_BASELINE)
    assert base == T.snapshot_of(matrix_result.programs)


def test_engine_consumes_introspect_donation_table(tiny_mp2_engine):
    """ISSUE satellite: donation metadata for the engine steps comes
    from the ONE introspect table both analyzers read — the engine
    must consume it, not restate magic argnums."""
    eng = tiny_mp2_engine
    assert eng._donate_argnums == introspect.ENGINE_STEP_DONATE_ARGNUMS
    for step in ("engine_prefill_chunk", "engine_decode_step",
                 "engine_verify_step"):
        assert introspect.ENGINE_STEP_DONATION[step] == \
            introspect.ENGINE_STEP_DONATE_ARGNUMS
        assert T.get_contract(step).donate_argnums == \
            introspect.ENGINE_STEP_DONATION[step]
    assert T.get_contract("engine_cow_copy").donate_argnums == \
        introspect.ENGINE_COW_DONATE_ARGNUMS
    # and the constants resolve through DONATION_CONSTANTS (TPU004)
    assert introspect.DONATION_CONSTANTS[
        "ENGINE_STEP_DONATE_ARGNUMS"] == (1, 2)
    assert introspect.DONATION_CONSTANTS[
        "ENGINE_COW_DONATE_ARGNUMS"] == (0, 1)


def test_tpu101_fires_when_sharded_donation_is_demoted(tiny_mp2_engine):
    """Deliberate contract break #1 (and the regression test for the
    PR's engine fix): lowering the mp=2 decode step WITHOUT the
    engine's explicit out_shardings demotes donate_argnums to
    best-effort `jax.buffer_donor` markers — no pinned aliases, the
    paged pools may silently double. TPU101 must fail that program;
    the engine's own jit (WITH out_shardings) must pass it."""
    eng = tiny_mp2_engine
    args = _decode_args(eng)
    contract = T.get_contract("engine_decode_step")

    def prog_from(lowered_text):
        return T.TracedProgram(
            contract=contract, config="dense,K=0,mp=2", mp=2,
            num_layers=2, jaxpr=jax.make_jaxpr(eng._decode_pure)(*args),
            lowered_text=lowered_text, donated_leaves=2)

    # the pre-fix engine shape: donation declared, out_shardings inferred
    broken = jax.jit(eng._decode_pure,
                     donate_argnums=(1, 2)).lower(*args).as_text()
    assert broken.count("tf.aliasing_output") == 0
    assert broken.count("jax.buffer_donor") == 2
    from paddle_tpu.analysis.trace.rules import check_tpu101

    found = check_tpu101(prog_from(broken))
    assert [f.rule for f in found] == ["TPU101"]
    assert "demoted" in found[0].message

    # the engine's real jit: pinned aliases, rule passes
    fixed = eng._decode.lower(*args).as_text()
    assert fixed.count("tf.aliasing_output") == 2
    assert fixed.count("jax.buffer_donor") == 0
    assert check_tpu101(prog_from(fixed)) == []


def test_tpu104_fires_on_an_extra_all_gather(tiny_mp2_engine):
    """Deliberate contract break #2: one accidental extra all-gather
    appended to the mp=2 decode step busts the declared per-layer
    budget (9 = 4/layer x 2 layers + 1 fixed) and TPU104 says so."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.analysis.trace.rules import check_tpu104

    eng = tiny_mp2_engine
    args = _decode_args(eng)
    contract = T.get_contract("engine_decode_step")

    extra = shard_map(
        lambda t: jax.lax.all_gather(t, "mp", axis=0, tiled=True),
        mesh=eng.mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False)

    def broken_step(*a):
        nxt, kp, vp = eng._decode_pure(*a)
        return extra(nxt)[: nxt.shape[0]], kp, vp

    def prog_from(fn):
        return T.TracedProgram(
            contract=contract, config="dense,K=0,mp=2", mp=2,
            num_layers=2, jaxpr=jax.make_jaxpr(fn)(*args),
            lowered_text="", donated_leaves=0)

    found = check_tpu104(prog_from(broken_step))
    assert [f.rule for f in found] == ["TPU104"]
    assert "all_gather appears 10x" in found[0].message
    assert "allowed 9" in found[0].message
    assert check_tpu104(prog_from(eng._decode_pure)) == []


def test_sharded_cow_step_pins_aliases(tiny_mp2_engine):
    """The COW block-copy donates both sharded pools too — same
    pinned-alias contract as the decode step (the fix covers every
    compiled program, not just the four steps)."""
    eng = tiny_mp2_engine
    low = eng._cow.lower(eng.cache.kpool, eng.cache.vpool,
                         jnp.int32(1), jnp.int32(2)).as_text()
    assert low.count("tf.aliasing_output") == 2
    assert low.count("jax.buffer_donor") == 0


def test_sharded_engine_still_token_exact_after_donation_fix():
    """The out_shardings donation fix must not perturb serving
    results: the mp=2 engine's outputs stay identical to mp=1 on a
    small mixed trace (the PR 8 exactness contract, re-proven over
    the changed jit configuration)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import GenerationEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig.tiny(vocab=64, hidden=32,
                                          layers=2, heads=4, seq=32))
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 64, size=n).tolist()
               for n in (3, 9, 17)]

    def serve(mp):
        eng = GenerationEngine(model, num_slots=2, block_size=8,
                               attention_backend="dense",
                               mp_degree=mp, donate=True)
        for i, p in enumerate(prompts):
            eng.add_request(p, max_new_tokens=6, req_id=i)
        return eng.run()

    assert serve(1) == serve(2)


def test_harvest_accepts_legacy_matrix_shapes():
    """Pre-sampling callers hold 3/4/5-tuple explicit matrix entries:
    the normalizer must pad the MISSING trailing fields with their
    defaults (kv=None, lora=False, sampling=False)."""
    from paddle_tpu.analysis.trace.harvest import harvest

    programs = harvest(matrix=(("dense", 0, 1, None, False),))
    # a dense K=0 mp=1 fp config: decode + prefill chunk + cow
    assert len(programs) == 3
    assert all(",sampling" not in p.config for p in programs)


def test_cli_acceptance_command_exits_zero():
    """The ISSUE acceptance command, verbatim: the CLI runs the full
    contract matrix self-clean on CPU."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpu_verify.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "tpu-verify clean: 38 programs" in res.stdout
