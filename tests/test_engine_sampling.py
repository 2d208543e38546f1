"""Probabilistic serving (ISSUE 15): per-slot on-device sampling +
rejection-sampling speculative acceptance.

The contracts, proven the way PRs 7/8/11 proved theirs:

- GREEDY IS BIT-EXACT: a sampling-enabled engine serving
  temperature-0 (or param-less) requests emits token streams
  identical to a sampling-OFF engine across the
  {dense,pallas} x K in {0,4} x mp in {1,2} matrix — and a mixed
  greedy/sampled batch never perturbs its greedy lanes.
- PARAMS ARE DATA: `decode_traces == 1` per (backend, K, mp) for any
  live mix of sampling params, with steady-state `expect_traces(0)`.
- SEEDED RUNS REPLAY: same (seed, trace, config) => same tokens —
  across backends, prefill modes, cold/warm caches, and the
  disaggregated prefill->decode handoff (the slot's key state is a
  pure function of (seed, position), so adoption re-derives it).
- REJECTION SAMPLING PRESERVES THE TARGET DISTRIBUTION: chi-square of
  the device draws against the independent CPU oracle
  (`inference.sampling.oracle_probs`) over >= 10k draws on a tiny
  vocab — for the rejected-draft marginal, the bonus draw, and the
  plain sampled token.
- the `GptDrafter` learned drafter never changes greedy output
  tokens; `best_of_n` seats the shared prompt blocks ONCE.
"""
import dataclasses
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.jit as jit
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference import (GenerationEngine, GptDrafter,
                                  NgramDrafter, SamplingParams,
                                  ServingFleet)
from paddle_tpu.inference.sampling import key_row, oracle_probs
from paddle_tpu.observability.metrics import series_total
from paddle_tpu.ops import sampling as sops

VOCAB = 64     # mp=2-divisible (vocab-parallel embedding)


def _model(seed=0, heads=4):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(seed)
    cfg = GPTConfig.tiny(vocab=VOCAB, hidden=32, layers=2,
                         heads=heads, seq=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model()


_PROMPTS = [(9, 12), (17, 10), (5, 12), (20, 8)]


def _trace(rng_seed=0):
    rng = np.random.RandomState(rng_seed)
    return [(rng.randint(0, VOCAB, plen).astype(np.int32), max_new)
            for plen, max_new in _PROMPTS]


SAMPLED = SamplingParams(temperature=0.9, top_k=20, top_p=0.95,
                         seed=77)


def _serve(model, trace, params_of, **kw):
    eng = GenerationEngine(model, num_slots=4, block_size=8, **kw)
    ids = [eng.add_request(p, max_new_tokens=mn, req_id=i,
                           sampling_params=params_of(i))
           for i, (p, mn) in enumerate(trace)]
    out = eng.run()
    return [out[i] for i in ids], eng


# -- the greedy bit-exactness matrix ------------------------------------

_MATRIX = [("dense", 0, 1),
           pytest.param("dense", 4, 1, marks=pytest.mark.slow),
           pytest.param("pallas", 4, 1, marks=pytest.mark.slow),
           pytest.param("dense", 0, 2, marks=pytest.mark.slow),
           pytest.param("pallas", 0, 1, marks=pytest.mark.slow),
           pytest.param("pallas", 0, 2, marks=pytest.mark.slow),
           pytest.param("dense", 4, 2, marks=pytest.mark.slow),
           pytest.param("pallas", 4, 2, marks=pytest.mark.slow)]


@pytest.mark.parametrize("backend,k,mp", _MATRIX)
def test_greedy_bit_exact_and_one_trace_per_config(model, backend, k,
                                                   mp):
    """temperature=0 requests on a sampling-enabled engine are
    token-identical to the pre-sampling (sampling=False) engine — in
    an ALL-greedy batch and in a mixed batch whose other lanes sample
    — and one compiled decode program serves the whole mix."""
    trace = _trace()
    kw = dict(attention_backend=backend, spec_decode_k=k,
              mp_degree=mp)
    ref, _ = _serve(model, trace, lambda i: None, **kw)
    all_greedy, eng = _serve(model, trace, lambda i: None,
                             sampling=True, **kw)
    assert all_greedy == ref
    mixed, eng2 = _serve(model, trace,
                         lambda i: SAMPLED if i in (1, 3) else None,
                         sampling=True, **kw)
    assert mixed[0] == ref[0] and mixed[2] == ref[2], \
        "sampled lanes perturbed a greedy lane"
    assert eng.decode_traces == 1
    assert eng2.decode_traces == 1


def test_steady_state_never_retraces(model):
    """Any live param mix reuses the one compiled program: after the
    first mixed run, further mixed traffic traces NOTHING."""
    eng = GenerationEngine(model, num_slots=4, block_size=8,
                           sampling=True, spec_decode_k=2)
    trace = _trace()
    for i, (p, mn) in enumerate(trace):
        eng.add_request(p, mn, sampling_params=SAMPLED if i % 2
                        else None)
    eng.run()
    assert eng.decode_traces == 1 and eng.prefill_traces == 1
    with jit.expect_traces(eng._decode_pure, 0), \
            jit.expect_traces(eng._prefill_pure, 0):
        for i, (p, mn) in enumerate(_trace(1)):
            eng.add_request(
                p, mn, sampling_params=None if i % 2 else
                SamplingParams(temperature=0.4, top_k=3, seed=i))
        eng.run()


# -- seeded reproducibility ---------------------------------------------

@pytest.mark.slow
def test_sampled_streams_reproduce_and_agree_across_paths(model):
    """Same (seed, trace, config) => same tokens; and because draws
    are keyed by (seed, absolute position) on logits both backends
    compute bit-identically, the sampled streams agree across
    dense/pallas, prefill chunk sizes, and cold/warm caches."""
    trace = _trace()
    params = lambda i: dataclasses.replace(SAMPLED, seed=100 + i)
    base, _ = _serve(model, trace, params, sampling=True)
    again, _ = _serve(model, trace, params, sampling=True)
    assert again == base
    pallas, _ = _serve(model, trace, params, sampling=True,
                       attention_backend="pallas")
    assert pallas == base
    chunk8, _ = _serve(model, trace, params, sampling=True,
                       prefill_chunk=8)
    assert chunk8 == base
    # warm: the same engine serves the same sampled requests twice —
    # the second pass seats the prompts from the prefix cache and
    # must replay the identical stream (keys are position-pure)
    eng = GenerationEngine(model, num_slots=4, block_size=8,
                           sampling=True)
    ids = [eng.add_request(p, mn, sampling_params=params(i))
           for i, (p, mn) in enumerate(trace)]
    out = eng.run()
    cold = [out[i] for i in ids]
    assert cold == base
    ids = [eng.add_request(p, mn, sampling_params=params(i))
           for i, (p, mn) in enumerate(trace)]
    out = eng.run()
    warm = [out[i] for i in ids]
    assert warm == base
    assert eng.prefix_hit_tokens > 0     # the warm pass actually hit


@pytest.mark.slow
def test_none_seed_resolves_deterministically(model):
    """A None seed draws from the engine's counter: two fresh engines
    serving the same trace produce the same streams (and the resolved
    request carries its seed)."""
    p = SamplingParams(temperature=1.0)
    assert p.seed is None
    one, _ = _serve(model, _trace(), lambda i: p, sampling=True)
    two, _ = _serve(model, _trace(), lambda i: p, sampling=True)
    assert one == two


@pytest.mark.slow
def test_spec_sampled_reproduces_and_preserves_greedy(model):
    """Speculation + sampling: same-seed reproducibility at K=4, and
    the drafter cannot perturb a greedy lane (exact acceptance)."""
    trace = _trace()
    params = lambda i: dataclasses.replace(SAMPLED, seed=50 + i)
    a, enga = _serve(model, trace, params, sampling=True,
                     spec_decode_k=4)
    b, _ = _serve(model, trace, params, sampling=True,
                  spec_decode_k=4)
    assert a == b
    assert enga.decode_traces == 1
    # cross-backend identity holds under speculation too
    c, _ = _serve(model, trace, params, sampling=True,
                  spec_decode_k=4, attention_backend="pallas")
    assert c == a


# -- distribution preservation (the statistical acceptance test) --------

def _chi2_crit(dof):
    """chi-square critical value at alpha=1e-3 (scipy's table — the
    tests are seed-deterministic, so pass/fail never flakes)."""
    from scipy import stats

    return float(stats.chi2.isf(1e-3, dof))


def _chi2(counts, probs, n):
    exp = probs * n
    keep = exp > 0
    assert counts[~keep].sum() == 0, \
        "draws landed on zero-probability tokens"
    return float(((counts[keep] - exp[keep]) ** 2 / exp[keep]).sum()), \
        int(keep.sum()) - 1


N_DRAWS = 20000


def _draw_rows(n=N_DRAWS):
    """n independent per-slot key rows (distinct requests' seeds)."""
    return jnp.asarray(np.asarray(jax.random.split(
        jax.random.PRNGKey(123), n), np.uint32))


def test_rejection_sampling_preserves_target_distribution():
    """The Leviathan guarantee, measured: with a deterministic draft
    token d, the emitted marginal `accept ? d : resample` must equal
    the target distribution p — for a mid-probability d, for a
    top-probability d, and for a d the masking zeroed out. Chi-square
    vs the CPU oracle over 20k device draws on an 8-token vocab."""
    rng = np.random.RandomState(3)
    logits = rng.randn(8).astype(np.float32) * 1.5
    params = SamplingParams(temperature=0.8, top_k=6, top_p=0.92,
                            seed=0)
    p = oracle_probs(logits, params)
    order = np.argsort(-p)
    keys = _draw_rows()
    B = keys.shape[0]
    lg = jnp.asarray(np.tile(logits, (B, 2, 1)))
    temps = jnp.full(B, params.temperature, jnp.float32)
    tks = jnp.full(B, params.top_k, jnp.int32)
    tps = jnp.full(B, params.top_p, jnp.float32)
    dlens = jnp.ones(B, jnp.int32)
    pos = jnp.zeros(B, jnp.int32)
    vw = jax.jit(sops.verify_window)
    for d in (int(order[2]),       # mid-probability draft
              int(order[0]),       # the argmax itself
              int(order[-1])):     # masked out (p == 0): always reject
        tokens = jnp.asarray(
            np.stack([np.zeros(B), np.full(B, d)], axis=1)
            .astype(np.int32))
        choices, accepts = vw(lg, tokens, dlens, temps, tks, tps,
                              keys, pos)
        choices, accepts = np.asarray(choices), np.asarray(accepts)
        emitted = np.where(accepts[:, 0], d, choices[:, 0])
        if p[d] == 0:
            assert not accepts[:, 0].any()
        stat, dof = _chi2(np.bincount(emitted, minlength=8), p, B)
        assert stat < _chi2_crit(dof), \
            (f"draft {d}: chi2={stat:.1f} over dof={dof} exceeds the "
             f"0.001 critical value — distribution not preserved")
        # the bonus draw (row 1 carries no draft) is a plain sample
        # from p, whatever happened at row 0
        stat, dof = _chi2(np.bincount(choices[:, 1], minlength=8), p,
                          B)
        assert stat < _chi2_crit(dof)


def test_sample_token_matches_oracle_distribution():
    """The plain (K=0 decode / prefill first-token) draw: chi-square
    of `sample_token` against the CPU oracle, with masking on."""
    rng = np.random.RandomState(4)
    logits = rng.randn(8).astype(np.float32)
    params = SamplingParams(temperature=1.3, top_k=5, top_p=0.85,
                            seed=0)
    p = oracle_probs(logits, params)
    keys = _draw_rows()
    B = keys.shape[0]
    toks = np.asarray(jax.jit(sops.sample_token)(
        jnp.asarray(np.tile(logits, (B, 1))),
        jnp.full(B, params.temperature, jnp.float32),
        jnp.full(B, params.top_k, jnp.int32),
        jnp.full(B, params.top_p, jnp.float32), keys,
        jnp.zeros(B, jnp.int32)))
    stat, dof = _chi2(np.bincount(toks, minlength=8), p, B)
    assert stat < _chi2_crit(dof)
    # temperature=0 rows are the literal argmax, whatever the knobs
    g = np.asarray(jax.jit(sops.sample_token)(
        jnp.asarray(np.tile(logits, (4, 1))),
        jnp.zeros(4, jnp.float32), jnp.full(4, 2, jnp.int32),
        jnp.full(4, 0.5, jnp.float32), _draw_rows(4),
        jnp.arange(4, dtype=jnp.int32)))
    assert (g == int(np.argmax(logits))).all()


def test_verify_window_greedy_rows_reproduce_equality_contract():
    """Greedy rows of `verify_window`: accepts is exact argmax
    equality on the drafted columns, choices pins the argmax chain —
    the device form of the PR 7 host walk."""
    rng = np.random.RandomState(5)
    lg = jnp.asarray(rng.randn(3, 3, 8).astype(np.float32))
    am = np.asarray(jnp.argmax(lg, axis=-1))
    tokens = np.zeros((3, 3), np.int32)
    tokens[0, 1:] = am[0, :2]          # perfect draft: all accepted
    tokens[1, 1] = (am[1, 0] + 1) % 8  # wrong first draft
    tokens[2, 1:] = am[2, :2]          # drafts beyond dlen ignored
    choices, accepts = sops.verify_window(
        lg, jnp.asarray(tokens), jnp.asarray([2, 2, 0]),
        jnp.zeros(3, jnp.float32), jnp.zeros(3, jnp.int32),
        jnp.ones(3, jnp.float32),
        jnp.zeros((3, 2), jnp.uint32), jnp.zeros(3, jnp.int32))
    choices, accepts = np.asarray(choices), np.asarray(accepts)
    assert (choices == am).all()
    assert accepts[0].tolist() == [True, True, False]
    assert accepts[1].tolist() == [False, False, False]
    assert accepts[2].tolist() == [False, False, False]  # dlen = 0


# -- best_of_n ----------------------------------------------------------

def test_best_of_n_shares_prompt_blocks_once(model):
    """The fan-out convenience: n candidates of one prompt, the
    prompt's FULL blocks registered by candidate 0 and seated
    read-only ((n-1) full-prefix hits) — never re-prefilled, never
    duplicated — and a fixed base seed replays all candidates."""
    eng = GenerationEngine(model, num_slots=4, block_size=8,
                           sampling=True)
    prompt = _trace()[1][0]            # 17 tokens -> 2 full blocks
    params = SamplingParams(temperature=1.0, seed=5)
    cands = eng.best_of_n(prompt, 3, 10, sampling_params=params)
    assert len(cands) == 3
    plen = len(prompt)
    shared = (plen // 8) * 8
    for c in cands:
        assert c[:plen] == list(map(int, prompt))
    # seated once: candidates 1..2 each hit the whole registered
    # prefix; the cache holds ONE copy of the prompt's full blocks
    assert eng.prefix_hit_tokens == 2 * shared
    assert eng.cache.num_cached_blocks == plen // 8
    # replay: a fresh engine with the same base seed reproduces all n
    eng2 = GenerationEngine(model, num_slots=4, block_size=8,
                            sampling=True)
    assert eng2.best_of_n(prompt, 3, 10,
                          sampling_params=params) == cands
    # and a greedy request is a usage error, not n duplicates
    with pytest.raises(ValueError, match="temperature > 0"):
        eng.best_of_n(prompt, 2, 4,
                      sampling_params=SamplingParams(temperature=0))
    # a None-seed fan-out claims the WHOLE seed range from the
    # counter: a later None-seed request must not replay a candidate
    eng3 = GenerationEngine(model, num_slots=4, block_size=8,
                            sampling=True)
    eng3.best_of_n(prompt, 2, 2,
                   sampling_params=SamplingParams(temperature=1.0))
    assert eng3._seed_counter == 2
    # a load-shed candidate is a LOUD error, never a silent None in
    # the returned list (max_queue pressure, same-priority lanes)
    eng4 = GenerationEngine(model, num_slots=1, block_size=8,
                            sampling=True, max_queue=1)
    with pytest.raises(RuntimeError, match="shed"):
        eng4.best_of_n(prompt, 4, 2,
                       sampling_params=SamplingParams(temperature=1.0,
                                                      seed=3))


@pytest.mark.slow
def test_fleet_best_of_n(model):
    fleet = ServingFleet(model, num_replicas=2, num_slots=4,
                         block_size=8, sampling=True)
    prompt = _trace()[1][0]
    cands = fleet.best_of_n(prompt, 3, 8,
                            sampling_params=SamplingParams(
                                temperature=1.0, seed=9))
    assert len(cands) == 3
    plen = len(prompt)
    for c in cands:
        assert c[:plen] == list(map(int, prompt))
    # candidates 1..n-1 routed to the replica candidate 0 warmed and
    # hit its whole registered prefix (seated once fleet-wide)
    snap = fleet.metrics_snapshot()
    assert series_total(
        snap, "fleet_affinity_hit_tokens_total") == 2 * (plen // 8) * 8
    # wrong-typed params take the engine's validation path (loud
    # TypeError, not an AttributeError inside the fleet)
    with pytest.raises(TypeError, match="SamplingParams"):
        fleet.best_of_n(prompt, 2, 4,
                        sampling_params={"temperature": 0.8})
    # None-seed fan-out claims the whole range fleet-side too
    before = fleet._seed_counter
    fleet.best_of_n(prompt, 2, 2,
                    sampling_params=SamplingParams(temperature=1.0))
    assert fleet._seed_counter == before + 2
    # the prefix-cache guard holds fleet-side (replicas without a
    # cache — n-1 silent re-prefills otherwise)
    nocache = ServingFleet(model, num_replicas=1, num_slots=4,
                           block_size=8, sampling=True,
                           enable_prefix_cache=False)
    with pytest.raises(ValueError, match="prefix cache"):
        nocache.best_of_n(prompt, 2, 4,
                          sampling_params=SamplingParams(
                              temperature=1.0, seed=1))


# -- fleet plumbing (sampled handoff) -----------------------------------

@pytest.mark.slow
def test_fleet_single_replica_matches_bare_engine(model):
    trace = _trace()
    params = lambda i: dataclasses.replace(SAMPLED, seed=200 + i)
    ref, _ = _serve(model, trace, params, sampling=True)
    fleet = ServingFleet(model, num_replicas=1, num_slots=4,
                         block_size=8, sampling=True)
    ids = [fleet.add_request(p, mn, req_id=i,
                             sampling_params=params(i))
           for i, (p, mn) in enumerate(trace)]
    out = fleet.run()
    assert [out[i] for i in ids] == ref


def test_disaggregated_sampled_handoff_token_identical(model):
    """The satellite contract: prefill->decode adoption keeps the
    slot's key state — a temperature>0 request with a fixed seed is
    token-identical colocated vs disaggregated (the seed travels with
    the handoff and the decode replica re-derives the same key
    row)."""
    trace = _trace()
    params = lambda i: dataclasses.replace(SAMPLED, seed=300 + i) \
        if i != 2 else None            # one greedy lane rides along
    ref, _ = _serve(model, trace, params, sampling=True)
    fleet = ServingFleet(model, num_replicas=1,
                         num_prefill_replicas=1, num_slots=4,
                         block_size=8, sampling=True)
    ids = [fleet.add_request(p, mn, req_id=i,
                             sampling_params=params(i))
           for i, (p, mn) in enumerate(trace)]
    out = fleet.run()
    assert [out[i] for i in ids] == ref


@pytest.mark.slow
def test_fleet_resolves_none_seed_before_handoff(model):
    """A None seed must pin fleet-side: the prefill replica's first
    token and the decode replica's adopted lane share one seed, so
    two identical fleets replay each other."""
    def serve_fleet():
        fleet = ServingFleet(model, num_replicas=1,
                             num_prefill_replicas=1, num_slots=4,
                             block_size=8, sampling=True)
        ids = [fleet.add_request(p, mn, req_id=i,
                                 sampling_params=SamplingParams(
                                     temperature=1.0))
               for i, (p, mn) in enumerate(_trace())]
        out = fleet.run()
        return [out[i] for i in ids]

    assert serve_fleet() == serve_fleet()


def test_adopt_requires_resolved_seed(model):
    eng = GenerationEngine(model, num_slots=2, block_size=8,
                           sampling=True)
    with pytest.raises(ValueError, match="explicit seed"):
        eng.adopt_request(np.arange(8, dtype=np.int32), 3,
                          blocks=[1], max_new_tokens=4,
                          sampling_params=SamplingParams(
                              temperature=1.0))


# -- the learned drafter ------------------------------------------------

@pytest.mark.slow
def test_gpt_drafter_never_changes_greedy_tokens(model):
    """The PR 7 follow-up: a tiny draft GPT through the propose()
    protocol — greedy output stays token-identical to K=0 whatever
    the drafter's quality (here: a DIFFERENT random model)."""
    draft = _model(seed=9, heads=2)
    trace = _trace()
    ref, _ = _serve(model, trace, lambda i: None)
    out, eng = _serve(model, trace, lambda i: None, spec_decode_k=3,
                      drafter=GptDrafter(draft))
    assert out == ref
    assert eng.decode_traces == 1


@pytest.mark.slow
def test_gpt_drafter_mechanics(model):
    draft = _model(seed=9, heads=2)
    d = GptDrafter(draft)
    prompt = np.arange(5, dtype=np.int32)
    out = d.propose(prompt, [1, 2], 3)
    assert len(out) == 3
    assert all(0 <= t < VOCAB for t in out)
    # proposals are the draft model's own greedy continuation: token
    # i+1 conditions on token i (re-fed, not parallel-sampled)
    again = d.propose(prompt, [1, 2], 3)
    assert again == out                # deterministic
    assert d.propose(prompt, [1, 2], 0) == []
    # out-of-vocab context (disjoint tokenizer): refuse to guess
    assert d.propose(np.asarray([VOCAB + 5]), [], 3) == []
    # max_context=0 is a loud range error, never silently coerced to
    # the full window by falsy-zero defaulting
    with pytest.raises(ValueError, match="max_context"):
        GptDrafter(draft, max_context=0)
    # an eval-less dropout model is a usage error
    drop = _model(seed=3, heads=2)
    drop.config.dropout = 0.1
    drop.train()
    with pytest.raises(ValueError, match="eval"):
        GptDrafter(drop)
    # and GptDrafter composes with sampling: rejection acceptance
    # reproduces under the learned drafter too
    params = lambda i: dataclasses.replace(SAMPLED, seed=400 + i)
    a, _ = _serve(model, _trace(), params, sampling=True,
                  spec_decode_k=3, drafter=GptDrafter(draft))
    b, _ = _serve(model, _trace(), params, sampling=True,
                  spec_decode_k=3, drafter=GptDrafter(draft))
    assert a == b


# -- validation, knobs, metrics -----------------------------------------

def test_sampling_params_validation(model):
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.5)
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=1.5)
    # a sampling request on a greedy-only engine is a loud error
    eng = GenerationEngine(model, num_slots=2, block_size=8)
    with pytest.raises(ValueError, match="sampling=True"):
        eng.add_request(np.arange(4, dtype=np.int32), 2,
                        sampling_params=SamplingParams())
    with pytest.raises(TypeError, match="SamplingParams"):
        GenerationEngine(model, num_slots=2, block_size=8,
                         sampling=True).add_request(
            np.arange(4, dtype=np.int32), 2,
            sampling_params={"temperature": 1.0})
    # best_of_n needs the subsystem (and the prefix cache)
    with pytest.raises(ValueError, match="sampling=True"):
        eng.best_of_n(np.arange(8, dtype=np.int32), 2, 4)
    with pytest.raises(ValueError, match="prefix cache"):
        GenerationEngine(model, num_slots=2, block_size=8,
                         sampling=True, enable_prefix_cache=False
                         ).best_of_n(np.arange(8, dtype=np.int32), 2,
                                     4)


def test_sampling_metrics(model):
    """The info gauge says which programs this engine runs; the
    sampled-token counter counts ONLY temperature>0 lanes (and only
    exists on sampling engines — plain exposition unchanged)."""
    trace = _trace()
    outs, eng = _serve(model, trace,
                       lambda i: SAMPLED if i == 1 else None,
                       sampling=True)
    snap = eng.metrics_snapshot()
    fam = {s["labels"]["enabled"]: s["value"]
           for s in snap["engine_sampling_info"]["series"]}
    assert fam == {"1": 1.0}
    # exactly the sampled lane's generated tokens, nothing from the
    # greedy lanes
    sampled = series_total(snap, "engine_sampled_tokens_total")
    assert sampled == len(outs[1]) - len(trace[1][0])
    _, plain = _serve(model, trace, lambda i: None)
    assert "engine_sampled_tokens_total" not in plain.metrics_snapshot()
    assert {s["labels"]["enabled"]: s["value"]
            for s in plain.metrics_snapshot()
            ["engine_sampling_info"]["series"]} == {"0": 1.0}


def test_key_row_is_seed_pure():
    assert (key_row(7) == key_row(7)).all()
    assert (key_row(7) != key_row(8)).any()
    assert key_row(7).dtype == np.uint32 and key_row(7).shape == (2,)
    # the full 64-bit seed range stays distinct: seeds congruent mod
    # 2^31 / 2^32 (hash-derived seeds, negatives) must not collide
    assert (key_row(7) != key_row(7 + 2**31)).any()
    assert (key_row(7) != key_row(7 + 2**32)).any()
    assert (key_row(-1) != key_row(2**31 - 1)).any()


# -- bench runner (tiny) ------------------------------------------------

@pytest.mark.slow
def test_sampling_bench_runner_tiny():
    """The gpt_engine_sampling row's runner at CI scale: structure +
    in-runner assertions (greedy identity, seeded reproducibility,
    best-of-n block sharing) on a tiny config."""
    import bench_ops
    from paddle_tpu.models import GPTConfig

    paddle.seed(0)
    rec = bench_ops._engine_sampling_case(
        model_cfg=GPTConfig.tiny(vocab=VOCAB, hidden=32, layers=2,
                                 heads=2, seq=64),
        num_requests=3, num_slots=2, block_size=8, max_new=6,
        best_n=2)()
    for key in ("tokens_per_s_greedy_off", "tokens_per_s_greedy",
                "tokens_per_s_sampled", "tokens_per_s_best_of_n",
                "sampled_tokens", "best_of_n_hit_tokens"):
        assert key in rec, rec
    assert rec["sampled_tokens"] > 0
    assert rec["best_of_n_hit_tokens"] > 0


def test_suite_rows_carry_sampling_row():
    import bench_ops

    assert "gpt_engine_sampling" in bench_ops.SUITE_ROWS
