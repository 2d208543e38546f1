"""Speculative decoding on the paged engine (ISSUE 7).

The exact-acceptance contract, proven the way PR 3/6 proved theirs:
speculative output must be TOKEN-IDENTICAL to the non-speculative
engine (and to the single-request compiled-decode oracle) for every
(backend, prefill-mode, cache-state, K) combination and for ANY
drafter — a perfect drafter only compresses steps, an adversarial one
only wastes verify columns. Plus: `decode_traces == 1` per
(backend, K) with steady-state `expect_traces(0)`; speculative writes
into shared/registered prefix blocks COW-promote first (cached KV
byte-identical via `dense_gather_reference`, rollback never resurrects
a shared block); multi-token TPOT/accepted-tokens accounting; K=0
building today's decode step bit-for-bit; and the n-gram drafter's
lookup mechanics.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.jit as jit
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference import GenerationEngine, NgramDrafter
from paddle_tpu.observability.metrics import series_total

VOCAB = 61


def _model(seed=0):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(seed)
    cfg = GPTConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=2,
                         seq=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model()


def _reference(model, prompt, max_new, eos=None):
    out = model.generate(
        Tensor._wrap(np.asarray(prompt, np.int32)[None]),
        max_length=len(prompt) + max_new, eos_token_id=eos,
        use_cache=True)
    return np.asarray(out._array)[0]


class OracleDrafter:
    """A PERFECT drafter: proposes the oracle continuation itself, so
    every verify step must accept its whole window. This is the seam a
    tiny draft GPT plugs into, driven at its best case — and the
    exact-acceptance contract probed from the other side (accepting
    everything must still emit exactly the oracle stream)."""

    def __init__(self):
        self.table = {}

    def register(self, model, prompt, max_new):
        full = _reference(model, prompt, max_new)
        self.table[np.asarray(prompt, np.int32).tobytes()] = \
            [int(t) for t in full]

    def propose(self, prompt, generated, k):
        cont = self.table.get(np.asarray(prompt, np.int32).tobytes())
        if cont is None:
            return []
        start = len(np.asarray(prompt).reshape(-1)) + len(generated)
        return cont[start:start + k]


class WrongDrafter(OracleDrafter):
    """An ADVERSARIAL drafter: proposes a token guaranteed to mismatch
    the target's argmax (oracle token + 1 mod vocab), so NOTHING is
    ever accepted beyond the target's own next token — and the output
    must still be exact."""

    def propose(self, prompt, generated, k):
        return [(t + 1) % VOCAB
                for t in super().propose(prompt, generated, k)]


# ---------------------------------------------------------------------------
# satellite: the n-gram / prompt-lookup drafter
# ---------------------------------------------------------------------------

def test_ngram_drafter_lookup_mechanics():
    d = NgramDrafter(max_ngram=3, min_ngram=1)
    # suffix [8, 9] last occurred earlier, followed by 10, 11
    assert d.propose([1, 8, 9, 10, 11, 8, 9], [], 2) == [10, 11]
    # proposals cap at k and at the context end
    assert d.propose([1, 8, 9, 10, 11, 8, 9], [], 1) == [10]
    assert d.propose([8, 9, 10, 8, 9], [], 8) == [10, 8, 9]
    # generated tokens extend the searchable context
    assert d.propose([5, 6, 7], [5, 6], 2) == [7, 5]
    # longest n-gram wins: suffix ..., 2, 3 matches the 2-gram at the
    # front (-> 4), not the more recent 1-gram [3] (-> 9)
    assert d.propose([2, 3, 4, 3, 9, 2, 3], [], 1) == [4]
    # no earlier occurrence -> no proposal
    assert d.propose([1, 2, 3, 4], [], 4) == []
    # min_ngram > available match length -> no proposal
    assert NgramDrafter(max_ngram=3, min_ngram=2).propose(
        [7, 1, 2, 3, 7], [], 2) == []
    with pytest.raises(ValueError):
        NgramDrafter(max_ngram=1, min_ngram=2)
    with pytest.raises(ValueError):
        NgramDrafter(min_ngram=0)


# ---------------------------------------------------------------------------
# tentpole: token-exact parity across the whole serving matrix
# ---------------------------------------------------------------------------

def _trace(rng, n):
    return [(rng.randint(0, VOCAB, rng.randint(1, 14)).astype(np.int32),
             int(rng.randint(2, 9))) for _ in range(n)]


def _run_trace(eng, reqs, midrun=True):
    ids = [eng.add_request(p, n) for p, n in reqs[:len(reqs) // 2]]
    if midrun:
        for _ in range(2):
            eng.step()                 # admissions land mid-decode
    ids += [eng.add_request(p, n) for p, n in reqs[len(reqs) // 2:]]
    out = eng.run()
    return [np.asarray(out[rid]) for rid in ids]


@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_spec_token_identical_across_modes(model, backend):
    """THE acceptance gate: one mixed trace (repetitive prompts the
    n-gram drafter hits, shared prefixes, a block-aligned full-prefix
    hit, mid-run admissions) through the speculative engine in
    (a) prefix cache cold, (b) same engine warm
    — all token-identical to the
    single-request oracle, under both paged-attention backends, with
    decode_traces == 1 per (backend, K) and steady state retracing
    NOTHING."""
    rng = np.random.RandomState(11)
    base = _trace(rng, 4)
    motif = rng.randint(0, VOCAB, 4)
    shared = rng.randint(0, VOCAB, 8).astype(np.int32)   # hot prefix
    reqs = base + [
        (np.tile(motif, 5).astype(np.int32), 8),   # drafter food
        (np.concatenate([shared, rng.randint(0, VOCAB, 3)])
         .astype(np.int32), 4),
        (shared.copy(), 4),            # block-aligned full-prefix hit
    ]
    K = 2

    eng = GenerationEngine(model, num_slots=3, block_size=4,
                           num_blocks=64, spec_decode_k=K,
                           attention_backend=backend, prefill_chunk=8)
    outs_cold = _run_trace(eng, reqs)
    outs_warm = _run_trace(eng, reqs, midrun=False)   # same engine

    for (p, n), a, b in zip(reqs, outs_cold, outs_warm):
        want = _reference(model, p, n)
        np.testing.assert_array_equal(a, want)
        np.testing.assert_array_equal(b, want)

    # the warm pass re-served the prompts from the prefix cache
    assert eng.prefix_hit_tokens > 0
    # ONE verify program per (backend, K) across all of that churn;
    # prefill traces stay bounded by the chunk shape (1)
    assert eng.decode_traces == 1
    assert eng._decode_pure.__name__ == "engine_verify_step"
    assert isinstance(eng.drafter, NgramDrafter)
    assert eng.prefill_traces == 1
    # steady state: a warmed speculative engine retraces NOTHING
    with jit.expect_traces(eng._decode_pure, 0), \
            jit.expect_traces(eng._prefill_pure, 0):
        eng.add_request(np.tile(motif, 4).astype(np.int32), 4)
        eng.run()
    assert eng.cache.num_free == eng.cache.num_blocks - 1


def test_spec_eos_early_stop_mid_window(model):
    """An EOS the verify step accepts mid-window must truncate the
    emission AT the EOS — trailing accepted tokens are dropped exactly
    like the one-token path never would have produced them."""
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, VOCAB, 6).astype(np.int32)
    plain = _reference(model, prompt, 12)
    eos = int(plain[len(prompt) + 2])            # 3rd generated token
    ref_eos = _reference(model, prompt, 12, eos=eos)

    oracle = OracleDrafter()
    oracle.register(model, prompt, 12)           # drafts PAST the eos
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=64, prefill_chunk=8,
                           spec_decode_k=4, drafter=oracle)
    rid = eng.add_request(prompt, 12, eos_token_id=eos)
    got = list(eng.run()[rid])
    assert got[-1] == eos and len(got) < len(prompt) + 12
    np.testing.assert_array_equal(got, ref_eos[:len(got)])


@pytest.mark.parametrize("drafter_cls, want_rate",
                         [(OracleDrafter, 1.0), (WrongDrafter, 0.0)])
def test_drafter_quality_never_changes_tokens(model, drafter_cls,
                                              want_rate):
    """The drafter seam driven at both extremes: a perfect drafter
    accepts every window (fewer verify steps than tokens, hit rate 1)
    and an adversarial drafter accepts nothing (hit rate 0) — both
    emit exactly the oracle stream."""
    rng = np.random.RandomState(7)
    reqs = [(rng.randint(0, VOCAB, 5).astype(np.int32), 9)
            for _ in range(2)]
    drafter = drafter_cls()
    for p, n in reqs:
        drafter.register(model, p, n)
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=64, prefill_chunk=8,
                           spec_decode_k=3, drafter=drafter)
    ids = [eng.add_request(p, n) for p, n in reqs]
    out = eng.run()
    for (p, n), rid in zip(reqs, ids):
        np.testing.assert_array_equal(np.asarray(out[rid]),
                                      _reference(model, p, n))
    snap = eng.metrics_snapshot()
    rate = snap["engine_spec_draft_hit_rate"]["series"][0]["value"]
    assert rate == want_rate
    fam = snap["engine_spec_accepted_tokens"]["series"][0]
    # every generated token was emitted by a verify step (prompts are
    # 5 tokens into 4-token blocks: no full-prefix hits, so the first
    # token comes from prefill and the rest from verify windows)
    assert fam["sum"] == series_total(
        snap, "engine_tokens_generated_total") - len(reqs)
    if drafter_cls is OracleDrafter:
        # K=3 windows emit up to 4 tokens: strictly fewer steps than
        # tokens is the whole point of speculation
        assert fam["count"] < fam["sum"]
    else:
        assert fam["count"] == fam["sum"]      # 1 token per step


# ---------------------------------------------------------------------------
# satellite: speculative writes vs the prefix cache (COW + rollback)
# ---------------------------------------------------------------------------

def test_spec_cow_keeps_cached_blocks_byte_identical(model):
    """A warm-cache speculative run: the second request seats ALL its
    blocks read-only from the prefix cache and its verify windows
    write straight into that footprint — every touched block must
    COW-promote BEFORE the verify step writes, the cached KV must stay
    byte-identical (dense_gather_reference), and rollback must never
    resurrect a shared block (a fresh match still returns the original
    block ids, pristine)."""
    from paddle_tpu.ops.paged_attention import dense_gather_reference

    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=32, prefill_chunk=8,
                           spec_decode_k=3)
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, VOCAB, 8).astype(np.int32)  # 2 full blocks
    want = _reference(model, prompt, 6)

    ra = eng.add_request(prompt, 6)
    np.testing.assert_array_equal(np.asarray(eng.run()[ra]), want)
    cached, hit = eng.cache.match_prefix(prompt)
    assert hit == 8
    row = np.zeros(eng.max_blocks, np.int32)
    row[:len(cached)] = cached
    gk0, gv0 = dense_gather_reference(eng.cache.kpool, eng.cache.vpool,
                                      0, row, 8)
    eng.cache.free(cached)

    # second serve: full-prefix hit -> the FIRST verify window's write
    # position sits inside a registered cached block
    cow0 = series_total(eng.metrics_snapshot(),
                        "engine_cow_copies_total")
    rb = eng.add_request(prompt, 6)
    np.testing.assert_array_equal(np.asarray(eng.run()[rb]), want)
    snap = eng.metrics_snapshot()
    assert series_total(snap, "engine_cow_copies_total") > cow0
    # the cached blocks' KV is byte-identical after the speculative
    # run (accepted writes AND rolled-back rejects both landed in the
    # private COW copy, never the shared block)
    gk1, gv1 = dense_gather_reference(eng.cache.kpool, eng.cache.vpool,
                                      0, row, 8)
    np.testing.assert_array_equal(np.asarray(gk0), np.asarray(gk1))
    np.testing.assert_array_equal(np.asarray(gv0), np.asarray(gv1))
    # rollback never resurrected the shared blocks: a fresh match
    # still serves the ORIGINAL block ids, and a third request served
    # from them is exact
    again, hit = eng.cache.match_prefix(prompt)
    assert hit == 8 and again == cached
    eng.cache.free(again)
    rc = eng.add_request(prompt, 6)
    np.testing.assert_array_equal(np.asarray(eng.run()[rc]), want)


def test_spec_cow_pressure_sheds_draft_instead_of_deadlocking(model):
    """An oversubscribed pool where the COW copy for a warm-cache lane
    cannot be served WHILE that lane holds freshly-allocated window
    blocks: the lane must shed its draft and return the surplus tail
    blocks so the plain one-token window can proceed — not sit on
    them and deadlock a pool the K=0 engine completes on."""

    class GreedyDrafter:
        def propose(self, prompt, generated, k):
            return [0] * k             # always drafts a full window

    eng = GenerationEngine(model, num_slots=1, block_size=4,
                           num_blocks=4, prefill_chunk=8,
                           spec_decode_k=4, drafter=GreedyDrafter())
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, VOCAB, 8).astype(np.int32)  # 2 full blocks
    want = _reference(model, prompt, 4)
    ra = eng.add_request(prompt, 4)    # fills + registers the cache
    np.testing.assert_array_equal(np.asarray(eng.run()[ra]), want)
    # second serve: full-prefix hit seats both cached blocks, the
    # window grabs the last free block, and the COW copy for the
    # feed block then has NOTHING left — the draft must be shed
    rb = eng.add_request(prompt, 4)
    np.testing.assert_array_equal(np.asarray(eng.run()[rb]), want)
    snap = eng.metrics_snapshot()
    assert series_total(snap, "engine_cow_copies_total") >= 1
    # the shed path actually fired: the COW copy DID fail under
    # pressure and the lane DEGRADED (ran draftless) — which must not
    # read as a skipped-iteration decode stall
    stalls = {s["labels"]["path"]: s["value"]
              for s in snap["engine_block_stalls_total"]["series"]}
    assert stalls.get("spec_degrade", 0) >= 1
    assert stalls.get("decode", 0) == 0
    assert eng.cache.num_free == eng.cache.num_blocks - 1


# ---------------------------------------------------------------------------
# satellite: multi-token-step latency + speculation accounting
# ---------------------------------------------------------------------------

def test_spec_multi_token_step_accounting(model):
    """With speculation, a decode step emits SEVERAL tokens: every
    accepted token must land in the TPOT histogram against its
    producing step (so per-request TPOT observations still equal
    generated-tokens - 1), engine_spec_accepted_tokens must record
    per-step emission counts, and the tokens counter must integrate
    exactly."""
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, VOCAB, 5).astype(np.int32)
    oracle = OracleDrafter()
    oracle.register(model, prompt, 6)
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=32, prefill_chunk=8,
                           spec_decode_k=2, drafter=oracle)
    rid = eng.add_request(prompt, 6, priority="interactive")
    np.testing.assert_array_equal(np.asarray(eng.run()[rid]),
                                  _reference(model, prompt, 6))
    snap = eng.metrics_snapshot()
    assert series_total(snap, "engine_tokens_generated_total") == 6
    # prefill emits token 1; perfect K=2 windows emit 3 then 2:
    # exactly 2 verify steps for the remaining 5 tokens
    fam = snap["engine_spec_accepted_tokens"]["series"][0]
    assert fam["count"] == 2 and fam["sum"] == 5
    # TPOT: one observation per token after the first, in the
    # request's priority series
    tpot = {s["labels"]["priority"]: s["count"]
            for s in snap["engine_tpot_seconds"]["series"]}
    assert tpot == {"interactive": 5}
    ttft = {s["labels"]["priority"]: s["count"]
            for s in snap["engine_ttft_seconds"]["series"]}
    assert ttft == {"interactive": 1}
    assert snap["engine_spec_draft_hit_rate"]["series"][0]["value"] \
        == 1.0


def test_spec_instant_finish_stays_visible(model):
    """The PR-6 instant-finish contract under speculation: a
    max_new==1 full-prefix-hit request takes its single token from a
    verify step and must still record that token's producing-step
    latency in the TPOT histogram."""
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=32, prefill_chunk=8,
                           spec_decode_k=2)
    rng = np.random.RandomState(9)
    p = rng.randint(0, VOCAB, 8).astype(np.int32)   # block-aligned
    eng.add_request(p, 1)
    eng.run()
    eng.add_request(p, 1)                 # full hit -> verify path
    eng.run()
    snap = eng.metrics_snapshot()
    assert sum(s["count"]
               for s in snap["engine_tpot_seconds"]["series"]) == 2
    assert series_total(snap, "engine_tokens_generated_total") == 2


# ---------------------------------------------------------------------------
# satellite: K=0 recovers today's path; env override
# ---------------------------------------------------------------------------

def test_spec_k0_is_exactly_todays_decode_path(model):
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=32, prefill_chunk=8,
                           spec_decode_k=0)
    # K=0 builds the ORIGINAL one-token decode step (same function,
    # not a degenerate verify window) and loads no drafter
    assert eng._decode_pure.__name__ == "engine_decode_step"
    assert eng.drafter is None and eng.spec_decode_k == 0
    rng = np.random.RandomState(13)
    p = rng.randint(0, VOCAB, 6).astype(np.int32)
    rid = eng.add_request(p, 5)
    np.testing.assert_array_equal(np.asarray(eng.run()[rid]),
                                  _reference(model, p, 5))


# ---------------------------------------------------------------------------
# satellite: bench row (CI-scale runner + suite registration)
# ---------------------------------------------------------------------------

def test_speculative_bench_row():
    """The gpt_engine_speculative SUITE_ROWS runner at test scale: the
    record must carry net tokens/s for both K=spec_k and the K=0
    baseline (token-identical outputs — asserted inside the runner),
    accepted-tokens/step >= 1 (every verify step nets a token), and
    the draft hit rate."""
    import bench_ops
    from paddle_tpu.models import GPTConfig

    cfg = GPTConfig.tiny(vocab=32, hidden=16, layers=1, heads=2, seq=64)
    paddle.seed(0)
    rec = bench_ops._engine_speculative_case(
        model_cfg=cfg, num_requests=3, num_slots=2, block_size=4,
        prefill_chunk=8, spec_k=3, max_new=8)()
    assert rec["tokens_per_s"] > 0 and rec["tokens_per_s_k0"] > 0
    assert rec["accepted_tokens_per_step"] >= 1.0
    assert rec["verify_steps"] > 0
    assert 0.0 <= rec["draft_hit_rate"] <= 1.0
    assert rec["decode_recompiles"] == 0
    assert "gpt_engine_speculative" in bench_ops.suite_names()


def test_spec_negative_k_is_refused(model):
    with pytest.raises(ValueError, match="spec_decode_k"):
        GenerationEngine(model, num_slots=2, block_size=4,
                         prefill_chunk=8, spec_decode_k=-1)
