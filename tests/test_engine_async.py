"""The pipelined engine core (ISSUE 18; the default since ISSUE 29):
`step()` returns with one decode step launched and unread. Plain
decode goes one step AHEAD (step N+1 is launched from step N's tokens
on the device before the host reads them); the speculative verify step
completes step N before it launches N+1. `async_core=False` is the
serial order everything here is compared against.

The contract under test is brutal on purpose: the pipelined core is a
SCHEDULING refactor, not a numerics change —

- token IDENTITY serial vs async across the whole serving matrix
  ({dense, pallas} x K in {0, 4} x mp in {1, 2} x kv in {fp, int8}),
  cold + warm, with mid-run
  admissions, saturation shedding, and adapter-pool evictions in the
  mix.  Sampled lanes hold too: the acceptance coin at each verify
  position is compared against p(draft token), so identical tokens
  REQUIRE identical drafts — the helper-thread proposals must equal
  the serial ones bit-for-bit (`_m_spec_ok/_m_spec_rej` equality is
  asserted as the direct witness).
- the pipeline DRAINS: an in-flight dispatched step outstanding when
  EOS lands / drain() is called completes on the step thread, and the
  block/adapter-page leak audits stay green.
- `decode_traces == 1` per config and steady-state `expect_traces(0)`
  — dispatch-ahead reuses the exact compiled programs.
- the default is ON;
  `async_core=False` leaves the engine on the serial path with no
  in-flight machinery engaged.
- the ahead order's late knowledge is safe: a finish by length is a
  count and never overshoots; an EOS is seen one step late, the
  overshoot token is discarded and counted, and the lane's blocks go
  back only when the step that rode it has completed; the last chunk
  of a prompt no longer syncs inside `_prefill_step`.
- the flight recorder shows the pipeline actually pipelining:
  `async_dispatch(seq)` strictly precedes `async_complete(seq)` and
  completes interleave one-ahead, never deeper.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.jit as jit
from paddle_tpu.adapters import AdapterRegistry
from paddle_tpu.inference import GenerationEngine, ServingFleet
from paddle_tpu.inference import speculative
from paddle_tpu.inference.sampling import SamplingParams

VOCAB = 64


def _model(seed=0):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(seed)
    cfg = GPTConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=4,
                         seq=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model()


def _trace(rng, n=4):
    """Mixed lengths + motif-tiled prompts (so the NgramDrafter
    actually matches and the accept walk sees non-empty windows) + a
    hot shared prefix."""
    motif = rng.randint(0, VOCAB, 3).astype(np.int32)
    reqs = [(rng.randint(0, VOCAB, rng.randint(2, 13)).astype(np.int32),
             int(rng.randint(2, 7))) for _ in range(n)]
    reqs += [(np.tile(motif, 5).astype(np.int32), 6),
             (np.tile(motif, 3).astype(np.int32), 8)]
    shared = rng.randint(0, VOCAB, 8).astype(np.int32)
    reqs += [(np.concatenate([shared, rng.randint(0, VOCAB, 3)])
              .astype(np.int32), 4),
             (shared.copy(), 4)]
    return reqs


def _run_trace(eng, reqs, midrun=True):
    ids = [eng.add_request(p, n) for p, n in reqs[:len(reqs) // 2]]
    if midrun:
        for _ in range(2):
            eng.step()                 # admissions land mid-pipeline
    ids += [eng.add_request(p, n) for p, n in reqs[len(reqs) // 2:]]
    out = eng.run()
    return [list(map(int, out[rid])) for rid in ids]


def _spec_counters(eng):
    return (int(eng._m_spec_ok.value), int(eng._m_spec_rej.value))


def _assert_async_matrix_cell(model, backend, K, mp=None, kv=None):
    """One (backend, K, mp, kv_dtype) cell: the same mixed trace
    served serial then async over (a) a cold cache, (b) the same
    engine warm — token lists identical per mode, ONE
    decode trace each, and at K>0 identical draft-acceptance counters
    (the direct witness that helper-thread drafts equal serial
    drafts)."""
    rng = np.random.RandomState(11)
    reqs = _trace(rng)

    def serve(async_core):
        quant = dict(kv_dtype=kv, weight_dtype=kv) if kv else {}
        eng = GenerationEngine(model, num_slots=3, block_size=4,
                               num_blocks=64, spec_decode_k=K,
                               attention_backend=backend,
                               mp_degree=mp, async_core=async_core,
                               prefill_chunk=8, **quant)
        out = [_run_trace(eng, reqs),
               _run_trace(eng, reqs, midrun=False)]   # warm cache
        assert eng.async_core == async_core
        assert eng.decode_traces == 1, \
            (f"{backend} K={K} mp={mp} kv={kv} "
             f"async={async_core}: decode retraced")
        return out, eng

    serial, eng_s = serve(False)
    amode, eng_a = serve(True)
    assert amode == serial, \
        f"{backend} K={K} mp={mp} kv={kv}: async diverged from serial"
    if K:
        assert _spec_counters(eng_a) == _spec_counters(eng_s), \
            "helper-thread drafts diverged from serial proposals"
        assert sum(_spec_counters(eng_s)) > 0, \
            "trace never exercised the drafter — weak test"
        # the verify step completes before it dispatches
        assert eng_a.decode_steps_ahead == 0
    else:
        # plain decode went ahead; no EOS in the trace, so nothing
        # was computed and discarded, and every token asked for came
        assert eng_a.decode_steps_ahead >= 0.8 * eng_a.decode_steps > 0
        assert eng_a.overshoot_tokens == 0
        assert eng_a.tokens_generated == eng_s.tokens_generated
        assert eng_a._feed_select._cache_size() == 1, \
            "the feed select compiled more than once"
    assert eng_s.decode_steps_ahead == 0
    # the async engine retired every dispatched step before returning
    assert eng_a._inflight is None and eng_a._ahead is None


# ---------------------------------------------------------------------------
# tentpole: serial-vs-async token identity
# ---------------------------------------------------------------------------

# The 1-core CI box can't fit the whole suite in the tier-1 window,
# so tier-1 carries ONE identity cell — dense K=4, the cell that
# exercises the helper-thread drafter AND the pipeline at once — and
# the slow tier carries the rest (the test_engine_sharded precedent).
@pytest.mark.parametrize("K", [0, 4])
def test_async_token_identity_dense(model, K):
    """Tier-1 cut of THE acceptance gate: (dense, K, mp=1, fp) over
    cold + warm cache with mid-run admissions."""
    _assert_async_matrix_cell(model, "dense", K)


@pytest.mark.slow
def test_async_token_identity_pallas_spec(model):
    """Tier-1 lean probe of the (pallas, K=4) cell — the fused verify
    kernel under the dispatch-ahead pipeline (the slow full matrix
    adds mp + int8)."""
    _assert_async_matrix_cell(model, "pallas", 4)


@pytest.mark.slow
@pytest.mark.parametrize("kv", [None, "int8"])
@pytest.mark.parametrize("mp", [None, 2])
@pytest.mark.parametrize("backend,K", [("dense", 0), ("dense", 4),
                                       ("pallas", 0), ("pallas", 4)])
def test_async_token_identity_full_matrix(model, backend, K, mp, kv):
    """The full {backend} x K x mp x kv_dtype identity matrix the
    ISSUE gates on (slow-marked; tier-1 carries the three lean cells
    above — the test_engine_sharded precedent)."""
    _assert_async_matrix_cell(model, backend, K, mp=mp, kv=kv)


@pytest.mark.parametrize(
    "K", [0, pytest.param(4, marks=pytest.mark.slow)])
def test_async_sampled_lanes_identical(model, K):
    """Sampled lanes are where draft identity has teeth: the
    acceptance coin compares against p(draft token), so ANY
    helper-thread draft divergence shows up as a different token
    stream. Under the ahead order (K = 0) a draw folds the token's
    position, a count the host knows without the unread token. Mixed
    greedy + sampled lanes, serial vs pipelined."""
    rng = np.random.RandomState(7)
    reqs = _trace(rng)

    def serve(async_core):
        eng = GenerationEngine(model, num_slots=3, block_size=4,
                               num_blocks=64, prefill_chunk=8,
                               spec_decode_k=K, sampling=True,
                               async_core=async_core)
        ids = []
        for i, (p, n) in enumerate(reqs):
            sp = SamplingParams(temperature=0.9, top_k=8,
                                seed=100 + i) if i % 2 else None
            ids.append(eng.add_request(p, n, sampling_params=sp))
        out = eng.run()
        return [list(map(int, out[rid])) for rid in ids], eng

    serial, eng_s = serve(False)
    amode, eng_a = serve(True)
    assert amode == serial
    assert _spec_counters(eng_a) == _spec_counters(eng_s)


# ---------------------------------------------------------------------------
# draft_window: the ONE filter both the serial scheduler and the
# async drafter thread run — pure-function contract (no engine, no
# jit; a divergence here breaks sampled-lane token identity, so the
# edge cases get direct coverage)
# ---------------------------------------------------------------------------

class _ListDrafter:
    """Stub drafter replaying a fixed proposal regardless of input."""

    def __init__(self, tokens):
        self.tokens = list(tokens)

    def propose(self, prompt, generated, budget):
        return list(self.tokens)


@pytest.mark.parametrize("proposal,budget,vocab,want", [
    ([3, 5, 7], 3, 64, [3, 5, 7]),        # in-vocab, exact budget
    ([3, 5, 7, 9], 2, 64, [3, 5]),        # over-proposal capped
    ([3, 64, 7], 3, 64, [3]),             # vocab edge truncates...
    ([3, -1, 7], 3, 64, [3]),             # ...as does a negative id
    ([64, 3, 5], 3, 64, []),              # junk head: verify nothing
    ([3, 5], 0, 64, []),                  # exhausted budget: no call
    ([3, 5], -2, 64, []),                 # clamped budget stays empty
    ([], 4, 64, []),                      # drafter declined
])
def test_draft_window_junk_filter_and_budget(proposal, budget, vocab,
                                             want):
    got = speculative.draft_window(_ListDrafter(proposal), [1, 2],
                                   [0], budget, vocab)
    assert got == want


def test_draft_window_numpy_scalars_coerced():
    """Drafters may return numpy ints; the window must hand the
    engine plain Python ints (they're compared + device_put later)."""
    got = speculative.draft_window(
        _ListDrafter(np.array([3, 5], dtype=np.int32)), [1], [], 2, 64)
    assert got == [3, 5] and all(type(t) is int for t in got)


def test_draft_window_snapshot_equals_live_context():
    """The async core hands the helper thread a SNAPSHOT of
    slot.generated; the ngram drafter must propose identically from
    the copy (purity — the thread-safety contract in the docstring)."""
    rng = np.random.RandomState(3)
    motif = rng.randint(0, 64, 4).tolist()
    prompt = np.array(motif * 3, dtype=np.int32)
    live = list(motif) + [7]
    drafter = speculative.NgramDrafter()
    a = speculative.draft_window(drafter, prompt, list(live), 4, 64)
    b = speculative.draft_window(drafter, prompt, live, 4, 64)
    assert a == b
    assert live == list(motif) + [7]      # context never mutated


# ---------------------------------------------------------------------------
# satellite: knob resolution + serial path untouched
# ---------------------------------------------------------------------------

def test_async_knob_default_on_and_ctor(model):
    """`None` resolves to the pipelined core; which order it takes
    follows from the step (`spec_decode_k`), not from a knob."""
    mk = lambda **kw: GenerationEngine(model, num_slots=2,
                                       block_size=4, num_blocks=32,
                                       **kw)
    assert mk().async_core is True and mk()._goes_ahead
    assert mk(async_core=True).async_core is True
    assert mk(async_core=False).async_core is False
    assert not mk(async_core=False)._goes_ahead
    spec = mk(spec_decode_k=2)
    assert spec.async_core is True and not spec._goes_ahead


def test_async_default_falls_to_serial_where_the_spec_refuses(model,
                                                              monkeypatch):
    """A model whose spec refuses the pipelined core is served in the
    serial order by default (an observable of the spec, not a name),
    and refused only where the caller asks for the core outright."""
    spec = model.serving_spec()
    monkeypatch.setattr(type(spec), "refuses",
                        {"async_core": "not proven for this model"},
                        raising=False)
    mk = lambda **kw: GenerationEngine(model, num_slots=2,
                                       block_size=4, num_blocks=32,
                                       **kw)
    assert mk().async_core is False
    with pytest.raises(ValueError, match="async_core is not served"):
        mk(async_core=True)


@pytest.mark.parametrize("K", [0, 4])
def test_async_off_engages_no_pipeline_state(model, K):
    """The serial order never touches the in-flight machinery: no step
    left unread across calls, no helper thread, no async flight
    events, no step counted as launched ahead — the op-for-op
    guarantee has an observable witness."""
    rng = np.random.RandomState(3)
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=32, prefill_chunk=8,
                           spec_decode_k=K, async_core=False)
    eng.add_request(rng.randint(0, VOCAB, 6).astype(np.int32), 5)
    while eng.num_pending or eng.num_active:
        eng.step()
        assert eng._inflight is None and eng._first is None
        assert all(s is None or s.ahead == 0 for s in eng._slots)
    assert eng._ahead is None and eng.decode_steps_ahead == 0
    assert eng._feed_select._cache_size() == 0
    events = {e["event"] for e in eng.flight.dump()}
    assert not (events & {"async_dispatch", "async_complete",
                          "adapter_prefetch", "overshoot"})


# ---------------------------------------------------------------------------
# the ahead order: what the host knows late, and the rule that keeps it safe
# ---------------------------------------------------------------------------

def _ahead_engine(model, async_core=None, **kw):
    return GenerationEngine(model, num_slots=2, block_size=4,
                            num_blocks=64, prefill_chunk=8,
                            async_core=async_core, **kw)


def test_ahead_one_decode_step_is_unread_when_step_returns(model):
    """The invariant: between two decode programs the device always
    has the next one queued — `step()` returns with exactly one decode
    step launched and unread while any lane decodes, no first token
    left unread, and every lane in that step is one token ahead of
    what the host has read."""
    rng = np.random.RandomState(3)
    eng = _ahead_engine(model)
    for n in (6, 11):
        eng.add_request(rng.randint(0, VOCAB, n).astype(np.int32), 7)
    seen = 0
    while eng.num_pending or eng.num_active:
        eng.step()
        assert eng._first is None
        decoding = [s for s in eng._slots if s is not None
                    and not s.prefilling and s.done is None
                    and len(s.generated) < s.req.max_new_tokens]
        if decoding:
            seen += 1
            assert eng._inflight is not None
            assert sorted(map(id, eng._inflight.slots)) == \
                sorted(map(id, decoding))
            assert all(s.ahead == 1 for s in decoding)
    assert seen > 5 and eng._inflight is None
    assert eng.decode_steps_ahead == eng.decode_steps - 1
    text = eng.metrics.render_prometheus()
    assert f"engine_decode_steps_ahead_total {eng.decode_steps_ahead}" \
        in text
    assert "engine_overshoot_tokens_total" in text
    assert eng._m_overshoot.value == 0


def test_ahead_finish_by_length_never_overshoots(model):
    """A finish by length is a count: the scheduler leaves the lane
    out of the step after its last, nothing is computed and thrown
    away, and exactly the tokens asked for are generated."""
    rng = np.random.RandomState(8)
    eng = _ahead_engine(model)
    asked = [1, 2, 3, 9, 5, 1]
    ids = [eng.add_request(rng.randint(0, VOCAB, 5 + n).astype(np.int32),
                           n) for n in asked]
    out = eng.drain()
    assert [len(out[i]) - (5 + n) for i, n in zip(ids, asked)] == asked
    assert eng.overshoot_tokens == 0
    assert eng.tokens_generated == sum(asked)
    # a lane rides exactly as many decode steps as tokens it was owed
    # past its first: no step was launched over a finished lane
    assert eng.decode_steps <= sum(n - 1 for n in asked)


def test_ahead_eos_overshoot_is_discarded_and_blocks_wait(model):
    """An EOS is seen one step late: the lane rides the step already
    launched, that token is discarded and counted, the result is out
    at once — and the lane's blocks are NOT on the free list until
    the step that rode it has completed. drain()'s leak audit sees
    the deferred release done."""
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, VOCAB, 7).astype(np.int32)

    def serve(async_core, eos):
        eng = _ahead_engine(model, async_core=async_core)
        rid = eng.add_request(prompt, 12, eos_token_id=eos)
        held = []
        while eng.num_pending or eng.num_active:
            eng.step()
            slot = eng._slots[0]
            if slot is not None and slot.done is not None:
                # the result is out, the lane still seated: its
                # blocks are held while the unread step rides them
                assert rid in eng._results
                assert slot.ahead == 1 and eng._inflight is None \
                    or slot in eng._inflight.slots
                assert all(eng.cache.refcount(b) == 1
                           for b in slot.blocks)
                assert not set(slot.blocks) & set(eng.cache._free)
                held.append(len(slot.blocks))
        out = eng.drain()               # audits blocks; raises on leak
        return list(map(int, out[rid])), eng, held

    base, _, _ = serve(False, None)
    eos = base[len(prompt) + 4]         # emitted mid-stream
    serial, eng_s, _ = serve(False, eos)
    ahead, eng, held = serve(None, eos)
    assert ahead == serial and len(serial) < len(base)
    assert eng.overshoot_tokens == 1 and held, \
        "the EOS never caught a step in flight — weak test"
    assert eng.tokens_generated == eng_s.tokens_generated
    assert eng.decode_steps == eng_s.decode_steps + 1
    assert eng_s.overshoot_tokens == 0
    assert "engine_overshoot_tokens_total 1" in \
        eng.metrics.render_prometheus()
    assert [e["event"] for e in eng.flight.dump()].count("overshoot") == 1
    assert eng.cache.num_free == eng.cache.num_blocks - 1


def test_ahead_release_with_a_step_unread_is_refused(model):
    """The fence behind the rule: `_release` refuses a lane over which
    a launched step is still unread (what the static gate cannot see
    across `step()` calls, the engine checks as it runs)."""
    rng = np.random.RandomState(2)
    eng = _ahead_engine(model)
    eng.add_request(rng.randint(0, VOCAB, 6).astype(np.int32), 8)
    for _ in range(3):
        eng.step()
    slot = eng._slots[0]
    assert slot.ahead == 1 and eng._inflight is not None
    with pytest.raises(RuntimeError, match="still unread"):
        eng._release(slot)
    eng.drain()


@pytest.mark.parametrize("async_core", [None, False])
def test_last_chunk_syncs_only_in_the_serial_order(model, async_core):
    """The ahead order's `_prefill_step` launches a prompt's last
    chunk and returns: no `device_wait` phase inside it, the first
    token still on the device (fed to this iteration's decode step
    from there) and read after that launch. The serial order reads it
    inside `_prefill_step`, as before."""
    rng = np.random.RandomState(6)
    eng = _ahead_engine(model, async_core=async_core)
    eng.add_request(rng.randint(0, VOCAB, 13).astype(np.int32), 4)
    seen = []
    inner = eng._prefill_step

    def spy():
        n = inner()
        seen.append((eng._first is not None,
                     "device_wait" in eng._phases.totals()))
        return n

    eng._prefill_step = spy
    out = eng.run()
    assert len(out[0]) == 13 + 4
    final = seen[1]                     # 13 tokens: two chunks of 8
    assert seen[0] == (False, False)
    assert final == ((True, False) if async_core is None
                     else (False, True))
    firsts = [e for e in eng.flight.dump() if e["event"] == "first_token"]
    assert len(firsts) == 1


# ---------------------------------------------------------------------------
# satellite: pipeline drain — EOS / shed / drain() with a step in flight
# ---------------------------------------------------------------------------

def test_async_drain_completes_inflight_step(model):
    """drain() called while a dispatched-ahead step is outstanding:
    the in-flight step must complete (not leak device work or
    blocks), results must match the serial engine, and both leak
    audits must pass."""
    rng = np.random.RandomState(9)
    reqs = _trace(rng)

    def serve(async_core):
        eng = GenerationEngine(model, num_slots=3, block_size=4,
                               num_blocks=64, prefill_chunk=8,
                               spec_decode_k=4, async_core=async_core)
        ids = [eng.add_request(p, n) for p, n in reqs]
        # step until a dispatched step is actually in flight, then
        # drain with it outstanding
        for _ in range(16):
            eng.step()
            if async_core and eng._inflight is not None:
                break
        if async_core:
            assert eng._inflight is not None, \
                "trace never left a step in flight — weak test"
        out = eng.drain()               # audits blocks + raises on leak
        return [list(map(int, out[rid])) for rid in ids], eng

    serial, _ = serve(False)
    amode, eng = serve(True)
    assert amode == serial
    assert eng._inflight is None and eng._ahead is None


@pytest.mark.slow
def test_async_eos_mid_pipeline(model):
    """An EOS accepted while the pipeline is warm truncates exactly
    like the serial engine — the in-flight step covering the retired
    lane completes and the lane's blocks come back."""
    rng = np.random.RandomState(5)
    motif = rng.randint(0, VOCAB, 3).astype(np.int32)
    reqs = [(np.tile(motif, 4).astype(np.int32), 12),
            (rng.randint(0, VOCAB, 7).astype(np.int32), 12)]

    def serve(async_core, eos):
        eng = GenerationEngine(model, num_slots=2, block_size=4,
                               num_blocks=64, prefill_chunk=8,
                               spec_decode_k=4, async_core=async_core)
        ids = [eng.add_request(p, n, eos_token_id=eos)
               for p, n in reqs]
        out = eng.drain()
        return [list(map(int, out[rid])) for rid in ids]

    base = serve(False, None)
    # pick an eos the streams actually emit -> mid-run truncation
    eos = int(base[0][len(reqs[0][0]) + 1])
    serial = serve(False, eos)
    amode = serve(True, eos)
    assert amode == serial
    assert any(len(a) < len(b) for a, b in zip(serial, base)), \
        "eos never truncated a stream — weak test"


@pytest.mark.slow
def test_async_shed_midrun_identical(model):
    """Saturation shedding under the async core: same losers (None
    results), same survivors' tokens as serial."""
    rng = np.random.RandomState(13)
    reqs = [(rng.randint(0, VOCAB, rng.randint(3, 10))
             .astype(np.int32), 4) for _ in range(8)]

    def serve(async_core):
        eng = GenerationEngine(model, num_slots=2, block_size=4,
                               num_blocks=64, prefill_chunk=8,
                               max_queue=2, async_core=async_core)
        ids = [eng.add_request(p, n, priority="batch")
               for p, n in reqs]
        out = eng.run()
        shed = sum(out[rid] is None for rid in ids)
        return [None if out[rid] is None else
                list(map(int, out[rid])) for rid in ids], shed

    serial, shed_s = serve(False)
    amode, shed_a = serve(True)
    assert amode == serial
    assert shed_a == shed_s > 0, "queue never saturated — weak test"


# ---------------------------------------------------------------------------
# satellite: compiled-program identity + steady state
# ---------------------------------------------------------------------------

def test_async_steady_state_retraces_nothing(model):
    """A warmed async engine serves new work under
    `expect_traces(0)` on both compiled steps — dispatch-ahead feeds
    the EXACT programs the serial core compiled."""
    rng = np.random.RandomState(2)
    eng = GenerationEngine(model, num_slots=3, block_size=4,
                           num_blocks=64, prefill_chunk=8,
                           spec_decode_k=4, async_core=True)
    _run_trace(eng, _trace(rng))
    assert eng.decode_traces == 1 and eng.prefill_traces == 1
    with jit.expect_traces(eng._decode_pure, 0), \
            jit.expect_traces(eng._prefill_pure, 0):
        eng.add_request(rng.randint(0, VOCAB, 9).astype(np.int32), 5)
        eng.run()


# ---------------------------------------------------------------------------
# satellite: the flight recorder shows the pipeline pipelining
# ---------------------------------------------------------------------------

def test_async_flight_recorder_interleave(model):
    """The black box proves the dispatch-ahead shape: per sequence
    number, `async_dispatch(s)` strictly precedes `async_complete(s)`;
    the pipe never runs deeper than ONE in-flight step (dispatch s+1
    only after complete s); every dispatch is eventually completed."""
    rng = np.random.RandomState(4)
    eng = GenerationEngine(model, num_slots=3, block_size=4,
                           num_blocks=64, prefill_chunk=8,
                           spec_decode_k=4, async_core=True,
                           flight_capacity=4096)
    _run_trace(eng, _trace(rng))
    evs = [(e["event"], e["seq"]) for e in eng.flight.dump()
           if e["event"] in ("async_dispatch", "async_complete")]
    assert evs, "no pipeline events recorded"
    outstanding = None
    seen = 0
    for event, seq in evs:
        if event == "async_dispatch":
            assert outstanding is None, \
                f"dispatch {seq} while {outstanding} in flight"
            assert seq == seen + 1, f"dispatch seq skipped: {evs}"
            outstanding, seen = seq, seq
        else:
            assert outstanding == seq, \
                f"complete {seq} without its dispatch"
            outstanding = None
    assert outstanding is None, "a dispatched step was never completed"
    assert seen > 2, "trace too short to exercise the pipeline"


# ---------------------------------------------------------------------------
# satellite: adapter prefetch rides the pipeline
# ---------------------------------------------------------------------------

def _strong_registry(cfg, ranks=(2, 3), seed=7, scale=0.3, group=None):
    rng = np.random.RandomState(seed)
    reg = AdapterRegistry(cfg, max_rank=4)
    H, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    for aid, r in enumerate(ranks, start=1):
        w = {}
        for site, (i_d, o_d) in (("qkv", (H, 3 * H)), ("out", (H, H)),
                                 ("fc1", (H, I)), ("fc2", (I, H))):
            w[site] = [(rng.randn(r, i_d).astype(np.float32) * scale,
                        rng.randn(o_d, r).astype(np.float32) * scale)
                       for _ in range(L)]
        reg.register(aid, w, scaling=0.5, group=group)
    return reg


@pytest.mark.slow
def test_async_adapter_prefetch_and_evictions(model):
    """Multi-tenant trace under pool pressure (3 hot adapters + base
    over 1-2 usable pages): async serves token-identically to serial
    while
    `adapter_prefetch` events land in the flight recorder, evictions
    still happen mid-run, and the drain audit stays green."""
    registry = _strong_registry(model.config, ranks=(2, 3, 2))
    rng = np.random.RandomState(11)
    reqs = []
    for aid in (1, 2, 0, 3, 0, 1, 3, 2):
        reqs.append((rng.randint(0, VOCAB, rng.randint(2, 12))
                     .astype(np.int32), int(rng.randint(2, 6)), aid))

    def serve(async_core, pages):
        eng = GenerationEngine(model, num_slots=2, block_size=4,
                               num_blocks=64, prefill_chunk=8,
                               adapters=registry,
                               adapter_pool_pages=pages,
                               async_core=async_core)
        ids = [eng.add_request(p, n, adapter_id=a)
               for p, n, a in reqs]
        out = eng.drain()
        return [list(map(int, out[rid])) for rid in ids], eng

    # pressure leg: ONE usable page -> the tenants thrash it, and the
    # prefetcher must never steal it from a live lane
    serial, eng_s = serve(False, pages=2)
    amode, eng_a = serve(True, pages=2)
    assert amode == serial
    assert eng_a.adapter_pool.evictions > 0, \
        "pool never thrashed — weak test"
    # headroom leg: with a spare page the pipeline warms the queue
    # head's adapter behind the dispatched step
    serial, _ = serve(False, pages=3)
    amode, eng_a = serve(True, pages=3)
    assert amode == serial
    prefetches = [e for e in eng_a.flight.dump()
                  if e["event"] == "adapter_prefetch"]
    assert prefetches, "async core never prefetched an adapter page"
    # prefetch is an optimization, not an accounting channel: pages
    # still audit clean (drain() above already asserted leak_check)
    assert eng_a.adapter_pool.leak_check() == []


# ---------------------------------------------------------------------------
# satellite: the gpt_engine_async_overlap bench row
# ---------------------------------------------------------------------------

def test_suite_rows_carry_async_overlap_row():
    import bench_ops

    assert "gpt_engine_async_overlap" in bench_ops.SUITE_ROWS


@pytest.mark.slow
def test_async_overlap_bench_runner_tiny():
    """The `gpt_engine_async_overlap` runner end-to-end on a tiny
    config — its in-runner gates ARE the acceptance criteria: per-rep
    token identity, async overlappable host gap
    (schedule+draft_propose+adapter_swap) strictly below serial's,
    async device fraction no lower. Here we only re-check the record
    shape; the runner already threw if any gate failed."""
    from paddle_tpu.models import GPTConfig

    import bench_ops

    # hidden=256/layers=3 keeps the step device-bound even on the CPU
    # runner: the device-fraction gate (async >= serial) only holds
    # structurally when there IS device time left to hide host work
    # behind — a host-bound toy model lets the async core drive the
    # device_wait residual toward zero, which is the pipeline working,
    # not a regression.
    cfg = GPTConfig.tiny(vocab=VOCAB, hidden=256, layers=3, heads=4,
                         seq=128)
    rec = bench_ops._engine_async_overlap_case(
        model_cfg=cfg, num_requests=12, block_size=8, max_new=6)()
    assert "ms" in rec and rec["ms"] > 0
    for mode in ("serial", "async"):
        phases = rec[mode]["phase_ms_per_step_warm"]
        assert "dispatch" in phases and "adapter_swap" in phases
        assert 0.0 <= rec[mode]["device_fraction_warm"] <= 1.0
    assert rec["async"]["host_overlap_gap_ms"] \
        < rec["serial"]["host_overlap_gap_ms"]


# ---------------------------------------------------------------------------
# satellite: fleet replicas run the async core
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_async_replicas_token_exact(model):
    """A disaggregated fleet with every replica on the async core
    (`async_core=True` passed through to the engines it builds) stays
    token-exact vs the serial bare engine, and the prestaged handoff
    flush still drains every parked prefill."""
    rng = np.random.RandomState(6)
    trace = [(rng.randint(0, VOCAB, int(rng.randint(3, 30))), 5)
             for _ in range(6)]

    def eng_serve():
        eng = GenerationEngine(model, num_slots=4, block_size=8,
                               async_core=False)
        ids = [eng.add_request(p, max_new_tokens=n) for p, n in trace]
        out = eng.run()
        return {i: list(map(int, out[i])) for i in ids}

    ref = eng_serve()
    fleet = ServingFleet(model, num_slots=4, block_size=8,
                         num_replicas=1, num_prefill_replicas=1,
                         async_core=True)
    ids = [fleet.add_request(p, max_new_tokens=n) for p, n in trace]
    out = fleet.run()
    assert {i: list(map(int, out[i])) for i in ids} == ref
    for rep in fleet._replicas.values():
        assert rep.engine.async_core is True
