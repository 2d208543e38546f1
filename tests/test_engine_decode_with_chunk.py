"""The ahead order's one program for a prefill chunk AND a decode step
(`ServingSpec.decode_with_chunk`), on the tiny hybrid decoder on the CPU:
an iteration that holds a chunk and decode lanes launches it in place of
the two programs; it stays unread when the call returns, the prompt's
first token is read with its decode tokens by the next call's one wait,
and the lane joins the decode step after it, fed from the device. Against
the serial order (the two programs, one after the other): the same tokens
for every request, whatever the admissions, the prompt lengths, the ways
to finish and the draws. A model that offers no such step (the GPT-2
block) keeps its two programs.
"""
import numpy as np
import pytest

from paddle_tpu.inference.engine import GenerationEngine
from paddle_tpu.inference.sampling import SamplingParams
from paddle_tpu.observability.metrics import series_total
from test_nemotron_h import engine_for, prompts, seeded

pytestmark = pytest.mark.usefixtures("fused_step_offered")
LENGTHS = [5, 19, 33, 12, 16, 7]          # one chunk of 16, and several


def serve(model, cfg, n_new=9, late=3, eos=None, sampling=False, **kw):
    """More requests than slots, `late` of them added after two
    iterations, so chunks meet lanes that decode. `eos` and `n_new` may
    be one value or one a request. -> (streams in request order, engine)."""
    asked = prompts(cfg, LENGTHS)
    per = lambda v, i: v[i] if isinstance(v, (list, tuple)) else v
    eng = engine_for(model, sampling=sampling, **kw)

    def add(i):
        sp = SamplingParams(temperature=0.9, top_k=24, top_p=0.95,
                            seed=100 + i) if sampling and i % 3 else None
        return eng.add_request(asked[i], max_new_tokens=per(n_new, i),
                               eos_token_id=per(eos, i),
                               sampling_params=sp)

    ids = [add(i) for i in range(len(asked) - late)]
    for _ in range(2):
        eng.step()
    ids += [add(i) for i in range(len(asked) - late, len(asked))]
    out = eng.drain()                     # audits blocks and state rows
    return [out[i] for i in ids], eng


@pytest.fixture(scope="module")
def hybrid():
    return seeded("ME*E")


@pytest.mark.parametrize("pattern", ["ME*E", "M*", "E*E", "E*M"])
def test_the_fused_order_serves_the_serial_orders_tokens(pattern):
    model, cfg = seeded(pattern)
    serial, eng_s = serve(model, cfg, async_core=False)
    ahead, eng = serve(model, cfg)
    assert ahead == serial
    assert [len(a) for a in ahead] == [n + 9 for n in LENGTHS]
    # the fused step engaged, and counts as a decode step and a chunk
    assert eng._fused is not None and eng_s._fused is None
    assert 0 < eng.decode_steps_with_chunk <= eng.decode_steps
    assert series_total(eng.metrics_snapshot(),
                        "engine_decode_steps_with_chunk_total") == \
        eng.decode_steps_with_chunk
    assert eng_s.decode_steps_with_chunk == 0
    chunks = sum(-(-n // 16) for n in LENGTHS)
    for e in (eng, eng_s):
        assert f"engine_prefill_chunks_total {chunks}" in \
            e.metrics.render_prometheus()
    # one trace a program, however many requests: a compile of the fused
    # step shows in `decode_traces`, where the benchmark's guard looks
    assert (eng.decode_traces, eng.prefill_traces) == (2, 1)
    assert (eng_s.decode_traces, eng_s.prefill_traces) == (1, 1)
    assert series_total(eng.metrics_snapshot(),
                        "engine_decode_recompiles_total") == 0
    assert eng.overshoot_tokens == 0
    assert eng.decode_steps_ahead >= eng.decode_steps - 2


@pytest.mark.parametrize("slots,chunk", [(2, 8), (3, 16), (6, 4)])
def test_whatever_the_lanes_and_the_chunk(hybrid, slots, chunk):
    """Prompts of one chunk and of up to nine, two to six lanes: a lane
    in the middle of its prompt rides fused steps that hand out no token,
    and the one that ends it hands the first."""
    model, cfg = hybrid
    kw = dict(num_slots=slots, prefill_chunk=chunk)
    serial, _ = serve(model, cfg, async_core=False, **kw)
    ahead, eng = serve(model, cfg, **kw)
    assert ahead == serial
    assert eng.decode_steps_with_chunk > 0
    assert eng.decode_traces == 2


def test_the_fused_step_stays_unread_and_its_first_token_waits_a_call(
        hybrid):
    """Call c launches the fused step and returns with it unread: the
    prompt's first token is not out. Call c+1 launches the next decode
    step over BOTH lanes — the new one fed from the device — and only
    then reads the fused step: first token and decode token together."""
    model, cfg = hybrid
    a, b = prompts(cfg, [5, 9])
    eng = engine_for(model)
    eng.add_request(a, max_new_tokens=12)
    eng.step()
    eng.step()
    assert eng.decode_steps_with_chunk == 0     # a chunk alone, a step alone
    eng.add_request(b, max_new_tokens=6)
    eng.step()                                          # call c
    fused = eng._inflight
    lane_b = eng._slots[1]
    assert eng.decode_steps_with_chunk == 0             # counted when read
    assert fused.first is not None and fused.first.slots == [lane_b]
    assert fused.runnable == [0] and eng._first is None
    assert lane_b.generated == [] and lane_b.ahead == 1
    assert not lane_b.prefilling
    firsts = [e for e in eng.flight.dump() if e["event"] == "first_token"]
    assert len(firsts) == 1                             # a's alone
    eng.step()                                          # call c+1
    assert eng._inflight.runnable == [0, 1] and eng._inflight.first is None
    assert len(lane_b.generated) == 1 and lane_b.ahead == 1
    assert eng.decode_steps_with_chunk == 1
    firsts = [e for e in eng.flight.dump() if e["event"] == "first_token"]
    assert len(firsts) == 2
    out = eng.drain()
    alone = engine_for(model, async_core=False)
    alone.add_request(b, max_new_tokens=6)
    assert out[1] == alone.run()[0]


def test_a_first_token_that_is_the_eos_vacates_the_lane_a_step_late(
        hybrid):
    """The first token a fused step hands out is the request's EOS. The
    host reads it a call later, when the next decode step already rides
    the lane: the result is out at once, that step's token is discarded,
    and blocks and the row of state go back only after it completed."""
    model, cfg = hybrid
    base, _ = serve(model, cfg, async_core=False)
    eos = [None] * 3 + [stream[n] for stream, n in
                        zip(base[3:], LENGTHS[3:])]
    serial, _ = serve(model, cfg, eos=eos, async_core=False)
    assert [len(s) for s in serial[3:]] == [n + 1 for n in LENGTHS[3:]]
    ahead, eng = serve(model, cfg, eos=eos)
    assert ahead == serial
    assert eng.decode_steps_with_chunk > 0 and eng.overshoot_tokens > 0

    a, b = prompts(cfg, [5, 9])
    eng = engine_for(model)
    eng.add_request(a, max_new_tokens=12)
    eng.step()
    eng.step()
    alone = engine_for(model, async_core=False)
    alone.add_request(b, max_new_tokens=2)
    first_b = alone.run()[0][9]
    rid = eng.add_request(b, max_new_tokens=6, eos_token_id=first_b)
    eng.step()                          # the fused step, unread
    lane_b = eng._slots[1]
    assert eng._inflight.first is not None and rid not in eng._results
    eng.step()                          # rides the next step; EOS read
    assert eng.decode_steps_with_chunk == 1
    assert eng._results[rid] == list(map(int, b)) + [first_b]
    assert eng._slots[1] is lane_b and lane_b.done == "eos"
    assert lane_b.ahead == 1 and eng.cache.state_rows_used == 2
    held = eng.cache.num_free
    with pytest.raises(RuntimeError, match="still unread"):
        eng._release(lane_b)
    eng.step()                          # that step completed: vacated
    assert eng._slots[1] is None and eng.overshoot_tokens == 1
    assert eng.cache.state_rows_used == 1 and eng.cache.num_free > held
    eng.drain()


@pytest.mark.parametrize("n_new", [1, 2, [9, 9, 9, 1, 2, 1]])
def test_a_finish_by_length_never_overshoots(hybrid, n_new):
    """A count needs no token: a request of one new token never rides a
    decode step (its fused step's first token is its last), one of two
    rides exactly one."""
    model, cfg = hybrid
    serial, eng_s = serve(model, cfg, n_new=n_new, async_core=False)
    ahead, eng = serve(model, cfg, n_new=n_new)
    assert ahead == serial
    want = n_new if isinstance(n_new, list) else [n_new] * len(LENGTHS)
    assert [len(a) - n for a, n in zip(ahead, LENGTHS)] == want
    assert eng.overshoot_tokens == 0
    assert eng.tokens_generated == sum(want) == eng_s.tokens_generated
    assert eng.step_counter_totals["decode_live_lanes"] == \
        sum(want) - len(want)


@pytest.mark.parametrize("pattern", ["ME*E", "M*"])
def test_sampled_draws_are_the_two_program_orders(pattern):
    """`sampling=True`: the fused step draws the chunk's first token with
    the fold of `plen - 1` and the decode rows with their positions, as
    the two programs do; greedy lanes beside sampled ones."""
    model, cfg = seeded(pattern)
    serial, _ = serve(model, cfg, sampling=True, async_core=False)
    ahead, eng = serve(model, cfg, sampling=True)
    greedy, _ = serve(model, cfg, async_core=False)
    assert ahead == serial
    assert eng.decode_steps_with_chunk > 0
    assert [a == g for a, g in zip(ahead, greedy)] == \
        [i % 3 == 0 for i in range(len(LENGTHS))]


def test_a_model_that_offers_no_fused_step_keeps_its_two_programs():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    gpt = GPTForCausalLM(GPTConfig.tiny())
    gpt.eval()
    rng = np.random.default_rng(3)
    eng = GenerationEngine(gpt, num_slots=2, block_size=4, prefill_chunk=8)
    assert eng._fused is None and eng.async_core
    for n in (5, 19):
        eng.add_request(rng.integers(0, 128, n, dtype=np.int32), 6)
    eng.step()
    eng.step()
    eng.add_request(rng.integers(0, 128, 11, dtype=np.int32), 6)
    out = eng.drain()
    assert sorted(len(v) for v in out.values()) == [11, 17, 25]
    assert eng.decode_steps_with_chunk == 0
    assert (eng.decode_traces, eng.prefill_traces) == (1, 1)
    assert "decode_steps_with_chunk" not in eng.metrics.render_prometheus()
