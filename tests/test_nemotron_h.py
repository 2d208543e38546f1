"""The `nemotron_h` hybrid decoder (Mamba-2 + LatentMoE + grouped-KV
attention by a layer-pattern string) at a tiny size on the CPU, float32:
each mixer and the whole model against the benchmark's plain reference on
seeded weights; chunked prefill then decode through `GenerationEngine`
against the reference's full forward; the slots' recurrent state zeroed
with the slot; the features that need a snapshot of that state refused;
the share test (the routed parts of all the shares, with the shared
expert once, add up to the uncut layer; the sliced vocabulary's logits
are the whole table's first rows); the kernels (interpreter) against the
plain XLA forms; the dropless dispatch.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights
from benchmarks.reference import common, nemotron_h as ref, stepwise
from paddle_tpu.distributed import moe
from paddle_tpu.inference.engine import GenerationEngine
from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                          NemotronHForCausalLM)
from paddle_tpu.ops import ssm

SEED = 11
REF_KEYS = (
    "hidden_size", "hybrid_override_pattern", "mamba_num_heads",
    "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "n_routed_experts", "router_experts", "expert_offset",
    "num_experts_per_tok", "moe_latent_size", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "routed_scaling_factor",
    "norm_eps", "vocab_size", "initializer_range", "conv_init_std")


def ref_cfg(cfg):
    """What the reference reads of the program's configuration, and the
    spread of `A_log` in the seeded weights: at the matrices' spread
    every head has A = -1 and forgets a state within a few tokens, so a
    stale row or a lost carry would pass; at 2 some heads remember the
    whole of these short sequences."""
    return dict({k: getattr(cfg, k) for k in REF_KEYS},
                a_log_init_std=2.0)


def seeded(pattern="ME*E", seed=SEED, **kw):
    """The program's model with the reference's seeded weights bound."""
    cfg = NemotronHConfig.tiny(pattern=pattern, router_experts=16,
                               n_routed_experts=8, expert_offset=4, **kw)
    model = NemotronHForCausalLM(cfg)
    model.eval()
    arrays = weights.make_all(seed, ref.param_spec(ref_cfg(cfg)),
                              jnp.float32)
    named = dict(model.named_parameters())
    assert set(named) == set(arrays)
    for name, p in named.items():
        assert tuple(p.shape) == tuple(arrays[name].shape), name
        p._in_place_update(arrays[name])
    return model, cfg


def reference_logits(cfg, ids, seed=SEED):
    return np.asarray(stepwise.logits_of(
        ref.build(ref_cfg(cfg), common.MM["f32"]), seed,
        np.asarray(ids, np.int32), jnp.float32))


def prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
            for n in lengths]


# -- the mixers and the whole model against the reference ----------------------

@pytest.mark.parametrize("pattern", ["M", "*", "E", "MM", "ME*E"])
def test_forward_matches_the_reference(pattern):
    """Each mixer alone (`M`, `*`, `E`), two scans in a row, and a
    pattern holding all three: the program's whole-sequence forward
    against the reference's, float32."""
    model, cfg = seeded(pattern)
    ids = np.stack(prompts(cfg, [21, 21]))
    got = np.asarray(model(ids)._array)
    want = reference_logits(cfg, ids)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_state_matters_to_the_logits():
    """The seeded weights must let the recurrence show: with the conv's
    taps at the matrices' 0.02 the state would be nought beside `D x`;
    at `conv_init_std` a carried state left at nought moves the logits
    by far more than any tolerance here."""
    model, cfg = seeded("M")
    ids = np.stack(prompts(cfg, [16]))
    whole = np.asarray(model(ids)._array)[0]
    tail = np.asarray(model(ids[:, 8:])._array)[0]      # state forgotten
    assert np.abs(whole[8:] - tail).max() > 1e-2


# -- through the engine -----------------------------------------------------

def engine_for(model, **kw):
    base = dict(num_slots=2, block_size=4, prefill_chunk=16,
                max_model_len=64)
    base.update(kw)
    return GenerationEngine(model, **base)


@pytest.mark.parametrize("pattern", ["ME*E", "M*", "E*M"])
def test_engine_chunked_prefill_then_decode_agrees_with_the_reference(
        pattern, fused_step_offered):
    """Prompts shorter and longer than a chunk, more requests than
    slots: every served token is the reference's own first choice given
    the tokens before it, by the reference's full forward over the whole
    sequence (no cache, no chunks) — unless the reference's best two
    logits tie closer than 1e-4."""
    model, cfg = seeded(pattern)
    asked = prompts(cfg, [5, 19, 33, 12, 16])
    eng = engine_for(model)
    for p in asked:
        eng.add_request(p, max_new_tokens=9)
    out = eng.run()
    # one decode step, and one that carries a chunk (the second request
    # is admitted beside a lane that decodes)
    assert eng.decode_traces == 2 and eng.prefill_traces == 1
    assert eng.decode_steps_with_chunk > 0
    assert eng.cache.state_rows_used == 0 and eng.cache.num_free == \
        eng.cache.num_blocks - 1
    compared = 0
    for rid, tokens in out.items():
        tokens = np.asarray(tokens, np.int32)
        plen = len(asked[rid])
        assert tokens[:plen].tolist() == asked[rid].tolist()
        rows = reference_logits(cfg, tokens[None, :-1])[0][plen - 1:]
        served = rows[np.arange(len(rows)), tokens[plen:]]
        assert (rows.max(-1) - served).max() < 1e-4
        compared += len(rows)
    assert compared == 9 * len(asked)


def test_engine_step_functions_give_the_references_logits():
    """The spec's own step functions, driven by hand over the engine's
    pools: two prefill chunks (the second padded past the prompt) carry
    the conv window and the state, then decode steps read them; the
    logits of every fed position against the reference's."""
    from paddle_tpu.core.tensor import Tensor

    model, cfg = seeded("ME*EM")
    eng = engine_for(model, prefill_chunk=8)
    spec, cache = eng.spec, eng.cache
    (seq,) = prompts(cfg, [18])
    plen, chunk = 11, 8
    want = reference_logits(cfg, seq[None])[0]
    blocks = cache.allocate(-(-len(seq) // 4))
    row = np.zeros(eng.max_blocks, np.int32)
    row[:len(blocks)] = blocks
    state_row = cache.allocate_state()
    wrap = Tensor._wrap
    kp, vp, state = cache.kpool, cache.vpool, cache.state
    got = {}
    for start in range(0, plen, chunk):
        ids = np.zeros((1, chunk), np.int32)
        n = min(chunk, plen - start)
        ids[0, :n] = seq[start:start + n]
        r = spec.prefill_chunk(
            wrap(jnp.asarray(ids)), wrap(jnp.int32(start)), wrap(kp),
            wrap(vp), wrap(jnp.asarray(row)), wrap(jnp.int32(plen)),
            slot_state=state, state_row=jnp.int32(state_row))
        kp, vp, state = r.kpool._array, r.vpool._array, r.slot_state
        logits = np.asarray(spec.logits(r.hidden)._array)[0]
        for j in range(n):
            got[start + j] = logits[j]
    for pos in range(plen, len(seq)):
        tokens = np.zeros((eng.num_slots, 1), np.int32)
        tokens[1, 0] = seq[pos]               # lane 1; lane 0 is idle
        tables = np.zeros((eng.num_slots, eng.max_blocks), np.int32)
        tables[1] = row
        r = spec.decode(
            wrap(jnp.asarray(tokens)),
            wrap(jnp.asarray(np.array([0, pos], np.int32))), wrap(kp),
            wrap(vp), wrap(jnp.asarray(tables)),
            backend=eng.attention_backend, slot_state=state,
            state_rows=jnp.asarray(np.array([0, state_row], np.int32)))
        kp, vp, state = r.kpool._array, r.vpool._array, r.slot_state
        got[pos] = np.asarray(spec.logits(r.hidden)._array)[1, 0]
        assert np.asarray(r.counters)[0] == 1           # one live lane
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], atol=2e-5,
                                   err_msg=f"position {pos}")


def _prefilled(spec, eng, seq, plen, chunk):
    """Blocks and a state row for one prompt, its first `plen` tokens
    pushed through `prefill_chunk`: -> (block row, state row)."""
    from paddle_tpu.core.tensor import Tensor

    cache, wrap = eng.cache, Tensor._wrap
    blocks = cache.allocate(-(-len(seq) // eng.block_size))
    row = np.zeros(eng.max_blocks, np.int32)
    row[:len(blocks)] = blocks
    kw, state_row = {}, 0
    if cache.state:
        state_row = cache.allocate_state()
        kw["state_row"] = jnp.int32(state_row)
    for start in range(0, plen, chunk):
        ids = np.zeros((1, chunk), np.int32)
        n = min(chunk, plen - start)
        ids[0, :n] = seq[start:start + n]
        if cache.state:
            kw["slot_state"] = cache.state
        r = spec.prefill_chunk(
            wrap(jnp.asarray(ids)), wrap(jnp.int32(start)),
            wrap(cache.kpool), wrap(cache.vpool), wrap(jnp.asarray(row)),
            wrap(jnp.int32(plen)), **kw)
        cache.kpool, cache.vpool = r.kpool._array, r.vpool._array
        cache.state = r.slot_state
    return row, state_row


@pytest.mark.parametrize("start,plen", [(8, 13), (0, 13)],
                         ids=["last_chunk_padded", "first_chunk_full"])
@pytest.mark.parametrize("pattern", ["ME*EM", "M*", "E*E"])
def test_decode_with_chunk_is_the_chunk_then_the_decode_step(pattern,
                                                             start, plen):
    """The fused step against `prefill_chunk` then `decode` over the same
    pools, rows of state and ids: two lanes decode (one idle between
    them) while a third lane's chunk runs — the chunk's hidden rows, the
    decode rows', the K/V pools and every row of state agree; the
    counters are the decode lanes' and ONE expert product's; a chunk's
    padding and an idle lane are routed nowhere."""
    from paddle_tpu.core.tensor import Tensor

    model, cfg = seeded(pattern)
    chunk = 8
    eng = engine_for(model, prefill_chunk=chunk, num_slots=4)
    spec, cache, wrap = eng.spec, eng.cache, Tensor._wrap
    a, b, c = prompts(cfg, [7, 11, plen])
    row_a, state_a = _prefilled(spec, eng, a, 6, chunk)
    row_b, state_b = _prefilled(spec, eng, b, 10, chunk)
    row_c, state_c = _prefilled(spec, eng, c, start, chunk)
    kp, vp, state = cache.kpool, cache.vpool, cache.state
    has_state = bool(state)

    def host_rows(idle_token, pad_token):
        ids = np.full((1, chunk), pad_token, np.int32)
        n = min(chunk, plen - start)
        ids[0, :n] = c[start:start + n]
        tokens = np.full((4, 1), idle_token, np.int32)
        tokens[0, 0], tokens[2, 0] = a[6], b[10]
        tables = np.zeros((4, eng.max_blocks), np.int32)
        tables[0], tables[2] = row_a, row_b
        return (jnp.asarray(ids), jnp.asarray(tokens),
                jnp.asarray(np.array([6, 0, 10, 0], np.int32)),
                jnp.asarray(tables))

    def lanes(rows):
        return dict(slot_state=state, state_rows=jnp.asarray(
            np.array(rows, np.int32))) if has_state else {}

    def fused(ids, tokens, positions, tables, rows):
        kw = lanes(rows)
        if has_state:
            kw["state_row"] = jnp.int32(state_c)
        r, hidden = spec.decode_with_chunk(
            wrap(ids), wrap(jnp.int32(start)), wrap(jnp.asarray(row_c)),
            wrap(jnp.int32(plen)), wrap(tokens), wrap(positions),
            wrap(tables), wrap(kp), wrap(vp),
            backend=eng.attention_backend, **kw)
        return r, np.asarray(hidden._array)

    ids, tokens, positions, tables = host_rows(0, 0)
    live_rows = [state_a, 0, state_b, 0]
    r1 = spec.prefill_chunk(
        wrap(ids), wrap(jnp.int32(start)), wrap(kp), wrap(vp),
        wrap(jnp.asarray(row_c)), wrap(jnp.int32(plen)),
        **(dict(slot_state=state, state_row=jnp.int32(state_c))
           if has_state else {}))
    kw = lanes(live_rows)
    if has_state:
        kw["slot_state"] = r1.slot_state
    r2 = spec.decode(wrap(tokens), wrap(positions), r1.kpool, r1.vpool,
                     wrap(tables), backend=eng.attention_backend, **kw)
    got, hidden = fused(ids, tokens, positions, tables, live_rows)

    n = min(chunk, plen - start)
    live = [0, 2] if has_state else [0, 1, 2, 3]
    tol = dict(atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got.hidden._array)[0, :n],
                               np.asarray(r1.hidden._array)[0, :n], **tol)
    np.testing.assert_allclose(hidden[live],
                               np.asarray(r2.hidden._array)[live], **tol)
    for mine, theirs in zip(
            (got.kpool._array, got.vpool._array) + tuple(got.slot_state),
            (r2.kpool._array, r2.vpool._array) + tuple(r2.slot_state)):
        np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs),
                                   **tol)

    names = [name for name, _ in spec.step_counters]
    mine = dict(zip(names, np.asarray(got.counters).tolist()))
    plain = dict(zip(names, np.asarray(r2.counters).tolist()))
    assert mine["decode_live_lanes"] == len(live) == \
        plain["decode_live_lanes"]
    assert (mine["decode_steps_with_chunk"],
            plain["decode_steps_with_chunk"]) == (1, 0)
    # other ids in the padding and the idle lanes move nothing (where
    # no state tells an idle lane, every lane counts: its id stays)
    other, hidden2 = fused(*host_rows(5 if has_state else 0, 7),
                           live_rows)
    np.testing.assert_array_equal(hidden2[live], hidden[live])
    if "E" not in pattern:
        return
    np.testing.assert_array_equal(np.asarray(other.counters),
                                  np.asarray(got.counters))
    if has_state:
        # the chunk's rows alone: every lane idle
        alone, _ = fused(ids, tokens, positions, tables, [0, 0, 0, 0])
        alone = dict(zip(names, np.asarray(alone.counters).tolist()))
        layers = pattern.count("E")
        assert 0 < alone["moe_assignments_held"] <= \
            n * layers * cfg.num_experts_per_tok
        assert mine["moe_assignments_held"] == \
            alone["moe_assignments_held"] + plain["moe_assignments_held"]
        # an expert the chunk and a lane both touch counts once
        assert max(alone["moe_experts_touched"],
                   plain["moe_experts_touched"]) \
            <= mine["moe_experts_touched"] \
            < alone["moe_experts_touched"] + plain["moe_experts_touched"]
        # and its rows share tiles: no more than the two products' tiles
        assert max(alone["moe_row_tiles"], plain["moe_row_tiles"],
                   mine["moe_experts_touched"]) \
            <= mine["moe_row_tiles"] \
            <= alone["moe_row_tiles"] + plain["moe_row_tiles"]


def test_a_spec_without_the_fused_step_says_so():
    from paddle_tpu.inference.serving_spec import ServingSpec
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    spec = GPTForCausalLM(GPTConfig.tiny()).serving_spec()
    assert not spec.offers_decode_with_chunk
    with pytest.raises(NotImplementedError):
        ServingSpec.decode_with_chunk(spec, *[None] * 9)


def test_a_slot_another_request_just_left_starts_from_nought():
    """One lane: the second request sits where the first sat. Its tokens
    are what it gets alone on a fresh engine — the row of recurrent state
    was zeroed at admission."""
    model, cfg = seeded("ME*E")
    first, second = prompts(cfg, [23, 14])
    eng = engine_for(model, num_slots=1)
    a = eng.add_request(first, max_new_tokens=8)
    b = eng.add_request(second, max_new_tokens=8)
    both = eng.run()
    alone = engine_for(model, num_slots=1)
    c = alone.add_request(second, max_new_tokens=8)
    assert both[b] == alone.run()[c]
    assert len(both[a]) == 23 + 8
    assert eng.cache.state_rows_used == 0
    assert "engine_state_slots_used 0" in eng.metrics.render_prometheus()


def _serve_with_admissions_midrun(model, cfg, eos=None, **kw):
    """More requests than slots, half of them added after two
    iterations, so rows of state are taken, given back and taken again
    while decode steps are in flight."""
    asked = prompts(cfg, [5, 19, 33, 12, 16, 7])
    eng = engine_for(model, **kw)
    ids = [eng.add_request(p, max_new_tokens=9, eos_token_id=eos)
           for p in asked[:3]]
    for _ in range(2):
        eng.step()
    ids += [eng.add_request(p, max_new_tokens=9, eos_token_id=eos)
            for p in asked[3:]]
    out = eng.drain()                   # audits blocks and state rows
    return [out[i] for i in ids], eng


@pytest.mark.parametrize("pattern", ["ME*E", "M*"])
def test_ahead_and_serial_orders_serve_the_same_tokens(
        pattern, fused_step_offered):
    """The hybrid decoder rides the default loop: decode step N+1 is
    launched from step N's tokens on the device, the rows of state are
    host rows, the program that zeroes a row, the chunk that first reads
    it and the decode steps follow one another through the state arrays
    they hand on. Token for token what the serial order serves."""
    model, cfg = seeded(pattern)
    serial, eng_s = _serve_with_admissions_midrun(model, cfg,
                                                  async_core=False)
    ahead, eng = _serve_with_admissions_midrun(model, cfg)
    assert eng.async_core and not eng_s.async_core
    assert ahead == serial
    assert eng.decode_steps_ahead >= eng.decode_steps - 2 > 0
    assert eng_s.decode_steps_ahead == 0
    assert eng.overshoot_tokens == 0
    assert eng.tokens_generated == 9 * 6 == eng_s.tokens_generated
    # the lanes that decoded agree; the experts' counts depend on which
    # rows share a step, and the ahead order's also hold the rows of
    # the chunks that rode a decode step
    assert eng.step_counter_totals["decode_live_lanes"] == \
        eng_s.step_counter_totals["decode_live_lanes"]
    if "E" in pattern:
        assert eng.step_counter_totals["moe_assignments_held"] > \
            eng_s.step_counter_totals["moe_assignments_held"]
    assert eng.decode_steps_with_chunk > 0 == eng_s.decode_steps_with_chunk
    assert eng.cache.state_rows_used == 0


def test_an_eos_seen_a_step_late_keeps_the_row_until_its_step_ends():
    """The ahead order reads an EOS one step late: the lane rides one
    more decode step, which updates its row of state once more. The
    row goes back only after that step, the next owner starts from
    nought, and the streams are the serial order's."""
    model, cfg = seeded("ME*E")
    base, _ = _serve_with_admissions_midrun(model, cfg, async_core=False)
    eos = base[1][19 + 3]               # a token the streams do emit
    serial, _ = _serve_with_admissions_midrun(model, cfg, eos=eos,
                                              async_core=False)
    ahead, eng = _serve_with_admissions_midrun(model, cfg, eos=eos)
    assert ahead == serial
    assert any(len(a) < len(b) for a, b in zip(serial, base))
    assert eng.overshoot_tokens > 0
    assert eng.cache.state_rows_used == 0


@pytest.mark.parametrize("async_core", [None, False])
def test_a_state_row_left_unzeroed_changes_the_tokens(async_core,
                                                      monkeypatch):
    """The planted fault of PR 28, under both orders: a row handed out
    as the request before left it. The next request in that lane reads
    another request's state, and its tokens change."""
    from paddle_tpu.inference.engine import PagedKVCache

    model, cfg = seeded("ME*E")
    sound, _ = _serve_with_admissions_midrun(model, cfg,
                                             async_core=async_core)

    def unzeroed(self):
        return self._free_rows.pop() if self._free_rows else None

    monkeypatch.setattr(PagedKVCache, "allocate_state", unzeroed)
    faulty, _ = _serve_with_admissions_midrun(model, cfg,
                                              async_core=async_core)
    assert faulty[:2] == sound[:2]      # first owners of their rows
    assert faulty != sound


def test_engine_counts_the_experts_load_and_the_state_rows(
        fused_step_offered):
    model, cfg = seeded("ME*E")
    eng = engine_for(model)
    for p in prompts(cfg, [9, 9]):
        eng.add_request(p, max_new_tokens=6)
    eng.run()
    totals = eng.step_counter_totals
    # one prefill chunk an iteration, the second request's inside the
    # first's first decode step: its first token leaves with that step
    # and it decodes from the next, 5 decode steps each
    assert eng.decode_steps == 7 and totals["decode_live_lanes"] == 10
    assert totals["decode_steps_with_chunk"] == 1 == \
        eng.decode_steps_with_chunk
    # 2 E layers, 3 of 16 experts a token, 8 held: at most 3 a token,
    # and the 9 rows of the chunk that rode a decode step
    assert 0 < totals["moe_assignments_held"] <= (10 + 9) * 2 * 3
    assert 0 < totals["moe_experts_touched"] <= \
        totals["moe_assignments_held"]
    assert 1 <= totals["moe_max_expert_load"] <= 1 + 9
    assert totals["moe_experts_touched"] <= totals["moe_row_tiles"] \
        <= totals["moe_assignments_held"]
    text = eng.metrics.render_prometheus()
    for name in ("engine_moe_assignments_held_total",
                 "engine_decode_steps_with_chunk_total 1",
                 "engine_moe_experts_touched_total",
                 "engine_moe_row_tiles_total",
                 "engine_moe_max_expert_load",
                 "engine_state_slots_used"):
        assert name in text
    for phase in ("state_alloc", "state_free"):
        assert f'phase="{phase}"' in text


def test_the_row_tiles_are_each_experts_load_in_whole_tiles(
        fused_step_offered, expert_loads):
    """`moe_row_tiles` over a run: the sum over the E layers and the
    decode steps (a fused step's one product included, a chunk's alone
    not) of ceil(load / tile rows), each load as the product saw it."""
    import jax

    model, cfg = seeded("ME*E")
    eng = engine_for(model)
    for p in prompts(cfg, [9, 9]):
        eng.add_request(p, max_new_tokens=6)
    eng.run()
    jax.effects_barrier()
    totals = eng.step_counter_totals
    assert totals["decode_steps_with_chunk"] == 1
    # 2 lanes decode, 16 rows a chunk: a decode step's product holds 2
    # rows, a fused step's 18, a chunk's alone 16
    stepped = [s for rows, s in expert_loads if rows in (2, 2 + 16)]
    assert len(stepped) == 2 * eng.decode_steps
    assert totals["moe_row_tiles"] == sum(
        int((-(-s // 4)).sum()) for s in stepped)
    assert totals["moe_row_tiles"] > totals["moe_experts_touched"] \
        == sum(int((s > 0).sum()) for s in stepped)


@pytest.mark.parametrize("kwargs,feature", [
    (dict(enable_prefix_cache=True), "prefix_cache"),
    (dict(spec_decode_k=2), "spec_decode"),
    (dict(kv_dtype="int8"), "kv_int8"),
    (dict(weight_dtype="int8"), "weight_int8"),
])
def test_what_needs_a_state_snapshot_is_refused_at_construction(kwargs,
                                                                feature):
    model, _ = seeded("M*")
    with pytest.raises(ValueError, match=feature + " is not served"):
        engine_for(model, **kwargs)


def test_forks_handoffs_shards_and_adapters_are_refused():
    from paddle_tpu.inference.sampling import SamplingParams

    model, cfg = seeded("M*")
    eng = engine_for(model, sampling=True)
    assert eng.enable_prefix_cache is False
    (p,) = prompts(cfg, [6])
    with pytest.raises(ValueError, match="fork is not served.*snapshot"):
        eng.best_of_n(p, 2, 4, sampling_params=SamplingParams(
            temperature=1.0, seed=1))
    with pytest.raises(ValueError, match="handoff is not served"):
        eng.add_request(p, 1, prefill_only=True)
    with pytest.raises(ValueError, match="handoff is not served"):
        eng.adopt_request(p, 3, [1, 2], 4)
    with pytest.raises(ValueError, match="not sharded"):
        engine_for(model, mp_degree=2)
    with pytest.raises(ValueError, match="take no adapters"):
        engine_for(model, adapters=object())
    with pytest.raises(ValueError, match="grouped KV"):
        engine_for(model, attention_backend="pallas")


def test_a_model_without_recurrent_layers_keeps_every_option():
    """The refusals follow the state, not the model's name: the same
    engine serves the GPT-2 block with its prefix cache on and no state
    rows, and its compiled steps take no state argument."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    gpt = GPTForCausalLM(GPTConfig.tiny())
    gpt.eval()
    eng = GenerationEngine(gpt, num_slots=2, block_size=4)
    assert eng.enable_prefix_cache and eng.cache.state == ()
    assert eng.spec.refuses == {} and eng.spec.step_counters == ()
    eng.add_request([1, 2, 3, 4, 5], max_new_tokens=4)
    assert len(eng.run()[0]) == 9
    assert "engine_state_slots_used" not in \
        eng.metrics.render_prometheus()


# -- the share test -----------------------------------------------------------

def _moe_leaves(cfg, first, held, seed=3):
    """Reference leaves of one E layer that holds `held` experts from
    `first`, cut out of ONE uncut layer's seeded arrays."""
    whole = dict(ref_cfg(cfg), hybrid_override_pattern="E",
                 n_routed_experts=cfg.router_experts, expert_offset=0)
    arrays = weights.make_all(seed, ref.param_spec(whole), jnp.float32)
    p = [arrays[f"layers.0.mixer.{leaf}"] for leaf in ref.MIXER_LEAVES["E"]]
    p[6], p[7] = p[6][first:first + held], p[7][first:first + held]
    return p


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that all 4 shares give, plus the shared expert
    counted once, are the uncut layer — for the reference, and for the
    program's layer told which experts it holds."""
    cfg = NemotronHConfig.tiny(pattern="E", router_experts=16,
                               n_routed_experts=4, num_experts_per_tok=5)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 7, 64))
    mm = common.mm_f32
    uncut = dict(ref_cfg(cfg), n_routed_experts=16, expert_offset=0)
    whole = ref.moe_mixer(_moe_leaves(cfg, 0, 16), u, ref.sizes(uncut), mm)
    parts = []
    for first in (0, 4, 8, 12):
        z = ref.sizes(dict(ref_cfg(cfg), expert_offset=first))
        p = _moe_leaves(cfg, first, 4)
        parts.append(ref.moe_routed_part(p, u, z, mm))
    shared = ref.moe_shared_part(_moe_leaves(cfg, 0, 4), u, mm)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5)
    assert all(np.abs(p).max() > 1e-3 for p in parts)

    flat = u.reshape(-1, 64)
    got = 0
    for first in (0, 4, 8, 12):
        layer = NemotronHForCausalLM(NemotronHConfig.tiny(
            pattern="E", router_experts=16, n_routed_experts=4,
            num_experts_per_tok=5, expert_offset=first)).layers[0].mixer
        for leaf, a in zip(ref.MIXER_LEAVES["E"],
                           _moe_leaves(cfg, first, 4)):
            owner, name = leaf.split(".")
            getattr(getattr(layer, owner), name)._in_place_update(a)
        out, counters = layer.forward_rows(flat, jnp.ones(14, bool))
        routed_and_shared = np.asarray(out).reshape(2, 7, 64)
        got = got + routed_and_shared - (np.asarray(shared)
                                         if first else 0)
        assert counters[0] > 0
    np.testing.assert_allclose(got, whole, atol=1e-5)


def test_the_sliced_vocabularys_logits_are_the_whole_tables_first_rows():
    """Ids drawn from the slice; embedding and head cut to the slice's
    rows: the logits are the first rows of the whole table's."""
    model, cfg = seeded("M*", vocab=128)
    ids = np.stack(prompts(NemotronHConfig.tiny(vocab=32), [12]))
    whole = np.asarray(model(ids)._array)
    cut = NemotronHForCausalLM(NemotronHConfig.tiny(
        pattern="M*", vocab=32, router_experts=16, n_routed_experts=8,
        expert_offset=4))
    cut.eval()
    full = dict(model.named_parameters())
    for name, p in cut.named_parameters():
        a = full[name]._array
        p._in_place_update(a[:32] if name in ("embed.weight",
                                              "lm_head.weight") else a)
    np.testing.assert_allclose(np.asarray(cut(ids)._array),
                               whole[..., :32], atol=1e-6)


# -- the kernels against the plain forms -------------------------------------

def test_chunked_scan_carries_the_state_and_skips_the_padding():
    """`ssd_chunk_scan` from a carried state, in chunks that do not
    divide the length, with a padded tail: against the recurrence taken a
    token at a time."""
    key = jax.random.split(jax.random.PRNGKey(1), 6)
    t, heads, p, g, n, valid = 13, 4, 8, 2, 16, 10
    x = jax.random.normal(key[0], (t, heads, p))
    dt = jax.nn.softplus(jax.random.normal(key[1], (t, heads)))
    a = -jnp.exp(0.3 * jax.random.normal(key[2], (heads,)))
    b = jax.random.normal(key[3], (t, g, n))
    c = jax.random.normal(key[4], (t, g, n))
    d = jnp.ones(heads)
    state = jax.random.normal(key[5], (heads, p, n))
    y, after = ssm.ssd_chunk_scan(x, dt, a, b, c, d, state, valid,
                                  chunk_size=4)
    s, want = np.asarray(state, np.float64), []
    for i in range(valid):
        bi = np.repeat(np.asarray(b[i]), heads // g, 0)
        ci = np.repeat(np.asarray(c[i]), heads // g, 0)
        decay = np.exp(np.asarray(dt[i] * a))[:, None, None]
        s = decay * s + (np.asarray(dt[i])[:, None] * np.asarray(x[i])
                         )[:, :, None] * bi[:, None, :]
        want.append((s * ci[:, None, :]).sum(-1) + np.asarray(x[i]))
    np.testing.assert_allclose(y[:valid], np.stack(want), atol=1e-4)
    np.testing.assert_allclose(after, s, atol=1e-4)


def test_decode_kernel_matches_the_plain_update_and_leaves_other_rows():
    key = jax.random.split(jax.random.PRNGKey(2), 6)
    slots, heads, p, g, n = 3, 4, 8, 2, 128
    pool = jax.random.normal(key[0], (2, slots + 1, heads, p, n))
    rows = jnp.asarray([2, 0, 3], jnp.int32)          # lane 1 is idle
    x = jax.random.normal(key[1], (slots, heads, p))
    dt = jax.nn.softplus(jax.random.normal(key[2], (slots, heads)))
    a = -jnp.exp(0.3 * jax.random.normal(key[3], (heads,)))
    b = jax.random.normal(key[4], (slots, g, n))
    c = jax.random.normal(key[5], (slots, g, n))
    d = jnp.ones(heads)
    ssm.reset_ssm_path_stats()
    got = ssm.ssm_decode_step(pool, 1, rows, x, dt, a, d, b, c, "pallas")
    want = ssm.ssm_decode_step(pool, 1, rows, x, dt, a, d, b, c, "xla")
    assert ssm.SSM_PATH_STATS == {"xla": 1, "pallas": 1}
    live = np.array([0, 2])
    np.testing.assert_allclose(np.asarray(got[0])[live],
                               np.asarray(want[0])[live], atol=1e-5)
    for row in (2, 3):
        np.testing.assert_allclose(got[1][1, row], want[1][1, row],
                                   atol=1e-5)
    np.testing.assert_array_equal(got[1][0], pool[0])   # the other layer
    np.testing.assert_array_equal(got[1][1, 1], pool[1, 1])   # a free row


@pytest.mark.parametrize("crowded", [False, True])
def test_dropless_dispatch_and_grouped_matmul(crowded):
    """Every assignment to an expert held here gets a row of the buffer,
    whatever the crowding (no capacity): with every token on ONE expert,
    and spread; the kernel (interpreter) and the plain form agree with
    the sum over experts written out."""
    key = jax.random.split(jax.random.PRNGKey(4), 5)
    t, k, d, h, held, first = 12, 3, 32, 48, 4, 6
    x = jax.random.normal(key[0], (t, d))
    w1 = 0.2 * jax.random.normal(key[1], (held, d, h))
    w2 = 0.2 * jax.random.normal(key[2], (held, h, d))
    weights_ = jax.random.uniform(key[3], (t, k))
    if crowded:
        ids = jnp.tile(jnp.asarray([[7, 0, 15]], jnp.int32), (t, 1))
    else:
        ids = jax.random.randint(key[4], (t, k), 0, 16).astype(jnp.int32)
    plan = moe.sorted_dispatch(ids, first, held, tile_rows=8)
    local = np.asarray(ids) - first
    here = (local >= 0) & (local < held)
    rows = np.asarray(plan["slot_row"])
    m = len(plan["row_token"])
    assert (rows[here] < m).all() and (rows[~here] == m).all()
    assert len(set(rows[here].tolist())) == here.sum()    # none shares
    assert np.asarray(plan["group_sizes"]).sum() == here.sum()
    tokens = np.asarray(plan["row_token"])[rows[here]]
    assert (tokens == np.nonzero(here)[0]).all()

    want = np.zeros((t, d))
    for e in range(held):
        w_e = np.where(local == e, np.asarray(weights_), 0).sum(-1)
        hid = np.square(np.maximum(np.asarray(x) @ np.asarray(w1[e]), 0))
        want += w_e[:, None] * (hid @ np.asarray(w2[e]))
    moe.reset_moe_path_stats()
    for backend in ("xla", "pallas"):
        out, counters = moe.expert_share(x, ids, weights_, w1, w2, first,
                                         backend=backend, tile_rows=8)
        np.testing.assert_allclose(out, want, atol=1e-4)
        sizes = np.bincount(local[here], minlength=held)
        assert counters.tolist() == [here.sum(), (sizes > 0).sum(),
                                     sizes.max(), (-(-sizes // 8)).sum()]
    assert moe.MOE_PATH_STATS == {"xla": 1, "pallas": 1}


@pytest.mark.parametrize("on_chip,widths,offered", [
    (False, (1024, 2688), False), (True, (1024, 2688), True),
    (True, (1024, 48), False)],
    ids=["off_the_chip", "the_kernels_widths", "a_narrow_width"])
def test_the_fused_step_is_offered_where_the_experts_run_the_kernel(
        monkeypatch, on_chip, widths, offered):
    """`decode_with_chunk` saves what the grouped kernel saves, an
    expert's weights crossing once a call: the spec offers it where
    `auto` resolves the experts' product to that kernel, and nowhere
    else."""
    monkeypatch.setattr("paddle_tpu.core.device.on_tpu", lambda: on_chip)
    model, cfg = seeded("ME*E")
    cfg.moe_latent_size, cfg.moe_intermediate_size = widths
    assert model.serving_spec().offers_decode_with_chunk is offered


def test_an_engine_off_the_chip_keeps_the_two_plain_programs():
    model, cfg = seeded("ME*E")
    eng = engine_for(model)
    for p in prompts(cfg, [9, 9]):
        eng.add_request(p, max_new_tokens=6)
    eng.run()
    assert eng._fused is None and eng.decode_steps_with_chunk == 0
    assert (eng.decode_traces, eng.prefill_traces) == (1, 1)


def test_the_fused_steps_kernel_forms_are_counted_like_any_programs(
        fused_step_offered):
    """`SSM_PATH_STATS` / `MOE_PATH_STATS` are what the benchmark's
    `unexpected_kernel_path` reads: the fused program's trace adds its
    scan steps and its expert products to them, so a form it resolved
    that the cell does not expect would show."""
    model, cfg = seeded("ME*E")
    ssm.reset_ssm_path_stats()
    moe.reset_moe_path_stats()
    eng = engine_for(model)
    for p in prompts(cfg, [9, 9]):
        eng.add_request(p, max_new_tokens=6)
    eng.run()
    assert eng.decode_traces == 2 and eng.prefill_traces == 1
    # one M layer in the two decode programs; two E layers in all three
    assert ssm.SSM_PATH_STATS == {"xla": 2, "pallas": 0}
    assert moe.MOE_PATH_STATS == {"xla": 6, "pallas": 0}


def test_engine_source_names_no_architecture():
    """C1's "done when": the engine schedules, allocates and dispatches,
    and what it knows of a model is its serving spec. C3's: what it
    knows of its options is its constructor's arguments."""
    import inspect

    from paddle_tpu.inference import engine

    source = inspect.getsource(engine).lower()
    for word in ("gpt", "nemotron", "mamba", "cfg.num_heads",
                 "model.config", "os.environ", "paddle_serve_",
                 "paddle_paged_attention_backend", "paddle_spec_decode_k"):
        assert word not in source, word
