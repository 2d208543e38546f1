"""The `brumby` decoder (the Qwen3 block with power retention for
attention: no K/V cache, a gated second-power state a slot) at a tiny size
on the CPU, float32: the whole-sequence forward against the benchmark's
plain reference (the ATTENTION form) on seeded weights; chunked prefill
then decode through `GenerationEngine` against the reference's full
forward, logits compared, and the same with the state kept in bf16 shown
to fail; `phi` in the layout the kernel uses; the decode kernel
(interpreter) against the `jnp` form; padding rows and the null row; a
slot's rows zeroed for the next request; the refusals; an engine with NO
paged pool; the chunk kernel through the engine (its own tests are
`tests/test_retention_chunk_kernel.py`).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights
from benchmarks.reference import brumby as ref, common, stepwise
from paddle_tpu.inference.engine import GenerationEngine
from paddle_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM
from paddle_tpu.ops import retention

SEED = 13
REF_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "rms_norm_eps", "rope_theta", "vocab_size",
            "initializer_range")
# The engine's logits (state form: chunked prefill from a carried state,
# then one token a step) against the reference's (attention form, one
# `[T, T]` matrix): the same sums in another order, float32 at `highest`
# both. At logits of about 3 the two lie within 2e-5 of each other; a
# state held in bf16 moves them by 1e-2 and more. So 2e-4: ten times the
# reordering, a fiftieth of the next precision down.
LOGIT_TOLERANCE = 2e-4


def ref_cfg(cfg):
    """What the reference reads of the program's configuration, and the
    gate bias of the seeded weights: 1 + N(0, 2), so that some heads
    forget in a token and some remember these whole sequences (at the
    matrices' spread every gate is 0.5 and a stale row or a lost carry
    would pass)."""
    return dict({k: getattr(cfg, k) for k in REF_KEYS},
                gate_bias_init="ones_normal", gate_bias_std=2.0)


def seeded(seed=SEED, **kw):
    """The program's model with the reference's seeded weights bound."""
    cfg = BrumbyConfig.tiny(**kw)
    model = BrumbyForCausalLM(cfg)
    model.eval()
    arrays = weights.make_all(seed, ref.param_spec(ref_cfg(cfg)),
                              jnp.float32)
    named = dict(model.named_parameters())
    assert set(named) == set(arrays)
    for name, p in named.items():
        assert tuple(p.shape) == tuple(arrays[name].shape), name
        p._in_place_update(arrays[name])
    return model, cfg


def reference_logits(cfg, ids, seed=SEED, mm="f32"):
    return np.asarray(stepwise.logits_of(
        ref.build(ref_cfg(cfg), common.MM[mm]), seed,
        np.asarray(ids, np.int32), jnp.float32))


def prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
            for n in lengths]


def engine_for(model, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("prefill_chunk", 16)
    return GenerationEngine(model, **kw)


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("layers,length", [(1, 24), (2, 40)])
def test_forward_matches_the_reference(layers, length):
    """The program's whole-sequence forward (the state form, walked in
    sub-chunks of 8) against the reference's attention form."""
    model, cfg = seeded(layers=layers)
    ids = np.stack(prompts(cfg, [length, length]))
    got = np.asarray(model(ids)._array)
    want = reference_logits(cfg, ids)
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < LOGIT_TOLERANCE


def test_the_carried_state_matters_to_the_logits():
    """A forward that forgets at every sub-chunk's edge is far from the
    reference: the comparison sees the recurrence."""
    model, cfg = seeded()
    (ids,) = prompts(cfg, [40])
    want = reference_logits(cfg, ids[None])[0]
    pieces = [np.asarray(model(ids[None, lo:lo + 8])._array)[0]
              for lo in range(0, 40, 8)]
    assert np.abs(np.concatenate(pieces)[8:] - want[8:]).max() > 0.05


def _serve(model, asked, new_tokens, **kw):
    eng = engine_for(model, **kw)
    ids = [eng.add_request(p, max_new_tokens=new_tokens) for p in asked]
    out = eng.run()
    return eng, [np.asarray(out[i], np.int32) for i in ids]


@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_engine_chunked_prefill_then_decode_agrees_with_the_reference(
        backend):
    """Prompts shorter and longer than a chunk, more requests than
    slots: every served token's logit lies within the tolerance of the
    reference's best, by the reference's full forward over the whole
    sequence (no state, no chunks). `pallas` runs the decode kernel
    and the chunk kernel under the interpreter."""
    model, cfg = seeded()
    asked = prompts(cfg, [5, 19, 33, 12, 16])
    eng, served = _serve(model, asked, 9, attention_backend=backend)
    assert eng.attention_backend == backend
    assert eng.decode_traces == 1 and eng.prefill_traces == 1
    assert eng.cache.state_rows_used == 0
    compared = 0
    for prompt, tokens in zip(asked, served):
        plen = len(prompt)
        assert tokens[:plen].tolist() == prompt.tolist()
        rows = reference_logits(cfg, tokens[None, :-1])[0][plen - 1:]
        got = rows[np.arange(len(rows)), tokens[plen:]]
        assert (rows.max(-1) - got).max() < LOGIT_TOLERANCE
        compared += len(rows)
    assert compared == 9 * len(asked)


def _step_logits(model, cfg, prompt, new, state_dtype=jnp.float32):
    """The spec's own step functions by hand (chunks of 16, then one
    token a step over row 2 of a 3-row pool): float32 logits of every
    position from the last prompt row on, the next token always the
    reference's choice given in `new`."""
    spec = model.serving_spec()
    from paddle_tpu.core.tensor import Tensor

    state = tuple(jnp.zeros((s.layers, 3) + tuple(s.shape), state_dtype)
                  for s in spec.slot_state)
    plen, rows = len(prompt), []
    for start in range(0, plen, 16):
        chunk = np.zeros((1, 16), np.int32)
        part = prompt[start:start + 16]
        chunk[0, :len(part)] = part
        r = spec.prefill_chunk(
            Tensor._wrap(jnp.asarray(chunk)), Tensor._wrap(jnp.int32(start)),
            None, None, None, Tensor._wrap(jnp.int32(plen)),
            slot_state=state, state_row=jnp.int32(2))
        state = r.slot_state
        assert r.kpool is None and r.vpool is None
    last = (plen - 1) % 16
    rows.append(np.asarray(spec.logits(r.hidden)._array)[0, last])
    for i, tok in enumerate(new[:-1]):
        r = spec.decode(
            Tensor._wrap(jnp.asarray([[0], [tok]], jnp.int32)),
            Tensor._wrap(jnp.asarray([0, plen + i], jnp.int32)),
            None, None, None, backend="dense", slot_state=state,
            state_rows=jnp.asarray([0, 2], jnp.int32))
        state = r.slot_state
        rows.append(np.asarray(spec.logits(r.hidden)._array)[1, 0])
    return np.stack(rows)


def test_step_functions_give_the_references_logits_and_bf16_state_does_not():
    """Logits against logits: prefill in chunks then decode through the
    state rows, float32 state within the tolerance; the same steps over a
    state kept in bfloat16 (the nearest precision below) outside it."""
    model, cfg = seeded()
    (prompt,) = prompts(cfg, [37])
    new = prompts(cfg, [12], seed=5)[0]
    want = reference_logits(
        cfg, np.concatenate([prompt, new[:-1]])[None])[0][36:]
    got = _step_logits(model, cfg, prompt, new)
    assert np.abs(got - want).max() < LOGIT_TOLERANCE
    low = _step_logits(model, cfg, prompt, new, jnp.bfloat16)
    assert np.abs(low - want).max() > 10 * LOGIT_TOLERANCE


# -- the op ---------------------------------------------------------------------

@pytest.mark.parametrize("d", [16, 128])
def test_phi_is_the_second_power_in_the_kernels_layout(d):
    rng = np.random.default_rng(d)
    q = rng.normal(size=(5, d)).astype(np.float32)
    k = rng.normal(size=(7, d)).astype(np.float32)
    pq, pk = np.asarray(retention.phi(q)), np.asarray(retention.phi(k))
    assert pq.shape == (5, retention.state_width(d))
    # the two ways to the same values (slices for a chunk, index arrays
    # for a decode step), bit for bit
    assert np.array_equal(pq, np.asarray(retention.phi(q, gathered=True)))
    want = (q @ k.T) ** 2
    assert np.abs(pq @ pk.T - want).max() < 1e-5 * np.abs(want).max()
    # tiles of 8 in pairs a <= b, each the whole 8 x 8 product
    n = d // 8
    assert retention.state_width(d) == n * (n + 1) // 2 * 64
    assert retention.state_width(128) == 8704
    first = np.outer(q[0, :8], q[0, :8]).reshape(-1)       # pair (0, 0)
    assert np.allclose(pq[0, :64], first)
    if n > 1:                                              # pair (0, 1)
        assert np.allclose(
            pq[0, 64:128],
            np.sqrt(2) * np.outer(q[0, :8], q[0, 8:16]).reshape(-1))


def _random_step(rng, slots=4, h=2, r=3, d=16, layers=2):
    width = retention.state_width(d)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return dict(
        pool=f(layers, slots + 1, h, width, d),
        norm_pool=jnp.abs(f(layers, slots + 1, h, width)),
        q=f(slots, h, r, d), k=f(slots, h, d), v=f(slots, h, d),
        log_g=-jnp.abs(f(slots, h)))


def test_decode_kernel_matches_the_plain_update_and_leaves_other_rows():
    """The Pallas kernel (interpreter) against the `jnp` form; lanes on
    the null row compute nothing; every row but the lanes' own, and every
    other layer, is left as it was."""
    s = _random_step(np.random.default_rng(3))
    rows = jnp.asarray([2, 0, 4, 1], jnp.int32)
    args = (s["pool"], s["norm_pool"], 1, rows, s["q"], s["k"], s["v"],
            s["log_g"])
    retention.reset_retention_path_stats()
    y_x, pool_x, norm_x = retention.power_retention_decode(
        *args, backend="xla")
    y_p, pool_p, norm_p = retention.power_retention_decode(
        *args, backend="pallas")
    assert retention.RETENTION_PATH_STATS == {"xla": 1, "pallas": 1}
    live = np.asarray(rows) > 0
    scale = np.abs(np.asarray(y_x)[live]).max()
    assert np.abs(np.asarray(y_x - y_p))[live].max() < 1e-5 * scale
    assert np.abs(np.asarray(pool_x - pool_p))[:, 1:].max() < 1e-5
    assert np.array_equal(np.asarray(norm_x), np.asarray(norm_p))
    assert np.array_equal(np.asarray(pool_p[0]), np.asarray(s["pool"][0]))
    assert np.array_equal(np.asarray(pool_p[1, 3]),
                          np.asarray(s["pool"][1, 3]))
    assert not np.array_equal(np.asarray(pool_p[1, 2]),
                              np.asarray(s["pool"][1, 2]))
    with pytest.raises(ValueError, match="backend must be one of"):
        retention.power_retention_decode(*args, backend="mosaic")


def test_decode_is_the_attention_form_one_token_on():
    """From an empty state, T decode steps give the reference's attention
    form over the T tokens (one KV head a query head here)."""
    rng = np.random.default_rng(8)
    t, h, d = 9, 2, 16
    width = retention.state_width(d)
    q, k, v = (jnp.asarray(rng.normal(size=(t, h, d)), jnp.float32)
               for _ in range(3))
    log_g = -jnp.abs(jnp.asarray(rng.normal(size=(t, h)), jnp.float32))
    want = np.asarray(ref.power_retention(
        q[None], k[None], v[None], log_g[None]))[0]
    pool = jnp.zeros((1, 2, h, width, d), jnp.float32)
    norm = jnp.zeros((1, 2, h, width), jnp.float32)
    rows = jnp.asarray([1], jnp.int32)
    for i in range(t):
        y, pool, norm = retention.power_retention_decode(
            pool, norm, 0, rows, q[i][None, :, None], k[i][None],
            v[i][None], log_g[i][None], backend="xla")
        assert np.abs(np.asarray(y)[0, :, 0] - want[i]).max() < 1e-4
    # and the chunk form, in sub-chunks of 4 with two rows of padding
    pad = lambda x: jnp.pad(x, [(0, 2)] + [(0, 0)] * (x.ndim - 1), "constant",
                            constant_values=7.0)
    y, state, z = retention.power_retention_chunk(
        pad(q)[:, :, None], pad(k), pad(v), pad(log_g),
        jnp.zeros((h, width, d)), jnp.zeros((h, width)), jnp.int32(t), 4)
    assert np.abs(np.asarray(y)[:t, :, 0] - want).max() < 1e-4
    assert np.abs(np.asarray(state - pool[0, 1])).max() < 1e-4
    assert np.abs(np.asarray(z - norm[0, 1])).max() < 1e-4


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_padding_rows_leave_the_carried_state_as_it_is(backend):
    """A chunk of nothing but padding hands back the state it was given,
    bit for bit; a chunk whose prompt ends inside it carries what the real
    rows made of it. The XLA form and the kernel alike."""
    rng = np.random.default_rng(4)
    h, r, d, t = 2, 2, 16, 8
    width = retention.state_width(d)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k, v, lg = f(t, h, r, d), f(t, h, d), f(t, h, d), -jnp.abs(f(t, h))
    state, norm = f(h, width, d), jnp.abs(f(h, width))
    _, s0, z0 = retention.power_retention_chunk(
        q, k, v, lg, state, norm, jnp.int32(0), 4, backend)
    assert np.array_equal(np.asarray(s0), np.asarray(state))
    assert np.array_equal(np.asarray(z0), np.asarray(norm))
    _, s5, z5 = retention.power_retention_chunk(
        q, k, v, lg, state, norm, jnp.int32(5), 4, backend)
    _, s5b, z5b = retention.power_retention_chunk(
        q[:5], k[:5], v[:5], lg[:5], state, norm, jnp.int32(5), 5, "xla")
    assert np.abs(np.asarray(s5 - s5b)).max() < 1e-4
    assert np.abs(np.asarray(z5 - z5b)).max() < 1e-5


# -- the engine with no paged pool -------------------------------------------------

def test_an_engine_for_a_model_without_a_cache_holds_no_pool_and_no_block():
    model, cfg = seeded()
    spec = model.serving_spec()
    assert spec.paged_kv is None
    assert [(s.name, s.layers, s.shape) for s in spec.slot_state] == [
        ("ret_state", 2, (2, 192, 16)), ("ret_norm", 2, (2, 192))]
    eng = engine_for(model)
    c = eng.cache
    assert c.kpool is None and c.vpool is None and c.scales is None
    assert c.num_blocks - 1 == 0 and c.num_free == 0
    assert c.pool_nbytes() == 0 and eng.max_blocks == 0
    assert c.state_nbytes() == 2 * 4 * 2 * 192 * (16 + 1) * 4
    asked = prompts(cfg, [5, 19, 33, 12])
    for p in asked:
        eng.add_request(p, max_new_tokens=6)
    held = []
    while eng.num_active or eng.num_pending:
        eng.step()
        held.append(sum(len(s.blocks) for s in eng._slots
                        if s is not None))
        assert c.kpool is None and c.vpool is None
    assert max(held) == 0 and c.num_free == 0
    out = eng.pop_results()
    assert sorted(len(v) for v in out.values()) == [11, 18, 25, 39]
    assert c.leak_check() == [] and c.state_leak_check() == []
    assert eng.step_counter_totals["decode_live_lanes"] == 4 * 5
    assert eng.chunk_counter_totals == {
        "prefill_rows_computed": 5 + 19 + 33 + 12}
    text = eng.metrics.render_prometheus()
    assert "engine_prefill_rows_computed_total 69" in text
    assert "engine_state_bytes 0" in text
    assert "engine_pool_used_blocks" in text
    # the context's one limit is the model's own
    with pytest.raises(ValueError, match="exceeds max_model_len=128"):
        eng.add_request(np.zeros(120, np.int32), max_new_tokens=9)


def test_state_bytes_gauge_follows_the_rows_held():
    model, cfg = seeded()
    eng = engine_for(model)
    (p,) = prompts(cfg, [6])
    eng.add_request(p, max_new_tokens=4)
    eng.step()
    row = 2 * 2 * 192 * (16 + 1) * 4
    assert f"engine_state_bytes {row}" in eng.metrics.render_prometheus()
    eng.run()


def test_a_slot_another_request_just_left_starts_from_nought():
    """One lane: the second request sits where the first sat. Its tokens
    are what it gets alone on a fresh engine, and its rows were zero."""
    model, cfg = seeded()
    first, second = prompts(cfg, [23, 14])
    eng = engine_for(model, num_slots=1)
    a = eng.add_request(first, max_new_tokens=8)
    out = eng.run()
    assert len(out[a]) == 31
    assert all(float(jnp.abs(s[:, 1]).max()) > 0 for s in eng.cache.state)
    row = eng.cache.allocate_state()
    assert row == 1 and all(float(jnp.abs(s[:, 1]).max()) == 0
                            for s in eng.cache.state)
    eng.cache.free_state(row)
    b = eng.add_request(second, max_new_tokens=8)
    alone = engine_for(model, num_slots=1)
    c = alone.add_request(second, max_new_tokens=8)
    assert eng.run()[b] == alone.run()[c]


def test_the_kernels_serve_the_xla_forms_tokens():
    """One backend choice picks both retention forms: a `pallas` engine
    runs the chunk kernel (and the decode kernel) under the interpreter
    and serves the tokens a `dense` engine serves; the chunk's form is
    counted apart from the decode step's."""
    model, cfg = seeded()
    asked = prompts(cfg, [5, 19, 33, 12, 40])
    retention.reset_retention_path_stats()
    _, dense = _serve(model, asked, 9, attention_backend="dense")
    assert retention.RETENTION_CHUNK_STATS == {"xla": 2, "pallas": 0}
    eng, fused = _serve(model, asked, 9, attention_backend="pallas")
    assert retention.RETENTION_CHUNK_STATS == {"xla": 2, "pallas": 2}
    assert retention.RETENTION_PATH_STATS == {"xla": 2, "pallas": 2}
    assert eng.prefill_traces == 1
    assert [t.tolist() for t in fused] == [t.tolist() for t in dense]


def test_the_benchmarks_dropped_carry_still_reaches_the_kernel():
    """`calibrate_recurrent.py` plants its faults by wrapping
    `retention.power_retention_chunk` (positional arguments, the backend
    among them): the carry it drops changes what a `pallas` engine
    serves, so the seam still wraps the kernel's path."""
    from benchmarks import calibrate_recurrent

    model, cfg = seeded()
    asked = prompts(cfg, [37, 40])
    _, whole = _serve(model, asked, 6, attention_backend="pallas")
    with calibrate_recurrent.planted("carry_dropped"):
        _, dropped = _serve(model, asked, 6, attention_backend="pallas")
    assert [t.tolist() for t in dropped] != [t.tolist() for t in whole]


def test_ahead_and_serial_orders_serve_the_same_tokens():
    model, cfg = seeded()
    asked = prompts(cfg, [5, 19, 33, 12, 16, 7])
    got = []
    for async_core in (True, False):
        eng = engine_for(model, async_core=async_core)
        ids = [eng.add_request(p, max_new_tokens=9) for p in asked[:3]]
        for _ in range(2):
            eng.step()
        ids += [eng.add_request(p, max_new_tokens=9) for p in asked[3:]]
        out = eng.drain()
        got.append([out[i] for i in ids])
    assert got[0] == got[1]


@pytest.mark.parametrize("kwargs,feature,why", [
    (dict(enable_prefix_cache=True), "prefix_cache", "snapshot"),
    (dict(spec_decode_k=2), "spec_decode", "snapshot"),
    (dict(kv_dtype="int8"), "kv_int8", "no paged cache"),
    (dict(weight_dtype="int8"), "weight_int8", "no int8 plan"),
])
def test_what_hangs_on_blocks_or_snapshots_is_refused_with_its_reason(
        kwargs, feature, why):
    model, _ = seeded()
    with pytest.raises(ValueError,
                       match=f"{feature} is not served.*{why}"):
        engine_for(model, **kwargs)


def test_forks_handoffs_shards_and_adapters_are_refused():
    from paddle_tpu.inference.sampling import SamplingParams

    model, cfg = seeded()
    eng = engine_for(model, sampling=True)
    assert eng.enable_prefix_cache is False
    (p,) = prompts(cfg, [6])
    with pytest.raises(ValueError, match="fork is not served.*snapshot"):
        eng.best_of_n(p, 2, 4, sampling_params=SamplingParams(
            temperature=1.0, seed=1))
    with pytest.raises(ValueError,
                       match="handoff is not served.*no paged cache"):
        eng.add_request(p, 1, prefill_only=True)
    with pytest.raises(ValueError, match="handoff is not served"):
        eng.adopt_request(p, 3, [1, 2], 4)
    with pytest.raises(ValueError, match="not sharded"):
        engine_for(model, mp_degree=2)
    with pytest.raises(ValueError, match="take no adapters"):
        engine_for(model, adapters=object())
    with pytest.raises(ValueError, match="auto, dense or pallas"):
        engine_for(model, attention_backend="fused")


def test_the_manager_without_layers_refuses_nothing_it_cannot_do():
    """`PagedKVCache` of no layers: no pool, one block (the null block),
    nothing to allocate; with layers it still wants two blocks."""
    from paddle_tpu.inference.engine import PagedKVCache

    c = PagedKVCache(0, 64, 16, None, 0)
    assert c.kpool is None and c.num_blocks == 1 and c.allocate(1) is None
    assert c.allocate(0) == [] and c.pool_nbytes() == 0
    with pytest.raises(ValueError, match=">= 2 blocks"):
        PagedKVCache(2, 1, 16, 2, 16)
