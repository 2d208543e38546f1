"""Continuous-batching generation engine tests (the serving tier the
north star's "heavy traffic" clause asks for): token parity of the
paged-cache engine against the single-request compiled decode path,
mid-run admissions/evictions, recompile-count bounds via the
jit.count_traces probe, paged-vs-dense op parity, and pool-pressure
behavior.

Reference analogs: vLLM PagedAttention layout + Orca iteration-level
scheduling over the repo's forward_prefill/forward_decode split.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.jit as jit
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference import GenerationEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM

VOCAB = 61


def _model(seed=0, dropout=0.0):
    paddle.seed(seed)
    cfg = GPTConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=2,
                         seq=64)
    cfg.dropout = dropout
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model()


def _reference(model, prompt, max_new, eos=None):
    """Single-request greedy decode through the compiled fixed-buffer
    KV-cache path — the parity oracle."""
    out = model.generate(Tensor._wrap(np.asarray(prompt, np.int32)[None]),
                         max_length=len(prompt) + max_new,
                         eos_token_id=eos, use_cache=True)
    return np.asarray(out._array)[0]


def test_engine_parity_midrun_arrivals_and_zero_recompiles(model):
    """The two headline acceptance criteria in one serving run:
    (a) >= 8 requests with heterogeneous prompt/output lengths,
    admissions AFTER decode started, slots < requests (finished
    requests vacate lanes for later arrivals), per-request output
    exactly equal to single-request greedy_decode; (b) steady-state
    decode compiles ONCE across all that churn and prefill compiles
    once for every prompt length — proven by the jit.count_traces
    probe, not inferred from timing."""
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(0, VOCAB, rng.randint(1, 8)).astype(np.int32),
             int(rng.randint(3, 10))) for _ in range(8)]

    eng = GenerationEngine(model, num_slots=3, block_size=4,
                           num_blocks=40, prefill_chunk=8)
    ids = [eng.add_request(p, n) for p, n in reqs[:4]]
    for _ in range(3):
        eng.step()                      # decode is mid-stream...
    ids += [eng.add_request(p, n) for p, n in reqs[4:]]  # ...arrivals
    out = eng.run()

    assert len(out) == 8
    for (p, n), rid in zip(reqs, ids):
        got = np.asarray(out[rid])
        assert got.shape == (len(p) + n,)   # no-EOS: exactly max_new
        np.testing.assert_array_equal(got, _reference(model, p, n))

    assert eng.decode_traces == 1
    assert eng.prefill_traces == 1
    # steady state: further churn retraces NOTHING — a prompt longer
    # than the chunk included (`start`/`plen` are traced)
    with jit.expect_traces(eng._decode_pure, 0), \
            jit.expect_traces(eng._prefill_pure, 0):
        eng.add_request(rng.randint(0, VOCAB, 5), 3)
        eng.add_request(rng.randint(0, VOCAB, 12), 2)   # two chunks
        eng.run()


def test_engine_eos_early_stop_and_pool_pressure(model):
    """EOS mid-continuation evicts the lane early with exact parity to
    the frozen-row single-request semantics; and a pool smaller than
    sum-of-max-contexts forces block stalls that recover with outputs
    still exact (HBM shared by live context, not reserved per
    request). One small pool serves both scenarios."""
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, VOCAB, 5).astype(np.int32)
    plain = _reference(model, prompt, 12)
    eos = int(plain[len(prompt) + 2])       # 3rd generated token
    ref_eos = _reference(model, prompt, 12, eos=eos)

    # 8 usable blocks x 4 tokens = 32 cached tokens vs 3 slots x 17
    # max demanded: stalls under full occupancy
    eng = GenerationEngine(model, num_slots=3, block_size=4,
                           num_blocks=9)
    reqs = [(rng.randint(0, VOCAB, rng.randint(2, 7)).astype(np.int32),
             int(rng.randint(4, 9))) for _ in range(4)]
    ids = [eng.add_request(p, n) for p, n in reqs]
    rid_eos = eng.add_request(prompt, 12, eos_token_id=eos)
    out = eng.run()

    got = out[rid_eos]
    assert len(got) < len(prompt) + 12      # stopped early
    assert got[-1] == eos
    np.testing.assert_array_equal(got, ref_eos[:len(got)])
    for (p, n), rid in zip(reqs, ids):
        np.testing.assert_array_equal(np.asarray(out[rid]),
                                      _reference(model, p, n))
    # all lanes vacated, every block returned to the free list
    assert eng.num_active == 0
    assert eng.cache.num_free == eng.cache.num_blocks - 1


def test_one_prefill_strategy_compiles_once_for_every_length(model):
    """Chunked prefill is the engine's prefill: there is no bucketed
    strategy to select, and a prompt of every length from 1 token to 3
    chunks goes through ONE compiled program, oracle-exact."""
    with pytest.raises(TypeError):
        GenerationEngine(model, prefill_chunk=None)
    with pytest.raises(TypeError, match="prefill_buckets"):
        GenerationEngine(model, prefill_buckets=(8, 64))
    rng = np.random.RandomState(8)
    eng = GenerationEngine(model, num_slots=3, block_size=4,
                           prefill_chunk=8, enable_prefix_cache=False)
    prompts = [rng.randint(0, VOCAB, plen).astype(np.int32)
               for plen in range(1, 3 * 8 + 1)]
    ids = [eng.add_request(p, 2) for p in prompts]
    out = eng.run()
    assert eng.prefill_traces == 1 and eng.decode_traces == 1
    for p, rid in list(zip(prompts, ids))[::5]:
        np.testing.assert_array_equal(np.asarray(out[rid]),
                                      _reference(model, p, 2))


@pytest.mark.parametrize("name,value", [
    ("PADDLE_SERVE_MP", "2"),
    ("PADDLE_SERVE_KV_DTYPE", "int8"),
    ("PADDLE_SERVE_WEIGHT_DTYPE", "int8"),
    ("PADDLE_SERVE_SAMPLING", "1"),
    ("PADDLE_SERVE_TRACING", "1"),
    ("PADDLE_SERVE_ASYNC", "0"),
    ("PADDLE_PAGED_ATTENTION_BACKEND", "pallas"),
    ("PADDLE_SPEC_DECODE_K", "3"),
])
def test_retired_environment_names_change_nothing(model, monkeypatch,
                                                  name, value):
    """The constructor is the one way to set the engine: a variable
    that used to override it builds the default engine."""
    monkeypatch.setenv(name, value)
    eng = GenerationEngine(model, num_slots=2, block_size=4)
    assert eng.mp_degree == 1 and eng.mesh is None
    assert eng.kv_dtype is None and eng.weight_dtype is None
    assert eng.sampling is False
    assert eng.tracing is False and eng.tracer is None
    assert eng.async_core is True
    assert eng.spec_decode_k == 0 and eng.drafter is None
    assert eng.attention_backend == "dense"       # auto off-TPU


def test_engine_deadlock_is_loud(model):
    """A request whose prompt can never fit the pool must fail with
    sizing guidance, not spin forever."""
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=3)
    eng.add_request(np.arange(12) % VOCAB, 4)     # needs 3 blocks, has 2
    with pytest.raises(RuntimeError, match="grow num_blocks"):
        eng.run()


def test_engine_request_validation_and_eval_gate(model):
    eng = GenerationEngine(model, num_slots=2)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.add_request([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.add_request([1, 2], 0)
    with pytest.raises(ValueError, match="exceeds max_model_len"):
        eng.add_request(np.zeros(60, np.int32), 10)   # 70 > 64

    dropout_model = _model(seed=5, dropout=0.1)
    dropout_model.train()
    with pytest.raises(ValueError, match="eval"):
        GenerationEngine(dropout_model)


def test_paged_attention_step_matches_dense_attention():
    """Op-level parity: the block-table gather attention equals dense
    masked attention over the same context (the dense fallback the
    engine's correctness rests on)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.paged_attention import (
        dense_gather_reference, paged_attention_step)

    L, nb, bs, H, D = 2, 9, 4, 2, 8
    B, maxb = 3, 4
    rng = np.random.RandomState(7)
    kpool = np.zeros((L, nb, bs, H, D), np.float32)
    vpool = np.zeros((L, nb, bs, H, D), np.float32)
    # three slots with distinct context depths and disjoint blocks
    plens = [5, 2, 9]
    tables = np.zeros((B, maxb), np.int32)
    tables[0, :2] = [1, 2]
    tables[1, :1] = [3]
    tables[2, :3] = [4, 5, 6]
    ctx_k = rng.randn(B, maxb * bs, H, D).astype(np.float32)
    ctx_v = rng.randn(B, maxb * bs, H, D).astype(np.float32)
    for b in range(B):                 # seed each slot's prior context
        for pos in range(plens[b]):
            kpool[:, tables[b, pos // bs], pos % bs] = ctx_k[b, pos]
            vpool[:, tables[b, pos // bs], pos % bs] = ctx_v[b, pos]
    kpool, vpool = jnp.asarray(kpool), jnp.asarray(vpool)

    q = rng.randn(B, 1, H, D).astype(np.float32)
    k_new = rng.randn(B, 1, H, D).astype(np.float32)
    v_new = rng.randn(B, 1, H, D).astype(np.float32)
    positions = np.asarray(plens, np.int32)       # write AT the depth
    for layer in range(L):
        out, kpool, vpool = paged_attention_step(
            q, k_new, v_new, kpool, vpool, layer, tables, positions)
        out, kpool, vpool = (np.asarray(out._array), kpool._array,
                             vpool._array)
        for b in range(B):
            T = plens[b] + 1
            kd = np.concatenate([ctx_k[b, :plens[b]], k_new[b]], 0)
            vd = np.concatenate([ctx_v[b, :plens[b]], v_new[b]], 0)
            # the written pool rows reassemble to exactly this context
            gk, gv = dense_gather_reference(kpool, vpool, layer,
                                            tables[b], T)
            np.testing.assert_allclose(gk, kd, rtol=1e-6)
            np.testing.assert_allclose(gv, vd, rtol=1e-6)
            logits = np.einsum("qhd,khd->hqk", q[b], kd) / np.sqrt(D)
            p = np.exp(logits - logits.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            ref = np.einsum("hqk,khd->qhd", p, vd)
            np.testing.assert_allclose(out[b], ref, rtol=1e-4,
                                       atol=1e-5)


def test_forward_decode_per_row_positions_matches_scalar(model):
    """The dense fixed-buffer decode now takes a [B] vector of per-row
    positions (the continuous-batching shape); each row must equal the
    scalar-pos single-row result."""
    rng = np.random.RandomState(6)
    Lbuf = 16
    prompts = [rng.randint(0, VOCAB, 3), rng.randint(0, VOCAB, 6)]

    caches = []
    for p in prompts:
        _, ks, vs = model.gpt.forward_prefill(
            Tensor._wrap(np.asarray(p, np.int32)[None]))
        ks, vs = np.asarray(ks._array), np.asarray(vs._array)
        pad = Lbuf - ks.shape[2]
        widths = [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)]
        caches.append((np.pad(ks, widths), np.pad(vs, widths)))

    toks = np.asarray([[5], [9]], np.int32)
    pos = np.asarray([len(prompts[0]), len(prompts[1])], np.int32)
    kb = np.concatenate([c[0] for c in caches], axis=1)
    vb = np.concatenate([c[1] for c in caches], axis=1)
    h_b, kb2, vb2 = model.gpt.forward_decode(
        Tensor._wrap(toks), Tensor._wrap(pos),
        Tensor._wrap(kb), Tensor._wrap(vb))
    h_b = np.asarray(h_b._array)

    for r in range(2):
        h1, k1, v1 = model.gpt.forward_decode(
            Tensor._wrap(toks[r:r + 1]), Tensor._wrap(pos[r]),
            Tensor._wrap(caches[r][0]), Tensor._wrap(caches[r][1]))
        np.testing.assert_allclose(h_b[r], np.asarray(h1._array)[0],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(kb2._array)[:, r],
                                   np.asarray(k1._array)[:, 0],
                                   rtol=1e-6)


def test_count_traces_probe_and_expect_traces():
    """The CI recompile probe itself: counts jit cache misses, and the
    assertion helper trips on an unexpected retrace."""
    import jax
    import jax.numpy as jnp

    fn = jit.count_traces(lambda x: jnp.sin(x) * 2)
    jfn = jax.jit(fn)
    with jit.expect_traces(fn, 1):
        jfn(jnp.ones(3))
        jfn(jnp.ones(3) * 2)          # same shape: cached
    with pytest.raises(AssertionError, match="retracing"):
        with jit.expect_traces(fn, 0):
            jfn(jnp.ones(5))          # new shape: retrace
    with pytest.raises(TypeError):
        with jit.expect_traces(lambda: None, 0):
            pass


def test_engine_offered_load_bench_runner_tiny():
    """The OPBENCH engine row's runner, at test scale: mixed
    prompt/output lengths through the engine, aggregate tokens/s out
    (the TPU run uses the representative 350M defaults)."""
    # isolate from the deploy knob: the default row must resolve auto
    import bench_ops

    model_cfg = GPTConfig.tiny(vocab=32, hidden=16, layers=1, heads=2,
                               seq=32)
    paddle.seed(0)
    rec = bench_ops._engine_offered_load_case(
        model_cfg=model_cfg,
        requests=[(3, 4), (6, 4), (10, 5)],
        num_slots=2, block_size=4)()
    assert rec["requests"] == 3
    assert rec["tokens_per_s"] > 0 and rec["ms"] > 0
    assert rec["attention_backend"] == "dense"     # auto off-TPU
    # the pallas variant row runs the same trace on the fused kernel
    # (interpreted off-TPU) and must serve every request too; ONE
    # request — interpret-mode compiles dominate, and the
    # backend itself is parity-tested in test_paged_attention_backends
    paddle.seed(0)
    rec_p = bench_ops._engine_offered_load_case(
        model_cfg=model_cfg, requests=[(3, 3)],
        num_slots=1, block_size=4, attention_backend="pallas")()
    assert rec_p["attention_backend"] == "pallas"
    assert rec_p["requests"] == 1 and rec_p["tokens_per_s"] > 0
    # names the gate will track are emitted by the suite
    s = bench_ops.suite()
    assert "gpt_decode_kv_350m" in s and callable(s["gpt_decode_kv_350m"])
    assert "gpt_engine_offered_load" in s
    # the cheap names-only view (check_bench_result --pending) must
    # never drift from the real suite
    assert list(s) == bench_ops.suite_names()


def test_engine_metrics_spans_and_steady_state_recompiles(model):
    """ISSUE 2 acceptance: a loaded engine run yields nonzero TTFT and
    per-token latency histograms, admission/completion counters exact
    vs the request trace, recompile counter == 0 in steady state — and
    the scheduler's iterations land as spans in the host tracer next to
    the metrics story."""
    from paddle_tpu.observability.metrics import series_total
    from paddle_tpu.profiler import Profiler

    rng = np.random.RandomState(3)
    reqs = [(rng.randint(0, VOCAB, rng.randint(2, 8)).astype(np.int32),
             int(rng.randint(3, 9))) for _ in range(6)]
    eng = GenerationEngine(model, num_slots=3, block_size=4,
                           num_blocks=40)
    prof = Profiler()
    with prof:
        for p, n in reqs:
            eng.add_request(p, n)
        eng.run()
        # steady state: more churn through warmed programs
        for p, n in reqs[:2]:
            eng.add_request(p, n)
        eng.run()
    snap = eng.metrics_snapshot()

    total_reqs = len(reqs) + 2
    new_tokens = sum(n for _, n in reqs) + sum(n for _, n in reqs[:2])
    ttft = snap["engine_ttft_seconds"]["series"][0]
    tpot = snap["engine_tpot_seconds"]["series"][0]
    assert ttft["count"] == total_reqs and ttft["sum"] > 0
    # each admitted request's first token comes from prefill; the rest
    # are decode-iteration observations
    assert tpot["count"] == new_tokens - total_reqs and tpot["sum"] > 0
    assert series_total(snap, "engine_admissions_total") == total_reqs
    assert series_total(snap, "engine_finished_total") == total_reqs
    by_reason = {s["labels"]["reason"]: s["value"]
                 for s in snap["engine_finished_total"]["series"]}
    assert by_reason.get("length", 0) == total_reqs  # no EOS configured
    assert series_total(snap, "engine_tokens_generated_total") \
        == new_tokens == eng.tokens_generated
    # steady-state SLO: zero decode recompiles, one compiled program
    assert series_total(snap, "engine_decode_recompiles_total") == 0
    assert snap["engine_decode_traces"]["series"][0]["value"] == 1
    # drained: gauges back to idle, pool fully returned
    assert snap["engine_queue_depth"]["series"][0]["value"] == 0
    assert snap["engine_active_slots"]["series"][0]["value"] == 0
    assert snap["engine_pool_used_blocks"]["series"][0]["value"] == 0
    assert snap["engine_pool_used_high_water_blocks"]["series"][0][
        "value"] > 0

    # trace correlation: scheduler + compiled-step spans in the tracer
    names = {e["name"] for e in prof._events}
    assert {"engine.step", "engine.prefill", "engine.decode"} <= names


def test_engine_pool_pressure_stall_counter(model):
    """A pool smaller than the live-context demand must surface as a
    nonzero block-stall counter while outputs stay exact (the graceful
    degradation PR-1 built, now measurable)."""
    from paddle_tpu.observability.metrics import series_total

    rng = np.random.RandomState(4)
    # 5 usable blocks, 3 slots: two 6-token prompts occupy 4 blocks;
    # the third has a LANE but cannot get its chunk's 2 blocks until a
    # lane finishes — a deterministic prefill-path stall with decode
    # still progressing (no deadlock)
    eng = GenerationEngine(model, num_slots=3, block_size=4,
                           num_blocks=6)
    reqs = [(rng.randint(0, VOCAB, 6).astype(np.int32), 4)
            for _ in range(3)]
    ids = [eng.add_request(p, n) for p, n in reqs]
    out = eng.run()
    for (p, n), rid in zip(reqs, ids):
        np.testing.assert_array_equal(np.asarray(out[rid]),
                                      _reference(model, p, n))
    snap = eng.metrics_snapshot()
    stalls = {s["labels"]["path"]: s["value"]
              for s in snap["engine_block_stalls_total"]["series"]}
    assert stalls.get("prefill", 0) >= 1
    assert series_total(snap, "engine_block_stalls_total") > 0
    assert series_total(snap, "engine_decode_recompiles_total") == 0
    # pressure showed up as pool saturation at the peak
    assert snap["engine_pool_used_high_water_blocks"]["series"][0][
        "value"] == 5
    assert snap["engine_pool_used_blocks"]["series"][0]["value"] == 0

    # the engine registry speaks prometheus end-to-end
    text = eng.metrics.render_prometheus()
    assert "engine_block_stalls_total{path=" in text
    # TTFT is priority-labeled since the QoS tier; buckets append `le`
    assert 'engine_ttft_seconds_bucket{priority="standard",le=' in text
