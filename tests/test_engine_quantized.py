"""Quantized serving (ISSUE 11): int8 per-block-scaled KV cache +
int8 weights through the backend seam.

The contract, proven the way PR 6/7/8 proved theirs:

- `kv_dtype='int8'` serves
  the standard mixed trace TOKEN-PARITY-WITHIN-TOLERANCE vs the fp
  engine across {dense, pallas} x {cache cold, warm} x
  K in {0, 4} x mp in {1, 2} — and the int8 engine is token-IDENTICAL
  across mesh shapes (the per-block grids are pmax-folded, so mp=2
  quantizes on mp=1's exact grid);
- the fp path stays BIT-identical to pre-PR behavior (the fp engine
  still matches the `generate(use_cache=True)` oracle exactly);
- `decode_traces == 1` per (backend, K, mp, kv_dtype);
- int8 pool bytes (codes + scales) <= 0.55x the fp16/bf16 pool — the
  capacity claim, measurable on CPU;
- COW byte-identity and read-only prefix-block seating under int8:
  shared quantized blocks AND their scales are never mutated by a
  borrower (dense_gather_reference, both backends, mp in {1, 2});
- int8 weights (`weight_dtype='int8'` / engine.quantize_weights())
  ride the compiled steps as (codes, per-channel scale) pairs and
  dequantize inside the step; refresh_weights() requantizes.

Tolerance budget (documented here and in README "Quantized
serving"): greedy token streams must match the fp engine on >= 90%
of tokens over the standard mixed trace (INT8_TOKEN_PARITY_MIN in
bench_ops.py — the bench row enforces the same number), and the
dequantized KV rows must reconstruct the fp rows within 2% of each
block's absmax (the per-block int8 grid's resolution is absmax/127
~= 0.8%; 2% leaves headroom for the write-then-attend feedback).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.jit as jit
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference import GenerationEngine

VOCAB = 64
TOKEN_PARITY_MIN = 0.90       # the documented budget (see docstring)


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


def _reference(model, prompt, max_new):
    out = model.generate(
        Tensor._wrap(np.asarray(prompt, np.int32)[None]),
        max_length=len(prompt) + max_new, use_cache=True)
    return list(map(int, np.asarray(out._array)[0]))


def _mixed_trace(rng, n=4):
    """The standard mixed trace: mixed lengths + a hot shared prefix
    + a block-aligned full-prefix hit (block_size 4)."""
    reqs = [(rng.randint(0, VOCAB, rng.randint(2, 13)).astype(np.int32),
             int(rng.randint(2, 7))) for _ in range(n)]
    shared = rng.randint(0, VOCAB, 8).astype(np.int32)
    reqs += [(np.concatenate([shared, rng.randint(0, VOCAB, 3)])
              .astype(np.int32), 4),
             (shared.copy(), 4)]
    return reqs


def _run_trace(eng, reqs, midrun=True):
    ids = [eng.add_request(p, n) for p, n in reqs[:len(reqs) // 2]]
    if midrun:
        for _ in range(2):
            eng.step()
    ids += [eng.add_request(p, n) for p, n in reqs[len(reqs) // 2:]]
    out = eng.run()
    return [list(map(int, out[rid])) for rid in ids]


def _match_fraction(ref, got):
    from bench_ops import _token_match_fraction

    return _token_match_fraction(ref, got)


# ---------------------------------------------------------------------------
# tentpole: tolerance parity across the whole quantized serving matrix
# ---------------------------------------------------------------------------

def _assert_quantized_matrix(model, backend, K):
    """One mixed trace served fp (anchored bit-exact to the generate
    oracle — the fp path must be byte-for-byte pre-PR) and int8 at
    mp=1 and mp=2 in (a) cache cold, (b) same engine warm
    — int8 within the tolerance budget vs fp per
    mode, int8 mp=2 token-IDENTICAL to int8 mp=1, decode_traces==1
    per configuration."""
    rng = np.random.RandomState(11)
    reqs = _mixed_trace(rng)

    def serve(mp, kv):
        quant = dict(kv_dtype="int8", weight_dtype="int8") \
            if kv else {}
        eng = GenerationEngine(model, num_slots=3, block_size=4,
                               num_blocks=64, spec_decode_k=K,
                               attention_backend=backend,
                               mp_degree=mp, prefill_chunk=8, **quant)
        out = [_run_trace(eng, reqs),
               _run_trace(eng, reqs, midrun=False)]   # hot cache
        assert eng.prefix_hit_tokens > 0
        assert eng.decode_traces == 1, \
            f"mp={mp} {backend} K={K} kv={eng.kv_dtype}: retraced"
        return out

    fp = serve(None, kv=False)
    # fp path bit-identical to pre-PR: still exactly the oracle
    p, n = reqs[0]
    assert fp[0][0] == _reference(model, p, n)
    q1 = serve(None, kv=True)
    # tolerance parity vs fp, per serving mode
    for mode, ref, got in zip(("cold", "warm"), fp, q1):
        frac = _match_fraction(ref, got)
        assert frac >= TOKEN_PARITY_MIN, \
            (f"{backend} K={K} {mode}: int8 matched only {frac:.3f} "
             f"of fp tokens (budget {TOKEN_PARITY_MIN})")
    # int8 across mesh shapes is EXACT (pmax-folded global grids)
    q2 = serve(2, kv=True)
    assert q2 == q1, \
        f"{backend} K={K}: int8 mp=2 diverged from int8 mp=1"


def test_quantized_tolerance_parity_matrix(model):
    """THE acceptance gate, tier-1 cut: the (dense, K=0) cell across
    mp in {1, 2} x {cache cold, warm} plus the lean
    pallas/K=4 probe below; the remaining (backend, K) cells run in
    the slow-marked full-matrix test — the test_engine_sharded
    precedent for keeping the timed tier-1 window bounded."""
    _assert_quantized_matrix(model, "dense", 0)


def test_quantized_pallas_spec_decode_tolerance(model):
    """Lean tier-1 probe for the (pallas, K=4) cell: the int8 verify
    kernel serves the mixed trace cold + warm within the tolerance
    budget vs the fp reference (fp tokens are backend- and
    K-invariant by the PR 3/7 exactness contracts, so the dense fp
    K=0 stream is the oracle here too)."""
    rng = np.random.RandomState(11)
    reqs = _mixed_trace(rng)

    def serve(**kw):
        eng = GenerationEngine(model, num_slots=3, block_size=4,
                               num_blocks=64, prefill_chunk=8, **kw)
        out = [_run_trace(eng, reqs),
               _run_trace(eng, reqs, midrun=False)]
        return out, eng

    fp, _ = serve()
    q, eng = serve(kv_dtype="int8", weight_dtype="int8",
                   attention_backend="pallas", spec_decode_k=4)
    assert eng.decode_traces == 1
    for mode, ref, got in zip(("cold", "warm"), fp, q):
        frac = _match_fraction(ref, got)
        assert frac >= TOKEN_PARITY_MIN, \
            (f"pallas K=4 {mode}: int8 matched only {frac:.3f} of fp "
             f"tokens (budget {TOKEN_PARITY_MIN})")


@pytest.mark.slow
@pytest.mark.parametrize("backend,K", [("pallas", 4), ("dense", 4),
                                       ("pallas", 0)])
def test_quantized_tolerance_parity_full_matrix(model, backend, K):
    _assert_quantized_matrix(model, backend, K)


def test_quantized_backends_agree_token_for_token(model):
    """dense-int8 and pallas-int8 share one quantization policy and
    one operation order — their token streams must be identical, not
    merely both-within-tolerance."""
    rng = np.random.RandomState(5)
    reqs = _mixed_trace(rng, n=3)

    def serve(backend):
        eng = GenerationEngine(model, num_slots=2, block_size=4,
                               num_blocks=64, prefill_chunk=8,
                               kv_dtype="int8",
                               attention_backend=backend)
        return _run_trace(eng, reqs)

    assert serve("dense") == serve("pallas")


# ---------------------------------------------------------------------------
# capacity claim: int8 pool bytes <= 0.55x the fp16/bf16 pool
# ---------------------------------------------------------------------------

def test_int8_pool_bytes_half_of_bf16(model):
    import jax.numpy as jnp

    from paddle_tpu.inference import PagedKVCache

    bf16 = PagedKVCache(2, 32, 8, 4, 16, dtype=jnp.bfloat16)
    int8 = PagedKVCache(2, 32, 8, 4, 16, dtype=jnp.bfloat16,
                        kv_dtype="int8")
    assert int8.pool_spec()[1] == jnp.int8
    assert int8.scale_spec() == ((2, 32, 2), jnp.float32)
    ratio = int8.pool_nbytes() / bf16.pool_nbytes()
    assert ratio <= 0.55, f"int8 pool ratio {ratio:.3f} > 0.55"
    # and the engine-level gauge reports the quantized footprint
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=16, prefill_chunk=8,
                           kv_dtype="int8")
    snap = eng.metrics_snapshot()
    series = snap["engine_pool_bytes"]["series"]
    assert [s["labels"] for s in series] \
        == [{"shard": "0", "kv_dtype": "int8"}]
    eng.add_request(np.arange(5, dtype=np.int32), 2)
    eng.run()
    snap = eng.metrics_snapshot()
    assert snap["engine_pool_bytes"]["series"][0]["value"] \
        == eng.cache.pool_nbytes()
    with pytest.raises(ValueError, match="kv_dtype"):
        PagedKVCache(2, 8, 4, 4, 8, kv_dtype="fp8")
    for knob in ("kv_dtype", "weight_dtype"):
        with pytest.raises(ValueError, match=knob):
            GenerationEngine(model, num_slots=2, block_size=4,
                             **{knob: "fp8"})


def test_dtype_info_gauges_and_utilization_labels(model):
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=16, prefill_chunk=8,
                           kv_dtype="int8", weight_dtype="int8")
    snap = eng.metrics_snapshot()
    assert [s["labels"] for s in
            snap["engine_kv_dtype_info"]["series"]] \
        == [{"kv_dtype": "int8"}]
    assert [s["labels"] for s in
            snap["engine_weight_dtype_info"]["series"]] \
        == [{"weight_dtype": "int8"}]
    assert [s["labels"] for s in
            snap["engine_pool_utilization"]["series"]] \
        == [{"shard": "0", "kv_dtype": "int8"}]
    # the fp engine reports its real dtype, not a missing series
    fp = GenerationEngine(model, num_slots=2, block_size=4,
                          num_blocks=16, prefill_chunk=8)
    snap = fp.metrics_snapshot()
    assert [s["labels"] for s in
            snap["engine_kv_dtype_info"]["series"]] \
        == [{"kv_dtype": "float32"}]
    assert [s["labels"] for s in
            snap["engine_weight_dtype_info"]["series"]] \
        == [{"weight_dtype": "float32"}]


# ---------------------------------------------------------------------------
# quantized sharing: COW byte-identity + read-only prefix seating
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,mp", [("dense", 1), ("pallas", 2)])
def test_quantized_cow_keeps_shared_blocks_and_scales(model, backend, mp):
    """ISSUE 11 satellite: a borrower decoding off shared quantized
    prefix blocks must never mutate the cached int8 CODES or their
    per-block SCALES — COW promotes (copying scale rows with the
    block) before any write lands. Proven via dense_gather_reference
    over raw codes, raw scale rows, and dequantized values, across
    both backends and mp in {1, 2} (tier-1 runs the diagonal cells;
    the complementary pair is slow-marked below)."""
    _assert_cow_immutable(model, backend, mp)


@pytest.mark.slow
@pytest.mark.parametrize("backend,mp", [("pallas", 1), ("dense", 2)])
def test_quantized_cow_full_matrix(model, backend, mp):
    _assert_cow_immutable(model, backend, mp)


def _assert_cow_immutable(model, backend, mp):
    import jax.numpy as jnp

    from paddle_tpu.ops.paged_attention import dense_gather_reference

    rng = np.random.RandomState(3)
    shared = rng.randint(0, VOCAB, 8).astype(np.int32)   # 2 blocks
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=32, prefill_chunk=8,
                           kv_dtype="int8", attention_backend=backend,
                           mp_degree=None if mp == 1 else mp)
    rid = eng.add_request(shared, 3)
    first = eng.run()[rid]
    assert eng.cache.num_cached_blocks >= 2
    # snapshot the CACHED blocks' codes + scales before the borrower
    cached_blocks = sorted(eng.cache._hash_of)
    kp0 = np.asarray(eng.cache.kpool)[:, cached_blocks].copy()
    vp0 = np.asarray(eng.cache.vpool)[:, cached_blocks].copy()
    sc0 = np.asarray(eng.cache.scales)[:, cached_blocks].copy()
    # the borrower: full-prefix hit, decodes (COW) off the shared rows
    rid2 = eng.add_request(shared.copy(), 3)
    second = eng.run()[rid2]
    assert eng.prefix_hit_tokens >= len(shared)
    assert list(first) == list(second)      # same prompt, same stream
    assert np.array_equal(
        np.asarray(eng.cache.kpool)[:, cached_blocks], kp0)
    assert np.array_equal(
        np.asarray(eng.cache.vpool)[:, cached_blocks], vp0)
    assert np.array_equal(
        np.asarray(eng.cache.scales)[:, cached_blocks], sc0)
    # dequantized reconstruction through the probe stays within the
    # grid's resolution of the fp engine's rows (the documented 2%-
    # of-block-absmax budget)
    fp = GenerationEngine(model, num_slots=2, block_size=4,
                          num_blocks=32, prefill_chunk=8,
                          attention_backend=backend)
    ridf = fp.add_request(shared, 3)
    fp.run()
    row = np.zeros(fp.max_blocks, np.int32)
    row[:2] = cached_blocks[:2]
    # both engines cached the same prompt's first 2 blocks; rebuild
    # via each engine's own table layout
    qrow = np.zeros(eng.max_blocks, np.int32)
    qrow[:2] = cached_blocks[:2]
    for layer in range(model.config.num_layers):
        gkq, gvq = dense_gather_reference(
            eng.cache.kpool, eng.cache.vpool, layer,
            jnp.asarray(qrow), 8, scales=eng.cache.scales)
        gkf, gvf = dense_gather_reference(
            fp.cache.kpool, fp.cache.vpool, layer, jnp.asarray(row),
            8)
        for q, f in ((gkq, gkf), (gvq, gvf)):
            tol = 0.02 * max(np.abs(np.asarray(f)).max(), 1e-6)
            assert np.abs(np.asarray(q) - np.asarray(f)).max() <= tol


def test_quantized_eviction_under_pressure_stays_consistent(model):
    """A pool tight enough to evict cached quantized blocks mid-trace
    rides the same stall/retry path; allocate() resets recycled
    blocks' scale rows to the floor so a new tenant never quantizes
    on a stale grid."""
    from paddle_tpu.ops.paged_attention import KV_QUANT_EPS

    rng = np.random.RandomState(7)
    reqs = _mixed_trace(rng, n=3)
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=10, prefill_chunk=8,
                           kv_dtype="int8")
    out1 = _run_trace(eng, reqs) + _run_trace(eng, reqs, midrun=False)
    assert eng.cache.num_free == eng.cache.num_blocks - 1
    # a freshly allocated block's scale rows are back at the floor
    got = eng.cache.allocate(2)
    sc = np.asarray(eng.cache.scales)[:, got]
    assert np.all(sc == np.float32(KV_QUANT_EPS))
    eng.cache.free(got)
    # determinism: the same trace on a fresh engine replays exactly
    eng2 = GenerationEngine(model, num_slots=2, block_size=4,
                            num_blocks=10, prefill_chunk=8,
                            kv_dtype="int8")
    out2 = _run_trace(eng2, reqs) + _run_trace(eng2, reqs,
                                               midrun=False)
    assert out1 == out2


# ---------------------------------------------------------------------------
# int8 weights: quantize_weights / refresh_weights / dequantize(dtype=)
# ---------------------------------------------------------------------------

def test_weight_quantization_state_and_refresh():
    """weight_dtype='int8' swaps qkv/out/fc1/fc2 state entries for
    (int8 codes, per-output-channel scale) pairs; refresh_weights()
    requantizes after a live weight update (the served snapshot is
    weight-stationary, like the mp engine's)."""
    m = _model(seed=3)
    prompt = np.arange(5, dtype=np.int32)
    eng = GenerationEngine(m, num_slots=1, block_size=4,
                           prefill_chunk=8, weight_dtype="int8")
    quantized = [e for e in eng._state_arrays() if isinstance(e, tuple)]
    assert len(quantized) == 4 * m.config.num_layers
    for q, s in quantized:
        assert str(q.dtype) == "int8"
        assert str(s.dtype) == "float32" and s.shape[0] == 1
    rid = eng.add_request(prompt, 4)
    before = list(map(int, eng.run()[rid]))
    fp = GenerationEngine(m, num_slots=1, block_size=4,
                          prefill_chunk=8)
    ridf = fp.add_request(prompt, 4)
    ref = list(map(int, fp.run()[ridf]))
    from bench_ops import _token_match_fraction
    assert _token_match_fraction([ref], [before]) >= TOKEN_PARITY_MIN
    # a live weight update is invisible until requantized...
    w = m.gpt.blocks[0].attn.qkv_proj.weight
    old = w._array
    w._array = -old
    rid = eng.add_request(prompt, 4)
    assert list(map(int, eng.run()[rid])) == before
    # ...and visible after refresh_weights()
    eng.refresh_weights()
    ridf = fp.add_request(prompt, 4)
    want = list(map(int, fp.run()[ridf]))
    rid = eng.add_request(prompt, 4)
    got = list(map(int, eng.run()[rid]))
    assert _token_match_fraction([want], [got]) >= TOKEN_PARITY_MIN
    assert eng.decode_traces == 1      # refresh never retraces
    w._array = old


def test_dequantize_dtype_parameter_regression():
    """ISSUE 11 satellite: dequantize() grows dtype= (default fp32 —
    the legacy contract — regression-tested both ways)."""
    import jax.numpy as jnp

    from paddle_tpu.quantization import dequantize, quantize_absmax

    w = np.linspace(-3, 3, 24, dtype=np.float32).reshape(4, 6)
    q, s = quantize_absmax(w, axis=1)
    legacy = dequantize(q, s)
    assert legacy.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(legacy), w, atol=0.03)
    bf = dequantize(q, s, dtype=jnp.bfloat16)
    assert bf.dtype == jnp.bfloat16      # straight to compute dtype
    np.testing.assert_allclose(
        np.asarray(bf.astype(jnp.float32)), w, atol=0.05)


def test_steady_state_and_donation_with_int8(model):
    """A warmed int8 engine retraces nothing on churn; the pools stay
    donated ((1, 2) — the scale array rides undonated, it is tiny)."""
    rng = np.random.RandomState(9)
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=64, prefill_chunk=8,
                           kv_dtype="int8", donate=True)
    assert eng._donate_argnums == (1, 2)
    for _ in range(2):
        eng.add_request(rng.randint(0, VOCAB, 6).astype(np.int32), 3)
    eng.run()
    with jit.expect_traces(eng._decode_pure, 0), \
            jit.expect_traces(eng._prefill_pure, 0):
        eng.add_request(rng.randint(0, VOCAB, 9).astype(np.int32), 4)
        eng.run()


# ---------------------------------------------------------------------------
# bench row (CI-scale runner + suite registration)
# ---------------------------------------------------------------------------

def test_offered_load_int8_bench_row():
    """The gpt_engine_offered_load_int8 SUITE_ROWS runner at test
    scale: serves the same trace fp then int8 (KV + weights), asserts
    tolerance inside the runner, records tokens/s and pool bytes."""
    import bench_ops
    from paddle_tpu.models import GPTConfig

    cfg = GPTConfig.tiny(vocab=32, hidden=16, layers=1, heads=2,
                         seq=32)
    paddle.seed(0)
    rec = bench_ops._engine_offered_load_case(
        model_cfg=cfg, requests=[(3, 4), (6, 4), (10, 3)],
        num_slots=2, block_size=4, kv_dtype="int8")()
    assert rec["kv_dtype"] == "int8" and rec["weight_dtype"] == "int8"
    assert rec["tokens_per_s"] > 0 and rec["tokens_per_s_fp"] > 0
    assert rec["token_match_fraction"] >= bench_ops.INT8_TOKEN_PARITY_MIN
    assert rec["pool_bytes_int8"] < rec["pool_bytes_fp"]
    assert rec["pool_bytes_ratio"] <= 0.55
    assert rec["decode_recompiles"] == 0
    assert "gpt_engine_offered_load_int8" in bench_ops.suite_names()
