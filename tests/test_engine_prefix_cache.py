"""Prefix-cached paged KV + chunked prefill + admission QoS (ISSUE 6).

The serving-scheduler contracts, proven the way PR 1/3 proved theirs:
token-exact parity (prefix cache on vs off, cold vs warm, chunked vs
legacy whole-bucket prefill, all against the single-request compiled
decode oracle), copy-on-write leaving cached KV byte-identical,
refcount/eviction bookkeeping, trace-count bounds via jit.count_traces
(decode == 1, chunked prefill == 1 regardless of prompt-length mix),
allocator hardening (double-free / null-block free raise), QoS
priority admission + shed-on-saturation, and the instant-finish TPOT
accounting fix.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.jit as jit
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference import GenerationEngine, PagedKVCache
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


# ---------------------------------------------------------------------------
# satellite: allocator hardening
# ---------------------------------------------------------------------------

def test_paged_kv_cache_free_hardening():
    """free() must raise on double-free and on the null block — a
    scheduler bug silently double-allocating a live block is the worst
    kind of KV corruption (two requests writing one block)."""
    c = PagedKVCache(1, 6, 4, 2, 8)
    blocks = c.allocate(2)
    assert all(c.refcount(b) == 1 for b in blocks)
    c.free(blocks)
    with pytest.raises(RuntimeError, match="double free"):
        c.free([blocks[0]])
    with pytest.raises(ValueError, match="null block"):
        c.free([0])
    # share/free pairs keep the count exact
    (b,) = c.allocate(1)
    c.share([b])
    assert c.refcount(b) == 2
    c.free([b])
    assert c.refcount(b) == 1
    c.free([b])
    with pytest.raises(RuntimeError, match="double free"):
        c.free([b])
    with pytest.raises(RuntimeError, match="dead block"):
        c.share([b])


def test_prefix_cache_match_register_evict_lifecycle():
    """Unit-level prefix map mechanics: register publishes full blocks,
    match takes refs (reviving evictable entries), refcount-zero cached
    blocks are evicted LRU-deepest-first only under allocation
    pressure, and first-writer-wins on hash races."""
    c = PagedKVCache(1, 8, 4, 2, 8)        # 7 usable blocks
    toks = np.arange(12, dtype=np.int32)   # 3 full blocks
    blocks = c.allocate(3)
    assert c.register_prefix(toks, blocks) == 3
    assert c.num_cached_blocks == 3
    # a racing identical prompt keeps the original mapping
    other = c.allocate(3)
    assert c.register_prefix(toks, other) == 0
    c.free(other)

    hit_blocks, hit = c.match_prefix(np.concatenate([toks, [7, 7]]))
    assert hit == 12 and hit_blocks == blocks
    assert all(c.refcount(b) == 2 for b in blocks)
    c.free(hit_blocks)
    # a shorter prefix only matches its aligned part
    part, hit = c.match_prefix(toks[:9])   # 2 full blocks + 1 token
    assert hit == 8 and part == blocks[:2]
    c.free(part)
    # a diverging prompt misses
    div = toks.copy()
    div[0] += 1
    assert c.match_prefix(div) == ([], 0)

    # owner releases: blocks go EVICTABLE (still matchable), not free
    c.free(blocks)
    assert c.num_free == 7 and c.num_cached_blocks == 3
    again, hit = c.match_prefix(toks)
    assert hit == 12 and again == blocks   # revived from evictable
    c.free(blocks)
    # allocation pressure evicts cold cache blocks (deepest link first)
    got = c.allocate(6)                    # 4 free + 2 evicted
    assert got is not None and c.num_cached_blocks == 1
    _, hit = c.match_prefix(toks)
    assert hit == 4                        # only the chain head is left
    assert c.allocate(2) is None           # stall path intact


# ---------------------------------------------------------------------------
# tentpole: token-exact parity across every scheduler mode
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


def test_chunked_cache_on_off_all_token_identical(model):
    """THE acceptance gate: one mixed trace (prompts shorter and longer
    than the chunk, shared prefixes by construction) through (a) the
    prefix cache off, (b) cache cold, (c) cache warm — identical outputs
    everywhere, equal to the single-request oracle; decode compiles
    once and the prefill chunk compiles once TOTAL (bounded by the
    chunk shape, not the prompt-length mix); the warm pass serves hit
    tokens without prefill compute."""
    rng = np.random.RandomState(11)
    base = _trace(rng, 6)
    shared = rng.randint(0, VOCAB, 8).astype(np.int32)   # hot prefix
    reqs = base + [
        (np.concatenate([shared, rng.randint(0, VOCAB, 3)])
         .astype(np.int32), 4),
        (np.concatenate([shared, rng.randint(0, VOCAB, 5)])
         .astype(np.int32), 3),
        (shared.copy(), 4),            # block-aligned full-prefix hit
    ]

    def mk(**kw):
        return GenerationEngine(model, num_slots=3, block_size=4,
                                num_blocks=64, **kw)

    eng_off = mk(prefill_chunk=8, enable_prefix_cache=False)
    outs_off = _run_trace(eng_off, reqs)
    eng = mk(prefill_chunk=8)
    outs_cold = _run_trace(eng, reqs)
    chunks_cold = series_total(eng.metrics_snapshot(),
                               "engine_prefill_chunks_total")
    outs_warm = _run_trace(eng, reqs, midrun=False)   # same engine
    snap = eng.metrics_snapshot()
    chunks_warm = series_total(
        snap, "engine_prefill_chunks_total") - chunks_cold

    for (p, n), a, b, c in zip(reqs, outs_off, outs_cold, outs_warm):
        want = _reference(model, p, n)
        np.testing.assert_array_equal(a, want)
        np.testing.assert_array_equal(b, want)
        np.testing.assert_array_equal(c, want)

    # cache off never hits; cold run hits the shared prefix reqs
    assert eng_off.prefix_hit_tokens == 0
    assert series_total(snap,
                        "engine_prefix_cache_hit_tokens_total") > 0
    # warm pass: every prompt re-served from cache -> fewer chunks
    assert 0 < chunks_warm < chunks_cold
    # trace bounds: ONE decode program, ONE chunk program, ONE cow
    # program across all of that churn (cache on/off, cold/warm)
    for e in (eng, eng_off):
        assert e.decode_traces == 1
        assert e.prefill_traces == 1
    assert eng._cow_pure.traces <= 1
    # steady state: a warmed engine retraces NOTHING
    with jit.expect_traces(eng._decode_pure, 0), \
            jit.expect_traces(eng._prefill_pure, 0):
        eng.add_request(rng.randint(0, VOCAB, 13), 3)
        eng.run()
    # drained: every block reference returned (cached blocks count as
    # allocatable capacity)
    assert eng.cache.num_free == eng.cache.num_blocks - 1


def test_full_prefix_hit_cow_keeps_cached_blocks_byte_identical(model):
    """A block-aligned prompt served twice: the second request seats
    ALL its blocks from the cache (zero prefill chunks) and its first
    decode write lands inside a cached block — copy-on-write must give
    it a private copy and leave the cached KV bytes untouched, so a
    third request still hits pristine content."""
    from paddle_tpu.ops.paged_attention import dense_gather_reference

    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=32, prefill_chunk=8)
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, VOCAB, 8).astype(np.int32)  # 2 full blocks
    want = _reference(model, prompt, 5)

    ra = eng.add_request(prompt, 5)
    outa = eng.run()
    np.testing.assert_array_equal(np.asarray(outa[ra]), want)
    cached, hit = eng.cache.match_prefix(prompt)
    assert hit == 8
    row = np.zeros(eng.max_blocks, np.int32)
    row[:len(cached)] = cached
    gk0, gv0 = dense_gather_reference(eng.cache.kpool, eng.cache.vpool,
                                      0, row, 8)
    eng.cache.free(cached)

    chunks0 = series_total(eng.metrics_snapshot(),
                           "engine_prefill_chunks_total")
    rb = eng.add_request(prompt, 5)
    outb = eng.run()
    snap = eng.metrics_snapshot()
    np.testing.assert_array_equal(np.asarray(outb[rb]), want)
    # full hit: no prefill chunk ran, COW promoted the write block
    assert series_total(snap, "engine_prefill_chunks_total") == chunks0
    assert series_total(snap, "engine_cow_copies_total") >= 1
    # the cached blocks' KV is byte-identical after B's decode run
    gk1, gv1 = dense_gather_reference(eng.cache.kpool, eng.cache.vpool,
                                      0, row, 8)
    np.testing.assert_array_equal(np.asarray(gk0), np.asarray(gk1))
    np.testing.assert_array_equal(np.asarray(gv0), np.asarray(gv1))
    # and a third request still decodes exactly
    rc = eng.add_request(prompt, 5)
    np.testing.assert_array_equal(np.asarray(eng.run()[rc]), want)


def test_eviction_under_pressure_stays_exact(model):
    """A pool far smaller than the distinct-prompt working set: cold
    cached blocks must be evicted (LRU) to serve new admissions, with
    every output still exact and the allocator ending balanced."""
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=9, prefill_chunk=8)
    rng = np.random.RandomState(7)
    reqs = [(rng.randint(0, VOCAB, 8).astype(np.int32), 3)
            for _ in range(6)]          # 6 distinct 2-block prompts
    for p, n in reqs:
        rid = eng.add_request(p, n)
        np.testing.assert_array_equal(np.asarray(eng.run()[rid]),
                                      _reference(model, p, n))
    snap = eng.metrics_snapshot()
    # the cache filled, then pressure forced evictions: fewer resident
    # cached blocks than the 12 full prompt blocks seen
    resident = snap["engine_prefix_cached_blocks"]["series"][0]["value"]
    assert 0 < resident <= 8
    assert eng.cache.num_free == eng.cache.num_blocks - 1
    # a repeat of the LAST prompt still hits (most recently used)
    base = eng.prefix_hit_tokens
    rid = eng.add_request(reqs[-1][0], 2)
    eng.run()
    assert eng.prefix_hit_tokens > base


# ---------------------------------------------------------------------------
# tentpole: admission QoS
# ---------------------------------------------------------------------------

def test_priority_classes_order_admission_and_label_metrics(model):
    """Priority classes admit best-first regardless of arrival order,
    and TTFT/TPOT land in priority-labeled series."""
    eng = GenerationEngine(model, num_slots=1, block_size=4,
                           num_blocks=32, prefill_chunk=8)
    rng = np.random.RandomState(3)
    # prompts span two chunks, so after one step the admitted request
    # is still seated (mid-prefill) and observable
    rb = eng.add_request(rng.randint(0, VOCAB, 12), 2, priority="batch")
    ri = eng.add_request(rng.randint(0, VOCAB, 12), 2,
                         priority="interactive")
    eng.step()                          # one admission: the single lane
    seated = [s for s in eng._slots if s is not None]
    assert seated and seated[0].req.req_id == ri   # jumped the queue
    out = eng.run()
    assert set(out) == {rb, ri}
    with pytest.raises(ValueError, match="priority"):
        eng.add_request([1, 2], 2, priority="vip")
    snap = eng.metrics_snapshot()
    ttft_by = {s["labels"]["priority"]: s["count"]
               for s in snap["engine_ttft_seconds"]["series"]}
    assert ttft_by.get("interactive") == 1
    assert ttft_by.get("batch") == 1


def test_shed_on_saturation_prefers_high_priority(model):
    """max_queue exceeded: the lowest class loses — either the worst
    queued request (when the incoming ranks higher) or the incoming
    one; shed results surface as None and engine_shed_total counts
    them by class."""
    eng = GenerationEngine(model, num_slots=1, block_size=4,
                           num_blocks=32, prefill_chunk=8, max_queue=2)
    rng = np.random.RandomState(4)
    p = rng.randint(0, VOCAB, 4).astype(np.int32)
    keep = [eng.add_request(p, 2, priority="standard"),
            eng.add_request(p, 2, priority="batch")]
    # queue full (lane not yet filled: nothing ran). Interactive
    # arrival sheds the newest batch request...
    vip = eng.add_request(p, 2, priority="interactive")
    # ...and a batch arrival into a still-full queue sheds ITSELF
    loser = eng.add_request(p, 2, priority="batch")
    out = eng.run()
    assert out[keep[1]] is None and out[loser] is None
    assert out[keep[0]] is not None and out[vip] is not None
    np.testing.assert_array_equal(np.asarray(out[vip]),
                                  _reference(model, p, 2))
    snap = eng.metrics_snapshot()
    shed_by = {s["labels"]["priority"]: s["value"]
               for s in snap["engine_shed_total"]["series"]}
    assert shed_by == {"batch": 2.0}


# ---------------------------------------------------------------------------
# satellite: instant-finish TPOT accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache", [True, False])
def test_instant_finish_lands_in_tpot_histogram(model, cache):
    """A max_new_tokens==1 request produces exactly one token and used
    to vanish from the TPOT histogram while still counting in
    engine_tokens_generated_total; its producing-step latency must now
    be recorded — from the prompt's last chunk (prefix cache on or
    off), and on the full-prefix-hit decode path too."""
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=32, prefill_chunk=8,
                           enable_prefix_cache=cache)
    rng = np.random.RandomState(9)
    p = rng.randint(0, VOCAB, 8).astype(np.int32)
    eng.add_request(p, 1)
    eng.run()
    snap = eng.metrics_snapshot()
    tpot = sum(s["count"]
               for s in snap["engine_tpot_seconds"]["series"])
    assert tpot == 1                   # the single token is visible
    assert series_total(snap, "engine_tokens_generated_total") == 1
    if cache:
        # the same prompt again: full-prefix hit, first token comes
        # from the DECODE step — still visible
        eng.add_request(p, 1)
        eng.run()
        snap = eng.metrics_snapshot()
        assert sum(s["count"] for s in
                   snap["engine_tpot_seconds"]["series"]) == 2


# ---------------------------------------------------------------------------
# satellite: bench rows (CI-scale runners + suite registration)
# ---------------------------------------------------------------------------

def test_prefix_cache_and_chunked_bench_rows():
    """The prefix-cache SUITE_ROW at test scale: the multi-tenant trace
    runner must show warm prefix hits skipping prefill compute (hit
    tokens > 0, fewer chunk dispatches than cold)."""
    import bench_ops
    from paddle_tpu.models import GPTConfig

    cfg = GPTConfig.tiny(vocab=32, hidden=16, layers=1, heads=2, seq=64)
    paddle.seed(0)
    rec = bench_ops._engine_prefix_cache_case(
        model_cfg=cfg, num_tenants=2, per_tenant=2, uniques=1,
        prefix_len=8, suffix_max=4, max_new=3, num_slots=2,
        block_size=4, prefill_chunk=8)()
    assert rec["hit_tokens"] > 0
    assert rec["prefill_chunks_warm"] < rec["prefill_chunks_cold"]
    assert rec["tokens_per_s"] > 0 and rec["ms"] > 0

    assert "gpt_engine_prefix_cache" in bench_ops.suite_names()
