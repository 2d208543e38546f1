"""Multi-tenant adapter serving: paged batched-LoRA (ISSUE 13).

The contract, proven the way PR 8/11/12 proved theirs:

- MIXED-TENANT EXACTNESS: a multi-adapter trace served on ONE engine
  is token-identical, per request, to serving each request on a
  dedicated engine that only ever sees that adapter — across both
  attention backends and with speculation on (the verify window
  scores under the adapted model). No cross-slot adapter leakage, by
  assertion rather than by construction.
- NULL PATH: adapter id 0 is bit-identical to a pre-adapter engine
  across {dense,pallas} x K in {0,4} x
  mp in {1,2} (tier-1 runs a 4-cell cut; the other 4 cells are
  slow-marked), and `decode_traces == 1` per config regardless of how
  many adapters are live.
- PAGING: the adapter pool's refcount/LRU/stall-and-retry story
  mirrors the paged KV cache — eviction under pressure never changes
  tokens, `drain()` audits adapter-page refcounts as loudly as KV
  blocks, and the prefix-cache chain hash is adapter-salted so one
  tenant's KV can never alias another's.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.adapters import (AdapterRegistry, PagedAdapterPool,
                                 adapter_pool_spec)
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference import GenerationEngine, prefix_key

VOCAB = 64          # divisible by mp in {2, 4}


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


def _registry(cfg, max_rank=4, ranks=(2, 3), seed=7, scale=0.3,
              group=None):
    """A registry with len(ranks) strong adapters (ids 1..) — factors
    big enough that every adapter visibly changes greedy streams.
    `group` registers them all as ONE rank group (a tenant shipping
    quality/latency variants that share a single page budget)."""
    rng = np.random.RandomState(seed)
    reg = AdapterRegistry(cfg, max_rank=max_rank)
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


@pytest.fixture(scope="module")
def registry(model):
    return _registry(model.config)


def _mixed_trace(rng, adapters=(0, 1, 2), n_per=2):
    """Mixed lengths + a hot base prompt shared ACROSS adapters (the
    aliasing hazard the salt exists for): [(prompt, max_new, aid)]."""
    shared = rng.randint(0, VOCAB, 8).astype(np.int32)
    reqs = []
    for aid in adapters:
        for _ in range(n_per):
            reqs.append((rng.randint(0, VOCAB, rng.randint(2, 13))
                         .astype(np.int32), int(rng.randint(2, 6)),
                         aid))
        reqs.append((np.concatenate(
            [shared, rng.randint(0, VOCAB, 3)]).astype(np.int32), 4,
            aid))
        reqs.append((shared.copy(), 4, aid))
    return reqs


def _serve(eng, reqs, midrun=True):
    ids = [eng.add_request(p, n, adapter_id=a)
           for p, n, a in reqs[:len(reqs) // 2]]
    if midrun:
        for _ in range(2):
            eng.step()                 # admissions land mid-decode
    ids += [eng.add_request(p, n, adapter_id=a)
            for p, n, a in reqs[len(reqs) // 2:]]
    out = eng.run()
    return [list(map(int, out[rid])) for rid in ids]


# ---------------------------------------------------------------------------
# tentpole: mixed-tenant exactness vs dedicated engines
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,K", [("dense", 0), ("pallas", 4)])
def test_mixed_tenants_token_identical_to_dedicated(model, registry,
                                                    backend, K):
    """THE acceptance gate: one engine serving three tenants (base +
    two adapters) interleaved, with mid-run admissions, emits per
    request exactly the tokens a dedicated single-tenant engine
    would — both backends, speculation on for one of them, ONE decode
    trace regardless of tenant mix."""
    rng = np.random.RandomState(11)
    reqs = _mixed_trace(rng)

    def mk():
        return GenerationEngine(model, num_slots=3, block_size=4,
                                num_blocks=64, prefill_chunk=8,
                                spec_decode_k=K,
                                attention_backend=backend,
                                adapters=registry)

    eng = mk()
    mixed = _serve(eng, reqs)
    assert eng.decode_traces == 1, \
        f"{backend} K={K}: decode retraced on a tenant mix"
    for aid in (0, 1, 2):
        mine = [(i, r) for i, r in enumerate(reqs) if r[2] == aid]
        ded = mk()
        got = _serve(ded, [r for _, r in mine], midrun=False)
        assert ded.decode_traces == 1
        for (i, _), toks in zip(mine, got):
            assert toks == mixed[i], \
                (f"{backend} K={K}: adapter {aid} request {i} "
                 "diverged between mixed and dedicated serving")


def test_adapters_actually_change_tokens(model, registry):
    """Effectiveness sanity: a strong adapter's greedy stream differs
    from the base model's AND from another adapter's for the same
    prompt (otherwise every parity assert above is vacuous)."""
    p = np.arange(1, 9, dtype=np.int32)
    eng = GenerationEngine(model, num_slots=3, block_size=4,
                           prefill_chunk=8, adapters=registry)
    ids = [eng.add_request(p, 6, adapter_id=a) for a in (0, 1, 2)]
    out = eng.run()
    assert out[ids[0]] != out[ids[1]]
    assert out[ids[0]] != out[ids[2]]
    assert out[ids[1]] != out[ids[2]]
    # and the base lane matches the no-adapter oracle exactly
    ref = model.generate(
        Tensor._wrap(p[None]), max_length=len(p) + 6, use_cache=True)
    assert out[ids[0]] == list(map(int, np.asarray(ref._array)[0]))


# ---------------------------------------------------------------------------
# null path: adapter id 0 bit-identical to the pre-adapter engine
# ---------------------------------------------------------------------------

_CELLS = [(b, K, mp) for b in ("dense", "pallas") for K in (0, 4)
          for mp in (1, 2)]
_T1_CELLS = [("dense", 0, 1), ("pallas", 4, 2),
             ("dense", 4, 1), ("pallas", 0, 2)]


def _assert_null_cell(model, registry, backend, K, mp):
    rng = np.random.RandomState(5)
    reqs = [(p, n, 0) for p, n, _ in _mixed_trace(rng, adapters=(0,),
                                                  n_per=3)]

    def mk(adapters):
        return GenerationEngine(model, num_slots=2, block_size=4,
                                num_blocks=64, spec_decode_k=K,
                                attention_backend=backend,
                                mp_degree=mp, adapters=adapters,
                                prefill_chunk=8)

    plain = mk(None)
    ref = _serve(plain, reqs)
    lora = mk(registry)
    assert _serve(lora, reqs) == ref, \
        (f"{backend}/K={K}/mp={mp}: adapter id 0 diverged "
         "from the pre-adapter engine")
    assert plain.decode_traces == lora.decode_traces == 1


@pytest.mark.parametrize("backend,K,mp", _T1_CELLS)
def test_null_adapter_bit_identical(model, registry, backend, K, mp):
    """Adapter id 0 through a LoRA-enabled engine emits exactly the
    pre-adapter engine's tokens (tier-1 cut of the 8-cell matrix)."""
    _assert_null_cell(model, registry, backend, K, mp)


@pytest.mark.slow
@pytest.mark.parametrize("backend,K,mp",
                         [c for c in _CELLS if c not in _T1_CELLS])
def test_null_adapter_bit_identical_full_matrix(model, registry,
                                                backend, K, mp):
    """The remaining cells of the null-path matrix (identical
    machinery, outside the timed tier-1 window)."""
    _assert_null_cell(model, registry, backend, K, mp)


def test_mp2_and_int8_weights_compose(model, registry):
    """Adapters under the sharded engine (column-parallel B pages) are
    token-identical to mp=1, and int8 BASE weights compose with fp
    adapters (mixed == dedicated under the same quantized config)."""
    rng = np.random.RandomState(3)
    reqs = _mixed_trace(rng, n_per=1)

    def serve(**kw):
        eng = GenerationEngine(model, num_slots=2, block_size=4,
                               num_blocks=64, prefill_chunk=8,
                               adapters=registry, **kw)
        out = _serve(eng, reqs, midrun=False)
        assert eng.decode_traces == 1
        return out

    assert serve(mp_degree=2) == serve()
    q_mixed = serve(weight_dtype="int8")
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=64, prefill_chunk=8,
                           adapters=registry, weight_dtype="int8")
    only1 = [(i, r) for i, r in enumerate(reqs) if r[2] == 1]
    got = _serve(eng, [r for _, r in only1], midrun=False)
    for (i, _), toks in zip(only1, got):
        assert toks == q_mixed[i]


# ---------------------------------------------------------------------------
# prefix-cache adapter salting
# ---------------------------------------------------------------------------

def test_prefix_chain_is_adapter_salted(model, registry):
    """The same base prompt under two adapters must never share KV:
    the salted chains are disjoint per tenant, id-0 keys are exactly
    the unsalted ones, and a warm hit only ever lands same-tenant."""
    p = np.arange(12, dtype=np.int32)
    assert prefix_key(p, 4, 0) == prefix_key(p, 4)
    assert prefix_key(p, 4, 1) != prefix_key(p, 4)
    assert prefix_key(p, 4, 1) != prefix_key(p, 4, 2)
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=64, prefill_chunk=8,
                           adapters=registry)
    rid = eng.add_request(p, 3, adapter_id=1)
    warm1 = eng.run()[rid]
    # the published chain is adapter-1's: visible under its salt only
    c = eng.cache
    assert c.warm_prefix_tokens(p, adapter_id=1) == len(p)
    assert c.warm_prefix_tokens(p, adapter_id=2) == 0
    assert c.warm_prefix_tokens(p, adapter_id=0) == 0
    # router keys ARE cache keys: the prefix_key digests peek the
    # same depth the cache would serve
    assert c.warm_prefix_tokens(p, keys=prefix_key(p, 4, 1)) == len(p)
    # a warm re-serve under adapter 1 HITS (tokens unchanged); the
    # same prompt under adapter 2 misses and computes its own KV
    hit0 = eng.prefix_hit_tokens
    rid = eng.add_request(p, 3, adapter_id=1)
    assert eng.run()[rid] == warm1
    assert eng.prefix_hit_tokens > hit0
    hit1 = eng.prefix_hit_tokens
    rid = eng.add_request(p, 3, adapter_id=2)
    out2 = eng.run()[rid]
    assert eng.prefix_hit_tokens == hit1      # no cross-tenant hit
    assert out2 != warm1
    # dedicated-engine oracle for the adapter-2 stream
    ded = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=64, prefill_chunk=8,
                           adapters=registry)
    rid = ded.add_request(p, 3, adapter_id=2)
    assert ded.run()[rid] == out2


# ---------------------------------------------------------------------------
# paging: eviction under pressure, stall/retry, drain audit
# ---------------------------------------------------------------------------

def test_adapter_pool_eviction_never_changes_tokens(model, registry):
    """A 2-page pool (null + ONE tenant page) serving two adapters
    must swap/evict continuously — admissions stall-and-retry on
    page pressure — and still emit exactly the big-pool tokens."""
    rng = np.random.RandomState(9)
    reqs = _mixed_trace(rng, adapters=(1, 2), n_per=2)

    def serve(pages):
        eng = GenerationEngine(model, num_slots=2, block_size=4,
                               num_blocks=64, prefill_chunk=8,
                               adapters=registry,
                               adapter_pool_pages=pages)
        out = _serve(eng, reqs, midrun=False)
        eng.drain()                      # page accounting must close
        return out, eng

    big, _ = serve(pages=3)              # both tenants resident
    small, eng = serve(pages=2)          # one page: thrash
    assert small == big
    pool = eng.adapter_pool
    assert pool.evictions > 0 and pool.swapins > pool.evictions
    snap = eng.metrics_snapshot()
    stalls = [s for s in snap["engine_block_stalls_total"]["series"]
              if s["labels"]["path"] == "adapter"]
    assert stalls and stalls[0]["value"] > 0
    assert pool.leak_check() == []


def test_drain_audits_adapter_pages(model, registry):
    """A leaked adapter-page reference fails drain() as loudly as a
    leaked KV block."""
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           prefill_chunk=8, adapters=registry)
    rid = eng.add_request(np.arange(5, dtype=np.int32), 3,
                          adapter_id=1)
    eng.run()
    eng2 = GenerationEngine(model, num_slots=2, block_size=4,
                            prefill_chunk=8, adapters=registry)
    eng2.drain()                         # clean engine drains clean
    eng.adapter_pool.acquire(2)          # simulate a dropped release
    with pytest.raises(RuntimeError, match="adapter page"):
        eng.drain()


def test_prebuilt_pool_is_single_engine(model, registry):
    """Paging state is per-engine: a prebuilt pool adopted by one
    engine refuses a second (interleaved refcounts would make one
    replica's drain audit see another's live references); the
    REGISTRY is the safely-shared object."""
    pool = PagedAdapterPool(registry, num_pages=3)
    e1 = GenerationEngine(model, num_slots=1, block_size=4,
                          prefill_chunk=8, adapters=pool)
    assert e1.adapter_pool is pool
    with pytest.raises(ValueError, match="another"):
        GenerationEngine(model, num_slots=1, block_size=4,
                         prefill_chunk=8, adapters=pool)
    # one registry, two engines, two pools: fine
    e2 = GenerationEngine(model, num_slots=1, block_size=4,
                          prefill_chunk=8, adapters=registry)
    assert e2.adapter_pool is not pool


def test_pool_release_and_over_release_harden(model, registry):
    pool = PagedAdapterPool(registry, num_pages=3)
    page = pool.acquire(1)
    assert page != 0 and pool.page_of(1) == page
    assert pool.acquire(1) == page       # refcount 2, same page
    pool.release(1)
    pool.release(1)
    assert pool.leak_check() == []
    with pytest.raises(RuntimeError, match="release"):
        pool.release(1)
    # the null adapter is never paged
    assert pool.acquire(0) == 0 and pool.page_of(0) == 0
    pool.release(0)                      # no-op, never raises


# ---------------------------------------------------------------------------
# rank groups: one tenant at several ranks, ONE page budget (ISSUE 18
# — the grouped multi-rank tail of the PR 13 paged-pool design)
# ---------------------------------------------------------------------------

def test_rank_group_shares_one_page_budget(model):
    """Three rank variants of one tenant in a pool with room for all
    of them: switching variants must REUSE the group's single page in
    place (eviction + swap-in), a referenced sibling must stall the
    acquire, and the free pages must never be touched by the group."""
    reg = _registry(model.config, ranks=(2, 3, 4), group="tenantA")
    pool = PagedAdapterPool(reg, num_pages=4)    # null + 3 usable
    assert reg.group_of(1) == "tenantA"
    assert reg.group_ids("tenantA") == [1, 2, 3]
    page = pool.acquire(1)
    assert page != 0
    # sibling referenced by a live lane: variant switch stalls — and
    # the placement probe agrees BEFORE the acquire is attempted
    assert not pool.can_acquire(2)
    assert pool.acquire(2) is None
    assert pool.can_acquire(1)                   # resident variant: hit
    pool.release(1)
    # idle sibling: the variant lands on THE group page, in place
    assert pool.can_acquire(2)
    evictions = pool.evictions
    assert pool.acquire(2) == page
    assert pool.evictions == evictions + 1
    assert pool.page_of(1) is None and pool.page_of(2) == page
    pool.release(2)
    # prefetch honors the shared budget too: warms in place, takes no
    # reference, never grabs a second page
    assert pool.prefetch(3) == page
    assert pool.page_of(3) == page and pool.page_of(2) is None
    # ONE materialized page ever; the other two stayed truly free
    assert pool.num_resident == 1 and len(pool._free) == 2
    assert pool.leak_check() == []


def test_rank_group_leak_audit_flags_second_page(model, monkeypatch):
    """The audit half of the budget: if an acquire path ever lets a
    rank group spread over two pages (simulated here by disabling the
    sibling lookup), `leak_check` must flag it even though every page
    is properly released — the PR 13 refcount audit cannot see this
    class."""
    reg = _registry(model.config, ranks=(2, 3), group="tenantA")
    pool = PagedAdapterPool(reg, num_pages=3)
    monkeypatch.setattr(pool, "_group_sibling_page", lambda aid: None)
    pool.acquire(1)
    pool.acquire(2)
    pool.release(1)
    pool.release(2)
    leaked = pool.leak_check()
    assert leaked, "a rank group holding two pages passed the audit"


@pytest.mark.slow
def test_rank_group_serving_token_identical_under_shared_budget(model):
    """End to end through the admission path: two rank variants of
    one tenant interleaved across lanes. The shared budget turns
    concurrent variants into stall/retry admissions (the KV
    allocator's contract), pages swap in place — and the tokens are
    exactly the ungrouped registry's: grouping is paging policy, not
    numerics."""
    rng = np.random.RandomState(9)
    reqs = _mixed_trace(rng, adapters=(1, 2), n_per=2)

    def serve(group):
        reg = _registry(model.config, group=group)
        eng = GenerationEngine(model, num_slots=2, block_size=4,
                               num_blocks=64, prefill_chunk=8,
                               adapters=reg, adapter_pool_pages=4)
        out = _serve(eng, reqs, midrun=False)
        eng.drain()                      # group audit runs here too
        return out, eng

    plain, _ = serve(None)
    grouped, eng = serve("tenantA")
    assert grouped == plain
    pool = eng.adapter_pool
    assert pool.evictions > 0, "variants never swapped in place"
    snap = eng.metrics_snapshot()
    stalls = [s for s in snap["engine_block_stalls_total"]["series"]
              if s["labels"]["path"] == "adapter"]
    assert stalls and stalls[0]["value"] > 0, \
        "concurrent variants never contended for the shared page"
    assert pool.leak_check() == []


# ---------------------------------------------------------------------------
# registry validation + layout truth
# ---------------------------------------------------------------------------

def test_registry_validation(model):
    cfg = model.config
    reg = AdapterRegistry(cfg, max_rank=2)
    H = cfg.hidden_size
    ok = {"out": [(np.zeros((2, H), np.float32),
                   np.zeros((H, 2), np.float32))] * cfg.num_layers}
    with pytest.raises(ValueError, match="reserved"):
        reg.register(0, ok)
    with pytest.raises(ValueError, match="max_rank"):
        reg.register(1, {"out": [(np.zeros((3, H), np.float32),
                                  np.zeros((H, 3), np.float32))]
                         * cfg.num_layers})
    with pytest.raises(ValueError, match="want A"):
        reg.register(1, {"out": [(np.zeros((2, H + 1), np.float32),
                                  np.zeros((H, 2), np.float32))]
                         * cfg.num_layers})
    with pytest.raises(ValueError, match="unknown LoRA site"):
        reg.register(1, {"nope": ok["out"]})
    with pytest.raises(ValueError, match="per-layer"):
        reg.register(1, {"out": ok["out"][:1]})
    reg.register(1, ok)
    with pytest.raises(ValueError, match="already registered"):
        reg.register(1, ok)
    assert reg.has(1) and reg.has(0) and not reg.has(2)
    # engine-side intake validation
    eng = GenerationEngine(model, num_slots=1, block_size=4,
                           prefill_chunk=8)
    with pytest.raises(ValueError, match="adapters="):
        eng.add_request([1, 2, 3], 2, adapter_id=1)
    eng = GenerationEngine(model, num_slots=1, block_size=4,
                           prefill_chunk=8, adapters=reg)
    with pytest.raises(ValueError, match="not registered"):
        eng.add_request([1, 2, 3], 2, adapter_id=9)
    # a registry for a different geometry is rejected up front
    other = AdapterRegistry(
        type("C", (), {"num_layers": 1, "hidden_size": 32,
                       "intermediate_size": 128, "num_heads": 4})())
    with pytest.raises(ValueError, match="num_layers"):
        GenerationEngine(model, num_slots=1, block_size=4,
                         prefill_chunk=8, adapters=other)


def test_rank_padding_is_exact(model, registry):
    """A rank-2 adapter served from a max_rank=4 pool emits exactly
    the tokens the same adapter serves from a max_rank=2 pool: the
    padded rank rows are EXACT zeros, not noise."""
    cfg = model.config
    narrow = _registry(cfg, max_rank=2, ranks=(2,))
    wide = _registry(cfg, max_rank=4, ranks=(2,))
    p = np.arange(2, 9, dtype=np.int32)

    def serve(reg):
        eng = GenerationEngine(model, num_slots=1, block_size=4,
                               prefill_chunk=8, adapters=reg)
        rid = eng.add_request(p, 5, adapter_id=1)
        return eng.run()[rid]

    assert serve(narrow) == serve(wide)


def test_adapter_pool_spec_is_the_layout_truth(model, registry):
    """pool arrays, swap-in, and shard specs all derive from
    adapter_pool_spec — shapes match entry for entry, and the B pages
    (and only they) carry an mp shard axis."""
    pool = PagedAdapterPool(registry, num_pages=4)
    spec = pool.adapter_pool_spec()
    assert list(spec) == ["a_qkv", "b_qkv", "a_out", "b_out", "a_fc1",
                          "b_fc1", "a_fc2", "b_fc2", "scaling"]
    for arr, (shape, dt, _) in zip(pool.arrays(), spec.values()):
        assert tuple(arr.shape) == shape
    free = adapter_pool_spec(4, 2, 4, 32, 128, 4, np.float32)
    assert {k: v[0] for k, v in free.items()} \
        == {k: v[0] for k, v in spec.items()}
    assert [name for name, (_, _, ax) in spec.items()
            if ax is not None] == ["b_qkv", "b_out", "b_fc1", "b_fc2"]
    from paddle_tpu.distributed import serving_mesh

    sharded = PagedAdapterPool(registry, num_pages=4,
                               mesh=serving_mesh(2))
    specs = dict(zip(spec, sharded.pool_pspecs()))
    assert "mp" in specs["b_qkv"] and "mp" in specs["b_fc1"]
    assert specs["a_qkv"] == () and specs["scaling"] == ()


def test_lora_delta_matches_the_numpy_oracle(model):
    """The op-tier contract the engine parity tests CANNOT catch (a
    consistently-wrong layout would cancel between mixed and
    dedicated engines): the gathered delta equals the textbook
    `x . A^T . B^T * scaling` in the flat [3H]/[out] layout the user
    registered, null rows are exact zeros, and the head-major and
    3-major qkv orientations are transposes of one another."""
    from paddle_tpu.ops.lora import lora_linear_delta, lora_qkv_delta

    cfg = model.config
    H, L = cfg.hidden_size, cfg.num_layers
    rng = np.random.RandomState(0)
    A = rng.randn(2, H).astype(np.float32)
    Bq = rng.randn(3 * H, 2).astype(np.float32)
    Bo = rng.randn(H, 2).astype(np.float32)
    reg = AdapterRegistry(cfg, max_rank=4)
    reg.register(1, {"qkv": [(A, Bq)] * L, "out": [(A, Bo)] * L},
                 scaling=0.7)
    pool = PagedAdapterPool(reg, num_pages=3)
    page = pool.acquire(1)
    arrs = pool.arrays()
    x = rng.randn(3, 1, H).astype(np.float32)
    rows = np.asarray([page, 0, page], np.int32)
    want_q = (x[0, 0] @ A.T @ Bq.T) * 0.7          # flat [3H] oracle
    d = np.asarray(lora_qkv_delta(
        x, arrs[0], arrs[1], rows, arrs[8], 0,
        head_major=False)._array)                  # [B,S,3,heads,D]
    assert np.allclose(d[0, 0].reshape(3 * H), want_q, atol=1e-5)
    assert (d[1] == 0).all()                       # null page: exact 0
    dm = np.asarray(lora_qkv_delta(
        x, arrs[0], arrs[1], rows, arrs[8], 0,
        head_major=True)._array)                   # [B,S,heads,3,D]
    assert np.array_equal(dm[0, 0], d[0, 0].transpose(1, 0, 2))
    dl = np.asarray(lora_linear_delta(
        x, arrs[2], arrs[3], rows, arrs[8], 0)._array)
    assert np.allclose(dl[0, 0], (x[0, 0] @ A.T @ Bo.T) * 0.7,
                       atol=1e-5)
    assert (dl[1] == 0).all()


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_multitenant_lora_bench_runner_tiny(model):
    """The gpt_engine_multitenant_lora SUITE_ROWS runner at test
    scale: mixed-pool engine vs the engine-per-tenant strawman,
    outputs asserted identical inside the runner, per-tenant latency
    series populated, swap-ins visible with a page-tight pool."""
    import bench_ops

    assert "gpt_engine_multitenant_lora" in bench_ops.suite_names()
    rec = bench_ops._engine_multitenant_lora_case(
        model_cfg=model.config, num_tenants=3, per_tenant=4, rank=2,
        max_rank=4, prefix_len=8, suffix_max=6, max_new=6,
        num_slots=2, block_size=4, prefill_chunk=8,
        adapter_pool_pages=3)()
    assert rec["tokens_per_s"] > 0
    assert rec["tokens_per_s_dedicated"] > 0
    assert rec["tenants"] == 3 and rec["requests"] == 7
    assert rec["adapter_swapins"] > 0
    assert rec["decode_recompiles"] == 0
    assert set(rec["ttft_ms_p99_by_tenant"]) == {"1", "2", "3"}


def test_adapter_labeled_metrics(model, registry):
    """Per-tenant TTFT/TPOT series + pool paging health; a plain
    engine's exposition carries NONE of the adapter families."""
    rng = np.random.RandomState(2)
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           prefill_chunk=8, adapters=registry)
    for aid in (0, 1, 2):
        eng.add_request(rng.randint(0, VOCAB, 6).astype(np.int32), 3,
                        adapter_id=aid)
    eng.run()
    snap = eng.metrics_snapshot()
    ttft = {s["labels"]["adapter"]: s
            for s in snap["engine_adapter_ttft_seconds"]["series"]}
    assert set(ttft) == {"0", "1", "2"}
    assert all(s["count"] == 1 for s in ttft.values())
    tpot = {s["labels"]["adapter"]: s
            for s in snap["engine_adapter_tpot_seconds"]["series"]}
    assert set(tpot) == {"0", "1", "2"}
    assert snap["engine_adapter_pool_pages"]["series"][0]["value"] \
        == 1 + eng.num_slots
    assert snap["engine_adapter_pool_resident"]["series"][0][
        "value"] == 2
    assert snap["engine_adapter_swapins_total"]["series"][0][
        "value"] == 2
    assert snap["engine_adapter_pool_used_pages"]["series"][0][
        "value"] == 0                    # all lanes finished
    # the priority-labeled SLO series are untouched
    assert snap["engine_ttft_seconds"]["series"][0]["count"] == 3
    plain = GenerationEngine(model, num_slots=2, block_size=4,
                             prefill_chunk=8)
    assert "engine_adapter_ttft_seconds" not in plain.metrics_snapshot()


def test_alpha_with_mixed_ranks_is_rejected(model):
    """alpha=/rank is ambiguous when sites carry different ranks (one
    adapter-wide scaling cannot express per-module alpha/r) — require
    an explicit scaling instead of silently picking a rank."""
    cfg = model.config
    reg = AdapterRegistry(cfg, max_rank=8)
    H = cfg.hidden_size
    w = {"out": [(np.zeros((2, H), np.float32) + 1,
                  np.zeros((H, 2), np.float32) + 1)] * cfg.num_layers,
         "fc1": [(np.zeros((4, H), np.float32) + 1,
                  np.zeros((cfg.intermediate_size, 4),
                           np.float32) + 1)] * cfg.num_layers}
    with pytest.raises(ValueError, match="mixed ranks"):
        reg.register(1, w, alpha=16)
    reg.register(1, w, scaling=2.0)      # explicit scaling is fine
    assert reg.scaling_of(1) == 2.0


# ---------------------------------------------------------------------------
# live registration (ISSUE 17 satellite: the PR 13 operational tail)
# ---------------------------------------------------------------------------

def _adapter_weights(cfg, rank, seed, scale=0.3):
    """One adapter's weight dict, deterministic in `seed` — so two
    registries built on different schedules can hold bit-identical
    factors for the same adapter id."""
    rng = np.random.RandomState(seed)
    H, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    w = {}
    for site, (i_d, o_d) in (("qkv", (H, 3 * H)), ("out", (H, H)),
                             ("fc1", (H, I)), ("fc2", (I, H))):
        w[site] = [(rng.randn(rank, i_d).astype(np.float32) * scale,
                    rng.randn(o_d, rank).astype(np.float32) * scale)
                   for _ in range(L)]
    return w


def test_live_adapter_registration_token_identical(model):
    """Registering a NEW adapter on a registry already wired into a
    serving engine is legal (no construction-time freeze) and the
    late tenant's streams are token-identical to an engine whose
    registry carried it from the start — with tracing ON, the cold
    swap-in shows up as a labeled `adapter.swap_in` span and
    `decode_traces == 1` survives the tenant-set growth."""
    cfg = model.config
    w1 = _adapter_weights(cfg, 2, seed=21)
    w2 = _adapter_weights(cfg, 3, seed=22)

    def mk(reg, tracing=False):
        return GenerationEngine(model, num_slots=2, block_size=4,
                                num_blocks=64, prefill_chunk=8,
                                adapters=reg, tracing=tracing)

    rng = np.random.RandomState(3)
    reqs = [(rng.randint(0, VOCAB, rng.randint(3, 10))
             .astype(np.int32), int(rng.randint(2, 6)), aid)
            for aid in (0, 2, 1, 2) for _ in range(1)]

    # reference: adapter 2 present before the engine ever existed
    reg_ref = AdapterRegistry(cfg, max_rank=4)
    reg_ref.register(1, w1, scaling=0.5)
    reg_ref.register(2, w2, scaling=0.5)
    ref = _serve(mk(reg_ref), reqs, midrun=False)

    # live path: engine built with ONLY adapter 1; tenant 2 arrives
    # after construction — and after the engine has already served
    reg_live = AdapterRegistry(cfg, max_rank=4)
    reg_live.register(1, w1, scaling=0.5)
    eng = mk(reg_live, tracing=True)
    warm = [r for r in reqs if r[2] != 2]
    pre = _serve(eng, warm, midrun=False)
    assert pre == [t for t, r in zip(ref, reqs) if r[2] != 2]
    with pytest.raises(ValueError, match="is not registered"):
        eng.add_request(reqs[0][0], 2, adapter_id=2)
    reg_live.register(2, w2, scaling=0.5)          # live registration
    late = _serve(eng, reqs, midrun=False)
    assert late == ref
    assert eng.decode_traces == 1
    swaps = [e for e in eng.tracer.snapshot()
             if e["name"] == "adapter.swap_in"]
    assert any(e["args"]["adapter"] == 2 for e in swaps)
    # the live id is still guarded: re-registering it raises
    with pytest.raises(ValueError, match="already registered"):
        reg_live.register(2, w2, scaling=0.5)
