"""Replica-parallel serving tier (ISSUE 12): ServingFleet — the
prefix-affinity dp router over GenerationEngine replicas, with
disaggregated prefill/decode.

The contracts, proven the way the engine PRs proved theirs:

- ONE hashing truth: router keys ARE cache keys (`prefix_key` backs
  both `PagedKVCache.match_prefix`/`register_prefix` and the fleet's
  affinity decision), for aligned and ragged prompt lengths.
- Token exactness: a 1-replica fleet is BIT-identical to a bare
  engine on the same mixed-length QoS trace; an N-replica fleet
  produces the same per-request tokens (order-independent); the
  disaggregated prefill->decode handoff (block export/ingest +
  mid-stream adoption) is token-identical to a colocated engine at
  kv_dtype in {fp, int8} and under both prefill modes.
- Affinity routing demonstrably lands warm requests on the
  block-owning replica (hit tokens > 0 there, 0 elsewhere), and
  hysteresis spills a hot tenant once the warm replica's backlog
  exceeds the slack.
- drain(): admissions closed, in-flight lanes finished, every
  non-cached block back on the free list (the leak-check class the
  allocator's double-free hardening can't see).
- Fleet metrics fold replica-labeled through the exact-merge
  machinery (engine-metrics contract at N=2), and replicas
  join/leave the elastic registry under its token auth.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (GenerationEngine, PagedKVCache,
                                  ServingFleet, prefix_key)
from paddle_tpu.observability.metrics import (label_snapshot,
                                              merge_snapshots,
                                              series_total)

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


def _mixed_trace(rng, n=8):
    """(prompt, max_new, priority) mixed-length QoS trace."""
    prios = ("interactive", "standard", "batch")
    return [(rng.randint(0, VOCAB, int(rng.randint(3, 40))),
             int(rng.randint(2, 10)), prios[i % 3])
            for i in range(n)]


def _serve_engine(model, trace, eos=None, **kw):
    eng = GenerationEngine(model, num_slots=4, block_size=8, **kw)
    ids = [eng.add_request(p, max_new_tokens=n, priority=pr,
                           eos_token_id=eos)
           for p, n, pr in trace]
    out = eng.run()
    return {i: out[i] for i in ids}


def _serve_fleet(model, trace, eos=None, fleet_kw=(), **kw):
    fleet = ServingFleet(model, num_slots=4, block_size=8,
                         **dict(fleet_kw), **kw)
    ids = [fleet.add_request(p, max_new_tokens=n, priority=pr,
                             eos_token_id=eos)
           for p, n, pr in trace]
    out = fleet.run()
    return fleet, {i: out[i] for i in ids}


# ---------------------------------------------------------------------------
# satellite: one hashing truth — router keys ARE cache keys
# ---------------------------------------------------------------------------

def test_prefix_key_is_the_cache_key_aligned_and_ragged():
    """The digests prefix_key computes are exactly the keys the cache
    registers and matches under — for block-aligned prompts and for
    ragged tails (which must contribute nothing)."""
    bs = 4
    c = PagedKVCache(1, 10, bs, 2, 8)
    aligned = np.arange(12, dtype=np.int32)          # 3 full blocks
    ragged = np.concatenate([aligned, [7, 7]])       # + 2-token tail
    keys = prefix_key(aligned, bs)
    assert len(keys) == 3
    assert prefix_key(ragged, bs) == keys            # tail ignored
    assert prefix_key(aligned[:9], bs) == keys[:2]   # ragged shorter
    assert prefix_key(aligned[:3], bs) == ()         # sub-block
    # registering under the cache's walk publishes EXACTLY these keys
    blocks = c.allocate(3)
    assert c.register_prefix(aligned, blocks) == 3
    assert set(c._block_of) == set(keys)
    assert [c._block_of[k] for k in keys] == blocks
    # a router peek agrees with a cache match at every raggedness
    for toks in (aligned, ragged, aligned[:9], aligned[:3]):
        peek = c.warm_prefix_tokens(toks)
        got, hit = c.match_prefix(toks)
        assert peek == hit == (len(toks) // bs) * bs
        if got:
            c.free(got)
    # prefix-safety: same block content after a different parent
    # yields a DIFFERENT key chain
    shifted = np.concatenate([[9], aligned[:-1]]).astype(np.int32)
    assert prefix_key(shifted, bs)[1:] != keys[1:]
    assert c.warm_prefix_tokens(shifted) == 0


# ---------------------------------------------------------------------------
# tentpole: fleet-vs-engine token exactness
# ---------------------------------------------------------------------------

def test_single_replica_fleet_bit_identical_to_bare_engine(model):
    """The same mixed-length QoS trace through a 1-replica fleet and a
    bare engine: identical req ids, identical token lists — the fleet
    tier adds routing, not numerics."""
    rng = np.random.RandomState(0)
    trace = _mixed_trace(rng, n=8)
    ref = _serve_engine(model, trace, eos=5)
    _, got = _serve_fleet(model, trace, eos=5,
                          fleet_kw={"num_replicas": 1})
    assert got == ref


@pytest.mark.parametrize("n_replicas", [2, 3])
def test_n_replica_fleet_per_request_identical(model, n_replicas):
    """Whatever replica a request lands on, its tokens must equal the
    bare engine's (order-independent): replicas share the weights and
    the compiled-step numerics, and routing must not change either."""
    rng = np.random.RandomState(1)
    trace = _mixed_trace(rng, n=10)
    ref = _serve_engine(model, trace, eos=5)
    fleet, got = _serve_fleet(
        model, trace, eos=5, fleet_kw={"num_replicas": n_replicas})
    assert got == ref
    # the load actually spread: more than one replica generated
    active = [r.rid for r in fleet._replicas.values()
              if r.engine.tokens_generated > 0]
    assert len(active) > 1, "router sent everything to one replica"


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_disaggregated_fleet_token_exact(model, kv_dtype):
    """The ambitious end state: dedicated prefill replicas hand
    finished KV blocks (+ int8 scale rows) into a decode replica's
    pool via the compiled export/ingest path, and the output stays
    EXACTLY what a colocated engine of the same config produces —
    fp and quantized pools."""
    rng = np.random.RandomState(2)
    trace = _mixed_trace(rng, n=6)
    kw = {"kv_dtype": kv_dtype}
    ref = _serve_engine(model, trace, eos=5, **kw)
    fleet, got = _serve_fleet(
        model, trace, eos=5,
        fleet_kw={"num_replicas": 1, "num_prefill_replicas": 1}, **kw)
    assert got == ref
    snap = fleet.metrics_snapshot()
    assert series_total(snap, "fleet_handoffs_total") > 0
    assert series_total(snap, "fleet_handoff_blocks_total") > 0
    # the handoff seam stayed shape-stable: one decode trace per
    # replica, no recompiles
    for rep in fleet._replicas.values():
        assert rep.engine.decode_traces <= 1


def test_disaggregated_prefill_never_decodes(model):
    """Role separation is real: prefill replicas emit exactly one
    token per request (the final chunk's), decode replicas run no
    prefill chunks — long-prompt admission can't steal decode-step
    FLOPs by construction."""
    rng = np.random.RandomState(3)
    trace = _mixed_trace(rng, n=5)
    fleet, _ = _serve_fleet(
        model, trace,
        fleet_kw={"num_replicas": 1, "num_prefill_replicas": 1})
    roles = {r.role: r.engine for r in fleet._replicas.values()}
    pre_snap = roles["prefill"].metrics.snapshot()
    dec_snap = roles["decode"].metrics.snapshot()
    assert roles["prefill"].tokens_generated == 5  # one per request
    # every prefill-side finish is a handoff, none a decode finish
    pre_fin = {s["labels"]["reason"]: s["value"]
               for s in pre_snap["engine_finished_total"]["series"]}
    assert set(pre_fin) == {"handoff"} and pre_fin["handoff"] == 5
    assert series_total(dec_snap, "engine_prefill_chunks_total") == 0
    assert roles["decode"].tokens_generated > 0


# ---------------------------------------------------------------------------
# tentpole: prefix-affinity routing with hysteresis
# ---------------------------------------------------------------------------

def test_affinity_routes_warm_requests_to_block_owner(model):
    """After a cold pass seeds one replica's prefix cache, every warm
    request for that tenant must land on the block-owning replica and
    be served from its cache (hit tokens > 0 there, zero on the
    other)."""
    rng = np.random.RandomState(4)
    fleet = ServingFleet(model, num_replicas=2, num_slots=4,
                         block_size=8)
    tenant = rng.randint(0, VOCAB, 24)          # 3 full blocks
    fleet.add_request(np.concatenate([tenant, rng.randint(0, VOCAB, 3)]),
                      max_new_tokens=3)
    fleet.run()
    owner = [r for r in fleet._replicas.values()
             if r.engine.cache.warm_prefix_tokens(tenant) > 0]
    assert len(owner) == 1                       # exactly one owner
    owner = owner[0]
    for _ in range(3):                           # warm passes
        fleet.add_request(
            np.concatenate([tenant, rng.randint(0, VOCAB, 3)]),
            max_new_tokens=3)
        fleet.run()
    snap = fleet.metrics_snapshot()
    routed = {(s["labels"]["replica"], s["labels"]["reason"]):
              s["value"] for s in snap["fleet_routed_total"]["series"]}
    assert routed.get((str(owner.rid), "affinity")) == 3
    assert series_total(snap, "fleet_affinity_hit_tokens_total") \
        == 3 * 24
    for rep in fleet._replicas.values():
        hits = series_total(
            rep.engine.metrics.snapshot(),
            "engine_prefix_cache_hit_tokens_total")
        assert (hits > 0) == (rep.rid == owner.rid)


def test_affinity_hysteresis_spills_hot_tenant(model):
    """affinity_slack bounds the imbalance affinity may create: with
    slack 0, the second warm request (warm replica already carrying
    the first) must spill to the least-loaded replica instead of
    queueing behind its tenant-mates."""
    rng = np.random.RandomState(5)
    fleet = ServingFleet(model, num_replicas=2, num_slots=4,
                         block_size=8, affinity_slack=0)
    tenant = rng.randint(0, VOCAB, 16)
    fleet.add_request(tenant, max_new_tokens=2)
    fleet.run()                                  # seed the owner
    # two warm adds back-to-back WITHOUT running: the first takes the
    # affinity route (loads equal), making the owner strictly more
    # loaded — the second must fall back to least-loaded
    fleet.add_request(np.concatenate([tenant, [1]]), max_new_tokens=2)
    fleet.add_request(np.concatenate([tenant, [2]]), max_new_tokens=2)
    snap = fleet.metrics_snapshot()
    by_reason = {}
    for s in snap["fleet_routed_total"]["series"]:
        by_reason[s["labels"]["reason"]] = \
            by_reason.get(s["labels"]["reason"], 0) + s["value"]
    assert by_reason.get("affinity") == 1
    assert by_reason.get("least_loaded") == 2    # cold seed + spill
    fleet.run()


# ---------------------------------------------------------------------------
# satellite: drain — admissions closed, lanes finished, no leaks
# ---------------------------------------------------------------------------

def test_engine_drain_finishes_and_leak_checks(model):
    """drain(): rejects new admissions, runs existing lanes to
    completion, and audits that every non-cached block returned to
    the free list (cached blocks parked evictable)."""
    rng = np.random.RandomState(6)
    eng = GenerationEngine(model, num_slots=2, block_size=8)
    ids = [eng.add_request(rng.randint(0, VOCAB, 12), max_new_tokens=4)
           for _ in range(4)]
    out = eng.drain()
    assert sorted(out) == sorted(ids)
    assert all(len(out[i]) == 12 + 4 for i in ids)
    with pytest.raises(RuntimeError, match="draining"):
        eng.add_request([1, 2], max_new_tokens=1)
    with pytest.raises(RuntimeError, match="draining"):
        eng.adopt_request([1, 2], 3, [1], 2)
    assert eng.cache.leak_check() == []


def test_engine_drain_catches_block_leak(model):
    """The audit really fires: a block held without an owner (the
    leak class refcounts alone can't flag) fails the drain loudly."""
    eng = GenerationEngine(model, num_slots=2, block_size=8)
    eng.add_request([1, 2, 3], max_new_tokens=2)
    eng.cache.allocate(1)                # leaked: never freed/seated
    with pytest.raises(RuntimeError, match="leak check failed"):
        eng.drain()


def test_engine_drain_refuses_parked_handoff(model):
    """A parked handoff holds blocks ON PURPOSE — drain must demand
    the fleet export-and-release it rather than declare a leak or
    silently recycle prompt KV."""
    eng = GenerationEngine(model, num_slots=2, block_size=8)
    rid = eng.add_request(np.arange(10) % VOCAB, max_new_tokens=1,
                          prefill_only=True)
    with pytest.raises(RuntimeError, match="handoff"):
        eng.drain()
    blocks, _ = eng.take_handoff(rid)
    eng.release_handoff(blocks)
    assert eng.cache.leak_check() == []


def test_reused_req_id_collides_with_parked_handoff(model):
    """A parked handoff still owns blocks under its req_id: reusing
    that id must be rejected, or the second finish would overwrite
    the parked entry and leak the first one's blocks forever."""
    eng = GenerationEngine(model, num_slots=2, block_size=8)
    rid = eng.add_request(np.arange(10) % VOCAB, max_new_tokens=1,
                          prefill_only=True)
    eng.run()                            # result drained, handoff parked
    with pytest.raises(ValueError, match="already"):
        eng.add_request(np.arange(10) % VOCAB, max_new_tokens=1,
                        prefill_only=True, req_id=rid)
    blocks, _ = eng.take_handoff(rid)
    eng.release_handoff(blocks)
    assert eng.cache.leak_check() == []


def test_adopt_request_validations(model):
    eng = GenerationEngine(model, num_slots=1, block_size=8)
    blocks = eng.cache.allocate(2)
    with pytest.raises(ValueError, match="exactly"):
        eng.adopt_request(np.arange(10), 3, blocks[:1], 4)
    # occupy the only lane, then adoption must refuse
    eng.add_request(np.arange(12) % VOCAB, max_new_tokens=8)
    eng.step()
    with pytest.raises(RuntimeError, match="free lane"):
        eng.adopt_request(np.arange(10) % VOCAB, 3, blocks, 4)
    eng.cache.free(blocks)
    eng.run()


# ---------------------------------------------------------------------------
# satellite: fleet metrics — replica-labeled exact merge
# ---------------------------------------------------------------------------

def test_label_snapshot_relabel_and_exact_merge():
    """Unit mechanics: stamped labels appear on every series, merge
    keeps replica series side-by-side and sums exactly, and a label
    collision raises instead of shadowing."""
    from paddle_tpu.observability.metrics import MetricsRegistry

    regs = [MetricsRegistry() for _ in range(2)]
    for i, reg in enumerate(regs):
        c = reg.counter("toks_total", "t", labelnames=("priority",))
        c.labels(priority="standard").inc(10 * (i + 1))
        h = reg.histogram("lat_seconds", "l", buckets=(0.1, 1.0))
        h.observe(0.05)
    merged = merge_snapshots(
        [label_snapshot(r.snapshot(), replica=str(i))
         for i, r in enumerate(regs)])
    fam = merged["toks_total"]
    assert fam["labelnames"] == ["priority", "replica"]
    vals = {s["labels"]["replica"]: s["value"] for s in fam["series"]}
    assert vals == {"0": 10.0, "1": 20.0}
    lat = merged["lat_seconds"]["series"]
    assert len(lat) == 2 and all(s["count"] == 1 for s in lat)
    with pytest.raises(ValueError, match="shadow"):
        label_snapshot(regs[0].snapshot(), priority="x")


def test_fleet_metrics_contract_two_replicas(model):
    """The engine-metrics contract survives the fold at N=2: merged
    token/admission counters equal the sums of the per-replica
    registries, every engine family carries the replica label, and
    the fleet's own router series ride alongside."""
    rng = np.random.RandomState(7)
    trace = _mixed_trace(rng, n=8)
    fleet, got = _serve_fleet(model, trace,
                              fleet_kw={"num_replicas": 2})
    snap = fleet.metrics_snapshot()
    per_replica = {
        str(r.rid): series_total(r.engine.metrics.snapshot(),
                                 "engine_tokens_generated_total")
        for r in fleet._replicas.values()}
    fam = snap["engine_tokens_generated_total"]
    assert "replica" in fam["labelnames"]
    merged = {s["labels"]["replica"]: s["value"]
              for s in fam["series"]}
    assert merged == per_replica
    total_new = sum(len(t) for t in got.values()) \
        - sum(len(p) for p, _, _ in trace)
    assert sum(merged.values()) == total_new
    assert series_total(snap, "engine_admissions_total") == len(trace)
    # TTFT observations: one per request, summed over (priority,
    # replica) series
    fam = snap["engine_ttft_seconds"]
    assert {"priority", "replica"} <= set(fam["labelnames"])
    assert sum(s["count"] for s in fam["series"]) == len(trace)
    # router-owned series are present and unlabeled-by-replica
    assert series_total(snap, "fleet_routed_total") == len(trace)


def test_fleet_admission_shed_at_max_queue(model):
    """Fleet-level admission control: past max_queue queued fleet-wide
    the incoming request is shed (result None) and counted."""
    rng = np.random.RandomState(8)
    fleet = ServingFleet(model, num_replicas=1, num_slots=2,
                         block_size=8, max_queue=2)
    ids = [fleet.add_request(rng.randint(0, VOCAB, 8),
                             max_new_tokens=2, priority="batch")
           for _ in range(8)]
    out = fleet.run()
    shed = [i for i in ids if out[i] is None]
    assert shed, "max_queue never shed"
    snap = fleet.metrics_snapshot()
    assert series_total(snap, "fleet_shed_total") == len(shed)
    assert all(out[i] is not None for i in ids if i not in shed)


# ---------------------------------------------------------------------------
# satellite: elastic join/leave under token auth
# ---------------------------------------------------------------------------

def test_fleet_elastic_join_drain_leave(model):
    from paddle_tpu.distributed.launch.elastic import ElasticMaster

    master = ElasticMaster(token="job-tok")
    try:
        with pytest.raises(RuntimeError, match="unauthorized"):
            ServingFleet(model, num_replicas=1, num_slots=2,
                         block_size=8,
                         elastic_endpoint=master.endpoint,
                         elastic_token="wrong")
        fleet = ServingFleet(model, num_replicas=2, num_slots=2,
                             block_size=8,
                             elastic_endpoint=master.endpoint,
                             elastic_token="job-tok")
        live = master.live()
        assert sorted(live) == ["fleet-replica-0", "fleet-replica-1"]
        assert live["fleet-replica-0"]["role"] == "mixed"
        assert live["fleet-replica-0"]["num_slots"] == 2
        # elastic scale-out rides the same path
        rid = fleet.add_replica()
        assert f"fleet-replica-{rid}" in master.live()
        # graceful leave: in-flight work finishes first, then the
        # membership drops
        rng = np.random.RandomState(9)
        ids = [fleet.add_request(rng.randint(0, VOCAB, 10),
                                 max_new_tokens=3) for _ in range(4)]
        fleet.remove_replica(rid)
        assert f"fleet-replica-{rid}" not in master.live()
        out = fleet.run()
        assert sorted(out) == sorted(ids)
        fleet.drain()
        assert master.live() == {}
        with pytest.raises(RuntimeError, match="draining"):
            fleet.add_request([1], max_new_tokens=1)
        with pytest.raises(RuntimeError, match="draining"):
            fleet.add_replica()
    finally:
        master.close()


def test_remove_last_replica_refused(model):
    fleet = ServingFleet(model, num_replicas=1, num_slots=2,
                         block_size=8)
    (rid,) = list(fleet._replicas)
    with pytest.raises(ValueError, match="last"):
        fleet.remove_replica(rid)


# ---------------------------------------------------------------------------
# CI plumbing: bench row registered + runner at test scale
# ---------------------------------------------------------------------------

def test_fleet_offered_load_bench_runner_tiny(model):
    import bench_ops

    assert "gpt_fleet_offered_load" in bench_ops.suite_names()
    rec = bench_ops._fleet_offered_load_case(
        model_cfg=model.config, num_tenants=2, per_tenant=4,
        uniques=2, prefix_len=16, suffix_max=6, max_new=6,
        num_slots=4, block_size=8, prefill_chunk=16)()
    assert rec["replicas"] == 2
    assert rec["tokens_per_s"] > 0 and rec["tokens_per_s_r1"] > 0
    assert rec["affinity_hit_tokens"] > 0
    assert rec["prefix_hit_tokens"] > 0


# ---------------------------------------------------------------------------
# multi-tenant adapters (ISSUE 13 satellite): adapter-salted routing
# ---------------------------------------------------------------------------

def _lora_registry(cfg, seed=3):
    from paddle_tpu.adapters import AdapterRegistry

    rng = np.random.RandomState(seed)
    reg = AdapterRegistry(cfg, max_rank=2)
    H, L = cfg.hidden_size, cfg.num_layers
    for aid in (1, 2):
        w = {"qkv": [(rng.randn(2, H).astype(np.float32) * 0.5,
                      rng.randn(3 * H, 2).astype(np.float32) * 0.5)
                     for _ in range(L)]}
        reg.register(aid, w, scaling=0.5)
    return reg


def test_adapter_salted_affinity_routes_tenants_independently(model):
    """ISSUE 13 satellite: `prefix_key`'s affinity chain carries the
    SAME adapter-id salt the caches hash with (router keys stay ==
    cache keys), so a hot base prompt under two adapters routes AND
    caches independently — each tenant's requests land on the replica
    owning ITS chain, and neither can claim the other's KV."""
    reg = _lora_registry(model.config)
    fleet = ServingFleet(model, num_replicas=2, num_slots=2,
                         block_size=8, prefill_chunk=8, adapters=reg)
    reps = list(fleet._replicas.values())
    p = (np.arange(16, dtype=np.int32) % VOCAB)
    # warm each tenant's chain on its own replica (driving the engines
    # directly pins placement)
    reps[0].engine.add_request(p, 2, adapter_id=1)
    reps[0].engine.run()
    reps[1].engine.add_request(p, 2, adapter_id=2)
    reps[1].engine.run()
    # router keys ARE cache keys, per tenant: the salted digests peek
    # exactly the chain that tenant's prefill registered
    assert reps[0].engine.cache.warm_prefix_tokens(
        p, keys=prefix_key(p, 8, 1)) == 16
    assert reps[0].engine.cache.warm_prefix_tokens(
        p, keys=prefix_key(p, 8, 2)) == 0
    rep, reason, warm = fleet._route(p, 1)
    assert (rep.rid, reason, warm) == (reps[0].rid, "affinity", 16)
    rep, reason, warm = fleet._route(p, 2)
    assert (rep.rid, reason, warm) == (reps[1].rid, "affinity", 16)
    # the base adapter owns neither chain: cold, least-loaded
    rep, reason, warm = fleet._route(p, 0)
    assert reason == "least_loaded" and warm == 0
    # end-to-end: each tenant's request lands on ITS warm replica and
    # actually hits (hit tokens grow there, never cross-tenant)
    h0 = reps[0].engine.prefix_hit_tokens
    h1 = reps[1].engine.prefix_hit_tokens
    r1 = fleet.add_request(p, 3, adapter_id=1)
    r2 = fleet.add_request(p, 3, adapter_id=2)
    out = fleet.run()
    assert reps[0].engine.prefix_hit_tokens == h0 + 16
    assert reps[1].engine.prefix_hit_tokens == h1 + 16
    assert out[r1] != out[r2]
    snap = fleet.metrics_snapshot()
    routed = {(s["labels"]["replica"], s["labels"]["reason"]):
              s["value"] for s in snap["fleet_routed_total"]["series"]}
    assert routed[(str(reps[0].rid), "affinity")] == 1
    assert routed[(str(reps[1].rid), "affinity")] == 1


def test_unknown_adapter_rejected_before_router_state(model):
    """Regression: an unregistered adapter_id must reject CLEANLY at
    fleet intake — before the routing record exists — or the phantom
    in-flight request deadlocks every later run() and strands all
    other results."""
    reg = _lora_registry(model.config)
    fleet = ServingFleet(model, num_replicas=2, num_slots=2,
                         block_size=8, prefill_chunk=8, adapters=reg)
    p = (np.arange(9, dtype=np.int32) % VOCAB)
    good = fleet.add_request(p, 2, adapter_id=1)
    with pytest.raises(ValueError, match="not registered"):
        fleet.add_request(p, 2, adapter_id=99)
    # no adapter subsystem at all: nonzero ids reject the same way
    bare = ServingFleet(model, num_replicas=1, num_slots=2,
                        block_size=8, prefill_chunk=8)
    with pytest.raises(ValueError, match="adapters="):
        bare.add_request(p, 2, adapter_id=1)
    assert fleet.num_outstanding == 1          # no phantom request
    out = fleet.run()                          # and the fleet still runs
    assert list(out) == [good]
    snap = fleet.metrics_snapshot()
    assert series_total(snap, "fleet_routed_total") == 1
