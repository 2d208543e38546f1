"""Tensor-parallel sharded serving (ISSUE 8).

ONE logical GenerationEngine scheduler driving shard_map-compiled
steps over an mp-axis device mesh (virtual CPU devices in CI — the
conftest forces --xla_force_host_platform_device_count=8, so the REAL
mp=2/mp=4 programs compile and run here). The contract, proven the
way PR 3/6/7 proved theirs:

- token-EXACT parity vs the mp=1 engine across
  {dense, pallas} x {cold, warm prefix cache}
  x K in {0, 4}, with mid-run admissions and cache evictions in the
  trace — exactness by construction (column-parallel sharding: every
  dot stays full length, activations reassembled by exact gathers),
  not by tolerance;
- `decode_traces == 1` per (backend, K, mesh shape) and steady-state
  `expect_traces(0)`; donation of the sharded pools wires up;
- the serving-mesh helper fails loudly on indivisible shapes;
- mesh/shard observability: `engine_mesh_info`, shard-labeled pool
  gauges, and exact per-shard folding through merge_snapshots.
"""
import copy

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.jit as jit
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference import GenerationEngine
from paddle_tpu.observability.metrics import merge_snapshots, \
    series_total

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


def _reference(model, prompt, max_new):
    out = model.generate(
        Tensor._wrap(np.asarray(prompt, np.int32)[None]),
        max_length=len(prompt) + max_new, use_cache=True)
    return list(map(int, np.asarray(out._array)[0]))


def _mixed_trace(rng, n=4):
    """Mixed lengths + a hot shared prefix + a block-aligned
    full-prefix hit (block_size 4)."""
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
            eng.step()                 # admissions land mid-decode
    ids += [eng.add_request(p, n) for p, n in reqs[len(reqs) // 2:]]
    out = eng.run()
    return [list(map(int, out[rid])) for rid in ids]


# ---------------------------------------------------------------------------
# tentpole: token-exact parity across the whole serving matrix
# ---------------------------------------------------------------------------

def _assert_parity_matrix(model, backend, K):
    """One mixed trace (shared prefixes, a full-prefix hit, mid-run
    admissions) served at mp=1, mp=2 and mp=4 in (a) prefix
    cache cold, (b) same engine warm —
    all token-identical across mesh shapes, with ONE decode trace per
    (backend, K, mesh shape)."""
    rng = np.random.RandomState(11)
    reqs = _mixed_trace(rng)

    def serve(mp):
        eng = GenerationEngine(model, num_slots=3, block_size=4,
                               num_blocks=64, spec_decode_k=K,
                               attention_backend=backend,
                               mp_degree=mp, prefill_chunk=8)
        cold = _run_trace(eng, reqs)
        warm = _run_trace(eng, reqs, midrun=False)   # hot cache
        assert eng.prefix_hit_tokens > 0
        assert eng.decode_traces == 1, \
            f"mp={mp} {backend} K={K}: decode retraced"
        return cold, warm

    ref = serve(None)
    for mp in (2, 4):
        assert serve(mp) == ref, \
            f"mp={mp} {backend} K={K}: output diverged from mp=1"
    # anchor the mp=1 reference itself against the compiled-decode
    # oracle (the cheaper spec/prefix suites prove this exhaustively)
    p, n = reqs[0]
    assert ref[0][0] == _reference(model, p, n)


@pytest.mark.parametrize("backend,K", [("dense", 0), ("pallas", 4)])
def test_sharded_token_identical_across_modes(model, backend, K):
    """THE acceptance gate, tier-1 cut: both backends and both K
    values across mp in {1, 2, 4} x {cache cold, warm}.
    The two complementary (backend, K) cells run in the slow-marked
    full-matrix test below — together the 2x2 product is covered."""
    _assert_parity_matrix(model, backend, K)


@pytest.mark.slow
@pytest.mark.parametrize("backend,K", [("dense", 4), ("pallas", 0)])
def test_sharded_token_identical_full_matrix(model, backend, K):
    """The remaining (backend, K) cells of the acceptance matrix —
    identical machinery, kept out of the timed tier-1 window."""
    _assert_parity_matrix(model, backend, K)


def test_sharded_eviction_under_pressure_stays_exact(model):
    """A pool tight enough to evict cached prefix blocks mid-trace
    (the PR-6 pressure path) behaves identically on the sharded
    engine: same outputs, same host-side allocator story, stalls
    surfaced on the shard-labeled counter."""
    rng = np.random.RandomState(7)
    reqs = _mixed_trace(rng, n=3)

    def serve(mp):
        eng = GenerationEngine(model, num_slots=2, block_size=4,
                               num_blocks=10, prefill_chunk=8,
                               mp_degree=mp)
        outs = _run_trace(eng, reqs) + _run_trace(eng, reqs,
                                                  midrun=False)
        assert eng.cache.num_free == eng.cache.num_blocks - 1
        return outs, eng

    ref, _ = serve(None)
    got, eng2 = serve(2)
    assert got == ref
    snap = eng2.metrics_snapshot()
    for s in snap["engine_block_stalls_total"]["series"]:
        assert s["labels"]["shard"] == "0"


# ---------------------------------------------------------------------------
# trace stability + donation on the sharded step
# ---------------------------------------------------------------------------

def test_sharded_steady_state_and_donated_pools(model):
    """A warmed mp=2 engine retraces NOTHING on further churn, and the
    donated sharded pools compile and run (donation demands matching
    input/output shardings — this is the aliasing contract check the
    virtual mesh can express)."""
    rng = np.random.RandomState(3)
    reqs = [(rng.randint(0, VOCAB, 6).astype(np.int32), 4)
            for _ in range(3)]
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=64, prefill_chunk=8,
                           mp_degree=2, donate=True)
    assert eng._donate_argnums == (1, 2)     # pools stay donated
    ids = [eng.add_request(p, n) for p, n in reqs]
    out = eng.run()
    for (p, n), rid in zip(reqs, ids):
        assert list(map(int, out[rid])) == _reference(model, p, n)
    with jit.expect_traces(eng._decode_pure, 0), \
            jit.expect_traces(eng._prefill_pure, 0):
        eng.add_request(rng.randint(0, VOCAB, 9).astype(np.int32), 5)
        eng.run()


def test_refresh_weights_resnapshots_the_sharded_state():
    """The tensor-parallel engine serves a weight-stationary SNAPSHOT
    (placed on the mesh once); refresh_weights() re-shards after a
    live weight update — without it the mp engine intentionally keeps
    serving the placed weights."""
    m = _model(seed=3)
    prompt = np.arange(5, dtype=np.int32)
    eng = GenerationEngine(m, num_slots=1, block_size=4,
                           prefill_chunk=8, mp_degree=2)
    rid = eng.add_request(prompt, 4)
    before = list(map(int, eng.run()[rid]))
    assert before == _reference(m, prompt, 4)
    # perturb the embedding enough to change the greedy stream
    w = m.gpt.wte.weight
    w._array = -w._array
    want = _reference(m, prompt, 4)
    eng.refresh_weights()
    rid = eng.add_request(prompt, 4)
    assert list(map(int, eng.run()[rid])) == want


# ---------------------------------------------------------------------------
# satellite: serving-mesh construction + validation
# ---------------------------------------------------------------------------

def test_serving_mesh_and_divisibility_validation(model):
    import jax

    from paddle_tpu.distributed import serving_mesh
    from paddle_tpu.distributed.topology import HybridCommunicateGroup
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    mesh = serving_mesh(2)
    assert mesh.axis_names == ("mp",) and mesh.size == 2
    # the convenience topology builds without a dp/pp/sharding launch
    hcg = HybridCommunicateGroup.for_serving(2)
    assert hcg.get_model_parallel_world_size() == 2
    # clear errors UP FRONT, not deep inside a reshape
    with pytest.raises(ValueError, match="num_heads"):
        serving_mesh(3, num_heads=4)
    with pytest.raises(ValueError, match="vocab"):
        serving_mesh(4, num_heads=4, vocab_size=62)
    with pytest.raises(ValueError, match="devices"):
        serving_mesh(2 * len(jax.devices()))
    # an explicitly passed mesh is validated too
    paddle.seed(1)
    cfg = GPTConfig.tiny(vocab=63, hidden=32, heads=2, layers=1,
                         seq=32)
    odd = GPTForCausalLM(cfg)
    odd.eval()
    with pytest.raises(ValueError, match="vocab"):
        GenerationEngine(odd, mesh=serving_mesh(2))
    cfg2 = GPTConfig.tiny(vocab=VOCAB, hidden=32, heads=4, layers=1,
                          seq=32)
    cfg2.intermediate_size = 50
    mlp_odd = GPTForCausalLM(cfg2)
    mlp_odd.eval()
    with pytest.raises(ValueError, match="intermediate_size"):
        GenerationEngine(mlp_odd, mp_degree=4)
    # a mesh without an mp axis is rejected
    from jax.sharding import Mesh

    with pytest.raises(ValueError, match="'mp' axis"):
        GenerationEngine(model, mesh=Mesh(
            np.asarray(jax.devices()[:2]), ("dp",)))
    # a mesh and an mp_degree that disagree fail loudly; so does a
    # degree below one
    with pytest.raises(ValueError, match="mp_degree=2"):
        GenerationEngine(model, mesh=serving_mesh(4), mp_degree=2)
    with pytest.raises(ValueError, match="must be >= 1"):
        GenerationEngine(model, mp_degree=0)


def test_pool_spec_is_the_single_source_of_truth(model):
    """ISSUE 8 satellite (latent-bug fix): both pool constructors
    derive `[L, B, bs, H, D]`/dtype from pool_spec(), so the sharded
    and unsharded layouts cannot drift."""
    from paddle_tpu.distributed import serving_mesh
    from paddle_tpu.inference import PagedKVCache

    import jax.numpy as jnp

    plain = PagedKVCache(2, 8, 4, 4, 8, dtype=jnp.float32)
    shard = PagedKVCache(2, 8, 4, 4, 8, dtype=jnp.float32,
                         mesh=serving_mesh(2))
    assert plain.pool_spec() == shard.pool_spec()
    for c in (plain, shard):
        shape, dt = c.pool_spec()
        assert tuple(c.kpool.shape) == shape == (2, 8, 4, 4, 8)
        assert c.vpool.dtype == dt
    assert str(plain.pool_pspec()) == "PartitionSpec()"
    assert shard.pool_pspec()[3] == "mp"
    with pytest.raises(ValueError, match="num_heads"):
        PagedKVCache(2, 8, 4, 3, 8, mesh=serving_mesh(2))


# ---------------------------------------------------------------------------
# satellite: mesh/shard observability (the engine-metrics test at mp=2)
# ---------------------------------------------------------------------------

def test_engine_metrics_on_the_mp2_virtual_mesh(model):
    """The PR-2 engine-metrics contract re-proven on the sharded
    engine, plus the mesh-info gauge and shard-labeled pool series;
    merge_snapshots folds two shards' snapshots EXACTLY (side-by-side
    series, summed counters)."""
    rng = np.random.RandomState(5)
    reqs = [(rng.randint(0, VOCAB, rng.randint(2, 9)).astype(np.int32),
             int(rng.randint(2, 6))) for _ in range(4)]
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=32, prefill_chunk=8,
                           mp_degree=2)
    for p, n in reqs:
        eng.add_request(p, n)
    eng.run()
    snap = eng.metrics_snapshot()
    # core serving contract holds under the mesh
    new_tokens = sum(n for _, n in reqs)
    assert series_total(snap, "engine_admissions_total") == len(reqs)
    assert series_total(snap, "engine_tokens_generated_total") \
        == new_tokens
    ttft = snap["engine_ttft_seconds"]["series"][0]
    assert ttft["count"] == len(reqs) and ttft["sum"] > 0
    assert series_total(snap, "engine_decode_recompiles_total") == 0
    assert snap["engine_decode_traces"]["series"][0]["value"] == 1
    # mesh info: one series naming the degree and device count
    mesh_info = snap["engine_mesh_info"]["series"]
    assert [s["labels"] for s in mesh_info] \
        == [{"mp_degree": "2", "devices": "2"}]
    assert mesh_info[0]["value"] == 1
    # pool gauges are shard-labeled
    used = snap["engine_pool_used_blocks"]["series"]
    assert [s["labels"] for s in used] == [{"shard": "0"}]
    assert snap["engine_pool_used_high_water_blocks"]["series"][0][
        "labels"] == {"shard": "0"}
    # two shards' snapshots fold EXACTLY: distinct shard labels stay
    # side-by-side (no cross-shard min/max/mean blur), counters sum
    other = copy.deepcopy(snap)
    for fam in other.values():
        for s in fam.get("series", []):
            if "shard" in s.get("labels", {}):
                s["labels"]["shard"] = "1"
    merged = merge_snapshots([snap, other])
    used = {s["labels"]["shard"]: s for s in
            merged["engine_pool_used_blocks"]["series"]}
    assert set(used) == {"0", "1"}
    hw = {s["labels"]["shard"]: s for s in
          merged["engine_pool_used_high_water_blocks"]["series"]}
    assert hw["0"]["min"] == hw["0"]["max"] \
        == snap["engine_pool_used_high_water_blocks"]["series"][0][
            "value"]
    assert series_total(merged, "engine_tokens_generated_total") \
        == 2 * new_tokens
    # prometheus exposition renders the new labels
    text = eng.metrics.render_prometheus()
    assert 'engine_mesh_info{mp_degree="2",devices="2"} 1' in text
    assert 'engine_pool_used_blocks{shard="0"}' in text


# ---------------------------------------------------------------------------
# satellite: bench row (CI-scale runner + suite registration)
# ---------------------------------------------------------------------------

def test_offered_load_mp2_bench_row():
    """The gpt_engine_offered_load_mp2 SUITE_ROWS runner at test
    scale: serves the same trace at mp=1 then mp=2, asserts the
    outputs identical inside the runner, and records both tokens/s."""
    import bench_ops
    from paddle_tpu.models import GPTConfig

    cfg = GPTConfig.tiny(vocab=32, hidden=16, layers=1, heads=2,
                         seq=32)
    paddle.seed(0)
    rec = bench_ops._engine_offered_load_case(
        model_cfg=cfg, requests=[(3, 4), (6, 4), (10, 3)],
        num_slots=2, block_size=4, mp_degree=2)()
    assert rec["mp_degree"] == 2 and rec["devices"] == 2
    assert rec["tokens_per_s"] > 0 and rec["tokens_per_s_mp1"] > 0
    assert rec["requests"] == 3
    assert rec["decode_recompiles"] == 0
    assert "gpt_engine_offered_load_mp2" in bench_ops.suite_names()
