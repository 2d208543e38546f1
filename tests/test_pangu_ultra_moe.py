"""The `pangu_ultra_moe` decoder (latent attention over ONE paged pool of
compressed rows, sandwich norms, dense then sigmoid-routed SwiGLU experts)
at a tiny size on the CPU, float32: the whole model against the
benchmark's plain reference on seeded weights; absorbed against expanded
attention; chunked prefill then decode through `GenerationEngine`'s latent
pool against the reference's one pass; the Pallas walk (interpreter)
against the XLA walk; a request served through prefix hits against the
same request served cold; the pool's size; idle lanes and padding routed
nowhere; the counters; the share test; the step that walks a prefill
chunk's rows and the decode rows together (`decode_with_chunk`) against
the chunk program then the decode step, and its order against the two
programs', prefix hits included.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights
from benchmarks.reference import common, pangu_ultra_moe as ref, stepwise
from paddle_tpu.inference.engine import GenerationEngine, PagedKVCache
from paddle_tpu.inference.serving_spec import PagedLatent
from paddle_tpu.models.pangu_ultra_moe import (PanguUltraMoEConfig,
                                               PanguUltraMoEForCausalLM)
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops.pallas import paged_attention as pk
from paddle_tpu.ops.pallas.paged_attention import (
    latent_pages_per_step, latent_prefill_pages_per_step, mla_paged_decode)

SEED = 7
REF_KEYS = (
    "hidden_size", "num_hidden_layers", "first_k_dense_replace",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "intermediate_size", "moe_intermediate_size", "n_routed_experts",
    "router_experts", "expert_offset", "num_experts_per_tok",
    "routed_scaling_factor", "rope_theta", "rms_norm_eps", "vocab_size",
    "initializer_range", "attn_query_init_std", "attn_key_init_std",
    "router_init_std")


def ref_cfg(cfg):
    return {k: getattr(cfg, k) for k in REF_KEYS}


def seeded(seed=SEED, **kw):
    """The program's model with the reference's seeded weights bound: 8
    experts held (4-11) of a 16-wide router."""
    kw = dict(dict(router_experts=16, n_routed_experts=8, expert_offset=4),
              **kw)
    cfg = PanguUltraMoEConfig.tiny(**kw)
    model = PanguUltraMoEForCausalLM(cfg)
    model.eval()
    arrays = weights.make_all(seed, ref.param_spec(ref_cfg(cfg)),
                              jnp.float32)
    named = dict(model.named_parameters())
    assert set(named) == set(arrays)
    for name, p in named.items():
        assert tuple(p.shape) == tuple(arrays[name].shape), name
        p._in_place_update(arrays[name])
    return model, cfg


def reference_logits(cfg, ids, seed=SEED, fault=None):
    return np.asarray(stepwise.logits_of(
        ref.build(ref_cfg(cfg), common.MM["f32"], fault=fault), seed,
        np.asarray(ids, np.int32), jnp.float32))


def prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
            for n in lengths]


def engine_for(model, **kw):
    kw = dict(dict(num_slots=3, block_size=8, prefill_chunk=16,
                   max_model_len=96), **kw)
    return GenerationEngine(model, **kw)


# -- the whole model against the reference -------------------------------------

@pytest.mark.parametrize("layers,dense", [(1, 1), (1, 0), (3, 1)],
                         ids=["dense_layer", "expert_layer", "three"])
@pytest.mark.parametrize("absorbed", [False, True],
                         ids=["expanded", "absorbed"])
def test_forward_matches_the_reference(layers, dense, absorbed):
    """Both forms of the attention: the same numbers as the reference's
    expanded pass (so absorbed against expanded too)."""
    model, cfg = seeded(layers=layers, dense=dense)
    ids = np.stack(prompts(cfg, [29, 29]))
    got = np.asarray(model.forward(ids, absorbed=absorbed)._array)
    want = reference_logits(cfg, ids)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_the_positions_and_the_routing_matter_to_the_logits():
    """What the seeded scales are for: with the rotation left off the
    keys, or a page of keys hidden, the reference's own logits move far
    more than any tolerance used here."""
    _, cfg = seeded()
    ids = np.stack(prompts(cfg, [40]))
    want = reference_logits(cfg, ids)
    for fault in ("no_key_rotation", ("skip_keys", 8, 16),
                  ("late_keys", 24, 8)):
        off = reference_logits(cfg, ids, fault=fault)
        assert np.abs(off - want)[0, 30:].max() > 0.05, fault


# -- through the engine ---------------------------------------------------------

@pytest.mark.parametrize("backend", ["dense", "pallas"])
@pytest.mark.parametrize("chunk,lengths", [
    (32, [21]),            # a prompt of one chunk, ending mid-block
    (8, [37, 16]),         # of several (absorbed form), and whole blocks
    (64, [40, 33, 5]),     # expanded form, more requests than one chunk
], ids=["one_chunk", "several_chunks", "expanded_chunk"])
def test_engine_chunked_prefill_then_decode_agrees_with_the_reference(
        backend, chunk, lengths):
    model, cfg = seeded()
    eng = engine_for(model, prefill_chunk=chunk, attention_backend=backend)
    pa.reset_latent_path_stats()
    ps = prompts(cfg, lengths, seed=3)
    rids = [eng.add_request(p, max_new_tokens=7) for p in ps]
    out = eng.run()
    assert eng.decode_traces == 1 and eng.prefill_traces == 1
    assert pa.LATENT_PATH_STATS[backend] == cfg.num_hidden_layers
    # a chunk wide enough to expand attends in the kernel under `pallas`
    form = "absorbed" if chunk < 32 else \
        "pallas_expanded" if backend == "pallas" else "expanded"
    assert pa.LATENT_CHUNK_STATS == dict(
        {"expanded": 0, "absorbed": 0, "pallas_expanded": 0},
        **{form: cfg.num_hidden_layers})
    for rid, p in zip(rids, ps):
        seq = np.asarray(out[rid], np.int32)
        logits = reference_logits(cfg, seq[None, :-1])[0, len(p) - 1:]
        served = seq[len(p):]
        gap = logits.max(-1) - logits[np.arange(len(served)), served]
        assert gap.max() < 1e-3


@pytest.mark.parametrize("chunk,form", [(16, "absorbed"),
                                        (32, "pallas_expanded")])
def test_the_pallas_walk_serves_the_xla_walks_tokens(chunk, form):
    """A `dense` engine runs XLA in both programs, a `pallas` engine the
    decode kernel and, from a chunk wide enough to expand, the prefill
    kernel: the same tokens through chunked prefill and decode."""
    model, cfg = seeded()
    ps = prompts(cfg, [30, 9, 44, 17, 70], seed=5)
    streams = []
    for backend in ("dense", "pallas"):
        pa.reset_latent_path_stats()
        eng = engine_for(model, attention_backend=backend,
                         prefill_chunk=chunk)
        rids = [eng.add_request(p, max_new_tokens=9) for p in ps]
        out = eng.run()
        streams.append([out[r] for r in rids])
        assert pa.LATENT_PATH_STATS[backend] == cfg.num_hidden_layers
        assert pa.LATENT_CHUNK_STATS["pallas_expanded"] == (
            cfg.num_hidden_layers
            if (backend, form) == ("pallas", "pallas_expanded") else 0)
    assert streams[0] == streams[1]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("start,plen,shared", [
    (0, 32, 0),        # a prompt's first chunk, full
    (0, 21, 0),        # ... that ends short of the chunk
    (64, 96, 0),       # from a page's edge
    (160, 185, 0),     # a later chunk, two steps of keys below it
    (40, 72, 5),       # after a prefix hit: the leading blocks another's
    (283, 300, 3),     # three steps, the last one partly masked
], ids=["first", "short", "page_edge", "later", "shared_prefix", "steps"])
def test_the_prefill_kernel_matches_the_xla_loop_and_the_pool(
        monkeypatch, dtype, start, plen, shared):
    """`paged_latent_prefill_chunk` under `pallas` (the fused kernel,
    interpreted) against `dense` (the XLA loop): the valid rows' outputs
    and the whole pool, with steps of 128 keys so that a context of a few
    hundred rows takes several."""
    monkeypatch.setattr(pk, "_LATENT_PREFILL_PAIRS", 32 * 128)
    rng = np.random.default_rng(1)
    C, heads, dn, dr, dv, rank, width, bs, blocks, layers, maxb = \
        32, 4, 16, 8, 16, 32, 40, 8, 60, 2, 40
    assert latent_prefill_pages_per_step(C, bs, width, dtype) == 16
    pool = jnp.asarray(rng.normal(size=(layers, blocks, bs, width)), dtype)
    qn = jnp.asarray(rng.normal(size=(C, heads, dn)), dtype)
    qr = jnp.asarray(rng.normal(size=(C, heads, dr)), dtype)
    new = jnp.asarray(rng.normal(size=(C, width)), dtype)
    w = jnp.asarray(rng.normal(size=(rank, heads, dn + dv)) * 0.2, dtype)
    held = -(-plen // bs)
    row = np.zeros(maxb, np.int32)
    # the leading `shared` blocks are low-numbered ones another slot
    # filled (a prefix hit), the rest this slot's own, in no order
    row[:shared] = np.arange(1, 1 + shared)
    row[shared:held] = rng.permutation(
        np.arange(1 + shared, blocks))[:held - shared]
    args = (jnp.asarray(row), jnp.int32(start), jnp.int32(plen), 0.2)
    pa.reset_latent_path_stats()
    want, pool_x = pa.paged_latent_prefill_chunk(
        qn, qr, new, w, pool, 1, *args, backend="dense")
    got, pool_k = pa.paged_latent_prefill_chunk(
        qn, qr, new, w, pool, 1, *args, backend="pallas")
    assert pa.LATENT_CHUNK_STATS == {"expanded": 1, "absorbed": 0,
                                     "pallas_expanded": 1}
    assert bool(jnp.all(pool_x == pool_k))
    valid = plen - start
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()              # padding rows too
    np.testing.assert_allclose(
        got[:valid], want[:valid],
        atol=1e-5 if dtype == jnp.float32 else 2e-2)
    # no row's output depends on what lies past its own position
    dirty = pool.at[1, row[held - 1], (plen - 1) % bs + 1:].set(1e4)
    again, _ = pa.paged_latent_prefill_chunk(
        qn, qr, new, w, dirty, 1, *args, backend="pallas")
    np.testing.assert_allclose(np.asarray(again, np.float32)[:valid],
                               got[:valid], atol=1e-6)


@pytest.mark.parametrize("chunk,block,width,want", [
    (256, 64, 640, 16), (512, 64, 640, 8), (32, 16, 256, 64),
    (4096, 64, 640, 2)])
def test_the_prefill_kernels_pages_a_step_follow_from_shapes(
        chunk, block, width, want):
    """1,024 keys a step at the cell's chunk of 256; a wider chunk takes
    fewer, so that a head's scores stay 1 MB."""
    assert latent_prefill_pages_per_step(chunk, block, width,
                                         jnp.bfloat16) == want


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernel_matches_the_xla_walk_and_writes_the_same_pool(dtype):
    rng = np.random.default_rng(0)
    slots, heads, width, value, bs, blocks, layers, maxb = \
        5, 8, 48, 32, 16, 40, 2, 6
    pool = jnp.asarray(rng.normal(size=(layers, blocks, bs, width)), dtype)
    q = jnp.asarray(rng.normal(size=(slots, heads, width)), dtype)
    new = jnp.asarray(rng.normal(size=(slots, width)), dtype)
    pos = np.array([0, 5, 16, 33, 95], np.int32)   # lane 0 idle
    bt = np.zeros((slots, maxb), np.int32)
    nxt = 1
    for s in range(1, slots):
        n = pos[s] // bs + 1
        bt[s, :n] = np.arange(nxt, nxt + n)
        nxt += n
    bt, pos = jnp.asarray(bt), jnp.asarray(pos)
    want, pool_x = pa._latent_dense_step(q, new, pool, 1, bt, pos, value,
                                         0.2)
    got, pool_k = mla_paged_decode(q, new, pool, 1, bt, pos, value, 0.2,
                                   interpret=True)
    assert bool(jnp.all(pool_x == pool_k))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=1e-5 if dtype == jnp.float32 else 1e-2)
    # a lane's output does not depend on rows past its position
    dirty = pool.at[1, bt[3, 2], 2:].set(1e4)
    again, _ = mla_paged_decode(q, new, dirty, 1, bt, pos, value, 0.2,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(again[3], np.float32),
                               np.asarray(got[3], np.float32), atol=1e-6)


@pytest.mark.parametrize("block,width,want", [
    (64, 640, 8), (64, 576, 8), (16, 48, 32), (256, 640, 2)])
def test_pages_a_step_follow_from_shapes(block, width, want):
    assert latent_pages_per_step(block, width, jnp.bfloat16) == want


@pytest.mark.parametrize("rows,form", [(512, "expanded"), (256, "expanded"),
                                       (171, "expanded"), (170, "absorbed"),
                                       (64, "absorbed")])
def test_a_chunks_form_follows_from_its_width(rows, form):
    """At the published head sizes expanding a cached row pays from 171
    rows on."""
    assert pa.latent_chunk_form(rows, 128, 64, 128, 512) == form


# -- the prefix cache over the latent pool ---------------------------------------

@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_a_request_served_through_prefix_hits_is_the_request_served_cold(
        backend):
    model, cfg = seeded()
    doc = prompts(cfg, [43], seed=9)[0]
    questions = prompts(cfg, [6, 11, 3], seed=10)

    def serve(engine):
        outs = []
        for q in questions:
            rid = engine.add_request(np.concatenate([doc, q]),
                                     max_new_tokens=8)
            outs.append(engine.run()[rid])
        return outs

    cold = [serve(engine_for(model, attention_backend=backend,
                             enable_prefix_cache=False))]
    warm_engine = engine_for(model, attention_backend=backend)
    assert warm_engine.enable_prefix_cache
    warm = serve(warm_engine)
    assert warm == cold[0]
    # the document's 5 full blocks of 8 hit on the second and third turn
    assert warm_engine.prefix_hit_tokens == 2 * 40
    assert warm_engine.cache.num_cached_blocks >= 5
    # and every served token is the reference's
    seq = np.asarray(warm[2], np.int32)
    plen = len(doc) + len(questions[2])
    logits = reference_logits(cfg, seq[None, :-1])[0, plen - 1:]
    gap = logits.max(-1) - logits[np.arange(8), seq[plen:]]
    assert gap.max() < 1e-3


def test_a_shared_block_is_copied_before_a_hit_lane_writes_into_it():
    """A prompt that IS a cached prefix (whole blocks): the first decode
    feeds its last token into a shared block, which copy-on-write makes
    private through the one-pool copy program."""
    model, cfg = seeded()
    eng = engine_for(model)
    p = prompts(cfg, [32], seed=2)[0]
    first = eng.add_request(p, max_new_tokens=5)
    a = eng.run()[first]
    second = eng.add_request(p, max_new_tokens=5)
    b = eng.run()[second]
    assert a == b and eng.prefix_hit_tokens == 32
    assert eng._cow_pure.traces == 1
    assert eng.cache.vpool is None


# -- the pool ------------------------------------------------------------------

def test_the_pool_is_one_array_of_rows_and_nothing_beside_it():
    model, cfg = seeded()
    eng = engine_for(model, num_blocks=20)
    spec = eng.spec.paged_kv
    assert spec == PagedLatent(cfg.num_hidden_layers, 40, 32, 4)
    shape, dtype = eng.cache.pool_spec()
    assert shape == (3, 20, 8, 40) and eng.cache.vpool is None
    assert eng.cache.pool_nbytes() == 20 * 8 * 40 * 4 * 3 \
        == int(eng.cache.kpool.nbytes)
    assert "engine_pool_bytes" in eng.metrics.render_prometheus()


def test_the_published_rows_are_576_values_in_640_lanes():
    cfg = PanguUltraMoEConfig()
    assert cfg.row_values == 576 and cfg.pool_row_width == 640
    assert PanguUltraMoEConfig.tiny().pool_row_width == 40
    cache = PagedKVCache(5, 4, 64, None, cfg.pool_row_width,
                         dtype=jnp.bfloat16)
    assert cache.pool_spec()[0] == (5, 4, 64, 640)
    assert cache.pool_nbytes() == 4 * 64 * 640 * 2 * 5


def test_a_latent_pool_takes_neither_a_mesh_nor_int8():
    with pytest.raises(ValueError, match="no head axis"):
        PagedKVCache(1, 4, 8, None, 40, kv_dtype="int8")


@pytest.mark.parametrize("kwargs,feature", [
    (dict(kv_dtype="int8"), "kv_int8"),
    (dict(weight_dtype="int8"), "weight_int8"),
    (dict(spec_decode_k=2), "spec_decode"),
    (dict(mp_degree=2), "mp_degree"),
])
def test_what_the_spec_refuses_is_refused_at_construction(kwargs, feature):
    model, _ = seeded(layers=1)
    with pytest.raises(ValueError, match=feature.split("_")[0]):
        engine_for(model, **kwargs)


# -- routed nowhere, and the counters ---------------------------------------------

def test_idle_lanes_and_padding_are_routed_nowhere():
    model, cfg = seeded(layers=1, dense=0)
    mlp = model.layers[0].mlp
    u = jax.random.normal(jax.random.PRNGKey(1), (6, cfg.hidden_size))
    live = jnp.array([True, False, True, False, False, True])
    out, counters = mlp.forward_rows(u, live)
    alone, c3 = mlp.forward_rows(u[jnp.array([0, 2, 5])],
                                 jnp.ones(3, bool))
    np.testing.assert_allclose(np.asarray(out)[[0, 2, 5]],
                               np.asarray(alone), atol=1e-5)
    assert int(counters[0]) == int(c3[0]) > 0
    # a dead row gets the shared expert's part alone
    from paddle_tpu.models.pangu_ultra_moe import _gated_mlp

    shared = _gated_mlp(u, mlp.shared.gate_up._array,
                        mlp.shared.down._array)
    np.testing.assert_allclose(np.asarray(out)[1], np.asarray(shared)[1],
                               atol=1e-6)


def test_engine_counts_the_experts_load_and_the_rows_walked():
    model, cfg = seeded()
    eng = engine_for(model, async_core=False)
    ps = prompts(cfg, [10, 20], seed=4)
    for p in ps:
        eng.add_request(p, max_new_tokens=4)
    eng.run()
    t = eng.step_counter_totals
    assert set(t) == {"decode_live_lanes", "moe_assignments_held",
                      "moe_experts_touched", "moe_max_expert_load",
                      "moe_row_tiles", "mla_context_rows",
                      "decode_steps_with_chunk"}
    assert t["decode_steps_with_chunk"] == 0
    # three decode steps a request (the first token is the chunk's); a
    # step at position p walks p + 1 rows
    assert t["decode_live_lanes"] == 2 * 3
    assert t["mla_context_rows"] == sum(
        len(p) + k + 1 for p in ps for k in range(3))
    # 2 expert layers, 3 of 16 experts a token, 8 held: at most 3 a lane
    assert 0 < t["moe_assignments_held"] <= 6 * 2 * 3
    assert 0 < t["moe_experts_touched"] <= t["moe_assignments_held"]
    assert 1 <= t["moe_max_expert_load"] <= 2
    # a load of at most 2 fills one 16-row tile
    assert t["moe_row_tiles"] == t["moe_experts_touched"]
    text = eng.metrics.render_prometheus()
    assert "mla_context_rows" in text
    assert "engine_moe_row_tiles_total" in text


def test_the_row_tiles_are_each_experts_load_in_whole_tiles(expert_loads):
    """`moe_row_tiles` over a run: the sum over the expert layers and the
    decode steps of ceil(load / tile rows); a chunk's products are not
    counted."""
    model, cfg = seeded()
    eng = engine_for(model, async_core=False)
    for p in prompts(cfg, [10, 20], seed=4):
        eng.add_request(p, max_new_tokens=8)
    eng.run()
    jax.effects_barrier()
    t = eng.step_counter_totals
    slots = eng.num_slots
    stepped = [s for rows, s in expert_loads if rows == slots]
    assert len(stepped) == 2 * eng.decode_steps
    assert t["moe_row_tiles"] == sum(int((-(-s // 4)).sum())
                                     for s in stepped)
    assert t["moe_experts_touched"] == sum(int((s > 0).sum())
                                           for s in stepped)
    assert t["moe_row_tiles"] >= t["moe_experts_touched"]


def test_ahead_and_serial_orders_serve_the_same_tokens():
    model, cfg = seeded()
    ps = prompts(cfg, [12, 31, 7, 22, 18], seed=6)
    streams = []
    for async_core in (None, False):
        eng = engine_for(model, async_core=async_core)
        rids = [eng.add_request(p, max_new_tokens=6) for p in ps[:3]]
        eng.step()
        rids += [eng.add_request(p, max_new_tokens=6) for p in ps[3:]]
        out = eng.run()
        streams.append([out[r] for r in rids])
    assert streams[0] == streams[1]


# -- the share test -----------------------------------------------------------

def _sparse_leaves(cfg, first, held, seed=3):
    """Reference leaves of one expert layer's MLP that holds `held`
    experts from `first`, cut out of ONE uncut layer's seeded arrays."""
    whole = dict(ref_cfg(cfg), num_hidden_layers=1, first_k_dense_replace=0,
                 n_routed_experts=cfg.router_experts, expert_offset=0)
    arrays = weights.make_all(seed, ref.param_spec(whole), jnp.float32)
    p = [arrays[f"layers.0.mlp.{leaf}"] for leaf in ref.MLP_LEAVES["sparse"]]
    p[3], p[4] = p[3][first:first + held], p[4][first:first + held]
    return p


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that all 4 shares give, plus the shared expert
    counted once, are the uncut layer — for the reference, and for the
    program's layer told which experts it holds."""
    kw = dict(layers=1, dense=0, router_experts=16, n_routed_experts=4,
              num_experts_per_tok=5)
    cfg = PanguUltraMoEConfig.tiny(**kw)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 7, 64))
    mm = common.mm_f32
    uncut = dict(ref_cfg(cfg), n_routed_experts=16, expert_offset=0)
    whole = ref.ffn(_sparse_leaves(cfg, 0, 16), u, ref.sizes(uncut), mm)
    parts = []
    for first in (0, 4, 8, 12):
        z = ref.sizes(dict(ref_cfg(cfg), expert_offset=first))
        parts.append(ref.moe_routed_part(_sparse_leaves(cfg, first, 4), u,
                                         z, mm))
    shared = ref.moe_shared_part(_sparse_leaves(cfg, 0, 4), u, mm)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5)
    assert all(np.abs(p).max() > 1e-3 for p in parts)

    flat = u.reshape(-1, 64)
    got = 0
    for first in (0, 4, 8, 12):
        layer = PanguUltraMoEForCausalLM(PanguUltraMoEConfig.tiny(
            expert_offset=first, **kw)).layers[0].mlp
        for leaf, a in zip(ref.MLP_LEAVES["sparse"],
                           _sparse_leaves(cfg, first, 4)):
            owner, name = leaf.split(".")
            getattr(getattr(layer, owner), name)._in_place_update(a)
        out, counters = layer.forward_rows(flat, jnp.ones(14, bool))
        got = got + np.asarray(out).reshape(2, 7, 64) \
            - (np.asarray(shared) if first else 0)
        assert counters[0] > 0
    np.testing.assert_allclose(got, whole, atol=1e-5)


# -- the fused step: a chunk's rows and the decode rows in one walk ----------

def _step_inputs(cfg, slots, width, bs=8, seed=0):
    """A pool of random rows and the host args of one chunk (one lane's,
    past two cached blocks, its last 3 rows padding) and of one decode
    step (every lane but the last decodes; the last is idle)."""
    rng = np.random.default_rng(seed)
    per = 6                                        # blocks a lane
    pool = rng.normal(size=(cfg.num_hidden_layers, 1 + per * (slots + 1),
                            bs, cfg.pool_row_width)).astype(np.float32)
    row = np.zeros(per, np.int32)
    row[:] = np.arange(1, 1 + per)
    start = 2 * bs
    chunk = rng.integers(0, cfg.vocab_size, (1, width), dtype=np.int32)
    tables = np.zeros((slots, per), np.int32)
    positions = np.zeros(slots, np.int32)
    for j in range(slots - 1):
        tables[j] = np.arange(1 + per * (j + 1), 1 + per * (j + 2))
        positions[j] = 5 + 9 * j
    tokens = rng.integers(0, cfg.vocab_size, (slots, 1), dtype=np.int32)
    return (pool, chunk, np.int32(start), row,
            np.int32(start + width - 3), tokens, positions, tables)


@pytest.mark.parametrize("slots,width,backend", [
    (2, 16, "dense"), (3, 32, "dense"), (3, 16, "pallas"),
    (2, 32, "pallas")],
    ids=["absorbed_2", "expanded_3", "absorbed_pallas_3",
         "expanded_pallas_2"])
def test_the_fused_step_is_the_chunk_then_the_decode_step(slots, width,
                                                          backend):
    """`decode_with_chunk` against `prefill_chunk` then `decode` on the
    same inputs: the same pool rows written, the same hidden rows for
    the chunk's valid rows and the lanes that decode, the decode step's
    own counts of lanes and rows walked, and the flag of the step that
    carried a chunk. A chunk of 16 rows attends absorbed at these widths,
    one of 32 expanded."""
    from paddle_tpu.core.tensor import Tensor

    model, cfg = seeded()
    spec = model.serving_spec()
    pool, chunk, start, row, plen, tokens, positions, tables = \
        _step_inputs(cfg, slots, width, seed=slots * width)
    w = lambda a: Tensor._wrap(jnp.asarray(a))  # noqa: E731
    pa.reset_latent_path_stats()

    @jax.jit
    def two(pool):
        c = spec.prefill_chunk(w(chunk), w(start), w(pool), None, w(row),
                               w(plen), backend=backend)
        s = spec.decode(w(tokens), w(positions), c.kpool, None, w(tables),
                        backend=backend)
        return c.hidden._array, s.hidden._array, s.kpool._array, \
            s.counters

    @jax.jit
    def one(pool):
        r, h = spec.decode_with_chunk(
            w(chunk), w(start), w(row), w(plen), w(tokens), w(positions),
            w(tables), w(pool), None, backend=backend)
        return r.hidden._array, h._array, r.kpool._array, r.counters

    h_c, h_s, pool_two, c_two = two(jnp.asarray(pool))
    form = "absorbed" if width < 32 else \
        "pallas_expanded" if backend == "pallas" else "expanded"
    assert pa.LATENT_CHUNK_STATS[form] == cfg.num_hidden_layers
    assert pa.LATENT_PATH_STATS[backend] == cfg.num_hidden_layers
    f_c, f_s, pool_one, c_one = one(jnp.asarray(pool))
    assert pa.LATENT_CHUNK_STATS[form] == 2 * cfg.num_hidden_layers
    assert pa.LATENT_PATH_STATS[backend] == 2 * cfg.num_hidden_layers
    np.testing.assert_allclose(np.asarray(pool_one), np.asarray(pool_two),
                               atol=2e-5)
    # the chunk's rows reached its blocks, past the cached two
    assert np.abs(np.asarray(pool_one) - pool)[:, 3].max() > 0.1
    valid = int(plen - start)
    np.testing.assert_allclose(np.asarray(f_c)[:, :valid],
                               np.asarray(h_c)[:, :valid], atol=2e-5)
    np.testing.assert_allclose(np.asarray(f_s)[:-1], np.asarray(h_s)[:-1],
                               atol=2e-5)
    names = [n for n, _ in spec.step_counters]
    one_c, two_c = dict(zip(names, c_one.tolist())), \
        dict(zip(names, c_two.tolist()))
    for name in ("decode_live_lanes", "mla_context_rows"):
        assert one_c[name] == two_c[name]
    assert two_c["decode_live_lanes"] == slots - 1
    assert (one_c["decode_steps_with_chunk"],
            two_c["decode_steps_with_chunk"]) == (1, 0)
    # the chunk's valid rows are routed in the one product too
    assert one_c["moe_assignments_held"] > two_c["moe_assignments_held"]


LENGTHS = [5, 19, 33, 12, 16, 7]


def serve_late(model, cfg, n_new=9, late=3, **kw):
    """More requests than slots, `late` of them added after two
    iterations, so chunks meet lanes that decode. -> (streams in request
    order, engine)."""
    asked = prompts(cfg, LENGTHS)
    eng = engine_for(model, **kw)
    ids = [eng.add_request(p, max_new_tokens=n_new)
           for p in asked[:len(asked) - late]]
    for _ in range(2):
        eng.step()
    ids += [eng.add_request(p, max_new_tokens=n_new)
            for p in asked[len(asked) - late:]]
    out = eng.drain()                     # audits the blocks
    return [out[i] for i in ids], eng


@pytest.mark.parametrize("slots,chunk", [(2, 8), (3, 16), (4, 32)],
                         ids=["absorbed_2", "absorbed_3", "expanded_4"])
def test_the_fused_order_serves_the_two_program_orders_tokens(
        fused_step_offered, slots, chunk):
    model, cfg = seeded()
    kw = dict(num_slots=slots, prefill_chunk=chunk)
    serial, eng_s = serve_late(model, cfg, async_core=False, **kw)
    ahead, eng = serve_late(model, cfg, **kw)
    assert ahead == serial
    assert [len(a) for a in ahead] == [n + 9 for n in LENGTHS]
    assert eng._fused is not None and eng_s._fused is None
    assert 0 < eng.decode_steps_with_chunk <= eng.decode_steps
    assert eng.overshoot_tokens == 0


def test_questions_over_a_cached_document_under_the_fused_order(
        fused_step_offered):
    """A lane decodes throughout, so every chunk rides a fused step: the
    document's first request ends its prompt inside one, and its blocks
    are published when that first token is read a call later. The
    questions after it start past the document's shared blocks, and a
    prompt that IS the document's whole blocks copies the shared block
    its first decode writes into. The same tokens as each request served
    cold, the hits of the two-program order, and the document's blocks
    cached once."""
    from paddle_tpu.observability.metrics import series_total

    model, cfg = seeded()
    doc = prompts(cfg, [43], seed=9)[0]
    busy = prompts(cfg, [5], seed=11)[0]
    asked = [np.concatenate([doc, q])
             for q in prompts(cfg, [6, 11, 3], seed=10)] + [doc[:40]]

    def serve(engine):
        engine.add_request(busy, max_new_tokens=80)
        rids = [engine.add_request(asked[0], max_new_tokens=8)]
        while engine.cache.num_cached_blocks < 5:
            engine.step()
        rids += [engine.add_request(p, max_new_tokens=8)
                 for p in asked[1:]]
        out = engine.drain()
        return [out[r] for r in rids]

    alone = engine_for(model, enable_prefix_cache=False)
    cold = []
    for p in asked:
        rid = alone.add_request(p, max_new_tokens=8)
        cold.append(alone.run()[rid])
    two = engine_for(model, num_slots=5, async_core=False)
    fused = engine_for(model, num_slots=5)
    assert serve(two) == cold
    assert serve(fused) == cold
    assert fused._fused is not None and fused.decode_steps_with_chunk > 0
    # the two later questions hit the document's 5 full blocks, and the
    # prompt of those blocks alone hits all of itself
    assert fused.prefix_hit_tokens == two.prefix_hit_tokens == 3 * 40
    assert series_total(fused.metrics_snapshot(),
                        "engine_cow_copies_total") == 1
    # the document's 5 blocks, then each question's own full blocks
    own = sum(len(p) // 8 - 5 for p in asked)
    assert fused.cache.num_cached_blocks == 5 + own \
        + len(busy) // 8 == two.cache.num_cached_blocks


def test_the_fused_steps_counters(fused_step_offered, expert_loads):
    """The lanes and the rows walked are the two-program order's; the
    experts' counts are those of ONE product over both row sets (each
    call's loads as the product runs, in tiles of 4 rows); and
    `decode_steps_with_chunk` counts the fused steps."""
    from paddle_tpu.observability.metrics import series_total

    model, cfg = seeded()
    kw = dict(num_slots=3, prefill_chunk=16)
    _, eng_s = serve_late(model, cfg, async_core=False, **kw)
    del expert_loads[:]
    _, eng = serve_late(model, cfg, **kw)
    jax.effects_barrier()
    t, t_s = eng.step_counter_totals, eng_s.step_counter_totals
    for name in ("decode_live_lanes", "mla_context_rows"):
        assert t[name] == t_s[name] > 0
    experts = cfg.num_hidden_layers - cfg.first_k_dense_replace
    fused = [s for rows, s in expert_loads if rows == 16 + 3]
    stepped = [s for rows, s in expert_loads if rows == 3]
    assert len(fused) == experts * eng.decode_steps_with_chunk > 0
    assert len(fused) + len(stepped) == experts * eng.decode_steps
    counted = fused + stepped
    assert t["moe_assignments_held"] == sum(int(s.sum()) for s in counted)
    assert t["moe_experts_touched"] == sum(int((s > 0).sum())
                                           for s in counted)
    assert t["moe_row_tiles"] == sum(int((-(-s // 4)).sum())
                                     for s in counted)
    assert series_total(eng.metrics_snapshot(),
                        "engine_decode_steps_with_chunk_total") == \
        eng.decode_steps_with_chunk
    assert t_s["decode_steps_with_chunk"] == 0


@pytest.mark.parametrize("offered", [True, False],
                         ids=["offered", "not_offered"])
def test_the_fused_step_traces_once_an_iteration_holds_both(request,
                                                            offered):
    """`decode_traces` is 1 until an iteration holds a chunk AND lanes
    that decode, then 2 where the spec offers the fused step, and stays 1
    where it does not."""
    if offered:
        request.getfixturevalue("fused_step_offered")
    model, cfg = seeded()
    a, b = prompts(cfg, [20, 30], seed=5)
    eng = engine_for(model)
    eng.add_request(a, max_new_tokens=4)
    eng.run()
    # a chunk with no lane beside it runs alone
    assert (eng.decode_traces, eng.decode_steps_with_chunk) == (1, 0)
    eng.add_request(a, max_new_tokens=12)
    eng.step()
    eng.step()
    eng.add_request(b, max_new_tokens=4)
    eng.run()
    assert (eng._fused is not None) is offered
    assert eng.decode_traces == (2 if offered else 1)
    assert eng.prefill_traces == 1
    assert (eng.decode_steps_with_chunk > 0) is offered


@pytest.mark.parametrize("on_chip,widths,dense,offered", [
    (False, (256, 128), 1, False), (True, (256, 128), 1, True),
    (True, (64, 32), 1, False), (True, (256, 128), 3, False)],
    ids=["off_the_chip", "the_kernels_widths", "a_narrow_width",
         "no_expert_layer"])
def test_the_fused_step_is_offered_where_the_experts_run_the_kernel(
        monkeypatch, on_chip, widths, dense, offered):
    """The hybrid's rule (`distributed/moe.one_product_reads_less`) at
    the width `expert_share` resolves its product from: the narrower of
    the hidden size and the gate-and-up product's."""
    monkeypatch.setattr("paddle_tpu.core.device.on_tpu", lambda: on_chip)
    model, cfg = seeded(dense=dense)
    cfg.hidden_size, cfg.moe_intermediate_size = widths
    assert model.serving_spec().offers_decode_with_chunk is offered
