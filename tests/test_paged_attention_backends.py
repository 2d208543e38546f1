"""ISSUE 3: the fused Pallas paged-attention decode kernel and its
backend-dispatching seam.

Covers the tentpole and satellites: pallas-(interpret)-vs-dense token
exactness for a FULL engine run (mid-run admissions, EOS early-stops,
lane evictions) with decode-traces == 1 per backend and the pool-parity
probe via `dense_gather_reference`; block-table edge cases under both
backends (block-boundary positions, single-block contexts, a slot at
max_model_len - 1, idle all-null slots never polluting live blocks);
the dense fallback's fp32 PV-accumulation numerics against an fp64
reference at bf16; the import smoke (no JAX backend init); and the two
new bench rows being registered + `--pending`-flagged until a TPU
`--save` refresh adopts them.
"""
import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference import GenerationEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 61


def _model(seed=0):
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


# -- op-level: block-table edge cases under both backends -----------------

def _np_step_reference(q, k_new, v_new, ctx_k, ctx_v, pos):
    """fp64 dense attention over one slot's context + this token."""
    kd = np.concatenate([ctx_k[:pos], k_new], 0).astype(np.float64)
    vd = np.concatenate([ctx_v[:pos], v_new], 0).astype(np.float64)
    d = q.shape[-1]
    logits = np.einsum("qhd,khd->hqk", q.astype(np.float64), kd) \
        / np.sqrt(d)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", p, vd)


@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_block_table_edge_cases(backend):
    """Position exactly on a block boundary (the write opens a fresh
    block), a single-block context, a slot at max_model_len - 1 (full
    table walked), and an idle all-null slot whose garbage write must
    land in block 0 and nowhere else."""
    from paddle_tpu.ops.paged_attention import (
        dense_gather_reference, paged_attention_step)

    bs, maxb, H, D = 4, 4, 2, 8
    B, nb = 4, 20                       # slots; spare blocks stay 0
    rng = np.random.RandomState(3)
    tables = np.zeros((B, maxb), np.int32)
    tables[0, :2] = [1, 2]              # pos 4 = boundary: block 1 full,
    positions = np.zeros(B, np.int32)   # write opens block 2
    positions[0] = 4
    tables[1, :1] = [3]                 # single-block context, pos 2
    positions[1] = 2
    tables[2] = [4, 5, 6, 7]            # max_model_len - 1 = 15
    positions[2] = bs * maxb - 1
    # slot 3 idle: all-null table, pos 0, HUGE values — any pollution
    # of a live block or output would be macroscopic

    kpool = np.zeros((1, nb, bs, H, D), np.float32)
    vpool = np.zeros((1, nb, bs, H, D), np.float32)
    ctx_k = rng.randn(B, bs * maxb, H, D).astype(np.float32)
    ctx_v = rng.randn(B, bs * maxb, H, D).astype(np.float32)
    for b in range(3):
        for t in range(positions[b]):
            kpool[0, tables[b, t // bs], t % bs] = ctx_k[b, t]
            vpool[0, tables[b, t // bs], t % bs] = ctx_v[b, t]
    q = rng.randn(B, 1, H, D).astype(np.float32)
    k_new = rng.randn(B, 1, H, D).astype(np.float32)
    v_new = rng.randn(B, 1, H, D).astype(np.float32)
    k_new[3] = 1e4
    v_new[3] = 1e4

    out, kp, vp = paged_attention_step(q, k_new, v_new, kpool, vpool, 0,
                                       tables, positions,
                                       backend=backend)
    out = np.asarray(out._array)
    kp, vp = np.asarray(kp._array), np.asarray(vp._array)

    for b in range(3):                  # live slots: exact attention
        ref = _np_step_reference(q[b], k_new[b], v_new[b], ctx_k[b],
                                 ctx_v[b], int(positions[b]))
        np.testing.assert_allclose(out[b], ref, rtol=2e-4, atol=2e-5)
        # the written row landed at (table[pos//bs], pos%bs) and the
        # reassembled context is exactly [ctx[:pos], k_new]
        gk, gv = dense_gather_reference(kp, vp, 0, tables[b],
                                        int(positions[b]) + 1)
        np.testing.assert_allclose(
            gk, np.concatenate([ctx_k[b, :positions[b]], k_new[b]], 0),
            rtol=1e-6)
        np.testing.assert_allclose(
            gv, np.concatenate([ctx_v[b, :positions[b]], v_new[b]], 0),
            rtol=1e-6)

    # idle slot: its write went to the null block...
    np.testing.assert_allclose(kp[0, 0, 0], k_new[3, 0], rtol=1e-6)
    np.testing.assert_allclose(vp[0, 0, 0], v_new[3, 0], rtol=1e-6)
    # ...and nowhere else: every spare block is still zero, and no live
    # block picked up the 1e4 garbage
    np.testing.assert_array_equal(kp[0, 8:], 0.0)
    np.testing.assert_array_equal(vp[0, 8:], 0.0)
    assert np.abs(kp[0, 1:8]).max() < 100.0
    assert np.abs(vp[0, 1:8]).max() < 100.0


@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_shared_prefix_blocks_read_only_in_both_backends(backend):
    """The prefix-cache layout: two slots whose tables alias the SAME
    context blocks (a shared system prompt seated read-only) but own
    private write blocks — the post-COW invariant the engine
    guarantees. Both backends must (a) compute each slot's attention
    over the shared context exactly, and (b) leave the shared blocks'
    bytes untouched: the step's only writes land in each slot's own
    block."""
    from paddle_tpu.ops.paged_attention import (
        dense_gather_reference, paged_attention_step)

    bs, maxb, H, D = 4, 4, 2, 8
    nb = 12
    rng = np.random.RandomState(13)
    shared_blocks = [1, 2]              # 8 shared prefix tokens
    tables = np.zeros((2, maxb), np.int32)
    tables[0, :3] = shared_blocks + [3]   # slot 0 writes into block 3
    tables[1, :3] = shared_blocks + [4]   # slot 1 into block 4
    positions = np.asarray([8, 8], np.int32)   # both at the boundary

    kpool = np.zeros((1, nb, bs, H, D), np.float32)
    vpool = np.zeros((1, nb, bs, H, D), np.float32)
    ctx_k = rng.randn(2 * bs, H, D).astype(np.float32)
    ctx_v = rng.randn(2 * bs, H, D).astype(np.float32)
    for t in range(2 * bs):
        kpool[0, shared_blocks[t // bs], t % bs] = ctx_k[t]
        vpool[0, shared_blocks[t // bs], t % bs] = ctx_v[t]
    shared_k0 = kpool[0, shared_blocks].copy()
    shared_v0 = vpool[0, shared_blocks].copy()

    q = rng.randn(2, 1, H, D).astype(np.float32)
    k_new = rng.randn(2, 1, H, D).astype(np.float32)
    v_new = rng.randn(2, 1, H, D).astype(np.float32)
    out, kp, vp = paged_attention_step(q, k_new, v_new, kpool, vpool,
                                       0, tables, positions,
                                       backend=backend)
    out = np.asarray(out._array)
    kp, vp = np.asarray(kp._array), np.asarray(vp._array)

    ctx = np.broadcast_to(ctx_k, (2,) + ctx_k.shape)
    ctxv = np.broadcast_to(ctx_v, (2,) + ctx_v.shape)
    for b in range(2):
        ref = _np_step_reference(q[b], k_new[b], v_new[b], ctx[b],
                                 ctxv[b], 8)
        np.testing.assert_allclose(out[b], ref, rtol=2e-4, atol=2e-5)
        gk, gv = dense_gather_reference(kp, vp, 0, tables[b], 9)
        np.testing.assert_allclose(gk[-1], k_new[b, 0], rtol=1e-6)
        np.testing.assert_allclose(gv[-1], v_new[b, 0], rtol=1e-6)
    # the aliased context blocks are byte-identical to before the step
    np.testing.assert_array_equal(kp[0, shared_blocks], shared_k0)
    np.testing.assert_array_equal(vp[0, shared_blocks], shared_v0)


def test_backends_agree_bitwise_on_pool_writes():
    """The two backends must produce the SAME pool bytes (writes are
    scatter-vs-DMA of identical rows) and outputs within float
    tolerance of each other at a mixed-depth batch."""
    from paddle_tpu.ops.paged_attention import paged_attention_step

    bs, maxb, H, D = 4, 3, 2, 8
    B, nb = 3, 12
    rng = np.random.RandomState(11)
    kpool = rng.randn(1, nb, bs, H, D).astype(np.float32)
    vpool = rng.randn(1, nb, bs, H, D).astype(np.float32)
    tables = np.zeros((B, maxb), np.int32)
    tables[0, :3] = [1, 2, 3]
    tables[1, :1] = [4]
    tables[2, :2] = [5, 6]
    positions = np.asarray([9, 0, 7], np.int32)
    q = rng.randn(B, 1, H, D).astype(np.float32)
    kn = rng.randn(B, 1, H, D).astype(np.float32)
    vn = rng.randn(B, 1, H, D).astype(np.float32)

    res = {}
    for backend in ("dense", "pallas"):
        out, kp, vp = paged_attention_step(q, kn, vn, kpool, vpool, 0,
                                           tables, positions,
                                           backend=backend)
        res[backend] = (np.asarray(out._array), np.asarray(kp._array),
                        np.asarray(vp._array))
    np.testing.assert_array_equal(res["dense"][1], res["pallas"][1])
    np.testing.assert_array_equal(res["dense"][2], res["pallas"][2])
    np.testing.assert_allclose(res["dense"][0], res["pallas"][0],
                               rtol=2e-5, atol=2e-6)


# -- the decode walk: several pages a compute step (PR 27) ----------------

_WALK_BS, _WALK_H, _WALK_D, _WALK_MAXB = 16, 2, 8, 12


def _walk_pages():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.paged_attention import pages_per_step
    return pages_per_step(_WALK_BS, _WALK_H, _WALK_D, jnp.float32)


# context lengths in tokens (the incoming one counted) as functions of
# the pages a step N and the page size; None is an idle slot
_WALK_CASES = {
    "one_token": lambda n, bs: [1],
    "one_page": lambda n, bs: [bs],
    "one_page_plus_1": lambda n, bs: [bs + 1],
    "n_pages": lambda n, bs: [n * bs],
    "n_pages_plus_1": lambda n, bs: [n * bs + 1],
    "n_plus_1_pages": lambda n, bs: [(n + 1) * bs],
    "ragged_with_idle": lambda n, bs: [
        (n + 1) * bs, 1, None, n * bs + 1, bs, n * bs, bs + 1, None, 7],
}


@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_decode_walk_over_context_lengths(case):
    """The walk's boundaries: a step that is one token, one page, one
    page and a row, exactly the pages of a compute step, one row more,
    one page more — alone (first the cold program, then behind an idle
    slot, whose last step starts the next slot's copies) and mixed in
    one ragged batch. Against fp64 attention at the file's tolerance,
    with the pool written bitwise as the dense path writes it."""
    from paddle_tpu.ops.paged_attention import paged_attention_step

    bs, H, D, maxb = _WALK_BS, _WALK_H, _WALK_D, _WALK_MAXB
    n = _walk_pages()
    assert 1 < n and (n + 1) * bs <= maxb * bs    # the cases are distinct
    lens = _WALK_CASES[case](n, bs)
    if len(lens) == 1:
        lens = [lens[0], None, lens[0]]
    B = len(lens)
    nb = 1 + B * maxb
    rng = np.random.RandomState(27)
    kpool = rng.randn(1, nb, bs, H, D).astype(np.float32)
    vpool = rng.randn(1, nb, bs, H, D).astype(np.float32)
    tables = np.zeros((B, maxb), np.int32)
    positions = np.zeros(B, np.int32)
    for b, n_tok in enumerate(lens):
        if n_tok is None:
            continue
        positions[b] = n_tok - 1
        pages = (n_tok - 1) // bs + 1
        tables[b, :pages] = 1 + b * maxb + np.arange(pages)
    q = rng.randn(B, 1, H, D).astype(np.float32)
    k_new = rng.randn(B, 1, H, D).astype(np.float32)
    v_new = rng.randn(B, 1, H, D).astype(np.float32)
    # every idle slot writes the null row: make the rows equal, so the
    # scatter's winner and the kernel's last writer agree
    idle = [b for b, n_tok in enumerate(lens) if n_tok is None]
    k_new[idle], v_new[idle] = k_new[idle[0]], v_new[idle[0]]

    res = {}
    for backend in ("dense", "pallas"):
        out, kp, vp = paged_attention_step(q, k_new, v_new, kpool, vpool,
                                           0, tables, positions,
                                           backend=backend)
        res[backend] = (np.asarray(out._array), np.asarray(kp._array),
                        np.asarray(vp._array))
    out, kp, vp = res["pallas"]
    np.testing.assert_array_equal(kp, res["dense"][1])
    np.testing.assert_array_equal(vp, res["dense"][2])
    assert np.isfinite(out).all()                 # idle slots too
    for b, n_tok in enumerate(lens):
        if n_tok is None:
            continue
        pos = n_tok - 1
        ctx_k = kpool[0, tables[b]].reshape(-1, H, D)
        ctx_v = vpool[0, tables[b]].reshape(-1, H, D)
        ref = _np_step_reference(q[b], k_new[b], v_new[b], ctx_k, ctx_v,
                                 pos)
        np.testing.assert_allclose(out[b], ref, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(out, res["dense"][0], rtol=2e-5, atol=2e-6)


def test_pages_per_step_follows_from_shapes_alone(model, monkeypatch):
    """The chat cell's shape reads 8 pages a step on every call; every
    geometry `auto` admits on a TPU reads at least one, inside the
    walk's VMEM budget once it reads more; the engine publishes the
    kernel's own number."""
    import importlib

    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import paged_attention as kernels

    chat = (16, 16, 128, jnp.bfloat16)
    assert [kernels.pages_per_step(*chat) for _ in range(3)] == [8] * 3
    pa = importlib.import_module("paddle_tpu.ops.paged_attention")
    with monkeypatch.context() as on_chip:
        on_chip.setattr(pa, "on_tpu", lambda: True)
        admitted = [
            (block, heads, head_dim)
            for heads in (1, 2, 4, 8, 12, 16, 24, 32, 40, 64)
            for head_dim in (64, 128, 256)
            for block in (4, 8, 16, 32, 64, 128, 256)
            if pa.resolve_backend("auto", head_dim=head_dim,
                                  block_size=block,
                                  num_heads=heads) == "pallas"]
    assert len(admitted) >= 30
    for block, heads, head_dim in admitted:
        for dt in (jnp.bfloat16, jnp.float32):
            n = kernels.pages_per_step(block, heads, head_dim, dt)
            assert n >= 1 and n * block <= max(block, 128)
            held = 4 * n * block * heads * head_dim * jnp.dtype(dt).itemsize
            assert n == 1 or held <= kernels._WALK_VMEM_BYTES

    for backend, want in (
            ("pallas", kernels.pages_per_step(4, 2, 16, jnp.float32)),
            ("dense", 0)):
        eng = GenerationEngine(model, num_slots=2, block_size=4,
                               num_blocks=20,
                               attention_backend=backend)
        (series,) = eng.metrics_snapshot()[
            "engine_paged_decode_pages_per_step"]["series"]
        assert series["value"] == want


# -- satellite: dense-fallback bf16 numerics ------------------------------

def test_dense_bf16_pv_accumulation_fp32(model=None):
    """The PV product must accumulate in fp32 across the block loop
    and cast to bf16 ONCE at the end. Near-uniform attention (tiny
    irregular logits) over large alternating +/-A value rows makes the
    true output a small residual that survives only if neither the
    probs nor a partial accumulator rounds to bf16 — the pre-fix path
    (probs cast to q.dtype, PV materialized at q.dtype) leaves an
    O(A * bf16_eps) ~ 2.0 error where the fixed path lands within
    ~1e-2. fp64 reference computed from the same bf16-rounded
    inputs."""
    import jax.numpy as jnp

    from paddle_tpu.ops.paged_attention import paged_attention_step

    bs, maxb, H, D = 8, 16, 2, 8
    ctx = bs * maxb - 1                 # 127 cached + 1 incoming
    nb = maxb + 1
    rng = np.random.RandomState(5)
    A = 512.0
    # value rows: +/-A alternating (pairs cancel under near-uniform
    # weights) plus an O(1) signal that IS the answer
    signal = rng.randn(ctx + 1, H, D).astype(np.float32)
    v_rows = (np.where((np.arange(ctx + 1) % 2 == 0), A, -A)
              [:, None, None] + signal).astype(np.float32)
    v16 = np.asarray(jnp.asarray(v_rows, jnp.bfloat16)
                     .astype(jnp.float32))
    # tiny irregular keys: softmax weights are NEAR 1/T but not exactly
    # representable in bf16, so a probs-to-bf16 cast alone already
    # perturbs each +/-512 term by ~0.4%
    k_rows = np.asarray(jnp.asarray(
        0.02 * rng.randn(ctx + 1, H, D), jnp.bfloat16)
        .astype(jnp.float32))

    kpool = np.zeros((1, nb, bs, H, D), np.float32)
    vpool = np.zeros((1, nb, bs, H, D), np.float32)
    table = np.arange(1, maxb + 1, dtype=np.int32)[None]
    for t in range(ctx):
        kpool[0, table[0, t // bs], t % bs] = k_rows[t]
        vpool[0, table[0, t // bs], t % bs] = v16[t]
    q = np.asarray(jnp.asarray(0.02 * rng.randn(1, 1, H, D),
                               jnp.bfloat16).astype(jnp.float32))
    kn = k_rows[ctx][None, None]
    vn = v16[ctx][None, None]
    pos = np.asarray([ctx], np.int32)

    out, _, _ = paged_attention_step(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(kn, jnp.bfloat16),
        jnp.asarray(vn, jnp.bfloat16),
        jnp.asarray(kpool, jnp.bfloat16), jnp.asarray(vpool, jnp.bfloat16),
        0, table, pos, backend="dense")
    got = np.asarray(out._array.astype(jnp.float32))[0, 0]

    ref = _np_step_reference(q[0], kn[0], vn[0], k_rows, v16,
                             ctx)[0]            # fp64 softmax + PV
    # |out| is O(1) while the cancelled +/-A terms are 512: bf16
    # rounding of probs or of a partial accumulator leaves an O(1)+
    # residual error; the fp32-accumulation path stays ~1e-2
    assert np.abs(ref).max() < 3.0
    np.testing.assert_allclose(got, ref, atol=0.08)


# -- engine-level: pallas (interpret) vs dense, full serving run ----------

def _lockstep_engines(model, **kw):
    return {b: GenerationEngine(model, attention_backend=b, **kw)
            for b in ("dense", "pallas")}


def test_engine_run_token_exact_across_backends(model):
    """The tentpole acceptance: a full engine run — mid-run admissions,
    an EOS early-stop, finished lanes vacated for later arrivals — is
    TOKEN-EXACT between the dense and pallas (interpret) backends, each
    with the decode count_traces == 1 contract, and the mid-run pool
    contents agree via the dense_gather_reference probe."""
    import paddle_tpu.jit as jit
    from paddle_tpu.ops.paged_attention import (
        PAGED_PATH_STATS, dense_gather_reference, reset_paged_path_stats)

    rng = np.random.RandomState(0)
    reqs = [(rng.randint(0, VOCAB, rng.randint(1, 8)).astype(np.int32),
             int(rng.randint(3, 10))) for _ in range(8)]
    prompt = rng.randint(0, VOCAB, 5).astype(np.int32)
    plain = _reference(model, prompt, 12)
    eos = int(plain[len(prompt) + 2])            # 3rd generated token
    ref_eos = _reference(model, prompt, 12, eos=eos)

    reset_paged_path_stats()
    engines = _lockstep_engines(model, num_slots=3, block_size=4,
                                num_blocks=40)
    ids = {}
    for b, eng in engines.items():
        ids[b] = [eng.add_request(p, n) for p, n in reqs[:4]]
        ids[b].append(eng.add_request(prompt, 12, eos_token_id=eos))
        for _ in range(3):
            eng.step()                           # decode mid-stream

    # mid-run pool parity: every live slot's reassembled context is
    # bit-identical across backends (scatter writes vs fused DMA)
    de, pe = engines["dense"], engines["pallas"]
    for sd, sp in zip(de._slots, pe._slots):
        if sd is None or sp is None:
            assert sd is None and sp is None
            continue
        assert sd.req.req_id == sp.req.req_id
        n = len(sd.req.prompt) + len(sd.generated)
        for layer in range(model.config.num_layers):
            rowd = np.zeros(de.max_blocks, np.int32)
            rowd[:len(sd.blocks)] = sd.blocks
            rowp = np.zeros(pe.max_blocks, np.int32)
            rowp[:len(sp.blocks)] = sp.blocks
            gkd, gvd = dense_gather_reference(
                de.cache.kpool, de.cache.vpool, layer, rowd, n)
            gkp, gvp = dense_gather_reference(
                pe.cache.kpool, pe.cache.vpool, layer, rowp, n)
            np.testing.assert_allclose(gkd, gkp, rtol=2e-5, atol=2e-6)
            np.testing.assert_allclose(gvd, gvp, rtol=2e-5, atol=2e-6)

    outs = {}
    for b, eng in engines.items():
        ids[b] += [eng.add_request(p, n) for p, n in reqs[4:]]  # mid-run
        outs[b] = eng.run()
        assert eng.decode_traces == 1            # one program per backend
        # steady state: more churn retraces nothing
        with jit.expect_traces(eng._decode_pure, 0):
            eng.add_request(rng.randint(0, VOCAB, 5), 3)
            eng.run()

    assert PAGED_PATH_STATS["pallas"] > 0        # the kernel engaged
    assert PAGED_PATH_STATS["dense"] > 0
    for rid_d, rid_p in zip(ids["dense"], ids["pallas"]):
        assert outs["dense"][rid_d] == outs["pallas"][rid_p]
    # and both equal the single-request oracle (incl. the EOS stop)
    got = outs["pallas"][ids["pallas"][4]]
    assert got[-1] == eos and len(got) < len(prompt) + 12
    np.testing.assert_array_equal(got, ref_eos[:len(got)])
    for (p, n), rid in zip(reqs[:4], ids["pallas"][:4]):
        np.testing.assert_array_equal(np.asarray(outs["pallas"][rid]),
                                      _reference(model, p, n))


def test_engine_backend_metrics_and_bad_value(model):
    """The kernel-backend gauge + per-backend decode-span labels land
    in the engine's registry; `auto` resolves to dense off-TPU; bad
    values are rejected loudly."""
    eng = GenerationEngine(model, num_slots=2, block_size=4,
                           num_blocks=20,
                           attention_backend="pallas")
    assert eng.attention_backend == "pallas"
    eng.add_request([1, 2, 3], 4)
    eng.run()
    snap = eng.metrics_snapshot()
    info = {s["labels"]["backend"]: s["value"]
            for s in snap["engine_attention_backend_info"]["series"]}
    assert info == {"pallas": 1.0}
    spans = {s["labels"]["backend"]: s["count"]
             for s in snap["engine_decode_step_seconds"]["series"]}
    assert spans["pallas"] >= 3                  # 4 tokens: 3 decodes
    text = eng.metrics.render_prometheus()
    assert 'engine_attention_backend_info{backend="pallas"} 1' in text
    assert 'engine_decode_step_seconds_bucket{backend="pallas"' in text

    # off-TPU `auto` resolves dense (the DESIGN_DECISIONS crossover)
    auto = GenerationEngine(model, num_slots=2)
    assert auto.attention_backend == "dense"
    assert auto.attention_backend_requested == "auto"

    with pytest.raises(ValueError, match="backend"):
        GenerationEngine(model, num_slots=2, attention_backend="cuda")


# -- CI / tooling satellites ----------------------------------------------

def test_paged_kernel_import_has_no_backend_init():
    """Importing the kernel module must not initialize a JAX backend
    (the observability-smoke precedent): the module is imported by the
    op seam at dispatch time on serving hosts."""
    code = (
        "import paddle_tpu.ops.pallas.paged_attention as pk\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, 'backend initialized'\n"
        "assert callable(pk.paged_decode_attention)\n"
        "print('SMOKE_OK')\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "SMOKE_OK" in res.stdout


def test_new_bench_rows_registered_and_pending(capsys):
    """Both ISSUE-3 rows are in the suite (so a TPU run measures them)
    and `check_bench_result --pending` flags them until a `--save`
    refresh adopts them into OPBENCH.json."""
    import bench_ops

    names = bench_ops.suite_names()
    assert "paged_attention_decode_sweep" in names
    assert "gpt_engine_offered_load_pallas" in names

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import check_bench_result as gate

    with open(os.path.join(REPO, "OPBENCH.json")) as f:
        baseline = json.load(f)
    assert "paged_attention_decode_sweep" not in baseline  # not adopted
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(baseline, f)
        tmp = f.name
    try:
        rc = gate.check_pending(tmp, suite_names=names, strict=True)
        out = capsys.readouterr().out
        assert rc == 1
        assert "PENDING: paged_attention_decode_sweep" in out
        assert "PENDING: gpt_engine_offered_load_pallas" in out
    finally:
        os.unlink(tmp)


def test_paged_sweep_bench_runner_tiny():
    """The microbench row's runner at test scale: dense cost must GROW
    with active context at fixed max_model_len (the bounded-work
    acceptance criterion — the pre-fix gather was flat at the
    max_model_len cost), and both backend curves are recorded."""
    import jax.numpy as jnp

    import bench_ops

    rec = bench_ops._paged_attention_sweep_case(
        num_slots=2, heads=2, head_dim=8, block_size=4,
        max_model_len=64, ctx_lengths=(4, 64),
        backends=("dense", "pallas"), dtype=jnp.float32)()
    assert rec["max_model_len"] == 64
    d4, d64 = rec["dense_ms_by_ctx"]["4"], rec["dense_ms_by_ctx"]["64"]
    assert d4 > 0 and d64 > 0
    # 16x the active context: the bounded fori_loop must cost clearly
    # more at full context than near-empty (flat == unbounded gather)
    assert d64 > 2.0 * d4
    assert set(rec["pallas_ms_by_ctx"]) == {"4", "64"}
    assert rec["ms"] == rec["pallas_ms_by_ctx"]["64"]
