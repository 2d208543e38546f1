"""Pallas kernel tests. On CPU the pallas TPU kernels run in interpret
mode or are skipped; the flash router must fall back to XLA and stay
numerically correct either way."""
import numpy as np
import pytest

import paddle_tpu as paddle


def _dense_ref(q, k, v, causal=True):
    import jax
    import jax.numpy as jnp

    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    d = q.shape[-1]
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(d)
    if causal:
        S = logits.shape[-1]
        logits = jnp.where(jnp.tril(jnp.ones((S, S), bool)), logits, -1e30)
    p = jax.nn.softmax(logits, -1)
    return np.asarray(jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vh), 1, 2))


def test_flash_router_fallback_matches_dense():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 128, 4, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 128, 4, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 128, 4, 64).astype(np.float32))
    out = np.asarray(flash_attention(q, k, v, causal=True))
    ref = _dense_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-2)


def test_sdpa_routes_and_differentiates():
    """sdpa with causal+TPU-friendly shapes must stay differentiable
    through whichever backend is picked."""
    import paddle_tpu.nn.functional as F

    q = paddle.randn([1, 128, 2, 64])
    q.stop_gradient = False
    out = F.scaled_dot_product_attention(q, q, q, is_causal=True,
                                         training=False)
    out.sum().backward()
    assert q.grad is not None
    assert np.isfinite(q.grad.numpy()).all()


def test_causal_cross_length_bottom_right_aligned():
    """causal attention with Sq < Skv (KV-cache continuation) must align
    the mask bottom-right: query i attends keys 0..(Skv-Sq+i). The last
    Sq rows of full self-attention are the reference."""
    import jax.numpy as jnp

    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    rng = np.random.RandomState(3)
    B, S, H, D = 1, 128, 2, 64
    q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    full = np.asarray(fa.flash_attention(q, k, v, causal=True))
    Sq = 32
    part = np.asarray(fa.flash_attention(q[:, -Sq:], k, v, causal=True))
    np.testing.assert_allclose(part, full[:, -Sq:], atol=2e-5)


def test_flash_router_records_path():
    """The router must record which backend each trace used — on CPU that
    is the XLA fallback (and the pallas counter must stay untouched)."""
    import jax.numpy as jnp

    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    fa.reset_path_stats()
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 128, 2, 64).astype(np.float32))
    fa.flash_attention(q, q, q, causal=True)
    assert fa.PATH_STATS["xla"] == 1
    assert fa.PATH_STATS["pallas"] == 0


@pytest.mark.parametrize("kernel,causal,shape", [
    ("chunked_causal_attention", True, (1, 512, 2, 128)),
    ("shortseq_attention", False, (1, 512, 2, 64)),
])
def test_flash_kernel_failure_propagates_on_tpu(monkeypatch, kernel,
                                                causal, shape):
    """On a TPU, at a shape a kernel claims, the kernel's failure is the
    caller's failure: no warning, no dense path (ROADMAP C2f). That the
    kernels really run there is phase 2 of chip_smoke.py."""
    import importlib

    import jax.numpy as jnp

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    class Refused(Exception):
        pass

    def refuse(*a, **k):
        raise Refused("the compiler said no")

    from paddle_tpu.distributed import topology

    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    monkeypatch.setattr(fa, kernel, refuse)
    monkeypatch.setattr(topology, "_default_hcg", None)  # one chip
    fa.reset_path_stats()
    q = jnp.zeros(shape, jnp.bfloat16)
    with pytest.raises(Refused):
        fa.flash_attention(q, q, q, causal=causal)
    assert fa.PATH_STATS == {"pallas": 0, "xla": 0}


def test_own_pallas_kernel_interpret_mode():
    """Run our kernel in pallas interpret mode on CPU for correctness."""
    import jax
    import jax.numpy as jnp

    import importlib

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    rng = np.random.RandomState(1)
    B, S, H, D = 1, 256, 2, 64
    q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))

    out = fa.pallas_sdpa_forward(q, k, v, causal=True,
                                 block_q=128, block_k=128, interpret=True)
    ref = _dense_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-3)


def test_shortseq_attention_interpret_fwd_and_grad():
    """The fused encoder kernel (whole-seq per program, single-pass bwd)
    must match dense attention in value AND gradient — interpret mode
    exercises the exact kernel code on CPU."""
    import importlib

    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    rng = np.random.RandomState(0)
    B, S, H, D = 2, 256, 3, 64  # BH=6 exercises hb=6 head batching
    q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))

    out = fa.shortseq_attention(q, k, v, interpret=True)
    ref = _dense_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-3)

    def loss_kernel(q, k, v):
        return jnp.sum(fa.shortseq_attention(q, k, v, interpret=True) ** 2)

    def loss_dense(q, k, v):
        qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(D)
        p = jax.nn.softmax(logits, -1)
        o = jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vh), 1, 2)
        return jnp.sum(o ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_shortseq_hb_divisor():
    from paddle_tpu.ops.pallas.flash_attention import _shortseq_hb

    assert _shortseq_hb(768) == 6
    assert _shortseq_hb(8) == 4
    assert _shortseq_hb(7) == 1
    for bh in (2, 3, 4, 6, 12, 768):
        assert bh % _shortseq_hb(bh) == 0


def test_chunked_causal_attention_interpret_fwd_and_grad():
    """The chunked causal decoder kernel (whole head per program,
    prefix-k blocks, single-pass bwd) must match dense causal attention
    in value and gradient — interpret mode runs the kernel on CPU."""
    import importlib

    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    rng = np.random.RandomState(0)
    B, S, H, D = 1, 512, 2, 64
    q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))

    out = fa.chunked_causal_attention(q, k, v, interpret=True)
    ref = _dense_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-3)

    def loss_kernel(q, k, v):
        return jnp.sum(
            fa.chunked_causal_attention(q, k, v, interpret=True) ** 2)

    def loss_dense(q, k, v):
        qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(D)
        logits = jnp.where(jnp.tril(jnp.ones((S, S), bool)), logits,
                           -1e30)
        p = jax.nn.softmax(logits, -1)
        o = jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vh), 1, 2)
        return jnp.sum(o ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3, err_msg=f"d{name}")


def test_causal_shape_gate():
    from paddle_tpu.ops.pallas.flash_attention import (
        _causal_bq, _shapes_ok_for_causal)

    assert _shapes_ok_for_causal(2048, 2048, 128)   # the GPT shape
    assert _shapes_ok_for_causal(512, 512, 64)
    assert not _shapes_ok_for_causal(2048, 1024, 128)  # cross-attn
    assert not _shapes_ok_for_causal(2048, 2048, 96)   # odd head dim
    assert not _shapes_ok_for_causal(16384, 16384, 128)  # VMEM blowout
    for S in (512, 1024, 2048, 4096):
        bq = _causal_bq(S, 128)
        assert bq and S % bq == 0 and bq >= 128
        assert 10 * bq * S <= 11 * 1024 * 1024


def test_shortseq_attention_key_mask_interpret():
    """The additive key (padding) mask path: masked keys contribute
    nothing, matching dense attention with the same mask — value AND
    gradients."""
    import importlib

    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    rng = np.random.RandomState(2)
    B, S, H, D = 2, 256, 3, 64
    q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    # row 0 pads the last 56 keys, row 1 pads nothing
    km = np.zeros((B, S), np.float32)
    km[0, 200:] = -1e30
    kmj = jnp.asarray(km)

    def dense(q, k, v):
        qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(D)
        logits = logits + kmj[:, None, None, :]
        p = jax.nn.softmax(logits, -1)
        return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vh), 1, 2)

    out = fa.shortseq_attention(q, k, v, key_mask=kmj, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense(q, k, v)),
                               atol=2e-3)

    gk = jax.grad(lambda v: jnp.sum(fa.shortseq_attention(
        q, k, v, key_mask=kmj, interpret=True) ** 2))(v)
    gd = jax.grad(lambda v: jnp.sum(dense(q, k, v) ** 2))(v)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gd), atol=5e-3)
    # padded keys receive zero dv
    assert np.abs(np.asarray(gk)[0, 200:]).max() == 0.0


def test_paged_decode_attention_interpret_mode():
    """The fused paged-attention decode kernel (ISSUE 3), kernel-tier:
    interpret mode on CPU must match a dense fp64 reference over a
    mixed-depth batch, write the incoming rows into the aliased pools,
    and leave every block outside the written rows untouched."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    L, nb, bs, H, D = 2, 10, 4, 2, 8
    B, maxb = 3, 3
    rng = np.random.RandomState(9)
    kpool = rng.randn(L, nb, bs, H, D).astype(np.float32)
    vpool = rng.randn(L, nb, bs, H, D).astype(np.float32)
    tables = np.zeros((B, maxb), np.int32)
    tables[0, :3] = [1, 2, 3]
    tables[1, :1] = [4]
    tables[2] = 0                       # idle slot: all-null, pos 0
    positions = np.asarray([8, 3, 0], np.int32)  # 8 = block boundary
    q = rng.randn(B, 1, H, D).astype(np.float32)
    kn = rng.randn(B, 1, H, D).astype(np.float32)
    vn = rng.randn(B, 1, H, D).astype(np.float32)

    layer = 1
    out, kp, vp = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(kpool), jnp.asarray(vpool), layer,
        jnp.asarray(tables), jnp.asarray(positions), interpret=True)
    out, kp, vp = (np.asarray(out), np.asarray(kp), np.asarray(vp))

    # fp64 oracle shared with the backend-seam tests (one reference to
    # keep correct); context reassembled by the dense_gather probe
    from paddle_tpu.ops.paged_attention import dense_gather_reference
    from test_paged_attention_backends import _np_step_reference

    for b in range(2):                  # live slots vs fp64 reference
        pos = int(positions[b])
        ctx_k, ctx_v = dense_gather_reference(
            jnp.asarray(kpool), jnp.asarray(vpool), layer, tables[b],
            pos)
        ref = _np_step_reference(q[b], kn[b], vn[b], ctx_k, ctx_v, pos)
        np.testing.assert_allclose(out[b], ref, rtol=2e-5, atol=2e-6)

    # fused writes landed: slot0 at (block 3, row 0), slot1 at
    # (block 4, row 3), idle slot at the null block row 0
    np.testing.assert_array_equal(kp[layer, 3, 0], kn[0, 0])
    np.testing.assert_array_equal(vp[layer, 4, 3], vn[1, 0])
    np.testing.assert_array_equal(kp[layer, 0, 0], kn[2, 0])
    # everything else is byte-identical to the input pools (the other
    # layer plane included: the kernel only touches `layer`)
    mask = np.ones((L, nb, bs), bool)
    for (lay, blk, row) in [(layer, 3, 0), (layer, 4, 3), (layer, 0, 0)]:
        mask[lay, blk, row] = False
    np.testing.assert_array_equal(kp[mask], kpool[mask])
    np.testing.assert_array_equal(vp[mask], vpool[mask])


def test_flash_kernel_runs_per_shard_under_a_hybrid_mesh():
    """A Mosaic kernel cannot be partitioned by the compiler, so under
    a multi-device hybrid mesh (`DistributedTrainStep`'s GSPMD step)
    `flash_attention` wraps it in a shard_map — batch over the data
    axes, heads over `mp`. Same numbers as the unwrapped kernel; on one
    device, or already inside a shard_map, it is the kernel itself."""
    import functools
    import importlib

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed import topology

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    kernel = functools.partial(fa.chunked_causal_attention,
                               interpret=True)
    B, S, H, D = 4, 128, 4, 64
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
               for _ in range(3))
    saved = topology._default_hcg
    try:
        topology.set_hybrid_communicate_group(None)
        assert fa._per_shard(kernel, B, H) is kernel     # no mesh
        hcg = topology.HybridCommunicateGroup(dp=2, mp=2, pp=2)
        topology.set_hybrid_communicate_group(hcg)
        wrapped = fa._per_shard(kernel, B, H)
        assert wrapped is not kernel
        got = jax.jit(wrapped)(q, k, v)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(kernel(q, k, v)),
                                   atol=1e-6)

        def inside(a):      # manual axes: already per shard
            assert fa._per_shard(kernel, B, H) is kernel
            return a

        jax.shard_map(inside, mesh=hcg.mesh, in_specs=P("dp"),
                      out_specs=P("dp"))(q)
        topology.set_hybrid_communicate_group(
            topology.HybridCommunicateGroup(devices=jax.devices()[:1]))
        assert fa._per_shard(kernel, B, H) is kernel     # one device
    finally:
        topology.set_hybrid_communicate_group(saved)
