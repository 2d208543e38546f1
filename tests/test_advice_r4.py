"""Regression tests for ADVICE round-3 findings.

1 (medium): buffer updates (BN running stats, SpectralNorm u/v power
   iteration) must persist across compiled TrainStep /
   DistributedTrainStep calls — previously bound_state restored them
   every step, so sigma never converged and BN eval stats stayed at
   init under compiled training.
2 (low): unfold/fold run the patch conv at HIGHEST precision (pure data
   movement must be exact).
3 (low): Engine.predict feeds the WHOLE batch as inputs (no label
   split) so multi-input unlabeled datasets keep their last input.
4 (low): ASP n:m masks are re-applied inside the compiled update, not
   just eager optimizer.step.
5 (low): complex() on complex-less backends keeps gradients to both
   inputs and derives the complex dtype from the inputs.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.jit as jit
import paddle_tpu.nn as nn


class _BNNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.conv = nn.Conv2D(3, 4, 3, padding=1)
        self.bn = nn.BatchNorm2D(4)
        self.fc = nn.Linear(4, 2)

    def forward(self, x):
        h = self.bn(self.conv(x)).mean(axis=[2, 3])
        return self.fc(h)


def _loss(out, label):
    return ((out - label) ** 2).mean()


def test_bn_running_stats_advance_under_trainstep():
    paddle.seed(0)
    model = _BNNet()
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=model.parameters())
    step = jit.TrainStep(model, opt, _loss)
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(4, 3, 8, 8).astype(np.float32))
    y = paddle.to_tensor(np.zeros((4, 2), np.float32))
    m0 = np.asarray(model.bn._mean._array).copy()
    step(x, y)
    m1 = np.asarray(model.bn._mean._array)
    assert not np.allclose(m0, m1), \
        "BN running mean did not advance under compiled TrainStep"
    # a second step advances again (state threads, not just one write)
    step(x, y)
    m2 = np.asarray(model.bn._mean._array)
    assert not np.allclose(m1, m2)


def test_bn_stats_match_eager_under_trainstep():
    """The compiled step's stat update must equal the eager one."""
    rs = np.random.RandomState(1)
    xnp = rs.randn(4, 3, 8, 8).astype(np.float32)
    ynp = np.zeros((4, 2), np.float32)

    paddle.seed(0)
    m_eager = _BNNet()
    out = m_eager(paddle.to_tensor(xnp))
    loss = _loss(out, paddle.to_tensor(ynp))
    loss.backward()  # grads unused; forward already updated stats

    paddle.seed(0)
    m_comp = _BNNet()
    opt = paddle.optimizer.SGD(learning_rate=0.0,
                               parameters=m_comp.parameters())
    jit.TrainStep(m_comp, opt, _loss)(paddle.to_tensor(xnp),
                                      paddle.to_tensor(ynp))
    np.testing.assert_allclose(np.asarray(m_eager.bn._mean._array),
                               np.asarray(m_comp.bn._mean._array),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m_eager.bn._variance._array),
                               np.asarray(m_comp.bn._variance._array),
                               rtol=1e-5, atol=1e-6)


def test_bn_stats_advance_under_run_repeat_and_scan():
    paddle.seed(0)
    model = _BNNet()
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=model.parameters())
    step = jit.TrainStep(model, opt, _loss)
    x = paddle.to_tensor(np.random.RandomState(2)
                         .randn(4, 3, 8, 8).astype(np.float32))
    y = paddle.to_tensor(np.zeros((4, 2), np.float32))
    m0 = np.asarray(model.bn._mean._array).copy()
    step.run_repeat(x, y, steps=3)
    m1 = np.asarray(model.bn._mean._array)
    assert not np.allclose(m0, m1)
    xs = paddle.to_tensor(np.random.RandomState(3)
                          .randn(2, 4, 3, 8, 8).astype(np.float32))
    ys = paddle.to_tensor(np.zeros((2, 4, 2), np.float32))
    step.run_scan(xs, ys)
    m2 = np.asarray(model.bn._mean._array)
    assert not np.allclose(m1, m2)


class _SNNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(6, 6)
        self.sn = nn.SpectralNorm([6, 6], power_iters=1)
        self.out = nn.Linear(6, 1)

    def forward(self, x):
        w = self.sn(self.fc.weight)
        return self.out(x @ w + self.fc.bias)


def test_spectral_norm_power_iteration_converges_compiled():
    """u/v must advance across compiled steps: with power_iters=1 the
    sigma estimate converges to the true max singular value only if
    state persists (the round-3 advisor finding)."""
    paddle.seed(0)
    model = _SNNet()
    opt = paddle.optimizer.SGD(learning_rate=0.0,  # freeze params
                               parameters=model.parameters())
    step = jit.TrainStep(model, opt, _loss)
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(4, 6).astype(np.float32))
    y = paddle.to_tensor(np.zeros((4, 1), np.float32))
    u0 = np.asarray(model.sn.weight_u._array).copy()
    for _ in range(30):
        step(x, y)
    u_final = np.asarray(model.sn.weight_u._array)
    assert not np.allclose(u0, u_final), \
        "SpectralNorm u did not advance under compiled training"
    # after many persisted iterations sigma(u,v) ~= true sigma_max
    w = np.asarray(model.fc.weight._array)
    v = np.asarray(model.sn.weight_v._array)
    sigma_est = float(u_final @ (w @ v))
    sigma_true = float(np.linalg.svd(w, compute_uv=False)[0])
    assert abs(sigma_est - sigma_true) / sigma_true < 1e-3


def test_unfold_fold_exact_roundtrip():
    import paddle_tpu.nn.functional as F

    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(2, 3, 8, 8).astype(np.float32))
    cols = F.unfold(x, 3, strides=1, paddings=1)
    back = F.fold(cols, (8, 8), 3, strides=1, paddings=1)
    # every pixel is covered by a known number of patches; dividing by
    # the coverage count must reproduce x EXACTLY (data movement only)
    ones = paddle.ones_like(x)
    cnt = F.fold(F.unfold(ones, 3, strides=1, paddings=1), (8, 8), 3,
                 strides=1, paddings=1)
    rec = np.asarray(back._array) / np.asarray(cnt._array)
    # float32 summation order costs ~1e-7 relative; the bf16 default-
    # precision bug this guards against costs ~2e-3
    np.testing.assert_allclose(rec, np.asarray(x._array),
                               rtol=1e-5, atol=1e-6)


def test_engine_predict_multi_input_no_label_split():
    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.distributed.topology import (
        HybridCommunicateGroup, set_hybrid_communicate_group)

    set_hybrid_communicate_group(HybridCommunicateGroup())

    class TwoIn(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 2)

        def forward(self, a, b):
            return self.fc(a + b)

    paddle.seed(0)
    model = TwoIn()
    eng = Engine(model)
    a = np.random.RandomState(0).randn(6, 4).astype(np.float32)
    b = np.random.RandomState(1).randn(6, 4).astype(np.float32)

    class DS(paddle.io.Dataset):
        def __len__(self):
            return 6

        def __getitem__(self, i):
            return a[i], b[i]

    pred = eng.predict(DS(), batch_size=3)
    model.eval()
    want = np.asarray(model(paddle.to_tensor(a),
                            paddle.to_tensor(b))._array)
    np.testing.assert_allclose(pred, want, rtol=1e-5, atol=1e-6)


def test_asp_masks_hold_under_trainstep():
    from paddle_tpu.incubate import asp

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 2))
    masks = asp.prune_model(model, n=2, m=4)
    assert masks, "prune_model found nothing to prune"
    opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                 parameters=model.parameters())
    step = jit.TrainStep(model, opt, _loss)
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(4, 8).astype(np.float32))
    y = paddle.to_tensor(np.zeros((4, 2), np.float32))
    for _ in range(3):
        step(x, y)
    w = np.asarray(model[0].weight._array)
    assert asp.check_mask_1d(w, n=2, m=4), \
        "n:m sparsity decayed under compiled training"


def test_recompute_threads_bn_buffers():
    """recompute (jax.checkpoint) composed with BatchNorm inside a
    compiled TrainStep: no tracer leak, and running stats advance
    (the buffer updates ride the vjp aux, r4 fix)."""
    from paddle_tpu.distributed.recompute import recompute

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.block = nn.Sequential(
                nn.Conv2D(3, 4, 3, padding=1), nn.BatchNorm2D(4),
                nn.ReLU())
            self.fc = nn.Linear(4, 2)

        def forward(self, x):
            h = recompute(self.block, x)
            return self.fc(h.mean(axis=[2, 3]))

    paddle.seed(0)
    model = Net()
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=model.parameters())
    step = jit.TrainStep(model, opt, _loss)
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(4, 3, 8, 8).astype(np.float32))
    y = paddle.to_tensor(np.zeros((4, 2), np.float32))
    bn = model.block[1]
    m0 = np.asarray(bn._mean._array).copy()
    w0 = np.asarray(model.block[0].weight._array).copy()
    step(x, y)
    assert not np.allclose(m0, np.asarray(bn._mean._array)), \
        "BN stats did not advance through recompute"
    assert not np.allclose(w0, np.asarray(model.block[0].weight._array)), \
        "grads did not reach the rematted block's params"


def test_shared_sublayer_no_double_donation():
    """A layer registered under two parents yields duplicate
    parameters()/buffers() entries; the compiled step must dedup them
    (duplicates crash XLA donation with INVALID_ARGUMENT, r4 fix)."""

    class Shared(nn.Layer):
        def __init__(self):
            super().__init__()
            self.body = nn.Sequential(nn.Linear(4, 4), nn.ReLU())
            self.alias = self.body  # second registration, same object
            self.bn = nn.BatchNorm1D(4)
            self.bn_alias = self.bn
            self.out = nn.Linear(4, 2)

        def forward(self, x):
            h = self.alias(self.body(x))
            h = self.bn(h.unsqueeze(-1)).squeeze(-1)
            return self.out(h)

    paddle.seed(0)
    model = Shared()
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=model.parameters())
    step = jit.TrainStep(model, opt, _loss)
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(4, 4).astype(np.float32))
    y = paddle.to_tensor(np.zeros((4, 2), np.float32))
    l0 = float(step(x, y))
    l1 = float(step(x, y))
    assert np.isfinite(l0) and np.isfinite(l1)


def test_avg_pool3d_divisor_override():
    import paddle_tpu.nn.functional as F

    x = paddle.to_tensor(np.ones((1, 1, 4, 4, 4), np.float32))
    out = F.avg_pool3d(x, kernel_size=2, stride=2, divisor_override=4)
    # window sum is 8 ones; / 4 override = 2
    np.testing.assert_allclose(np.asarray(out._array),
                               np.full((1, 1, 2, 2, 2), 2.0))


def test_hybrid_coo_partial_sparse_dim():
    a = np.zeros((3, 2), np.float32)
    a[1] = [5.0, 0.0]
    t = paddle.to_tensor(a)
    sp = t.to_sparse_coo(1)  # hybrid: 1 sparse dim, 1 dense dim
    assert sp.nnz() == 1
    np.testing.assert_array_equal(np.asarray(sp.indices()._array), [[1]])
    np.testing.assert_array_equal(np.asarray(sp.values()._array),
                                  [[5.0, 0.0]])
    np.testing.assert_allclose(np.asarray(sp.to_dense()._array), a)


def test_asp_mask_2d_greedy():
    from paddle_tpu.incubate import asp

    rs = np.random.RandomState(0)
    w = rs.randn(8, 8).astype(np.float32)
    mask = asp.create_mask_2d_greedy(w, n=2, m=4)
    assert asp.check_mask_2d(w * mask, n=2, m=4)
    # exactly n*m survivors per complete block
    for r in range(0, 8, 4):
        for c in range(0, 8, 4):
            assert mask[r:r + 4, c:c + 4].sum() == 8
    # greedy keeps the largest entry of every block
    for r in range(0, 8, 4):
        for c in range(0, 8, 4):
            blk = np.abs(w[r:r + 4, c:c + 4])
            i, j = np.unravel_index(blk.argmax(), blk.shape)
            assert mask[r + i, c + j] == 1.0
    # a 1d-only mask generally violates the 2d column constraint check
    assert not asp.check_mask_2d(np.eye(8) * 0 + [1, 1, 0, 0] * 2)

    # prune_model accepts the algo and sparsity holds under training
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 2))
    masks = asp.prune_model(model, n=2, m=4, mask_algo="mask_2d_greedy")
    assert masks
    assert asp.check_mask_2d(np.asarray(model[0].weight._array))
