"""Neural-net structured ops: conv, pooling, normalization, embedding,
dropout, losses, attention.

Analogs of paddle/phi/kernels/{conv_kernel,pool_kernel,batch_norm_kernel,
layer_norm_kernel,embedding_kernel,softmax_kernel}.* and the fused ops in
paddle/fluid/operators/fused/. On TPU, convs and matmuls hit the MXU via
lax.conv_general_dilated / dot_general; "fusion" is XLA's job, so the
fused_* surface is expressed as single jax fns that compile to one
computation.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import random as random_mod
from paddle_tpu.core.random import next_key
from paddle_tpu.core.tensor import Tensor

from .dispatch import apply, apply_nograd, as_tensor

__all__ = [
    "linear", "conv2d", "conv1d", "conv2d_transpose", "conv3d",
    "max_pool2d", "avg_pool2d", "adaptive_avg_pool2d", "adaptive_max_pool2d",
    "max_pool1d", "avg_pool1d", "global_avg_pool2d",
    "batch_norm", "layer_norm", "group_norm", "instance_norm", "rms_norm",
    "embedding", "dropout", "dropout2d",
    "softmax_with_cross_entropy", "cross_entropy", "mse_loss", "l1_loss",
    "nll_loss", "bce_loss", "bce_with_logits", "smooth_l1_loss",
    "kl_div", "cosine_similarity", "margin_ranking_loss", "hinge_embedding_loss",
    "scaled_dot_product_attention", "interpolate", "pixel_shuffle",
    "fused_bias_dropout_residual_layer_norm", "label_smooth", "temporal_shift",
    "unfold", "fold", "grid_sample", "affine_grid",
    "max_pool3d", "avg_pool3d", "normalize", "local_response_norm",
    "dropout3d", "alpha_dropout", "pixel_unshuffle", "sequence_mask",
    "square_error_cost", "log_loss", "sigmoid_focal_loss", "dice_loss",
    "npair_loss", "triplet_margin_loss", "cosine_embedding_loss",
    "margin_cross_entropy", "ctc_loss",
]


# ---------------------------------------------------------------------------
# dense / conv
# ---------------------------------------------------------------------------

def linear(x, weight, bias=None):
    """y = x @ W + b. Analog of phi MatmulKernel+AddKernel; the reference's
    F.linear (python/paddle/nn/functional/common.py:1814). Weight layout is
    [in, out] (paddle convention)."""
    x, weight = as_tensor(x), as_tensor(weight)

    if bias is None:
        def fn(a, w):
            pet = jnp.float32 if jnp.issubdtype(a.dtype, jnp.floating) else None
            return jnp.matmul(a, w, preferred_element_type=pet).astype(
                jnp.promote_types(a.dtype, w.dtype)
            )

        return apply("linear", fn, x, weight)

    bias = as_tensor(bias)

    def fnb(a, w, b):
        pet = jnp.float32 if jnp.issubdtype(a.dtype, jnp.floating) else None
        out = jnp.matmul(a, w, preferred_element_type=pet)
        return (out + b.astype(out.dtype)).astype(jnp.promote_types(a.dtype, w.dtype))

    return apply("linear", fnb, x, weight, bias)


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


def _conv_padding(padding, k, stride, dilation, nsp):
    """Paddle padding spec -> lax padding list."""
    if isinstance(padding, str):
        return padding.upper()  # SAME / VALID
    if isinstance(padding, (list, tuple)):
        if len(padding) == nsp:
            return [(int(p), int(p)) for p in padding]
        if len(padding) == 2 * nsp:
            return [(int(padding[2 * i]), int(padding[2 * i + 1])) for i in range(nsp)]
    p = int(padding)
    return [(p, p)] * nsp


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW"):
    """2-D convolution on the MXU. Weight layout OIHW (paddle). Analog of
    phi Conv2dKernel (paddle/phi/kernels/conv_kernel.h)."""
    x, weight = as_tensor(x), as_tensor(weight)
    stride = _pair(stride)
    dilation = _pair(dilation)
    pad = _conv_padding(padding, None, stride, dilation, 2)

    def fn(a, w):
        # Compute ALWAYS runs NHWC/HWIO internally: the TPU conv engine
        # is an order of magnitude faster with channels-last operands
        # (measured on v5e, 28x28x256 3x3: 236 vs 20 TFLOPS). For NCHW
        # callers the wrapping transposes cancel between consecutive
        # layers inside one XLA program (algebraic simplifier moves them
        # through the elementwise/BN ops), so the paddle-default NCHW
        # API costs at most one transpose at each graph boundary.
        if data_format == "NCHW":
            a = jnp.transpose(a, (0, 2, 3, 1))
        w = jnp.transpose(w, (2, 3, 1, 0))  # OIHW -> HWIO
        # no preferred_element_type: the MXU accumulates bf16 convs in
        # fp32 natively, and an explicit fp32 output breaks the conv
        # transpose rule under AD (fp32 cotangent vs bf16 weight)
        out = jax.lax.conv_general_dilated(
            a, w, window_strides=stride, padding=pad,
            rhs_dilation=dilation, dimension_numbers=("NHWC", "HWIO",
                                                      "NHWC"),
            feature_group_count=groups,
        ).astype(a.dtype)
        if data_format == "NCHW":
            out = jnp.transpose(out, (0, 3, 1, 2))
        return out

    out = apply("conv2d", fn, x, weight)
    if bias is not None:
        bias = as_tensor(bias)
        bshape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        out = apply("conv2d_bias", lambda o, b: o + b.reshape(bshape), out, bias)
    return out


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL"):
    x, weight = as_tensor(x), as_tensor(weight)
    stride = _pair(stride, 1)
    dilation = _pair(dilation, 1)
    pad = _conv_padding(padding, None, stride, dilation, 1)
    dn = ("NCH", "OIH", "NCH")

    def fn(a, w):
        return jax.lax.conv_general_dilated(
            a, w, window_strides=stride, padding=pad,
            rhs_dilation=dilation, dimension_numbers=dn,
            feature_group_count=groups,
        ).astype(a.dtype)

    out = apply("conv1d", fn, x, weight)
    if bias is not None:
        out = apply("conv1d_bias", lambda o, b: o + b.reshape(1, -1, 1), out, as_tensor(bias))
    return out


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW"):
    x, weight = as_tensor(x), as_tensor(weight)
    stride = _pair(stride, 3)
    dilation = _pair(dilation, 3)
    pad = _conv_padding(padding, None, stride, dilation, 3)
    dn = ("NCDHW", "OIDHW", "NCDHW")

    def fn(a, w):
        return jax.lax.conv_general_dilated(
            a, w, window_strides=stride, padding=pad,
            rhs_dilation=dilation, dimension_numbers=dn,
            feature_group_count=groups,
        ).astype(a.dtype)

    out = apply("conv3d", fn, x, weight)
    if bias is not None:
        out = apply("conv3d_bias", lambda o, b: o + b.reshape(1, -1, 1, 1, 1), out, as_tensor(bias))
    return out


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0,
                     dilation=1, groups=1, data_format="NCHW"):
    """Transposed conv — analog of phi Conv2dTransposeKernel. Weight IOHW."""
    x, weight = as_tensor(x), as_tensor(weight)
    stride = _pair(stride)
    dilation = _pair(dilation)
    opad = _pair(output_padding)
    p = _conv_padding(padding, None, stride, dilation, 2)
    if isinstance(p, str):
        raise NotImplementedError("string padding for conv_transpose")

    def fn(a, w):
        # lax.conv_transpose with paddle's conv-grad-style padding math
        kh = (w.shape[2] - 1) * dilation[0] + 1
        kw = (w.shape[3] - 1) * dilation[1] + 1
        pad_cfg = [
            (kh - 1 - p[0][0], kh - 1 - p[0][1] + opad[0]),
            (kw - 1 - p[1][0], kw - 1 - p[1][1] + opad[1]),
        ]
        w_flip = jnp.flip(w, axis=(2, 3))  # IOHW flipped
        w_t = jnp.swapaxes(w_flip, 0, 1)  # -> OIHW with O=out channels
        if groups > 1:
            # grouped transpose: weight is (in, out/g, kh, kw)
            i, og, KH, KW = w.shape
            wg = w_flip.reshape(groups, i // groups, og, KH, KW)
            wg = jnp.swapaxes(wg, 1, 2).reshape(groups * og, i // groups, KH, KW)
            w_t = wg
        return jax.lax.conv_general_dilated(
            a, w_t, window_strides=(1, 1), padding=pad_cfg,
            lhs_dilation=stride, rhs_dilation=dilation,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups,
        ).astype(a.dtype)

    out = apply("conv2d_transpose", fn, x, weight)
    if bias is not None:
        out = apply("convt_bias", lambda o, b: o + b.reshape(1, -1, 1, 1), out, as_tensor(bias))
    return out


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------

def _pool2d(x, kernel_size, stride, padding, init, op, norm=False, ceil_mode=False):
    ks = _pair(kernel_size)
    st = _pair(stride if stride is not None else kernel_size)
    pd = _conv_padding(padding, ks, st, (1, 1), 2)
    if isinstance(pd, str):
        pad_cfg = pd
    else:
        pad_cfg = [(0, 0), (0, 0)] + list(pd)

    def fn(a):
        window = (1, 1) + ks
        strides = (1, 1) + st
        out = jax.lax.reduce_window(
            a, init, op, window, strides,
            padding=pad_cfg if isinstance(pad_cfg, str) else pad_cfg,
        )
        if norm:
            ones = jnp.ones_like(a)
            cnt = jax.lax.reduce_window(
                ones, 0.0, jax.lax.add, window, strides,
                padding=pad_cfg if isinstance(pad_cfg, str) else pad_cfg,
            )
            out = out / cnt
        return out

    return fn


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               data_format="NCHW"):
    x = as_tensor(x)
    fn = _pool2d(x, kernel_size, stride, padding, -jnp.inf, jax.lax.max)
    return apply("max_pool2d", fn, x)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               count_include_pad=True, data_format="NCHW"):
    x = as_tensor(x)
    if count_include_pad:
        ks = _pair(kernel_size)
        scale = 1.0 / (ks[0] * ks[1])
        raw = _pool2d(x, kernel_size, stride, padding, 0.0, jax.lax.add)
        return apply("avg_pool2d", lambda a: raw(a) * scale, x)
    fn = _pool2d(x, kernel_size, stride, padding, 0.0, jax.lax.add, norm=True)
    return apply("avg_pool2d", fn, x)


def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False):
    x = as_tensor(x)
    ks = int(kernel_size) if not isinstance(kernel_size, (list, tuple)) else int(kernel_size[0])
    st = ks if stride is None else (int(stride) if not isinstance(stride, (list, tuple)) else int(stride[0]))
    pd = int(padding) if not isinstance(padding, (list, tuple)) else int(padding[0])

    def fn(a):
        return jax.lax.reduce_window(
            a, -jnp.inf, jax.lax.max, (1, 1, ks), (1, 1, st),
            padding=[(0, 0), (0, 0), (pd, pd)],
        )

    return apply("max_pool1d", fn, x)


def avg_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False):
    x = as_tensor(x)
    ks = int(kernel_size) if not isinstance(kernel_size, (list, tuple)) else int(kernel_size[0])
    st = ks if stride is None else (int(stride) if not isinstance(stride, (list, tuple)) else int(stride[0]))
    pd = int(padding) if not isinstance(padding, (list, tuple)) else int(padding[0])

    def fn(a):
        s = jax.lax.reduce_window(
            a, 0.0, jax.lax.add, (1, 1, ks), (1, 1, st),
            padding=[(0, 0), (0, 0), (pd, pd)],
        )
        return s / ks

    return apply("avg_pool1d", fn, x)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    x = as_tensor(x)
    oh, ow = _pair(output_size)
    H, W = x.shape[2], x.shape[3]
    if H % oh == 0 and W % ow == 0:
        kh, kw = H // oh, W // ow

        def fn(a):
            n, c = a.shape[0], a.shape[1]
            a = a.reshape(n, c, oh, kh, ow, kw)
            return a.mean(axis=(3, 5))

        return apply("adaptive_avg_pool2d", fn, x)
    raise NotImplementedError("adaptive pool with non-divisible sizes")


def adaptive_max_pool2d(x, output_size, data_format="NCHW"):
    x = as_tensor(x)
    oh, ow = _pair(output_size)
    H, W = x.shape[2], x.shape[3]
    if H % oh == 0 and W % ow == 0:
        kh, kw = H // oh, W // ow

        def fn(a):
            n, c = a.shape[0], a.shape[1]
            a = a.reshape(n, c, oh, kh, ow, kw)
            return a.max(axis=(3, 5))

        return apply("adaptive_max_pool2d", fn, x)
    raise NotImplementedError("adaptive pool with non-divisible sizes")


def global_avg_pool2d(x):
    x = as_tensor(x)
    return apply("global_avg_pool2d", lambda a: a.mean(axis=(2, 3), keepdims=True), x)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW"):
    """BatchNorm. Analog of phi BatchNormKernel
    (paddle/phi/kernels/batch_norm_kernel.h). Running stats are updated
    in-place on the Tensor objects in training mode (eager semantics)."""
    x = as_tensor(x)
    c_axis = 1 if data_format == "NCHW" else x.ndim - 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != c_axis)
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]
    bshape = tuple(bshape)

    if training:
        def fn(a, *wb):
            # E[x^2] - m^2 instead of a.var(): both reductions fuse into
            # ONE pass over the activation (a.var needs the mean first,
            # i.e. a second full read) — BN traffic is the dominant cost
            # of conv nets on TPU (profiled: elementwise/reduce fusions
            # dwarf the convs on resnet50). Stats accumulate in f32 (the
            # convert fuses into the reduction read; bf16 m2-m^2 loses to
            # cancellation) and var clamps at 0.
            af = a.astype(jnp.float32)
            mean32 = af.mean(axis=reduce_axes)
            m2 = (af * af).mean(axis=reduce_axes)
            var32 = jnp.maximum(m2 - mean32 * mean32, 0.0)
            mean = mean32.astype(a.dtype)
            var = var32.astype(a.dtype)
            inv = jax.lax.rsqrt(var32.reshape(bshape) + epsilon) \
                .astype(a.dtype)
            out = (a - mean.reshape(bshape)) * inv
            i = 0
            if weight is not None:
                out = out * wb[i].reshape(bshape)
                i += 1
            if bias is not None:
                out = out + wb[i].reshape(bshape)
            return out, mean, var

        ins = [x]
        if weight is not None:
            ins.append(as_tensor(weight))
        if bias is not None:
            ins.append(as_tensor(bias))
        out, mean, var = apply("batch_norm", fn, *ins)

        # update running stats (stop-gradient side effect)
        if running_mean is not None:
            rm = running_mean._array if isinstance(running_mean, Tensor) else running_mean
            rv = running_var._array if isinstance(running_var, Tensor) else running_var
            n = float(np.prod([x.shape[i] for i in reduce_axes]))
            unbiased = var._array * (n / max(n - 1.0, 1.0))
            running_mean._array = momentum * rm + (1 - momentum) * jax.lax.stop_gradient(mean._array)
            running_var._array = momentum * rv + (1 - momentum) * jax.lax.stop_gradient(unbiased)
        return out

    rm = running_mean._array if isinstance(running_mean, Tensor) else jnp.asarray(running_mean)
    rv = running_var._array if isinstance(running_var, Tensor) else jnp.asarray(running_var)

    def infer_fn(a, *wb):
        inv = jax.lax.rsqrt(rv.reshape(bshape) + epsilon)
        out = (a - rm.reshape(bshape)) * inv
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        return out

    ins = [x]
    if weight is not None:
        ins.append(as_tensor(weight))
    if bias is not None:
        ins.append(as_tensor(bias))
    return apply("batch_norm_infer", infer_fn, *ins)


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5):
    """LayerNorm over trailing dims. Analog of phi LayerNormKernel; computed
    in fp32 for bf16 inputs (TPU numerics best practice)."""
    x = as_tensor(x)
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    naxes = tuple(range(x.ndim - len(normalized_shape), x.ndim))

    def fn(a, *wb):
        orig = a.dtype
        af = a.astype(jnp.float32) if a.dtype in (jnp.bfloat16, jnp.float16) else a
        mean = af.mean(axis=naxes, keepdims=True)
        var = af.var(axis=naxes, keepdims=True)
        out = (af - mean) * jax.lax.rsqrt(var + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i].astype(out.dtype)
            i += 1
        if bias is not None:
            out = out + wb[i].astype(out.dtype)
        return out.astype(orig)

    ins = [x]
    if weight is not None:
        ins.append(as_tensor(weight))
    if bias is not None:
        ins.append(as_tensor(bias))
    return apply("layer_norm", fn, *ins)


def rms_norm(x, weight=None, epsilon=1e-6):
    """RMSNorm (no reference analog in v2.4 — modern LLM staple)."""
    x = as_tensor(x)

    def fn(a, *w):
        orig = a.dtype
        af = a.astype(jnp.float32) if a.dtype in (jnp.bfloat16, jnp.float16) else a
        ms = jnp.mean(jnp.square(af), axis=-1, keepdims=True)
        out = af * jax.lax.rsqrt(ms + epsilon)
        if w:
            out = out * w[0].astype(out.dtype)
        return out.astype(orig)

    ins = [x] + ([as_tensor(weight)] if weight is not None else [])
    return apply("rms_norm", fn, *ins)


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW"):
    x = as_tensor(x)
    C = x.shape[1]

    def fn(a, *wb):
        n = a.shape[0]
        g = num_groups
        rest = a.shape[2:]
        a2 = a.reshape(n, g, C // g, *rest)
        axes = tuple(range(2, a2.ndim))
        mean = a2.mean(axis=axes, keepdims=True)
        var = a2.var(axis=axes, keepdims=True)
        out = ((a2 - mean) * jax.lax.rsqrt(var + epsilon)).reshape(a.shape)
        bshape = (1, C) + (1,) * (a.ndim - 2)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        return out

    ins = [x]
    if weight is not None:
        ins.append(as_tensor(weight))
    if bias is not None:
        ins.append(as_tensor(bias))
    return apply("group_norm", fn, *ins)


def instance_norm(x, weight=None, bias=None, epsilon=1e-5):
    x = as_tensor(x)
    axes = tuple(range(2, x.ndim))
    C = x.shape[1]

    def fn(a, *wb):
        mean = a.mean(axis=axes, keepdims=True)
        var = a.var(axis=axes, keepdims=True)
        out = (a - mean) * jax.lax.rsqrt(var + epsilon)
        bshape = (1, C) + (1,) * (a.ndim - 2)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        return out

    ins = [x]
    if weight is not None:
        ins.append(as_tensor(weight))
    if bias is not None:
        ins.append(as_tensor(bias))
    return apply("instance_norm", fn, *ins)


# ---------------------------------------------------------------------------
# embedding / dropout
# ---------------------------------------------------------------------------

def embedding(ids, weight, padding_idx=None, sparse=False):
    """Embedding lookup. Analog of phi EmbeddingKernel
    (paddle/phi/kernels/embedding_kernel.h). The backward is a dense
    scatter-add (XLA turns it into an efficient segment-sum on TPU);
    SelectedRows-style sparse grads are intentionally not replicated —
    under SPMD the all-to-all embedding path in distributed/ covers the
    sparse scale-out case."""
    ids_t = as_tensor(ids)
    weight = as_tensor(weight)
    idx = ids_t._array

    def fn(w):
        out = jnp.take(w, idx, axis=0)
        if padding_idx is not None:
            mask = (idx == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out

    return apply("embedding", fn, weight)


_dropout_trace_warned = False


def _warn_if_constant_key(arr, opname):
    """One-time warning shared by every op that draws a PRNG key at
    trace time: outside a key scope the key is baked as a constant and
    every execution reuses the same mask/noise."""
    global _dropout_trace_warned
    if isinstance(arr, jax.core.Tracer) and not random_mod.in_key_scope():
        if not _dropout_trace_warned:
            import warnings

            warnings.warn(
                f"{opname} traced with a constant PRNG key: every "
                "execution of this compiled function will reuse the SAME "
                "random draw. Use jit.TrainStep (which threads a per-step "
                "key) or wrap the call in "
                "paddle_tpu.core.random.key_scope(key).")
            _dropout_trace_warned = True


def dropout(x, p=0.5, training=True, mode="upscale_in_train", axis=None):
    """Dropout. Analog of phi DropoutKernel. RNG comes from the global
    Generator key chain (core/random.py); inside a compiled step the key
    derives from the step's traced key (random.key_scope) so every step
    gets a fresh mask. Tracing dropout OUTSIDE a key scope would bake a
    constant key (identical mask every step) — warn loudly."""
    x = as_tensor(x)
    if not training or p == 0.0:
        return x
    _warn_if_constant_key(x._array, "dropout")
    key = next_key()
    keep = 1.0 - p

    def fn(a):
        shape = a.shape if axis is None else tuple(
            a.shape[i] if i in (axis if isinstance(axis, (list, tuple)) else [axis]) else 1
            for i in range(a.ndim)
        )
        mask = jax.random.bernoulli(key, keep, shape)
        if mode == "upscale_in_train":
            return jnp.where(mask, a / keep, 0.0).astype(a.dtype)
        return jnp.where(mask, a, 0.0).astype(a.dtype)

    return apply("dropout", fn, x)


def dropout2d(x, p=0.5, training=True, data_format="NCHW"):
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p, training, axis=axis)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def softmax_with_cross_entropy(logits, label, soft_label=False, axis=-1,
                               ignore_index=-100):
    """Fused softmax+CE. Analog of phi CrossEntropyWithSoftmaxKernel
    (paddle/phi/kernels/cross_entropy_kernel.h) and the mp variant
    _c_softmax_with_cross_entropy (mp_ops.py:375)."""
    logits = as_tensor(logits)
    if soft_label:
        label_t = as_tensor(label)

        def fn(lg, lb):
            logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=axis)
            return -jnp.sum(lb * logp, axis=axis, keepdims=True)

        return apply("softmax_ce_soft", fn, logits, label_t)

    lab = label._array if isinstance(label, Tensor) else jnp.asarray(label)
    if lab.ndim == logits.ndim and lab.shape[axis] == 1:
        lab = jnp.squeeze(lab, axis)

    def fn(lg):
        logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=axis)
        idx = jnp.expand_dims(lab, axis).astype(jnp.int32)
        mask = idx != ignore_index
        ll = jnp.take_along_axis(logp, jnp.where(mask, idx, 0), axis=axis)
        loss = jnp.where(mask, -ll, 0.0)
        return loss.astype(lg.dtype)

    return apply("softmax_ce", fn, logits)


def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean",
                  soft_label=False, axis=-1, use_softmax=True, label_smoothing=0.0):
    """Analog of paddle.nn.functional.cross_entropy
    (python/paddle/nn/functional/loss.py). use_softmax=False means `input`
    is already a probability distribution over `axis` (paddle semantics):
    the loss is plain NLL -log(p[label]) / -sum(label*log(p))."""
    input = as_tensor(input)

    def _hard_labels():
        lab = label._array if isinstance(label, Tensor) else jnp.asarray(label)
        if lab.ndim == input.ndim and lab.shape[axis] == 1:
            lab = jnp.squeeze(lab, axis)
        return lab

    # keep the ORIGINAL hard labels: weight selection and the valid-count
    # must index by them even after label smoothing converts to soft
    hard_lab = None if soft_label else _hard_labels()

    smoothed = label_smoothing > 0.0 and not soft_label
    if smoothed:
        num_classes = input.shape[axis]
        onehot = jax.nn.one_hot(hard_lab, num_classes, dtype=jnp.float32,
                                axis=axis)
        soft = onehot * (1 - label_smoothing) + label_smoothing / num_classes
        label = Tensor._wrap(soft)
        soft_label = True

    if use_softmax:
        loss = softmax_with_cross_entropy(
            input, label, soft_label=soft_label, axis=axis,
            ignore_index=ignore_index)
    else:
        # input is probabilities: NLL without the softmax
        if soft_label:
            label_t = as_tensor(label)
            loss = apply(
                "nll_soft",
                lambda p, lb: -jnp.sum(
                    lb * jnp.log(jnp.maximum(p.astype(jnp.float32), 1e-30)),
                    axis=axis, keepdims=True),
                input, label_t)
        else:
            idx = jnp.expand_dims(hard_lab, axis).astype(jnp.int32)
            mask = idx != ignore_index

            def fn(p):
                logp = jnp.log(jnp.maximum(p.astype(jnp.float32), 1e-30))
                ll = jnp.take_along_axis(logp, jnp.where(mask, idx, 0),
                                         axis=axis)
                return jnp.where(mask, -ll, 0.0).astype(p.dtype)

            loss = apply("nll_hard", fn, input)

    if smoothed:
        # the soft-CE path has no ignore_index masking: zero ignored rows
        # so the valid-count mean below stays correct
        ig_mask = jnp.expand_dims(hard_lab != ignore_index, axis)
        loss = apply("ce_ignore_mask",
                     lambda l: jnp.where(ig_mask, l, 0.0).astype(l.dtype),
                     loss)

    wsel = None
    if weight is not None:
        if hard_lab is None:
            raise ValueError(
                "weight with soft_label=True is not supported (pass hard "
                "labels, optionally with label_smoothing)")
        w = weight._array if isinstance(weight, Tensor) else jnp.asarray(weight)
        safe_lab = jnp.where(hard_lab == ignore_index, 0, hard_lab)
        wsel = jnp.where(hard_lab == ignore_index, 0.0,
                         jnp.take(w, safe_lab.astype(jnp.int32)))
        loss = apply("ce_weight",
                     lambda l: l * jnp.expand_dims(wsel, axis).astype(l.dtype),
                     loss)

    loss_sq = apply("squeeze_loss", lambda l: jnp.squeeze(l, axis), loss)
    if reduction == "none":
        return loss_sq
    if reduction == "mean" and hard_lab is not None:
        # paddle semantics: mean over non-ignored labels; with class
        # weights the denominator is the sum of selected weights
        if wsel is not None:
            return apply(
                "reduce_loss",
                lambda l: jnp.sum(l) / jnp.maximum(jnp.sum(wsel), 1e-12),
                loss_sq)
        valid = (hard_lab != ignore_index).astype(jnp.float32)
        return apply(
            "reduce_loss",
            lambda l: jnp.sum(l) / jnp.maximum(jnp.sum(valid), 1.0), loss_sq)
    return apply("reduce_loss", lambda l: _reduce_loss(l, reduction), loss_sq)


def mse_loss(input, label, reduction="mean"):
    input, label = as_tensor(input), as_tensor(label)
    return apply(
        "mse_loss", lambda a, b: _reduce_loss(jnp.square(a - b), reduction), input, label
    )


def l1_loss(input, label, reduction="mean"):
    input, label = as_tensor(input), as_tensor(label)
    return apply(
        "l1_loss", lambda a, b: _reduce_loss(jnp.abs(a - b), reduction), input, label
    )


def smooth_l1_loss(input, label, reduction="mean", delta=1.0):
    input, label = as_tensor(input), as_tensor(label)

    def fn(a, b):
        d = jnp.abs(a - b)
        loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
        return _reduce_loss(loss, reduction)

    return apply("smooth_l1", fn, input, label)


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean"):
    """NLL over log-probs; class axis is 1, input may be [N,C] or
    [N,C,d1,...] with label [N] / [N,d1,...] (paddle semantics)."""
    input = as_tensor(input)
    lab = label._array if isinstance(label, Tensor) else jnp.asarray(label)

    def fn(a):
        idx = jnp.expand_dims(lab, 1).astype(jnp.int32)  # [N,1,d1,...]
        mask = idx != ignore_index
        ll = jnp.take_along_axis(a, jnp.where(mask, idx, 0), axis=1)
        loss = jnp.squeeze(jnp.where(mask, -ll, 0.0), 1)
        valid = jnp.squeeze(mask, 1)
        if weight is not None:
            w = weight._array if isinstance(weight, Tensor) else jnp.asarray(weight)
            wsel = jnp.take(w, jnp.where(lab == ignore_index, 0, lab).astype(jnp.int32))
            wsel = jnp.where(valid, wsel, 0.0)
            loss = loss * wsel
            if reduction == "mean":
                return jnp.sum(loss) / jnp.maximum(jnp.sum(wsel), 1e-12)
        elif reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(
                jnp.sum(valid.astype(loss.dtype)), 1.0)
        return _reduce_loss(loss, reduction)

    return apply("nll_loss", fn, input)


def bce_loss(input, label, weight=None, reduction="mean"):
    input, label = as_tensor(input), as_tensor(label)

    def fn(a, b):
        eps = 1e-12
        loss = -(b * jnp.log(a + eps) + (1 - b) * jnp.log(1 - a + eps))
        if weight is not None:
            loss = loss * (weight._array if isinstance(weight, Tensor) else weight)
        return _reduce_loss(loss, reduction)

    return apply("bce_loss", fn, input, label)


def bce_with_logits(logit, label, weight=None, reduction="mean", pos_weight=None):
    logit, label = as_tensor(logit), as_tensor(label)

    def fn(a, b):
        mx = jnp.maximum(a, 0)
        loss = mx - a * b + jnp.log1p(jnp.exp(-jnp.abs(a)))
        if pos_weight is not None:
            pw = pos_weight._array if isinstance(pos_weight, Tensor) else pos_weight
            loss = loss * (b * (pw - 1) + 1)
        if weight is not None:
            loss = loss * (weight._array if isinstance(weight, Tensor) else weight)
        return _reduce_loss(loss, reduction)

    return apply("bce_logits", fn, logit, label)


def kl_div(input, label, reduction="mean"):
    input, label = as_tensor(input), as_tensor(label)

    def fn(a, b):
        loss = b * (jnp.log(jnp.maximum(b, 1e-12)) - a)
        return _reduce_loss(loss, reduction)

    return apply("kl_div", fn, input, label)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    x1, x2 = as_tensor(x1), as_tensor(x2)

    def fn(a, b):
        num = jnp.sum(a * b, axis=axis)
        den = jnp.linalg.norm(a, axis=axis) * jnp.linalg.norm(b, axis=axis)
        return num / jnp.maximum(den, eps)

    return apply("cosine_similarity", fn, x1, x2)


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean"):
    input, other, label = as_tensor(input), as_tensor(other), as_tensor(label)

    def fn(a, b, l):
        return _reduce_loss(jnp.maximum(0.0, -l * (a - b) + margin), reduction)

    return apply("margin_ranking", fn, input, other, label)


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean"):
    input, label = as_tensor(input), as_tensor(label)

    def fn(a, l):
        loss = jnp.where(l == 1.0, a, jnp.maximum(0.0, margin - a))
        return _reduce_loss(loss, reduction)

    return apply("hinge_embedding", fn, input, label)


def label_smooth(label, prior_dist=None, epsilon=0.1):
    label = as_tensor(label)
    k = label.shape[-1]

    def fn(l):
        if prior_dist is not None:
            pd = prior_dist._array if isinstance(prior_dist, Tensor) else jnp.asarray(prior_dist)
            return (1 - epsilon) * l + epsilon * pd
        return (1 - epsilon) * l + epsilon / k

    return apply("label_smooth", fn, label)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, scale=None):
    """Plain-XLA attention used as reference/fallback; the Pallas flash
    kernel lives in paddle_tpu/ops/pallas/flash_attention.py and is
    selected by nn.MultiHeadAttention for long sequences. Analog of the
    reference's fused_attention (operators/fused/fused_attention_op.cu,
    fmha_ref.h). Layout: [batch, seq, heads, head_dim] (paddle layout)."""
    q, k, v = as_tensor(q), as_tensor(k), as_tensor(v)
    mask_arr = attn_mask._array if isinstance(attn_mask, Tensor) else attn_mask

    # flash path: no explicit mask/dropout. flash_attention is the single
    # source of truth for routing — it checks backend + shapes internally
    # and falls back to dense XLA attention (with a logged warning, and a
    # bottom-right-aligned causal mask for Sq != Skv) when the pallas
    # kernel can't be used.
    if mask_arr is None and dropout_p == 0.0:
        from .pallas.flash_attention import flash_attention

        return apply("flash_attention",
                     lambda qa, ka, va: flash_attention(
                         qa, ka, va, causal=is_causal, scale=scale),
                     q, k, v)

    # key-only additive mask (the encoder padding mask, [B,1,1,S]):
    # the fused short-seq kernel takes it natively, so padded BERT
    # fine-tunes keep the fast path instead of falling to dense
    if (mask_arr is not None and dropout_p == 0.0 and not is_causal
            and getattr(mask_arr, "ndim", 0) == 4
            and mask_arr.shape[1] == 1 and mask_arr.shape[2] == 1):
        from paddle_tpu.core.device import on_tpu

        from .pallas.flash_attention import (_shapes_ok_for_shortseq,
                                             shortseq_attention)

        Sq, Skv, D = q.shape[1], k.shape[1], q.shape[3]
        if on_tpu() and _shapes_ok_for_shortseq(Sq, Skv, D) and \
                mask_arr.shape[0] in (1, q.shape[0]) and \
                mask_arr.shape[3] == Skv:
            km = jnp.broadcast_to(
                jnp.asarray(mask_arr)[:, 0, 0, :],
                (q.shape[0], Skv))
            return apply(
                "flash_attention_keymask",
                lambda qa, ka, va: shortseq_attention(
                    qa, ka, va, scale=scale, key_mask=km),
                q, k, v)

    def fn(qa, ka, va):
        d = qa.shape[-1]
        s = scale if scale is not None else 1.0 / np.sqrt(d)
        # [B,S,H,D] -> [B,H,S,D]
        qh = jnp.swapaxes(qa, 1, 2)
        kh = jnp.swapaxes(ka, 1, 2)
        vh = jnp.swapaxes(va, 1, 2)
        logits = jnp.einsum(
            "bhqd,bhkd->bhqk", qh, kh, preferred_element_type=jnp.float32
        ) * s
        if is_causal:
            S, T = logits.shape[-2], logits.shape[-1]
            # bottom-right aligned for Sq != Skv (KV-cache continuation)
            cmask = jnp.tril(jnp.ones((S, T), bool), T - S)
            logits = jnp.where(cmask, logits, -1e30)
        if mask_arr is not None:
            logits = logits + mask_arr.astype(logits.dtype)
        probs = jax.nn.softmax(logits, axis=-1).astype(qa.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
        return jnp.swapaxes(out, 1, 2)

    out = apply("sdpa", fn, q, k, v)
    if dropout_p > 0.0 and training:
        out = dropout(out, dropout_p, training=True)
    return out


# ---------------------------------------------------------------------------
# vision misc
# ---------------------------------------------------------------------------

def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, data_format="NCHW"):
    x = as_tensor(x)
    H, W = x.shape[2], x.shape[3]
    if size is None:
        sf = scale_factor if isinstance(scale_factor, (list, tuple)) else (scale_factor,) * 2
        size = (int(H * sf[0]), int(W * sf[1]))
    size = tuple(int(s) for s in size)
    method = {"nearest": "nearest", "bilinear": "linear", "bicubic": "cubic"}[mode]

    def fn(a):
        n, c = a.shape[0], a.shape[1]
        if align_corners and mode != "nearest" and size[0] > 1 and size[1] > 1:
            # align_corners=True: in = o*(H-1)/(out-1). scale_and_translate
            # samples in = (o + 0.5 - t)/s - 0.5, so s=(out-1)/(H-1) and
            # t = 0.5*(1-s) makes corners map to corners exactly.
            s = jnp.asarray(
                [(size[0] - 1) / (H - 1), (size[1] - 1) / (W - 1)], jnp.float32)
            t = 0.5 * (1.0 - s)
            return jax.image.scale_and_translate(
                a, (n, c) + size, spatial_dims=(2, 3),
                scale=s, translation=t, method=method)
        return jax.image.resize(a, (n, c) + size, method=method)

    return apply("interpolate", fn, x)


def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    x = as_tensor(x)
    r = int(upscale_factor)

    def fn(a):
        n, c, h, w = a.shape
        a = a.reshape(n, c // (r * r), r, r, h, w)
        a = jnp.transpose(a, (0, 1, 4, 2, 5, 3))
        return a.reshape(n, c // (r * r), h * r, w * r)

    return apply("pixel_shuffle", fn, x)


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW"):
    x = as_tensor(x)

    def fn(a):
        nt, c, h, w = a.shape
        n = nt // seg_num
        a = a.reshape(n, seg_num, c, h, w)
        fold = int(c * shift_ratio)
        left = jnp.concatenate([a[:, 1:, :fold], jnp.zeros_like(a[:, :1, :fold])], axis=1)
        right = jnp.concatenate([jnp.zeros_like(a[:, :1, fold:2 * fold]), a[:, :-1, fold:2 * fold]], axis=1)
        rest = a[:, :, 2 * fold:]
        out = jnp.concatenate([left, right, rest], axis=2)
        return out.reshape(nt, c, h, w)

    return apply("temporal_shift", fn, x)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    x = as_tensor(x)
    ks = _pair(kernel_sizes)
    st = _pair(strides)
    pd = _pair(paddings)
    dl = _pair(dilations)

    def fn(a):
        n, c, h, w = a.shape
        patches = jax.lax.conv_general_dilated_patches(
            a, ks, st, [(pd[0], pd[0]), (pd[1], pd[1])],
            rhs_dilation=dl, dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )
        return patches.reshape(n, c * ks[0] * ks[1], -1)

    return apply("unfold", fn, x)


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros", align_corners=True):
    x, grid = as_tensor(x), as_tensor(grid)

    def fn(a, g):
        n, c, h, w = a.shape
        gx = (g[..., 0] + 1) * (w - 1) / 2 if align_corners else ((g[..., 0] + 1) * w - 1) / 2
        gy = (g[..., 1] + 1) * (h - 1) / 2 if align_corners else ((g[..., 1] + 1) * h - 1) / 2
        x0 = jnp.floor(gx)
        y0 = jnp.floor(gy)
        x1, y1 = x0 + 1, y0 + 1

        def sample(yy, xx):
            mask = (yy >= 0) & (yy < h) & (xx >= 0) & (xx < w)
            yc = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
            xc = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
            v = a[jnp.arange(n)[:, None, None], :, yc, xc]  # [n,H,W,c]
            return jnp.where(mask[..., None], v, 0.0)

        wa = (x1 - gx) * (y1 - gy)
        wb = (gx - x0) * (y1 - gy)
        wc = (x1 - gx) * (gy - y0)
        wd = (gx - x0) * (gy - y0)
        out = (sample(y0, x0) * wa[..., None] + sample(y0, x1) * wb[..., None]
               + sample(y1, x0) * wc[..., None] + sample(y1, x1) * wd[..., None])
        return jnp.transpose(out, (0, 3, 1, 2))

    return apply("grid_sample", fn, x, grid)


def affine_grid(theta, out_shape, align_corners=True):
    theta = as_tensor(theta)
    n, c, h, w = [int(s) for s in out_shape]

    def fn(th):
        if align_corners:
            ys = jnp.linspace(-1, 1, h)
            xs = jnp.linspace(-1, 1, w)
        else:
            ys = (jnp.arange(h) + 0.5) / h * 2 - 1
            xs = (jnp.arange(w) + 0.5) / w * 2 - 1
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx, gy, ones], axis=-1)  # [h,w,3]
        return jnp.einsum("nij,hwj->nhwi", th, base)

    return apply("affine_grid", fn, theta)


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None, ln_scale=None,
                                           ln_bias=None, dropout_rate=0.0,
                                           epsilon=1e-5, training=True):
    """Analog of operators/fused/fused_bias_dropout_residual_layer_norm — on
    TPU it's one jax fn; XLA fuses the whole chain."""
    x, residual = as_tensor(x), as_tensor(residual)
    key = next_key() if (dropout_rate > 0.0 and training) else None

    def fn(a, r, *rest):
        i = 0
        if bias is not None:
            a = a + rest[i]
            i += 1
        if key is not None:
            keep = 1.0 - dropout_rate
            mask = jax.random.bernoulli(key, keep, a.shape)
            a = jnp.where(mask, a / keep, 0.0)
        out = a + r
        mean = out.mean(axis=-1, keepdims=True)
        var = out.var(axis=-1, keepdims=True)
        y = (out - mean) * jax.lax.rsqrt(var + epsilon)
        if ln_scale is not None:
            y = y * rest[i]
            i += 1
        if ln_bias is not None:
            y = y + rest[i]
        return y

    ins = [x, residual]
    for p in (bias, ln_scale, ln_bias):
        if p is not None:
            ins.append(as_tensor(p))
    return apply("fused_bias_dropout_residual_ln", fn, *ins)


def _triple(v):
    return tuple(v) if isinstance(v, (list, tuple)) else (v, v, v)


def _pool3d_fn(kernel_size, stride, padding, init, op, norm=False,
               count_include_pad=True, divisor_override=None):
    ks = _triple(kernel_size)
    st = _triple(stride if stride is not None else kernel_size)
    pd = _triple(padding)
    pad_cfg = [(0, 0), (0, 0)] + [(p, p) for p in pd]

    def fn(a):
        window = (1, 1) + ks
        strides = (1, 1) + st
        out = jax.lax.reduce_window(a, init, op, window, strides,
                                    padding=pad_cfg)
        if divisor_override is not None:
            out = out / float(divisor_override)
        elif norm:
            cnt = jax.lax.reduce_window(jnp.ones_like(a), 0.0, jax.lax.add,
                                        window, strides, padding=pad_cfg)
            out = out / cnt
        elif op is jax.lax.add:
            out = out / float(np.prod(ks))
        return out

    return fn


def _check_pool3d_args(ceil_mode, data_format, return_mask=False):
    """Unsupported pool3d modes fail loudly instead of silently
    computing the wrong thing."""
    if ceil_mode:
        raise NotImplementedError("pool3d: ceil_mode=True not supported")
    if data_format != "NCDHW":
        raise NotImplementedError(
            f"pool3d: data_format={data_format!r}; NCDHW only")
    if return_mask:
        raise NotImplementedError("pool3d: return_mask not supported")


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCDHW"):
    """MaxPool3D (phi pool3d kernel analog); x [B,C,D,H,W]."""
    _check_pool3d_args(ceil_mode, data_format, return_mask)
    x = as_tensor(x)
    return apply("max_pool3d",
                 _pool3d_fn(kernel_size, stride, padding, -jnp.inf,
                            jax.lax.max), x)


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW"):
    _check_pool3d_args(ceil_mode, data_format)
    if divisor_override is not None and divisor_override <= 0:
        raise ValueError("divisor_override must be positive")
    x = as_tensor(x)
    return apply("avg_pool3d",
                 _pool3d_fn(kernel_size, stride, padding, 0.0, jax.lax.add,
                            norm=exclusive,
                            divisor_override=divisor_override), x)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col (phi unfold kernel analog): x [B,C,H,W] ->
    [B, C*kh*kw, L] with L = Ho*Wo. Built on
    conv_general_dilated_patches (one XLA gather, MXU-adjacent layout),
    whose blocks are already channel-major (c, kh, kw) — the same
    order paddle emits, so no reorder is needed (verified against a
    manual im2col in tests)."""
    x = as_tensor(x)
    ks = _pair(kernel_sizes)
    st = _pair(strides)
    pd = _pair(paddings)
    dl = _pair(dilations)

    def fn(a):
        # precision=HIGHEST: the patch extraction is pure data movement
        # (one-hot kernel) — default bf16 MXU precision would quantize
        # the activations, whereas the reference's im2col is exact
        p = jax.lax.conv_general_dilated_patches(
            a, ks, st, [(pd[0], pd[0]), (pd[1], pd[1])],
            rhs_dilation=dl,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=jax.lax.Precision.HIGHEST)
        # p: [B, C*kh*kw, Ho, Wo] with channel-major blocks already
        B, CK, Ho, Wo = p.shape
        return p.reshape(B, CK, Ho * Wo)

    return apply("unfold", fn, x)


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1,
         name=None):
    """col2im (phi fold kernel analog): [B, C*kh*kw, L] -> [B,C,H,W],
    overlapping patches summed. Implemented as the exact transpose of
    unfold via the VJP of the patch extraction (adjoint-of-gather —
    the XLA-native formulation of the reference's scatter kernel)."""
    x = as_tensor(x)
    oh, ow = _pair(output_sizes)
    ks = _pair(kernel_sizes)
    st = _pair(strides)
    pd = _pair(paddings)
    dl = _pair(dilations)

    def fn(a):
        B = a.shape[0]
        C = a.shape[1] // (ks[0] * ks[1])

        def extract(img):
            # HIGHEST precision for the same exactness reason as unfold
            # (the vjp of an exact gather is an exact scatter-add)
            p = jax.lax.conv_general_dilated_patches(
                img, ks, st, [(pd[0], pd[0]), (pd[1], pd[1])],
                rhs_dilation=dl,
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                precision=jax.lax.Precision.HIGHEST)
            return p.reshape(B, p.shape[1], -1)

        zeros = jnp.zeros((B, C, oh, ow), a.dtype)
        _, vjp = jax.vjp(extract, zeros)
        (out,) = vjp(a)
        return out

    return apply("fold", fn, x)


# ---------------------------------------------------------------------------
# functional-surface completion (losses + misc; python/paddle/nn/functional/)
# ---------------------------------------------------------------------------

def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    """L_p normalize along `axis` (functional/norm.py normalize)."""
    x = as_tensor(x)

    def fn(a):
        n = jnp.sum(jnp.abs(a) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return a / jnp.maximum(n, epsilon)

    return apply("normalize", fn, x)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    if data_format != "NCHW":
        raise NotImplementedError(
            f"local_response_norm: data_format={data_format!r}; NCHW only")
    x = as_tensor(x)

    def fn(a):
        half = size // 2
        summed = jax.lax.reduce_window(
            jnp.square(a), 0.0, jax.lax.add, (1, size, 1, 1), (1, 1, 1, 1),
            padding=[(0, 0), (half, size - 1 - half), (0, 0), (0, 0)])
        # paddle divides the window sum by size (avg-pool formulation)
        return a / jnp.power(k + alpha * summed / size, beta)

    return apply("local_response_norm", fn, x)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    """Channel-wise dropout for 5-D inputs (whole [D,H,W] blocks) —
    dropout2d's pattern, one more spatial dim."""
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p, training, axis=axis)


def alpha_dropout(x, p=0.5, training=True, name=None):
    """SELU-preserving dropout (functional/common.py alpha_dropout):
    dropped units take the negative saturation value alpha' and the
    output is affinely rescaled a*x+b with
    a = ((1-p)(1 + p*alpha'^2))^-1/2 (Klambauer et al. 2017, keeps
    zero mean / unit variance under SELU statistics)."""
    x = as_tensor(x)
    if not training or p == 0.0:
        return x
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    a_coef = ((1.0 - p) * (1.0 + p * alpha_p ** 2)) ** -0.5
    b_coef = -a_coef * p * alpha_p
    _warn_if_constant_key(x._array, "alpha_dropout")
    key = random_mod.next_key()

    def fn(t):
        keep = jax.random.bernoulli(key, 1.0 - p, t.shape)
        return (a_coef * jnp.where(keep, t, alpha_p) + b_coef) \
            .astype(t.dtype)

    return apply("alpha_dropout", fn, x)


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    """Inverse of pixel_shuffle: [B,C,H,W] -> [B,C*r^2,H/r,W/r]."""
    x = as_tensor(x)
    r = int(downscale_factor)
    nhwc = data_format == "NHWC"

    def fn(a):
        if nhwc:
            a = a.transpose(0, 3, 1, 2)
        B, C, H, W = a.shape
        a = a.reshape(B, C, H // r, r, W // r, r)
        out = a.transpose(0, 1, 3, 5, 2, 4).reshape(
            B, C * r * r, H // r, W // r)
        return out.transpose(0, 2, 3, 1) if nhwc else out

    return apply("pixel_unshuffle", fn, x)


def sequence_mask(lengths, maxlen=None, dtype="bool", name=None):
    """mask[i, t] = t < lengths[i] (functional sequence_mask)."""
    from paddle_tpu.core import dtype as dtypes

    lengths = as_tensor(lengths)
    if maxlen is None and isinstance(lengths._array, jax.core.Tracer):
        raise ValueError(
            "sequence_mask: maxlen is required under jit (the output "
            "shape would depend on traced values)")
    ml = int(maxlen) if maxlen is not None else \
        int(np.asarray(lengths._array).max())
    jd = dtypes.to_jax(dtype)
    return apply_nograd(
        "sequence_mask",
        lambda l: (jnp.arange(ml)[None, :] < l[..., None]).astype(jd),
        lengths)


def square_error_cost(input, label):
    input, label = as_tensor(input), as_tensor(label)
    return apply("square_error_cost", lambda a, b: (a - b) ** 2,
                 input, label)


def log_loss(input, label, epsilon=1e-4, name=None):
    input, label = as_tensor(input), as_tensor(label)
    return apply(
        "log_loss",
        lambda p, y: -y * jnp.log(p + epsilon) -
        (1.0 - y) * jnp.log(1.0 - p + epsilon), input, label)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25,
                       gamma=2.0, reduction="sum", name=None):
    """Focal loss on logits (functional/loss.py sigmoid_focal_loss)."""
    logit, label = as_tensor(logit), as_tensor(label)
    norm_arr = None if normalizer is None else as_tensor(normalizer)

    def fn(z, y, *rest):
        p = jax.nn.sigmoid(z)
        ce = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        loss = a_t * ((1 - p_t) ** gamma) * ce
        if rest:
            loss = loss / rest[0]
        return _reduce_loss(loss, reduction)

    args = (logit, label) + ((norm_arr,) if norm_arr is not None else ())
    return apply("sigmoid_focal_loss", fn, *args)


def dice_loss(input, label, epsilon=1e-5, name=None):
    """1 - Dice coefficient over the trailing class dim
    (functional/loss.py dice_loss): input [N,...,C] probs, label
    [N,...,1] int."""
    input = as_tensor(input)
    label = as_tensor(label)

    def fn(p, y):
        C = p.shape[-1]
        oh = jax.nn.one_hot(y.squeeze(-1), C, dtype=p.dtype)
        red = tuple(range(1, p.ndim))
        inter = jnp.sum(p * oh, axis=red)
        union = jnp.sum(p, axis=red) + jnp.sum(oh, axis=red)
        return jnp.mean(1.0 - (2.0 * inter + epsilon) / (union + epsilon))

    return apply("dice_loss", fn, input, label)


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    """Multi-class n-pair loss (functional/loss.py npair_loss)."""
    anchor, positive = as_tensor(anchor), as_tensor(positive)
    labels = as_tensor(labels)

    def fn(a, p, y):
        sim = a @ p.T  # [B,B]
        same = (y[:, None] == y[None, :]).astype(a.dtype)
        same = same / same.sum(axis=1, keepdims=True)
        xent = jnp.mean(jnp.sum(
            -same * jax.nn.log_softmax(sim, axis=1), axis=1))
        # reference weights the l2 term by 0.25
        reg = l2_reg * (jnp.mean(jnp.sum(a * a, axis=1)) +
                        jnp.mean(jnp.sum(p * p, axis=1))) * 0.25
        return xent + reg

    return apply("npair_loss", fn, anchor, positive, labels)


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean",
                        name=None):
    input, positive, negative = (as_tensor(input), as_tensor(positive),
                                 as_tensor(negative))

    def fn(a, pos, neg):
        # epsilon once per distance (numerical floor), not per element —
        # per-element would scale the "zero" distance with the feature dim
        dist = lambda u, v: (jnp.sum(jnp.abs(u - v) ** p, axis=-1)
                             + epsilon) ** (1 / p)
        dp = dist(a, pos)
        dn = dist(a, neg)
        if swap:
            dn = jnp.minimum(dn, dist(pos, neg))
        loss = jnp.maximum(dp - dn + margin, 0.0)
        return _reduce_loss(loss, reduction)

    return apply("triplet_margin_loss", fn, input, positive, negative)


def cosine_embedding_loss(input1, input2, label, margin=0.0,
                          reduction="mean", name=None):
    """label=1: pull together (1-cos); label=-1: push below margin."""
    input1, input2, label = (as_tensor(input1), as_tensor(input2),
                             as_tensor(label))

    def fn(a, b, y):
        cos = jnp.sum(a * b, axis=-1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1),
            1e-12)
        loss = jnp.where(y > 0, 1.0 - cos,
                         jnp.maximum(cos - margin, 0.0))
        return _reduce_loss(loss, reduction)

    return apply("cosine_embedding_loss", fn, input1, input2, label)


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, group=None,
                         return_softmax=False, reduction="mean"):
    """ArcFace-family margin softmax (functional margin_cross_entropy):
    logits are cosines; the target class angle gets margins
    cos(m1*θ + m2) - m3 before scaled softmax CE. (The reference's
    model-parallel group sharding is subsumed by running it under a
    pjit step with mp-sharded logits.)"""
    logits, label = as_tensor(logits), as_tensor(label)

    def fn(z, y):
        C = z.shape[-1]
        oh = jax.nn.one_hot(y, C, dtype=z.dtype)
        theta = jnp.arccos(jnp.clip(z, -1.0 + 1e-7, 1.0 - 1e-7))
        target = jnp.cos(margin1 * theta + margin2) - margin3
        adj = z * (1 - oh) + target * oh
        logp = jax.nn.log_softmax(scale * adj, axis=-1)
        loss = _reduce_loss(-jnp.sum(oh * logp, axis=-1), reduction)
        if return_softmax:
            return loss, jnp.exp(logp)
        return loss

    return apply("margin_cross_entropy", fn, logits, label)


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC loss (functional/loss.py ctc_loss; phi warpctc analog) via
    the log-domain forward algorithm as ONE lax.scan over time — the
    TPU-native replacement for warp-ctc's CUDA kernels. log_probs
    [T,B,C] (time-major, like paddle), labels [B,S] int, returns the
    negative log-likelihood per sample (reduced)."""
    log_probs = as_tensor(log_probs)
    labels_t = as_tensor(labels)
    in_len = as_tensor(input_lengths)
    lab_len = as_tensor(label_lengths)

    def fn(lp, lab, T_len, S_len):
        lp = jax.nn.log_softmax(lp, axis=-1)  # idempotent on log-probs
        T, B, C = lp.shape
        S = lab.shape[1]
        L = 2 * S + 1  # blank-interleaved target length
        NEG = -1e30

        # extended labels: blank, l1, blank, l2, ..., blank
        ext = jnp.full((B, L), blank, lab.dtype)
        ext = ext.at[:, 1::2].set(lab)
        # alpha recurrence allows skip (i-2) when ext[i] != ext[i-2]
        # and ext[i] != blank
        ext_prev2 = jnp.pad(ext, ((0, 0), (2, 0)),
                            constant_values=blank)[:, :L]
        can_skip = (ext != blank) & (ext != ext_prev2)

        def emit(t_lp, idx):
            return jnp.take_along_axis(t_lp, idx, axis=-1)

        alpha0 = jnp.full((B, L), NEG)
        alpha0 = alpha0.at[:, 0].set(emit(lp[0], ext[:, :1])[:, 0])
        alpha0 = alpha0.at[:, 1].set(
            jnp.where(S_len > 0, emit(lp[0], ext[:, 1:2])[:, 0], NEG))

        def step(alpha, t_lp):
            a_prev1 = jnp.pad(alpha, ((0, 0), (1, 0)),
                              constant_values=NEG)[:, :L]
            a_prev2 = jnp.pad(alpha, ((0, 0), (2, 0)),
                              constant_values=NEG)[:, :L]
            merged = jnp.logaddexp(alpha, a_prev1)
            merged = jnp.where(can_skip,
                               jnp.logaddexp(merged, a_prev2), merged)
            return merged + emit(t_lp, ext), None

        def body(carry, t):
            alpha, = carry
            new, _ = step(alpha, lp[t])
            # freeze past each sample's input length
            new = jnp.where((t < T_len)[:, None], new, alpha)
            return (new,), None

        (alpha,), _ = jax.lax.scan(body, (alpha0,),
                                   jnp.arange(1, T))
        # NLL = -log(alpha[last blank] + alpha[last label])
        last = 2 * S_len  # index of final blank
        aN = jnp.take_along_axis(alpha, last[:, None], axis=1)[:, 0]
        aN1 = jnp.take_along_axis(
            alpha, jnp.maximum(last - 1, 0)[:, None], axis=1)[:, 0]
        nll = -jnp.logaddexp(aN, jnp.where(S_len > 0, aN1, NEG))
        if norm_by_times:
            nll = nll / T_len.astype(nll.dtype)
        if reduction == "mean":
            # paddle normalizes each sample by its label length first
            return (nll / jnp.maximum(S_len, 1).astype(nll.dtype)).mean()
        return _reduce_loss(nll, reduction)

    return apply("ctc_loss", fn, log_probs, labels_t, in_len, lab_len)
