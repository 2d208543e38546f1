"""Fused layer blocks — the nn tier over the Pallas conv suite
(`ops/pallas/conv.py`) plus inference-time BatchNorm folding.

`ConvBNReLU` is the building block `vision/models/resnet.py` consumes:
a Conv2D + BatchNorm2D (+ optional ReLU) whose EVAL forward can run as
ONE fused Pallas kernel — conv as MXU matmuls with fp32 accumulation,
the BN scale/shift and ReLU applied in-register before the single HBM
write-back — behind the same `auto`/`dense`/`pallas` backend seam as
paged attention (env override `PADDLE_CONV_BACKEND` wins, resolved
ONCE at construction). The dense backend is byte-for-byte today's
`nn_ops.conv2d` + `BatchNorm` + `relu` composition and stays the
exactness foil. TRAINING on a pallas-resolved block runs fused too:
`fused_conv_bn_relu_train` is a `jax.custom_vjp` whose forward fuses
the batch-stat computation into the conv kernel's epilogue and whose
backward runs the fused dInput/dWeight kernels — the block updates
the BN running stats from the returned batch mean/var with exactly
the `nn_ops.batch_norm` momentum rule. Dense-resolved training (and
any geometry the train gate rejects — use_global_stats BN, untileable
walks) keeps the identical pre-suite composition graph. NOTE: the
refactor is graph-compatible, not checkpoint-key-compatible — resnet
block state_dict keys moved from `conv1.weight`/`bn1.*` to
`convbn1.conv.weight`/`convbn1.bn.*` (and `downsample.0.*` to
`downsample.conv.*`); checkpoints saved before the suite landed need
a key rename on load.

`fold_bn_into_conv` / `fuse_conv_bn` are the deploy-time counterpart:
fold the (running-stat) BatchNorm affine into the conv weights/bias so
eval forward skips the BN op entirely — the standard inference
deployment transform, exact up to one float rounding of the folded
weights.
"""
from __future__ import annotations

import numpy as np

from paddle_tpu.ops.dispatch import apply_nograd, as_tensor

from .common import Identity
from .conv import Conv2D
from .layer import Layer
from .norm import BatchNorm2D

__all__ = ["ConvBNReLU", "fold_bn_into_conv", "fuse_conv_bn"]


class ConvBNReLU(Layer):
    """Conv2D + BatchNorm2D + optional ReLU with a fused-kernel eval
    path.

    `act` is `"relu"` or None (the bn3 / downsample shape). `backend`
    is `auto`/`dense`/`pallas` (default auto; `PADDLE_CONV_BACKEND`
    wins), resolved once here: unsupported geometries — the 7x7/s2
    stem, grouped/dilated convs, ragged channels — resolve `dense`
    cleanly whatever was asked. On a resolved-`pallas` block the
    fused kernels engage in BOTH modes: eval through the forward-only
    folded-affine kernel, training through the `custom_vjp` batch-stat
    op with fused backward. Everything else (the dense backend, a
    custom norm layer, use_global_stats BN, a geometry either tile
    gate rejects) runs the composition the rest of the framework
    already trains through — `CONV_PATH_STATS` counts the train-mode
    routes separately so a fallback is observable."""

    def __init__(self, in_channels, out_channels, kernel_size,
                 stride=1, padding=0, dilation=1, groups=1,
                 act="relu", backend=None, norm_layer=None,
                 data_format="NCHW"):
        super().__init__()
        from paddle_tpu.ops.pallas.conv import resolve_conv_backend

        if act not in ("relu", None):
            raise ValueError(f"act must be 'relu' or None, got {act!r}")
        norm_layer = norm_layer or BatchNorm2D
        self.conv = Conv2D(in_channels, out_channels, kernel_size,
                           stride=stride, padding=padding,
                           dilation=dilation, groups=groups,
                           bias_attr=False, data_format=data_format)
        self.bn = norm_layer(out_channels)
        self._act = act
        self._data_format = data_format
        self._folded = False
        self.backend_requested = backend or "auto"
        self.backend = resolve_conv_backend(
            backend, kernel=self.conv._kernel_size,
            stride=self.conv._stride, in_channels=in_channels,
            out_channels=out_channels, dilation=self.conv._dilation,
            groups=groups, padding=padding)
        if not isinstance(self.bn, BatchNorm2D):
            # a custom norm has no (mean, var, gamma, beta) affine to
            # fold into the kernel epilogue — composition only
            self.backend = "dense"

    def extra_repr(self):
        return (f"{self.conv._in_channels}, {self.conv._out_channels}, "
                f"kernel_size={self.conv._kernel_size}, "
                f"stride={self.conv._stride}, act={self._act!r}, "
                f"backend={self.backend}")

    def _compose(self, x):
        """The dense exactness foil: today's conv -> BN -> ReLU
        composition, unchanged (XLA fuses the element-wise tail)."""
        from paddle_tpu.ops.pallas.conv import CONV_PATH_STATS

        CONV_PATH_STATS["dense_train" if self.training
                        else "dense"] += 1
        out = self.conv(x)
        if not self._folded:
            out = self.bn(out)
        if self._act == "relu":
            from paddle_tpu.ops.activation import relu

            out = relu(out)
        return out

    def forward(self, x):
        if self.backend == "pallas" and not self._folded:
            if not self.training and self._geometry_tileable(x):
                return self._forward_fused(x)
            if self.training and self._train_fusible(x):
                return self._forward_fused_train(x)
        return self._compose(x)

    def _geometry_tileable(self, x):
        """The H/W-dependent half of the support gate, checked per
        forward (static resolution cannot see the input size): a
        geometry the 3x3 kernel cannot tile — too many row tiles, a
        slab overrunning the padded input or the VMEM budget — runs
        the dense composition, the same clean fallback as the static
        gate."""
        from paddle_tpu.ops.pallas.conv import conv_geometry_tileable

        hw = x.shape[2:4] if self._data_format == "NCHW" \
            else x.shape[1:3]
        return conv_geometry_tileable(self.conv._kernel_size,
                                      self.conv._stride,
                                      self.conv._padding, in_hw=hw,
                                      in_channels=self.conv._in_channels)

    def _train_fusible(self, x):
        """Training-mode gate on a pallas-resolved block: batch-stat
        BatchNorm only (`use_global_stats` pins running stats — the
        fused train op computes batch stats by construction) and both
        the forward AND backward walks must tile
        (`conv_train_geometry_tileable`). Anything else runs the
        dense composition — a clean fallback counted in
        `CONV_PATH_STATS["dense_train"]`, never a silent
        divergence."""
        from paddle_tpu.ops.pallas.conv import \
            conv_train_geometry_tileable

        if not isinstance(self.bn, BatchNorm2D) or \
                self.bn._use_global_stats:
            return False
        hw = x.shape[2:4] if self._data_format == "NCHW" \
            else x.shape[1:3]
        return conv_train_geometry_tileable(
            self.conv._kernel_size, self.conv._stride,
            self.conv._padding, in_hw=hw,
            in_channels=self.conv._in_channels,
            out_channels=self.conv._out_channels)

    def _forward_fused(self, x):
        """ONE dispatch: BN affine folded to (scale, shift) in fp32,
        layout swapped to the kernels' NHWC, the fused Pallas kernel,
        and the layout swapped back. Forward-only (`apply_nograd`) —
        gradients always flow through the composition."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.conv import fused_conv_bn_relu

        x = as_tensor(x)
        eps = self.bn._epsilon
        stride = self.conv._stride
        padding = self.conv._padding
        nchw = self._data_format == "NCHW"
        relu = self._act == "relu"

        def fn(a, w, gamma, beta, mean, var):
            scale = gamma.astype(jnp.float32) * jax.lax.rsqrt(
                var.astype(jnp.float32) + eps)
            shift = beta.astype(jnp.float32) - \
                mean.astype(jnp.float32) * scale
            if nchw:
                a = jnp.transpose(a, (0, 2, 3, 1))
            wt = jnp.transpose(w, (2, 3, 1, 0))      # OIHW -> HWIO
            out = fused_conv_bn_relu(a, wt, scale, shift,
                                     stride=stride, padding=padding,
                                     relu=relu)
            if nchw:
                out = jnp.transpose(out, (0, 3, 1, 2))
            return out

        return apply_nograd("conv_bn_relu_fused", fn, x,
                            self.conv.weight, self.bn.weight,
                            self.bn.bias, self.bn._mean,
                            self.bn._variance)

    def _forward_fused_train(self, x):
        """ONE differentiable dispatch for training: layouts swapped
        to the kernels' NHWC, the `fused_conv_bn_relu_train`
        custom_vjp (batch-stat forward with the stats fused into the
        conv epilogue; fused dInput/dWeight backward), layouts swapped
        back — through `apply`, so the tape (or an outer
        value_and_grad) differentiates straight through the
        custom_vjp. The BN running stats update from the returned
        batch mean/var with exactly the `nn_ops.batch_norm` rule
        (stop-gradient, unbiased variance, momentum)."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.dispatch import apply
        from paddle_tpu.ops.pallas.conv import fused_conv_bn_relu_train

        x = as_tensor(x)
        eps = self.bn._epsilon
        stride = self.conv._stride
        padding = self.conv._padding
        nchw = self._data_format == "NCHW"
        relu = self._act == "relu"

        def fn(a, w, gamma, beta):
            if nchw:
                a = jnp.transpose(a, (0, 2, 3, 1))
            wt = jnp.transpose(w, (2, 3, 1, 0))      # OIHW -> HWIO
            y, mean, var = fused_conv_bn_relu_train(
                a, wt, gamma, beta, stride=stride, padding=padding,
                relu=relu, eps=eps)
            if nchw:
                y = jnp.transpose(y, (0, 3, 1, 2))
            return y, mean, var

        out, mean, var = apply("conv_bn_relu_fused_train", fn, x,
                               self.conv.weight, self.bn.weight,
                               self.bn.bias)
        # running-stat update — the exact nn_ops.batch_norm side
        # effect (under a compiled TrainStep the buffer assignment is
        # captured and persisted like any in-forward buffer write)
        bn = self.bn
        rm, rv = bn._mean._array, bn._variance._array
        os_ = out.shape
        n = float(np.prod([os_[i] for i in ((0, 2, 3) if nchw
                                            else (0, 1, 2))]))
        unbiased = var._array * (n / max(n - 1.0, 1.0))
        mom = bn._momentum
        bn._mean._array = mom * rm + (1 - mom) * \
            jax.lax.stop_gradient(mean._array)
        bn._variance._array = mom * rv + (1 - mom) * \
            jax.lax.stop_gradient(unbiased)
        return out

    def fold(self):
        """Inference-time BN folding: absorb the running-stat affine
        into the conv weights/bias and drop the BN op from forward.
        Idempotent; training after folding would train the folded conv
        against a dead BN, so it flips eval mode on."""
        if self._folded:
            return self
        fold_bn_into_conv(self.conv, self.bn)
        self._folded = True
        self.eval()
        return self


def fold_bn_into_conv(conv, bn):
    """Fold an eval-mode BatchNorm's affine into `conv` IN PLACE:
    w' = w * scale per out-channel, b' = beta - mean*scale (+ old bias
    * scale), with scale = gamma * rsqrt(var + eps) computed in fp64 on
    host so the fold itself adds no low-precision rounding beyond the
    final cast back to the weight dtype."""
    w = conv.weight.numpy().astype(np.float64)          # OIHW
    gamma = bn.weight.numpy().astype(np.float64)
    beta = bn.bias.numpy().astype(np.float64)
    mean = bn._mean.numpy().astype(np.float64)
    var = bn._variance.numpy().astype(np.float64)
    scale = gamma / np.sqrt(var + bn._epsilon)
    shift = beta - mean * scale
    if conv.bias is not None:
        shift = shift + conv.bias.numpy().astype(np.float64) * scale
    wdt = conv.weight.numpy().dtype
    conv.weight.set_value(
        (w * scale[:, None, None, None]).astype(wdt))
    if conv.bias is None:
        # a bias_attr=False conv stored bias=None in the instance
        # __dict__, which would shadow the _parameters registration
        if "bias" in conv.__dict__:
            object.__delattr__(conv, "bias")
        conv.bias = conv.create_parameter(
            [conv._out_channels], is_bias=True)
    conv.bias.set_value(shift.astype(wdt))
    return conv


def fuse_conv_bn(layer):
    """Walk a Layer tree and fold every foldable BatchNorm for eval
    deployment: `ConvBNReLU` blocks fold in place, and any (Conv2D,
    BatchNorm2D) pair ADJACENT in a container's sublayer order (the
    `conv1`/`bn1` stem idiom, `Sequential(conv, bn)` downsamples)
    folds into the conv with the BN replaced by `Identity`. Returns
    the number of BatchNorms folded. Call on an eval-mode model; the
    transform assumes forward applies the BN directly to the conv
    output (true of every pair this repo ships)."""
    n = 0
    if isinstance(layer, ConvBNReLU):
        if not layer._folded and isinstance(layer.bn, BatchNorm2D):
            layer.fold()
            n += 1
        return n
    prev = None
    for name, sub in list(layer._sub_layers.items()):
        if sub is None:
            continue
        if isinstance(sub, BatchNorm2D) and isinstance(prev, Conv2D):
            fold_bn_into_conv(prev, sub)
            layer._sub_layers[name] = Identity()
            prev = None
            n += 1
            continue
        n += fuse_conv_bn(sub)
        prev = sub
    return n
