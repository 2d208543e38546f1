"""Fused conv+BN+ReLU Pallas kernels — the custom conv suite the
ResNet-50 MFU plateau calls for (ROADMAP item 5, DESIGN_DECISIONS r17).

Pre-PR-1 records (the `conv_c2_*`/`conv_c5_*` rows of OPBENCH.json;
not measured on today's code) put numbers on the problem: the
stage-1/2 ResNet shapes ran at 24-76 TFLOP/s through
`lax.conv_general_dilated` against 184 TFLOP/s for a same-FLOP matmul,
and a fusion probe showed even perfect XLA conv+BN fusion capping at
~0.20 MFU — the early stages are bandwidth-bound on activation
re-reads between conv, BN and ReLU.
These kernels attack exactly that traffic: ONE HBM read of the
activation, the conv as explicit MXU matmuls with fp32 accumulation,
and the BatchNorm scale/shift + ReLU applied in-register before the
single HBM write-back.

Two kernel families cover the ResNet bottleneck sweep:

- 1x1 convs (`_conv1x1_kernel`): a 1x1 conv IS a matmul — the input is
  viewed as `[N*Ho*Wo, Cin]`, tiled over rows, and each grid program
  runs one `[TM, Cin] x [Cin, Cout]` MXU pass with the epilogue fused.
  This alone targets `conv_c2_1x1_64_256` and `conv_c5_1x1_512_2048`,
  the worst matmul-gap rows of the sweep. Stride-2 1x1 (the downsample
  path) pre-slices the input — exact, and the slice is 1/4 the read.
- 3x3 stride-1/2 convs (`_conv3x3_kernel`): implicit GEMM. One grid
  program per image streams output-row slabs of the (pre-padded) input
  HBM->VMEM through a double-buffered scratch — the next slab's DMA in
  flight behind the current slab's compute, halo rows riding inside
  each slab — and computes the conv as 9 shifted `[TH*Wo, Cin] x
  [Cin, Cout]` tap matmuls accumulated in fp32
  (`preferred_element_type`; tpu-verify TPU103 pins it), epilogue
  fused, one output write.

Padding is materialized once with `jnp.pad` before the 3x3 kernel (a
single fused memset+copy) so every slab DMA is in-bounds with a static
shape; the win this suite claims is eliminating the BN/ReLU activation
round-trips, which dwarf the one-off pad. Both `"SAME"` (the bench
sweep's convention — asymmetric at stride 2) and paddle's explicit
symmetric padding (the ResNet blocks' convention) resolve to the same
VALID-over-padded-input geometry, so one kernel serves both.

Backend seam — the `ops/paged_attention.py` pattern verbatim:
`resolve_conv_backend` maps `auto`/`dense`/`pallas` (env override
`PADDLE_CONV_BACKEND` wins, resolved ONCE at block construction by
`nn/fused.py`); `auto` picks a fused kernel only on TPU and only from
the 1x1 family — compiled for a v5e chip (PR 23) the 3x3 family is
refused by Mosaic (unaligned slab slices at stride 1, strided vector
slices at stride 2; ROADMAP A4), so it runs only on an explicit
`pallas`; explicit `pallas` off-TPU runs the interpreter (the CPU CI
path, tested numerically against the dense composition like the
paged-attention kernels); unsupported shapes — the 7x7/s2 stem,
grouped/dilated convs, ragged channel counts — fall back to `dense`
CLEANLY whatever was requested, and `CONV_PATH_STATS` records every
dispatch so a silent fallback is impossible (flash_attention
PATH_STATS precedent).

The suite covers BOTH halves of training. Forward in train mode runs
the same kernels with the BN affine epilogue replaced by a fused
stats epilogue (`_conv1x1_train_kernel`/`_conv3x3_train_kernel`
accumulate per-channel f32 sum/sum-of-squares across the sequential
grid), and the backward runs fused too: **dInput** as a
transposed-filter implicit GEMM (1x1: row-tiled MXU matmuls over the
transposed weight with the whole ReLU+BN backward chain folded
in-register; 3x3: the mirrored shifted-tap walk — the SAME
`_conv3x3_call` machinery over the stride-dilated dOut and the
flipped/transposed filter, halo rows in-slab) and **dWeight** as a
slab-streamed accumulation over the same double-buffered HBM->VMEM
walk (`_conv1x1_dw_kernel`/`_conv3x3_dw_kernel`), every matmul
accumulating fp32 via `preferred_element_type`. `nn/fused.py` wires
the pair through ONE `jax.custom_vjp` per static config
(`fused_conv_bn_relu_train`), so a pallas-resolved `ConvBNReLU`
trains fused while the dense composition remains the fallback and
the bit-exactness foil — unsupported geometries resolve dense
cleanly through `resolve_conv_backend`/`conv_train_geometry_tileable`
and `CONV_PATH_STATS` counts train-mode dispatches separately, never
a silent divergence. See DESIGN_DECISIONS r19 for the BN-stats
placement policy (stats-in-epilogue forward, two-pass backward with
dOut-chain materialized once for the 3x3 family).

TraceContracts for all four kernel families (fwd + bwd) are declared
here, colocated with the builders, and `harvest_programs()` hands
tpu-verify tiny-but-real jitted instances so their lowering is gated
like every other compiled program.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from paddle_tpu.analysis.trace.contracts import TraceContract, \
    register_contract
from paddle_tpu.core.device import on_tpu, pallas_interpret
from paddle_tpu.ops.pallas.naming import kernel_name

__all__ = ["fused_conv_bn_relu", "fused_conv_bn_relu_train",
           "conv_bn_relu_reference", "conv_bn_relu_train_reference",
           "resolve_conv_backend", "conv_shapes_supported",
           "conv_geometry_tileable", "conv_train_geometry_tileable",
           "normalize_conv_padding",
           "CONV_BACKENDS", "CONV_PATH_STATS",
           "reset_conv_path_stats", "harvest_programs",
           "CONV_HARVEST_SHAPES", "CONV_BWD_HARVEST_SHAPES"]

CONV_BACKENDS = ("auto", "dense", "pallas")

# which backend a fused-conv dispatch actually ran, incremented per
# call (per TRACE under jit), with TRAIN-mode dispatches counted
# separately from eval so a training fallback is observable on its
# own. Tests read it to prove the requested kernel engaged / the stem
# fell back — never a silent fallback.
CONV_PATH_STATS = {"dense": 0, "pallas": 0,
                   "dense_train": 0, "pallas_train": 0}


def reset_conv_path_stats():
    for k in CONV_PATH_STATS:
        CONV_PATH_STATS[k] = 0


def _pair(v=1):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * 2


def normalize_conv_padding(padding=0, kernel=3, stride=1, in_hw=None):
    """Paddle/lax padding spec -> ((top, bottom), (left, right)).

    Accepts an int, a 2-int per-dim pad, 2 (lo, hi) pairs, or the
    "SAME"/"VALID" strings. "SAME" needs `in_hw` because lax pads it
    asymmetrically at stride > 1 (total = (ceil(d/s)-1)*s + k - d, lo =
    total//2) — the bench sweep's convention, distinct from the ResNet
    blocks' symmetric padding=1."""
    kh, kw = _pair(kernel)
    sh, sw = _pair(stride)
    if isinstance(padding, str):
        p = padding.upper()
        if p == "VALID":
            return ((0, 0), (0, 0))
        if p == "SAME":
            if in_hw is None:
                raise ValueError("SAME padding needs the input H/W")
            out = []
            for d, k, s in zip(in_hw, (kh, kw), (sh, sw)):
                total = max((-(-d // s) - 1) * s + k - d, 0)
                out.append((total // 2, total - total // 2))
            return tuple(out)
        raise ValueError(f"unsupported conv padding {padding!r}")
    if isinstance(padding, (list, tuple)):
        if len(padding) == 2 and all(
                isinstance(p, (list, tuple)) for p in padding):
            return tuple((int(lo), int(hi)) for lo, hi in padding)
        if len(padding) == 2:
            return tuple((int(p), int(p)) for p in padding)
        if len(padding) == 4:
            return ((int(padding[0]), int(padding[1])),
                    (int(padding[2]), int(padding[3])))
        raise ValueError(f"unsupported conv padding {padding!r}")
    p = int(padding)
    return ((p, p), (p, p))


def conv_shapes_supported(kernel=3, stride=1, in_channels=8,
                          out_channels=8, dilation=1, groups=1,
                          padding=0):
    """Static-shape gate for the fused kernels: k in {1, 3} square,
    stride in {1, 2} square, no dilation/groups, channel counts in
    multiples of 8 (sublane-friendly tiles), and zero padding for the
    1x1 family (a padded 1x1 conv is not a matmul). Everything else —
    the 7x7/s2 stem above all — runs the dense composition; callers
    resolve ONCE so the answer never flips mid-serving."""
    kh, kw = _pair(kernel)
    sh, sw = _pair(stride)
    dh, dw = _pair(dilation)
    if (kh, kw) not in ((1, 1), (3, 3)) or kh != kw:
        return False
    if sh != sw or sh not in (1, 2):
        return False
    if dh != 1 or dw != 1 or groups != 1:
        return False
    if in_channels % 8 or out_channels % 8:
        return False
    if (kh, kw) == (1, 1) and not isinstance(padding, str):
        pads = normalize_conv_padding(padding, kernel, stride,
                                      in_hw=(8, 8))
        if any(p != (0, 0) for p in pads):
            return False
    return True


def conv_geometry_tileable(kernel=3, stride=1, padding=0, in_hw=None,
                           in_channels=8):
    """Per-call geometry gate for the 3x3 family — the H/W-dependent
    half `conv_shapes_supported` (static, construction-time) cannot
    see: True when the output rows tile within the kernel's unroll
    bound, the double-buffered slab fits the VMEM budget at SOME
    output-width tile (`_pick_w_tile` — wide resolutions W-tile
    instead of falling back dense), and every slab DMA lands in-bounds
    of the padded input. 1x1 geometries always tile (the row-tile pad
    covers any M). `nn/fused.py` checks this per forward and runs the
    dense composition when it fails — the same clean-fallback contract
    as the static gate, just resolved at the first shape-bearing
    call."""
    kh, kw = _pair(kernel)
    if (kh, kw) == (1, 1):
        return True
    sh, _ = _pair(stride)
    pads = normalize_conv_padding(padding, kernel, stride, in_hw=in_hw)
    return _conv3x3_geometry(int(in_hw[0]), int(in_hw[1]),
                             int(in_channels), sh, pads) is not None


def _dx_row_rounding(ho=8):
    """Extra zero ROWS appended to the dInput walk's grid when its
    natural row count cannot tile (e.g. the 58-row grid of a 56x56
    stage-1 conv: no divisor <= 8 keeps it within the 16-tile unroll
    bound): round up to the next multiple of 8 — th=8 tiles any
    multiple up to 128 within the bound, the appended rows are zeros
    the conv ignores, and the `[pt:pt+H]` slice discards the tail.
    Returns 0 when the natural count already tiles, None past the
    128-row ceiling (H ~> 126 trains dense)."""
    th = _pick_h_tile(ho)
    if ho // th <= 16:
        return 0
    target = ((ho + 7) // 8) * 8
    return target - ho if target <= 128 else None


def conv_train_geometry_tileable(kernel=3, stride=1, padding=0,
                                 in_hw=None, in_channels=8,
                                 out_channels=8):
    """Per-call geometry gate for the TRAINING path: the forward walk
    must tile AND the backward dInput conv — a stride-1 3x3 walk over
    the stride-dilated dOut (Cout channels) with full (2, 2) halo
    padding, its row grid rounded up per `_dx_row_rounding` — must
    tile too. The dWeight walk reuses the forward slab geometry, so
    the forward check covers it. 1x1 family: always (both directions
    are row-tiled matmuls)."""
    kh, kw = _pair(kernel)
    if (kh, kw) == (1, 1):
        return True
    if not conv_geometry_tileable(kernel, stride, padding, in_hw=in_hw,
                                  in_channels=in_channels):
        return False
    sh, _ = _pair(stride)
    pads = normalize_conv_padding(padding, kernel, stride, in_hw=in_hw)
    hp = int(in_hw[0]) + sum(pads[0])
    wp = int(in_hw[1]) + sum(pads[1])
    ho = (hp - 3) // sh + 1
    wo = (wp - 3) // sh + 1
    hd = sh * (ho - 1) + 1                    # dilated dOut extent
    wd = sh * (wo - 1) + 1
    eh = _dx_row_rounding(hd + 2)
    if eh is None:
        return False
    return _conv3x3_geometry(hd, wd, int(out_channels), 1,
                             ((2, 2 + eh), (2, 2))) is not None


def resolve_conv_backend(backend=None, *, kernel=(3, 3), stride=(1, 1),
                         in_channels=8, out_channels=8, dilation=1,
                         groups=1, padding=0):
    """Resolve `auto`/`dense`/`pallas` to the backend a fused conv
    block will run — ONCE, at construction (the paged-attention
    `resolve_backend` pattern). The `PADDLE_CONV_BACKEND` env override
    wins over the constructor argument (deploy semantics). Unsupported
    static shapes resolve `dense` whatever was requested — the clean
    fallback the 7x7 stem rides — while a supported shape honours an
    explicit `dense`/`pallas` (off-TPU, `pallas` runs the interpreter:
    the CPU CI path); `auto` picks a fused kernel only on TPU, and
    only the 1x1 family."""
    requested = os.environ.get("PADDLE_CONV_BACKEND") or backend \
        or "auto"
    if requested not in CONV_BACKENDS:
        raise ValueError(f"conv backend must be one of {CONV_BACKENDS}, "
                         f"got {requested!r}")
    if not conv_shapes_supported(kernel, stride, in_channels,
                                 out_channels, dilation, groups,
                                 padding):
        return "dense"
    if requested != "auto":
        return requested
    # the 3x3 family is written but the chip's compiler refuses it
    # (ROADMAP A4), so `auto` selects only the 1x1 kernels
    return "pallas" if on_tpu() and _pair(kernel) == (1, 1) else "dense"


# ---------------------------------------------------------------------------
# dense reference (the exactness foil)
# ---------------------------------------------------------------------------

def conv_bn_relu_reference(x, w, scale, shift, stride=1, padding=0,
                           relu=True):
    """The dense `lax.conv_general_dilated` composition the fused
    kernels are tested and benched against: conv with fp32
    accumulation, BN scale/shift in fp32, optional ReLU, ONE cast back
    to the input dtype. x `[N, H, W, Cin]`, w `[kh, kw, Cin, Cout]`,
    scale/shift `[Cout]` f32 (the folded BatchNorm affine)."""
    sh, sw = _pair(stride)
    pads = normalize_conv_padding(padding, w.shape[:2], stride,
                                  in_hw=x.shape[1:3])
    out = jax.lax.conv_general_dilated(
        x, w, (sh, sw), list(pads),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    out = out * scale.astype(jnp.float32) + shift.astype(jnp.float32)
    if relu:
        out = jnp.maximum(out, 0.0)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# 1x1 family: the conv IS a matmul
# ---------------------------------------------------------------------------

def _conv1x1_kernel(x_ref, w_ref, scale_ref, shift_ref, o_ref, *, relu):
    """One `[TM, Cin] x [Cin, Cout]` MXU pass, epilogue in-register:
    fp32 accumulation, BN scale/shift, optional ReLU, one cast."""
    acc = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y = acc * scale_ref[...] + shift_ref[...]      # [TM,Cout]*[1,Cout]
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[...] = y.astype(o_ref.dtype)


def _pick_row_tile(m=8):
    """Row-tile for the 1x1 matmul: a power-of-two divisor keeps every
    grid step identical; otherwise the wrapper zero-pads M up to the
    tile (the pad rows are sliced off after — ~one tile of waste)."""
    for tm in (512, 256, 128):
        if m % tm == 0:
            return tm
    return 128 if m >= 128 else 8


def _conv1x1_call(x2, w2, scale, shift, relu, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, Cin = x2.shape
    Cout = w2.shape[1]
    TM = _pick_row_tile(M)
    pad = (-M) % TM
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_conv1x1_kernel, relu=relu),
        **kernel_name("conv1x1_fwd"),
        grid=((M + pad) // TM,),
        in_specs=[
            pl.BlockSpec((TM, Cin), lambda i: (i, 0)),
            pl.BlockSpec((Cin, Cout), lambda i: (0, 0)),
            pl.BlockSpec((1, Cout), lambda i: (0, 0)),
            pl.BlockSpec((1, Cout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TM, Cout), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M + pad, Cout), x2.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2, w2, scale.reshape(1, Cout), shift.reshape(1, Cout))
    return out[:M] if pad else out


# ---------------------------------------------------------------------------
# 3x3 family: implicit GEMM over streamed input slabs
# ---------------------------------------------------------------------------

#: VMEM budget for ONE double-buffered input slab (both buffers,
#: bytes). Conservatively sized against fp32 slabs (`_pick_w_tile`
#: uses a constant itemsize so the geometry gate and the kernel
#: wrapper always agree); ~4 MB of the ~16 MB/core leaves room for
#: the weight block, the fp32 accumulator and the output tile. Tests
#: monkeypatch this down to force W-tiling on small shapes.
_VMEM_SLAB_BYTES = 4 * 1024 * 1024


def _pick_w_tile(wo=8, slab=3, stride=1, cin=8, itemsize=4):
    """Output-width tile for the 3x3 slab walk: the largest divisor of
    Wo whose double-buffered input slab `2 * slab_rows * (stride*(tw-1)
    + 3) * Cin` fits `_VMEM_SLAB_BYTES`. TW=Wo (one tile, today's
    whole-width slab) whenever it fits; None when even TW=1 does not
    (pathological Cin — dense handles it)."""
    for tw in range(int(wo), 0, -1):
        if wo % tw:
            continue
        twp = stride * (tw - 1) + 3
        if 2 * slab * twp * cin * itemsize <= _VMEM_SLAB_BYTES:
            return tw
    return None


def _conv3x3_kernel(xp_ref, w_ref, scale_ref, shift_ref, o_ref,
                    xbuf, copy_sems, *, stride, th, num_tiles, tw,
                    relu):
    """One program per (image, width tile). xp_ref is the PADDED
    `[N, Hp, Wp, Cin]` input left in ANY/HBM; the program walks
    `num_tiles` output-row tiles of height `th` within its width tile,
    streaming each tile's input slab (the `stride*(th-1)+3` rows x
    `stride*(tw-1)+3` columns it reads, halo included both ways) into
    the double-buffered VMEM scratch `xbuf` with the next slab's DMA
    in flight behind the current slab's 9 tap matmuls. The epilogue
    (BN scale/shift + optional ReLU) runs on the fp32 accumulator
    before the single cast + output-tile write."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = pl.program_id(0)
    j = pl.program_id(1)
    slab = stride * (th - 1) + 3
    twp, cin = xbuf.shape[2], xbuf.shape[3]
    cout = w_ref.shape[3]

    def slab_copy(t, buf):
        return pltpu.make_async_copy(
            xp_ref.at[n, pl.ds(t * th * stride, slab),
                      pl.ds(j * tw * stride, twp)],
            xbuf.at[buf], copy_sems.at[buf])

    slab_copy(0, 0).start()
    for t in range(num_tiles):                # static unroll (<= 16)
        if t + 1 < num_tiles:
            slab_copy(t + 1, (t + 1) % 2).start()
        slab_copy(t, t % 2).wait()
        x = xbuf[t % 2]                       # [slab, TWp, Cin]
        acc = jnp.zeros((th * tw, cout), jnp.float32)
        for dy in range(3):
            for dx in range(3):
                xs = jax.lax.slice(
                    x, (dy, dx, 0),
                    (dy + stride * (th - 1) + 1,
                     dx + stride * (tw - 1) + 1, cin),
                    (stride, stride, 1))      # [th, TW, Cin]
                acc = acc + jax.lax.dot_general(
                    xs.reshape(th * tw, cin), w_ref[dy, dx],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        y = acc * scale_ref[...] + shift_ref[...]
        if relu:
            y = jnp.maximum(y, 0.0)
        o_ref[0, t * th:(t + 1) * th] = \
            y.reshape(th, tw, cout).astype(o_ref.dtype)


def _pick_h_tile(ho=8):
    """Output-row tile: the largest divisor of Ho <= 8 (TH=1 always
    divides, so every Ho has a tile); the kernel's unrolled tile walk
    is bounded by the caller via conv_shapes_supported + the <= 16
    check in the wrapper."""
    for th in (8, 7, 6, 5, 4, 3, 2, 1):
        if ho % th == 0:
            return th
    return 1


def _conv3x3_geometry(H=8, W=8, Cin=8, stride=1, pads=None):
    """Shared slab/tile geometry for every 3x3-family walk ->
    (Hp, Wp, Ho, Wo, th, num_tiles, slab, tw, num_wtiles, twp), or
    None when the walk cannot tile (unroll bound, VMEM budget, or a
    slab DMA past the padded input)."""
    pads = pads if pads is not None else ((1, 1), (1, 1))
    s = stride
    (pt, pb), (plft, prgt) = pads
    Hp, Wp = H + pt + pb, W + plft + prgt
    Ho = (Hp - 3) // s + 1
    Wo = (Wp - 3) // s + 1
    if Ho < 1 or Wo < 1:
        return None
    th = _pick_h_tile(Ho)
    num_tiles = Ho // th
    if num_tiles > 16:                        # unroll-depth bound
        return None
    slab = s * (th - 1) + 3
    if s * (num_tiles - 1) * th + slab > Hp:
        # the last slab would read past the padded input (possible
        # when padding under-covers the kernel); dense handles it
        return None
    tw = _pick_w_tile(Wo, slab=slab, stride=s, cin=Cin)
    if tw is None:
        return None
    num_wtiles = Wo // tw
    twp = s * (tw - 1) + 3
    if s * (num_wtiles - 1) * tw + twp > Wp:
        return None
    return Hp, Wp, Ho, Wo, th, num_tiles, slab, tw, num_wtiles, twp


def _conv3x3_call(x, w, scale, shift, stride=1, pads=None, relu=True,
                  interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, H, W, Cin = x.shape
    Cout = w.shape[3]
    s = stride
    pads = pads if pads is not None else ((1, 1), (1, 1))
    geo = _conv3x3_geometry(H, W, Cin, s, pads)
    if geo is None:
        return None
    Hp, Wp, Ho, Wo, th, num_tiles, slab, tw, num_wtiles, twp = geo
    (pt, pb), (plft, prgt) = pads
    xp = jnp.pad(x, ((0, 0), (pt, pb), (plft, prgt), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_conv3x3_kernel, stride=s, th=th,
                          num_tiles=num_tiles, tw=tw, relu=relu),
        **kernel_name("conv3x3_fwd"),
        grid=(N, num_wtiles),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((3, 3, Cin, Cout), lambda n, j: (0, 0, 0, 0)),
            pl.BlockSpec((1, Cout), lambda n, j: (0, 0)),
            pl.BlockSpec((1, Cout), lambda n, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Ho, tw, Cout),
                               lambda n, j: (n, 0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Ho, Wo, Cout), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, slab, twp, Cin), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(xp, w, scale.reshape(1, Cout), shift.reshape(1, Cout))
    return out


# ---------------------------------------------------------------------------
# training forward: same walks, BN-affine epilogue replaced by a fused
# per-channel stats epilogue (sum / sum-of-squares accumulated in f32
# across the SEQUENTIAL grid — "arbitrary" dimension semantics make
# the revisited stats block a legal accumulator)
# ---------------------------------------------------------------------------

def _conv1x1_train_kernel(x_ref, w_ref, o_ref, s_ref):
    """The 1x1 matmul pass with the stats epilogue: the conv tile is
    written in the compute dtype and the SAME cast value feeds the f32
    sum/sum-sq accumulator (the dense foil computes batch stats from
    the cast conv output — bit-parity demands the kernel do too).
    Zero-padded tail rows contribute zero to both sums."""
    from jax.experimental import pallas as pl

    acc = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    yc = acc.astype(o_ref.dtype)
    o_ref[...] = yc

    @pl.when(pl.program_id(0) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    p = yc.astype(jnp.float32)
    s_ref[...] += jnp.concatenate(
        [jnp.sum(p, axis=0, keepdims=True),
         jnp.sum(p * p, axis=0, keepdims=True)], axis=0)


def _conv1x1_train_call(x2, w2, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, Cin = x2.shape
    Cout = w2.shape[1]
    TM = _pick_row_tile(M)
    pad = (-M) % TM
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out, sums = pl.pallas_call(
        _conv1x1_train_kernel,
        **kernel_name("conv1x1_train_fwd"),
        grid=((M + pad) // TM,),
        in_specs=[
            pl.BlockSpec((TM, Cin), lambda i: (i, 0)),
            pl.BlockSpec((Cin, Cout), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TM, Cout), lambda i: (i, 0)),
            pl.BlockSpec((2, Cout), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M + pad, Cout), x2.dtype),
            jax.ShapeDtypeStruct((2, Cout), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2, w2)
    return (out[:M] if pad else out), sums


def _conv3x3_train_kernel(xp_ref, w_ref, o_ref, s_ref, xbuf,
                          copy_sems, *, stride=1, th=8, num_tiles=1,
                          tw=8):
    """The 3x3 slab walk (same double-buffered HBM->VMEM stream as
    `_conv3x3_kernel`) with the stats epilogue of
    `_conv1x1_train_kernel`: per-tile conv write in the compute dtype
    plus f32 sum/sum-sq accumulation into the revisited `s_ref`
    block, initialized at the first grid step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = pl.program_id(0)
    j = pl.program_id(1)
    slab = stride * (th - 1) + 3
    twp, cin = xbuf.shape[2], xbuf.shape[3]
    cout = w_ref.shape[3]

    def slab_copy(t, buf):
        return pltpu.make_async_copy(
            xp_ref.at[n, pl.ds(t * th * stride, slab),
                      pl.ds(j * tw * stride, twp)],
            xbuf.at[buf], copy_sems.at[buf])

    @pl.when((n == 0) & (j == 0))
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    slab_copy(0, 0).start()
    for t in range(num_tiles):                # static unroll (<= 16)
        if t + 1 < num_tiles:
            slab_copy(t + 1, (t + 1) % 2).start()
        slab_copy(t, t % 2).wait()
        x = xbuf[t % 2]                       # [slab, TWp, Cin]
        acc = jnp.zeros((th * tw, cout), jnp.float32)
        for dy in range(3):
            for dx in range(3):
                xs = jax.lax.slice(
                    x, (dy, dx, 0),
                    (dy + stride * (th - 1) + 1,
                     dx + stride * (tw - 1) + 1, cin),
                    (stride, stride, 1))
                acc = acc + jax.lax.dot_general(
                    xs.reshape(th * tw, cin), w_ref[dy, dx],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        yc = acc.astype(o_ref.dtype)
        o_ref[0, t * th:(t + 1) * th] = yc.reshape(th, tw, cout)
        p = yc.astype(jnp.float32)
        s_ref[...] += jnp.concatenate(
            [jnp.sum(p, axis=0, keepdims=True),
             jnp.sum(p * p, axis=0, keepdims=True)], axis=0)


def _conv3x3_train_call(x, w, stride=1, pads=((1, 1), (1, 1)),
                        interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, H, W, Cin = x.shape
    Cout = w.shape[3]
    s = stride
    geo = _conv3x3_geometry(H, W, Cin, s, pads)
    if geo is None:
        return None
    Hp, Wp, Ho, Wo, th, num_tiles, slab, tw, num_wtiles, twp = geo
    (pt, pb), (plft, prgt) = pads
    xp = jnp.pad(x, ((0, 0), (pt, pb), (plft, prgt), (0, 0)))
    out, sums = pl.pallas_call(
        functools.partial(_conv3x3_train_kernel, stride=s, th=th,
                          num_tiles=num_tiles, tw=tw),
        **kernel_name("conv3x3_train_fwd"),
        grid=(N, num_wtiles),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((3, 3, Cin, Cout), lambda n, j: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Ho, tw, Cout), lambda n, j: (n, 0, j, 0)),
            pl.BlockSpec((2, Cout), lambda n, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, Ho, Wo, Cout), x.dtype),
            jax.ShapeDtypeStruct((2, Cout), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, slab, twp, Cin), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(xp, w)
    return out, sums


# ---------------------------------------------------------------------------
# backward: dInput as a transposed-filter implicit GEMM, dWeight as a
# slab-streamed accumulation — fp32 accumulation throughout
# ---------------------------------------------------------------------------

def _conv1x1_bwd_kernel(x_ref, dy_ref, y_ref, rows_ref, wt_ref,
                        dx_ref, dw_ref, *, relu=True):
    """One row tile of the FULL 1x1 backward, the ReLU+BN chain folded
    in-register (no padding in the 1x1 family, so the affine chain is
    exact everywhere): recompute the pre-activation from the saved
    conv tile, mask dy, form dConv = scale*(dz - c1 - xhat*c2), then
    BOTH matmuls — dX = dConv @ W^T against the transposed filter and
    the dW accumulation X^T @ dConv into the revisited f32 output
    block. `rows_ref` is the (8, Cout) f32 channel bundle
    [mean_n, inv_n, gamma, beta, mean32, rstd32, c1, c2] (the *_n rows
    are the dtype-cast normalize-path stats, so the recomputed mask
    matches the forward bit-for-bit in fp32). Zero-padded tail rows:
    dX rows are sliced off by the wrapper and X rows are zero, so the
    nonzero dConv they produce cannot leak into dW."""
    from jax.experimental import pallas as pl

    r = rows_ref[...]
    yv = y_ref[...].astype(jnp.float32)
    dz = dy_ref[...].astype(jnp.float32)
    if relu:
        pre = (yv - r[0:1]) * r[1:2] * r[2:3] + r[3:4]
        dz = jnp.where(pre > 0, dz, 0.0)
    xh = (yv - r[4:5]) * r[5:6]
    dcv = ((r[2:3] * r[5:6]) * (dz - r[6:7] - xh * r[7:8])) \
        .astype(dx_ref.dtype)
    dx_ref[...] = jax.lax.dot_general(
        dcv, wt_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dx_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw_ref[...] += jax.lax.dot_general(
        x_ref[...], dcv, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _conv1x1_bwd_call(x2, dy2, y2, rows, wt, relu=True,
                      interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, Cin = x2.shape
    Cout = wt.shape[0]
    TM = _pick_row_tile(M)
    pad = (-M) % TM
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        dy2 = jnp.pad(dy2, ((0, pad), (0, 0)))
        y2 = jnp.pad(y2, ((0, pad), (0, 0)))
    dx, dw = pl.pallas_call(
        functools.partial(_conv1x1_bwd_kernel, relu=relu),
        **kernel_name("conv1x1_bwd"),
        grid=((M + pad) // TM,),
        in_specs=[
            pl.BlockSpec((TM, Cin), lambda i: (i, 0)),
            pl.BlockSpec((TM, Cout), lambda i: (i, 0)),
            pl.BlockSpec((TM, Cout), lambda i: (i, 0)),
            pl.BlockSpec((8, Cout), lambda i: (0, 0)),
            pl.BlockSpec((Cout, Cin), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TM, Cin), lambda i: (i, 0)),
            pl.BlockSpec((Cin, Cout), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M + pad, Cin), x2.dtype),
            jax.ShapeDtypeStruct((Cin, Cout), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2, dy2, y2, rows, wt)
    return (dx[:M] if pad else dx), dw


def _conv3x3_dw_kernel(xp_ref, g_ref, o_ref, xbuf, copy_sems, *,
                       stride=1, th=8, num_tiles=1, tw=8):
    """dWeight for the 3x3 family: the SAME double-buffered input-slab
    walk as the forward kernel, but each of the 9 taps contracts the
    shifted input slice against the dConv tile over the spatial rows —
    `[TH*TW, Cin]^T @ [TH*TW, Cout]` — accumulating into the revisited
    (3, 3, Cin, Cout) f32 output block across every (image, width
    tile, row tile) grid step, initialized at the first."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = pl.program_id(0)
    j = pl.program_id(1)
    slab = stride * (th - 1) + 3
    twp, cin = xbuf.shape[2], xbuf.shape[3]
    cout = g_ref.shape[3]

    def slab_copy(t, buf):
        return pltpu.make_async_copy(
            xp_ref.at[n, pl.ds(t * th * stride, slab),
                      pl.ds(j * tw * stride, twp)],
            xbuf.at[buf], copy_sems.at[buf])

    @pl.when((n == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    slab_copy(0, 0).start()
    for t in range(num_tiles):                # static unroll (<= 16)
        if t + 1 < num_tiles:
            slab_copy(t + 1, (t + 1) % 2).start()
        slab_copy(t, t % 2).wait()
        x = xbuf[t % 2]                       # [slab, TWp, Cin]
        g2 = g_ref[0, t * th:(t + 1) * th].reshape(th * tw, cout)
        for dy in range(3):
            for dx in range(3):
                xs = jax.lax.slice(
                    x, (dy, dx, 0),
                    (dy + stride * (th - 1) + 1,
                     dx + stride * (tw - 1) + 1, cin),
                    (stride, stride, 1)).reshape(th * tw, cin)
                o_ref[dy, dx] += jax.lax.dot_general(
                    xs, g2, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)


def _conv3x3_dw_call(x, g, stride=1, pads=((1, 1), (1, 1)),
                     interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, H, W, Cin = x.shape
    Cout = g.shape[3]
    s = stride
    geo = _conv3x3_geometry(H, W, Cin, s, pads)
    if geo is None:
        return None
    Hp, Wp, Ho, Wo, th, num_tiles, slab, tw, num_wtiles, twp = geo
    (pt, pb), (plft, prgt) = pads
    xp = jnp.pad(x, ((0, 0), (pt, pb), (plft, prgt), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_conv3x3_dw_kernel, stride=s, th=th,
                          num_tiles=num_tiles, tw=tw),
        **kernel_name("conv3x3_dw"),
        grid=(N, num_wtiles),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, Ho, tw, Cout), lambda n, j: (n, 0, j, 0)),
        ],
        out_specs=pl.BlockSpec((3, 3, Cin, Cout),
                               lambda n, j: (0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((3, 3, Cin, Cout), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, slab, twp, Cin), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(xp, g)
    return out


# ---------------------------------------------------------------------------
# the training composition: dense foil + fused fwd/bwd + custom_vjp
# ---------------------------------------------------------------------------

def conv_bn_relu_train_reference(x, w, gamma, beta, stride=1,
                                 padding=0, relu=True, eps=1e-5):
    """The dense TRAINING composition the fused custom_vjp is tested
    and benched against — conv + batch-stat BN + ReLU with exactly the
    `nn_ops.conv2d`/`nn_ops.batch_norm` numerics (no
    preferred_element_type on the conv, single-pass f32 E[x^2]-m^2
    stats clamped at 0, mean/inv cast to the compute dtype before the
    normalize, the f32 gamma/beta promoting the affine tail). Returns
    (y, mean, var) like `batch_norm` training mode; fully
    differentiable, so `jax.grad` of this IS the dense backward the
    fused kernels must match."""
    sh, sw = _pair(stride)
    pads = normalize_conv_padding(padding, w.shape[:2], stride,
                                  in_hw=x.shape[1:3])
    conv = jax.lax.conv_general_dilated(
        x, w, (sh, sw), list(pads),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    af = conv.astype(jnp.float32)
    mean32 = af.mean(axis=(0, 1, 2))
    m2 = (af * af).mean(axis=(0, 1, 2))
    var32 = jnp.maximum(m2 - mean32 * mean32, 0.0)
    mean = mean32.astype(conv.dtype)
    var = var32.astype(conv.dtype)
    inv = jax.lax.rsqrt(var32 + eps).astype(conv.dtype)
    out = (conv - mean) * inv
    out = out * gamma + beta
    if relu:
        out = jnp.maximum(out, 0.0)
    return out, mean, var


def _train_fwd_impl(x, w, gamma, beta, kernel=1, stride=1,
                    pads=((0, 0), (0, 0)), relu=True, eps=1e-5,
                    interpret=True):
    """Fused training forward -> (y, mean, var, conv, mean32, var32):
    the conv runs through the train kernels (stats in the epilogue —
    ONE pass over the activation produces both the conv output and the
    f32 channel sums), then the normalize+affine+ReLU tail runs as one
    plain-jnp elementwise pass XLA fuses, with the exact
    `nn_ops.batch_norm` dtype staging so the dense foil is matched
    bit-for-bit in fp32."""
    s = stride
    if kernel == 1:
        N = x.shape[0]
        xs = x[:, ::s, ::s] if s != 1 else x
        Ho, Wo = xs.shape[1], xs.shape[2]
        Cin, Cout = x.shape[3], w.shape[3]
        conv2, sums = _conv1x1_train_call(
            xs.reshape(N * Ho * Wo, Cin), w[0, 0], interpret)
        conv = conv2.reshape(N, Ho, Wo, Cout)
    else:
        r = _conv3x3_train_call(x, w, s, pads, interpret)
        if r is None:
            raise ValueError(
                "fused 3x3 train kernel cannot tile this geometry "
                f"(H={x.shape[1]} pad={pads} stride={s}) — run the "
                "dense composition")
        conv, sums = r
    m = float(conv.shape[0] * conv.shape[1] * conv.shape[2])
    mean32 = sums[0] / m
    var32 = jnp.maximum(sums[1] / m - mean32 * mean32, 0.0)
    mean = mean32.astype(conv.dtype)
    var = var32.astype(conv.dtype)
    inv = jax.lax.rsqrt(var32 + eps).astype(conv.dtype)
    y = (conv - mean) * inv
    y = y * gamma + beta
    if relu:
        y = jnp.maximum(y, 0.0)
    return y, mean, var, conv, mean32, var32


def _train_bwd_impl(kernel=1, stride=1, pads=((0, 0), (0, 0)),
                    relu=True, eps=1e-5, interpret=True, res=None,
                    dy=None):
    """Fused training backward (two-pass stats — see DESIGN_DECISIONS
    r19). Pass 1 is ONE fused elementwise+reduce over (dy, conv):
    recompute the pre-activation with the forward's exact dtype
    staging for the ReLU mask, then the f32 channel reductions
    sum(dz) and sum(dz*xhat) — which ARE dbeta/dgamma and fund the
    per-channel c1/c2 of the BN input gradient
    dConv = gamma*rstd*(dz - c1 - xhat*c2). Pass 2 runs the Pallas
    kernels: the 1x1 family folds the whole chain in-register
    (`_conv1x1_bwd_kernel` — dX and the dW accumulation in one
    pallas_call); the 3x3 family materializes dConv once (the chain is
    AFFINE, not linear — on zero-padded halo rows it is nonzero, so it
    cannot be recomputed inside the transposed-conv walk without a
    validity mask; one write + two reads also beats two fused
    recomputes' 2x2 reads), then dX = the stride-1 `_conv3x3_call`
    walk over the s-dilated dConv against the flipped In/Out-swapped
    filter (the mirrored shifted-tap walk, halo in-slab) and dW = the
    `_conv3x3_dw_kernel` slab-streamed accumulation."""
    x, w, gamma, beta, conv, mean32, var32 = res
    s = stride
    dt = x.dtype
    N, H, W, Cin = x.shape
    Ho, Wo, Cout = conv.shape[1], conv.shape[2], conv.shape[3]
    m = float(N * Ho * Wo)
    rstd32 = jax.lax.rsqrt(var32 + eps)
    g32 = gamma.astype(jnp.float32)
    b32 = beta.astype(jnp.float32)
    mean_dt = mean32.astype(dt)
    inv_dt = rstd32.astype(dt)

    # pass 1: mask + channel reductions (one fused XLA pass)
    dz = dy.astype(jnp.float32)
    if relu:
        xn = (conv - mean_dt) * inv_dt        # fwd normalize, bit-exact
        pre = xn.astype(jnp.float32) * g32 + b32
        dz = jnp.where(pre > 0, dz, 0.0)
    xh = (conv.astype(jnp.float32) - mean32) * rstd32
    dbeta32 = dz.sum(axis=(0, 1, 2))
    dgamma32 = (dz * xh).sum(axis=(0, 1, 2))
    c1 = dbeta32 / m
    c2 = dgamma32 / m

    # pass 2: the Pallas kernels
    if kernel == 1:
        rows = jnp.stack([mean_dt.astype(jnp.float32),
                          inv_dt.astype(jnp.float32),
                          g32, b32, mean32, rstd32, c1, c2])
        M = N * Ho * Wo
        xs = x[:, ::s, ::s] if s != 1 else x
        dx2, dw2 = _conv1x1_bwd_call(
            xs.reshape(M, Cin), dy.reshape(M, Cout),
            conv.reshape(M, Cout), rows,
            jnp.transpose(w[0, 0], (1, 0)), relu, interpret)
        dxs = dx2.reshape(N, Ho, Wo, Cin)
        if s != 1:
            dx = jnp.zeros((N, H, W, Cin), dt) \
                .at[:, ::s, ::s].set(dxs)     # fwd sampled; rest is 0
        else:
            dx = dxs
        dw = dw2.reshape(1, 1, Cin, Cout).astype(w.dtype)
    else:
        dconv = ((g32 * rstd32) * (dz - c1 - xh * c2)).astype(dt)
        if s != 1:
            hd, wd = s * (Ho - 1) + 1, s * (Wo - 1) + 1
            dil = jnp.zeros((N, hd, wd, Cout), dt) \
                .at[:, ::s, ::s].set(dconv)
        else:
            dil = dconv
        wflip = jnp.transpose(w[::-1, ::-1], (0, 1, 3, 2))
        # round the walk's row grid up to a tileable count with zero
        # rows (the conv ignores them; the slice below discards them)
        eh = _dx_row_rounding(dil.shape[1] + 2)
        if eh is None:                         # pre-gated; can't happen
            raise ValueError(
                "fused 3x3 dInput kernel cannot tile this geometry — "
                "run the dense composition")
        dxp = _conv3x3_call(
            dil, wflip, jnp.ones((Cin,), jnp.float32),
            jnp.zeros((Cin,), jnp.float32), stride=1,
            pads=((2, 2 + eh), (2, 2)), relu=False,
            interpret=interpret)
        if dxp is None:                        # pre-gated; can't happen
            raise ValueError(
                "fused 3x3 dInput kernel cannot tile this geometry — "
                "run the dense composition")
        (pt, pb), (plft, prgt) = pads
        hfull, wfull = dxp.shape[1], dxp.shape[2]
        need_h, need_w = pt + H, plft + W
        # padded rows/cols the forward never read get zero grad; the
        # pad amounts are 0 whenever the walk already covers them
        dxp = jnp.pad(dxp, ((0, 0), (0, max(0, need_h - hfull)),
                            (0, max(0, need_w - wfull)), (0, 0)))
        dx = dxp[:, pt:pt + H, plft:plft + W]
        dw = _conv3x3_dw_call(x, dconv, s, pads, interpret)
        if dw is None:                         # pre-gated; can't happen
            raise ValueError(
                "fused 3x3 dWeight kernel cannot tile this geometry — "
                "run the dense composition")
        dw = dw.astype(w.dtype)
    return (dx.astype(dt), dw, dgamma32.astype(gamma.dtype),
            dbeta32.astype(beta.dtype))


@functools.lru_cache(maxsize=None)
def _train_vjp(kernel=1, stride=1, pads=((0, 0), (0, 0)), relu=True,
               eps=1e-5, interpret=True):
    """ONE cached `jax.custom_vjp` per static kernel config — the seam
    `nn/fused.py` dispatches training through. The primal runs the
    fused train forward; the vjp pairs it with the fused backward.
    Caching keeps retracing cheap and gives every ConvBNReLU with the
    same geometry the same program identity."""
    def fwd(x, w, gamma, beta):
        return _train_fwd_impl(x, w, gamma, beta, kernel=kernel,
                               stride=stride, pads=pads, relu=relu,
                               eps=eps, interpret=interpret)

    @jax.custom_vjp
    def f(x, w, gamma, beta):
        y, mean, var, _, _, _ = fwd(x, w, gamma, beta)
        return y, mean, var

    def f_fwd(x, w, gamma, beta):
        y, mean, var, conv, mean32, var32 = fwd(x, w, gamma, beta)
        return (y, mean, var), (x, w, gamma, beta, conv, mean32, var32)

    def f_bwd(res, cts):
        # the mean/var outputs feed only the stop-gradient running-stat
        # updates, so their cotangents are structurally zero — the
        # backward is driven by dy alone
        return _train_bwd_impl(kernel=kernel, stride=stride, pads=pads,
                               relu=relu, eps=eps, interpret=interpret,
                               res=res, dy=cts[0])

    f.defvjp(f_fwd, f_bwd)
    return f


def fused_conv_bn_relu_train(x, w, gamma, beta, stride=1, padding=0,
                             relu=True, eps=1e-5, interpret=None):
    """Fused conv+BN+ReLU TRAINING op, NHWC layout — the differentiable
    counterpart of `fused_conv_bn_relu`: batch-stat BN (gamma/beta are
    the learnable affine; running stats are the caller's side-channel,
    `nn/fused.py` updates them from the returned mean/var exactly like
    `nn_ops.batch_norm`). Returns (y, mean, var); differentiating y
    w.r.t. (x, w, gamma, beta) runs the fused backward kernels through
    the cached `jax.custom_vjp`. Raises ValueError on shapes
    `conv_shapes_supported` rejects or geometries
    `conv_train_geometry_tileable` cannot walk — resolve the backend
    and gate first (the `nn/fused.py` blocks do) for the clean dense
    fallback."""
    if interpret is None:
        interpret = pallas_interpret()
    kh, kw = int(w.shape[0]), int(w.shape[1])
    sh, sw = _pair(stride)
    pads = normalize_conv_padding(padding, (kh, kw), (sh, sw),
                                  in_hw=x.shape[1:3])
    if not conv_shapes_supported((kh, kw), (sh, sw), x.shape[3],
                                 w.shape[3], padding=pads):
        raise ValueError(
            f"fused conv train kernels do not cover k={kh}x{kw} "
            f"s={sh}x{sw} cin={x.shape[3]} cout={w.shape[3]} "
            f"pad={pads} — resolve the backend first and run the "
            "dense composition")
    if not conv_train_geometry_tileable((kh, kw), (sh, sw), pads,
                                        in_hw=x.shape[1:3],
                                        in_channels=x.shape[3],
                                        out_channels=w.shape[3]):
        # reject at call time, not first-grad time: the forward walk
        # or the mirrored dX walk cannot tile this geometry
        raise ValueError(
            f"fused conv train kernels cannot tile hw={x.shape[1:3]} "
            f"k={kh}x{kw} s={sh}x{sw} pad={pads} — run the dense "
            "composition")
    f = _train_vjp(kernel=kh, stride=sh, pads=pads, relu=bool(relu),
                   eps=float(eps), interpret=bool(interpret))
    CONV_PATH_STATS["pallas_train"] += 1
    return f(x, w, gamma, beta)


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

def fused_conv_bn_relu(x, w, scale, shift, stride=1, padding=0,
                       relu=True, interpret=None):
    """Fused conv+BN+ReLU through the Pallas kernels, NHWC layout.

    x `[N, H, W, Cin]`; w `[kh, kw, Cin, Cout]` (HWIO); scale/shift
    `[Cout]` — the BatchNorm affine folded to `y = conv(x)*scale +
    shift` (scale = gamma*rsqrt(var+eps), shift = beta - mean*scale).
    `padding` accepts ints / pairs / (lo, hi) pairs / "SAME"/"VALID".
    Forward-only (no VJP) — the eval/serving op; training runs
    `fused_conv_bn_relu_train` (batch stats + fused backward) via
    `nn/fused.py`. Off-TPU (or `interpret=True`) the kernels run under
    the Pallas interpreter — the CPU CI path. Raises ValueError on
    shapes `conv_shapes_supported` rejects; resolve the backend first
    (the `nn/fused.py` blocks do) for the clean dense fallback."""
    if interpret is None:
        interpret = pallas_interpret()
    kh, kw = int(w.shape[0]), int(w.shape[1])
    sh, sw = _pair(stride)
    pads = normalize_conv_padding(padding, (kh, kw), (sh, sw),
                                  in_hw=x.shape[1:3])
    if not conv_shapes_supported((kh, kw), (sh, sw), x.shape[3],
                                 w.shape[3], padding=pads):
        raise ValueError(
            f"fused conv kernels do not cover k={kh}x{kw} s={sh}x{sw} "
            f"cin={x.shape[3]} cout={w.shape[3]} pad={pads} — resolve "
            "the backend first and run the dense composition")
    scale = scale.astype(jnp.float32)
    shift = shift.astype(jnp.float32)
    if (kh, kw) == (1, 1):
        N, H, W, Cin = x.shape
        if (sh, sw) != (1, 1):
            x = x[:, ::sh, ::sw]              # exact: SAME k=1 samples
        Ho, Wo = x.shape[1], x.shape[2]
        out2 = _conv1x1_call(x.reshape(N * Ho * Wo, Cin), w[0, 0],
                             scale, shift, relu, interpret)
        out = out2.reshape(N, Ho, Wo, w.shape[3])
    else:
        out = _conv3x3_call(x, w, scale, shift, sh, pads, relu,
                            interpret)
        if out is None:
            raise ValueError(
                "fused 3x3 kernel cannot tile this geometry "
                f"(H={x.shape[1]} pad={pads} stride={sh}) — run the "
                "dense composition")
    CONV_PATH_STATS["pallas"] += 1
    return out


# ---------------------------------------------------------------------------
# tpu-verify: contracts + harvest builders
# ---------------------------------------------------------------------------

# All four kernel families (fwd + bwd) are pure programs: nothing
# donated, no collectives at any mp (TPU104 allows zero by default),
# weights ride as traced arguments (TPU102), and every tap/row matmul
# must accumulate fp32 (TPU103 walks the pallas kernel jaxprs — the
# bf16-input harvest shapes give the rule teeth, and the *_bwd
# programs put the dInput/dWeight matmuls under the same rule).
register_contract(TraceContract(
    name="conv_bn_relu_1x1",
    declared_at="paddle_tpu/ops/pallas/conv.py"))
register_contract(TraceContract(
    name="conv_bn_relu_3x3",
    declared_at="paddle_tpu/ops/pallas/conv.py"))
register_contract(TraceContract(
    name="conv_bn_relu_1x1_bwd",
    declared_at="paddle_tpu/ops/pallas/conv.py"))
register_contract(TraceContract(
    name="conv_bn_relu_3x3_bwd",
    declared_at="paddle_tpu/ops/pallas/conv.py"))

#: (contract name, config, kernel, stride, padding, N, H/W, Cin, Cout)
#: — tiny-but-structurally-real instances of every kernel family x
#: stride the suite ships; the asymmetric "SAME" stride-2 3x3 entry
#: covers the halo/padding geometry the bench sweep runs.
CONV_HARVEST_SHAPES = (
    ("conv_bn_relu_1x1", "1x1,s=1", 1, 1, 0, 2, 8, 16, 32),
    ("conv_bn_relu_1x1", "1x1,s=2", 1, 2, 0, 2, 8, 16, 32),
    ("conv_bn_relu_3x3", "3x3,s=1", 3, 1, 1, 2, 8, 16, 16),
    ("conv_bn_relu_3x3", "3x3,s=2", 3, 2, "SAME", 2, 8, 16, 16),
)

#: the backward suite: same family x stride coverage, each program the
#: FULL custom_vjp pullback (ReLU/BN chain + dInput + dWeight) of the
#: training op over bf16 activations.
CONV_BWD_HARVEST_SHAPES = (
    ("conv_bn_relu_1x1_bwd", "1x1,s=1,bwd", 1, 1, 0, 2, 8, 16, 32),
    ("conv_bn_relu_1x1_bwd", "1x1,s=2,bwd", 1, 2, 0, 2, 8, 16, 32),
    ("conv_bn_relu_3x3_bwd", "3x3,s=1,bwd", 3, 1, 1, 2, 8, 16, 16),
    ("conv_bn_relu_3x3_bwd", "3x3,s=2,bwd", 3, 2, "SAME", 2, 8, 16,
     16),
)


def _out_hw(k=1, s=1, pad=0, hw=8):
    pads = normalize_conv_padding(pad, k, s, in_hw=(hw, hw))
    return (hw + sum(pads[0]) - k) // s + 1


def _bwd_harvest_fn(k=1, s=1, pad=0):
    """The bwd harvest program: vjp of the fused training op — the
    jaxpr tpu-verify walks contains the pass-1 reductions AND both
    backward Pallas kernels."""
    def pure(x, w, gamma, beta, dy):
        def run(a, b, g, c):
            y, _, _ = fused_conv_bn_relu_train(
                a, b, g, c, stride=s, padding=pad, relu=True,
                interpret=True)
            return y
        out, vjp = jax.vjp(run, x, w, gamma, beta)
        return vjp(dy.astype(out.dtype))
    return pure


def harvest_programs():
    """-> [(name, config, pure_fn, jitted, args)] for the tpu-verify
    harvester: one jitted fused-conv program per CONV_HARVEST_SHAPES
    entry plus one full-pullback program per CONV_BWD_HARVEST_SHAPES
    entry, interpret-mode (the CPU path the gate runs), bf16 inputs so
    TPU103's narrow-operand accumulation check actually bites."""
    out = []
    for name, config, k, s, pad, n, hw, cin, cout in \
            CONV_HARVEST_SHAPES:
        pure = functools.partial(fused_conv_bn_relu, stride=s,
                                 padding=pad, relu=True,
                                 interpret=True)
        args = (jnp.zeros((n, hw, hw, cin), jnp.bfloat16),
                jnp.zeros((k, k, cin, cout), jnp.bfloat16),
                jnp.ones((cout,), jnp.float32),
                jnp.zeros((cout,), jnp.float32))
        out.append((name, config, pure, jax.jit(pure), args))
    for name, config, k, s, pad, n, hw, cin, cout in \
            CONV_BWD_HARVEST_SHAPES:
        pure = _bwd_harvest_fn(k=k, s=s, pad=pad)
        oh = _out_hw(k=k, s=s, pad=pad, hw=hw)
        args = (jnp.zeros((n, hw, hw, cin), jnp.bfloat16),
                jnp.zeros((k, k, cin, cout), jnp.bfloat16),
                jnp.ones((cout,), jnp.float32),
                jnp.zeros((cout,), jnp.float32),
                jnp.zeros((n, oh, oh, cout), jnp.float32))
        out.append((name, config, pure, jax.jit(pure), args))
    return out
