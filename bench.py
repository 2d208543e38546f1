"""Benchmark suite: training throughput on one chip, bf16, fully-compiled
TrainStep (fwd+bwd+optimizer in a single donated XLA program).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} where
vs_baseline is achieved MFU / 0.45 (the BASELINE.md target MFU).

BENCH_MODEL selects the BASELINE.md row:
  gpt      (default) GPT-3 1.3B class, tokens/s/chip      — row 3
  bert     BERT-base seq-512 fine-tune, tokens/s/chip      — row 2
  resnet50 ResNet-50 @224 synthetic data, images/s/chip    — row 1
Run all three: for m in gpt bert resnet50; do BENCH_MODEL=$m python bench.py; done
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

V5E_PEAK = 197e12  # bf16 FLOP/s per v5e chip

# ResNet-50 @224 fwd FLOPs (2*MACs, the torchvision/PaddleClas-quoted
# 4.1 GFLOPs); training fwd+bwd ~= 3x fwd.
RESNET50_FWD_FLOPS = 4.09e9


def _run_scan_steps(step, xs, ys):
    """Time xs.shape[0] training steps executed as ONE XLA program
    (lax.scan); returns (dt_seconds, compile_seconds, last_loss)."""
    t0 = time.time()
    losses = step.run_scan(xs, ys)
    losses._array.block_until_ready()
    compile_s = time.time() - t0
    t1 = time.time()
    losses = step.run_scan(xs, ys)
    losses._array.block_until_ready()
    dt = time.time() - t1
    return dt, compile_s, losses[-1]


def _run_repeat_steps(step, x, y, steps):
    """Like _run_scan_steps but feeds ONE batch repeatedly (TrainStep.
    run_repeat): a [steps, batch, 3, 224, 224] input stack would occupy
    multiple GB of HBM and starve the model (measured: batch=256 resnet
    went 61ms -> 1814ms/step purely from stacked-input pressure)."""
    t0 = time.time()
    losses = step.run_repeat(x, y, steps)
    losses._array.block_until_ready()
    compile_s = time.time() - t0
    t1 = time.time()
    losses = step.run_repeat(x, y, steps)
    losses._array.block_until_ready()
    dt = time.time() - t1
    return dt, compile_s, losses[-1]


def _emit(metric, unit, rate, flops_per_unit, on_tpu, extra):
    """Uniform result row: rate in units/s, MFU vs the BASELINE.md 0.45
    target on the v5e peak (1e12 nominal peak in CPU smoke mode).
    hbm_gb = currently-allocated device bytes after the run (the
    allocator's count on a TPU, live arrays on the CPU — see
    paddle_tpu/device/memory.py)."""
    peak = V5E_PEAK if on_tpu else 1e12
    mfu = rate * flops_per_unit / peak
    try:
        from paddle_tpu.device import memory as dmem

        hbm_gb = round(dmem.record_peak() / 1e9, 2)
    except Exception:
        hbm_gb = None
    return {
        "metric": metric,
        "value": round(rate, 1),
        "unit": unit,
        "vs_baseline": round(mfu / 0.45, 4),
    }, f"{extra} mfu={mfu:.3f} hbm_gb={hbm_gb}"


def bench_gpt(on_tpu):
    import paddle_tpu as paddle
    import paddle_tpu.jit as jit
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    if on_tpu:
        # the BASELINE.md flagship: GPT-3 1.3B class. hidden=2048/head_dim=128
        # saturates the MXU; batch 2 fits without remat — recompute-free
        # beats every remat policy measured.
        cfg = GPTConfig(vocab_size=32768, hidden_size=2048, num_layers=24,
                        num_heads=16, max_seq_len=2048, dropout=0.0)
        batch = int(os.environ.get("BENCH_BATCH", "2"))
        steps = int(os.environ.get("BENCH_STEPS", "10"))
    else:  # CPU smoke mode
        cfg = GPTConfig.tiny(vocab=512, hidden=128, layers=2, heads=4, seq=128)
        batch, steps = 2, 5

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()  # no dropout inside compiled step
    model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = jit.TrainStep(model, opt, model.loss_fn)

    seq = cfg.max_seq_len
    ids = paddle.to_tensor(
        np.random.randint(0, cfg.vocab_size, (steps, batch, seq), np.int32))
    dt, compile_s, loss = _run_scan_steps(step, ids, ids)

    tok_s = batch * seq * steps / dt
    return _emit(
        "gpt_1p3b_train_tokens_per_sec_per_chip", "tokens/s", tok_s,
        model.flops_per_token(seq), on_tpu,
        f"params={model.num_params()/1e6:.1f}M batch={batch} seq={seq} "
        f"steps={steps} compile={compile_s:.1f}s step={dt/steps*1000:.1f}ms "
        f"loss={float(loss):.3f}")


# Ceilings recorded on a v5e before PR 1 (not measured on today's
# code), for reading the numbers below in context:
# - Large-matmul FLOPs (GPT ffn shapes) sustain ~118 TF/s inside the
#   full compiled train step. The flagship's decoder attention was the
#   next-largest term (~110ms of the r3 305ms step; the tuned library
#   flash kernel runs 22.5 TF/s causal-useful at B2 H16 S2048 D128);
#   the chunked causal kernel (flash_attention.py
#   chunked_causal_attention: whole head per program, static prefix-k
#   blocks, exact softmax, single-pass bwd) runs 1.74x faster and took
#   the row from 0.61 to 0.66 MFU in r4.
# - BERT-base e2e was attention-bound in r3 (0.36 mfu): at S512/D64 the
#   library flash kernel runs 8.9 ms/layer fwd+bwd (768 tiny programs,
#   twice-recomputing backward). The fused short-seq kernel
#   (ops/pallas/flash_attention.py shortseq_attention: whole seq in
#   VMEM, 6 heads per program, single-pass 5-GEMM backward) runs 4.15
#   ms/layer, lifting the row to 0.53 mfu (r4).
# - ResNet-50's ~0.15 mfu is an HBM-bandwidth roofline, NOT a conv-
#   engine ceiling. The r4 OPBENCH sweep (fixed adaptive timing)
#   shows the convs themselves run fast — 150-280 TF/s fwd+bwd for
#   every stage-2+ shape (OPBENCH.json conv_* rows). Stage-resolved
#   e2e timing at batch 256 (truncated-model runs): layer1 36.6ms,
#   layer2 26.0ms, layer3 21.9ms, layer4 4.4ms, stem+pool+head 19.9ms.
#   A c2 bottleneck block moves ~10GB of activations fwd+bwd
#   (56x56x256 tensors through 3 convs + 3 BNs + residual), i.e.
#   ~12ms at the 819GB/s HBM peak — and measures 12.2ms: the early
#   stages run at ~90% of the bandwidth roofline. v5e's 240 FLOP/byte
#   ratio makes bf16 ResNet-50 bandwidth-bound below ~0.18 mfu at any
#   batch (remat of blocks: -3%; BN removal: -27ms, confirming BN
#   traffic as the second-largest term). 2350 img/s/chip is in line
#   with published v5e ResNet-50 numbers; throughput, not
#   mfu-vs-matmul-peak, is the comparable metric for the conv bench.
# - r5 bounded fusion attempt (the one untried lever): replacing batch
#   BN with a per-channel affine — the zero-traffic upper bound for a
#   perfect conv+BN+ReLU fusion with epilogue stats + load-time
#   normalize — takes a c2 bottleneck block fwd+bwd from 1.79 ms to
#   1.14 ms at B64 (fwd-only 0.69->0.38; the gap splits evenly fwd/
#   bwd). So full fusion could reach ~0.19-0.20 MFU, but BOTH passes
#   need conv-kernel-resident stats/normalize: scale-shift cannot fold
#   through ReLU into the next conv's weights, and XLA does not fuse
#   elementwise into conv operands on TPU — realizing it means a
#   custom Pallas conv suite (fwd+bwd), out of scope. The repo BN is
#   already the optimal XLA formulation (single-pass f32 E[x^2]-m^2
#   stats). The row's justification: HBM roofline, evidence above.


def bench_bert(on_tpu):
    import paddle_tpu as paddle
    import paddle_tpu.jit as jit
    from paddle_tpu.models import BertConfig, BertForSequenceClassification

    if on_tpu:
        cfg = BertConfig.bert_base()
        # 64 = the largest power-of-two batch that fits 16G HBM at seq 512
        batch = int(os.environ.get("BENCH_BATCH", "64"))
        seq = 512
        steps = int(os.environ.get("BENCH_STEPS", "10"))
    else:
        cfg = BertConfig.tiny()
        batch, seq, steps = 2, 64, 5
    cfg.hidden_dropout = 0.0
    cfg.attention_dropout = 0.0

    paddle.seed(0)
    model = BertForSequenceClassification(cfg)
    model.eval()
    model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=2e-5,
                                 parameters=model.parameters())
    step = jit.TrainStep(model, opt, model.loss_fn)

    ids = paddle.to_tensor(
        np.random.randint(0, cfg.vocab_size, (batch, seq), np.int32))
    labels = paddle.to_tensor(
        np.random.randint(0, cfg.num_labels, (batch,), np.int64))
    dt, compile_s, loss = _run_repeat_steps(step, ids, labels, steps)

    tok_s = batch * seq * steps / dt
    return _emit(
        "bert_base_finetune_tokens_per_sec_per_chip", "tokens/s", tok_s,
        model.flops_per_token(seq), on_tpu,
        f"params={model.num_params()/1e6:.1f}M batch={batch} seq={seq} "
        f"steps={steps} compile={compile_s:.1f}s step={dt/steps*1000:.1f}ms "
        f"loss={float(loss):.3f}")


def bench_resnet50(on_tpu):
    import paddle_tpu as paddle
    import paddle_tpu.jit as jit
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import resnet50

    if on_tpu:
        batch = int(os.environ.get("BENCH_BATCH", "256"))
        size, classes = 224, 1000
        steps = int(os.environ.get("BENCH_STEPS", "10"))
        fwd_flops = RESNET50_FWD_FLOPS
    else:
        batch, size, classes, steps = 4, 32, 10, 3
        fwd_flops = RESNET50_FWD_FLOPS * (32 / 224) ** 2

    paddle.seed(0)
    model = resnet50(num_classes=classes)
    model.to(dtype="bfloat16")
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    step = jit.TrainStep(model, opt, F.cross_entropy)

    imgs = paddle.to_tensor(np.random.uniform(
        -1, 1, (batch, 3, size, size)).astype(np.float32))
    imgs = imgs.astype("bfloat16")
    labels = paddle.to_tensor(
        np.random.randint(0, classes, (batch,), np.int64))
    dt, compile_s, loss = _run_repeat_steps(step, imgs, labels, steps)

    imgs_s = batch * steps / dt
    return _emit(
        "resnet50_train_images_per_sec_per_chip", "images/s", imgs_s,
        3 * fwd_flops, on_tpu,
        f"batch={batch} size={size} steps={steps} compile={compile_s:.1f}s "
        f"step={dt/steps*1000:.1f}ms loss={float(loss):.3f} "
        "| hbm-roofline row: early stages ~90% of bandwidth bound; "
        "r5 fusion probe: perfect conv+BN fusion caps at ~0.20 MFU — "
        "the custom conv suite now exists (ops/pallas/conv.py, eval "
        "path; BENCH_MODEL=resnet50_infer + bench_ops conv_fused_sweep "
        "measure it) and the training-graph fusion is the follow-up")


def bench_resnet50_infer(on_tpu):
    """ResNet-50 EVAL forward through the fused Pallas conv suite
    (ISSUE 14): the same synthetic-data geometry as the training row,
    served once with `conv_backend='dense'` (today's conv->BN->ReLU
    composition — the r5 fusion-probe ceiling) and once with
    `conv_backend='pallas'` (every bottleneck conv+BN+ReLU one fused
    kernel, `PADDLE_CONV_BACKEND` seam). Outputs are tolerance-
    asserted before timing; the emitted metric is the FUSED images/s,
    with the dense number in the info line. Named-row only
    (`BENCH_MODEL=resnet50_infer`) so the default three-row output —
    and the committed BENCH_BASELINE metric set — is unchanged until
    a TPU run decides a baseline for it."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.vision.models import resnet50

    if on_tpu:
        batch, size, classes = 256, 224, 1000
        steps = int(os.environ.get("BENCH_STEPS", "10"))
        fwd_flops = RESNET50_FWD_FLOPS
    else:
        batch, size, classes, steps = 4, 32, 10, 2
        fwd_flops = RESNET50_FWD_FLOPS * (32 / 224) ** 2

    imgs = np.random.uniform(-1, 1, (batch, 3, size, size)) \
        .astype(np.float32)
    x = paddle.to_tensor(imgs).astype("bfloat16")

    def serve(backend):
        paddle.seed(0)                  # identical weights per build
        model = resnet50(num_classes=classes, conv_backend=backend)
        model.to(dtype="bfloat16")
        model.eval()
        fwd = jax.jit(lambda a: model(Tensor._wrap(a))._array)
        t0 = time.time()
        out = fwd(x._array)
        np.asarray(out)                 # compile + first run
        compile_s = time.time() - t0
        t1 = time.time()
        for _ in range(steps):
            out = fwd(x._array)
        np.asarray(out)
        return out, (time.time() - t1) / steps, compile_s

    out_d, dt_d, _ = serve("dense")
    out_p, dt_p, compile_s = serve("pallas")
    ref = np.asarray(out_d, np.float32)
    got = np.asarray(out_p, np.float32)
    err = np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-6)
    from bench_ops import CONV_FUSED_REL_TOL

    assert err <= CONV_FUSED_REL_TOL, \
        f"fused eval diverged from dense ({err:.4f}, budget " \
        f"{CONV_FUSED_REL_TOL})"
    imgs_s = batch / dt_p
    return _emit(
        "resnet50_infer_images_per_sec_per_chip", "images/s", imgs_s,
        fwd_flops, on_tpu,
        f"batch={batch} size={size} compile={compile_s:.1f}s "
        f"fused={dt_p*1000:.1f}ms dense={dt_d*1000:.1f}ms "
        f"dense_images_s={batch/dt_d:.0f} rel_err={err:.4f}")


def bench_resnet50_train(on_tpu):
    """ResNet-50 TRAINING through the fused Pallas conv suite
    (ISSUE 16): the same TrainStep geometry as the tracked `resnet50`
    row, run once with `conv_backend='dense'` (the composition the
    pre-PR-1 0.152-MFU record and its ~0.20 perfect-fusion ceiling
    were taken on) and once with `conv_backend='pallas'` (all 52
    bottleneck/downsample convs through the fused custom_vjp — fused
    forward epilogue stats AND fused dInput/dWeight backward).
    First-step losses (identical weights, pre-update) are tolerance-
    asserted before timing; the emitted metric is the FUSED images/s
    with the dense number in the info line. Named-row only
    (`BENCH_MODEL=resnet50_train`) so the committed BENCH_BASELINE
    metric set is unchanged until a TPU `--save` refresh adopts it —
    this is the row that shows whether training moved past the
    fusion ceiling."""
    import paddle_tpu as paddle
    import paddle_tpu.jit as jit
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import resnet50

    if on_tpu:
        batch = int(os.environ.get("BENCH_BATCH", "256"))
        size, classes = 224, 1000
        steps = int(os.environ.get("BENCH_STEPS", "10"))
        fwd_flops = RESNET50_FWD_FLOPS
    else:
        batch, size, classes, steps = 2, 32, 10, 2
        fwd_flops = RESNET50_FWD_FLOPS * (32 / 224) ** 2

    imgs_np = np.random.uniform(
        -1, 1, (batch, 3, size, size)).astype(np.float32)
    labels = paddle.to_tensor(
        np.random.randint(0, classes, (batch,), np.int64))

    def train(backend):
        paddle.seed(0)                  # identical weights per build
        model = resnet50(num_classes=classes, conv_backend=backend)
        model.to(dtype="bfloat16")
        opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                        parameters=model.parameters())
        step = jit.TrainStep(model, opt, F.cross_entropy)
        imgs = paddle.to_tensor(imgs_np).astype("bfloat16")
        t0 = time.time()
        first = float(step(imgs, labels))     # compile + step 1
        compile_s = time.time() - t0
        dt, _, loss = _run_repeat_steps(step, imgs, labels, steps)
        return first, float(loss), dt, compile_s

    first_d, _, dt_d, _ = train("dense")
    first_p, loss_p, dt_p, compile_s = train("pallas")
    from bench_ops import CONV_FUSED_REL_TOL

    err = abs(first_p - first_d) / max(abs(first_d), 1e-6)
    assert err <= CONV_FUSED_REL_TOL, \
        f"fused first-step loss diverged from dense ({err:.4f}, " \
        f"budget {CONV_FUSED_REL_TOL})"
    imgs_s = batch * steps / dt_p
    return _emit(
        "resnet50_train_fused_images_per_sec_per_chip", "images/s",
        imgs_s, 3 * fwd_flops, on_tpu,
        f"batch={batch} size={size} steps={steps} "
        f"compile={compile_s:.1f}s step={dt_p/steps*1000:.1f}ms "
        f"dense_step={dt_d/steps*1000:.1f}ms "
        f"dense_images_s={batch*steps/dt_d:.0f} loss={loss_p:.3f} "
        f"first_loss_rel_err={err:.4f}")


def main():
    import jax

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    which = os.environ.get("BENCH_MODEL", "all")
    table = {"gpt": bench_gpt, "bert": bench_bert,
             "resnet50": bench_resnet50,
             "resnet50_infer": bench_resnet50_infer,
             "resnet50_train": bench_resnet50_train}
    if which == "all":
        # every BASELINE.md model row, one JSON line each — the GPT
        # flagship LAST so a last-line parser still reads the headline
        order = ["bert", "resnet50", "gpt"]
    elif which in table:
        order = [which]
    else:
        sys.exit(f"unknown BENCH_MODEL={which!r}; valid: "
                 f"{sorted(table)} or 'all'")
    any_failed = False
    for name in order:
        try:
            result, info = table[name](on_tpu)
        except Exception as e:  # one broken row must not hide the rest
            print(f"# {name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
            # explicit failure row in-position: a last-line parser can
            # never mistake an earlier model's row for the flagship
            print(json.dumps({"metric": f"{name}_FAILED", "value": 0,
                              "unit": "error", "vs_baseline": 0.0}),
                  flush=True)
            any_failed = True
            if len(order) == 1:
                raise
            continue
        print(json.dumps(result), flush=True)
        print(f"# backend={backend} {info}", file=sys.stderr)
    if any_failed:
        sys.exit(1)


if __name__ == "__main__":
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
