"""Per-op microbenchmark harness — analog of the reference's op_tester
(paddle/fluid/operators/benchmark/op_tester.cc) + ci benchmark gate.

Times a fixed suite of core ops as jitted XLA programs on the current
backend, prints one JSON
line per op, and can gate regressions against a stored baseline:

    python bench_ops.py                         # run + print
    python bench_ops.py --save OPBENCH.json     # record baseline
    python bench_ops.py --check OPBENCH.json    # exit 1 on >25% regress
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, *args, n_small=8, target_s=0.4, n_cap=1 << 15):
    """In-program timing, independent of per-dispatch latency. Each
    measurement runs N iterations of the op INSIDE one program — a
    lax.fori_loop with N as a DYNAMIC argument, so one compilation
    serves every N (inputs salted per-iteration so nothing is
    loop-invariant, outputs folded into a scalar carry so every
    iteration is on the data path). N grows adaptively until the
    in-loop time rises far above the dispatch jitter (>= target_s),
    then the slope between N_small and N_big cancels the fixed
    overhead. Micro-ops (tens of us) need thousands of iterations to
    clear the noise floor — a static-N scan would recompile per N,
    which is why the loop bound must be dynamic."""

    def salted(a, s):
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.inexact):
            return a + s.astype(a.dtype)
        return a

    def scalarize(out):
        leaves = jax.tree_util.tree_leaves(out)
        return sum(jnp.sum(l).astype(jnp.float32) for l in leaves
                   if hasattr(l, "dtype") and
                   jnp.issubdtype(l.dtype, jnp.inexact))

    @jax.jit
    def many(salt, args, n):
        def body(i, c):
            varied = tuple(salted(a, (i.astype(jnp.float32) + salt))
                           for a in args)
            return c + scalarize(fn(*varied))
        return jax.lax.fori_loop(0, n, body, jnp.float32(0.0))

    def run_once(salt, n):
        t0 = time.perf_counter()
        float(many(jnp.float32(salt), args, jnp.int32(n)))
        return time.perf_counter() - t0

    salt = [0.0]

    def best(n, reps=3):
        ts = []
        for _ in range(reps):
            salt[0] += 1.0
            ts.append(run_once(salt[0], n))
        return min(ts)

    best(n_small, reps=1)  # compile (one program serves every n)
    n_big = max(4 * n_small, 128)
    while n_big < n_cap and best(n_big, reps=1) < target_s:
        n_big *= 2
    t_small, t_big = best(n_small), best(n_big)
    slope = (t_big - t_small) / (n_big - n_small)
    if slope <= 0:  # below the noise floor even at n_cap
        slope = t_big / n_big
    return slope * 1e3  # ms


def _rand(shape, dtype=jnp.bfloat16, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32) \
        .astype(dtype)


# every suite() row, in order — kept literal so tooling that only needs
# the NAMES (check_bench_result --pending) doesn't pay suite()'s eager
# input allocation + backend init; test_engine_offered_load_bench_
# runner_tiny asserts it matches suite() exactly, so it cannot drift
SUITE_ROWS = (
    "matmul_4096_bf16", "conv2d_7x7_s2",
    "conv_c2_1x1_64_256", "conv_c2_3x3_64", "conv_c3_3x3_128_s2",
    "conv_c3_3x3_128", "conv_c4_3x3_256_s2", "conv_c4_3x3_256",
    "conv_c5_3x3_512_s2", "conv_c5_3x3_512", "conv_c5_1x1_512_2048",
    "flash_attention_2k", "layernorm_2048", "softmax_xent_50k",
    "embedding_50k", "reduce_sum_64M", "gpt_decode_kv_32tok",
    "gpt_decode_kv_350m", "gpt_engine_offered_load",
    "paged_attention_decode_sweep", "gpt_engine_offered_load_pallas",
    "gpt_engine_prefix_cache",
    "gpt_engine_speculative", "gpt_engine_offered_load_mp2",
    "gpt_engine_offered_load_int8", "gpt_fleet_offered_load",
    "gpt_engine_multitenant_lora", "gpt_engine_sampling",
    "conv_fused_sweep", "resnet50_fused_block",
    "conv_fused_bwd_sweep", "resnet50_fused_block_train",
    "gpt_engine_host_gap", "gpt_engine_async_overlap",
)


def suite_names():
    """Row names without building any case (cheap to import + call)."""
    return list(SUITE_ROWS)


def suite():
    """name -> (fn, args, flops-or-None). Shapes sized for one chip."""
    import paddle_tpu  # noqa: F401  (registers pallas kernels)
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    B, S, H, D = 4, 2048, 16, 128
    M = 4096
    cases = {}

    x = _rand((M, M))
    w = _rand((M, M), seed=1)
    cases["matmul_4096_bf16"] = (
        jax.jit(lambda a, b: a @ b), (x, w), 2 * M ** 3)

    img = _rand((32, 224, 224, 3))
    ker = _rand((7, 7, 3, 64), seed=2)
    cases["conv2d_7x7_s2"] = (
        jax.jit(lambda i, k: jax.lax.conv_general_dilated(
            i, k, (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))),
        (img, ker),
        2 * 32 * 112 * 112 * 64 * 7 * 7 * 3)

    # ResNet-50 conv-shape sweep (VERDICT r3 weak #2): every distinct
    # (kernel, stride, width, resolution) class in the network, batch 32.
    # This is the evidence base for the "conv ceiling" reading of the
    # resnet50 bench row: if any of these clears well above ~43 TF/s the
    # stem/stage strategy should be revisited. Reference analog:
    # paddle/fluid/operators/benchmark/op_tester.cc config sweeps.
    def conv_case(name, n, hw, cin, cout, k, s):
        i = _rand((n, hw, hw, cin))
        # crc32, not hash(): str hash is randomized per process and
        # would make the sweep's inputs differ run-to-run
        w = _rand((k, k, cin, cout), seed=zlib.crc32(name.encode()) % 97)
        ho = hw // s
        cases[name] = (
            jax.jit(lambda a, b: jax.lax.conv_general_dilated(
                a, b, (s, s), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))),
            (i, w), 2 * n * ho * ho * cout * k * k * cin)

    conv_case("conv_c2_1x1_64_256", 32, 56, 64, 256, 1, 1)
    conv_case("conv_c2_3x3_64", 32, 56, 64, 64, 3, 1)
    conv_case("conv_c3_3x3_128_s2", 32, 56, 128, 128, 3, 2)
    conv_case("conv_c3_3x3_128", 32, 28, 128, 128, 3, 1)
    conv_case("conv_c4_3x3_256_s2", 32, 28, 256, 256, 3, 2)
    conv_case("conv_c4_3x3_256", 32, 14, 256, 256, 3, 1)
    conv_case("conv_c5_3x3_512_s2", 32, 14, 512, 512, 3, 2)
    conv_case("conv_c5_3x3_512", 32, 7, 512, 512, 3, 1)
    conv_case("conv_c5_1x1_512_2048", 32, 7, 512, 2048, 1, 1)

    q = _rand((B, S, H, D))
    k = _rand((B, S, H, D), seed=3)
    v = _rand((B, S, H, D), seed=4)
    cases["flash_attention_2k"] = (
        jax.jit(lambda a, b, c: flash_attention(a, b, c, causal=True)),
        (q, k, v), 4 * B * H * S * S * D // 2)

    h = _rand((B * S, M // 2))
    g = _rand((M // 2,))
    b2 = _rand((M // 2,), seed=5)
    cases["layernorm_2048"] = (
        jax.jit(lambda a, gg, bb: (a - a.mean(-1, keepdims=True))
                / jnp.sqrt(a.var(-1, keepdims=True) + 1e-5) * gg + bb),
        (h, g, b2), None)

    logits = _rand((2048, 50304), jnp.float32)
    cases["softmax_xent_50k"] = (
        jax.jit(lambda lg: -jax.nn.log_softmax(lg)[:, 0].mean()),
        (logits,), None)

    tbl = _rand((50304, 2048))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 50304, B * S))
    cases["embedding_50k"] = (
        jax.jit(lambda t, i: t[i]), (tbl, ids), None)

    big = _rand((64, 1 << 20))
    cases["reduce_sum_64M"] = (
        jax.jit(lambda a: a.astype(jnp.float32).sum()), (big,), None)

    cases["gpt_decode_kv_32tok"] = _decode_case()
    # heavy inference rows build lazily: suite() stays cheap to enumerate
    # (CPU CI imports it), run() resolves the callables when measuring
    cases["gpt_decode_kv_350m"] = _decode_350m_case
    cases["gpt_engine_offered_load"] = _engine_offered_load_case()
    cases["paged_attention_decode_sweep"] = _paged_attention_sweep_case()
    cases["gpt_engine_offered_load_pallas"] = _engine_offered_load_case(
        attention_backend="pallas")
    cases["gpt_engine_prefix_cache"] = _engine_prefix_cache_case()
    cases["gpt_engine_speculative"] = _engine_speculative_case()
    cases["gpt_engine_offered_load_mp2"] = _engine_offered_load_case(
        mp_degree=2)
    cases["gpt_engine_offered_load_int8"] = _engine_offered_load_case(
        kv_dtype="int8")
    cases["gpt_fleet_offered_load"] = _fleet_offered_load_case()
    cases["gpt_engine_multitenant_lora"] = \
        _engine_multitenant_lora_case()
    cases["gpt_engine_sampling"] = _engine_sampling_case()
    cases["conv_fused_sweep"] = _conv_fused_sweep_case()
    cases["resnet50_fused_block"] = _resnet50_fused_block_case()
    cases["conv_fused_bwd_sweep"] = _conv_fused_bwd_sweep_case()
    cases["resnet50_fused_block_train"] = \
        _resnet50_fused_block_train_case()
    cases["gpt_engine_host_gap"] = _engine_host_gap_case()
    cases["gpt_engine_async_overlap"] = _engine_async_overlap_case()
    # every suite() caller trips on drift immediately, not just the one
    # CI test — SUITE_ROWS must stay the cheap names-only mirror
    assert tuple(cases) == SUITE_ROWS, \
        "bench_ops.SUITE_ROWS is out of sync with suite(); update it"
    return cases


def _decode_case():
    """KV-cache greedy-decode throughput (VERDICT r4 next #8): a small
    GPT config (~21M params — the 1.3B cached-decode program takes
    >10 min through the remote compiler, so the tracked number lives
    here) decoding 32 new tokens per call through the SAME compiled
    fixed-buffer lax.while_loop path the big model uses
    (models/gpt.py _generate_cached). The fn takes a FLOAT fuzz input
    (so _timeit's per-iteration salting varies the prompt — int inputs
    aren't salted and XLA would hoist a constant decode out of the
    timing loop) and returns the tokens as float (so they land in the
    scalarized carry). rec extra: tokens per call for tokens/s."""
    import paddle_tpu  # noqa: F401  (registers ops)
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    B, S0, L, vocab = 4, 16, 48, 4096
    cfg = GPTConfig(vocab_size=vocab, hidden_size=512, num_layers=6,
                    num_heads=8, max_seq_len=L)
    model = GPTForCausalLM(cfg)
    model.eval()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    new_tokens = L - S0

    def decode(fuzz):
        ids = (jnp.abs(fuzz).astype(jnp.int32) % vocab)
        toks = model.generate(Tensor._wrap(ids), max_length=L,
                              use_cache=True)
        return toks._array.astype(jnp.float32)

    fuzz = jnp.abs(_rand((B, S0), jnp.float32, seed=11)) * 997.0
    flops = 2 * n_params * B * new_tokens  # matmul-dominated decode
    return (decode, (fuzz,), flops, {"tokens": B * new_tokens})


def _decode_350m_case():
    """The VERDICT r5 next-#9 representative decode row: GPT-medium
    (~350M params — the published GPT-2-medium shape) decoding 256 new
    tokens per call for a batch of 8 through the compiled fixed-buffer
    lax.while_loop KV-cache path, timed inside _timeit's dynamic-N
    fori_loop like every other row. Supersedes the 21M 32-token toy as
    the single-program decode health number (the toy stays for cheap
    CPU coverage of the code path). Same float-fuzz prompt trick as
    _decode_case so nothing is loop-invariant."""
    import numpy as np

    import paddle_tpu  # noqa: F401  (registers ops)
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    B, S0, L, vocab = 8, 128, 384, 50304
    cfg = GPTConfig(vocab_size=vocab, hidden_size=1024, num_layers=24,
                    num_heads=16, max_seq_len=L)
    model = GPTForCausalLM(cfg)
    model.eval()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    new_tokens = L - S0

    def decode(fuzz):
        ids = (jnp.abs(fuzz).astype(jnp.int32) % vocab)
        toks = model.generate(Tensor._wrap(ids), max_length=L,
                              use_cache=True)
        return toks._array.astype(jnp.float32)

    fuzz = jnp.abs(_rand((B, S0), jnp.float32, seed=13)) * 9973.0
    flops = 2 * n_params * B * new_tokens
    return (decode, (fuzz,), flops, {"tokens": B * new_tokens})


def _paged_attention_sweep_case(num_slots=8, heads=16, head_dim=128,
                                block_size=16, max_model_len=2048,
                                ctx_lengths=(128, 512, 2048),
                                backends=("dense", "pallas"),
                                dtype=None, seed=17):
    """ISSUE-3 paged-attention microbench: one decode-attention step
    (fused KV write + attention over the slot's cached context) at a
    FIXED max_model_len while the ACTIVE context sweeps `ctx_lengths`,
    timed per backend. The sweep is the O(active-context) evidence the
    tentpole claims: the dense fallback's per-step time must track the
    active-context high-water mark (its fori_loop trip count), not sit
    flat at the max_model_len cost PR 1's full-table gather paid, and
    the pallas kernel must track it with a lower slope (per-slot
    block streaming instead of a batch gather). Headline `ms` is the
    pallas full-context time — the fused kernel is what this row
    tracks; the per-backend curves ride in the record. The int8
    curves (`<backend>_int8_ms_by_ctx`, PR 11) run the SAME sweep
    against int8 per-block-quantized pools + scales — the
    streamed-bytes halving the quantized KV cache claims, visible as
    a flatter dense slope and a cheaper pallas walk on TPU.
    Lazy-built like every heavy inference row; tests call it at a
    tiny shape (pallas runs interpreted off-TPU)."""

    def run_bench():
        import paddle_tpu  # noqa: F401  (registers ops)
        from paddle_tpu.ops.paged_attention import (KV_QUANT_EPS,
                                                    paged_attention_step)

        dt = dtype or jnp.bfloat16
        max_blocks = max(max_model_len // block_size, 1)
        num_blocks = 1 + num_slots * max_blocks
        L = 1                            # one layer plane: the op cost
        kpool = _rand((L, num_blocks, block_size, heads, head_dim), dt,
                      seed=seed)
        vpool = _rand((L, num_blocks, block_size, heads, head_dim), dt,
                      seed=seed + 1)

        def quantize_pool(pool):
            arr = pool.astype(jnp.float32)
            s = jnp.maximum(
                jnp.max(jnp.abs(arr), axis=(2, 3, 4)) / 127.0,
                KV_QUANT_EPS)                        # [L, blocks]
            q = jnp.clip(jnp.round(arr / s[:, :, None, None, None]),
                         -127, 127).astype(jnp.int8)
            return q, s

        kq, ks = quantize_pool(kpool)
        vq, vs = quantize_pool(vpool)
        kpool_q, vpool_q = kq, vq
        scales_q = jnp.stack([ks, vs], axis=-1)      # [L, blocks, 2]
        # disjoint per-slot tables covering the whole budget; the sweep
        # only moves `positions`, so every backend sees the same layout
        tables = 1 + np.arange(num_slots * max_blocks, dtype=np.int32) \
            .reshape(num_slots, max_blocks)
        q = _rand((num_slots, 1, heads, head_dim), dt, seed=seed + 2)
        k_new = _rand((num_slots, 1, heads, head_dim), dt, seed=seed + 3)
        v_new = _rand((num_slots, 1, heads, head_dim), dt, seed=seed + 4)

        curves = {b: {} for b in backends}
        curves_q = {b: {} for b in backends}
        for ctx in ctx_lengths:
            positions = np.full(num_slots, ctx - 1, np.int32)
            for b in backends:
                # pools ride in the closure (the _decode_350m_case
                # idiom), NOT as _timeit args: salting them would add
                # an O(pool-size) element-wise pass per iteration that
                # swamps the O(active-context) attention traffic this
                # row exists to expose; q/k/v salting alone keeps the
                # step off the loop-invariant path
                def step(qa, ka, va, _b=b, _pos=positions):
                    out, _, _ = paged_attention_step(
                        qa, ka, va, kpool, vpool, 0, tables, _pos,
                        backend=_b)
                    return out._array
                ms = _timeit(step, q, k_new, v_new)
                curves[b][str(ctx)] = round(ms, 4)

                def step_q(qa, ka, va, _b=b, _pos=positions):
                    out, _, _, _ = paged_attention_step(
                        qa, ka, va, kpool_q, vpool_q, 0, tables,
                        _pos, backend=_b, scales=scales_q)
                    return out._array
                ms = _timeit(step_q, q, k_new, v_new)
                curves_q[b][str(ctx)] = round(ms, 4)
        head = "pallas" if "pallas" in curves else backends[0]
        rec = {"ms": curves[head][str(ctx_lengths[-1])],
               "max_model_len": max_model_len,
               "block_size": block_size}
        for b in backends:
            rec[f"{b}_ms_by_ctx"] = curves[b]
            rec[f"{b}_int8_ms_by_ctx"] = curves_q[b]
        return rec

    return run_bench


#: The nine ResNet-50 sweep shapes (name, hw, cin, cout, k, s) the
#: conv_case rows above measure through lax.conv_general_dilated —
#: the fused-vs-dense row runs the SAME geometry through both paths.
CONV_SWEEP_SHAPES = (
    ("conv_c2_1x1_64_256", 56, 64, 256, 1, 1),
    ("conv_c2_3x3_64", 56, 64, 64, 3, 1),
    ("conv_c3_3x3_128_s2", 56, 128, 128, 3, 2),
    ("conv_c3_3x3_128", 28, 128, 128, 3, 1),
    ("conv_c4_3x3_256_s2", 28, 256, 256, 3, 2),
    ("conv_c4_3x3_256", 14, 256, 256, 3, 1),
    ("conv_c5_3x3_512_s2", 14, 512, 512, 3, 2),
    ("conv_c5_3x3_512", 7, 512, 512, 3, 1),
    ("conv_c5_1x1_512_2048", 7, 512, 2048, 1, 1),
)

#: Documented numeric budget for the fused conv suite (ISSUE 14): the
#: fused Pallas conv+BN+ReLU output must agree with the dense
#: lax.conv_general_dilated composition within this relative-Linf
#: tolerance at bf16 inputs (both paths accumulate fp32 and cast
#: once; only reduction order differs). README "Pallas conv suite"
#: states the policy; tests/test_pallas_conv.py enforces it per
#: sweep shape, fp32 at a far tighter bound.
CONV_FUSED_REL_TOL = 0.03


def _conv_rel_err(got, ref):
    import jax.numpy as jnp

    g = jnp.asarray(got, jnp.float32)
    r = jnp.asarray(ref, jnp.float32)
    denom = jnp.maximum(jnp.max(jnp.abs(r)), 1e-6)
    return float(jnp.max(jnp.abs(g - r)) / denom)


def _conv_rel_err_l2(got, ref):
    """Relative L2 error — the GRADIENT metric: bf16 rounding feeds
    sign-cancelling sums in dInput/dWeight, so per-element Linf
    deviations run ~10x the aggregate error for BOTH the fused and
    the dense backward (each sits the same L2 distance from the fp32
    truth; DESIGN_DECISIONS r19). The Linf metric stays the forward
    budget, where no cancellation exists."""
    import jax.numpy as jnp

    g = jnp.asarray(got, jnp.float32)
    r = jnp.asarray(ref, jnp.float32)
    denom = jnp.maximum(jnp.linalg.norm(r), 1e-6)
    return float(jnp.linalg.norm(g - r) / denom)


def _conv_fused_sweep_case(shapes=None, batch=32, dtype=None,
                           seed=23):
    """ISSUE-14 fused-conv microbench: every ResNet sweep shape run
    through BOTH paths — the dense `lax.conv_general_dilated` + BN
    scale/shift + ReLU composition (one jitted program: XLA's best
    fusion, the r5 probe's ceiling) and the fused Pallas kernel
    (`ops/pallas/conv.py`, interpret off-TPU) — with the outputs
    tolerance-asserted in-runner before anything is timed. The per-
    shape dense/fused ms + TFLOP/s curves are the evidence the
    tentpole claims: on TPU the fused kernels must close the 24-76 vs
    184 TFLOP/s matmul gap the sweep rows above measure. Headline
    `ms` is the fused time of the worst matmul-gap row
    (conv_c2_1x1_64_256). Lazy-built; tests call it at tiny shapes
    (the interpreter is the off-TPU path)."""

    def run_bench():
        import paddle_tpu  # noqa: F401  (registers pallas kernels)
        from paddle_tpu.core.device import pallas_interpret
        from paddle_tpu.ops.pallas.conv import (conv_bn_relu_reference,
                                                fused_conv_bn_relu)

        if os.environ.get("PADDLE_CONV_BACKEND"):
            # the row compares the two paths by name; an env override
            # rerouting either side would record a lie under it
            raise RuntimeError(
                "unset PADDLE_CONV_BACKEND to run the fused-vs-dense "
                "sweep")
        dt = dtype or jnp.bfloat16
        interpret = pallas_interpret()
        rows = shapes or CONV_SWEEP_SHAPES
        curves, head_ms = {}, None
        for name, hw, cin, cout, k, s in rows:
            x = _rand((batch, hw, hw, cin), dt,
                      seed=zlib.crc32(name.encode()) % 89 + seed)
            w = _rand((k, k, cin, cout), dt, seed=seed + 1) * 0.1
            scale = jnp.abs(_rand((cout,), jnp.float32, seed=seed + 2)) \
                + 0.5
            shift = _rand((cout,), jnp.float32, seed=seed + 3)

            dense = jax.jit(lambda a, b, sc, sh, _s=s:
                            conv_bn_relu_reference(a, b, sc, sh,
                                                   stride=_s,
                                                   padding="SAME"))
            fused = jax.jit(lambda a, b, sc, sh, _s=s:
                            fused_conv_bn_relu(a, b, sc, sh, stride=_s,
                                               padding="SAME",
                                               interpret=interpret))
            err = _conv_rel_err(fused(x, w, scale, shift),
                                dense(x, w, scale, shift))
            assert err <= CONV_FUSED_REL_TOL, \
                (f"{name}: fused output diverges from the dense "
                 f"composition (rel err {err:.4f}, budget "
                 f"{CONV_FUSED_REL_TOL})")
            dense_ms = _timeit(dense, x, w, scale, shift)
            fused_ms = _timeit(fused, x, w, scale, shift)
            ho = hw // s
            flops = 2 * batch * ho * ho * cout * k * k * cin
            curves[name] = {
                "dense_ms": round(dense_ms, 4),
                "fused_ms": round(fused_ms, 4),
                "dense_tflops": round(flops / (dense_ms / 1e3) / 1e12,
                                      2),
                "fused_tflops": round(flops / (fused_ms / 1e3) / 1e12,
                                      2),
                "rel_err": round(err, 5)}
            if head_ms is None or name == "conv_c2_1x1_64_256":
                head_ms = fused_ms
        return {"ms": round(head_ms, 4), "batch": batch,
                "shapes": curves}

    return run_bench


def _resnet50_fused_block_case(batch=32, hw=56, inplanes=256,
                               planes=64, dtype="bfloat16", seed=29):
    """ISSUE-14 block-level row: one ResNet-50 stage-2 BottleneckBlock
    (1x1 256->64, 3x3 64->64, 1x1 64->256 + residual) served in eval
    mode through BOTH conv backends — `pallas` (every conv+BN+ReLU one
    fused kernel) and `dense` (today's composition, the exactness
    foil) — outputs tolerance-asserted in-runner, both forward times
    recorded. This is the end-to-end shape the MFU plateau lives in:
    three bandwidth-bound convs whose BN/ReLU re-reads the fused path
    deletes. Off-TPU the kernels run interpreted (structure only);
    the TPU refresh gives the measured speedup."""

    def run_bench():
        import paddle_tpu as paddle
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.vision.models.resnet import BottleneckBlock

        if os.environ.get("PADDLE_CONV_BACKEND"):
            raise RuntimeError(
                "unset PADDLE_CONV_BACKEND to run the fused-vs-dense "
                "block row")

        def build(backend):
            paddle.seed(seed)            # identical weights per build
            blk = BottleneckBlock(inplanes, planes,
                                  conv_backend=backend)
            if dtype == "bfloat16":
                blk.to(dtype="bfloat16")
            blk.eval()
            return blk

        x = _rand((batch, inplanes, hw, hw),
                  jnp.bfloat16 if dtype == "bfloat16" else jnp.float32,
                  seed=seed)

        def timed(blk):
            fn = jax.jit(lambda a: blk(Tensor._wrap(a))._array)
            out = fn(x)
            return out, _timeit(fn, x)

        out_d, dense_ms = timed(build("dense"))
        out_p, fused_ms = timed(build("pallas"))
        err = _conv_rel_err(out_p, out_d)
        assert err <= CONV_FUSED_REL_TOL, \
            (f"fused block diverges from dense (rel err {err:.4f}, "
             f"budget {CONV_FUSED_REL_TOL})")
        width = planes
        flops = 2 * batch * hw * hw * (
            inplanes * width + width * width * 9 + width * inplanes)
        return {"ms": round(fused_ms, 4),
                "dense_ms": round(dense_ms, 4),
                "speedup_vs_dense": round(dense_ms / fused_ms, 3),
                "tflops": round(flops / (fused_ms / 1e3) / 1e12, 2),
                "rel_err": round(err, 5),
                "batch": batch, "hw": hw}

    return run_bench


def _conv_fused_bwd_sweep_case(shapes=None, batch=32, dtype=None,
                               seed=41):
    """ISSUE-16 backward microbench: every ResNet sweep shape's full
    train-mode grad program — forward + dInput + dWeight + BN-param
    grads — run through BOTH paths: `jax.vjp` of the dense
    differentiable composition (`conv_bn_relu_train_reference`, XLA's
    best training-graph fusion, the ~0.20-MFU ceiling of the r5
    probe) and the fused `custom_vjp` op (`fused_conv_bn_relu_train`:
    stats-in-epilogue forward, two-pass Pallas backward). All four
    gradients are tolerance-asserted in-runner before timing. FLOPs
    count the three convolutions a grad step performs (fwd, dX, dW).
    Headline `ms` is the fused grad time of the worst matmul-gap row
    (conv_c2_1x1_64_256). Lazy-built; tests call it at tiny shapes
    (the interpreter is the off-TPU path)."""

    def run_bench():
        import paddle_tpu  # noqa: F401  (registers pallas kernels)
        from paddle_tpu.core.device import pallas_interpret
        from paddle_tpu.ops.pallas.conv import (
            conv_bn_relu_train_reference, fused_conv_bn_relu_train)

        if os.environ.get("PADDLE_CONV_BACKEND"):
            raise RuntimeError(
                "unset PADDLE_CONV_BACKEND to run the fused-vs-dense "
                "bwd sweep")
        dt = dtype or jnp.bfloat16
        interpret = pallas_interpret()
        rows = shapes or CONV_SWEEP_SHAPES
        curves, head_ms = {}, None
        for name, hw, cin, cout, k, s in rows:
            x = _rand((batch, hw, hw, cin), dt,
                      seed=zlib.crc32(name.encode()) % 83 + seed)
            w = _rand((k, k, cin, cout), dt, seed=seed + 1) * 0.1
            gamma = jnp.abs(_rand((cout,), jnp.float32,
                                  seed=seed + 2)) + 0.5
            beta = _rand((cout,), jnp.float32, seed=seed + 3)
            ho = hw // s
            # both paths emit the fp32-affine output dtype, so the
            # incoming cotangent is fp32 for either
            dy = _rand((batch, ho, ho, cout), jnp.float32,
                       seed=seed + 4)

            def make_grads(fn):
                def run(a, b, g2, b2, ct):
                    _, vjp = jax.vjp(lambda *ar: fn(*ar)[0],
                                     a, b, g2, b2)
                    return vjp(ct)
                return jax.jit(run)

            dense = make_grads(
                lambda a, b, g2, b2, _s=s: conv_bn_relu_train_reference(
                    a, b, g2, b2, stride=_s, padding="SAME"))
            fused = make_grads(
                lambda a, b, g2, b2, _s=s: fused_conv_bn_relu_train(
                    a, b, g2, b2, stride=_s, padding="SAME",
                    interpret=interpret))
            ref = dense(x, w, gamma, beta, dy)
            got = fused(x, w, gamma, beta, dy)
            err = max(_conv_rel_err_l2(g, r)
                      for g, r in zip(got, ref))
            assert err <= CONV_FUSED_REL_TOL, \
                (f"{name}: fused gradients diverge from the dense "
                 f"composition (rel err {err:.4f}, budget "
                 f"{CONV_FUSED_REL_TOL})")
            dense_ms = _timeit(dense, x, w, gamma, beta, dy)
            fused_ms = _timeit(fused, x, w, gamma, beta, dy)
            flops = 3 * 2 * batch * ho * ho * cout * k * k * cin
            curves[name] = {
                "dense_ms": round(dense_ms, 4),
                "fused_ms": round(fused_ms, 4),
                "dense_tflops": round(flops / (dense_ms / 1e3) / 1e12,
                                      2),
                "fused_tflops": round(flops / (fused_ms / 1e3) / 1e12,
                                      2),
                "rel_err": round(err, 5)}
            if head_ms is None or name == "conv_c2_1x1_64_256":
                head_ms = fused_ms
        return {"ms": round(head_ms, 4), "batch": batch,
                "shapes": curves}

    return run_bench


def _resnet50_fused_block_train_case(batch=32, hw=56, inplanes=256,
                                     planes=64, seed=43, steps=10):
    """ISSUE-16 block-level training row: one ResNet-50 stage-2
    BottleneckBlock run through a full compiled `jit.TrainStep`
    (fwd + bwd + SGD update, one donated XLA program) with
    `conv_backend='dense'` (today's training composition — the
    hbm-roofline wall a pre-PR-1 record put at 0.152 MFU) and
    `conv_backend='pallas'` (all four conv+BN+ReLU stacks through the
    fused custom_vjp, forward AND backward). Losses after one
    identical-weights step are tolerance-asserted before timing; both
    per-step times are recorded. This is the row structured to show
    training moving past the ~0.20 fusion ceiling on the next TPU
    `--save` refresh. fp32 (TrainStep's eager-parity dtype); the
    full-model bf16 number is BENCH_MODEL=resnet50_train."""

    def run_bench():
        import numpy as np

        import paddle_tpu as paddle
        import paddle_tpu.jit as jit
        from paddle_tpu.vision.models.resnet import BottleneckBlock

        if os.environ.get("PADDLE_CONV_BACKEND"):
            raise RuntimeError(
                "unset PADDLE_CONV_BACKEND to run the fused-vs-dense "
                "train row")

        xnp = np.random.RandomState(seed) \
            .randn(batch, inplanes, hw, hw).astype(np.float32)
        label = paddle.to_tensor(np.zeros(1, np.float32))

        def build_step(backend):
            paddle.seed(seed)            # identical weights per build
            blk = BottleneckBlock(inplanes, planes,
                                  conv_backend=backend)
            blk.train()
            opt = paddle.optimizer.SGD(
                learning_rate=0.01, parameters=blk.parameters())
            return jit.TrainStep(
                blk, opt, loss_fn=lambda out, lbl: (out * out).mean())

        def timed(step):
            # TrainStep mutates parameters host-side between calls, so
            # it cannot ride the fori_loop _timeit — wall-clock the
            # donated program like bench.py's _run_repeat_steps
            loss = float(step(paddle.to_tensor(xnp.copy()), label))
            t0 = time.perf_counter()
            for _ in range(steps):
                last = step(paddle.to_tensor(xnp.copy()), label)
            float(last)                 # host sync
            return loss, (time.perf_counter() - t0) / steps * 1e3

        loss_d, dense_ms = timed(build_step("dense"))
        loss_p, fused_ms = timed(build_step("pallas"))
        err = abs(loss_p - loss_d) / max(abs(loss_d), 1e-6)
        assert err <= CONV_FUSED_REL_TOL, \
            (f"fused train step diverges from dense (loss rel err "
             f"{err:.4f}, budget {CONV_FUSED_REL_TOL})")
        width = planes
        # 3x the forward conv flops (fwd, dInput, dWeight per conv)
        flops = 3 * 2 * batch * hw * hw * (
            inplanes * width + width * width * 9 + width * inplanes)
        return {"ms": round(fused_ms, 4),
                "dense_ms": round(dense_ms, 4),
                "speedup_vs_dense": round(dense_ms / fused_ms, 3),
                "tflops": round(flops / (fused_ms / 1e3) / 1e12, 2),
                "loss_rel_err": round(err, 6),
                "batch": batch, "hw": hw}

    return run_bench


# Documented tolerance budget for int8 serving (ISSUE 11): the
# quantized engine's greedy token streams must agree with the fp
# engine's on at least this fraction of generated tokens over the
# standard mixed trace (README "Quantized serving" states the policy;
# tests/test_engine_quantized.py enforces it at CI scale).
INT8_TOKEN_PARITY_MIN = 0.90


def _token_match_fraction(ref_outs, got_outs):
    """Fraction of positionally matching tokens across two runs'
    aligned output lists (prompt + generated per request)."""
    match = total = 0
    for a, b in zip(ref_outs, got_outs):
        n = max(len(a), len(b))
        total += n
        match += sum(x == y for x, y in zip(a, b))
    return match / max(total, 1)


def _engine_offered_load_case(model_cfg=None, requests=None, num_slots=8,
                              block_size=16,
                              seed=0, attention_backend=None,
                              mp_degree=None, kv_dtype=None):
    """Engine-level offered-load row: the continuous-batching engine
    (paged KV cache + slot scheduler, inference/engine.py) serving a
    mixed trace of prompts/output lengths; the metric is AGGREGATE new
    tokens per wall-clock second — the serving-health number the gate
    tracks from this PR on. Self-timed (the scheduler loop is
    host-driven admission between compiled iterations, so _timeit's
    in-graph fori_loop doesn't apply): compile is excluded by warming
    the prefill chunk + the decode step on a throwaway request first.
    The row also carries the engine's metrics snapshot distilled to
    serving-SLO numbers (TTFT/TPOT percentiles, block stalls, pool
    high-water, recompiles) so BENCH rounds record latency health, not
    just aggregate tokens/s — warmup observations are dropped by a
    registry reset before the measured window.
    Returns a zero-arg runner producing the result record (run()
    resolves it); tests call it with a tiny config.
    `attention_backend` selects the paged-attention kernel
    (`gpt_engine_offered_load_pallas` is this same trace with
    attention_backend='pallas' — the fused-kernel serving number).
    `mp_degree` serves the SAME trace tensor-parallel over an mp-axis
    mesh (`gpt_engine_offered_load_mp2`): the row first serves at mp=1
    for the reference outputs + tokens/s, then at mp_degree, and
    ASSERTS the outputs token-identical — the headline numbers are the
    sharded engine's.
    `kv_dtype='int8'` is the quantized serving row
    (`gpt_engine_offered_load_int8`): the same trace served fp first
    (reference outputs + tokens/s + pool bytes), then with the int8
    per-block-scaled KV cache AND int8 weights; outputs must match
    within the documented tolerance (INT8_TOKEN_PARITY_MIN) and the
    record carries both tokens/s, both pool-byte footprints, and the
    measured match fraction."""

    def run_bench():
        import time

        import numpy as np

        import paddle_tpu  # noqa: F401
        from paddle_tpu.inference import GenerationEngine
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.observability.metrics import (
            quantile_from_buckets, series_total,
        )

        if mp_degree:
            import jax

            if len(jax.devices()) < mp_degree:
                raise RuntimeError(
                    f"bench row needs {mp_degree} devices for mp="
                    f"{mp_degree}, have {len(jax.devices())} — run on "
                    "a TPU slice or a virtual mesh "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count)")
        cfg = model_cfg or GPTConfig(
            vocab_size=50304, hidden_size=1024, num_layers=24,
            num_heads=16, max_seq_len=512)
        rng = np.random.RandomState(seed)
        reqs = requests or [
            (int(rng.randint(24, 193)), int(rng.randint(32, 129)))
            for _ in range(24)]                # (prompt_len, max_new)
        prompts = [rng.randint(0, cfg.vocab_size, plen)
                   for plen, _ in reqs]
        model = GPTForCausalLM(cfg)
        model.eval()

        def build(mp, quant=False):
            qkw = dict(kv_dtype="int8", weight_dtype="int8") \
                if quant else {}
            return GenerationEngine(model, num_slots=num_slots,
                                    block_size=block_size,
                                    attention_backend=attention_backend,
                                    mp_degree=mp, **qkw)

        def serve(engine, warm_rng_seed=1):
            """Warm both compiled programs the trace will hit (the
            prefill chunk + the one decode step), then measure."""
            wrng = np.random.RandomState(warm_rng_seed)
            engine.add_request(
                wrng.randint(0, cfg.vocab_size, reqs[0][0]),
                max_new_tokens=2)
            engine.run()
            base = engine.tokens_generated
            engine.metrics.reset()         # drop warmup observations
            ids = [engine.add_request(p, max_new_tokens=max_new)
                   for p, (_, max_new) in zip(prompts, reqs)]
            t0 = time.perf_counter()
            out = engine.run()
            dt = time.perf_counter() - t0
            new_toks = engine.tokens_generated - base
            assert len(out) == len(reqs)
            return dt, new_toks, [list(map(int, out[i])) for i in ids]

        mp_extra = {}
        if kv_dtype:
            if kv_dtype != "int8":
                raise ValueError(
                    f"kv_dtype={kv_dtype!r}: only 'int8' is benched")
            ref_engine = build(None)
            dt1, toks1, outs1 = serve(ref_engine)
            fp_bytes = ref_engine.cache.pool_nbytes()
            engine = build(None, quant=True)
            dt, new_toks, outs = serve(engine)
            match = _token_match_fraction(outs1, outs)
            assert match >= INT8_TOKEN_PARITY_MIN, \
                (f"int8 outputs match only {match:.3f} of fp tokens "
                 f"(tolerance budget {INT8_TOKEN_PARITY_MIN})")
            q_bytes = engine.cache.pool_nbytes()
            mp_extra = {"kv_dtype": "int8", "weight_dtype": "int8",
                        "tokens_per_s_fp": round(toks1 / dt1),
                        "token_match_fraction": round(match, 4),
                        "pool_bytes_fp": fp_bytes,
                        "pool_bytes_int8": q_bytes,
                        "pool_bytes_ratio": round(q_bytes / fp_bytes,
                                                  4)}
        elif mp_degree:
            if mp_degree < 2:
                raise ValueError(
                    f"mp_degree={mp_degree}: the sharded row compares "
                    "against mp=1 — ask for a degree >= 2")
            # reference serve at mp=1: the parity oracle AND the
            # single-chip tokens/s this row's speedup is judged against
            ref_engine = build(None)
            dt1, toks1, outs1 = serve(ref_engine)
            engine = build(mp_degree)
            dt, new_toks, outs = serve(engine)
            assert outs == outs1, \
                f"mp={mp_degree} outputs diverged from mp=1"
            mp_extra = {"mp_degree": mp_degree,
                        "devices": engine.mesh.size,
                        "tokens_per_s_mp1": round(toks1 / dt1)}
        else:
            engine = build(None)
            dt, new_toks, _ = serve(engine)

        snap = engine.metrics_snapshot()

        def pct_ms(name, q):
            fam = snap[name]
            if not fam["series"]:
                return None
            v = quantile_from_buckets(fam["buckets"],
                                      fam["series"][0]["counts"], q)
            return None if v is None else round(v * 1e3, 3)

        return {"ms": round(dt * 1e3, 1),
                "tokens_per_s": round(new_toks / dt),
                "attention_backend": engine.attention_backend,
                "requests": len(reqs),
                "ttft_ms_p50": pct_ms("engine_ttft_seconds", 0.5),
                "ttft_ms_p99": pct_ms("engine_ttft_seconds", 0.99),
                "tpot_ms_p50": pct_ms("engine_tpot_seconds", 0.5),
                "tpot_ms_p99": pct_ms("engine_tpot_seconds", 0.99),
                "block_stalls": int(series_total(
                    snap, "engine_block_stalls_total")),
                "pool_high_water_blocks": int(
                    snap["engine_pool_used_high_water_blocks"]
                    ["series"][0]["value"]),
                "decode_recompiles": int(series_total(
                    snap, "engine_decode_recompiles_total")),
                **mp_extra}

    return run_bench


def _tpot_pct(snap, q):
    """Tail TPOT from the engine's histogram, counts summed across the
    priority-labeled series (ms, or None before any observation)."""
    return _hist_pct(snap, "engine_tpot_seconds", q)


def _hist_pct(snap, name, q):
    """Quantile of any snapshot histogram with counts summed across
    ALL its labeled series (priority/replica/...): the fleet-level
    percentile view (ms, or None before any observation)."""
    from paddle_tpu.observability.metrics import quantile_from_buckets

    fam = snap[name]
    if not fam["series"]:
        return None
    counts = [sum(s["counts"][i] for s in fam["series"])
              for i in range(len(fam["series"][0]["counts"]))]
    v = quantile_from_buckets(fam["buckets"], counts, q)
    return None if v is None else round(v * 1e3, 3)


def _fleet_offered_load_case(model_cfg=None, num_tenants=3,
                             per_tenant=8, uniques=6, prefix_len=64,
                             suffix_max=32, max_new=32, num_slots=8,
                             block_size=16, prefill_chunk=64, seed=0,
                             replica_counts=(1, 2)):
    """Serving-tier offered-load row (ISSUE 12): the SAME skewed
    multi-tenant trace served by a 1-replica and an N-replica
    `ServingFleet` (prefix-affinity dp router over engine replicas,
    inference/fleet.py). The trace is deliberately skewed — tenant 0's
    hot shared system prompt carries ~half the requests, later tenants
    halve, plus a long-tail of one-off prompts — the shape where
    affinity routing either pays (hot prefixes stay on the replica
    owning their warm blocks) or collapses a replica (no hysteresis).
    Each fleet serves the trace twice: the COLD wave is the tracked
    offered-load number per replica count, the WARM wave (fresh
    suffixes, same tenants) must route hot tenants onto their warm
    blocks — the runner ASSERTS merged prefix-cache hit tokens AND
    router affinity tokens > 0, and asserts every request's output
    token-identical across replica counts (the fleet exactness
    contract at bench scale). Tracked numbers: aggregate cold
    tokens/s at each replica count, warm tokens/s, p99 TTFT/TPOT from
    the replica-labeled merged snapshot."""

    def run_bench():
        import time

        import numpy as np

        import paddle_tpu  # noqa: F401
        from paddle_tpu.inference import ServingFleet
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.observability.metrics import series_total

        cfg = model_cfg or GPTConfig(
            vocab_size=50304, hidden_size=1024, num_layers=24,
            num_heads=16, max_seq_len=512)
        rng = np.random.RandomState(seed)
        model = GPTForCausalLM(cfg)
        model.eval()
        tenants = [rng.randint(0, cfg.vocab_size, prefix_len)
                   for _ in range(num_tenants)]

        def wave():
            # skewed: tenant t carries per_tenant >> t requests
            reqs = []
            lo = max(1, min(8, max_new))
            for t, pre in enumerate(tenants):
                for _ in range(max(1, per_tenant >> t)):
                    sfx = rng.randint(0, cfg.vocab_size,
                                      rng.randint(1, suffix_max + 1))
                    reqs.append((np.concatenate([pre, sfx]),
                                 int(rng.randint(lo, max_new + 1))))
            for _ in range(uniques):
                reqs.append((rng.randint(
                    0, cfg.vocab_size,
                    rng.randint(prefix_len // 2, prefix_len * 2)),
                    int(rng.randint(lo, max_new + 1))))
            return reqs

        # both waves fixed up front so every fleet serves the same
        # bytes — the cross-replica-count identity assert needs it
        trace_cold, trace_warm = wave(), wave()

        def fleet_tokens(fleet):
            return sum(r.engine.tokens_generated
                       for r in fleet._replicas.values())

        def serve(fleet, trace):
            base = fleet_tokens(fleet)
            t0 = time.perf_counter()
            ids = [fleet.add_request(p, max_new_tokens=n)
                   for p, n in trace]
            out = fleet.run()
            dt = time.perf_counter() - t0
            assert len(out) == len(trace)
            return dt, fleet_tokens(fleet) - base, \
                [list(map(int, out[i])) for i in ids]

        results, outs_by_n = {}, {}
        for n in replica_counts:
            fleet = ServingFleet(model, num_replicas=n,
                                 num_slots=num_slots,
                                 block_size=block_size,
                                 prefill_chunk=prefill_chunk)
            # compile warmup per replica, off the record
            for rep in fleet._replicas.values():
                rep.engine.add_request(
                    rng.randint(0, cfg.vocab_size, prefill_chunk + 1),
                    max_new_tokens=2)
                rep.engine.run()
            fleet.reset_metrics()
            dt_cold, toks_cold, outs_cold = serve(fleet, trace_cold)
            snap = fleet.metrics_snapshot()
            ttft99 = _hist_pct(snap, "engine_ttft_seconds", 0.99)
            tpot99 = _hist_pct(snap, "engine_tpot_seconds", 0.99)
            fleet.reset_metrics()
            dt_warm, toks_warm, outs_warm = serve(fleet, trace_warm)
            snap = fleet.metrics_snapshot()
            hit = int(series_total(
                snap, "engine_prefix_cache_hit_tokens_total"))
            aff = int(series_total(
                snap, "fleet_affinity_hit_tokens_total"))
            assert hit > 0, \
                "warm wave must serve prefix-cache hits fleet-wide"
            assert aff > 0, \
                ("warm wave must land affinity routes (hot tenants "
                 "onto their block-owning replica)")
            outs_by_n[n] = outs_cold + outs_warm
            results[n] = {
                "tokens_per_s": round(toks_cold / dt_cold),
                "tokens_per_s_warm": round(toks_warm / dt_warm),
                "ms": round(dt_cold * 1e3, 1),
                "ttft_ms_p99": ttft99, "tpot_ms_p99": tpot99,
                "affinity_hit_tokens": aff,
                "prefix_hit_tokens": hit}
        base_n = replica_counts[0]
        for n in replica_counts[1:]:
            assert outs_by_n[n] == outs_by_n[base_n], \
                (f"fleet outputs diverged between replicas={base_n} "
                 f"and replicas={n}")
        head = results[replica_counts[-1]]
        return {**head,
                "replicas": replica_counts[-1],
                "requests": len(trace_cold) + len(trace_warm),
                **{f"tokens_per_s_r{n}": results[n]["tokens_per_s"]
                   for n in replica_counts}}

    return run_bench


def _engine_multitenant_lora_case(model_cfg=None, num_tenants=4,
                                  per_tenant=6, rank=8, max_rank=8,
                                  prefix_len=48, suffix_max=24,
                                  max_new=24, num_slots=8,
                                  block_size=16, prefill_chunk=64,
                                  adapter_pool_pages=None, seed=0):
    """Multi-tenant batched-LoRA serving row (ISSUE 13): one base
    model, `num_tenants` per-tenant adapters, a SKEWED trace (tenant t
    carries `per_tenant >> t` requests, each a tenant system prompt +
    fresh suffix) served two ways:

    - MIXED (the subsystem under test): ONE engine with the full
      adapter registry serves every tenant's requests interleaved —
      the paged adapter pool gathers per-slot pages inside the one
      compiled decode step, so the batch stays full across tenants.
    - STRAWMAN: one dedicated engine per tenant (the pre-LoRA shape:
      fork the engine per adapter), each serving only its own
      requests, timed end to end sequentially — lanes idle whenever a
      tenant has fewer live requests than slots.

    The runner ASSERTS every request's output token-identical between
    the two (the mixed-tenant exactness contract at bench scale) and
    decode_traces == 1 on the mixed engine regardless of how many
    adapters are live. Tracked numbers: mixed vs dedicated aggregate
    tokens/s (+ the speedup), adapter-pool swap-ins/evictions, and
    per-tenant p99 TTFT/TPOT off the adapter-labeled histograms —
    the per-tenant SLO view only the mixed engine can even report."""

    def run_bench():
        import time

        import numpy as np

        import paddle_tpu  # noqa: F401
        from paddle_tpu.adapters import AdapterRegistry
        from paddle_tpu.inference import GenerationEngine
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.observability.metrics import (
            quantile_from_buckets, series_total,
        )

        cfg = model_cfg or GPTConfig(
            vocab_size=50304, hidden_size=1024, num_layers=24,
            num_heads=16, max_seq_len=512)
        rng = np.random.RandomState(seed)
        model = GPTForCausalLM(cfg)
        model.eval()
        reg = AdapterRegistry(cfg, max_rank=max_rank)
        H, I, L = (cfg.hidden_size, cfg.intermediate_size,
                   cfg.num_layers)
        for t in range(num_tenants):
            w = {}
            for site, (i_d, o_d) in (("qkv", (H, 3 * H)),
                                     ("out", (H, H)), ("fc1", (H, I)),
                                     ("fc2", (I, H))):
                w[site] = [
                    (rng.randn(rank, i_d).astype(np.float32) * 0.05,
                     rng.randn(o_d, rank).astype(np.float32) * 0.05)
                    for _ in range(L)]
            reg.register(t + 1, w, alpha=2 * rank)
        tenants = [rng.randint(0, cfg.vocab_size, prefix_len)
                   for _ in range(num_tenants)]
        # skewed trace: tenant t carries per_tenant >> t requests
        reqs = []
        for t, pre in enumerate(tenants):
            for _ in range(max(1, per_tenant >> t)):
                sfx = rng.randint(0, cfg.vocab_size,
                                  rng.randint(1, suffix_max + 1))
                reqs.append((np.concatenate([pre, sfx]), t + 1,
                             int(rng.randint(max(2, max_new // 2),
                                             max_new + 1))))
        order = rng.permutation(len(reqs))
        # stable per-request ids so the mixed run and the per-tenant
        # dedicated runs key the same request identically
        reqs = [(f"r{i}", *reqs[j]) for i, j in enumerate(order)]

        def build(adapters):
            eng = GenerationEngine(
                model, num_slots=num_slots, block_size=block_size,
                prefill_chunk=prefill_chunk, adapters=adapters,
                adapter_pool_pages=adapter_pool_pages
                if adapters is not None else None)
            # compile warmup off the record (chunk + decode programs)
            eng.add_request(
                rng.randint(0, cfg.vocab_size, prefill_chunk + 1),
                max_new_tokens=2)
            eng.run()
            eng.metrics.reset()
            return eng

        def serve(eng, batch):
            base = eng.tokens_generated
            t0 = time.perf_counter()
            ids = [eng.add_request(p, max_new_tokens=n, adapter_id=a,
                                   req_id=rid)
                   for rid, p, a, n in batch]
            out = eng.run()
            dt = time.perf_counter() - t0
            return dt, eng.tokens_generated - base, \
                {i: list(map(int, out[i])) for i in ids}, ids

        mixed = build(reg)
        dt_mix, toks_mix, out_mix, _ = serve(mixed, reqs)
        assert mixed.decode_traces == 1, \
            "mixed-tenant decode retraced — the adapter row must be " \
            "traced, never a trace key"
        snap = mixed.metrics_snapshot()
        swapins = int(series_total(snap,
                                   "engine_adapter_swapins_total"))
        evictions = int(series_total(
            snap, "engine_adapter_evictions_total"))

        def tenant_pct(name, q):
            fam = snap[name]
            out = {}
            for s in fam["series"]:
                v = quantile_from_buckets(fam["buckets"], s["counts"],
                                          q)
                if v is not None:
                    out[s["labels"]["adapter"]] = round(v * 1e3, 3)
            return out

        # strawman: per-tenant dedicated engines, timed sequentially
        dt_ded, toks_ded, out_ded = 0.0, 0, {}
        for t in range(num_tenants):
            mine = [r for r in reqs if r[2] == t + 1]
            if not mine:
                continue
            ded = build(reg)
            dt, toks, outs, _ = serve(ded, mine)
            dt_ded += dt
            toks_ded += toks
            out_ded.update(outs)
        assert len(out_ded) == len(out_mix)
        match = _token_match_fraction(
            [out_mix[i] for i in sorted(out_mix, key=str)],
            [out_ded[i] for i in sorted(out_ded, key=str)])
        assert match == 1.0, \
            (f"mixed-tenant outputs diverged from dedicated engines "
             f"(match {match:.4f}) — cross-slot adapter leakage")
        return {"tokens_per_s": round(toks_mix / dt_mix),
                "tokens_per_s_dedicated": round(toks_ded / dt_ded),
                "speedup_vs_dedicated": round(
                    (toks_mix / dt_mix) / (toks_ded / dt_ded), 3),
                "ms": round(dt_mix * 1e3, 1),
                "tenants": num_tenants, "requests": len(reqs),
                "rank": rank, "max_rank": max_rank,
                "adapter_swapins": swapins,
                "adapter_evictions": evictions,
                "ttft_ms_p99_by_tenant": tenant_pct(
                    "engine_adapter_ttft_seconds", 0.99),
                "tpot_ms_p99_by_tenant": tenant_pct(
                    "engine_adapter_tpot_seconds", 0.99),
                "decode_recompiles": int(series_total(
                    snap, "engine_decode_recompiles_total"))}

    return run_bench


def _engine_prefix_cache_case(model_cfg=None, num_tenants=4,
                              per_tenant=6, uniques=8, prefix_len=64,
                              suffix_max=32, max_new=32, num_slots=8,
                              block_size=16, prefill_chunk=64, seed=0):
    """Prefix-cache serving row: a multi-tenant trace (each tenant is a
    hot shared system prompt carried by `per_tenant` requests with
    unique suffixes, plus `uniques` long-tail one-off prompts) served
    twice by ONE engine. The first wave computes and publishes every
    tenant prefix; the second wave (fresh suffixes, same tenants) must
    seat the shared blocks from the cache — the record proves it with
    the hit-token counter and a strictly lower prefill-chunk count,
    and the tracked numbers are warm tokens/s + warm tail TPOT vs the
    cold wave's. Runs chunked prefill + prefix cache (the default
    scheduler this row exists to track)."""

    def run_bench():
        import time

        import numpy as np

        import paddle_tpu  # noqa: F401
        from paddle_tpu.inference import GenerationEngine
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.observability.metrics import series_total

        cfg = model_cfg or GPTConfig(
            vocab_size=50304, hidden_size=1024, num_layers=24,
            num_heads=16, max_seq_len=512)
        rng = np.random.RandomState(seed)
        model = GPTForCausalLM(cfg)
        model.eval()
        engine = GenerationEngine(model, num_slots=num_slots,
                                  block_size=block_size,
                                  prefill_chunk=prefill_chunk)
        tenants = [rng.randint(0, cfg.vocab_size, prefix_len)
                   for _ in range(num_tenants)]

        def wave():
            reqs = []
            for pre in tenants:
                for _ in range(per_tenant):
                    sfx = rng.randint(0, cfg.vocab_size,
                                      rng.randint(1, suffix_max + 1))
                    reqs.append(np.concatenate([pre, sfx]))
            for _ in range(uniques):
                reqs.append(rng.randint(
                    0, cfg.vocab_size,
                    rng.randint(prefix_len // 2, prefix_len * 2)))
            return reqs

        def serve(reqs):
            base = engine.tokens_generated
            t0 = time.perf_counter()
            for p in reqs:
                engine.add_request(p, max_new_tokens=max_new)
            out = engine.run()
            dt = time.perf_counter() - t0
            assert len(out) == len(reqs)
            return dt, engine.tokens_generated - base

        # compile warmup (chunk + decode programs), off the record
        engine.add_request(
            rng.randint(0, cfg.vocab_size, prefill_chunk + 1), 2)
        engine.run()
        engine.metrics.reset()
        dt_cold, toks_cold = serve(wave())
        snap = engine.metrics_snapshot()
        chunks_cold = series_total(snap, "engine_prefill_chunks_total")
        tpot_cold = _tpot_pct(snap, 0.99)
        engine.metrics.reset()
        dt_warm, toks_warm = serve(wave())   # fresh suffixes, hot cache
        snap = engine.metrics_snapshot()
        chunks_warm = series_total(snap, "engine_prefill_chunks_total")
        hit = int(series_total(snap,
                               "engine_prefix_cache_hit_tokens_total"))
        assert hit > 0, "warm wave must serve prefix hits"
        assert chunks_warm < chunks_cold, \
            "prefix hits must shrink prefill compute"
        return {"ms": round(dt_warm * 1e3, 1),
                "tokens_per_s": round(toks_warm / dt_warm),
                "cold_tokens_per_s": round(toks_cold / dt_cold),
                "hit_tokens": hit,
                "prefill_chunks_cold": int(chunks_cold),
                "prefill_chunks_warm": int(chunks_warm),
                "tpot_ms_p99": _tpot_pct(snap, 0.99),
                "tpot_ms_p99_cold": tpot_cold,
                "cached_blocks": int(
                    snap["engine_prefix_cached_blocks"]["series"][0]
                    ["value"]),
                "requests_per_wave":
                    num_tenants * per_tenant + uniques}

    return run_bench


def _engine_speculative_case(model_cfg=None, num_requests=12,
                             num_slots=4, block_size=16,
                             prefill_chunk=64, spec_k=4, max_new=48,
                             seed=0):
    """Speculative-decoding offered-load row (ISSUE 7): one trace of
    REPETITIVE prompts (tiled motifs — the prompt-lookup drafter's
    favorable case, standing in for summarization/code workloads that
    repeat prompt spans) served by two engines over the same model:
    the K=0 baseline and the speculative engine at `spec_k`. The
    tracked numbers are net tokens/s under speculation vs the K=0
    baseline, accepted tokens per verify step, and the draft hit rate
    — the amortization evidence the tentpole claims. The two runs'
    outputs are asserted token-identical (the exact-acceptance
    contract, re-proven at bench scale). On TPU the speedup is the
    headline; CPU CI only asserts structure."""

    def run_bench():
        import time

        import numpy as np

        import paddle_tpu  # noqa: F401
        from paddle_tpu.inference import GenerationEngine
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.observability.metrics import series_total

        cfg = model_cfg or GPTConfig(
            vocab_size=50304, hidden_size=1024, num_layers=24,
            num_heads=16, max_seq_len=512)
        rng = np.random.RandomState(seed)
        reqs = []
        for _ in range(num_requests):
            motif = rng.randint(0, cfg.vocab_size, rng.randint(4, 9))
            p = np.tile(motif, 12)[:cfg.max_seq_len - max_new - 1]
            reqs.append(p.astype(np.int32))
        model = GPTForCausalLM(cfg)
        model.eval()

        def serve(k):
            engine = GenerationEngine(model, num_slots=num_slots,
                                      block_size=block_size,
                                      prefill_chunk=prefill_chunk,
                                      spec_decode_k=k)
            engine.add_request(reqs[0], 2)     # compile warmup
            engine.run()
            engine.metrics.reset()
            base = engine.tokens_generated
            t0 = time.perf_counter()
            ids = [engine.add_request(p, max_new_tokens=max_new)
                   for p in reqs]
            out = engine.run()
            dt = time.perf_counter() - t0
            toks = engine.tokens_generated - base
            assert len(out) == num_requests
            return engine, dt, toks, [out[r] for r in ids]

        eng0, dt0, toks0, outs0 = serve(0)
        engk, dtk, toksk, outsk = serve(spec_k)
        for a, b in zip(outs0, outsk):         # exact acceptance
            assert a == b, "speculative output diverged from K=0"
        snap = engk.metrics_snapshot()
        fam = snap["engine_spec_accepted_tokens"]["series"][0]
        steps = max(int(fam["count"]), 1)
        return {"ms": round(dtk * 1e3, 1),
                "tokens_per_s": round(toksk / dtk),
                "tokens_per_s_k0": round(toks0 / dt0),
                "speedup_vs_k0": round((toksk / dtk) / (toks0 / dt0),
                                       3),
                "spec_k": spec_k,
                "accepted_tokens_per_step": round(fam["sum"] / steps,
                                                  3),
                "draft_hit_rate": round(
                    snap["engine_spec_draft_hit_rate"]["series"][0]
                    ["value"], 4),
                "verify_steps": int(fam["count"]),
                "decode_recompiles": int(series_total(
                    snap, "engine_decode_recompiles_total")),
                "requests": num_requests}

    return run_bench


def _engine_sampling_case(model_cfg=None, num_requests=12,
                          num_slots=4, block_size=16, max_new=32,
                          best_n=4, seed=0):
    """Probabilistic-serving row (ISSUE 15): the offered-load trace
    served three ways on one sampling-enabled engine over one model —
    greedy (temperature 0, asserted TOKEN-IDENTICAL to a sampling-OFF
    engine: the bit-exact no-regression contract at bench scale),
    temperature 0.8 sampled (same fixed seeds served twice, asserted
    reproducible token-for-token), and a best-of-`best_n` fan-out of
    one prompt (asserted to seat the shared prompt blocks ONCE via the
    prefix-hit counter). The tracked numbers are tokens/s for all
    three modes — the cost of the on-device masking+draw relative to
    the pure-argmax step — plus the sampled-token and prefix-hit
    counters. On TPU the overhead is the headline; CPU CI only asserts
    structure."""

    def run_bench():
        import time

        import numpy as np

        import paddle_tpu  # noqa: F401
        from paddle_tpu.inference import (GenerationEngine,
                                          SamplingParams)
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.observability.metrics import series_total

        cfg = model_cfg or GPTConfig(
            vocab_size=50304, hidden_size=1024, num_layers=24,
            num_heads=16, max_seq_len=512)
        rng = np.random.RandomState(seed)
        # prompt + budget must fit the model window (tiny CI configs)
        hi = min(97, cfg.max_seq_len - max_new)
        lo = min(16, hi - 1)
        reqs = [rng.randint(0, cfg.vocab_size,
                            rng.randint(lo, hi)).astype(np.int32)
                for _ in range(num_requests)]
        model = GPTForCausalLM(cfg)
        model.eval()

        def build(on):
            return GenerationEngine(model, num_slots=num_slots,
                                    block_size=block_size,
                                    sampling=on)

        def serve(engine, params_of):
            engine.add_request(reqs[0], 2)     # compile warmup
            engine.run()
            engine.metrics.reset()
            base = engine.tokens_generated
            t0 = time.perf_counter()
            ids = [engine.add_request(p, max_new_tokens=max_new,
                                      sampling_params=params_of(i))
                   for i, p in enumerate(reqs)]
            out = engine.run()
            dt = time.perf_counter() - t0
            toks = engine.tokens_generated - base
            assert len(out) == num_requests
            return dt, toks, [out[r] for r in ids]

        ref = build(False)
        dt_ref, toks_ref, outs_ref = serve(ref, lambda i: None)
        eng = build(True)
        dt_g, toks_g, outs_g = serve(eng, lambda i: None)
        assert outs_g == outs_ref, \
            "temperature-0 serving diverged from the sampling-off " \
            "engine (the bit-exact greedy contract)"
        sp = lambda i: SamplingParams(temperature=0.8, top_k=50,
                                      top_p=0.95, seed=seed + i)
        eng_s = build(True)
        dt_s, toks_s, outs_s = serve(eng_s, sp)
        _, _, outs_s2 = serve(build(True), sp)
        assert outs_s == outs_s2, \
            "same-seed sampled serving is not reproducible"
        snap = eng_s.metrics_snapshot()
        sampled = int(series_total(snap,
                                   "engine_sampled_tokens_total"))
        bo = build(True)
        hit0 = bo.prefix_hit_tokens
        t0 = time.perf_counter()
        cands = bo.best_of_n(reqs[0], best_n, max_new,
                             sampling_params=SamplingParams(
                                 temperature=0.8, seed=seed))
        dt_b = time.perf_counter() - t0
        shared = (len(reqs[0]) // block_size) * block_size
        assert bo.prefix_hit_tokens - hit0 == (best_n - 1) * shared, \
            "best_of_n did not seat the shared prompt blocks once"
        toks_b = sum(len(c) - len(reqs[0]) for c in cands)
        return {"ms": round(dt_s * 1e3, 1),
                "tokens_per_s_greedy_off": round(toks_ref / dt_ref),
                "tokens_per_s_greedy": round(toks_g / dt_g),
                "tokens_per_s_sampled": round(toks_s / dt_s),
                "sampling_overhead_vs_off": round(
                    (toks_ref / dt_ref) / max(toks_s / dt_s, 1e-9),
                    3),
                "tokens_per_s_best_of_n": round(toks_b / dt_b),
                "best_n": best_n,
                "sampled_tokens": sampled,
                "best_of_n_hit_tokens": int(
                    bo.prefix_hit_tokens - hit0),
                "requests": num_requests}

    return run_bench


def _engine_host_gap_case(model_cfg=None, num_requests=12,
                          num_slots=4, block_size=16, max_new=32,
                          seed=0):
    """Host-gap row (ISSUE 17 — ROADMAP item 3's measured baseline):
    the offered-load trace served on a tracing-enabled engine at
    K in {0, 4}, cold (first serve after construction — compiles land
    in the dispatch phase) and warm (metrics reset, second serve).
    The tracked numbers are host-gap milliseconds per step BY PHASE
    (schedule/prefix_lookup/dispatch/device_wait/draft_propose/
    accept_walk/cow/finish — the `engine_step_host_gap_seconds`
    histogram, sum/count per phase) plus the device fraction
    (device_wait over the phase total), i.e. how much of every
    scheduler iteration is serial host work the async core of ROADMAP
    item 3 could overlap. On CPU the fraction is meaningless as an
    absolute; the row exists so a TPU `--save` pins the baseline the
    overlap claim is measured against."""

    def run_bench():
        import time

        import numpy as np

        import paddle_tpu  # noqa: F401
        from paddle_tpu.inference import GenerationEngine
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        cfg = model_cfg or GPTConfig(
            vocab_size=50304, hidden_size=1024, num_layers=24,
            num_heads=16, max_seq_len=512)
        rng = np.random.RandomState(seed)
        # prompt + budget must fit the model window (tiny CI configs)
        hi = min(97, cfg.max_seq_len - max_new)
        lo = min(16, hi - 1)
        reqs = [rng.randint(0, cfg.vocab_size,
                            rng.randint(lo, hi)).astype(np.int32)
                for _ in range(num_requests)]
        model = GPTForCausalLM(cfg)
        model.eval()

        def build(k):
            return GenerationEngine(model, num_slots=num_slots,
                                    block_size=block_size,
                                    spec_decode_k=k, tracing=True)

        def serve(engine):
            base = engine.tokens_generated
            t0 = time.perf_counter()
            for p in reqs:
                engine.add_request(p, max_new_tokens=max_new)
            out = engine.run()
            dt = time.perf_counter() - t0
            assert len(out) == num_requests
            return dt, engine.tokens_generated - base

        def phase_report(engine):
            """(phase -> ms/step, device fraction) from the host-gap
            histogram accumulated since the last metrics reset."""
            snap = engine.metrics_snapshot()
            series = snap["engine_step_host_gap_seconds"]["series"]
            per_step, sums = {}, {}
            for s in series:
                if not s["count"]:
                    continue
                ph = s["labels"]["phase"]
                sums[ph] = s["sum"]
                per_step[ph] = round(s["sum"] / s["count"] * 1e3, 4)
            total = sum(sums.values())
            frac = round(sums.get("device_wait", 0.0) / total, 4) \
                if total else 0.0
            return per_step, frac

        rec = {}
        for k in (0, 4):
            eng = build(k)
            dt_cold, toks_cold = serve(eng)       # includes compiles
            cold, frac_cold = phase_report(eng)
            eng.metrics.reset()
            dt_warm, toks_warm = serve(eng)
            warm, frac_warm = phase_report(eng)
            rec[f"k{k}"] = {
                "phase_ms_per_step_cold": cold,
                "phase_ms_per_step_warm": warm,
                "device_fraction_cold": frac_cold,
                "device_fraction_warm": frac_warm,
                "tokens_per_s_warm": round(toks_warm / dt_warm),
                "spans": int(eng.tracer.total_recorded),
            }
            if k == 0:
                ms_warm = dt_warm * 1e3
        return {"ms": round(ms_warm, 1), **rec,
                "requests": num_requests}

    return run_bench


def _engine_async_overlap_case(model_cfg=None, num_requests=24,
                               num_slots=2, block_size=16, max_new=6,
                               spec_k=4, reps=3, seed=0):
    """Async-core overlap row (ISSUE 18 — the refactor the host-gap
    row was built to measure): the SAME offered-load trace served by a
    persistent serial engine (`async_core=False`) and a persistent
    async engine (`async_core=True`), interleaved serial/async for
    `reps` measured passes, every pass asserted token-identical.

    The workload is built to have real overlappable host work, not
    just scheduler arithmetic: requests cycle FOUR tenant adapters
    over a pool with three usable pages and two lanes, so in steady
    state the queue head's adapter is never resident — the serial
    engine pays the host->device swap-in inside the admission path's
    `adapter_swap` phase, while the async core prefetches that page
    behind the in-flight step (stage 5 of `_step_async`) and admits
    against a resident hit. Drafter proposals ride the helper thread
    against the admission/prefill work of the same step.

    Reported: per-phase host-gap ms/step and device fraction for both
    modes (median across reps — the CPU runner's step costs are
    ms-scale where machine noise lives). Asserted where measured: the
    async overlappable host gap (schedule + draft_propose +
    adapter_swap) strictly below serial's, async device fraction no
    lower — the ROADMAP item 3 claim."""

    def run_bench():
        import time

        import numpy as np

        import paddle_tpu  # noqa: F401
        from paddle_tpu.adapters import AdapterRegistry
        from paddle_tpu.inference import GenerationEngine
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        cfg = model_cfg or GPTConfig(
            vocab_size=50304, hidden_size=1024, num_layers=24,
            num_heads=16, max_seq_len=512)
        rng = np.random.RandomState(seed)
        # repeat-heavy prompts from a small alphabet: the NgramDrafter
        # actually matches (and costs real host time) instead of
        # no-op'ing on unrepeated random ids
        alpha = min(64, cfg.vocab_size)
        hi = min(97, cfg.max_seq_len - max_new)
        lo = min(32, hi - 1)
        reqs = [(rng.randint(0, alpha,
                             rng.randint(lo, hi)).astype(np.int32),
                 1 + i % 4)             # cycle adapters 1..4
                for i in range(num_requests)]
        model = GPTForCausalLM(cfg)
        model.eval()

        def registry():
            # four tenants over three usable pages: the steady-state
            # queue head is never resident, every admission pays (or
            # prefetches) a swap-in
            w_rng = np.random.RandomState(7)
            reg = AdapterRegistry(cfg, max_rank=4)
            H, I = cfg.hidden_size, cfg.intermediate_size
            L = cfg.num_layers
            for aid in (1, 2, 3, 4):
                w = {"qkv": [(w_rng.randn(2, H).astype(np.float32)
                              * 0.01,
                              w_rng.randn(3 * H, 2).astype(np.float32)
                              * 0.01)
                             for _ in range(L)]}
                reg.register(aid, w, scaling=0.25)
            return reg

        def build(async_core):
            return GenerationEngine(
                model, num_slots=num_slots, block_size=block_size,
                spec_decode_k=spec_k, tracing=True,
                adapters=registry(), adapter_pool_pages=4,
                async_core=async_core)

        def serve(engine):
            t0 = time.perf_counter()
            ids = [engine.add_request(p, max_new_tokens=max_new,
                                      req_id=i, adapter_id=aid)
                   for i, (p, aid) in enumerate(reqs)]
            out = engine.run()
            dt = time.perf_counter() - t0
            return dt, [list(map(int, out[i])) for i in ids]

        def phase_report(engine):
            snap = engine.metrics_snapshot()
            series = snap["engine_step_host_gap_seconds"]["series"]
            per_step, sums = {}, {}
            for s in series:
                if not s["count"]:
                    continue
                ph = s["labels"]["phase"]
                sums[ph] = s["sum"]
                per_step[ph] = round(s["sum"] / s["count"] * 1e3, 4)
            total = sum(sums.values())
            frac = round(sums.get("device_wait", 0.0) / total, 4) \
                if total else 0.0
            overlap = sum(sums.get(p, 0.0) for p in
                          ("schedule", "draft_propose", "adapter_swap"))
            return per_step, frac, overlap

        def median(xs):
            xs = sorted(xs)
            return xs[len(xs) // 2]

        engines = {"serial": build(False), "async": build(True)}
        for eng in engines.values():
            serve(eng)                         # cold: compiles land
        samples = {m: [] for m in engines}
        for rep in range(reps):                # interleaved: machine
            tokens = {}                        # drift hits both modes
            for mode, eng in engines.items():
                eng.metrics.reset()
                dt, tokens[mode] = serve(eng)
                warm, frac, overlap = phase_report(eng)
                samples[mode].append(
                    {"phases": warm, "frac": frac,
                     "overlap_ms": overlap * 1e3, "serve_ms": dt * 1e3})
            assert tokens["async"] == tokens["serial"], \
                f"async core diverged from the serial stream (rep {rep})"
        rec = {}
        for mode, ss in samples.items():
            mid = median([s["overlap_ms"] for s in ss])
            rec[mode] = {
                "phase_ms_per_step_warm":
                    ss[[s["overlap_ms"] for s in ss].index(mid)]
                    ["phases"],
                "device_fraction_warm":
                    median([s["frac"] for s in ss]),
                "host_overlap_gap_ms": round(mid, 3),
                "serve_ms_warm":
                    round(median([s["serve_ms"] for s in ss]), 1),
            }
        # the remaining two gates, asserted where they're measured
        # (stream identity was asserted per rep above): a strictly
        # smaller overlappable host gap, a device fraction that did
        # not regress
        assert rec["async"]["host_overlap_gap_ms"] \
            < rec["serial"]["host_overlap_gap_ms"], (
                "async host gap (schedule+draft_propose+adapter_swap) "
                f"not below serial: {rec['async']} vs {rec['serial']}")
        assert rec["async"]["device_fraction_warm"] \
            >= rec["serial"]["device_fraction_warm"], (
                "async device fraction regressed vs serial: "
                f"{rec['async']} vs {rec['serial']}")
        return {"ms": rec["async"]["serve_ms_warm"], **rec,
                "requests": num_requests, "k": spec_k, "reps": reps}

    return run_bench


def run():
    results = {}
    for name, case in suite().items():
        if callable(case):                 # lazy heavy row: build now
            case = case()
        if isinstance(case, dict):         # self-timed (engine) row
            rec = {"op": name, **case}
        else:
            fn, args, flops = case[:3]
            extra = case[3] if len(case) > 3 else {}
            ms = _timeit(fn, *args)
            rec = {"op": name, "ms": round(ms, 4)}
            if flops:
                rec["tflops"] = round(flops / (ms / 1e3) / 1e12, 2)
            if extra.get("tokens"):
                rec["tokens_per_s"] = round(extra["tokens"] / (ms / 1e3))
        results[name] = rec
        print(json.dumps(rec), flush=True)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--save", metavar="FILE")
    ap.add_argument("--check", metavar="FILE")
    ap.add_argument("--threshold", type=float, default=0.25,
                    help="allowed fractional slowdown vs baseline")
    args = ap.parse_args()
    results = run()
    if args.save:
        with open(args.save, "w") as f:
            json.dump(results, f, indent=1)
        print(f"baseline saved to {args.save}")
    if args.check:
        with open(args.check) as f:
            base = json.load(f)
        failed = []
        for name, rec in results.items():
            if name in base:
                slow = rec["ms"] / base[name]["ms"] - 1.0
                if slow > args.threshold:
                    failed.append(f"{name}: {slow:+.0%} vs baseline "
                                  f"({rec['ms']}ms vs {base[name]['ms']}ms)")
        # a silently-skipped op is a disabled gate, not a pass
        for name in sorted(set(results) - set(base)):
            failed.append(f"{name}: not in baseline (refresh with --save)")
        for name in sorted(set(base) - set(results)):
            failed.append(f"{name}: in baseline but not measured")
        if failed:
            print("REGRESSION GATE FAILED:\n  " + "\n  ".join(failed))
            sys.exit(1)
        print(f"regression gate ok ({len(results)} ops, "
              f"threshold {args.threshold:.0%})")


if __name__ == "__main__":
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
