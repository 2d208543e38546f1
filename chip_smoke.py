"""The quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py             one TPU chip: device, trainer, server
    python chip_smoke.py --chips 4   four chips: the mesh phase ONLY
                                     (hybrid-parallel trainer and
                                     tensor-parallel server, each against
                                     its one-chip run in this process)
    python chip_smoke.py --rehearsal [--chips 4]
                                     the same control flow at a tiny size
                                     on whatever backend JAX has (the CPU
                                     rehearsal; four virtual devices via
                                     XLA_FLAGS for --chips 4). Never a
                                     result: its last line says "ok": false.

One process, normal entry points only (`jit.TrainStep`,
`GenerationEngine`, `DistributedTrainStep`), GPT-1.3B at full width and
depth (the mesh phase's trainer alone at 16 of the 24 layers, see
MESH_TRAINER_LAYERS), weights and requests from `--seed`. A phase that fails raises;
nothing is caught. Without `--rehearsal` the first phase refuses any
backend but a TPU. The last line of standard output is one JSON object,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib
import json
import time

import numpy as np

#: bf16 tolerance of the fused-kernel-against-dense comparison: both
#: accumulate in f32 and round once, so outputs (|o| < 1) may differ by
#: the accumulation order — two bf16 steps at 1.0.
KERNEL_ATOL = 2 ** -6
#: a greedy token counts as agreeing with the reference forward when
#: the reference scores it within this many logit units of its own
#: argmax (six bf16 steps at the top logit's magnitude of 2-4).
TOKEN_LOGIT_TOL = 0.1
#: `model.generate` re-generates this many tokens of this many requests
GENERATE_TOKENS, GENERATE_REQUESTS = 8, 2
#: depth of the mesh phase's trainer, cut from 24: its one-chip
#: reference runs the SAME stage-stacked model, whose AdamW update works
#: on whole [layers, 2048, 8192] stacks in f32 — at 24 layers that is
#: 4.5 GB of temporaries on top of 11.9 GB of state, more than one chip
#: has (the compiler says so); at 16 it is 8.8 + 4.3 GB. Widths are full.
MESH_TRAINER_LAYERS = 16
#: four-chip loss against the one-chip loss on the same weights and
#: batch, relative: bf16 weights, different reduction orders.
MESH_LOSS_RTOL = 2e-2


T0 = time.perf_counter()


def say(phase, **kv):
    print(f"[{phase} +{time.perf_counter() - T0:.0f}s] "
          + " ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def gpt_config(rehearsal):
    from paddle_tpu.models import GPTConfig

    if rehearsal:
        return GPTConfig.tiny(vocab=512, hidden=512, layers=2, heads=4,
                              seq=256)          # heads of 128, like 1.3B
    cfg = GPTConfig.gpt_1p3b()                  # 2048 x 24 x 16 heads
    cfg.vocab_size = 32768
    return cfg


def new_model(cfg, seed, rehearsal):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM

    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    model.eval()                                # no dropout in the steps
    if not rehearsal:
        model.to(dtype="bfloat16")
    return model


def free_device():
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def allocator(dev):
    """The PJRT allocator's statistics for one device, or None where
    the backend keeps none (the CPU). Never the live-array stand-in."""
    return dev.memory_stats() or None


def peak_line(phase, devs):
    for d in devs:
        st = allocator(d)
        if st is None:
            say(phase, device=d.id,
                peak_bytes_in_use="not reported by this backend")
        else:
            say(phase, device=d.id,
                peak_bytes_in_use=st["peak_bytes_in_use"],
                bytes_in_use=st["bytes_in_use"])


# -- phase 1: the device ----------------------------------------------------

def phase_device(args):
    import jax

    import paddle_tpu as paddle

    # set_device raises when the device is absent; the check below is
    # the contract's own and is only skipped for a declared rehearsal
    if not args.rehearsal:
        paddle.device.set_device("tpu")
    devs = jax.devices()
    d0 = devs[0]
    say("device", platform=d0.platform, kind=repr(d0.device_kind),
        count=len(devs), jax=jax.__version__)
    if not args.rehearsal and d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (platform={d0.platform})")
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX "
                         f"reports {len(devs)} device(s)")

    # round trip of an empty jitted call (ROADMAP A1's first probe):
    # every engine step pays one of these from the host
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((), jnp.int32)
    f(x).block_until_ready()
    ts = []
    for _ in range(200):
        t = time.perf_counter()
        f(x).block_until_ready()
        ts.append(time.perf_counter() - t)
    say("device", empty_jit_round_trip_us_median=np.median(ts) * 1e6,
        p99=np.percentile(ts, 99) * 1e6)
    return d0, devs


# -- phase 2: the trainer ---------------------------------------------------

def phase_trainer(args, cfg):
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.jit as jit
    # the package re-exports a function under the module's name
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    model = new_model(cfg, args.seed, args.rehearsal)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = jit.TrainStep(model, opt, model.loss_fn)
    batch, seq = 2, cfg.max_seq_len
    ids = paddle.to_tensor(np.random.RandomState(args.seed).randint(
        0, cfg.vocab_size, (batch, seq), np.int32))
    say("trainer", params_M=round(model.num_params() / 1e6, 1),
        batch=batch, seq=seq, dtype=model.gpt.wte.weight._array.dtype)

    fa.reset_path_stats()
    t0 = time.perf_counter()
    loss = step(ids, ids)
    loss._array.block_until_ready()
    compile_s = time.perf_counter() - t0
    losses, times = [float(loss)], []
    for _ in range(args.steps):
        t = time.perf_counter()
        loss = step(ids, ids)
        loss._array.block_until_ready()
        times.append(time.perf_counter() - t)
        losses.append(float(loss))
    say("trainer", compile_s=round(compile_s, 1),
        ms_per_step_median=np.median(times) * 1e3,
        tokens_per_s=batch * seq / np.median(times),
        losses=[round(v, 4) for v in losses],
        flash_path_stats=dict(fa.PATH_STATS))
    peak_line("trainer", jax.devices()[:1])
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    if not args.rehearsal:
        # the chunked causal kernel ran, not the dense path
        assert fa.PATH_STATS["pallas"] > 0, fa.PATH_STATS
        assert fa.PATH_STATS["xla"] == 0, fa.PATH_STATS


# -- phase 3: the server ----------------------------------------------------

def kernel_against_dense(args, cfg):
    """The fused paged kernels against the dense loop on the same seeded
    pools and tables, at the model's head geometry."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.paged_attention import (paged_attention_step,
                                                paged_verify_window)

    heads = cfg.num_heads
    hd = cfg.hidden_size // heads
    layers, nb, bs, slots, mb, W = 2, 256, 16, 8, 24, 4
    dt = jnp.float32 if args.rehearsal else jnp.bfloat16
    rng = np.random.RandomState(args.seed + 1)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32) \
            .astype(dt)

    kpool, vpool = rand(layers, nb, bs, heads, hd), \
        rand(layers, nb, bs, heads, hd)
    # every slot owns distinct blocks; the last slot is idle (position
    # 0, all-null table), as the engine encodes it
    tables = rng.permutation(np.arange(1, nb))[:slots * mb] \
        .reshape(slots, mb).astype(np.int32)
    tables[-1] = 0
    pos = rng.randint(0, mb * bs - W, slots).astype(np.int32)
    pos[0], pos[-1] = mb * bs - W - 1, 0    # a long walk and the idle
    dlen = rng.randint(0, W, slots).astype(np.int32)
    dlen[-1] = 0
    tables, pos, dlen = map(jnp.asarray, (tables, pos, dlen))

    def run(op, backend, *a):
        # layer 1 is static; everything else is a traced array
        f = jax.jit(lambda *x: tuple(
            t._array for t in op(*x[:5], 1, *x[5:], backend=backend)))
        return jax.block_until_ready(f(*a))

    for name, op, a in (
        ("decode", paged_attention_step,
         (rand(slots, 1, heads, hd), rand(slots, 1, heads, hd),
          rand(slots, 1, heads, hd), kpool, vpool, tables, pos)),
        ("verify", paged_verify_window,
         (rand(slots, W, heads, hd), rand(slots, W, heads, hd),
          rand(slots, W, heads, hd), kpool, vpool, tables, pos, dlen)),
    ):
        fused = run(op, "pallas", *a)
        dense = run(op, "dense", *a)
        o_f = np.asarray(fused[0], np.float32)
        o_d = np.asarray(dense[0], np.float32)
        if name == "verify":    # rows past a slot's draft are garbage
            live = np.arange(W)[None] <= np.asarray(dlen)[:, None]
            o_f, o_d = o_f[live], o_d[live]
        err = float(np.abs(o_f - o_d).max())
        pools_equal = all(
            bool(jnp.array_equal(f[:, 1:], d[:, 1:]))   # block 0: null
            for f, d in zip(fused[1:], dense[1:]))
        say("server", kernel=name, heads=heads, head_dim=hd, block=bs,
            dtype=jnp.dtype(dt).name, max_abs_err=err,
            atol=KERNEL_ATOL, pools_bitwise_equal=pools_equal)
        assert np.isfinite(o_f).all()
        assert err <= KERNEL_ATOL, (name, err)
        assert pools_equal, name


def make_requests(args, cfg):
    rng = np.random.RandomState(args.seed + 2)
    hi = min(512, cfg.max_seq_len // 2)
    lens = rng.randint(32, hi + 1, args.requests)
    news = rng.randint(16, 65, args.requests)
    return [(rng.randint(0, cfg.vocab_size, int(n)).astype(np.int32),
             int(m)) for n, m in zip(lens, news)]


def serve(model, requests, **engine_kw):
    """add_request + run() on a GenerationEngine; returns the engine
    and the generated tokens per request, in order."""
    from paddle_tpu.inference.engine import GenerationEngine

    engine = GenerationEngine(model, **engine_kw)
    ids = [engine.add_request(p, max_new_tokens=m) for p, m in requests]
    t0 = time.perf_counter()
    results = engine.run()
    dt = time.perf_counter() - t0
    outs = []
    for rid, (p, m) in zip(ids, requests):
        full = np.asarray(results[rid])
        assert full.size == p.size + m, (rid, full.size, p.size, m)
        assert (full[:p.size] == p).all(), rid
        outs.append(full[p.size:])
    assert engine.decode_traces == 1, engine.decode_traces
    return engine, outs, dt


def next_token_logits(model, contexts):
    """The model's own eager forward — the one `generate` runs — over
    `contexts` right-padded to ONE width (causal: padding cannot reach
    the last real position), so one set of eager programs serves them
    all. Yields each context's next-token logits in f32."""
    import paddle_tpu as paddle

    width = -(-max(c.size for c in contexts) // 128) * 128
    with paddle.no_grad():
        for c in contexts:
            padded = np.zeros((1, width), np.int32)
            padded[0, :c.size] = c
            yield np.asarray(
                model(paddle.to_tensor(padded))._array[0, c.size - 1],
                np.float32)


def within_tol(logits, token):
    return bool(logits[token] >= logits.max() - TOKEN_LOGIT_TOL)


def phase_server(args, cfg):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.ops import paged_attention as pa

    kernel_against_dense(args, cfg)

    model = new_model(cfg, args.seed, args.rehearsal)
    requests = make_requests(args, cfg)
    pa.reset_paged_path_stats()
    engine, outs, dt = serve(model, requests)   # DEFAULT arguments
    n_new = sum(m for _, m in requests)
    say("server", requests=len(requests),
        prompt_tokens=[int(p.size) for p, _ in requests],
        new_tokens=[m for _, m in requests],
        backend_requested=engine.attention_backend_requested,
        backend=engine.attention_backend,
        paged_path_stats=dict(pa.PAGED_PATH_STATS),
        decode_traces=engine.decode_traces,
        prefill_traces=engine.prefill_traces,
        run_s_with_compiles=round(dt, 1), generated=n_new)
    want = "dense" if args.rehearsal else "pallas"
    assert engine.attention_backend_requested == "auto"
    assert engine.attention_backend == want, engine.attention_backend
    assert pa.PAGED_PATH_STATS[want] > 0, pa.PAGED_PATH_STATS

    # a second pass over the same requests, nothing left to compile.
    # The prefix cache is on by default and these prompts are now in
    # it, so this is decode time with next to no prefill
    hits = engine.prefix_hit_tokens
    for p, m in requests:
        engine.add_request(p, max_new_tokens=m)
    t0 = time.perf_counter()
    engine.run()
    warm = time.perf_counter() - t0
    say("server", warm_run_s=round(warm, 2),
        new_tokens_per_s=n_new / warm,
        prefix_hit_tokens=engine.prefix_hit_tokens - hits,
        of_prompt_tokens=sum(int(p.size) for p, _ in requests),
        decode_traces=engine.decode_traces)
    assert engine.decode_traces == 1
    peak_line("server", jax.devices()[:1])

    # every request's first token against the model's own forward
    exact = within = 0
    for logits, out in zip(
            next_token_logits(model, [p for p, _ in requests]), outs):
        exact += int(out[0] == logits.argmax())
        within += within_tol(logits, out[0])
    say("server", first_token_is_forward_argmax=f"{exact}/{len(outs)}",
        first_token_within_logit_tol=f"{within}/{len(outs)}",
        logit_tol=TOKEN_LOGIT_TOL)
    assert within == len(outs), (within, len(outs))

    # `model.generate` itself, eager and op by op (every prompt length
    # compiles its own programs), so on the shortest prompts only and
    # for a short continuation. Random weights: later greedy tokens may
    # part on a rounding, so the agreeing share is printed, not asserted
    order = np.argsort([p.size for p, _ in requests])[:GENERATE_REQUESTS]
    first, agree = 0, []
    with paddle.no_grad():
        for i in order:
            (p, m), out = requests[i], outs[i]
            n = min(m, GENERATE_TOKENS)
            ref = np.asarray(model.generate(
                paddle.to_tensor(p[None]), max_length=p.size + n)
                ._array)[0, p.size:]
            first += int(out[0] == ref[0])
            same = out[:n] == ref
            agree.append(int(same.argmin()) if not same.all() else n)
    say("server", generate_on_prompts=[int(requests[i][0].size)
                                       for i in order],
        first_token_equal_to_generate=f"{first}/{len(order)}",
        greedy_tokens_agreeing_before_first_split=agree,
        of=GENERATE_TOKENS)


# -- the mesh phase (--chips 4) ---------------------------------------------

def device_shares(phase, devs, at_least, even=False):
    """Every device of the mesh holds its share, from each device's own
    allocator where the backend has one; `even` where nothing but the
    shares is resident, so that no device may hold much more."""
    used = []
    for d in devs:
        st = allocator(d)
        used.append(None if st is None else st["bytes_in_use"])
    say(phase, bytes_in_use_per_device=used, expected_at_least=at_least)
    if None in used:
        say(phase, note="backend reports no allocator statistics; "
            "shares not checked")
        return
    assert min(used) >= at_least, (used, at_least)
    if even:
        assert max(used) <= 2 * min(used), used     # nothing piled on one


def phase_mesh_trainer(args, cfg, devs):
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.jit as jit
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed.topology import (
        HybridCommunicateGroup, set_hybrid_communicate_group)
    from paddle_tpu.models.gpt import build_pipeline_gpt

    cfg = dataclasses.replace(
        cfg, num_layers=min(cfg.num_layers, MESH_TRAINER_LAYERS))
    batch, seq = 2, cfg.max_seq_len
    ids_np = np.random.RandomState(args.seed).randint(
        0, cfg.vocab_size, (batch, seq), np.int32)

    def loss_fn(out, lab):
        return F.cross_entropy(out.reshape([-1, cfg.vocab_size]),
                               lab.reshape([-1]))

    def build(hcg):
        set_hybrid_communicate_group(hcg)
        paddle.seed(args.seed)
        model = build_pipeline_gpt(cfg, num_stages=2, num_microbatches=2,
                                   recompute_interval=1)
        model.eval()
        if not args.rehearsal:
            model.to(dtype="bfloat16")
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())
        return model, opt

    def run(step):
        ids = paddle.to_tensor(ids_np)
        t0 = time.perf_counter()
        out, times = [], []
        for i in range(args.steps + 1):
            t = time.perf_counter()
            loss = step(ids, ids)
            loss._array.block_until_ready()
            times.append(time.perf_counter() - t)
            out.append(float(loss))
        return out, times[0], float(np.median(times[1:]))

    # one chip first: the same stacked model, the plain TrainStep
    model, opt = build(HybridCommunicateGroup(devices=devs[:1]))
    n_params = sum(p.size for p in model.parameters())
    one, c1, s1 = run(jit.TrainStep(model, opt, loss_fn))
    say("mesh-trainer", chips=1, layers=cfg.num_layers,
        params_M=round(n_params / 1e6, 1),
        compile_s=round(c1, 1), ms_per_step=s1 * 1e3,
        losses=[round(v, 4) for v in one])
    del model, opt
    free_device()

    hcg = HybridCommunicateGroup(mp=2, pp=2, devices=devs[:4])
    model, opt = build(hcg)
    step = dist.DistributedTrainStep(model, opt, loss_fn, hcg=hcg,
                                     batch_axes=("dp",))
    four, c4, s4 = run(step)
    say("mesh-trainer", chips=4, mesh="mp2 x pp2",
        compile_s=round(c4, 1), ms_per_step=s4 * 1e3,
        losses=[round(v, 4) for v in four], rtol=MESH_LOSS_RTOL)
    # params (+ AdamW moments on top), a quarter each
    itemsize = model.parameters()[0]._array.dtype.itemsize
    device_shares("mesh-trainer", devs[:4],
                  at_least=n_params * itemsize // 4, even=True)
    assert all(np.isfinite(four)), four
    assert four[-1] < four[0], four
    np.testing.assert_allclose(four, one, rtol=MESH_LOSS_RTOL)
    del model, opt, step
    set_hybrid_communicate_group(None)
    free_device()


def phase_mesh_server(args, cfg, devs):
    from paddle_tpu.ops import paged_attention as pa

    model = new_model(cfg, args.seed, args.rehearsal)
    requests = make_requests(args, cfg)
    engine, one, dt1 = serve(model, requests, mp_degree=1)
    say("mesh-server", mp_degree=1, backend=engine.attention_backend,
        run_s_with_compiles=round(dt1, 1))
    del engine
    free_device()

    pa.reset_paged_path_stats()
    engine, four, dt4 = serve(model, requests, mp_degree=4)
    equal = [bool((a == b).all()) for a, b in zip(one, four)]
    agree = [int((a == b).argmin()) if not (a == b).all() else a.size
             for a, b in zip(one, four)]
    say("mesh-server", mp_degree=4, backend=engine.attention_backend,
        paged_path_stats=dict(pa.PAGED_PATH_STATS),
        decode_traces=engine.decode_traces,
        run_s_with_compiles=round(dt4, 1),
        streams_equal=f"{sum(equal)}/{len(equal)}",
        tokens_agreeing_before_first_split=agree,
        of=[m for _, m in requests])
    # device 0 also holds the unsharded model the engine was built from
    pool = engine.cache.kpool.nbytes + engine.cache.vpool.nbytes
    device_shares("mesh-server", devs[:4], at_least=pool // 4)
    # the repo's own contract (README "Sharded serving") is that mp=N
    # emits the mp=1 streams token for token. On the chip in bf16 it
    # does not hold for every stream (PR 23: 7 of 8; ROADMAP C7), so a
    # stream that splits is held to this instead: where it splits, the
    # reference forward over the common prefix must score BOTH engines'
    # tokens within the tolerance of its argmax — a rounding, not a fault
    split = [i for i, e in enumerate(equal) if not e]
    contexts = [np.concatenate([requests[i][0], one[i][:agree[i]]])
                for i in split]
    for i, logits in zip(split, next_token_logits(model, contexts)):
        a, b = int(one[i][agree[i]]), int(four[i][agree[i]])
        gaps = [float(logits.max() - logits[t]) for t in (a, b)]
        say("mesh-server", split_stream=i, at_token=agree[i],
            mp1_token=a, mp4_token=b,
            logit_gap_to_reference_argmax=gaps, tol=TOKEN_LOGIT_TOL)
        assert within_tol(logits, a) and within_tol(logits, b), gaps


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny model, any backend; never a result")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=4,
                    help="timed train steps after the compiling one")
    ap.add_argument("--requests", type=int, default=8)
    args = ap.parse_args()

    d0, devs = phase_device(args)
    if not args.rehearsal:  # a rehearsal leaves no cache behind
        from paddle_tpu.utils.compile_cache import enable_compile_cache

        say("cache", dir=enable_compile_cache())
    cfg = gpt_config(args.rehearsal)
    if args.chips == 4:
        phase_mesh_trainer(args, cfg, devs)
        phase_mesh_server(args, cfg, devs)
    else:
        phase_trainer(args, cfg)
        free_device()
        phase_server(args, cfg)
    print(json.dumps({
        "ok": not args.rehearsal,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": args.chips if args.chips == 4
                   else len(devs)}}))


if __name__ == "__main__":
    main()
