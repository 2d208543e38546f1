"""Component-level timing to find the MFU gap on the flagship bench.

All timing is lax.scan-based (K iterations inside ONE jitted program,
single dispatch) so per-dispatch latency does not enter the timing.
Not part of the public bench surface.
"""
from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

K = 10  # scan iterations per measurement


def scan_time(body, init_carry, n=K, label=""):
    """body: carry -> carry. Times n iterations inside one program."""

    def scanned(c):
        def step(c, _):
            return body(c), ()

        c, _ = jax.lax.scan(step, c, None, length=n)
        return c

    f = jax.jit(scanned)
    out = f(init_carry)  # compile + run
    np.asarray(jax.tree_util.tree_leaves(out)[0].ravel()[:1])  # sync
    t0 = time.time()
    out = f(init_carry)
    np.asarray(jax.tree_util.tree_leaves(out)[0].ravel()[:1])
    dt = (time.time() - t0) / n
    del out
    return dt


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    print("backend:", jax.default_backend())

    if which in ("all", "matmul"):
        m = 4096
        a = jnp.ones((m, m), jnp.bfloat16)

        dt = scan_time(lambda c: (c @ c).astype(jnp.bfloat16), a)
        fl = 2 * m**3
        print(f"matmul {m}: {dt*1e3:.2f} ms, {fl/dt/1e12:.1f} TF/s "
              f"({fl/dt/197e12*100:.0f}% of peak)")

    if which in ("all", "attn"):
        B, S, H, D = 2, 2048, 16, 128
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention, _xla_attention, PATH_STATS)

        att_fwd = 4 * B * H * S * S * D

        q0 = jnp.ones((B, S, H, D), jnp.bfloat16)
        dt = scan_time(lambda q: flash_attention(q, q, q, causal=True), q0)
        print(f"flash fwd: {dt*1e3:.2f} ms ({att_fwd/dt/1e12:.1f} TF/s)")

        def fb_flash(q):
            return jax.grad(lambda q: jnp.sum(
                flash_attention(q, q, q, causal=True).astype(jnp.float32)))(q)

        dt = scan_time(fb_flash, q0)
        print(f"flash fwd+bwd: {dt*1e3:.2f} ms ({3*att_fwd/dt/1e12:.1f} TF/s) "
              f"stats={PATH_STATS}")

        def fb_dense(q):
            def loss(q):
                qh = jnp.swapaxes(q, 1, 2)
                return jnp.sum(_xla_attention(qh, qh, qh, True, 0.0884).astype(jnp.float32))
            return jax.grad(loss)(q)

        dt = scan_time(fb_dense, q0)
        print(f"dense fwd+bwd: {dt*1e3:.2f} ms ({3*att_fwd/dt/1e12:.1f} TF/s)")

    if which in ("all", "model", "fwd"):
        import paddle_tpu as paddle
        import paddle_tpu.jit as jit
        from paddle_tpu.core import random as random_mod
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.jit.api import build_step_fn
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        cfg = GPTConfig(vocab_size=32768, hidden_size=2048, num_layers=24,
                        num_heads=16, max_seq_len=2048, dropout=0.0)
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        model.eval()
        model.to(dtype="bfloat16")
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())
        step = jit.TrainStep(model, opt, model.loss_fn)
        params = [p._array for p in step._params]
        ids = jnp.asarray(np.random.randint(0, cfg.vocab_size, (2, 2048), np.int32))
        rng = jax.random.PRNGKey(0)
        tok = 2 * 2048
        fl_tok = model.flops_per_token(2048)
        ideal = tok * fl_tok / 197e12

        def fwd_loss(param_arrays, inputs, label, rng):
            originals = [p._array for p in step._params]
            try:
                for p, a in zip(step._params, param_arrays):
                    p._array = a
                with random_mod.key_scope(rng):
                    out = model(Tensor._wrap(inputs))
                    loss = model.loss_fn(out, Tensor._wrap(label))
                return loss._array
            finally:
                for p, o in zip(step._params, originals):
                    p._array = o

        if which == "fwd":
            # fwd only: carry = params (loss folded back in so scan isn't elided)
            def body2(c):
                ps, x = c
                l = fwd_loss(ps, x, x, rng)
                return (ps, x + (l * 0).astype(jnp.int32))

            dt = scan_time(body2, (params, ids))
            print(f"model fwd: {dt*1e3:.1f} ms (ideal fwd ~{ideal/3*1e3:.0f} ms)")

            def body3(c):
                ps, x = c
                l, gs = jax.value_and_grad(fwd_loss)(ps, x, x, rng)
                return (gs, x + (l * 0).astype(jnp.int32))

            dt = scan_time(body3, (params, ids))
            print(f"model fwd+bwd: {dt*1e3:.1f} ms (ideal ~{ideal*1e3:.0f} ms)")

        if which in ("all", "model"):
            step_fn = build_step_fn(model, opt, model.loss_fn, step._params,
                                    step._acc_idx)
            accums = step._gather_accums()
            bufs = step._buf_arrays()
            lr = jnp.asarray(1e-4, jnp.float32)

            def body(c):
                ps, acc, mb, st, x = c
                loss, nps, nacc, nmb = step_fn(ps, acc, mb, lr, st, (x,),
                                               x, rng)
                return (nps, nacc, nmb, st + 1,
                        x + (loss * 0).astype(jnp.int32))

            st = jnp.asarray(0, jnp.int32)
            dt = scan_time(body, (params, accums, bufs, st, ids))
            print(f"full step: {dt*1e3:.1f} ms  mfu={ideal/dt:.3f}  "
                  f"(ideal ~{ideal*1e3:.0f} ms)")


if __name__ == "__main__":
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
