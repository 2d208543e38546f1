"""Hybrid-parallel GPT benchmark — the BASELINE.md flagship config
(GPT-1.3B, mp=2 pp=2 sharding-stage-2) over a device mesh.

On a real v5e-16 slice this runs the full 1.3B config; on a single chip
or the virtual CPU mesh (BENCH_TINY=1 with
XLA_FLAGS=--xla_force_host_platform_device_count=8) it validates that
the exact same mp2/pp2/sharding2 program compiles and steps.

Prints ONE JSON line like bench.py.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def main():
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.topology import (
        HybridCommunicateGroup,
        set_hybrid_communicate_group,
    )
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.models.gpt import build_pipeline_gpt

    n_dev = len(jax.devices())
    tiny = os.environ.get("BENCH_TINY") == "1" or n_dev < 8
    mp = 2 if n_dev >= 2 else 1
    pp = 2 if n_dev >= 4 else 1
    sharding = 2 if n_dev >= 8 else 1
    dp = n_dev // (mp * pp * sharding)

    hcg = HybridCommunicateGroup(dp=dp, mp=mp, pp=pp, sharding=sharding)
    set_hybrid_communicate_group(hcg)

    if tiny:
        cfg = GPTConfig.tiny(vocab=512, hidden=64, layers=4, heads=4, seq=64)
        batch, steps, peak = 8, 3, 1e12
    else:
        cfg = GPTConfig.gpt_1p3b()
        cfg.vocab_size = 32768
        batch, steps = int(os.environ.get("BENCH_BATCH", "8")), 5
        peak = 197e12 * n_dev

    paddle.seed(0)
    model = build_pipeline_gpt(cfg, num_stages=pp, num_microbatches=max(pp, 2),
                               recompute_interval=0 if tiny else 1)
    model.eval()
    if not tiny:
        model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = dist.DistributedTrainStep(
        model, opt,
        lambda out, lab: F.cross_entropy(
            out.reshape([-1, cfg.vocab_size]), lab.reshape([-1])),
        hcg=hcg, sharding_stage=2, batch_axes=("dp", "sharding"))

    seq = cfg.max_seq_len
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq),
                                       np.int32))
    t0 = time.time()
    loss = step(ids, ids)
    _ = float(loss)
    compile_s = time.time() - t0

    t1 = time.time()
    for _ in range(steps):
        loss = step(ids, ids)
    val = float(loss)  # readback blocks
    dt = (time.time() - t1) / steps

    n_params = sum(p.size for p in model.parameters())
    flops_tok = 6 * n_params + 12 * cfg.num_layers * cfg.hidden_size * seq
    tok_s = batch * seq / dt
    mfu = tok_s * flops_tok / peak

    if tiny:
        # degenerate config (n_dev<8 collapses the hybrid degrees, or a
        # virtual CPU mesh): this validates compile+step only — emitting
        # a throughput-shaped metric line here would be misleading
        print(json.dumps({
            "metric": "gpt_hybrid_compile_check",
            "value": 1,
            "unit": "ok (NOT a throughput measurement: tiny/collapsed "
                    f"config, devices={n_dev} mp={mp} pp={pp} "
                    f"sharding={sharding})",
            "vs_baseline": None,
        }))
    else:
        print(json.dumps({
            "metric": "gpt_1p3b_hybrid_mp2_pp2_sharding2_tokens_per_sec",
            "value": round(tok_s, 1),
            "unit": "tokens/s",
            "vs_baseline": round(mfu / 0.45, 4),
        }))
    print(f"# devices={n_dev} mesh dp={dp} mp={mp} pp={pp} "
          f"sharding={sharding} params={n_params/1e6:.1f}M batch={batch} "
          f"seq={seq} compile={compile_s:.1f}s step={dt*1000:.1f}ms "
          f"mfu={mfu:.3f} loss={val:.3f}", file=sys.stderr)


if __name__ == "__main__":
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
