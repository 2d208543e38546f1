"""Driver `train`: a loop of compiled steps, each ended by
`block_until_ready`, until the clock runs out.

Set-up builds ONE object — the program's `TrainStep` with its model and
optimizer, weights bound from the seed — drives it through its first
steps on the window's own call and feed, reads what the comparison
needs (each step's loss, the first gradient from AdamW's state after one
step, the parameters' change after the checked steps), and hands that
same object to the window. The reference follows those steps once the
window has closed and the program's state is freed.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.lib import check, counts, norms, program, weights
from benchmarks.lib.tracing import Tracer


def make_batches(traffic, cfg, seed, n):
    """`n` batches that all differ, drawn on the host from the seed."""
    rng = np.random.default_rng([int(seed), 0x7261696E])
    b, s = traffic["batch"], traffic["seq"]
    vocab = cfg["vocab_size"]           # ids from the published rows
    out = []
    for _ in range(n):
        if traffic["objective"] == "causal_lm":
            ids = rng.integers(0, vocab, (b, s + 1), dtype=np.int32)
            out.append({"input_ids": ids[:, :-1].copy(),
                        "labels": ids[:, 1:].copy()})
        elif traffic["objective"] == "sequence_classification":
            # a fixed count of each label, in the seed's order: with
            # random weights every sequence pools to nearly the same
            # vector, so the batch's gradient is (ones - zeros) times a
            # common direction, and a draw that happens to balance
            # leaves only rounding to compare (PR 25: the same check
            # read 0.005 on one seed and 0.038 on the next)
            ones = round(b * traffic["label_ones_share"])
            labels = np.zeros(b, np.int32)
            labels[rng.permutation(b)[:ones]] = 1
            out.append({
                "input_ids": rng.integers(0, vocab, (b, s),
                                          dtype=np.int32),
                "labels": labels})
        else:
            raise ValueError(f"unknown objective {traffic['objective']!r}")
    return out


def _norms(arrays, parts, others=None):
    """`{leaf or leaf#part: |a|}`, or `|a - others(name)|`."""
    out = {}
    for n, a in arrays.items():
        out.update(norms.named(n, norms.part_norms(
            a, None if others is None else others(n), parts[n])))
    return out


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.traffic = ctx.config, ctx.traffic
        self.opt_cfg = self.traffic["optimizer"]
        self.check_steps = self.traffic.get("check_steps", 2)
        self.tokens_per_step = self.traffic["batch"] * self.traffic["seq"]
        self.slice_steps = 0

    # -- set-up ------------------------------------------------------------
    def setup(self):
        import jax.numpy as jnp

        ctx, cfg = self.ctx, self.cfg
        self.spec = ctx.reference.param_spec(cfg)
        self.dtype = jnp.dtype(cfg["dtype"])
        self.batches = make_batches(self.traffic, cfg, ctx.seed,
                                    self.traffic.get("distinct_batches",
                                                     16))
        model = program.build_model(cfg, ctx.bench_dir)
        program.bind_weights(
            model, weights.make_all(ctx.seed, self.spec, self.dtype))
        program.flash_path_stats(reset=True)
        self.step, self.opt = program.build_trainer(model, self.opt_cfg)
        self.model = model
        self.n_calls = 0
        index = {s[0]: i for i, s in enumerate(self.spec)}

        def seeded(name):
            return weights.make_leaves(ctx.seed, self.spec,
                                       [index[name]], self.dtype)[0]

        parts = norms.parts_of(self.spec)
        prog = {"loss": []}
        for k in range(self.check_steps):
            prog["loss"].append(float(self.call()))
            if k == 0:      # AdamW after one step: m = (1 - beta1) g
                b1 = self.opt_cfg["beta1"]
                moments = program.first_moments(model, self.opt)
                prog["grad_norm"] = {
                    n: v / (1 - b1)
                    for n, v in _norms(moments, parts).items()}
                prog["grad_cols"] = {
                    n: norms.column_norms(m) / (1 - b1)
                    for n, m in moments.items()}
                del moments
        prog["change_norm"] = _norms(program.parameters(model), parts,
                                     seeded)
        self.prog = prog
        self.traces_after_warmup = program.train_trace_count(self.step)
        self.path_stats = program.flash_path_stats()

    def call(self):
        """The window's own call and feed: the next batch from the host,
        one compiled step, ended by `block_until_ready`."""
        batch = self.batches[self.n_calls % len(self.batches)]
        self.n_calls += 1
        loss = self.step(program.to_tensor(batch["input_ids"]),
                         program.to_tensor(batch["labels"]))
        loss._array.block_until_ready()
        return loss._array

    # -- the window ----------------------------------------------------------
    def window(self, seconds, tracer: Tracer):
        steps, t0 = 0, time.perf_counter()
        t_end = t0
        while t_end - t0 < seconds:
            self.call()
            steps += 1
            t_end = time.perf_counter()
        self.steps, self.window_s = steps, t_end - t0
        if tracer.enabled:
            self.slice_steps = self.traffic.get("trace_steps", 4)
            with tracer.slice():
                for _ in range(self.slice_steps):
                    with tracer.span("bench.train_step"):
                        self.call()
        self.traces_after_window = program.train_trace_count(self.step)
        return {"attempted": steps, "failed": 0}

    def end_to_end(self):
        return {"train_tokens_per_s":
                self.steps * self.tokens_per_step / self.window_s}

    def facts(self):
        """What the per-layer readers may read."""
        return {
            "kind": "train", "cfg": self.cfg, "traffic": self.traffic,
            "window_s": self.window_s, "steps": self.steps,
            "tokens": self.steps * self.tokens_per_step,
            "flops_required": self.steps * self.tokens_per_step
            * counts.train_flops_per_token(self.cfg, self.traffic["seq"]),
            "slice_steps": self.slice_steps,
            "counters": {"flash_path": self.path_stats,
                         "train_traces": self.traces_after_window},
        }

    def guards(self):
        """Exact comparisons (limit 0): nothing compiled inside the
        window, and the expected kernel path was the one traced."""
        want = self.traffic.get("expect_flash_path")
        wrong_path = 0
        if want is not None:
            other = {"pallas": "xla", "xla": "pallas"}[want]
            wrong_path = int(self.path_stats[want] == 0) \
                + self.path_stats[other]
        return {"compiles_in_window":
                self.traces_after_window - self.traces_after_warmup,
                "unexpected_kernel_path": wrong_path}

    # -- after the window ------------------------------------------------------
    def free(self):
        import jax

        self.step = self.opt = self.model = None
        gc.collect()
        jax.clear_caches()
        gc.collect()

    def reference_numbers(self, mm="f32", half_batch=False):
        model = self.ctx.reference.build(
            self.cfg, self.ctx.reference_common.MM[mm])
        return self.ctx.reference_stepwise.train_two_steps(
            model, self.ctx.seed, self.batches, self.opt_cfg, self.dtype,
            steps=self.check_steps, half_batch=half_batch)

    def numbers(self):
        ref = self.reference_numbers()
        numbers, where = check.train_numbers(self.prog, ref)
        numbers.update(self.guards())
        return numbers, {"worst_leaf": where,
                         "loss": {"program": self.prog["loss"],
                                  "reference": ref["loss"]}}
