"""Driver `serve_closed_stateful`: `serve_closed`'s loop for a model
whose slots hold recurrent state and whose layers hold sparse experts.
The loop, the clocks, the drain and the comparison are `serve_closed`'s;
this file changes what that one takes from a GPT-2 block:

(a) weights are bound a leaf at a time (`lib/program_stateful.py`), so
    set-up never holds two copies of a model that fills most of a chip;
(b) `flops_required` is the architecture's own count
    (`lib/counts_<architecture>.py`, named by the configuration);
(c) the engine's counters of the new mechanisms reach `facts`: the state
    rows held a step, the experts' load a decode step, and the live
    lanes of the traced decode steps (what the scan's kernel moved);
(d) `unexpected_kernel_path` also holds the scan and the expert product
    to the paths the traffic file expects.
"""
from __future__ import annotations

import importlib

import numpy as np

from benchmarks.lib import program, program_stateful, registry, \
    traffic as traffic_gen

_base = registry.load_module("drivers", "serve_closed")
percentile = _base.percentile


class Cell(_base.Cell):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.counts = importlib.import_module(
            f"benchmarks.lib.counts_{self.cfg['architecture']}")
        self.state_used = []        # state rows held, before each step
        self.counted = {}           # phase -> the engine's counters' rise

    # -- set-up ------------------------------------------------------------
    def setup(self):
        import jax.numpy as jnp

        ctx, cfg, mix = self.ctx, self.cfg, self.mix
        self.spec = ctx.reference.param_spec(cfg)
        self.dtype = jnp.dtype(cfg["dtype"])
        model = program.build_model(cfg, ctx.bench_dir)
        program_stateful.bind_weights_leafwise(model, ctx.seed, self.spec,
                                               self.dtype)
        program.paged_path_stats(reset=True)
        program_stateful.kernel_path_stats(reset=True)
        self.engine = program.build_engine(model, dict(
            mix["engine"], flight_capacity=1 << 18))
        self.model = model
        self.queue = traffic_gen.requests(mix, cfg["vocab_size"], ctx.seed)
        self.free_clients = mix["clients"]
        self.issued, self.results = {}, {}
        self.steps, self.pool_used = [], []
        self.phase = "warm"
        self._loop(lambda: self._finished("warm") >= mix["warm_finished"])
        self.traces_after_warmup = (self.engine.decode_traces,
                                    self.engine.prefill_traces)

    def _issue_due(self):
        self.state_used.append(
            program_stateful.state_rows_used(self.engine))
        super()._issue_due()

    def _loop(self, done, tracer=None):
        before, _ = program_stateful.engine_counters(self.engine)
        super()._loop(done, tracer)
        after, highs = program_stateful.engine_counters(self.engine)
        rise = self.counted.setdefault(self.phase, {})
        for k, v in after.items():
            rise[k] = rise.get(k, 0) + v - before[k]
        rise.update(highs)          # high-water marks of the whole run
        # what a decode step offers the experts: experts held x E layers
        rise["moe_expert_steps"] = rise["decode_steps"] \
            * self.counts.experts_held_all_layers(self.cfg)

    # -- the window ----------------------------------------------------------
    def window(self, seconds, tracer, min_finished=0):
        engine = self.engine
        counted = super().window(seconds, tracer, min_finished)
        self.kernel_paths = program_stateful.kernel_path_stats()
        self.state_rows = program_stateful.state_rows_total(engine)
        self.state_pool_bytes = program_stateful.state_pool_bytes(engine)
        return counted

    def facts(self):
        done = [r for r in self.issued.values()
                if r.get("t_done") is not None
                and self.t0 <= r["t_done"] <= self.t1]
        inside = [i for i, (a, b) in enumerate(self.steps)
                  if a >= self.t0 and b <= self.t1]
        w = [self.steps[i][1] - self.steps[i][0] for i in inside]
        ttft = self._ttft()
        used = [self.pool_used[i] for i in inside]
        rows = [self.state_used[i] for i in inside]
        return {
            "kind": "serve", "cfg": self.cfg, "traffic": self.mix,
            "ttft_p95_ms": 1e3 * percentile(ttft, 95) if ttft else None,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50) if ttft else None,
            "ttft_samples": len(ttft),
            "window_s": self.t1 - self.t0,
            "tokens": self.window_tokens,
            "engine_steps": self.window_steps,
            "engine_step_ms_median": 1e3 * float(np.median(w)) if w else None,
            "requests_finished": len(done),
            "pool_blocks": self.pool_blocks,
            "pool_blocks_used_mean": float(np.mean(used)) if used else None,
            "pool_blocks_used_max": max(used) if used else None,
            "state_rows": self.state_rows,
            "state_rows_used_mean": float(np.mean(rows)) if rows else None,
            "state_pool_bytes": self.state_pool_bytes,
            "flops_required": sum(self.counts.serve_request_flops(
                self.cfg, len(r["prompt"]), r["new"]) for r in done),
            "counters": {"paged_path": self.path_stats,
                         "kernel_paths": self.kernel_paths,
                         "prefix_hit_tokens": self.prefix_hit_tokens,
                         "decode_traces": self.traces_after_window[0],
                         "prefill_traces": self.traces_after_window[1],
                         "window": self.counted.get("window", {}),
                         "slice": self.counted.get("slice", {})},
        }

    def guards(self):
        guards = super().guards()
        wrong = 0
        for kind, key in (("ssm", "expect_ssm_path"),
                          ("moe", "expect_moe_path")):
            want = self.mix.get(key)
            if want is not None:
                stats = self.kernel_paths[kind]
                wrong += int(stats[want] == 0) + sum(
                    n for path, n in stats.items() if path != want)
        guards["unexpected_kernel_path"] += wrong
        return guards
