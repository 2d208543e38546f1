"""Driver `serve_closed`: N clients, each sending its next request the
moment its last one finishes. The harness owns the loop — it issues due
requests with `add_request`, calls `engine.step()`, stamps its own
clock, takes first-token and finish times from the engine's flight
recorder (the same `perf_counter` clock) and outputs from
`pop_results()`.

Set-up runs the same loop until `warm_finished` requests have finished:
both programs compile, the clients fall out of step, and the window
opens on a steady state. After the window closes nothing new is issued
and every request in flight is waited for: one that comes late is late,
not wrong.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.lib import counts, program, traffic as traffic_gen, weights
from benchmarks.lib.tracing import Tracer

DRAIN_LIMIT_S = 60.0


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.mix = ctx.config, ctx.traffic

    # -- set-up ------------------------------------------------------------
    def setup(self):
        import jax.numpy as jnp

        ctx, cfg, mix = self.ctx, self.cfg, self.mix
        self.spec = ctx.reference.param_spec(cfg)
        self.dtype = jnp.dtype(cfg["dtype"])
        model = program.build_model(cfg, ctx.bench_dir)
        program.bind_weights(
            model, weights.make_all(ctx.seed, self.spec, self.dtype))
        program.paged_path_stats(reset=True)
        self.engine = program.build_engine(model, dict(
            mix["engine"], flight_capacity=1 << 18))
        self.model = model
        self.queue = traffic_gen.requests(mix, cfg["vocab_size"], ctx.seed)
        self.free_clients = mix["clients"]
        self.issued = {}            # req_id -> dict(t_issue, plen, new, phase)
        self.results = {}           # req_id -> token list
        self.steps = []             # (t_before, t_after) of every step()
        self.pool_used = []         # referenced pool blocks after each
        self.phase = "warm"
        self._loop(lambda: self._finished("warm") >= mix["warm_finished"])
        self.traces_after_warmup = (self.engine.decode_traces,
                                    self.engine.prefill_traces)

    def _finished(self, phase=None):
        return sum(1 for r in self.issued.values()
                   if "t_done" in r and phase in (None, r["phase"]))

    def _issue_due(self):
        while self.free_clients and self.phase != "drain":
            prompt, new = next(self.queue)
            t = time.perf_counter()
            rid = self.engine.add_request(prompt, max_new_tokens=new)
            self.issued[rid] = {"t_issue": t, "prompt": prompt,
                                "new": new, "phase": self.phase}
            self.free_clients -= 1

    def _loop(self, done, tracer=None):
        engine = self.engine
        while not done():
            self._issue_due()
            t_a = time.perf_counter()
            if tracer is not None:
                with tracer.span("bench.engine_step"):
                    engine.step()
            else:
                engine.step()
            t_b = time.perf_counter()
            self.steps.append((t_a, t_b))
            self.pool_used.append(program.pool_blocks_used(engine))
            for rid, tokens in engine.pop_results().items():
                self.issued[rid]["t_done"] = t_b
                self.results[rid] = tokens
                self.free_clients += 1

    # -- the window ----------------------------------------------------------
    def window(self, seconds, tracer: Tracer, min_finished=0):
        """`min_finished` keeps a test's window open until that many
        requests have finished, however slow the machine; a benchmark
        run leaves it 0 and the clock alone closes the window."""
        engine = self.engine
        self.phase = "window"
        tok0, t0 = engine.tokens_generated, time.perf_counter()
        step0 = len(self.steps)
        self._loop(lambda: time.perf_counter() - t0 >= seconds
                   and self._finished("window") >= min_finished)
        t1 = time.perf_counter()
        self.t0, self.t1 = t0, t1
        self.window_tokens = engine.tokens_generated - tok0
        self.window_steps = len(self.steps) - step0
        self.slice_steps = None
        if tracer.enabled:
            self.phase = "slice"
            s0, ts = len(self.steps), time.perf_counter()
            slice_s = self.mix.get("trace_seconds", 2.0)
            with tracer.slice():
                self._loop(lambda: time.perf_counter() - ts >= slice_s,
                           tracer)
            self.slice_steps = (s0, len(self.steps))
        self.phase = "drain"
        t_drain = time.perf_counter()
        self._loop(lambda: (not engine.num_active and not engine.num_pending)
                   or time.perf_counter() - t_drain > DRAIN_LIMIT_S)
        self.t_drained = time.perf_counter()
        self.traces_after_window = (engine.decode_traces,
                                    engine.prefill_traces)
        self.path_stats = program.paged_path_stats()
        self.backend = engine.attention_backend
        self.prefix_hit_tokens = engine.prefix_hit_tokens
        self.pool_blocks = program.pool_blocks_total(engine)
        self._read_flight()
        mine = [r for r in self.issued.values() if r["phase"] == "window"]
        self.failed = sum(1 for r in mine if "t_done" not in r)
        return {"attempted": len(mine), "failed": self.failed}

    def _read_flight(self):
        for ev in self.engine.flight.dump():
            rid = ev.get("req_id")
            if rid not in self.issued:
                continue
            if ev["event"] == "first_token":
                self.issued[rid]["t_first"] = ev["t_us"] / 1e6
            elif ev["event"] == "finish":
                self.issued[rid]["t_finish"] = ev["t_us"] / 1e6

    def _ttft(self):
        """Of every request issued in the window; one that never
        finished counts as the worst (it waited to the end)."""
        worst = self.t_drained
        return [(r.get("t_first", worst) if "t_done" in r else worst)
                - r["t_issue"]
                for r in self.issued.values() if r["phase"] == "window"]

    def end_to_end(self):
        tpot = [(r["t_finish"] - r["t_first"]) / (r["new"] - 1)
                for r in self.issued.values()
                if r.get("t_done") is not None and r["new"] > 1
                and self.t0 <= r["t_done"] <= self.t1]
        self.n_tpot = len(tpot)
        return {
            "serve_tokens_per_s": self.window_tokens / (self.t1 - self.t0),
            "tpot_p95_ms": 1e3 * percentile(tpot, 95),
            "ttft_p95_ms": 1e3 * percentile(self._ttft(), 95),
        }

    def facts(self):
        done = [r for r in self.issued.values()
                if r.get("t_done") is not None
                and self.t0 <= r["t_done"] <= self.t1]
        w = [b - a for a, b in self.steps
             if a >= self.t0 and b <= self.t1]
        ttft = self._ttft()
        used = [u for u, (a, b) in zip(self.pool_used, self.steps)
                if a >= self.t0 and b <= self.t1]
        facts = {
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
            "flops_required": sum(counts.serve_request_flops(
                self.cfg, len(r["prompt"]), r["new"]) for r in done),
            "counters": {"paged_path": self.path_stats,
                         "prefix_hit_tokens": self.prefix_hit_tokens,
                         "decode_traces": self.traces_after_window[0],
                         "prefill_traces": self.traces_after_window[1]},
        }
        if self.slice_steps:
            facts["slice_context_tokens"] = self._slice_context_tokens()
        return facts

    def _slice_context_tokens(self):
        """Sum, over the decode steps of the traced slice, of the live
        contexts' lengths: a request decodes in every engine step that
        starts after its first token and ends by its finish, its context
        one token longer each time."""
        starts = np.array([a for a, _ in self.steps])
        lo, hi = self.slice_steps
        total = 0
        for r in self.issued.values():
            if "t_first" not in r:
                continue
            first = int(np.searchsorted(starts, r["t_first"], "left"))
            last = int(np.searchsorted(
                starts, r.get("t_finish", np.inf), "left")) - 1
            a, b = max(first, lo), min(last, hi - 1)
            if b < a:
                continue
            # context at step k: prompt + 1 + (k - first)
            n = b - a + 1
            base = len(r["prompt"]) + 1 + (a - first)
            total += n * base + n * (n - 1) // 2
        return total

    def guards(self):
        want = self.mix.get("expect_paged_path")
        wrong = 0
        if want is not None:
            other = {"pallas": "dense", "dense": "pallas"}[want]
            wrong = int(self.path_stats[want] == 0) \
                + self.path_stats[other] + int(self.backend != want)
        compiles = sum(self.traces_after_window) \
            - sum(self.traces_after_warmup)
        return {"compiles_in_window": compiles,
                "unexpected_kernel_path": wrong,
                "prefix_hit_tokens": self.prefix_hit_tokens
                if self.mix.get("expect_no_prefix_hits") else 0}

    # -- after the window ------------------------------------------------------
    def free(self):
        import jax

        self.engine = self.model = None
        gc.collect()
        jax.clear_caches()
        gc.collect()

    def wrong_answers(self):
        """Every answer due: the prompt echoed and exactly the tokens
        asked for. One that never came counts too."""
        wrong = 0
        for rid, r in self.issued.items():
            if r["phase"] == "slice":
                continue
            out = self.results.get(rid)
            if out is None or len(out) != len(r["prompt"]) + r["new"] \
                    or list(out[:len(r["prompt"])]) != r["prompt"].tolist():
                wrong += 1
        return wrong

    def sample(self):
        """A sample, drawn from the seed, of the requests the window
        finished, the longest among them."""
        done = sorted(
            (rid for rid, r in self.issued.items()
             if r["phase"] == "window" and rid in self.results
             and self.t0 <= r["t_done"] <= self.t1),
            key=lambda rid: -len(self.results[rid]))
        if not done:
            return []
        k = self.mix.get("check_requests", 4)
        rng = np.random.default_rng([self.ctx.seed, 0x636865636B])
        rest = done[1:]
        picks = rng.choice(len(rest), min(k - 1, len(rest)), replace=False) \
            if rest else []
        return [done[0]] + [rest[i] for i in picks]

    def token_logit_gaps(self, mm="f32", served=True):
        """Reference logits over each sampled prompt with its served
        tokens. `served=True`: the widest gap by which a served token's
        logit lies below the reference's best. `served=False` (the
        control): at each position, the gap of the token that the
        reference in `mm` precision puts first, under the float32
        reference."""
        ids = self.sample()
        if not ids:
            return float("nan"), 0
        ref = self.ctx.reference
        seqs = [np.asarray(self.results[rid], np.int32) for rid in ids]
        width = -(-max(len(s) - 1 for s in seqs) // 128) * 128
        batch = np.zeros((len(seqs), width), np.int32)
        for i, s in enumerate(seqs):
            batch[i, :len(s) - 1] = s[:-1]
        common = self.ctx.reference_common
        logits = np.asarray(self.ctx.reference_stepwise.logits_of(
            ref.build(self.cfg, common.MM["f32"]), self.ctx.seed, batch,
            self.dtype))
        if not served:
            low = np.asarray(self.ctx.reference_stepwise.logits_of(
                ref.build(self.cfg, common.MM[mm]), self.ctx.seed, batch,
                self.dtype))
        worst, n = 0.0, 0
        for i, (rid, s) in enumerate(zip(ids, seqs)):
            plen = len(self.issued[rid]["prompt"])
            rows = logits[i, plen - 1:len(s) - 1]
            if served:
                tokens = s[plen:]
            else:
                tokens = low[i, plen - 1:len(s) - 1].argmax(-1)
            gap = rows.max(-1) - rows[np.arange(len(tokens)), tokens]
            worst, n = max(worst, float(gap.max())), n + len(tokens)
        return worst, n

    def numbers(self):
        gap, n = self.token_logit_gaps()
        numbers = {"token_logit_gap": gap,
                   "wrong_answers": self.wrong_answers()}
        numbers.update(self.guards())
        return numbers, {"served_tokens_compared": n,
                         "requests_compared": len(self.sample()),
                         "tpot_samples": getattr(self, "n_tpot", None)}
