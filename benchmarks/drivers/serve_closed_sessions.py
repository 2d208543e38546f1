"""Driver `serve_closed_sessions`: `serve_closed`'s loop, clocks, drain
and comparison, with `serve_closed_stateful`'s leaf-wise weights,
architecture counts and expert counters, for clients that each hold ONE
long document for the whole run and ask short questions of it.

Client `i`'s document (`document_tokens`: the stratified quantiles of a
log-uniform range, one a client, ids from the seed) is the head of every
prompt it sends; the question after it is fresh ids a request. So the
client's first request prefills its document cold, and every later one
finds the document's full blocks in the prefix cache and computes the
tail alone. The (question, answer) sizes are the general generator's
fixed multiset (`lib/traffic.sizes`), taken in the seed's order by
whichever client is free.

Set-up ends when EVERY client has finished a request and `warm_finished`
have finished in all: the window opens with every document cached, none
pending cold, the clients out of step.

Beside the numbers of the two drivers it builds on, this one compares

* `token_logit_gap_mean`: the mean, over the served tokens compared, of
  what `token_logit_gap` takes the largest of;
* `document_blocks_missed`: full document blocks of the window's
  requests that were not prefix hits (an eviction, a broken chain);

and plants three faults into the reference put in the program's place
(`token_logit_gaps(fault=...)`, read by `calibrate_sessions.py` and the
tests, never by a benchmark run): a cached page left out of the walk, the
rotation left off the cached keys, a document's blocks mapped one block
late.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.lib import program, program_sessions, program_stateful, \
    registry, traffic as traffic_gen

_base = registry.load_module("drivers", "serve_closed_stateful")
percentile = _base.percentile

FAULTS = ("skipped_page", "no_key_rotation", "late_blocks")


class Cell(_base.Cell):
    # -- set-up ------------------------------------------------------------
    def setup(self):
        import jax.numpy as jnp

        ctx, cfg, mix = self.ctx, self.cfg, self.mix
        self.spec = ctx.reference.param_spec(cfg)
        self.dtype = jnp.dtype(cfg["dtype"])
        model = program.build_model(cfg, ctx.bench_dir)
        program_stateful.bind_weights_leafwise(model, ctx.seed, self.spec,
                                               self.dtype)
        program_stateful.kernel_path_stats(reset=True)
        program_sessions.latent_path_stats(reset=True)
        self.engine = program.build_engine(model, dict(
            mix["engine"], flight_capacity=1 << 18))
        self.model = model
        rng = np.random.default_rng([int(ctx.seed), 0x646F6373])
        n = mix["clients"]
        lengths = traffic_gen.log_uniform_grid(*mix["document_tokens"], n)
        self.documents = [
            rng.integers(0, cfg["vocab_size"], int(k), dtype=np.int32)
            for k in lengths[rng.permutation(n)]]
        self.turns = self._turns(rng)
        self.current = [None] * n   # client -> its request in flight
        self.free_clients = 0       # the base's count: not what issues
        self.finished_by = [0] * n
        self.issued, self.results = {}, {}
        self.steps, self.pool_used = [], []
        self.phase = "warm"
        self._loop(lambda: min(self.finished_by) >= 1
                   and self._finished("warm") >= mix["warm_finished"])
        self.traces_after_warmup = (self.engine.decode_traces,
                                    self.engine.prefill_traces)

    def _turns(self, rng):
        """An endless stream of (question ids, new tokens): the seed's
        order of the mix's multiset, then another order of it."""
        pairs = traffic_gen.sizes(self.mix)
        vocab = self.cfg["vocab_size"]
        while True:
            for i in rng.permutation(len(pairs)):
                yield (rng.integers(0, vocab, pairs[i][0], dtype=np.int32),
                       int(pairs[i][1]))

    def _issue_due(self):
        self.state_used.append(
            program_stateful.state_rows_used(self.engine))
        if self.phase == "drain":
            return
        for client, rid in enumerate(self.current):
            if rid is not None:
                if "t_done" not in self.issued[rid]:
                    continue
                self.finished_by[client] += 1
            question, new = next(self.turns)
            prompt = np.concatenate([self.documents[client], question])
            t = time.perf_counter()
            rid = self.engine.add_request(prompt, max_new_tokens=new)
            self.issued[rid] = {"t_issue": t, "prompt": prompt, "new": new,
                                "phase": self.phase, "client": client}
            self.current[client] = rid

    # -- the window ----------------------------------------------------------
    def window(self, seconds, tracer, min_finished=0):
        counted = super().window(seconds, tracer, min_finished)
        self.latent_paths = program_sessions.latent_path_stats()
        return counted

    def _read_flight(self):
        for ev in self.engine.flight.dump():
            r = self.issued.get(ev.get("req_id"))
            if r is None:
                continue
            if ev["event"] == "first_token":
                r["t_first"] = ev["t_us"] / 1e6
            elif ev["event"] == "finish":
                r["t_finish"] = ev["t_us"] / 1e6
            elif ev["event"] == "admitted":
                r["hit_tokens"] = int(ev["hit_tokens"])

    def _window_done(self):
        return [r for r in self.issued.values()
                if r.get("t_done") is not None
                and self.t0 <= r["t_done"] <= self.t1]

    def _blocks_missed(self, requests):
        bs = self.mix["engine"]["block_size"]
        return sum(len(self.documents[r["client"]]) // bs
                   - min(r.get("hit_tokens", 0),
                         len(self.documents[r["client"]])) // bs
                   for r in requests)

    def facts(self):
        done = self._window_done()
        facts = super().facts()
        # what the requests REQUIRE with their documents served from the
        # cache: work the cache saved is not counted
        facts["flops_required"] = sum(self.counts.serve_request_flops(
            self.cfg, len(r["prompt"]), r["new"], r.get("hit_tokens", 0))
            for r in done)
        facts["prompt_tokens"] = sum(len(r["prompt"]) for r in done)
        facts["prompt_tokens_hit"] = sum(r.get("hit_tokens", 0)
                                         for r in done)
        facts["document_blocks_missed"] = self._blocks_missed(done)
        facts["counters"]["latent_paths"] = self.latent_paths
        return facts

    def guards(self):
        guards = super().guards()
        want = self.mix.get("expect_latent_path")
        if want is not None:
            stats = self.latent_paths["decode"]
            guards["unexpected_kernel_path"] += int(stats[want] == 0) \
                + sum(n for path, n in stats.items() if path != want)
        guards["document_blocks_missed"] = self._blocks_missed(
            r for r in self.issued.values() if r["phase"] == "window"
            and "hit_tokens" in r)
        return guards

    # -- after the window ------------------------------------------------------
    def fault_of(self, name, request):
        """The reference's `fault` argument that plants `name` into the
        sampled `request`."""
        bs = self.mix["engine"]["block_size"]
        doc_blocks = len(self.documents[request["client"]]) // bs
        if name == "skipped_page":
            lo = (doc_blocks // 2) * bs
            return ("skip_keys", lo, lo + bs)
        if name == "late_blocks":
            return ("late_keys", doc_blocks * bs, bs)
        return name

    def token_logit_gaps(self, mm="f32", served=True, fault=None):
        """Reference logits over each sampled prompt with its served
        tokens, ONE sequence at a time (128 heads of scores over 8k keys
        fill the chip). `served=True`: the gap by which a served token's
        logit lies below the reference's best, the largest and the mean
        over the served tokens. `served=False`: the same of the token
        that the reference computed in `mm` precision (the control), or
        with `fault` planted (one of `FAULTS`), puts first. -> (largest
        gap, tokens compared); the mean is left in `self.gap_mean`."""
        ids = self.sample()
        self.gap_mean = float("nan")
        if not ids:
            return float("nan"), 0
        ref, common = self.ctx.reference, self.ctx.reference_common
        logits_of = self.ctx.reference_stepwise.logits_of
        seqs = [np.asarray(self.results[rid], np.int32) for rid in ids]
        width = -(-max(len(s) - 1 for s in seqs) // 256) * 256
        exact = ref.build(self.cfg, common.MM["f32"])
        gaps = []
        for rid, s in zip(ids, seqs):
            r = self.issued[rid]
            batch = np.zeros((1, width), np.int32)
            batch[0, :len(s) - 1] = s[:-1]
            plen = len(r["prompt"])
            rows = np.asarray(logits_of(
                exact, self.ctx.seed, batch,
                self.dtype))[0, plen - 1:len(s) - 1]
            if served:
                tokens = s[plen:]
            else:
                other = ref.build(self.cfg, common.MM[mm]) if fault is None \
                    else ref.build(self.cfg, common.MM["f32"],
                                   fault=self.fault_of(fault, r))
                tokens = np.asarray(logits_of(
                    other, self.ctx.seed, batch,
                    self.dtype))[0, plen - 1:len(s) - 1].argmax(-1)
            gaps.append(rows.max(-1) - rows[np.arange(len(tokens)), tokens])
        gaps = np.concatenate(gaps)
        self.gap_mean = float(gaps.mean())
        return float(gaps.max()), len(gaps)

    def numbers(self):
        gap, n = self.token_logit_gaps()
        numbers = {"token_logit_gap": gap,
                   "token_logit_gap_mean": self.gap_mean,
                   "wrong_answers": self.wrong_answers()}
        numbers.update(self.guards())
        done = self._window_done()
        return numbers, {"served_tokens_compared": n,
                         "requests_compared": len(self.sample()),
                         "tpot_samples": getattr(self, "n_tpot", None),
                         "requests_finished": len(done),
                         "longest_compared": max(
                             (len(self.results[r]) for r in self.sample()),
                             default=0)}
