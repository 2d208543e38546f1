"""Driver `serve_closed_recurrent`: `serve_closed_stateful`'s loop for a
model whose every layer mixes through a recurrent state and which keeps
no paged cache. The loop, the clocks, the counters and the guards are the
stateful driver's; this file changes three things:

(a) `unexpected_kernel_path` also holds the retention's decode step to
    the path the traffic file expects (`expect_retention_path`), and the
    statistics of that op reach `facts` beside the scan's and the
    experts';
(b) the engine's counters of its prefill chunks (`chunk_counter_totals`:
    the prompt rows computed) reach `facts` a phase at a time, as the
    decode steps' do;
(c) the reference's logits are computed ONE SEQUENCE AT A TIME: with the
    whole vocabulary (151,936 rows) four sequences of 2,400 tokens are
    5.9 GB of float32 logits, and a `[T, T]` score matrix a head is 0.9
    GB a sequence;
(d) beside `token_logit_gap` (the LARGEST gap by which a served token's
    logit lies below the reference's best) it reads three more numbers
    of the same gaps: `token_logit_gap_mean` (the mean over the served
    tokens compared), `token_logit_gap_request_mean` (the largest of the
    sampled requests' own means: one slot that goes wrong cannot hide in
    the average of four), `token_logit_gap_over_half_pct` (the share of
    the served tokens whose gap is over half a logit: how OFTEN the
    program's choice is far from the reference's, which a fault that
    moves many tokens a little raises long before it moves the mean) and
    `token_logit_gap_p99`. A second-power score
    has a heavy tail in any reduced precision (where a query is nearly
    orthogonal to every key it remembers, rounding decides the weights),
    so the largest gap of a thousand tokens reads alike in bf16 and in
    fp8; PERF.md gives every reading beside its limit. `detail` names
    the largest gap's request, position and tokens, so that a second run
    of a seed can be held against the first.
"""
from __future__ import annotations

import numpy as np

from benchmarks.lib import registry

_base = registry.load_module("drivers", "serve_closed_stateful")


def retention_path_stats(reset=False):
    from paddle_tpu.ops import retention

    if reset:
        retention.reset_retention_path_stats()
    return dict(retention.RETENTION_PATH_STATS)


def gap_numbers(gaps):
    """`gaps`: one array a sampled request, a served token's gap each.
    -> the numbers beside the largest gap."""
    nan = float("nan")
    if not gaps:
        return {"token_logit_gap_mean": nan,
                "token_logit_gap_request_mean": nan,
                "token_logit_gap_over_half_pct": nan,
                "token_logit_gap_p99": nan}
    every = np.concatenate(gaps)
    return {"token_logit_gap_mean": float(every.mean()),
            "token_logit_gap_request_mean": max(
                float(g.mean()) for g in gaps),
            "token_logit_gap_over_half_pct": 100 * float(
                (every > 0.5).mean()),
            "token_logit_gap_p99": float(np.percentile(every, 99))}


class Cell(_base.Cell):
    def setup(self):
        retention_path_stats(reset=True)
        super().setup()

    def _loop(self, done, tracer=None):
        before = self.engine.chunk_counter_totals
        super()._loop(done, tracer)
        rise = self.counted[self.phase]
        for k, v in self.engine.chunk_counter_totals.items():
            rise[k] = rise.get(k, 0) + v - before[k]

    def window(self, seconds, tracer, min_finished=0):
        counted = super().window(seconds, tracer, min_finished)
        self.kernel_paths["retention"] = retention_path_stats()
        return counted

    def guards(self):
        guards = super().guards()
        want = self.mix.get("expect_retention_path")
        if want is not None:
            stats = self.kernel_paths["retention"]
            guards["unexpected_kernel_path"] += int(stats[want] == 0) \
                + sum(n for path, n in stats.items() if path != want)
        return guards

    def token_logit_gaps(self, mm="f32", served=True):
        """As `serve_closed`'s, the reference walked a sequence at a
        time; the other numbers of the same gaps are left in
        `self.gap_numbers`, the largest gap's witness in
        `self.gap_witness`."""
        ids = self.sample()
        self.gap_numbers, self.gap_witness = gap_numbers([]), {}
        if not ids:
            return float("nan"), 0
        ctx = self.ctx
        models = {m: ctx.reference.build(self.cfg, ctx.reference_common.MM[m])
                  for m in {"f32"} | (set() if served else {mm})}

        def logits(m, seq):
            width = -(-(len(seq) - 1) // 128) * 128
            row = np.zeros((1, width), np.int32)
            row[0, :len(seq) - 1] = seq[:-1]
            return np.asarray(ctx.reference_stepwise.logits_of(
                models[m], ctx.seed, row, self.dtype))[0]

        gaps, worst = [], (-1.0, None)
        for rid in ids:
            seq = np.asarray(self.results[rid], np.int32)
            plen = len(self.issued[rid]["prompt"])
            rows = logits("f32", seq)[plen - 1:len(seq) - 1]
            tokens = seq[plen:] if served else \
                logits(mm, seq)[plen - 1:len(seq) - 1].argmax(-1)
            gap = rows.max(-1) - rows[np.arange(len(tokens)), tokens]
            gaps.append(gap)
            at = int(gap.argmax())
            if gap[at] > worst[0]:
                worst = (float(gap[at]), {
                    "request": int(rid), "prompt_tokens": plen,
                    "answer_position": at, "token": int(tokens[at]),
                    "reference_token": int(rows[at].argmax()),
                    "reference_best_logit": float(rows[at].max()),
                    "reference_logit_std": float(rows[at].std())})
        every = np.concatenate(gaps)
        self.gap_numbers = gap_numbers(gaps)
        self.gap_witness = dict(
            worst[1], gaps_over_one=int((every > 1.0).sum()),
            request_means=[float(g.mean()) for g in gaps])
        return float(every.max()), len(every)

    def numbers(self):
        numbers, detail = super().numbers()
        numbers.update(self.gap_numbers)
        # every gap number, compared or not, beside the witness
        detail["gaps"] = {k: v for k, v in numbers.items() if "gap" in k}
        detail["largest_gap"] = self.gap_witness
        return numbers, detail
