"""The one general generator of serving traffic. A mix is a data file of
parameters (`traffic/<name>.json`); nothing here knows a mix by name.

Every seed gets the SAME multiset of request sizes — `requests_drawn`
pairs of (prompt tokens, new tokens), the stratified quantiles of the
mix's two log-uniform ranges, paired by a permutation fixed in the file
— in another order, with token ids of its own. So two seeds do the same
work, and the spread between runs is the system's, not the draw's.
Keep `requests_drawn` near what the clients hold at once: a window that
issues only a part of the multiset times another sample of it on every
seed (PR 25: with 2048 drawn and 570 issued a window, tokens/s and TPOT
p95 spread 2.8% and 4.9%; with 96 drawn, a pass a client, 0.7-1.6% and
0.8-0.9%).
"""
from __future__ import annotations

import numpy as np


def log_uniform_grid(lo, hi, n):
    """`n` whole numbers at the mid-quantiles of a log-uniform law on
    [lo, hi]."""
    q = (np.arange(n) + 0.5) / n
    return np.clip(np.rint(np.exp(
        np.log(lo) + q * (np.log(hi) - np.log(lo)))), lo, hi).astype(int)


def sizes(mix):
    """The mix's fixed multiset: `[(prompt_len, new_tokens)]`."""
    n = mix["requests_drawn"]
    prompts = log_uniform_grid(*mix["prompt_tokens"], n)
    news = log_uniform_grid(*mix["new_tokens"], n)
    pairing = np.random.default_rng(mix["pairing_seed"]).permutation(n)
    return list(zip(prompts.tolist(), news[pairing].tolist()))


def requests(mix, vocab, seed):
    """An endless stream: the seed's order of the multiset, then another
    order of it, each request with random ids of its own (no two prompts
    share a block, so a prefix cache must show nothing).
    Yields `(prompt ids int32, new_tokens)`."""
    rng = np.random.default_rng([int(seed), 0x73657276])
    pairs = sizes(mix)
    while True:
        for i in rng.permutation(len(pairs)):
            yield (rng.integers(0, vocab, pairs[i][0], dtype=np.int32),
                   int(pairs[i][1]))
