"""Per-request sampling parameters for the generation engine — the
host half of the probabilistic serving subsystem (the device half is
`paddle_tpu/ops/sampling.py`).

`SamplingParams(temperature, top_k, top_p, seed)` rides a request
through `GenerationEngine.add_request` / `ServingFleet.add_request`
(and the disaggregated `adopt_request` handoff) and is carried PER
SLOT through the fixed-shape compiled decode and verify steps as
traced per-row arrays — params are data, never trace keys, so
`decode_traces == 1` holds per (backend, K, mp, kv_dtype) for ANY mix
of live greedy and sampled lanes.

Seeding contract: every sampled request owns one integer seed
(explicit, or engine-assigned from a deterministic counter when None).
The seed becomes a `[2]` uint32 base key row (`key_row`) the slot
carries on device; each draw folds the slot's ABSOLUTE position (and a
draw-purpose salt) into it, so the token at position P+1 is drawn with
the key folded from P whatever path produced it — any prefill chunk
size, cold or warm cache, plain decode or a speculative window.
Same (seed, trace, config) => same tokens; `temperature=0` (the
default-off state) is bit-identical to the greedy engine.

`oracle_probs` is the CPU (numpy) reference of the masked sampling
distribution — an independent implementation the statistical
acceptance tests chi-square the device draws against.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import numpy as np

__all__ = ["SamplingParams", "key_row", "oracle_probs"]


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs.

    temperature: 0 = greedy (argmax — bit-identical to a no-sampling
      engine, whatever the other knobs say); > 0 scales the logits by
      1/temperature before the draw.
    top_k: keep only the k highest-probability tokens (0 = off).
    top_p: nucleus sampling — keep the smallest descending-probability
      prefix whose mass reaches top_p (1.0 = off).
    seed: the request's reproducibility anchor. None lets the engine
      (or the fleet, which must resolve it BEFORE a disaggregated
      handoff splits the request across replicas) assign one from its
      deterministic counter.
    """

    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = None

    def __post_init__(self):
        if not self.temperature >= 0:
            raise ValueError(
                f"temperature must be >= 0 (0 = greedy), got "
                f"{self.temperature!r}")
        if int(self.top_k) < 0:
            raise ValueError(f"top_k must be >= 0 (0 = off), got "
                             f"{self.top_k!r}")
        if not 0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got "
                             f"{self.top_p!r}")
        if self.seed is not None and int(self.seed) != self.seed:
            raise ValueError(f"seed must be an integer, got "
                             f"{self.seed!r}")

    @property
    def greedy(self):
        """True when this request decodes greedily (argmax) — the
        bit-exact path; the other knobs are inert."""
        return self.temperature <= 0

    def with_seed(self, seed):
        return dataclasses.replace(self, seed=int(seed))


def key_row(seed):
    """Host-side `[2]` uint32 base key row for a request seed — the
    per-slot key state the compiled steps fold positions into. Derived
    once at admission (and again, identically, when a disaggregated
    decode replica adopts the lane with the same seed). Distinct seeds
    get distinct keys across the full 64-bit range (the low word seeds
    the key, the high word folds in), so hash-derived and negative
    seeds never silently collide."""
    import jax

    s = int(seed) & 0xFFFFFFFFFFFFFFFF
    base = jax.random.PRNGKey(np.uint32(s & 0xFFFFFFFF))
    return np.asarray(jax.random.fold_in(base, np.uint32(s >> 32)),
                      np.uint32)


def oracle_probs(logits, params):
    """CPU (numpy) oracle of the masked sampling distribution one
    logits row induces under `params` — independent of the jnp path in
    `ops/sampling.py`, so the statistical acceptance tests compare two
    implementations, not one with itself. Returns float64 `[V]` probs
    (greedy params: a one-hot at the argmax)."""
    lg = np.asarray(logits, np.float64).reshape(-1)
    V = lg.shape[0]
    if params.greedy:
        p = np.zeros(V)
        p[int(np.argmax(lg))] = 1.0
        return p
    lg = lg / float(params.temperature)
    if params.top_k and params.top_k < V:
        kth = np.sort(lg)[::-1][int(params.top_k) - 1]
        lg = np.where(lg >= kth, lg, -np.inf)
    order = np.argsort(-lg, kind="stable")
    e = np.exp(lg[order] - np.max(lg))
    p_desc = e / e.sum()
    keep_desc = (np.cumsum(p_desc) - p_desc) < float(params.top_p)
    keep_desc[0] = True
    keep = np.empty(V, bool)
    keep[order] = keep_desc
    lg = np.where(keep, lg, -np.inf)
    e = np.exp(lg - np.max(lg))
    return e / e.sum()
