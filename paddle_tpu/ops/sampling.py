"""On-device categorical sampling + rejection-sampling speculative
acceptance — the op tier under the probabilistic serving subsystem
(paddle_tpu/inference/sampling.py wires it into the engine).

Per-slot sampling params (temperature / top-k / top-p) arrive as TRACED
per-row arrays — params are DATA, never trace keys, so one compiled
decode/verify program serves every live mix of greedy and sampled lanes
(the engine's `decode_traces == 1` contract is unchanged by sampling).
Greedy lanes (`temperature <= 0`) take the literal `jnp.argmax` the
pre-sampling engine computed — same op over the same logits, so their
token streams are BIT-identical to the greedy engine's.

Randomness is keyed per (request seed, absolute position): each slot
carries a `[2]` uint32 base key row (derived host-side from its
request's seed, threaded beside the pools as a `[slots, 2]` array) and
every draw folds the row's absolute position plus a draw-purpose salt
into it — `fold_in(fold_in(base, position), salt)` — so

- no key is ever consumed twice (tpu-lint TPU003 clean by
  construction: `fold_in` is a key DERIVER, and each derived key feeds
  exactly one sampler);
- the token at absolute position P+1 is always drawn with the key
  folded from P, whatever path produced it (a prompt's final prefill
  chunk, a full-prefix-hit decode, a speculative bonus draw) — same
  (seed, trace, config) => same tokens, and cold / warm runs agree
  token-for-token;
- the draws are backend-independent (they consume logits AFTER
  attention), so sampled streams are identical across the dense and
  pallas backends wherever the greedy streams are.

Rejection-sampling speculative acceptance (`verify_window`): the
engine's drafters are DETERMINISTIC (n-gram lookup, greedy tiny-GPT),
i.e. the draft distribution q is a point mass at the proposed token —
the Leviathan et al. ("Fast Inference from Transformers via
Speculative Decoding") accept test `u < min(1, p(x)/q(x))` reduces to
`u < p(x)`, and the residual resample `norm(max(p - q, 0))` reduces to
p with the rejected token's mass zeroed (renormalized by the softmax).
That preserves the target distribution EXACTLY: the emitted marginal is
`p(d)*1[x=d] + (1-p(d)) * p(x)/(1-p(d)) = p(x)` — a draft can change
which random numbers are consumed, never what distribution the stream
is drawn from. Greedy lanes run the same structure with the accept
test degraded to argmax EQUALITY and every choice pinned to argmax, so
the host's uniform walk (`drafts[:n] + choices[n]`) reproduces the
exact-acceptance token stream bit-for-bit.

All functions here are raw-jnp compiled-step bodies (the
`copy_pool_block` precedent), not user-facing Tensor ops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["masked_logits", "sample_token", "verify_window",
           "SALT_SAMPLE", "SALT_ACCEPT"]

#: Draw-purpose salts folded into the per-(slot, position) key: the
#: categorical draw (plain sample / speculative bonus / rejection
#: resample — mutually exclusive uses of one row, so they share a
#: stream) and the acceptance uniform must be independent.
SALT_SAMPLE = 0
SALT_ACCEPT = 1


def masked_logits(logits, temps, top_ks, top_ps):
    """Temperature/top-k/top-p masking, fused before the sample.

    `logits` `[N, V]` (any float dtype), `temps`/`top_ks`/`top_ps`
    `[N]` per-row params -> fp32 logits whose softmax is each row's
    sampling distribution: scaled by `1/temperature`, then everything
    below the k-th largest scaled logit masked to -inf (`top_k <= 0`
    = off), then the nucleus mask — ranked by descending probability,
    a token survives iff the cumulative mass BEFORE it is < `top_p`
    (the crossing token stays, so at least the argmax always
    survives). Greedy rows (`temperature <= 0`) are scaled by 1.0 —
    their masked logits are junk the callers never select (they take
    the argmax path instead)."""
    lg = logits.astype(jnp.float32)
    N, V = lg.shape
    safe_t = jnp.where(temps <= 0.0, 1.0,
                       temps.astype(jnp.float32))
    lg = lg / safe_t[:, None]
    # ONE descending sort serves both masks (this runs in the hot
    # decode/verify step): argsort is stable, so ties resolve
    # deterministically and runs reproduce. Top-k -infs entries below
    # the k-th largest; their descending rank doesn't move and their
    # probability is 0, so the nucleus cumsum over the UNMASKED order
    # is identical to one over the masked order.
    order = jnp.argsort(-lg, axis=-1)
    desc = jnp.take_along_axis(lg, order, axis=-1)
    k = jnp.where(top_ks <= 0, V, top_ks)
    kth = jnp.take_along_axis(desc, jnp.clip(k - 1, 0, V - 1)[:, None],
                              axis=1)
    lg = jnp.where(lg >= kth, lg, -jnp.inf)
    p_desc = jax.nn.softmax(jnp.where(desc >= kth, desc, -jnp.inf),
                            axis=-1)
    cum = jnp.cumsum(p_desc, axis=-1)
    keep_desc = (cum - p_desc) < top_ps.astype(jnp.float32)[:, None]
    keep_desc = keep_desc.at[:, 0].set(True)   # argmax always survives
    # un-permute by scatter (O(V)) instead of a second argsort: a
    # True landing on a top-k-masked entry keeps -inf either way
    keep = jnp.zeros((N, V), bool) \
        .at[jnp.arange(N)[:, None], order].set(keep_desc)
    return jnp.where(keep, lg, -jnp.inf)


def _draw_categorical(lg, key_rows, positions, salt):
    """One categorical draw per row of `lg` `[N, V]`: row i's key is
    `fold_in(fold_in(key_rows[i], positions[i]), salt)` — consumed by
    exactly one sampler."""
    def one(row_key, pos, row_lg):
        k = jax.random.fold_in(row_key, pos)
        return jax.random.categorical(jax.random.fold_in(k, salt),
                                      row_lg)

    return jax.vmap(one)(key_rows, positions, lg)


def _draw_uniform(key_rows, positions, salt):
    """One U[0, 1) per (row, position) — the acceptance test's coin."""
    def one(row_key, pos):
        k = jax.random.fold_in(row_key, pos)
        return jax.random.uniform(jax.random.fold_in(k, salt))

    return jax.vmap(one)(key_rows, positions)


def sample_token(logits, temps, top_ks, top_ps, key_rows, positions):
    """Per-row next token from `[N, V]` logits: greedy rows
    (`temperature <= 0`) take the literal `jnp.argmax` — bit-identical
    to the pre-sampling engine — and sampled rows a categorical draw
    from the masked distribution, keyed by the row's (seed, position).
    `key_rows` `[N, 2]` uint32, `positions` `[N]` int32 (the absolute
    position whose logits these are — the emitted token lands at
    position + 1). Returns `[N]` int32."""
    am = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = masked_logits(logits, temps, top_ks, top_ps)
    drawn = _draw_categorical(lg, key_rows, positions,
                              SALT_SAMPLE).astype(jnp.int32)
    return jnp.where(temps <= 0.0, am, drawn)


def verify_window(logits, tokens, draft_lens, temps, top_ks, top_ps,
                  key_rows, positions):
    """Rejection-sampling acceptance over one speculative verify
    window — all K+1 logit positions are already in hand, so per-slot
    accept/resample happens on-device in the same compiled program.

    `logits` `[B, W, V]` (window row j's distribution p_j governs the
    token AFTER row j), `tokens` `[B, W]` the window's input tokens
    (feed token at row 0, drafts after it), `draft_lens` `[B]`,
    per-slot `temps`/`top_ks`/`top_ps` `[B]`, `key_rows` `[B, 2]`
    uint32, `positions` `[B]` row-0 absolute positions. Returns

    - `accepts` `[B, W]` bool: row j tests the DRAFT in window row
      j+1 against p_j — sampled lanes the Leviathan coin
      `u < p_j(d)` (deterministic drafter: q is a point mass), greedy
      lanes exact argmax equality; False past the draft length.
    - `choices` `[B, W]` int32: the token to emit when the host's
      acceptance walk STOPS at row j — the residual resample
      `norm(max(p_j - q_j, 0))` (p_j with the rejected draft's mass
      zeroed) while a draft exists at row j+1, the plain bonus draw
      from p_j at j == draft_len; greedy lanes pin argmax.

    Host contract (`GenerationEngine._spec_decode_step`): accept the
    longest prefix `n` with `accepts[:, :n]` all true, emit
    `drafts[:n] + [choices[n]]` — for greedy lanes that reproduces the
    exact-acceptance stream bit-for-bit, for sampled lanes it provably
    preserves the target distribution (see the module docstring)."""
    B, W, V = logits.shape
    am = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # [B, W]
    rep = lambda a: jnp.repeat(a, W)       # [B] params -> [B*W] rows
    lg = masked_logits(logits.reshape(B * W, V), rep(temps),
                       rep(top_ks), rep(top_ps)).reshape(B, W, V)
    probs = jax.nn.softmax(lg, axis=-1)                    # fp32
    # the draft row j tests is window row j+1 (none at the last row)
    d_next = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)],
        axis=1).astype(jnp.int32)
    has_draft = jnp.arange(W)[None, :] < draft_lens[:, None]
    p_d = jnp.take_along_axis(probs, d_next[..., None],
                              axis=-1)[..., 0]             # [B, W]
    wpos = positions[:, None] + jnp.arange(W)[None, :]     # [B, W]
    keys_flat = jnp.repeat(key_rows, W, axis=0)            # [B*W, 2]
    u = _draw_uniform(keys_flat, wpos.reshape(-1),
                      SALT_ACCEPT).reshape(B, W)
    greedy = (temps <= 0.0)[:, None]
    accepts = jnp.where(greedy, d_next == am, u < p_d) & has_draft
    # the stop-row choice: zero the rejected draft's mass while a
    # draft exists (the softmax inside categorical renormalizes —
    # exactly norm(max(p - q, 0)) for a point-mass q); the j == dlen
    # row keeps p whole, which is the bonus draw — and the SAME
    # (position, salt) stream a K=0 decode step would consume, so
    # all-accepted sampled chains match the draftless stream's draws
    excl = has_draft[..., None] \
        & (jnp.arange(V)[None, None, :] == d_next[..., None])
    fb_lg = jnp.where(excl, -jnp.inf, lg)
    drawn = _draw_categorical(fb_lg.reshape(B * W, V), keys_flat,
                              wpos.reshape(-1),
                              SALT_SAMPLE).astype(jnp.int32)
    choices = jnp.where(greedy, am, drawn.reshape(B, W))
    return choices, accepts
