"""Norms of leaves, by parts. A fused leaf (`qkv_proj`: columns
[q | k | v]) is measured a part at a time, because its parts differ in
kind: the key's bias has no gradient at all under softmax, so under Adam
it moves by round-off alone, and a third of noise inside one leaf's norm
reads as a gap of a tenth and more (PR 25: 0.17 on every seed in
GPT-1.3B, 0.2-0.4 in BERT-base). The rule that leaves idle leaves out of
the comparison then works on the part, from the reference's gradient.

Always a program of its own over STORED arrays: inside an update's
program the compiler may keep unrounded float32 (excess precision) and
report a move the stored bf16 value never made.
"""
from __future__ import annotations

import functools


@functools.lru_cache(maxsize=8)
def _jitted(parts):
    import jax
    import jax.numpy as jnp

    def norms(a, b):
        d = a.astype(jnp.float32)
        if b is not None:
            d = d - b.astype(jnp.float32)
        d = d.reshape(d.shape[:-1] + (parts, d.shape[-1] // parts))
        axes = tuple(i for i in range(d.ndim) if i != d.ndim - 2)
        return jnp.sqrt(jnp.sum(jnp.square(d), axis=axes))

    return jax.jit(norms)


def part_norms(a, b=None, parts=1):
    """`[parts]` norms of `a` (or of `a - b`), the last axis cut into
    `parts` equal runs of columns."""
    return _jitted(int(parts))(a, b)


@functools.lru_cache(maxsize=1)
def _jitted_cols():
    import jax
    import jax.numpy as jnp

    def cols(a):
        d = jnp.square(a.astype(jnp.float32))
        return jnp.sqrt(jnp.sum(d, axis=tuple(range(d.ndim - 1))))

    return jax.jit(cols)


def column_norms(a):
    """The norm of every column (last axis kept), on the host. Unlike a
    whole leaf's norm, in which random rounding averages away, a
    column's norm keeps what the precision of the arithmetic does to it:
    the number that tells bf16 from fp8 where the leaves' norms do not
    (PR 25, BERT-base)."""
    import numpy as np

    return np.asarray(_jitted_cols()(a))


def named(name, values):
    """`{name or name#j: float}` from a `[parts]` array."""
    vals = [float(v) for v in values]
    if len(vals) == 1:
        return {name: vals[0]}
    return {f"{name}#{j}": v for j, v in enumerate(vals)}


def parts_of(spec):
    """`{leaf name: parts}` from a reference's `param_spec`."""
    return {s[0]: (s[3] if len(s) > 3 else 1) for s in spec}
