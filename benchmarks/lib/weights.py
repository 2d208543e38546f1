"""Weights from `--seed`, made on the device, in the type they are run in.

One generator serves both sides and neither side takes the other's
arrays: the harness calls `make_all` and binds the result to the
program's parameters; the reference calls `make_leaves` for one segment
at a time. A leaf is `(name, shape, init)` as a reference module's
`param_spec` lists it; leaf `i` of the list always draws from
`fold_in(key(seed), i)`. Every leaf is made by the one compiled program
of its (shape, init, dtype), whoever asks for it and in whatever company
— two different programs may round the last bit differently, and a
parameter that differs by one bf16 step between the program and its
reference is a fault the comparison would then report.

`init` is `["normal", std]`, `["ones_normal", std]` (1 + N(0, std), the
norm gains) — biases and gains are random too, so that no leaf's
gradient is idle by construction.
"""
from __future__ import annotations

import functools

import numpy as np


def seed_key(seed):
    """A JAX key from any whole number (the driver's seeds pass 2**31)."""
    import jax

    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32),
                                    impl="threefry2x32")


def _leaf(key, index, shape, init, dtype):
    import jax
    import jax.numpy as jnp

    kind, std = init
    x = jax.random.normal(jax.random.fold_in(key, index), shape,
                          jnp.float32) * std
    if kind == "ones_normal":
        x = x + 1.0
    elif kind != "normal":
        raise ValueError(f"unknown init {kind!r}")
    return x.astype(dtype)


@functools.lru_cache(maxsize=64)
def _jitted(shape, init, dtype_name):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    return jax.jit(lambda key, index: _leaf(key, index, shape, init,
                                            dtype))


def make_leaves(seed, spec, indices, dtype):
    """`[array]` for `spec[i] for i in indices`."""
    import jax.numpy as jnp

    key, name = seed_key(seed), jnp.dtype(dtype).name
    return [_jitted(tuple(spec[i][1]), tuple(spec[i][2]), name)(
        key, jnp.uint32(i)) for i in indices]


def make_all(seed, spec, dtype):
    """Every leaf: `{name: array}`."""
    arrays = make_leaves(seed, spec, range(len(spec)), dtype)
    return {s[0]: a for s, a in zip(spec, arrays)}
