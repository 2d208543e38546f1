"""Power retention (Manifest AI, arXiv:2507.04239): sequence mixing whose
score of a key is the SECOND POWER of the scaled dot product, damped by a
learned gate and normalised by the sum of the scores. A KV head, its
query heads i, token t (`g` the gate, 0 < g < 1):

    a_tj = (q_t,i . k_j / sqrt(d))^2 * prod_{l=j+1..t} g_l          j <= t
    y_t,i = sum_j a_tj v_j / (sum_j a_tj + eps)

Because `(q . k)^2 = phi(q) . phi(k)` for the symmetric second-power map
`phi`, the same numbers come from a state of fixed size and no cache:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T      z_t = g_t z_{t-1} + phi(k_t)
    y_t,i = phi(q_t,i)^T S_t / (phi(q_t,i)^T z_t + eps)

`phi` is kept in the TILED symmetric form the decode kernel reads: the d
values are cut into tiles of 8, a pair of tiles `a <= b` holds the whole
8 x 8 outer product `x_a (x) x_b` (times sqrt 2 where `a < b`, which
stands for both orders), pairs in the order `(0,0), (0,1) .. (0,n-1),
(1,1) ..`: `state_width(d)` = n (n + 1) / 2 x 64 values (8,704 at d =
128, of which 8,256 are distinct). A vreg of the state is then one `x_a[i]`
against the eight `x_b[j]`, and the kernel builds `phi` from the d values
alone.

Two forms from a CARRIED state, as `ops/ssm.py` has for the scan:

* `power_retention_chunk` — a prefill chunk of one slot: inside a
  sub-chunk the gated, squared, lower-triangular `Q K^T`, between
  sub-chunks the state, float32 at `highest`. On the chip one Pallas
  kernel (`ops/pallas/retention.py` `retention_chunk`: `phi` built in
  VMEM, the state held there across the sub-chunks); elsewhere, and as
  the kernel's reference, a `lax.scan` of XLA over the sub-chunks.
* `power_retention_decode` — one token for every slot over the pools
  `[layers, rows, kv_heads, D_run, d]` and `[layers, rows, kv_heads,
  D_run]`: the state's part is a Pallas kernel on the chip
  (`ops/pallas/retention.py`), gather / scatter in XLA elsewhere; the
  normaliser (1/128 of the bytes) is XLA in both.

`RETENTION_PATH_STATS` counts which decode form was traced,
`RETENTION_CHUNK_STATS` which chunk form: never a silent fallback.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.device import on_tpu, pallas_interpret

RETENTION_BACKENDS = ("auto", "xla", "pallas")
RETENTION_PATH_STATS = {"xla": 0, "pallas": 0}
RETENTION_CHUNK_STATS = {"xla": 0, "pallas": 0}
TILE = 8
EPS = 1e-6
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def reset_retention_path_stats():
    for stats in (RETENTION_PATH_STATS, RETENTION_CHUNK_STATS):
        for k in stats:
            stats[k] = 0


def resolve_retention_backend(backend, head_dim=128):
    """`auto` takes the kernel on a TPU where a head fills whole 128-lane
    tiles; an explicit choice always wins (off the chip `pallas` runs the
    interpreter)."""
    if backend not in RETENTION_BACKENDS:
        raise ValueError(f"backend must be one of {RETENTION_BACKENDS}, "
                         f"got {backend!r}")
    if backend != "auto":
        return backend
    return "pallas" if on_tpu() and head_dim % 128 == 0 else "xla"


def state_width(head_dim):
    """`D_run`: values of `phi` in the tiled symmetric form."""
    if head_dim % TILE:
        raise ValueError(f"head_dim must be a multiple of {TILE}, got "
                         f"{head_dim}")
    n = head_dim // TILE
    return n * (n + 1) // 2 * TILE * TILE


def _pair_weights(n, a):
    """Weights of the pairs `(a, a) .. (a, n - 1)`: 1, then sqrt 2."""
    w = np.full(n - a, np.sqrt(2.0), np.float32)
    w[0] = 1.0
    return w


def phi(x, gathered=False):
    """x `[.., d]` -> float32 `[.., state_width(d)]`, so that
    `phi(q) . phi(k) == (q . k)^2`. Two ways to the same values, and the
    caller says which, because the chip's compiler runs each well at one
    size only (my chip runs, PR 35): static slices, one row of pairs a
    tile `a` (a chunk's thousands of rows: 0.14 ms where the gather
    takes 0.5), or `gathered`, the tiles picked by the pairs' index
    arrays (a decode step's few rows: the slices' concatenate takes 0.5
    ms a layer there, a fifth of the whole step)."""
    n = x.shape[-1] // TILE
    tiles = x.astype(_F32).reshape(x.shape[:-1] + (n, TILE))
    if gathered:
        a, b = np.triu_indices(n)               # row-major: a <= b
        w = np.where(a == b, 1.0, np.sqrt(2.0)).astype(np.float32)
        pairs = w[:, None, None] * tiles[..., a, :, None] \
            * tiles[..., b, None, :]
    else:
        pairs = jnp.concatenate(
            [_pair_weights(n, a)[:, None, None]
             * tiles[..., a, None, :, None] * tiles[..., a:, None, :]
             for a in range(n)], axis=-3)
    return pairs.reshape(x.shape[:-1] + (-1,))


def _phi_dot(x, z):
    """`phi(x) . z` for x `[.., h, r, d]` against z `[h, D]`, without
    reading `phi(x)` (a chunk's is 178 MB): a pair's 8 x 8 block of z
    between the two tiles of x."""
    n = x.shape[-1] // TILE
    blocks = z.reshape(z.shape[:-1] + (-1, TILE, TILE))   # [.., h, p, 8, 8]
    tiles = x.astype(_F32).reshape(x.shape[:-1] + (n, TILE))
    out = 0.0
    first = 0
    for a in range(n):
        zb = blocks[..., first:first + n - a, :, :] \
            * _pair_weights(n, a)[:, None, None]
        first += n - a
        # left[.., r, p, j] = sum_i x_a[i] z[p, i, j]
        left = jnp.einsum("...ri,...pij->...rpj", tiles[..., a, :], zb,
                          precision=_HIGHEST)
        out = out + jnp.sum(left * tiles[..., a:, :], axis=(-2, -1))
    return out


def _sub_chunk(state, norm, q, k, v, lg, valid):
    """One sub-chunk. state `[h, D, d]`, norm `[h, D]`; q `[C, h, r, d]`
    scaled; k, v `[C, h, d]`; lg `[C, h]` (0 on padding rows); valid
    `[C]`. All float32. -> (y `[C, h, r, d]`, state, norm)."""
    c = q.shape[0]
    la = jnp.cumsum(lg, axis=0)                           # [C, h] <= 0
    scores = jnp.einsum("thid,shd->hits", q, k, precision=_HIGHEST)
    causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    gap = jnp.transpose(la[:, None] - la[None, :], (2, 0, 1))  # [h, t, s]
    decay = jnp.exp(jnp.where(causal, gap, -jnp.inf)) * valid
    a = jnp.square(scores) * decay[:, None]
    num = jnp.einsum("hits,shv->thiv", a, v, precision=_HIGHEST)
    den = jnp.transpose(jnp.sum(a, axis=-1), (2, 0, 1))   # [t, h, i]
    pq = phi(q)                                           # [C, h, r, D]
    carried = jnp.exp(la)                                 # [C, h]
    num = num + carried[..., None, None] * jnp.einsum(
        "thim,hmv->thiv", pq, state, precision=_HIGHEST)
    den = den + carried[..., None] * _phi_dot(q, norm)
    tail = jnp.exp(la[-1][None] - la) * valid[:, None]    # [C, h]
    pk = phi(k) * tail[..., None]                         # [C, h, D]
    last = jnp.exp(la[-1])
    state = last[:, None, None] * state + jnp.einsum(
        "shm,shv->hmv", pk, v, precision=_HIGHEST)
    norm = last[:, None] * norm + jnp.sum(pk, axis=0)
    return num / (den[..., None] + EPS), state, norm


def _pair_grid(n):
    """`[n, n]`: the pair `(a, b)`'s index in the tiled order where `a <=
    b`, else the index one past the last pair (a row of zeros)."""
    grid = np.full((n, n), n * (n + 1) // 2, np.int32)
    a, b = np.triu_indices(n)
    grid[a, b] = np.arange(a.size)
    return grid


def _norm_to_matrix(norm, d):
    """norm `[h, D_run]` (tiled `phi` form) -> `[h, d, d]`, value `(a, b,
    i, j)` at `(8a + i, 8b + j)` and 0 below the diagonal tiles: the
    matrix the kernel reads `phi(q) . z` from. Whole pairs of 64 values
    are gathered, never single values: a gather of single values would
    have the whole norm pool laid out anew round the program (PR 36's
    first trace)."""
    h, n = norm.shape[0], d // TILE
    pairs = jnp.pad(norm.reshape(h, -1, TILE * TILE), ((0, 0), (0, 1),
                                                       (0, 0)))
    full = jnp.take(pairs, _pair_grid(n).reshape(-1), axis=1)
    return jnp.transpose(full.reshape(h, n, n, TILE, TILE),
                         (0, 1, 3, 2, 4)).reshape(h, n * TILE, -1)


def _matrix_to_norm(zmat):
    """`_norm_to_matrix`'s inverse."""
    h, n = zmat.shape[0], zmat.shape[1] // TILE
    full = jnp.transpose(zmat.reshape(h, n, TILE, n, TILE),
                         (0, 1, 3, 2, 4)).reshape(h, n * n, -1)
    a, b = np.triu_indices(n)
    return jnp.take(full, a * n + b, axis=1).reshape(h, -1)


def _chunk_pallas(q, k, v, lg, valid, state, norm, chunk_size):
    """The kernel's form: q `[T, h, r, d]` scaled, k, v `[T, h, d]`, lg
    `[T, h]` (0 on padding rows), valid `[T]`, T a whole number of
    sub-chunks; the terms that only the gate makes are made here."""
    from .pallas.retention import retention_chunk

    t, h, r, d = q.shape
    c = chunk_size
    la = jnp.cumsum(lg.T.reshape(h, -1, c), axis=-1)     # [h, n, C]
    causal = np.arange(c)[:, None] >= np.arange(c)[None, :]
    gap = la[..., :, None] - la[..., None, :]            # [h, n, t, s]
    dec = jnp.exp(jnp.where(causal, gap, -jnp.inf)) \
        * valid.reshape(-1, 1, c)
    tail = jnp.exp(la[..., -1:] - la) * valid.reshape(-1, c)
    y, state, zmat = retention_chunk(
        jnp.transpose(q, (1, 2, 0, 3)), jnp.transpose(q, (1, 2, 3, 0)),
        jnp.transpose(k, (1, 0, 2)), jnp.transpose(k, (1, 2, 0)),
        jnp.transpose(v, (1, 0, 2)), dec.reshape(h, t, c),
        jnp.broadcast_to(jnp.exp(la).reshape(h, t, 1), (h, t, d)),
        tail.reshape(h, t, 1), state, _norm_to_matrix(norm, d), EPS,
        interpret=pallas_interpret())
    return jnp.transpose(y, (2, 0, 1, 3)), state, _matrix_to_norm(zmat)


def power_retention_chunk(q, k, v, log_g, state, norm, n_valid,
                          chunk_size=128, backend="auto"):
    """A chunk of ONE slot's prompt from the carried `state` and `norm`.
    q `[T, kv_heads, r, d]` (r query heads a KV head, not yet scaled);
    k, v `[T, kv_heads, d]`; log_g `[T, kv_heads]` float32; state
    `[kv_heads, D_run, d]` and norm `[kv_heads, D_run]` float32; rows at
    and past `n_valid` (the prompt's padding) leave both as they are;
    `backend` as `resolve_retention_backend` reads it. -> (y `[T,
    kv_heads, r, d]` float32, state, norm after the last real row)."""
    t, d = q.shape[0], q.shape[-1]
    pad = -t % chunk_size
    valid = jnp.arange(t + pad) < n_valid

    def padded(x, scale=None):
        """`[T, ..]` -> float32 `[T + pad, ..]`: whole sub-chunks."""
        x = x.astype(_F32) if scale is None else x.astype(_F32) * scale
        return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))

    q, k, v = padded(q, d ** -0.5), padded(k), padded(v)
    lg = padded(jnp.where(valid[:t, None], log_g.astype(_F32), 0.0))
    resolved = resolve_retention_backend(backend, d)
    RETENTION_CHUNK_STATS[resolved] += 1
    if resolved == "pallas":
        y, st, nm = _chunk_pallas(q, k, v, lg, valid, state.astype(_F32),
                                  norm.astype(_F32), chunk_size)
    else:
        def rows(x):
            return x.reshape((-1, chunk_size) + x.shape[1:])

        def step(carry, sub):
            y, st, nm = _sub_chunk(*carry, *sub)
            return (st, nm), y

        # the state is handed from one sub-chunk to the next
        (st, nm), y = jax.lax.scan(
            step, (state.astype(_F32), norm.astype(_F32)),
            (rows(q), rows(k), rows(v), rows(lg), rows(valid)))
        y = y.reshape((-1,) + y.shape[2:])
    return y[:t], st.astype(state.dtype), nm.astype(norm.dtype)


def _state_step_xla(pool, layer, rows, q, pk, v, g):
    state = g[..., None, None] * pool[layer, rows] \
        + pk[..., None] * v[..., None, :]
    num = jnp.einsum("shim,shmv->shiv", phi(q, gathered=True), state,
                     precision=_HIGHEST)
    return num, pool.at[layer, rows].set(state.astype(pool.dtype))


def power_retention_decode(pool, norm_pool, layer, rows, q, k, v, log_g,
                           backend="auto"):
    """One token for every slot. pool `[layers, rows, kv_heads, D_run,
    d]` and norm_pool `[layers, rows, kv_heads, D_run]` float32; `rows`
    `[slots]` int32, the slot's row of the pools (0, the null row, for
    lanes that do not decode this step: it takes their garbage); q
    `[slots, kv_heads, r, d]` (not yet scaled); k, v `[slots, kv_heads,
    d]`; log_g `[slots, kv_heads]` float32. -> (y `[slots, kv_heads, r,
    d]` float32, the pools with `layer` updated)."""
    d = q.shape[-1]
    resolved = resolve_retention_backend(backend, d)
    RETENTION_PATH_STATS[resolved] += 1
    qf = q.astype(_F32) * (d ** -0.5)
    kf, vf = k.astype(_F32), v.astype(_F32)
    g = jnp.exp(log_g.astype(_F32))
    pk = phi(kf, gathered=True)                           # [s, h, D]
    norm = g[..., None] * norm_pool[layer, rows] + pk
    # `phi(q)` of 20 lanes is small and the product reads z as it lies:
    # the tile-pair form would relay z out first (0.7 ms a layer on the
    # chip, PR 35's second trace)
    den = jnp.einsum("shim,shm->shi", phi(qf, gathered=True), norm,
                     precision=_HIGHEST)
    norm_pool = norm_pool.at[layer, rows].set(
        norm.astype(norm_pool.dtype))
    if resolved == "pallas":
        from .pallas.retention import retention_decode_update

        num, pool = retention_decode_update(
            pool, layer, rows, qf, kf, vf, g,
            interpret=pallas_interpret())
    else:
        num, pool = _state_step_xla(pool, layer, rows, qf, pk, vf, g)
    return num / (den[..., None] + EPS), pool, norm_pool
