"""The retention's prefill-chunk kernel (`ops/pallas/retention.py`
`retention_chunk`, under the interpreter) against the XLA form it
replaces on the chip (`ops/retention.power_retention_chunk` with
`backend="xla"`): the output, the state and the normaliser within 1e-5 of
each one's scale, from a carried state, over one and several sub-chunks,
with padding rows, with gates near 1 and near 0; the same kernel with
`phi` or the state rounded to bfloat16 inside it falls outside that
tolerance — the cell's `correct` cannot see a bfloat16 state, so this is
its judge. Then the path statistics.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import retention
from paddle_tpu.ops.pallas import retention as kernel

# float32 at `highest` on both sides, the same sums in another order:
# within 1e-6 of the scale; bfloat16 anywhere moves them by 1e-3
TOLERANCE = 1e-5
H, R, D, C = 2, 3, 32, 8          # 10 tile pairs: a whole step of 8 and 2


def _chunk(rng, t, gate):
    """q, k, v, log_g of a chunk of t rows; `gate` sets where the gates
    lie: near 1 (forgets nothing), near 0 (forgets at once), or spread."""
    f = lambda *shape: np.asarray(rng.normal(size=shape), np.float32)
    unit = lambda x: x / np.sqrt((x * x).mean(-1, keepdims=True))
    log_g = {"near_one": -1e-3 * np.abs(f(t, H)),
             "near_zero": -20.0 - np.abs(f(t, H)),
             "spread": -np.log1p(np.exp(-2.0 * f(t, H) - 1.0))}[gate]
    return tuple(jnp.asarray(x) for x in (
        unit(f(t, H, R, D)), unit(f(t, H, D)), f(t, H, D),
        log_g.astype(np.float32)))


def _carried(rng):
    """A state and normaliser as a prompt leaves them: 24 rows through
    the XLA form from nothing."""
    width = retention.state_width(D)
    _, state, norm = retention.power_retention_chunk(
        *_chunk(rng, 24, "spread"), jnp.zeros((H, width, D)),
        jnp.zeros((H, width)), jnp.int32(24), C, "xla")
    return state, norm


def _gaps(want, got, n_valid):
    """Each output's largest difference over its own scale."""
    return [float(np.abs(np.asarray(w) - np.asarray(g))[:n].max()
                  / np.abs(np.asarray(w))[:n].max())
            for w, g, n in zip(want, got, (n_valid, None, None))]


CASES = {
    "one_sub_chunk": (8, 8, "spread", True),
    "several_sub_chunks": (24, 24, "spread", True),
    "from_nothing": (24, 24, "spread", False),
    "padding_rows": (24, 13, "spread", True),
    "gates_near_one": (16, 16, "near_one", True),
    "gates_near_zero": (16, 16, "near_zero", True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_xla_form(case):
    t, n_valid, gate, carried = CASES[case]
    rng = np.random.default_rng(len(case))
    width = retention.state_width(D)
    state, norm = _carried(rng) if carried else (
        jnp.zeros((H, width, D)), jnp.zeros((H, width)))
    args = _chunk(rng, t, gate) + (state, norm, jnp.int32(n_valid), C)
    want = retention.power_retention_chunk(*args, "xla")
    got = retention.power_retention_chunk(*args, "pallas")
    assert [np.shape(g) for g in got] == [np.shape(w) for w in want]
    assert max(_gaps(want, got, n_valid)) < TOLERANCE
    if n_valid < t:
        # the padding rows leave the state as the real rows left it
        q, k, v, log_g = args[:4]
        short = retention.power_retention_chunk(
            q[:n_valid], k[:n_valid], v[:n_valid], log_g[:n_valid],
            state, norm, jnp.int32(n_valid), C, "pallas")
        assert max(_gaps(short[1:], got[1:], None)) < TOLERANCE


def _low(x):
    return x.astype(jnp.bfloat16).astype(x.dtype)


@pytest.mark.parametrize("planted", ["phi_bf16", "state_bf16"])
def test_a_kernel_that_rounds_to_bf16_falls_outside_the_tolerance(
        planted, monkeypatch):
    """`phi` of q and k rounded to bfloat16 as the kernel builds it, or
    the carried state rounded as the kernel reads it: the comparison
    above refuses either, by a wide margin."""
    if planted == "phi_bf16":
        build = kernel._phi_rows

        def rounded(*args):
            build(*args)
            out_ref = args[5]
            out_ref[...] = _low(out_ref[...])

        monkeypatch.setattr(kernel, "_phi_rows", rounded)
    else:
        body = kernel._chunk_kernel

        def rounded(*refs, **kw):
            refs[10][...] = _low(refs[10][...])          # s_ref
            return body(*refs, **kw)

        monkeypatch.setattr(kernel, "_chunk_kernel", rounded)
    rng = np.random.default_rng(11)
    args = _chunk(rng, 24, "near_one") + _carried(rng) + (jnp.int32(24), C)
    want = retention.power_retention_chunk(*args, "xla")
    got = retention.power_retention_chunk(*args, "pallas")
    assert max(_gaps(want, got, 24)) > 10 * TOLERANCE


def test_the_chunk_counts_its_form_apart_from_the_decode_step():
    """`RETENTION_CHUNK_STATS` says which chunk form was traced; `auto`
    is the XLA form off the chip; the decode step's own statistics are
    left alone (the benchmark reads those)."""
    rng = np.random.default_rng(5)
    args = _chunk(rng, 8, "spread") + _carried(rng) + (jnp.int32(8), C)
    retention.reset_retention_path_stats()
    retention.power_retention_chunk(*args, "pallas")
    assert retention.RETENTION_CHUNK_STATS == {"xla": 0, "pallas": 1}
    retention.power_retention_chunk(*args, "auto")
    retention.power_retention_chunk(*args)
    assert retention.RETENTION_CHUNK_STATS == {"xla": 2, "pallas": 1}
    assert retention.RETENTION_PATH_STATS == {"xla": 0, "pallas": 0}
    retention.reset_retention_path_stats()
    assert retention.RETENTION_CHUNK_STATS == {"xla": 0, "pallas": 0}
    with pytest.raises(ValueError, match="backend must be one of"):
        retention.power_retention_chunk(*args, "mosaic")


def test_the_normaliser_matrix_holds_each_value_once():
    """The kernel reads `phi(q) . z` as `q^T Z q`: the tiled normaliser
    laid out as a `[d, d]` matrix and back, bit for bit, with nothing
    below the diagonal tiles."""
    rng = np.random.default_rng(2)
    norm = jnp.asarray(rng.normal(size=(H, retention.state_width(D))),
                       jnp.float32)
    zmat = retention._norm_to_matrix(norm, D)
    assert zmat.shape == (H, D, D)
    tiles = np.arange(D) // 8
    assert not np.asarray(zmat)[:, tiles[:, None] > tiles[None, :]].any()
    assert np.array_equal(np.asarray(retention._matrix_to_norm(zmat)),
                          np.asarray(norm))
    q = rng.normal(size=(5, D)).astype(np.float32)
    w = np.asarray(kernel._pair_weights_matrix(D))
    want = np.asarray(retention.phi(q)) @ np.asarray(norm[0])
    got = np.einsum("ri,ij,rj->r", q, w * np.asarray(zmat[0]), q)
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
