"""The grouped expert matmul (`ops/pallas/moe.py`) under the interpreter,
in both of its weight blockings, against the plain form
(`distributed/moe._grouped_xla`): an expert whose whole `[K, N]` fits
one block, which its row tiles share, and a larger one cut into column
blocks. Compiles for the chip are `tests/test_chip_compile.py`'s."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed import moe
from paddle_tpu.ops.pallas import moe as kernel

TILE = 8
BF16 = jnp.bfloat16
# tiles an expert holds, experts 0-3: none, one, two, five; then dead
# tiles, which point at the last live tile's expert
TILES_HELD = (0, 1, 2, 5)
DEAD = 3


def _tile_plan():
    te = [e for e, n in enumerate(TILES_HELD) for _ in range(n)]
    live = len(te)
    te += [te[-1]] * DEAD
    return jnp.asarray(te, jnp.int32), jnp.asarray([live], jnp.int32)


def _operands(k, n, seed=0):
    tile_expert, live = _tile_plan()
    m = len(tile_expert) * TILE
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (m, k)).astype(BF16)
    w = (jax.random.normal(kw, (len(TILES_HELD), k, n)) / np.sqrt(k)) \
        .astype(BF16)
    return x, w, tile_expert, live


@pytest.fixture(params=["whole", "columns"])
def blocking(request, monkeypatch):
    """`whole`: the budgets as they are, which every test size fits;
    `columns`: no expert fits whole, and a column block holds 128."""
    if request.param == "columns":
        monkeypatch.setattr(kernel, "_EXPERT_BLOCK_BYTES", 0)
        monkeypatch.setattr(kernel, "_WEIGHT_BLOCK_BYTES", 128 * 128 * 2)
    return request.param


# the two products of a `relu2` expert (d -> h -> d) and of a `swiglu`
# one (d -> [gate | up] 2h, then h -> d), at test widths
SHAPES = {"relu2_up": (128, 256), "relu2_down": (256, 128),
          "swiglu_gate_up": (128, 512), "swiglu_down": (256, 128)}


@pytest.mark.parametrize("square", [True, False], ids=["relu2", "plain"])
@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
def test_each_row_tile_takes_its_experts_weights(blocking, shape, square):
    k, n = SHAPES[shape]
    tn = kernel.column_tile(k, n, 2)
    assert tn == (n if blocking == "whole" else 128)
    x, w, tile_expert, live = _operands(k, n)
    got = kernel.moe_grouped_matmul(x, w, tile_expert, live, TILE,
                                    relu_squared=square, interpret=True)
    want = moe._grouped_xla(x, w, tile_expert, live, TILE, square)
    assert got.dtype == BF16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2, atol=1e-2)
    live_rows = int(live[0]) * TILE
    assert not np.asarray(got, np.float32)[live_rows:].any()  # dead tiles
    assert np.abs(np.asarray(got, np.float32)[:live_rows]).max() > 0.1


def test_the_two_blockings_give_the_same_products(monkeypatch):
    """A column block sums over the whole K as the whole block does: the
    blocking moves bytes, not the arithmetic."""
    x, w, tile_expert, live = _operands(256, 512, seed=3)

    def product():
        return np.asarray(kernel.moe_grouped_matmul(
            x, w, tile_expert, live, TILE, relu_squared=True,
            interpret=True), np.float32)

    whole = product()
    monkeypatch.setattr(kernel, "_EXPERT_BLOCK_BYTES", 0)
    monkeypatch.setattr(kernel, "_WEIGHT_BLOCK_BYTES", 256 * 128 * 2)
    np.testing.assert_array_equal(product(), whole)


@pytest.mark.parametrize("k,n,tn", [
    (1024, 2688, 2688), (2688, 1024, 1024),     # nemotron: a whole expert
    (7680, 4096, 128), (2048, 7680, 768),       # pangu: column blocks
    (64, 200, 200),                             # not whole lanes: whole
], ids=["nemotron_up", "nemotron_down", "pangu_gate_up", "pangu_down",
        "narrow"])
def test_the_weight_block_follows_what_fits(k, n, tn):
    assert kernel.column_tile(k, n, 2) == tn
    assert n % tn == 0


def test_the_layers_counts_fold_by_their_kind():
    per_layer = jnp.asarray([[5, 2, 4, 3], [7, 3, 2, 4]], jnp.int32)
    assert moe.fold_expert_counters(per_layer).tolist() == [12, 5, 4, 7]
