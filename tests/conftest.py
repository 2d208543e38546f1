"""Test harness: run everything on CPU with 8 virtual XLA devices so the
multi-chip sharding paths compile and execute without TPU hardware —
SURVEY §4 "multi-node testing without a cluster" TPU equivalent.
Must run before jax initializes a backend.
"""
import os

# Both variables are read when jax is first imported / first builds a
# backend, and nothing imports jax before this file.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# numerical-parity tests need exact fp32 matmuls; production keeps the
# fast MXU default (bf16 passes) — this only affects the test process.
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: exhaustive sweeps excluded from the timed tier-1 gate "
        "(ROADMAP runs with -m 'not slow')")


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu

    paddle_tpu.seed(42)
    np.random.seed(42)
    yield


@pytest.fixture
def fused_step_offered(monkeypatch):
    """The hybrid and the latent decoders' specs offer
    `decode_with_chunk` where the experts' product is the chip's kernel,
    which this CPU never takes: a test of the fused order asks for the
    offer itself. The step then runs the forms this platform resolves."""
    from paddle_tpu.models.nemotron_h import NemotronHServing
    from paddle_tpu.models.pangu_ultra_moe import PanguUltraMoEServing

    for spec in (NemotronHServing, PanguUltraMoEServing):
        monkeypatch.setattr(spec, "offers_decode_with_chunk", True)


@pytest.fixture
def expert_loads(monkeypatch):
    """Every expert layer's load as its product runs, jitted or not:
    a list of (rows of the call, each held expert's assignments), filled
    from the device. The products are cut into tiles of 4 rows (the
    default 16 holds every load of a tiny engine in one tile, where a
    count of tiles could not tell loads from experts)."""
    import functools

    from paddle_tpu.distributed import moe

    seen = []
    dispatch, share = moe.sorted_dispatch, moe.expert_share

    def recorded(ids, first_expert, num_held, tile_rows=moe.TILE_ROWS):
        plan = dispatch(ids, first_expert, num_held, tile_rows)
        rows = ids.shape[0]
        jax.debug.callback(lambda s: seen.append((rows, np.asarray(s))),
                           plan["group_sizes"])
        return plan

    monkeypatch.setattr(moe, "sorted_dispatch", recorded)
    monkeypatch.setattr(moe, "expert_share",
                        functools.partial(share, tile_rows=4))
    return seen
