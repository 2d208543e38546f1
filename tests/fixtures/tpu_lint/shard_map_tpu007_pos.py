import jax  # noqa: F401
from jax import shard_map

import paddle_tpu.distributed as dist


def body(x):
    dist.all_reduce(x)
    return x


step = shard_map(body, mesh=None, in_specs=None, out_specs=None)
