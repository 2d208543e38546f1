import jax
from jax import shard_map


def body(x):
    y = jax.lax.psum(x, "mp")
    return jax.lax.all_gather(y, "mp", axis=0, tiled=True)


step = shard_map(body, mesh=None, in_specs=None, out_specs=None)
