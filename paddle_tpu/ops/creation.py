"""Tensor creation ops — analog of python/paddle/tensor/creation.py."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import dtype as dtypes
from paddle_tpu.core.tensor import Tensor

__all__ = [
    "to_tensor",
    "zeros",
    "ones",
    "full",
    "zeros_like",
    "ones_like",
    "full_like",
    "empty",
    "empty_like",
    "arange",
    "linspace",
    "eye",
    "diag",
    "tril",
    "triu",
    "meshgrid",
    "one_hot",
    "logspace",
    "vander",
    "diagflat",
    "complex",
]


def to_tensor(data, dtype=None, place=None, stop_gradient=True) -> Tensor:
    return Tensor(data, dtype=dtype, stop_gradient=stop_gradient)


def _shape_tuple(shape):
    if isinstance(shape, Tensor):
        shape = shape.tolist()
    if isinstance(shape, (int, np.integer)):
        return (int(shape),)
    return tuple(int(s) for s in shape)


def zeros(shape, dtype=None) -> Tensor:
    return Tensor._wrap(jnp.zeros(_shape_tuple(shape), dtypes.to_jax(dtype)))


def ones(shape, dtype=None) -> Tensor:
    return Tensor._wrap(jnp.ones(_shape_tuple(shape), dtypes.to_jax(dtype)))


def full(shape, fill_value, dtype=None) -> Tensor:
    if dtype is None and isinstance(fill_value, (bool, int, float)):
        dtype = dtypes.infer_dtype(fill_value)
    return Tensor._wrap(jnp.full(_shape_tuple(shape), fill_value, dtypes.to_jax(dtype)))


def zeros_like(x, dtype=None) -> Tensor:
    return Tensor._wrap(jnp.zeros_like(x._array, dtype=dtypes.to_jax(dtype) if dtype else None))


def ones_like(x, dtype=None) -> Tensor:
    return Tensor._wrap(jnp.ones_like(x._array, dtype=dtypes.to_jax(dtype) if dtype else None))


def full_like(x, fill_value, dtype=None) -> Tensor:
    return Tensor._wrap(
        jnp.full_like(x._array, fill_value, dtype=dtypes.to_jax(dtype) if dtype else None)
    )


def empty(shape, dtype=None) -> Tensor:
    # XLA has no uninitialized memory; zeros compiles to a broadcast
    return zeros(shape, dtype)


def empty_like(x, dtype=None) -> Tensor:
    return zeros_like(x, dtype)


def arange(start=0, end=None, step=1, dtype=None) -> Tensor:
    if end is None:
        start, end = 0, start
    if dtype is None:
        if any(isinstance(v, float) for v in (start, end, step)):
            dtype = dtypes.get_default_dtype()
        else:
            dtype = "int64"
    return Tensor._wrap(jnp.arange(start, end, step, dtype=dtypes.to_jax(dtype)))


def linspace(start, stop, num, dtype=None) -> Tensor:
    return Tensor._wrap(jnp.linspace(start, stop, int(num), dtype=dtypes.to_jax(dtype)))


def eye(num_rows, num_columns=None, dtype=None) -> Tensor:
    return Tensor._wrap(jnp.eye(num_rows, num_columns, dtype=dtypes.to_jax(dtype)))


def diag(x, offset=0) -> Tensor:
    return Tensor._wrap(jnp.diag(x._array if isinstance(x, Tensor) else jnp.asarray(x), offset))


def tril(x, diagonal=0) -> Tensor:
    from .dispatch import apply

    return apply("tril", lambda a: jnp.tril(a, diagonal), x)


def triu(x, diagonal=0) -> Tensor:
    from .dispatch import apply

    return apply("triu", lambda a: jnp.triu(a, diagonal), x)


def meshgrid(*xs):
    arrays = [x._array if isinstance(x, Tensor) else jnp.asarray(x) for x in xs]
    return tuple(Tensor._wrap(a) for a in jnp.meshgrid(*arrays, indexing="ij"))


def one_hot(x, num_classes, dtype=None) -> Tensor:
    import jax.nn

    arr = x._array if isinstance(x, Tensor) else jnp.asarray(x)
    out = jax.nn.one_hot(arr, num_classes, dtype=dtypes.to_jax(dtype or dtypes.get_default_dtype()))
    return Tensor._wrap(out)


def logspace(start, stop, num, base=10.0, dtype=None, name=None):
    from paddle_tpu.core import dtype as dtypes

    jd = dtypes.to_jax(dtype) if dtype is not None else jnp.float32
    return Tensor._wrap(jnp.logspace(float(start), float(stop), int(num),
                                     base=float(base), dtype=jd))


def vander(x, n=None, increasing=False, name=None):
    from .dispatch import apply, as_tensor

    x = as_tensor(x)
    return apply("vander",
                 lambda a: jnp.vander(a, N=n, increasing=increasing), x)


def diagflat(x, offset=0, name=None):
    from .dispatch import apply, as_tensor

    x = as_tensor(x)
    return apply("diagflat", lambda a: jnp.diagflat(a, k=offset), x)


def complex(real, imag, name=None):
    """Build a complex tensor from real/imag parts (paddle.complex)."""
    from .dispatch import apply, as_tensor

    r = as_tensor(real)
    i = as_tensor(imag, r)
    # float width follows the inputs (float64 → complex128 where x64 is
    # enabled), not a hard-coded float32
    fdt = jnp.promote_types(r._array.dtype, i._array.dtype)
    if not jnp.issubdtype(fdt, jnp.floating) or \
            jnp.finfo(fdt).bits < 32:
        # lax.complex accepts only f32/f64; sub-32-bit floats widen
        fdt = jnp.dtype(jnp.float32)

    def fn(a, b):
        a, b = jnp.broadcast_arrays(a.astype(fdt), b.astype(fdt))
        return jax.lax.complex(a, b)

    return apply("complex", fn, r, i)
