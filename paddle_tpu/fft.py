"""paddle.fft analog (python/paddle/fft.py): FFT family over jnp.fft,
dispatched through the op layer so transforms are differentiable on the
tape and fuse under jit (TPU lowers FFTs natively)."""
from __future__ import annotations

import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.ops.dispatch import apply, as_tensor

__all__ = ["fft", "ifft", "rfft", "irfft", "fft2", "ifft2", "rfft2",
           "irfft2", "fftn", "ifftn", "rfftn", "irfftn", "fftshift",
           "ifftshift", "fftfreq", "rfftfreq", "hfft", "ihfft",
           "hfft2", "ihfft2", "hfftn", "ihfftn"]


def _dispatch(opname, call, x):
    return apply(opname, call, as_tensor(x))


def _mk(opname, jfn, takes_n=True):
    if takes_n:
        def op(x, n=None, axis=-1, norm="backward", name=None):
            return _dispatch(opname,
                             lambda a: jfn(a, n=n, axis=axis, norm=norm), x)
    else:
        def op(x, s=None, axes=(-2, -1), norm="backward", name=None):
            return _dispatch(opname,
                             lambda a: jfn(a, s=s, axes=axes, norm=norm), x)
    op.__name__ = opname
    return op


fft = _mk("fft", jnp.fft.fft)
ifft = _mk("ifft", jnp.fft.ifft)
rfft = _mk("rfft", jnp.fft.rfft)
irfft = _mk("irfft", jnp.fft.irfft)
hfft = _mk("hfft", jnp.fft.hfft)
ihfft = _mk("ihfft", jnp.fft.ihfft)
fft2 = _mk("fft2", jnp.fft.fft2, takes_n=False)
ifft2 = _mk("ifft2", jnp.fft.ifft2, takes_n=False)
rfft2 = _mk("rfft2", jnp.fft.rfft2, takes_n=False)
irfft2 = _mk("irfft2", jnp.fft.irfft2, takes_n=False)


def _mkn(opname, jfn):
    def op(x, s=None, axes=None, norm="backward", name=None):
        return _dispatch(opname,
                         lambda a: jfn(a, s=s, axes=axes, norm=norm), x)
    op.__name__ = opname
    return op


fftn = _mkn("fftn", jnp.fft.fftn)
ifftn = _mkn("ifftn", jnp.fft.ifftn)
rfftn = _mkn("rfftn", jnp.fft.rfftn)
irfftn = _mkn("irfftn", jnp.fft.irfftn)


def _hermitian_nd(opname, axis_fn):
    """jnp.fft has no hfft2/hfftn; compose from the 1-d hermitian
    transform over the last axis + complex FFTs over the rest, matching
    scipy/paddle semantics. Order matters: hfft* runs the complex FFTs
    first and the C2R hfft over the last axis LAST (real output);
    ihfft* runs the R2C ihfft over the last axis FIRST."""
    def op(x, s=None, axes=None, norm="backward", name=None):
        def run(a):
            if axes is not None:
                ax = list(axes)
            elif "2" in opname:
                ax = [-2, -1]
            elif s is not None:
                ax = list(range(-len(s), 0))  # last len(s) axes
            else:
                ax = list(range(a.ndim))
            *rest, last = ax
            nlast = None if s is None else s[-1]

            def complex_ffts(out):
                for i, r in enumerate(rest):
                    nr = None if s is None else s[i]
                    jfn = jnp.fft.fft if opname.startswith("h") else \
                        jnp.fft.ifft
                    out = jfn(out, n=nr, axis=r, norm=norm)
                return out

            if opname.startswith("h"):  # C2R last
                return axis_fn(complex_ffts(a), n=nlast, axis=last,
                               norm=norm)
            # R2C first
            return complex_ffts(axis_fn(a, n=nlast, axis=last, norm=norm))
        return _dispatch(opname, run, x)
    op.__name__ = opname
    return op


hfft2 = _hermitian_nd("hfft2", jnp.fft.hfft)
ihfft2 = _hermitian_nd("ihfft2", jnp.fft.ihfft)
hfftn = _hermitian_nd("hfftn", jnp.fft.hfft)
ihfftn = _hermitian_nd("ihfftn", jnp.fft.ihfft)


def fftshift(x, axes=None, name=None):
    return apply("fftshift", lambda a: jnp.fft.fftshift(a, axes=axes),
                 as_tensor(x))


def ifftshift(x, axes=None, name=None):
    return apply("ifftshift", lambda a: jnp.fft.ifftshift(a, axes=axes),
                 as_tensor(x))


def fftfreq(n, d=1.0, dtype=None, name=None):
    from paddle_tpu.core import dtype as dtypes

    out = jnp.fft.fftfreq(n, d=d)
    return Tensor._wrap(out.astype(dtypes.to_jax(dtype))
                        if dtype is not None else out)


def rfftfreq(n, d=1.0, dtype=None, name=None):
    from paddle_tpu.core import dtype as dtypes

    out = jnp.fft.rfftfreq(n, d=d)
    return Tensor._wrap(out.astype(dtypes.to_jax(dtype))
                        if dtype is not None else out)
