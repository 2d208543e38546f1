"""Custom-op toolchain — analog of python/paddle/utils/cpp_extension/
(CppExtension/CUDAExtension/load at cpp_extension.py; C++ side
framework/custom_operator.cc, phi/api/ext/op_meta_info.h).

TPU-native split of the capability:

- **C++ host ops** (`load` + `CustomOpLibrary.wrap_elementwise`): user
  C++ compiled with g++ into a shared library, invoked through
  jax.pure_callback — runs host-side, works eagerly and inside jit
  (XLA inserts the host transfer), differentiable when a backward
  symbol is provided (jax.custom_vjp). This is the "extend without
  forking" seam for host preprocessing / CPU reference kernels.
- **Device custom kernels** (`custom_op`): arbitrary jax/Pallas
  functions registered as paddle ops with optional custom VJP — the
  TPU path for performance-critical fused kernels (the CUDAExtension
  analog; see ops/pallas/flash_attention.py for the house style).
- **Wheel builds** (`CppExtension` + `BuildExtension` + `setup`): thin
  setuptools passthroughs so a reference-style setup.py keeps working.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import tempfile
from typing import Callable, Optional, Sequence

import numpy as np

__all__ = ["CppExtension", "CUDAExtension", "BuildExtension", "setup",
           "load", "get_include", "CustomOpLibrary", "custom_op"]

_DTYPES = {
    "float32": (ctypes.c_float, np.float32),
    "float64": (ctypes.c_double, np.float64),
    "int32": (ctypes.c_int32, np.int32),
    "int64": (ctypes.c_int64, np.int64),
}


def get_include() -> str:
    """Directory containing paddle_ext.h — the PD_BUILD_OP analog: a
    plain C ABI instead of a macro DSL (shipped as package data)."""
    return os.path.join(os.path.dirname(__file__), "include")


def load(name: str, sources: Sequence[str], extra_cflags=None,
         extra_ldflags=None, build_directory: Optional[str] = None,
         verbose: bool = False) -> "CustomOpLibrary":
    """JIT-compile C++ sources into a shared library and load it
    (cpp_extension.load parity). Returns a CustomOpLibrary."""
    import hashlib

    build_dir = build_directory or os.path.join(
        tempfile.gettempdir(), "paddle_tpu_extensions", name)
    os.makedirs(build_dir, exist_ok=True)
    # build options are part of the cache identity (reference load()
    # hashes them too): changed flags must not reuse a stale binary
    tag = hashlib.sha1(repr((sorted(extra_cflags or []),
                             sorted(extra_ldflags or [])))
                       .encode()).hexdigest()[:8]
    so_path = os.path.join(build_dir, f"{name}-{tag}.so")
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
           f"-I{get_include()}", *list(sources),
           *(extra_cflags or []), *(extra_ldflags or []), "-o", so_path]
    # rebuild only when a source is newer than the library
    if not os.path.exists(so_path) or any(
            os.path.getmtime(s) > os.path.getmtime(so_path)
            for s in sources):
        if verbose:
            print("compiling:", " ".join(cmd))
        res = subprocess.run(cmd, capture_output=True, text=True)
        if res.returncode != 0:
            raise RuntimeError(
                f"custom-op build failed:\n{res.stderr[:4000]}")
    return CustomOpLibrary(name, so_path)


class CustomOpLibrary:
    """A loaded custom-op shared library. Raw symbols via .symbol(name);
    differentiable paddle ops via .wrap_elementwise(...)."""

    def __init__(self, name: str, so_path: str):
        self.name = name
        self.so_path = so_path
        self._lib = ctypes.CDLL(so_path)

    def symbol(self, name: str):
        return getattr(self._lib, name)

    def wrap_elementwise(self, symbol: str, backward: Optional[str] = None,
                         dtype: str = "float32") -> Callable:
        """Expose `void symbol(const T* x, T* y, int64_t n)` as a
        differentiable paddle op. `backward` names
        `void b(const T* x, const T* gy, T* gx, int64_t n)`; without it
        the op is forward-only (stop_gradient outputs)."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.dispatch import apply, apply_nograd, as_tensor

        cptr, npdt = _DTYPES[dtype]
        fwd_c = self.symbol(symbol)
        fwd_c.argtypes = [ctypes.POINTER(cptr), ctypes.POINTER(cptr),
                          ctypes.c_int64]
        fwd_c.restype = None

        def host_fwd(x):
            x = np.ascontiguousarray(x, npdt)
            y = np.empty_like(x)
            fwd_c(x.ctypes.data_as(ctypes.POINTER(cptr)),
                  y.ctypes.data_as(ctypes.POINTER(cptr)),
                  ctypes.c_int64(x.size))
            return y

        jdt = jnp.dtype(npdt)

        def check_dtype(t):
            if jnp.dtype(t._array.dtype) != jdt:
                raise TypeError(
                    f"custom op {symbol!r} is registered for {dtype}; got "
                    f"a {t._array.dtype} tensor — cast the input or wrap "
                    f"the symbol for that dtype")
            return t

        def cb_fwd(a):
            return jax.pure_callback(
                host_fwd, jax.ShapeDtypeStruct(a.shape, jdt), a,
                vmap_method="sequential")

        if backward is None:
            def op(x):
                return apply_nograd(symbol, cb_fwd,
                                    check_dtype(as_tensor(x)))
            op.__name__ = symbol
            return op

        bwd_c = self.symbol(backward)
        bwd_c.argtypes = [ctypes.POINTER(cptr), ctypes.POINTER(cptr),
                          ctypes.POINTER(cptr), ctypes.c_int64]
        bwd_c.restype = None

        def host_bwd(x, gy):
            x = np.ascontiguousarray(x, npdt)
            gy = np.ascontiguousarray(gy, npdt)
            gx = np.empty_like(x)
            bwd_c(x.ctypes.data_as(ctypes.POINTER(cptr)),
                  gy.ctypes.data_as(ctypes.POINTER(cptr)),
                  gx.ctypes.data_as(ctypes.POINTER(cptr)),
                  ctypes.c_int64(x.size))
            return gx

        @jax.custom_vjp
        def f(a):
            return cb_fwd(a)

        def f_fwd(a):
            return cb_fwd(a), a

        def f_bwd(a, ct):
            gx = jax.pure_callback(
                host_bwd, jax.ShapeDtypeStruct(a.shape, jdt), a, ct,
                vmap_method="sequential")
            return (gx,)

        f.defvjp(f_fwd, f_bwd)

        def op(x):
            return apply(symbol, f, check_dtype(as_tensor(x)))
        op.__name__ = symbol
        return op


def custom_op(name: Optional[str] = None, fwd: Optional[Callable] = None,
              bwd: Optional[Callable] = None):
    """Register a jax/Pallas function as a paddle op (the device-side
    custom-kernel path — CUDAExtension's role on TPU).

        @custom_op(name="fused_swiglu")
        def fused_swiglu(a, b):            # jnp / pallas_call code
            return a * jax.nn.sigmoid(a) * b

    With `fwd`/`bwd` the op gets a custom VJP (jax.custom_vjp contract:
    fwd(*args) -> (out, residuals); bwd(residuals, ct) -> grads tuple),
    which survives both eager autograd and jit tracing."""

    def deco(fn):
        import jax

        from paddle_tpu.ops.dispatch import apply, as_tensor

        opname = name or fn.__name__
        if (fwd is None) != (bwd is None):
            raise ValueError("custom_op needs both fwd and bwd, or neither")
        if fwd is not None:
            f = jax.custom_vjp(fn)
            f.defvjp(fwd, bwd)
        else:
            f = fn

        def op(*xs, **kw):
            # scalar args adopt the first *Tensor* arg's dtype (as_tensor
            # dereferences ref._array — a raw ndarray ref would crash)
            ref = next((x for x in xs if hasattr(x, "_array")), None)
            tensors = [as_tensor(x, ref) for x in xs]
            return apply(opname, lambda *arrs: f(*arrs, **kw), *tensors)
        op.__name__ = opname
        op.raw = f
        return op

    return deco


# -- wheel-build tier (setuptools passthrough) ---------------------------
def CppExtension(name=None, sources=(), *args, **kwargs):
    """setuptools.Extension preconfigured with our include dir
    (reference CppExtension parity for setup.py builds)."""
    from setuptools import Extension

    kwargs.setdefault("include_dirs", []).append(get_include())
    kwargs.setdefault("language", "c++")
    return Extension(name or "paddle_tpu_ext", list(sources),
                     *args, **kwargs)


def CUDAExtension(*args, **kwargs):
    raise NotImplementedError(
        "this build targets TPU with zero CUDA; write device kernels in "
        "Pallas and register them with paddle.utils.cpp_extension."
        "custom_op (see ops/pallas/flash_attention.py)")


def BuildExtension(*args, **kwargs):
    from setuptools.command.build_ext import build_ext

    return build_ext(*args, **kwargs) if args else build_ext


def setup(**kwargs):
    import setuptools

    kwargs.setdefault("cmdclass", {})["build_ext"] = BuildExtension
    return setuptools.setup(**kwargs)
