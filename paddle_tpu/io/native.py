"""ctypes binding for the native batch loader (cpp/fastloader.cc) — the
C++ DataLoader core analog (paddle/fluid/framework/data_feed.cc,
reader/buffered_reader.cc). Batch gather/shuffle runs in C++ worker
threads off the GIL, prefetching into a bounded queue while Python/JAX
work proceeds.

The shared library builds on first use with the system toolchain (g++);
environments without one fall back cleanly (`native_available()` is
False and NativeArrayLoader raises with a clear message).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

__all__ = ["native_available", "NativeArrayLoader"]

_lib = None
_lib_err = None
_lock = threading.Lock()


def _build_and_load():
    global _lib, _lib_err
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    src = os.path.join(repo, "cpp", "fastloader.cc")
    try:
        # the library's name carries its source's content hash, so a
        # binary is only ever loaded for the source it was built from
        # (mtimes say nothing in a copied or freshly checked-out tree)
        with open(src, "rb") as f:
            tag = hashlib.sha1(f.read()).hexdigest()[:12]
        out = os.path.join(repo, "cpp", f"libfastloader-{tag}.so")
        if not os.path.exists(out):
            # compile to a per-process temp and rename atomically:
            # concurrent processes (the 2-process launcher, parallel
            # pytest) must never dlopen a half-written .so
            tmp = f"{out}.{os.getpid()}.tmp"
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                 "-o", tmp, src, "-pthread"],
                check=True, capture_output=True, text=True)
            os.replace(tmp, out)
        lib = ctypes.CDLL(out)
    except (OSError, subprocess.CalledProcessError, FileNotFoundError) as e:
        _lib_err = getattr(e, "stderr", None) or str(e)
        return None
    lib.fl_create.restype = ctypes.c_void_p
    lib.fl_create.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int, ctypes.c_int, ctypes.c_uint64, ctypes.c_int64,
        ctypes.c_int]
    lib.fl_next.restype = ctypes.c_int
    lib.fl_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                            ctypes.POINTER(ctypes.c_int64)]
    lib.fl_num_batches.restype = ctypes.c_int64
    lib.fl_num_batches.argtypes = [ctypes.c_void_p]
    lib.fl_epoch.argtypes = [ctypes.c_void_p]
    lib.fl_destroy.argtypes = [ctypes.c_void_p]
    return lib


def _get_lib():
    global _lib
    with _lock:
        if _lib is None and _lib_err is None:
            _lib = _build_and_load()
    return _lib


def native_available():
    return _get_lib() is not None


class NativeArrayLoader:
    """Iterate (batches of) one or more aligned numpy arrays with C++
    worker-thread prefetch. All arrays share dim 0; shuffling is
    deterministic per (seed, epoch) and identical across the arrays
    (each array gets its own native loader seeded alike, stepped in
    lockstep — the multi-field sample case).

        loader = NativeArrayLoader((images, labels), batch_size=256,
                                   shuffle=True, workers=4)
        for epoch in range(E):
            for xb, yb in loader: ...
    """

    def __init__(self, arrays, batch_size, shuffle=False, drop_last=False,
                 seed=0, prefetch=4, workers=2):
        lib = _get_lib()
        if lib is None:
            raise RuntimeError(
                f"native loader unavailable (toolchain?): {_lib_err}")
        self._lib = lib
        if isinstance(arrays, np.ndarray):
            arrays = (arrays,)
        self._arrays = [np.ascontiguousarray(a) for a in arrays]
        n = {len(a) for a in self._arrays}
        if len(n) != 1:
            raise ValueError(f"arrays disagree on dim 0: {sorted(n)}")
        self.batch_size = int(batch_size)
        self._handles = []
        for a in self._arrays:
            item_bytes = a.dtype.itemsize * int(np.prod(a.shape[1:],
                                                        dtype=np.int64))
            h = lib.fl_create(
                a.ctypes.data_as(ctypes.c_void_p), len(a), item_bytes,
                self.batch_size, int(drop_last), int(shuffle),
                int(seed), int(prefetch), int(workers))
            self._handles.append((h, a, item_bytes))
        self._started = False

    def __len__(self):
        return int(self._lib.fl_num_batches(self._handles[0][0]))

    def __iter__(self):
        if self._started:
            for h, _, _ in self._handles:
                self._lib.fl_epoch(h)
        self._started = True
        nb = len(self)
        cnt = ctypes.c_int64()
        bufs = [np.empty((self.batch_size,) + a.shape[1:], a.dtype)
                for _, a, _ in self._handles]
        for _ in range(nb):
            outs = []
            for (h, a, _), buf in zip(self._handles, bufs):
                ok = self._lib.fl_next(
                    h, buf.ctypes.data_as(ctypes.c_void_p),
                    ctypes.byref(cnt))
                if not ok:
                    return
                outs.append(buf[:cnt.value].copy())
            yield tuple(outs) if len(outs) > 1 else outs[0]

    def __del__(self):
        lib = getattr(self, "_lib", None)
        if lib is not None:
            for h, _, _ in getattr(self, "_handles", []):
                lib.fl_destroy(h)
