"""Eager Tensor — the user-facing dygraph tensor.

TPU-native analog of the reference's eager Tensor
(paddle/fluid/pybind/eager.cc + phi::DenseTensor,
paddle/phi/core/dense_tensor.h:38). Instead of owning an allocation, it
wraps a `jax.Array` (a PJRT buffer on TPU) or, during `jit.to_static`
tracing, a jax tracer — the same Python code therefore serves both the
eager path and the compiled path (the reference needs two stacks for
this: eager kernels + ProgramDesc/InterpreterCore).

Method/dunder surface mirrors python/paddle/tensor/* and the math-op
patch (paddle/fluid/pybind/eager_math_op_patch.cc); methods are installed
by paddle_tpu.ops at import time to avoid an import cycle.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtypes
from .autograd import is_grad_enabled, no_grad


class Tensor:
    __slots__ = (
        "_array",
        "stop_gradient",
        "_grad",
        "_creator",
        "_out_idx",
        "name",
        "persistable",
        "dist_spec",  # PartitionSpec annotation consumed by spmd.TrainStep
        "_version",  # bumped on in-place mutation; tape nodes snapshot it
        "_leaf_hooks",  # grad hooks on leaf tensors (GradNodeAccumulation)
        "__weakref__",
    )

    # make numpy defer to our __r*__ dunders
    __array_priority__ = 100

    def __init__(self, data=None, dtype=None, stop_gradient: bool = True, name: str = ""):
        if data is None:
            data = []
        if isinstance(data, Tensor):
            arr = data._array
            if dtype is not None:
                arr = arr.astype(dtypes.to_jax(dtype))
        elif isinstance(data, (jax.Array, jnp.ndarray)) and not isinstance(data, np.ndarray):
            arr = data if dtype is None else data.astype(dtypes.to_jax(dtype))
        else:
            if dtype is None:
                dtype = dtypes.infer_dtype(data)
            jd = dtypes.to_jax(dtype)
            npd = np.asarray(data)
            arr = jnp.asarray(npd, dtype=jd)
        self._array = arr
        self.stop_gradient = stop_gradient
        self._grad: Optional[Tensor] = None
        self._creator = None
        self._out_idx = 0
        self.name = name
        self.persistable = False
        self.dist_spec = None
        self._version = 0
        self._leaf_hooks = None

    # -- construction ------------------------------------------------------
    @classmethod
    def _wrap(cls, array, stop_gradient: bool = True, creator=None, out_idx: int = 0):
        t = cls.__new__(cls)
        t._array = array
        t.stop_gradient = stop_gradient
        t._grad = None
        t._creator = creator
        t._out_idx = out_idx
        t.name = ""
        t.persistable = False
        t.dist_spec = None
        t._version = 0
        t._leaf_hooks = None
        return t

    # -- metadata ----------------------------------------------------------
    @property
    def shape(self):
        return list(self._array.shape)

    @property
    def ndim(self):
        return self._array.ndim

    @property
    def size(self):
        return int(np.prod(self._array.shape)) if self._array.shape else 1

    @property
    def dtype(self):
        return dtypes.canonical_name(self._array.dtype)

    @property
    def place(self):
        devs = getattr(self._array, "devices", None)
        if devs is None:
            return "traced"
        try:
            return str(next(iter(self._array.devices())))
        except Exception:
            return "traced"

    @property
    def is_leaf(self):
        return self._creator is None

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._array.shape[0]

    # -- grad --------------------------------------------------------------
    @property
    def grad(self):
        return self._grad

    @grad.setter
    def grad(self, value):
        if value is not None and not isinstance(value, Tensor):
            value = Tensor(value)
        self._grad = value

    def _accumulate_grad(self, ct):
        if self._grad is None:
            self._grad = Tensor._wrap(ct, stop_gradient=True)
        else:
            self._grad = Tensor._wrap(self._grad._array + ct, stop_gradient=True)

    def backward(self, grad_tensor=None, retain_graph: bool = False):
        from .autograd import run_backward

        run_backward(self, grad_tensor, retain_graph=retain_graph)

    def clear_grad(self):
        self._grad = None

    def clear_gradient(self):  # reference spelling
        self._grad = None

    def detach(self) -> "Tensor":
        return Tensor._wrap(self._array, stop_gradient=True)

    def detach_(self) -> "Tensor":
        self._creator = None
        self.stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        from paddle_tpu import ops

        return ops.manipulation.clone(self)

    def register_hook(self, hook):
        """Grad hook fired when this tensor's cotangent is materialized
        during backward; analog of egr RegisterGradientHookForTensor. For
        leaf tensors the hook fires at grad accumulation time — the
        GradNodeAccumulation hook point (accumulation_node.h) that e.g.
        DataParallel reducers attach to. The hook receives/returns a
        Tensor (or None to keep unchanged). Returns a handle with
        .remove()."""

        def array_hook(ct, _hook=hook):
            out = _hook(Tensor._wrap(ct))
            if out is None:
                return None
            return out._array if isinstance(out, Tensor) else out

        if self._creator is None:
            if self._leaf_hooks is None:
                self._leaf_hooks = []
            hooks_list = self._leaf_hooks
            hooks_list.append(array_hook)
        else:
            node, idx = self._creator, self._out_idx
            hooks_list = node.out_hooks.setdefault(idx, [])
            hooks_list.append(array_hook)

        class _Handle:
            def remove(self, _lst=hooks_list, _h=array_hook):
                if _h in _lst:
                    _lst.remove(_h)

        return _Handle()

    # -- host interop ------------------------------------------------------
    def numpy(self) -> np.ndarray:
        return np.asarray(self._array)

    def item(self):
        return self._array.item()

    def tolist(self):
        return np.asarray(self._array).tolist()

    def __array__(self, dtype=None):
        a = np.asarray(self._array)
        return a.astype(dtype) if dtype is not None else a

    def __float__(self):
        return float(self._array)

    def __int__(self):
        return int(self._array)

    def __bool__(self):
        if self.size != 1:
            raise ValueError(
                "truth value of a multi-element Tensor is ambiguous; use .any()/.all()"
            )
        return bool(self._array)

    def __repr__(self):
        sg = self.stop_gradient
        return (
            f"Tensor(shape={self.shape}, dtype={self.dtype}, stop_gradient={sg},\n"
            f"       {np.asarray(jax.device_get(self._array)) if not self._is_traced() else '<traced>'})"
        )

    def _is_traced(self) -> bool:
        return not isinstance(self._array, jax.Array) or isinstance(
            self._array, jax.core.Tracer
        )

    # -- in-place mutation (eager only) ------------------------------------
    def _mutate(self, new_array):
        """THE in-place mutation point: every op that overwrites the
        stored value routes here so the version counter (checked at
        backward against tape snapshots) can never be skipped."""
        self._array = new_array
        self._version += 1

    def set_value(self, value):
        if isinstance(value, Tensor):
            arr = value._array
        else:
            arr = jnp.asarray(np.asarray(value))
        self._mutate(arr.astype(self._array.dtype).reshape(self._array.shape))

    def copy_(self, other, blocking=True):
        self.set_value(other)
        return self

    def _in_place_update(self, new_array):
        """Optimizer-style parameter update; keeps identity and autograd
        leaf status. Old buffer is donated conceptually (PJRT frees it)."""
        self._mutate(new_array)

    # -- iteration / indexing installed by ops package ---------------------
    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __hash__(self):
        return id(self)


def _flatten_tensors(x):
    """Utility: pytree leaves -> arrays for functional APIs."""
    return jax.tree_util.tree_map(
        lambda v: v._array if isinstance(v, Tensor) else v, x
    )


class Parameter(Tensor):
    """Trainable tensor; analog of paddle's Parameter/EagerParamBase
    (python/paddle/fluid/framework.py Parameter). stop_gradient defaults
    False and it is persistable (enters state_dict)."""

    # _asp_mask: structured-sparsity mask (incubate.asp), carried by the
    # param itself so masks stay scoped to their model
    __slots__ = ("trainable", "optimize_attr", "regularizer", "_asp_mask")

    def __init__(self, data, dtype=None, name: str = "", trainable: bool = True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, name=name)
        self.persistable = True
        self.trainable = trainable
