"""Op dispatch: the eager execution + autograd-recording boundary.

TPU-native analog of the reference's generated `foo_ad_func` layer
(paddle/fluid/eager/api/generated/.../dygraph_functions.cc, emitted by
eager_gen.py:1049) plus PHI kernel dispatch
(paddle/phi/core/kernel_factory.cc:158). Where the reference selects a
(backend, layout, dtype) kernel and separately generates a GradNode per
op, here every op is ONE pure jax function: `jax.vjp` gives both the
forward value and the backward closure, XLA does kernel selection and
fusion, and the same code path works under tracing (to_static).

AMP autocast (the analog of eager_amp_auto_cast.h) is applied here, at
dispatch time, before the op runs.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from paddle_tpu.core import dtype as dtypes
from paddle_tpu.core.autograd import Node, is_grad_enabled
from paddle_tpu.core.tensor import Tensor

__all__ = ["apply", "apply_nograd", "as_tensor", "unwrap", "OpStats"]


class OpStats:
    """Per-op dispatch counters (profiler hook point).

    span_hook, when set by the Profiler, receives
    (name, start_us, end_us, synced) for every eager op dispatch —
    synced=True means the dispatch blocked until outputs were ready
    (ProfilerTarget.TPU sync timing: the span approximates
    host-dispatch + device-execute, the CUPTI-attribution analog)."""

    counts: dict = {}
    enabled = False
    span_hook = None
    sync_spans = False

    @classmethod
    def record(cls, name):
        if cls.enabled:
            cls.counts[name] = cls.counts.get(name, 0) + 1


def _timed_dispatch(name, run):
    """Wrap one op dispatch with the profiler span hook (no-op fast
    path when no profiler is recording)."""
    hook = OpStats.span_hook
    if hook is None:
        return run()
    import time as _time

    t0 = _time.perf_counter_ns() // 1000
    out = run()
    synced = False
    if OpStats.sync_spans:
        arrs = [o._array for o in out] if isinstance(out, tuple) \
            else [out._array]
        # block_until_ready is a no-op on tracers (it does NOT raise),
        # so trace-time dispatches must be tagged host-side explicitly
        # or the device column absorbs tracing/compile time
        # (non-array outputs get a host span only; a device error in
        # a synced span propagates)
        if all(isinstance(a, jax.Array) and
               not isinstance(a, jax.core.Tracer) for a in arrs):
            jax.block_until_ready(arrs)
            synced = True
    hook(name, t0, _time.perf_counter_ns() // 1000, synced)
    return out


def _maybe_check_numerics(op_name, arrays):
    """FLAGS_check_nan_inf hook (nan_inf_utils.h:37 analog): checks every
    op's outputs when the debug flag is on — concrete arrays host-side,
    tracer outputs via a staged in-graph check."""
    from paddle_tpu.framework import nan_inf

    if not nan_inf.check_enabled():
        return
    concrete = [a for a in arrays if not isinstance(a, jax.core.Tracer)
                and hasattr(a, "dtype")]
    traced = [a for a in arrays if isinstance(a, jax.core.Tracer)]
    if concrete:
        nan_inf.check_eager(op_name, concrete)
    if traced:
        nan_inf.stage_check(
            [(f"output[{i}]", a) for i, a in enumerate(traced)],
            f"op '{op_name}'")


def as_tensor(x, ref: Tensor = None) -> Tensor:
    """Coerce scalars / arrays to Tensor. Python scalars adopt the ref
    tensor's dtype (paddle scalar-promotion semantics: `x * 2.0` keeps
    x's dtype)."""
    if isinstance(x, Tensor):
        return x
    if isinstance(x, (bool, int, float)) and ref is not None and dtypes.is_inexact(ref.dtype):
        return Tensor._wrap(jnp.asarray(x, ref._array.dtype))
    if isinstance(x, (bool, int, float)) and ref is not None:
        # int scalar with int tensor: keep tensor dtype
        if isinstance(x, int) and not isinstance(x, bool):
            return Tensor._wrap(jnp.asarray(x, ref._array.dtype))
    return Tensor(x)


def unwrap(x):
    if isinstance(x, Tensor):
        return x._array
    return x


def _wrap_outputs(out_arrays, node, needs_grad, op_name=None):
    single = not isinstance(out_arrays, (tuple, list))
    outs = [out_arrays] if single else list(out_arrays)
    _maybe_check_numerics(op_name or (node.name if node else "op"), outs)
    tensors = []
    for i, arr in enumerate(outs):
        diffable = needs_grad and jnp.issubdtype(arr.dtype, jnp.inexact)
        t = Tensor._wrap(
            arr,
            stop_gradient=not diffable,
            creator=node if diffable else None,
            out_idx=i,
        )
        tensors.append(t)
    return tensors[0] if single else tuple(tensors)


def apply(name: str, fn: Callable, *inputs: Tensor, amp_policy: str = None):
    """Run differentiable op `fn(*arrays)`; record a tape Node if needed.

    `fn` must be a pure function of the input arrays (static attrs go in
    the closure). Returns Tensor or tuple of Tensors.
    """
    if OpStats.span_hook is not None:
        return _timed_dispatch(
            name, lambda: _apply_impl(name, fn, *inputs,
                                      amp_policy=amp_policy))
    return _apply_impl(name, fn, *inputs, amp_policy=amp_policy)


def _apply_impl(name: str, fn: Callable, *inputs: Tensor,
                amp_policy: str = None):
    OpStats.record(name)
    from paddle_tpu.amp.auto_cast import maybe_autocast  # lazy; amp optional

    inputs = maybe_autocast(name, inputs, amp_policy)
    arrays = [t._array for t in inputs]
    needs_grad = is_grad_enabled() and any(
        (not t.stop_gradient) and jnp.issubdtype(t._array.dtype, jnp.inexact)
        for t in inputs
    )
    if not needs_grad:
        out = fn(*arrays)
        return _wrap_outputs(out, None, False, op_name=name)

    if any(isinstance(a, jax.core.Tracer) for a in arrays):
        # Inside an outer jax trace (TrainStep's value_and_grad, to_static,
        # vmap...): run fn directly so the OUTER AD differentiates it —
        # eagerly calling jax.vjp here would linearize at trace time and
        # force higher-order AD through custom_vjp ops (this is what
        # silently knocked the pallas flash kernel back to dense attention
        # in round 1). The tape node gets a lazy vjp for the rare case of
        # tape backward under trace.
        out = fn(*arrays)
        outs = out if isinstance(out, (tuple, list)) else (out,)
        out_specs = [(o.shape, o.dtype) for o in outs]

        def lazy_vjp(cts, _fn=fn, _arrays=arrays):
            _, vjp_fn = jax.vjp(_fn, *_arrays)
            return vjp_fn(cts)

        node = Node(name, lazy_vjp, inputs, out_specs)
        return _wrap_outputs(out, node, True)

    out, vjp_fn = jax.vjp(fn, *arrays)
    outs = out if isinstance(out, (tuple, list)) else (out,)
    out_specs = [(o.shape, o.dtype) for o in outs]
    node = Node(name, vjp_fn, inputs, out_specs)
    return _wrap_outputs(out, node, True)


def apply_nograd(name: str, fn: Callable, *inputs: Tensor):
    """Run a non-differentiable op (comparisons, argmax, casts to int...)."""
    if OpStats.span_hook is not None:
        return _timed_dispatch(
            name, lambda: _apply_nograd_impl(name, fn, *inputs))
    return _apply_nograd_impl(name, fn, *inputs)


def _apply_nograd_impl(name: str, fn: Callable, *inputs: Tensor):
    OpStats.record(name)
    arrays = [t._array for t in inputs]
    out = fn(*arrays)
    return _wrap_outputs(out, None, False, op_name=name)
