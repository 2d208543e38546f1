"""jit.to_static — the dygraph→static bridge, TPU-native.

Reference analog: @paddle.jit.to_static traces python into a ProgramDesc
executed by InterpreterCore (SURVEY §3.3: program_translator.py:290 →
partial_program.py:644 → run_program op → interpretercore.cc:224).

Here the eager Tensor wraps jax arrays, so the SAME user function traces
under jax.jit directly: Tensors are wrapped around tracers, every op
flows through jnp, and the whole function lowers to ONE XLA computation.
The compile cache is keyed by input (shape, dtype) specs — the CacheKey
analog (program_translator.py:168).

`TrainStep` functionalizes a whole training step (forward + backward +
optimizer update) into one donated, jitted XLA program — the analog of
to_static over a full train loop body, and the perf path used by the
benchmarks.
"""
from __future__ import annotations

import builtins
import functools
import inspect
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from contextlib import contextmanager

from paddle_tpu.core import autograd
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit import introspect
from paddle_tpu.observability.tracing import install_host_pause_hooks
from paddle_tpu.profiler import RecordEvent


_bound_depth = 0


def buffer_writes_captured():
    """True while a bound_state scope is live — i.e. in-trace buffer
    assignments will be captured by the compiled step's buffer plumbing
    (make_forward_loss) and then restored; layers that guard against
    tracer leaks (SpectralNorm) may write tracers freely here."""
    return _bound_depth > 0


@contextmanager
def bound_state(bind_pairs, restore_tensors):
    """Bind traced arrays into live Tensor objects for the duration of a
    trace, restoring ALL of restore_tensors after — so in-trace mutations
    (e.g. BN running stats) can't leak tracers into the eager world. The
    one bind/restore dance shared by compiled train steps and the hapi
    eval path."""
    global _bound_depth
    originals = [t._array for t in restore_tensors]
    try:
        for t, a in bind_pairs:
            t._array = a
        _bound_depth += 1
        yield
    finally:
        _bound_depth -= 1
        for t, o in zip(restore_tensors, originals):
            t._array = o


class InputSpec:
    """Analog of paddle.static.InputSpec."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name


def _spec_of(x):
    if isinstance(x, Tensor):
        return ("T", x._array.shape, str(x._array.dtype))
    if isinstance(x, (np.ndarray, jax.Array)):
        return ("A", x.shape, str(x.dtype))
    if isinstance(x, (list, tuple)):
        return tuple(_spec_of(v) for v in x)
    return ("S", x)  # static python value — part of the cache key


def _unwrap(x):
    if isinstance(x, Tensor):
        return x._array
    if isinstance(x, (list, tuple)):
        return type(x)(_unwrap(v) for v in x)
    return x


def _is_arraylike(x):
    return isinstance(x, (Tensor, np.ndarray, jax.Array))


class StaticFunction:
    """Analog of dy2static StaticFunction (program_translator.py:290).

    When the traced function belongs to a Layer (decorating the layer, or
    a bound method of one), the layer's parameters AND buffers are threaded
    through the jitted program as traced arguments — so optimizer updates,
    `set_value`, `load_state_dict` etc. are visible on the next call
    instead of being baked in as compile-time constants (VERDICT r1 weak
    #1: to_static silently used stale weights). Free functions that close
    over tensors still bake them; wrap the owning Layer instead."""

    def __init__(self, fn, input_spec=None, build_strategy=None, backend=None,
                 layer=None):
        # data-dependent if/while become lax.cond/while_loop (dy2static
        # AST pass; python-bool conditions keep python semantics)
        from paddle_tpu.jit.dy2static import transform_function

        self._fn = transform_function(fn)
        self._input_spec = list(input_spec) if input_spec else None
        self._bucket_dynamic = bool(
            (build_strategy or {}).get("dynamic_dim_buckets")
            if isinstance(build_strategy, dict) else
            getattr(build_strategy, "dynamic_dim_buckets", False))
        self._layer = layer
        if layer is None and inspect.ismethod(fn):
            from paddle_tpu.nn.layer import Layer

            if isinstance(fn.__self__, Layer):
                self._layer = fn.__self__
        self._cache = {}  # spec key -> jitted callable
        functools.update_wrapper(self, fn)

    def _spec_tensors(self, args, kwargs):
        """Array-like inputs in parameter order (kwarg tensors included,
        via signature binding)."""
        if kwargs:
            try:
                ba = inspect.signature(self._fn).bind(*args, **kwargs)
                flat = list(ba.arguments.values())
            except TypeError:
                flat = list(args) + list(kwargs.values())
        else:
            flat = list(args)
        return [a for a in flat if _is_arraylike(a)]

    def _check_spec(self, args, kwargs):
        """input_spec is a contract, not a hint (program_translator.py:519
        spec-driven concretization): ranks/dtypes/fixed dims must match;
        None/-1/named dims accept any size."""
        tensors = self._spec_tensors(args, kwargs)
        if len(tensors) < len(self._input_spec):
            raise ValueError(
                f"to_static input_spec expects {len(self._input_spec)} "
                f"tensor inputs, got {len(tensors)}")
        for n, (s, a) in enumerate(zip(self._input_spec, tensors)):
            arr = a._array if isinstance(a, Tensor) else np.asarray(a)
            if len(arr.shape) != len(s.shape):
                raise ValueError(
                    f"input {n}: rank {len(arr.shape)} != input_spec rank "
                    f"{len(s.shape)} {tuple(s.shape)}")
            want = str(jnp.dtype(s.dtype if s.dtype is not None
                                 else "float32"))
            if str(arr.dtype) != want:
                raise TypeError(
                    f"input {n}: dtype {arr.dtype} != input_spec dtype "
                    f"{want}")
            for ax, d in enumerate(s.shape):
                if isinstance(d, int) and d >= 0 and arr.shape[ax] != d:
                    raise ValueError(
                        f"input {n}: dim {ax} is {arr.shape[ax]}, "
                        f"input_spec requires {d}")

    def _bucket_args(self, args, kwargs):
        """Pad AXIS-0 dynamic-spec dims up to the next power of two so N
        batch sizes share one compiled program (TPU dynamic-batch
        bucketing); outputs carrying the padded size on axis 0 are sliced
        back by the caller. Dynamic dims on other axes stay unpadded
        (each size gets its own trace). Opt-in, with two caveats: math
        that mixes rows across the batch (e.g. a mean over axis 0) sees
        the zero-pad rows, and a fixed-size output whose leading dim
        coincidentally equals the bucket size would be mis-sliced."""
        if kwargs:
            raise ValueError(
                "dynamic_dim_buckets requires the spec'd tensors to be "
                "passed positionally")
        arr_pos = [i for i, a in enumerate(args) if _is_arraylike(a)]
        args = list(args)
        orig = padded = None
        for s, i in zip(self._input_spec, arr_pos):
            if not s.shape:
                continue
            d = s.shape[0]
            if not (d is None or isinstance(d, str) or
                    (isinstance(d, int) and d < 0)):
                continue
            a = args[i]
            arr = a._array if isinstance(a, Tensor) else jnp.asarray(a)
            n = arr.shape[0]
            b = 1 << max(n - 1, 0).bit_length() if n & (n - 1) else n
            orig, padded = n, b
            if b != n:
                widths = [(0, b - n)] + [(0, 0)] * (arr.ndim - 1)
                arr = jnp.pad(arr, widths)
                args[i] = Tensor._wrap(arr) if isinstance(a, Tensor) else arr
        return tuple(args), (orig, padded) if orig is not None and \
            padded != orig else None

    @property
    def concrete_programs(self):
        return list(self._cache.values())

    def _live_state(self):
        if self._layer is None:
            return []
        return list(self._layer.parameters()) + list(self._layer.buffers())

    def __call__(self, *args, **kwargs):
        bucket = None
        if self._input_spec:
            self._check_spec(args, kwargs)
            if self._bucket_dynamic:
                args, bucket = self._bucket_args(args, kwargs)
        out = self._call_impl(args, kwargs)
        if bucket is not None:
            orig, padded = bucket

            def unslice(t):
                arr = t._array if isinstance(t, Tensor) else t
                if hasattr(arr, "shape") and arr.ndim >= 1 and \
                        arr.shape[0] == padded:
                    return t[:orig] if isinstance(t, Tensor) \
                        else arr[:orig]
                return t
            out = jax.tree_util.tree_map(
                unslice, out, is_leaf=lambda t: isinstance(t, Tensor))
        return out

    def _call_impl(self, args, kwargs):
        state = self._live_state()
        # key includes the state object identities: layer surgery that
        # REPLACES a Parameter (vs mutating it) must retrace, otherwise
        # pure_fn would bind arrays into dead objects and bake the new
        # object's value as a constant
        from paddle_tpu.framework.flags import debug_epoch

        key = (_spec_of(args), _spec_of(tuple(sorted(kwargs.items()))),
               tuple(id(t) for t in state), debug_epoch())
        entry = self._cache.get(key)
        if entry is None:
            entry = [self._build(args, kwargs, state), None]  # [jitted, tape_ok]
            self._cache[key] = entry
        jitted = entry[0]
        flat_arrays = [_unwrap(a) for a in args if _is_arraylike(a) or isinstance(a, (list, tuple))]
        kw_arrays = {k: _unwrap(v) for k, v in kwargs.items()
                     if _is_arraylike(v)}

        # Record the whole compiled program as ONE tape op so eager
        # backward flows through it into params and inputs — the analog of
        # run_program's GradNodeRunProgram (eager/to_static/
        # run_program_op_node.h). Taken for the common case: positional
        # Tensor/array args, flat Tensor(-tuple) output; anything fancier
        # falls back to no-grad wrapping.
        from paddle_tpu.core.autograd import is_grad_enabled
        from paddle_tpu.ops.dispatch import apply

        simple_args = builtins.all(
            _is_arraylike(a) or not isinstance(a, (list, tuple, dict))
            for a in args) and not kw_arrays
        tensor_args = [Tensor._wrap(jnp.asarray(a)) if not isinstance(a, Tensor) else a
                       for a in args if _is_arraylike(a)]
        if simple_args and is_grad_enabled() and any(
                not t.stop_gradient for t in state + tensor_args):
            n_state = len(state)

            def tape_fn(*all_arrays):
                return jitted(list(all_arrays[:n_state]),
                              *all_arrays[n_state:])

            if entry[1] is None:  # probe once per cache entry, not per call
                probe = jax.eval_shape(
                    tape_fn, *[t._array for t in state + tensor_args])
                leaves = probe if isinstance(probe, (tuple, list)) else [probe]
                entry[1] = builtins.all(
                    isinstance(p, jax.ShapeDtypeStruct) for p in leaves)
            if entry[1]:
                return apply(f"to_static:{getattr(self._fn, '__name__', 'fn')}",
                             tape_fn, *state, *tensor_args)

        out_arrays = jitted([t._array for t in state], *flat_arrays,
                            **kw_arrays)
        return jax.tree_util.tree_map(
            lambda a: Tensor._wrap(a) if isinstance(a, (jax.Array, jnp.ndarray)) else a,
            out_arrays)

    def _build(self, args, kwargs, state):
        fn = self._fn
        static_kwargs = {k: v for k, v in kwargs.items() if not _is_arraylike(v)}
        arr_kwarg_names = [k for k, v in kwargs.items() if _is_arraylike(v)]
        arg_templates = list(args)
        state_tensors = list(state)

        def pure_fn(state_arrays, *arrays, **akw):
            it = iter(arrays)

            def rebuild(tpl):
                if _is_arraylike(tpl):
                    return Tensor._wrap(next(it), stop_gradient=getattr(tpl, "stop_gradient", True))
                if isinstance(tpl, (list, tuple)):
                    return type(tpl)(rebuild(v) for v in tpl)
                return tpl

            new_args = [rebuild(a) for a in arg_templates]
            new_kwargs = dict(static_kwargs)
            for k in arr_kwarg_names:
                new_kwargs[k] = Tensor._wrap(akw[k])
            # bind live layer state for the trace; restore after so no
            # tracer leaks into the eager world (e.g. BN running stats
            # mutated inside the traced forward)
            originals = [t._array for t in state_tensors]
            try:
                for t, a in zip(state_tensors, state_arrays):
                    t._array = a
                out = fn(*new_args, **new_kwargs)
            finally:
                for t, o in zip(state_tensors, originals):
                    t._array = o
            return jax.tree_util.tree_map(
                lambda t: t._array if isinstance(t, Tensor) else t, out,
                is_leaf=lambda t: isinstance(t, Tensor))

        return jax.jit(pure_fn)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """Decorator analog of paddle.jit.to_static (jit/api.py:to_static)."""

    def decorate(fn):
        if isinstance(fn, StaticFunction):
            return fn
        # layer: wrap its forward
        from paddle_tpu.nn.layer import Layer

        if isinstance(fn, Layer):
            fn.forward = StaticFunction(fn.forward, input_spec,
                                        build_strategy, backend, layer=fn)
            return fn
        return StaticFunction(fn, input_spec, build_strategy, backend)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def count_traces(fn):
    """Trace-count probe: wrap a python callable BEFORE handing it to
    jax.jit so every retrace (jit cache miss) increments `.traces` —
    jax re-invokes the python function exactly once per new
    (shape, dtype) signature. CI uses this to PROVE a steady-state
    compiled path stays compiled (e.g. the generation engine's decode
    step must trace once, not once per request), instead of inferring
    it from wall-clock noise."""

    @functools.wraps(fn)
    def counted(*args, **kwargs):
        counted.traces += 1
        return fn(*args, **kwargs)

    counted.traces = 0
    return counted


@contextmanager
def expect_traces(counted, n):
    """Assertion helper over a `count_traces` probe: the wrapped block
    must trigger EXACTLY n new traces (n=0 asserts no recompiles —
    the steady-state-decode CI contract)."""
    if not hasattr(counted, "traces"):
        raise TypeError("expect_traces needs a count_traces-wrapped "
                        "callable (missing .traces)")
    before = counted.traces
    yield
    got = counted.traces - before
    if got != n:
        raise AssertionError(
            f"expected {n} trace(s) of {getattr(counted, '__name__', counted)} "
            f"in this block, observed {got} — a compiled path is "
            "retracing (shape/dtype drift or python-object cache-key "
            "churn)")


def dedup_params(params):
    """Identity-dedup for parameter/buffer lists: a layer registered
    under two parents (shared submodules) must not produce a
    twice-donated array."""
    seen, out = set(), []
    for p in params:
        if id(p) not in seen:
            seen.add(id(p))
            out.append(p)
    return out


def model_buffers(model):
    """The ordered buffer list threaded through compiled steps (must be
    identical between make_forward_loss and the caller's writeback),
    identity-deduplicated."""
    return dedup_params(model.buffers() if hasattr(model, "buffers")
                        else [])


def make_forward_loss(model, loss_fn, params, with_outputs=False,
                      buffers=None):
    """The traced forward: bind param AND buffer arrays into the live
    Tensors, run the eager forward under the per-step rng, return
    (loss, (new_buffers, outputs-or-None)). Buffer mutations made by the
    forward (BN running stats, SpectralNorm power-iteration u/v) are
    captured before bound_state restores the eager arrays, so compiled
    steps persist them — the analog of the reference's in-place
    MomentumTensor updates inside run_program. Shared by build_step_fn
    and the gradient-accumulation programs."""
    from paddle_tpu.core import random as random_mod

    if buffers is None:
        buffers = model_buffers(model)

    def forward_loss(param_arrays, buf_arrays, inputs, label, rng):
        # rng is the per-step traced key that dropout & friends derive
        # from (random.key_scope)
        with bound_state(zip(params + buffers,
                             list(param_arrays) + list(buf_arrays)),
                         params + buffers):
            with random_mod.key_scope(rng):
                out = model(*inputs) if isinstance(inputs, tuple) else model(inputs)
                loss = loss_fn(out, Tensor._wrap(label)) if loss_fn is not None else out
            loss_arr = loss._array if isinstance(loss, Tensor) else loss
            # capture in-trace buffer writes BEFORE bound_state restores;
            # stop_gradient — buffer state is never a differentiable path
            new_bufs = [jax.lax.stop_gradient(b._array) for b in buffers]
            out_arrs = None
            if with_outputs:
                out_arrs = jax.tree_util.tree_map(
                    lambda t: t._array if isinstance(t, Tensor) else t, out,
                    is_leaf=lambda t: isinstance(t, Tensor))
            return loss_arr, (new_bufs, out_arrs)

    return forward_loss


def make_update_fn(opt, acc_idx, params):
    """The optimizer tail: clip + per-param single_update over merged
    accumulator slots. (param_arrays, grads, accums, lr, step) ->
    (new_params, new_accums). Shared by build_step_fn and the
    gradient-merge apply program."""
    opt._ensure_state()
    single_update = opt._single_update
    accum_names = list(opt._accumulators.keys())
    grad_clip = opt._grad_clip
    extras_list = [opt._per_param_extras(j) for j in acc_idx]
    # ASP n:m sparsity masks (incubate.asp.prune_model sets _asp_mask):
    # re-applied after every compiled update so sparsity holds on the
    # TrainStep paths too, not just eager optimizer.step (the reference's
    # OptimizerWithSparsityGuarantee runs inside minimize). Masks are
    # constants baked at trace time — prune before the first step.
    asp_masks = [getattr(p, "_asp_mask", None) for p in params]

    def update(param_arrays, grads, accums, lr, step, skip=None):
        if grad_clip is not None:
            # under pjit the norm reduction is mesh-global: XLA inserts
            # the cross-shard collectives
            # (hybrid_parallel_optimizer.py:186)
            grads = grad_clip._clip_arrays(list(grads))
        new_params, new_accums = [], {k: [] for k in accum_names}
        for i, (p, g) in enumerate(zip(param_arrays, grads)):
            acc_i = {k: accums[k][i] for k in accum_names}
            np_, na = single_update(p, g, acc_i, lr, step,
                                    extras=extras_list[i])
            if asp_masks[i] is not None:
                np_ = np_ * jnp.asarray(asp_masks[i], np_.dtype)
            if skip is not None:
                # skip the whole update on overflow (GradScaler.step
                # semantics): params and opt state keep their old values
                np_ = jnp.where(skip, p, np_)
                na = {k: jnp.where(skip, acc_i[k], v)
                      for k, v in na.items()}
            new_params.append(np_)
            for k in accum_names:
                new_accums[k].append(na.get(k, acc_i[k]))
        return new_params, new_accums

    return update


def build_step_fn(model, opt, loss_fn, params, acc_idx,
                  with_outputs=False, with_scaler=False, buffers=None):
    """The ONE compiled-train-step body shared by jit.TrainStep (single
    device) and distributed.DistributedTrainStep (SPMD — which adds
    shardings around it): value_and_grad over the model's eager forward
    with params bound as traced args, grad clip, then the optimizer's
    per-param update. Signature of the returned fn:
    (param_arrays, accums, bufs, lr, step, inputs, label, rng) ->
    (loss, new_params, new_accums, new_bufs) — or with_outputs=True:
    ((loss, out), ...), the hapi train-metrics path (outputs ride along
    as value_and_grad aux, no second forward). `bufs` are the model's
    non-trainable buffers (BN running stats, spectral-norm u/v) whose
    in-forward updates persist across compiled steps."""
    if buffers is None:
        buffers = model_buffers(model)
    forward_loss = make_forward_loss(model, loss_fn, params, with_outputs,
                                     buffers=buffers)
    update = make_update_fn(opt, acc_idx, params)

    def step_fn(param_arrays, accums, bufs, lr, step, inputs, label, rng,
                scale=None):
        if with_scaler:
            # the UNSCALED loss rides along as aux, so the reported loss
            # stays exact even when the scaled one overflows
            def scaled_loss(pa, ins, lb, r):
                loss, aux = forward_loss(pa, bufs, ins, lb, r)
                return loss * scale, (loss, aux)
            (_, (loss, (new_bufs, out))), grads = jax.value_and_grad(
                scaled_loss, has_aux=True)(
                param_arrays, inputs, label, rng)
            found_inf = jnp.logical_not(jnp.stack(
                [jnp.all(jnp.isfinite(g)) for g in grads]).all())
            # divide, don't multiply by 1/scale: at large scales the
            # reciprocal is subnormal and XLA flushes it to zero
            grads = [(g.astype(jnp.float32) / scale).astype(p.dtype)
                     for g, p in zip(grads, param_arrays)]
            # a skipped step must not advance buffer state either
            new_bufs = [jnp.where(found_inf, b, nb)
                        for b, nb in zip(bufs, new_bufs)]
        else:
            (loss, (new_bufs, out)), grads = jax.value_and_grad(
                forward_loss, has_aux=True)(
                param_arrays, bufs, inputs, label, rng)
        from paddle_tpu.framework import nan_inf

        if nan_inf.check_enabled():
            # FLAGS_check_nan_inf inside the compiled step: loss + every
            # grad, named, via one staged host callback (SURVEY §7)
            named = [("loss", loss)] + [
                (f"{getattr(p, 'name', None) or f'param{i}'}.grad", g)
                for i, (p, g) in enumerate(zip(params, grads))]
            nan_inf.stage_check(named, "compiled train step")
        new_params, new_accums = update(
            param_arrays, grads, accums, lr, step,
            skip=found_inf if with_scaler else None)
        if with_outputs:
            loss = (loss, out)
        if with_scaler:
            return loss, found_inf, new_params, new_accums, new_bufs
        return loss, new_params, new_accums, new_bufs

    return step_fn


def make_accum_fns(model, optimizer, loss_fn, params, acc_idx, K,
                   avg=True, with_scaler=False):
    """Gradient-merge closure pair shared by TrainStep and
    DistributedTrainStep: accumulate (forward+backward into f32
    buffers, no update; FLAGS_check_nan_inf staged per micro-step) and
    apply (optimizer update from the MEAN — or SUM when avg=False,
    GradientMergeOptimizer parity — buffers zeroed). Built from the
    same make_forward_loss/make_update_fn pieces as the normal step so
    clip/nan-check behavior can't drift; callers add their own jit
    options/shardings.

    with_scaler=True (GradScaler x gradient accumulation, the
    reference's gradient_merge + amp composition): acc_fn gains
    (found, ..., scale) and accumulates SCALED f32 grads while OR-ing
    per-micro-step non-finiteness into `found`; upd_fn divides by
    scale*K and skips the whole window's update on overflow, exactly
    like the unaccumulated GradScaler.step path."""
    from paddle_tpu.framework import nan_inf

    buffers = model_buffers(model)
    forward_loss = make_forward_loss(model, loss_fn, params,
                                     buffers=buffers)
    update = make_update_fn(optimizer, acc_idx, params)

    def _grads_and_bufs(param_arrays, model_bufs, inputs, label, rng,
                        scale):
        if with_scaler:
            def scaled_loss(pa, ins, lb, r):
                loss, aux = forward_loss(pa, model_bufs, ins, lb, r)
                return loss * scale, (loss, aux)
            (_, (loss, (new_model_bufs, _))), grads = jax.value_and_grad(
                scaled_loss, has_aux=True)(param_arrays, inputs, label,
                                           rng)
        else:
            (loss, (new_model_bufs, _)), grads = jax.value_and_grad(
                forward_loss, has_aux=True)(
                param_arrays, model_bufs, inputs, label, rng)
        if nan_inf.check_enabled():
            named = [("loss", loss)] + [
                (f"{getattr(p, 'name', None) or f'param{i}'}.grad", g)
                for i, (p, g) in enumerate(zip(params, grads))]
            nan_inf.stage_check(named, "gradient-merge micro-step")
        return loss, grads, new_model_bufs

    if with_scaler:
        def acc_fn(bufs, found, param_arrays, model_bufs, inputs, label,
                   rng, scale):
            loss, grads, new_model_bufs = _grads_and_bufs(
                param_arrays, model_bufs, inputs, label, rng, scale)
            micro_inf = jnp.logical_not(jnp.stack(
                [jnp.all(jnp.isfinite(g)) for g in grads]).all())
            # an overflowed micro-step must not advance buffer state
            # (matches the unaccumulated scaler step)
            new_model_bufs = [jnp.where(micro_inf, b, nb)
                              for b, nb in zip(model_bufs,
                                               new_model_bufs)]
            return (loss, [b + g.astype(jnp.float32)
                           for b, g in zip(bufs, grads)],
                    jnp.logical_or(found, micro_inf), new_model_bufs)

        def upd_fn(param_arrays, accums, bufs, lr, step, scale, found):
            div = (K if avg else 1)
            # divide by the (large) scale BEFORE the micro-count: the
            # scaled f32 sum stays far from overflow, and dividing by
            # scale avoids the subnormal-reciprocal trap
            grads = [(b / scale / div).astype(p.dtype)
                     for b, p in zip(bufs, param_arrays)]
            new_params, new_accums = update(param_arrays, grads, accums,
                                            lr, step, skip=found)
            zeroed = [jnp.zeros_like(b) for b in bufs]
            return new_params, new_accums, zeroed

        return acc_fn, upd_fn

    def acc_fn(bufs, param_arrays, model_bufs, inputs, label, rng):
        loss, grads, new_model_bufs = _grads_and_bufs(
            param_arrays, model_bufs, inputs, label, rng, None)
        return loss, [b + g.astype(jnp.float32)
                      for b, g in zip(bufs, grads)], new_model_bufs

    def upd_fn(param_arrays, accums, bufs, lr, step):
        div = K if avg else 1
        grads = [(b / div).astype(p.dtype)
                 for b, p in zip(bufs, param_arrays)]
        new_params, new_accums = update(param_arrays, grads, accums,
                                        lr, step)
        zeroed = [jnp.zeros_like(b) for b in bufs]
        return new_params, new_accums, zeroed

    return acc_fn, upd_fn


def gather_accums(opt, acc_idx):
    """Select the accumulator slots for the trained-param subset (aligned
    with acc_idx into the optimizer's parameter list)."""
    return {k: [v[j] for j in acc_idx] for k, v in opt._accumulators.items()}


def scatter_accums(opt, acc_idx, new_accums):
    """Write updated accumulator slots back to their optimizer positions."""
    for k in opt._accumulators:
        for out_pos, j in enumerate(acc_idx):
            opt._accumulators[k][j] = new_accums[k][out_pos]


#: TrainStep's host stages as spans (`RecordEvent`: the profiler's clock
#: and the host-event recorder): the whole call, and inside it the
#: gathering of the live state, the call of the compiled program, and
#: the write-back into the parameters, accumulators and buffers
SPAN_STEP = "trainstep.step"
SPAN_GATHER = "trainstep.gather"
SPAN_DISPATCH = "trainstep.dispatch"
SPAN_SCATTER = "trainstep.scatter"


class TrainStep:
    """One fully-compiled training step over (model, optimizer, loss_fn).

    Usage:
        step = TrainStep(model, opt, loss_fn)   # loss_fn(model_out, label)
        loss = step(x, label)                   # one XLA execution

    Functionalizes parameters + optimizer state into pytrees, runs
    jax.value_and_grad over the forward, applies the optimizer update, and
    donates old params/opt-state buffers (in-place update in HBM). This is
    the idiomatic-TPU replacement for the reference's to_static training
    (run_program_op + InterpreterCore) and is what bench.py measures.
    """

    def __init__(self, model, optimizer, loss_fn=None, donate=True,
                 with_outputs=False, accumulate_steps=1, scaler=None,
                 telemetry=None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.with_outputs = with_outputs
        # the process's pause hooks: every collection inside a
        # `host.gc` span, every compile counted (always on)
        install_host_pause_hooks()
        # observability.TrainingTelemetry: when attached, each __call__
        # is timed end-to-end (blocking on the loss so the histogram
        # sees device time, not async dispatch) and recorded as one
        # step observation — the only sync telemetry costs
        self.telemetry = telemetry
        # gradient merge (GradientMergeOptimizer k_steps analog): grads
        # from K successive micro-batch calls accumulate in device
        # buffers; the optimizer applies the MEAN on the K-th call
        self.accumulate_steps = int(accumulate_steps)
        self._accum_count = 0
        self._grad_bufs = None
        # fp16 loss scaling (GradScaler) INSIDE the compiled step: scale
        # loss, unscale grads, skip the update when any grad is non-finite
        self.scaler = scaler
        if with_outputs and self.accumulate_steps > 1:
            raise NotImplementedError(
                "accumulate_steps with with_outputs is not supported")
        optimizer._ensure_state()
        # The traced/updated set is the intersection of the model's
        # trainable params (stop_gradient=False — frozen params stay baked
        # as constants, matching eager Optimizer.step skipping grad-None
        # params) and the optimizer's parameter list (whose accumulator
        # slots we must index consistently).
        opt_index = {id(p): j for j, p in enumerate(optimizer._parameter_list)}
        self._params = dedup_params(
            p for p in model.parameters()
            if not p.stop_gradient and id(p) in opt_index)
        self._acc_idx = [opt_index[id(p)] for p in self._params]
        # buffers thread through the compiled step so in-forward updates
        # (BN running stats, spectral-norm u/v) persist across steps
        self._buffers = model_buffers(model)
        self._jitted = None
        self._scan_jitted = None
        self._donate = donate
        self._opt_state = None

    def _build(self):
        # donation layout published via jit.introspect so tooling
        # (tpu-lint) reads it instead of string-matching this file
        return jax.jit(self._make_step_fn(),
                       donate_argnums=introspect.TRAINSTEP_DONATE_ARGNUMS
                       if self._donate else ())

    def _buf_arrays(self):
        return [b._array for b in self._buffers]

    def _write_buffers(self, new_bufs):
        for b, a in zip(self._buffers, new_bufs):
            b._array = a

    def _gather_accums(self):
        return gather_accums(self.optimizer, self._acc_idx)

    def _scatter_accums(self, new_accums):
        scatter_accums(self.optimizer, self._acc_idx, new_accums)

    def _next_step_key(self):
        from paddle_tpu.core import random as random_mod

        return random_mod.next_key()

    def _with_scaler(self):
        return self.scaler is not None and self.scaler.is_enable()

    def _check_plain(self, what):
        """Multi-step scan paths support neither loss scaling nor
        gradient merge (the scan body applies a full update per step)."""
        if self._with_scaler():
            raise NotImplementedError(
                f"{what} does not support a GradScaler; call the step "
                "per batch instead")
        if self.accumulate_steps > 1:
            raise NotImplementedError(
                f"{what} does not support accumulate_steps>1; call the "
                "step per micro-batch instead")

    def _make_step_fn(self):
        return build_step_fn(self.model, self.optimizer, self.loss_fn,
                             self._params, self._acc_idx,
                             with_outputs=self.with_outputs,
                             with_scaler=self._with_scaler(),
                             buffers=self._buffers)

    def run_scan(self, inputs_stacked, labels_stacked):
        """Run a whole sequence of steps inside ONE XLA program via
        lax.scan — amortizes dispatch latency to zero and lets XLA overlap
        steps. inputs/labels have a leading [num_steps] dim. Returns the
        per-step losses. (The analog of the reference's
        Executor.train_from_dataset inner loop, compiled.)"""
        from paddle_tpu.framework.flags import debug_epoch

        if self._scan_jitted is None or \
                getattr(self, "_scan_epoch", None) != debug_epoch():
            self.optimizer._ensure_state()
            self._scan_jitted = self._build_scan()
            self._scan_epoch = debug_epoch()
        xs = _unwrap(inputs_stacked)
        ys = _unwrap(labels_stacked)
        return self._dispatch_steps(
            lambda pa, acc, bufs, lr, st, rng: self._scan_jitted(
                pa, acc, bufs, lr, st, xs, ys, rng),
            int(xs.shape[0]))

    def run_repeat(self, inputs, labels, steps):
        """Like run_scan but re-feeds ONE batch for `steps` steps inside
        a single XLA program — throughput benchmarking without holding
        `steps` copies of the data in HBM (a [steps, batch, ...] stack of
        224px images overflows a chip long before compute does)."""
        assert not self.with_outputs, \
            "run_repeat returns losses only; use with_outputs=False"
        self._check_plain("run_repeat")
        from paddle_tpu.framework.flags import debug_epoch

        xs = _unwrap(inputs)
        ys = _unwrap(labels)
        key = ("repeat", xs.shape, str(xs.dtype), debug_epoch())
        if getattr(self, "_repeat_key", None) != key:
            self.optimizer._ensure_state()
            base_step = self._make_step_fn()

            def repeat_all(param_arrays, accums, bufs, lr, step0, x, y, n,
                           rng):
                def body(carry, i):
                    params, accs, mb, st = carry
                    loss, nparams, naccs, nmb = base_step(
                        params, accs, mb, lr, st, (x,), y,
                        jax.random.fold_in(rng, st))
                    return (nparams, naccs, nmb, st + 1), loss

                (fp, fa, fb, _), losses = jax.lax.scan(
                    body, (param_arrays, accums, bufs, step0),
                    jnp.arange(n, dtype=jnp.int32))
                return losses, fp, fa, fb

            self._repeat_jitted = jax.jit(
                repeat_all, static_argnames="n",
                donate_argnums=introspect.TRAINSTEP_DONATE_ARGNUMS
                if self._donate else ())
            self._repeat_key = key
        losses = self._dispatch_steps(
            lambda pa, acc, bufs, lr, st, rng: self._repeat_jitted(
                pa, acc, bufs, lr, st, xs, ys, steps, rng),
            steps)
        return losses

    def _build_accum_fns(self):
        """Two programs for gradient merge (shared closures from
        make_accum_fns so the mesh edition can't drift)."""
        acc_fn, upd_fn = make_accum_fns(
            self.model, self.optimizer, self.loss_fn, self._params,
            self._acc_idx, self.accumulate_steps,
            with_scaler=self._with_scaler())
        donate = introspect.ACCUM_DONATE_ARGNUMS if self._donate else ()
        return (jax.jit(acc_fn, donate_argnums=donate),
                jax.jit(upd_fn,
                        donate_argnums=introspect.TRAINSTEP_DONATE_ARGNUMS
                        if self._donate else ()))

    def _call_accumulate(self, in_arrays, label_arr):
        from paddle_tpu.framework.flags import debug_epoch

        opt = self.optimizer
        key = (debug_epoch(), self._with_scaler())
        if getattr(self, "_acc_jitted", None) is None or \
                getattr(self, "_acc_epoch", None) != key:
            self._acc_jitted, self._upd_jitted = self._build_accum_fns()
            self._acc_epoch = key
        if self._grad_bufs is None:
            self._grad_bufs = [jnp.zeros(p._array.shape, jnp.float32)
                               for p in self._params]
        with_scaler = self._with_scaler()
        with RecordEvent(SPAN_GATHER):
            param_arrays = [p._array for p in self._params]
            bufs = self._buf_arrays()
            key = self._next_step_key()
            if with_scaler:
                scale = jnp.float32(self.scaler.get_scale())
                found = getattr(self, "_accum_found", None)
                if found is None:
                    found = jnp.bool_(False)
        with RecordEvent(SPAN_DISPATCH):
            if with_scaler:
                loss, self._grad_bufs, found, new_model_bufs = \
                    self._acc_jitted(
                        self._grad_bufs, found, param_arrays, bufs,
                        in_arrays, label_arr, key, scale)
                self._accum_found = found
            else:
                loss, self._grad_bufs, new_model_bufs = self._acc_jitted(
                    self._grad_bufs, param_arrays, bufs, in_arrays,
                    label_arr, key)
        with RecordEvent(SPAN_SCATTER):
            self._write_buffers(new_model_bufs)
        self._accum_count += 1
        if self._accum_count >= self.accumulate_steps:
            with RecordEvent(SPAN_GATHER):
                lr = jnp.asarray(opt.get_lr(), jnp.float32)
                stepc = jnp.asarray(opt._step_count, jnp.int32)
                param_arrays = [p._array for p in self._params]
                accums = self._gather_accums()
            with RecordEvent(SPAN_DISPATCH):
                if with_scaler:
                    new_params, new_accums, self._grad_bufs = \
                        self._upd_jitted(
                            param_arrays, accums, self._grad_bufs, lr,
                            stepc, scale, self._accum_found)
                else:
                    new_params, new_accums, self._grad_bufs = \
                        self._upd_jitted(
                            param_arrays, accums, self._grad_bufs, lr,
                            stepc)
            if with_scaler:
                skipped = bool(self._accum_found)
                self.scaler._found_inf = skipped
                self.scaler.update()
                self._accum_found = jnp.bool_(False)
            else:
                skipped = False
            with RecordEvent(SPAN_SCATTER):
                for p, a in zip(self._params, new_params):
                    p._in_place_update(a)
                self._scatter_accums(new_accums)
            if not skipped:
                opt._step_count += 1
            self._accum_count = 0
        return Tensor._wrap(loss)

    def _dispatch_steps(self, call, nsteps):
        """Shared multi-step dispatch + writeback tail (run_scan and
        run_repeat): gather live state, run, write params/accums back,
        advance the step counter."""
        opt = self.optimizer
        param_arrays = [p._array for p in self._params]
        accums = self._gather_accums()
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        stepc = jnp.asarray(opt._step_count, jnp.int32)
        losses, new_params, new_accums, new_bufs = call(
            param_arrays, accums, self._buf_arrays(), lr, stepc,
            self._next_step_key())
        for p, a in zip(self._params, new_params):
            p._in_place_update(a)
        self._scatter_accums(new_accums)
        self._write_buffers(new_bufs)
        opt._step_count += nsteps
        return Tensor._wrap(losses)

    def _build_scan(self):
        assert not self.with_outputs, \
            "run_scan returns losses only; use with_outputs=False"
        self._check_plain("run_scan")
        base_step = self._make_step_fn()

        def scan_all(param_arrays, accums, bufs, lr, step0, xs, ys, rng):
            def body(carry, xy):
                params, accs, mb, st = carry
                x, y = xy
                loss, nparams, naccs, nmb = base_step(
                    params, accs, mb, lr, st, (x,), y,
                    jax.random.fold_in(rng, st))
                return (nparams, naccs, nmb, st + 1), loss

            (fparams, faccums, fbufs, _), losses = jax.lax.scan(
                body, (param_arrays, accums, bufs, step0), (xs, ys))
            return losses, fparams, faccums, fbufs

        donate = introspect.TRAINSTEP_DONATE_ARGNUMS if self._donate \
            else ()
        return jax.jit(scan_all, donate_argnums=donate)

    def __call__(self, *inputs, label=None):
        if self.telemetry is None:
            return self._call_inner(*inputs, label=label)
        import time

        # gradient merge: the K micro-batch calls of one optimizer step
        # record ONE observation, timed cycle-start to K-th-call-loss
        # with a single block — mid-cycle calls stay async so telemetry
        # doesn't serialize the dispatch pipeline
        if getattr(self, "_tel_t0", None) is None:
            self._tel_t0 = time.perf_counter()
        try:
            out = self._call_inner(*inputs, label=label)
        except BaseException:
            # a failed micro-batch must not leave the cycle timer armed
            # — the next successful cycle would observe failure + idle
            # time as one giant step. If earlier micro-batches of this
            # cycle already ran, the cycle completes with a PARTIAL
            # re-armed timer: taint it so no skewed observation lands.
            self._tel_t0 = None
            if self.accumulate_steps > 1 and self._accum_count != 0:
                self._tel_taint = True
            raise
        if self.accumulate_steps > 1 and self._accum_count != 0:
            return out                     # mid-cycle micro-batch
        if getattr(self, "_tel_taint", False):
            self._tel_taint = False        # tainted cycle: no sample
            self._tel_t0 = None
            return out
        loss_t = out[0] if isinstance(out, tuple) else out
        jax.block_until_ready(loss_t._array)
        dt = time.perf_counter() - self._tel_t0
        self._tel_t0 = None
        loss_val = float(loss_t._array) \
            if getattr(loss_t._array, "size", 0) == 1 else None
        self.telemetry.observe_step(dt, loss=loss_val)
        return out

    def _call_inner(self, *inputs, label=None):
        with RecordEvent(SPAN_STEP):
            return self._step(*inputs, label=label)

    def _step(self, *inputs, label=None):
        if label is None and len(inputs) >= 2:
            *inputs, label = inputs
            inputs = tuple(inputs)
        from paddle_tpu.framework.flags import debug_epoch

        build_key = (debug_epoch(), self._with_scaler())
        if self._jitted is None or \
                getattr(self, "_build_key", None) != build_key:
            self.optimizer._ensure_state()
            self._jitted = self._build()
            self._scan_jitted = None
            self._build_key = build_key
        opt = self.optimizer
        in_arrays = tuple(_unwrap(i) for i in inputs)
        label_arr = _unwrap(label) if label is not None else None
        if self.accumulate_steps > 1:
            return self._call_accumulate(in_arrays, label_arr)
        with RecordEvent(SPAN_GATHER):
            param_arrays = [p._array for p in self._params]
            accums = self._gather_accums()
            bufs = self._buf_arrays()
            lr = jnp.asarray(opt.get_lr(), jnp.float32)
            stepc = jnp.asarray(opt._step_count, jnp.int32)
            key = self._next_step_key()
            scale = jnp.float32(self.scaler.get_scale()) \
                if self._with_scaler() else None
        with RecordEvent(SPAN_DISPATCH):
            if scale is not None:
                loss, found_inf, new_params, new_accums, new_bufs = \
                    self._jitted(
                        param_arrays, accums, bufs, lr, stepc, in_arrays,
                        label_arr, key, scale)
            else:
                loss, new_params, new_accums, new_bufs = self._jitted(
                    param_arrays, accums, bufs, lr, stepc, in_arrays,
                    label_arr, key)
        if scale is not None:
            skipped = bool(found_inf)
            self.scaler._found_inf = skipped
            self.scaler.update()
        else:
            skipped = False
        with RecordEvent(SPAN_SCATTER):
            for p, a in zip(self._params, new_params):
                p._in_place_update(a)
            self._scatter_accums(new_accums)
            self._write_buffers(new_bufs)
        if not skipped:
            # a scaler-skipped step doesn't count (GradScaler.step skips
            # optimizer.step entirely — bias-correction t must match the
            # number of REAL updates the moments saw)
            opt._step_count += 1
        if self.with_outputs:
            loss, out = loss
            return Tensor._wrap(loss), jax.tree_util.tree_map(Tensor._wrap, out)
        return Tensor._wrap(loss)
