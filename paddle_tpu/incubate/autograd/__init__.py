"""paddle.incubate.autograd analog — functional differentiation over
jax's transform machinery.

Reference surface (python/paddle/incubate/autograd/functional.py):
``vjp`` (:22), ``jvp`` (:80), ``Jacobian`` (:171, lazy row-indexed),
``Hessian`` (:260) and ``primapi.forward_grad`` (primapi.py:25).

The reference implements these by replaying the eager tape (``_grad``
over ``paddle.grad``) or, for forward mode, by rewriting a static
program into primitive ops. On this stack all five are direct
applications of jax's functional transforms: ``jax.vjp`` / ``jax.jvp``
give the products, and the Jacobian/Hessian classes keep the
reference's lazy row-cached indexing contract on top of the vjp
pullback (rows) and jvp pushforward (single columns) instead of
materialising the full matrix eagerly.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor

__all__ = ["vjp", "jvp", "Jacobian", "Hessian", "forward_grad"]


def _as_tensor_tuple(xs):
    """Normalize the paddle-style ``Tensor | Sequence[Tensor]`` input
    contract; returns (tuple_of_tensors, was_sequence)."""
    if isinstance(xs, (tuple, list)):
        ts = tuple(x if isinstance(x, Tensor) else Tensor(x) for x in xs)
        return ts, True
    return (xs if isinstance(xs, Tensor) else Tensor(xs),), False


def _arrays(ts):
    return tuple(t._array for t in ts)


def _wrap_func(func, xs_is_seq):
    """Lift a Tensor->Tensor user function to arrays->arrays for jax.
    ``meta`` records whether the traced output was a sequence so results
    unwrap with the same structure the user returned."""
    meta = {}

    def jf(*arrays):
        args = [Tensor._wrap(a, stop_gradient=False) for a in arrays]
        out = func(*args) if xs_is_seq else func(args[0])
        multi = isinstance(out, (tuple, list))
        meta["multi"] = multi
        outs = tuple(out) if multi else (out,)
        return tuple(o._array if isinstance(o, Tensor) else jnp.asarray(o)
                     for o in outs)

    return jf, meta


def _pack(arrays, multi):
    ts = tuple(Tensor._wrap(a, stop_gradient=False) for a in arrays)
    return ts if multi else ts[0]


def _check_v(v, refs, kind):
    """The reference's _check_v_shape: v must match ``refs`` pairwise in
    length and shape (dtype needs no check here — Tensor construction
    canonicalizes it, and jvp re-casts tangents to the primal dtype)."""
    vs, _ = _as_tensor_tuple(v)
    if len(vs) != len(refs):
        raise RuntimeError(
            f"The length of {kind} v ({len(vs)}) does not match the "
            f"number of tensors it pairs with ({len(refs)})")
    for vi, ri in zip(vs, refs):
        if tuple(vi._array.shape) != tuple(ri.shape):
            raise RuntimeError(
                f"The v[{kind}] shape {tuple(vi._array.shape)} does not "
                f"match the paired tensor shape {tuple(ri.shape)}")
    return _arrays(vs)


def vjp(func, xs, v=None):
    """Vector-Jacobian product (reverse mode), reference
    functional.py:22. Returns ``(func_out, vjp_result)``; ``v`` defaults
    to all-ones matching ``func``'s outputs."""
    ts, is_seq = _as_tensor_tuple(xs)
    jf, meta = _wrap_func(func, is_seq)
    ys, pullback = jax.vjp(jf, *_arrays(ts))
    if v is None:
        cots = tuple(jnp.ones_like(y) for y in ys)
    else:
        cots = _check_v(v, ys, "output")
    grads = pullback(cots)
    return (_pack(ys, meta["multi"]),
            _pack(grads, is_seq))


def jvp(func, xs, v=None):
    """Jacobian-vector product (forward mode), reference
    functional.py:80. Returns ``(func_out, jvp_result)``; ``v`` defaults
    to all-ones matching ``xs``."""
    ts, is_seq = _as_tensor_tuple(xs)
    arrays = _arrays(ts)
    jf, meta = _wrap_func(func, is_seq)
    if v is None:
        tangents = tuple(jnp.ones_like(a) for a in arrays)
    else:
        tangents = _check_v(v, arrays, "input")
        tangents = tuple(jnp.asarray(t, a.dtype)
                         for t, a in zip(tangents, arrays))
    ys, dys = jax.jvp(jf, arrays, tangents)
    return (_pack(ys, meta["multi"]), _pack(dys, meta["multi"]))


class _FlatFunc:
    """func over the reference's flattened calling convention: all
    inputs flattened (batch axis kept when batched) and concatenated to
    one [N] / [B, N] array; outputs likewise to [M] / [B, M]."""

    def __init__(self, func, xs, is_batched):
        ts, self.is_seq = _as_tensor_tuple(xs)
        self.arrays = _arrays(ts)
        self.is_batched = bool(is_batched)
        if self.is_batched:
            b = self.arrays[0].shape[0]
            for a in self.arrays:
                if a.shape[0] != b:
                    raise ValueError(
                        "is_batched=True requires every input to share "
                        f"the leading batch axis; got {a.shape[0]} vs {b}")
            self.batch = b
            self.in_shapes = [a.shape[1:] for a in self.arrays]
            self.in_sizes = [max(1, math.prod(s)) for s in self.in_shapes]
            self.flat_x = jnp.concatenate(
                [a.reshape(self.batch, -1) for a in self.arrays], axis=-1)
        else:
            self.batch = None
            self.in_shapes = [a.shape for a in self.arrays]
            self.in_sizes = [int(a.size) for a in self.arrays]
            self.flat_x = jnp.concatenate(
                [a.reshape(-1) for a in self.arrays])
        self.func = func

    def __call__(self, flat_x):
        parts = []
        off = 0
        for shape, size in zip(self.in_shapes, self.in_sizes):
            sl = flat_x[..., off:off + size]
            full = (sl.reshape((self.batch,) + tuple(shape))
                    if self.is_batched else sl.reshape(shape))
            parts.append(full)
            off += size
        jf, _ = _wrap_func(self.func, self.is_seq)
        outs = jf(*parts)
        if self.is_batched:
            return jnp.concatenate(
                [o.reshape(self.batch, -1) for o in outs], axis=-1)
        return jnp.concatenate([o.reshape(-1) for o in outs])


class Jacobian:
    """Lazily indexed Jacobian matrix, reference functional.py:171.

    Shape is ``[M, N]`` (or ``[B, M, N]`` with ``is_batched=True``)
    over flatten-and-concatenated outputs/inputs. Rows are evaluated on
    demand through the cached vjp pullback and memoized; a single-column
    request without rows uses one jvp pushforward instead of M
    pullbacks. ``J[...]`` supports int/slice indexes per axis.
    """

    def __init__(self, func, xs, is_batched=False):
        self._f = _FlatFunc(func, xs, is_batched)
        ys, self._pullback = jax.vjp(self._f, self._f.flat_x)
        self._ys = ys
        self._rows: dict = {}
        self._cols: dict = {}
        if is_batched:
            self._B, self._M = ys.shape
            self._N = self._f.flat_x.shape[-1]
        else:
            self._M = int(ys.shape[0])
            self._N = int(self._f.flat_x.shape[-1])

    @property
    def shape(self):
        if self._f.is_batched:
            return (self._B, self._M, self._N)
        return (self._M, self._N)

    # -- evaluation --------------------------------------------------------
    def _row(self, i):
        """d flat_y[(:,) i] / d flat_x — shape [N] or [B, N]."""
        if i not in self._rows:
            if self._f.is_batched:
                cot = jnp.zeros((self._B, self._M),
                                self._ys.dtype).at[:, i].set(1.0)
            else:
                cot = jnp.zeros((self._M,), self._ys.dtype).at[i].set(1.0)
            self._rows[i] = self._pullback(cot)[0]
        return self._rows[i]

    def _col(self, j):
        """d flat_y / d flat_x[(:,) j] via ONE forward-mode pass
        (memoized, like rows)."""
        if j not in self._cols:
            if self._f.is_batched:
                tan = jnp.zeros((self._B, self._N),
                                self._f.flat_x.dtype).at[:, j].set(1.0)
            else:
                tan = jnp.zeros((self._N,),
                                self._f.flat_x.dtype).at[j].set(1.0)
            _, dy = jax.jvp(self._f, (self._f.flat_x,), (tan,))
            self._cols[j] = dy
        return self._cols[j]

    def _fill_rows(self, wanted):
        """Evaluate every uncached row in ``wanted`` with ONE vmapped
        pullback call instead of M separate dispatches."""
        missing = [i for i in wanted if i not in self._rows]
        if not missing:
            return
        eye = jnp.eye(self._M, dtype=self._ys.dtype)[jnp.array(missing)]
        if self._f.is_batched:
            cots = jnp.broadcast_to(
                eye[:, None, :], (len(missing), self._B, self._M))
        else:
            cots = eye
        rows = jax.vmap(lambda c: self._pullback(c)[0])(cots)
        for k, i in enumerate(missing):
            self._rows[i] = rows[k]

    # -- indexing ----------------------------------------------------------
    def __getitem__(self, indexes):
        idx = indexes if isinstance(indexes, tuple) else (indexes,)
        if self._f.is_batched:
            if len(idx) > 3:
                raise IndexError(
                    f"too many indexes for a batched Jacobian: {indexes}")
            bidx = idx[0] if len(idx) >= 1 else slice(None)
            ridx = idx[1] if len(idx) >= 2 else slice(None)
            cidx = idx[2] if len(idx) >= 3 else slice(None)
        else:
            if len(idx) > 2:
                raise IndexError(
                    f"too many indexes for a Jacobian: {indexes}")
            bidx = None
            ridx = idx[0] if len(idx) >= 1 else slice(None)
            cidx = idx[1] if len(idx) >= 2 else slice(None)

        full_rows = isinstance(ridx, slice) and ridx == slice(None)
        if (full_rows and isinstance(cidx, int)
                and len(self._rows) < self._M):
            # column fast path: one jvp instead of materializing the
            # uncached rows (taken whenever the row cache can't already
            # serve the column)
            out = self._col(range(self._N)[cidx])  # [N-normalized j]
        else:
            if isinstance(ridx, int):
                ridx = range(self._M)[ridx]  # normalize negatives
                out = self._row(ridx)
            else:
                wanted = list(range(self._M)[ridx])
                self._fill_rows(wanted)
                out = jnp.stack([self._rows[i] for i in wanted],
                                axis=1 if self._f.is_batched else 0)
            out = out[..., cidx]
        if bidx is not None:
            out = out[bidx]
        return Tensor._wrap(out, stop_gradient=False)


class Hessian:
    """Hessian matrix of a scalar-valued ``func``, reference
    functional.py:260 — built exactly as the reference does: the
    Jacobian of the function's (single-row) Jacobian."""

    def __init__(self, func, xs, is_batched=False):
        def _jac_func(*inner):
            xs_in = list(inner) if len(inner) > 1 else inner[0]
            jac = Jacobian(func, xs_in, is_batched=is_batched)
            if (is_batched and jac.shape[1] != 1) or (
                    not is_batched and jac.shape[0] != 1):
                raise RuntimeError(
                    "The function given to Hessian should return a "
                    "single element Tensor or batched single element "
                    "Tensor")
            return jac[:, 0, :] if is_batched else jac[0, :]

        self.symbolic = Jacobian(_jac_func, xs, is_batched=is_batched)

    @property
    def shape(self):
        return self.symbolic.shape

    def __getitem__(self, indexes):
        return self.symbolic[indexes]


def forward_grad(outputs, inputs, grad_inputs=None):
    """Forward-mode differentiation, reference primapi.py:25.

    The reference API is static-graph only: it rewrites a program into
    primitive ops and threads tangents through. On this stack forward
    mode is native (``jax.jvp``), so the natural calling convention is
    functional — pass the FUNCTION as ``outputs``::

        dy = forward_grad(func, xs, v)   # == jvp(func, xs, v)[1]

    Passing already-evaluated eager tensors cannot work here (an eager
    Tensor does not carry a forward graph to re-trace), so that form
    raises with guidance instead of silently returning zeros.
    """
    if callable(outputs):
        return jvp(outputs, inputs, grad_inputs)[1]
    raise TypeError(
        "forward_grad on this backend takes the function itself: "
        "forward_grad(func, xs, v). The reference's "
        "(outputs, inputs) form requires a static primitive program "
        "(primapi.py:25); eager tensors carry no forward graph — "
        "wrap the computation in a function, or use "
        "paddle.incubate.autograd.jvp.")
