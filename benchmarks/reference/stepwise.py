"""Walks a reference model one segment at a time, so that float32 at the
published widths fits beside nothing else on one chip.

A model is a chain: `segments[0]` makes the first activation from the
batch, each later segment maps an activation to the next, and a head
turns the last activation into the loss (training) or the logits
(serving). Forward keeps the activation at every boundary; backward
takes one segment's `jax.vjp` at a time, recomputing inside it, hands
each leaf's gradient to AdamW the moment its last use is done, and never
holds the whole gradient. Weights come from the seed through
`lib.weights`, a segment at a time where only logits are wanted.

AdamW as the cells state it: parameters STORED in `param_dtype` (bf16 in
the chip cells; the update is computed in float32 and rounded back),
moments in float32, decoupled decay. Two steps need no moment arrays:
after one step m = (1-b1) g1 and v = (1-b2) g1^2, so the first
gradient is all the state there is.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from ..lib import norms, weights


@dataclasses.dataclass(frozen=True)
class Segment:
    fn: Callable            # fn(leaves_tuple, x_or_None, batch) -> x
    leaves: tuple


@dataclasses.dataclass
class Model:
    spec: list              # [(name, shape, init)]
    segments: list          # embed, then one per layer
    loss_head: Segment
    logits_head: Segment

    def index(self):
        return {s[0]: i for i, s in enumerate(self.spec)}


_FWD, _BWD = {}, {}


def _fwd(fn):
    if fn not in _FWD:
        _FWD[fn] = jax.jit(fn)
    return _FWD[fn]


def _bwd(fn):
    """(leaves, x, batch, g) -> (grads of leaves, grad of x)."""
    if fn not in _BWD:
        def bwd(p, x, batch, g):
            if x is None:
                _, vjp = jax.vjp(lambda p_: fn(p_, None, batch), p)
                return vjp(g)[0], None
            _, vjp = jax.vjp(lambda p_, x_: fn(p_, x_, batch), p, x)
            return vjp(g)

        _BWD[fn] = jax.jit(bwd)
    return _BWD[fn]


@jax.jit
def _adamw_first(p, g, lr, b1, b2, eps, wd):
    pf = p.astype(jnp.float32)
    m, v = (1 - b1) * g, (1 - b2) * jnp.square(g)
    mhat, vhat = m / (1 - b1), v / (1 - b2)
    new = pf - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * pf)
    return new.astype(p.dtype)


@jax.jit
def _adamw_second(p, g1, g2, lr, b1, b2, eps, wd):
    pf = p.astype(jnp.float32)
    m = b1 * (1 - b1) * g1 + (1 - b1) * g2
    v = b2 * (1 - b2) * jnp.square(g1) + (1 - b2) * jnp.square(g2)
    mhat, vhat = m / (1 - b1 ** 2), v / (1 - b2 ** 2)
    new = pf - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * pf)
    return new.astype(p.dtype)


def _loss_and_grads(model, params, batch, on_grad):
    """One forward and backward; `on_grad(name, g)` is called once per
    leaf, when every segment that uses it has contributed."""
    acts, x = [], None
    for seg in model.segments:
        acts.append(x)
        x = _fwd(seg.fn)(tuple(params[n] for n in seg.leaves), x, batch)
    head = model.loss_head
    chain = model.segments + [head]
    uses = collections.Counter(n for s in chain for n in s.leaves)
    pending = {}

    def hand_over(seg, grads):
        for n, g in zip(seg.leaves, grads):
            if n in pending:
                g = g + pending.pop(n)
            uses[n] -= 1
            if uses[n]:
                pending[n] = g
            else:
                on_grad(n, g)

    hp = tuple(params[n] for n in head.leaves)
    loss = _fwd(head.fn)(hp, x, batch)
    gp, gx = _bwd(head.fn)(hp, x, batch, jnp.ones((), jnp.float32))
    hand_over(head, gp)
    for seg, x_in in zip(reversed(model.segments), reversed(acts)):
        gp, gx = _bwd(seg.fn)(tuple(params[n] for n in seg.leaves),
                              x_in, batch, gx)
        hand_over(seg, gp)
    return float(loss)


def train_two_steps(model, seed, batches, opt, param_dtype,
                    steps=2, half_batch=False):
    """The first `steps` (1 or 2) training steps from the seed.

    -> `{"loss": [l1, l2], "grad_norm": {leaf: |g1|},
    "change_norm": {leaf: |p2 - p0|}, "grad_cols": {leaf: column norms
    of g1}}` (after `steps` steps); a fused leaf is measured by parts
    (`lib/norms.py`), named `leaf#j`.

    `half_batch` plants a fault into the reference put in the program's
    place: the second half of every batch left out, the mean taken over
    the rest. (A step that returns its state unchanged needs no run: its
    change reads 0 against the reference's, a gap of 1.)"""
    spec, index = model.spec, model.index()
    parts = norms.parts_of(spec)
    params = weights.make_all(seed, spec, param_dtype)
    hyper = [jnp.float32(opt[k]) for k in
             ("learning_rate", "beta1", "beta2", "epsilon",
              "weight_decay")]
    out = {"loss": [], "grad_norm": {}, "change_norm": {}, "grad_cols": {}}
    g1 = {}

    def p0(name):
        return weights.make_leaves(seed, spec, [index[name]],
                                   param_dtype)[0]

    def first(name, g):
        new = _adamw_first(params[name], g, *hyper)
        out["grad_norm"][name] = norms.part_norms(g, None, parts[name])
        out["grad_cols"][name] = norms.column_norms(g)
        if steps == 1:
            out["change_norm"][name] = norms.part_norms(
                new, params[name], parts[name])
        else:
            g1[name] = g
        params[name] = new

    def second(name, g):
        new = _adamw_second(params[name], g1.pop(name), g, *hyper)
        out["change_norm"][name] = norms.part_norms(new, p0(name),
                                                    parts[name])
        params[name] = new

    for k in range(steps):
        batch = batches[k]
        if half_batch:
            batch = {n: a[:max(1, a.shape[0] // 2)]
                     for n, a in batch.items()}
        batch = {n: jnp.asarray(a) for n, a in batch.items()}
        out["loss"].append(_loss_and_grads(
            model, params, batch, first if k == 0 else second))
    for key in ("grad_norm", "change_norm"):
        flat = {}
        for n, v in out[key].items():
            flat.update(norms.named(n, v))
        out[key] = flat
    return out


def logits_of(model, seed, input_ids, param_dtype):
    """[B, S] token ids -> float32 logits [B, S, V] (GPT) through the
    whole chain, the weights made a segment at a time from the seed."""
    index = model.index()
    batch = {"input_ids": jnp.asarray(input_ids)}

    def leaves(seg):
        return tuple(weights.make_leaves(
            seed, model.spec, [index[n] for n in seg.leaves],
            param_dtype))

    x = None
    for seg in model.segments:
        x = _fwd(seg.fn)(leaves(seg), x, batch)
    head = model.logits_head
    return _fwd(head.fn)(leaves(head), x, batch)
