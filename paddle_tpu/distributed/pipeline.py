"""Pipeline parallelism — analog of
python/paddle/distributed/fleet/meta_parallel/pp_layers.py (LayerDesc :57,
SharedLayerDesc :77, SegmentLayers :93, PipelineLayer :209) and the 1F1B
schedule in meta_parallel/pipeline_parallel.py:117.

TPU-native re-design (NOT a port of the NCCL p2p machinery): the pipeline
is a *single SPMD program*. The uniform transformer body is held as
stage-stacked parameters [S, k, ...] sharded over the 'pp' mesh axis;
each schedule tick runs `vmap(stage_fn)` — every stage computes its
resident microbatch in parallel, placed on its own pp slice because the
stacked params/activations are sharded on the stage dim — then the
activation register shifts one stage forward via `jnp.roll` on the
sharded dim, which XLA lowers to a CollectivePermute on ICI (the
p2p_communication.py send/recv analog). The whole schedule (warmup,
steady state, drain: M + S - 1 ticks for M microbatches) lives inside
one jit/lax.scan, so backward is generated by jax AD with per-tick
rematerialization — memory behavior comparable to 1F1B without a
host-driven scheduler.

Non-uniform layers before/after the body (embedding, final LN, LM head)
run as plain SPMD compute over the full batch — on TPU there is no reason
to serialize them onto one stage. Tied weights (SharedLayerDesc) fall out
naturally: pre/post layers share Parameter objects directly.

Non-uniform segmentation (seg_method='parameters' or 'layer:Cls' with
unequal boundaries) pads every stage to the deepest one and masks padded
slots to identity, keeping the register rectangular.

Interleaved virtual stages (the reference's
PipelineParallelWithInterleave, pipeline_parallel.py:461) are
deliberately NOT replicated: in a host-driven 1F1B schedule interleave
shrinks the warmup/drain bubble by the chunk count v, but in this
synchronous single-program register every stage advances one hop per
tick, so splitting stages into v chunks multiplies the tick count by v
while dividing per-tick work by v — the bubble work (S-1 ticks' worth)
is unchanged. The bubble here is instead amortized by raising
num_microbatches (bubble fraction = (S-1)/(M+S-1)) and by jax AD
rematerializing per tick (memory ~ 1F1B's).
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import paddle_tpu.nn as nn
from paddle_tpu.core.tensor import Parameter, Tensor

from .topology import get_hybrid_communicate_group

__all__ = ["LayerDesc", "SharedLayerDesc", "SegmentLayers",
           "PipelineLayer", "PipelineStack"]


class LayerDesc:
    """Lazy layer description (pp_layers.py:57): the layer is constructed
    by the pipeline, not the user, so parameters can be materialized in
    the stage-stacked layout."""

    def __init__(self, layer_func, *inputs, **kwargs):
        if not issubclass(layer_func, nn.Layer):
            raise TypeError("LayerDesc expects an nn.Layer subclass")
        self.layer_func = layer_func
        self.inputs = inputs
        self.kwargs = kwargs

    def build_layer(self):
        return self.layer_func(*self.inputs, **self.kwargs)

    build = build_layer

    def spec_key(self):
        return (self.layer_func, repr(self.inputs), repr(sorted(self.kwargs.items())))

    def __repr__(self):
        return f"LayerDesc({self.layer_func.__name__})"


class SharedLayerDesc(LayerDesc):
    """Tied-weight layer (pp_layers.py:77): occurrences with the same
    `key` share one underlying layer instance; `forward_func(layer, x)`
    customizes the call (e.g. the logits projection reusing the embedding
    table)."""

    def __init__(self, key, layer_func, forward_func=None,
                 shared_weight_attr="weight", *inputs, **kwargs):
        super().__init__(layer_func, *inputs, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        # accepted for reference-API parity only: here sharing is
        # whole-instance (ONE layer object serves every occurrence), which
        # is exact tying — there is no cross-stage grad sync to scope to a
        # single weight, so this attribute has no effect
        self.shared_weight_attr = shared_weight_attr


class SegmentLayers:
    """Partition `num_items` layers into `num_parts` stages
    (pp_layers.py:93). 'uniform' balances counts; 'layer:ClassName'
    starts a new segment at each occurrence of that class."""

    def __init__(self, layers_desc, num_parts, method="uniform"):
        self.descs = layers_desc
        self.num_items = len(layers_desc)
        self.num_parts = num_parts
        self.method = method
        assert self.num_items >= self.num_parts, (
            "layer number should be greater than number of segments")

    def do_segment(self):
        if self.method == "uniform":
            return self.uniform(self.num_items, self.num_parts)
        if self.method == "parameters":
            # param-weighted balance (pp_layers.py segment_parts-by-size):
            # greedy prefix split minimizing per-part weight imbalance.
            # Identical descs (same spec_key) are sized once — building a
            # throwaway instance per body layer would double init cost.
            cache = {}
            weights = []
            for d in self.descs:
                key = d.spec_key() if isinstance(d, LayerDesc) else id(d)
                if key not in cache:
                    cache[key] = self._param_count(d)
                weights.append(cache[key])
            return self.weighted(weights, self.num_parts)
        if self.method.startswith("layer:"):
            cls_name = self.method.split(":", 1)[1]
            hits = [i for i, d in enumerate(self.descs)
                    if (d.layer_func.__name__ if isinstance(d, LayerDesc)
                        else type(d).__name__) == cls_name]
            assert len(hits) >= self.num_parts
            # balance the hit layers over parts; prefix goes to part 0
            per = self.uniform(len(hits), self.num_parts)
            bounds = [0]
            for p in range(1, self.num_parts):
                bounds.append(hits[per[p]])
            bounds.append(self.num_items)
            return bounds
        raise ValueError(f"unknown seg method {self.method}")

    @staticmethod
    def _param_count(d):
        if isinstance(d, LayerDesc):
            # build a throwaway instance to size it (descs are lazy)
            inst = d.build_layer()
            return sum(int(np.prod(p._array.shape))
                       for p in inst.parameters()) or 1
        if hasattr(d, "parameters"):
            return sum(int(np.prod(p._array.shape))
                       for p in d.parameters()) or 1
        return 1

    @staticmethod
    def weighted(weights, num_parts):
        """Contiguous split of `weights` into `num_parts` minimizing the
        max part weight (greedy threshold sweep, exact enough for layer
        counts in the hundreds)."""
        total = float(sum(weights))
        target = total / num_parts
        bounds, acc, part = [0], 0.0, 0
        for i, w in enumerate(weights):
            remaining_items = len(weights) - i
            remaining_parts = num_parts - part
            # must leave at least one item per remaining part
            if acc >= target and remaining_items >= remaining_parts \
                    and part < num_parts - 1:
                bounds.append(i)
                part += 1
                acc = 0.0
            acc += w
        while len(bounds) < num_parts:
            bounds.append(max(bounds[-1] + 1,
                              len(weights) - (num_parts - len(bounds))))
        bounds.append(len(weights))
        return bounds

    @staticmethod
    def uniform(num_items, num_parts):
        result = [0] * (num_parts + 1)
        part_size = num_items // num_parts
        extra = num_items % num_parts
        for i in range(1, num_parts + 1):
            result[i] = result[i - 1] + part_size + (1 if i <= extra else 0)
        return result


def _merge_spec(base_spec, extra_leading):
    """Prepend pipeline dims to a template param's PartitionSpec."""
    tail = tuple(base_spec) if base_spec is not None else ()
    return P(*extra_leading, *tail)


class PipelineStack(nn.Layer):
    """The uniform pipelined body: L = S*k identical-spec layers whose
    parameters live stage-stacked as [S, k, *shape] with dist_spec
    P('pp', None, ...). Sequential (eager / pp=1) and pipelined (SPMD
    shift-register) execution paths produce identical math."""

    def __init__(self, desc: LayerDesc, total_layers: int, num_stages: int,
                 recompute_interval: int = 0,
                 stage_counts: Optional[Sequence[int]] = None):
        super().__init__()
        if stage_counts is None:
            assert total_layers % num_stages == 0, (
                f"{total_layers} body layers not divisible by "
                f"{num_stages} stages (pass stage_counts for non-uniform)")
            stage_counts = [total_layers // num_stages] * num_stages
        assert len(stage_counts) == num_stages and \
            sum(stage_counts) == total_layers, (stage_counts, total_layers)
        self.num_stages = num_stages
        self.stage_counts = list(int(c) for c in stage_counts)
        # non-uniform stages are realized by padding every stage to the
        # deepest one and masking the padded slots to identity — the SPMD
        # register stays rectangular ([S, k_max, ...]) so vmap over
        # stages and the pp sharding are unchanged
        self.per_stage = max(self.stage_counts)
        self.uniform = all(c == self.per_stage for c in self.stage_counts)
        self.total_layers = total_layers
        self.recompute_interval = recompute_interval
        # the template holds the layer CODE; its own params are only the
        # slot-0 initial values. Bypass Layer registration so they never
        # appear in parameters()/state_dict — the stacked params below are
        # the real trainables.
        object.__setattr__(self, "template", desc.build_layer())
        object.__setattr__(self, "_tpl_params",
                           list(self.template.parameters()))

        # materialize L instances to harvest per-slot inits, then stack;
        # padded slots reuse the template init (they receive no gradient:
        # their output is masked to identity)
        insts = [self.template] + [desc.build_layer()
                                   for _ in range(total_layers - 1)]
        slot_map = []  # (stage, slot) -> instance index or None (pad)
        gi = 0
        for s, c in enumerate(self.stage_counts):
            for j in range(self.per_stage):
                slot_map.append(gi if j < c else None)
                if j < c:
                    gi += 1
        self._stacked = []
        for i, tp in enumerate(self._tpl_params):
            slot_arrays = []
            for idx in slot_map:
                inst = insts[idx if idx is not None else 0]
                slot_arrays.append(np.asarray(list(inst.parameters())[i]._array))
            stk = np.stack(slot_arrays).reshape(
                num_stages, self.per_stage, *tp._array.shape)
            sp = Parameter(stk, dtype=tp.dtype)
            sp.dist_spec = _merge_spec(tp.dist_spec, ("pp", None))
            setattr(self, f"stacked_{i}", sp)
            self._stacked.append(sp)

    def train(self):
        # the template bypasses _sub_layers registration, so propagate
        # train/eval mode to it explicitly (body dropout must honor eval)
        super().train()
        self.template.train()
        return self

    def eval(self):
        super().eval()
        self.template.eval()
        return self

    def _slot_apply(self, slot_arrays, x_array):
        """Run the template layer with one slot's parameter arrays bound."""
        originals = [p._array for p in self._tpl_params]
        try:
            for p, a in zip(self._tpl_params, slot_arrays):
                p._array = a
            out = self.template(Tensor._wrap(x_array))
            return out._array if isinstance(out, Tensor) else out
        finally:
            for p, o in zip(self._tpl_params, originals):
                p._array = o

    def _stage_fn(self, stage_param_arrays, x_array, stage_idx=None,
                  valid_mask=None):
        """Apply this stage's k layers sequentially: lax.scan over the
        slot dim of the per-stage param slices [k, ...]. Each slot folds
        its index (and the stage index, when vmapped) into the PRNG so
        dropout masks differ per layer (ADVICE r2 low #4). `valid_mask`
        ([k] bool) turns padded slots of non-uniform stages into
        identity (their params get zero gradient)."""
        from paddle_tpu.core import random as prandom

        masked = valid_mask is not None

        def slot_body(h, xs):
            slot_idx = xs[0]
            if masked:
                vm, slot_arrays = xs[1], list(xs[2:])
            else:
                slot_arrays = list(xs[1:])
            with prandom.fold_key(slot_idx):
                out = self._slot_apply(slot_arrays, h)
            if masked:
                out = jnp.where(vm, out, h)
            return out, None

        if self.recompute_interval:
            slot_body = jax.checkpoint(slot_body)
        k = (stage_param_arrays[0].shape[0] if stage_param_arrays
             else (self.per_stage if stage_idx is not None
                   else self.total_layers))
        xs = (jnp.arange(k),) + \
            ((valid_mask,) if masked else ()) + tuple(stage_param_arrays)
        import contextlib
        ctx = (prandom.fold_key(stage_idx) if stage_idx is not None
               else contextlib.nullcontext())
        with ctx:
            h, _ = jax.lax.scan(slot_body, x_array, xs)
        return h

    def forward(self, x, num_microbatches: Optional[int] = None,
                pipelined: Optional[bool] = None):
        from paddle_tpu.core import random as prandom
        from paddle_tpu.ops import dispatch
        import contextlib

        x_t = x if isinstance(x, Tensor) else Tensor(x)
        if pipelined is None:
            try:
                pipelined = get_hybrid_communicate_group().axis_size("pp") > 1
            except Exception:
                pipelined = False
        # one base key drawn OUTSIDE the op fn so forward and any vjp
        # replay of it see identical dropout masks
        base_key = prandom.next_key() if self.training else None

        def stack_fn(x_arr, *stacked):
            scope = (prandom.key_scope(base_key) if base_key is not None
                     else contextlib.nullcontext())
            with scope:
                if not pipelined:
                    # flatten [S, k_max, ...] -> [S*k_max, ...] and scan
                    # all slots (masking pads of non-uniform stages)
                    nslots = self.num_stages * self.per_stage
                    flat = [a.reshape(nslots, *a.shape[2:])
                            for a in stacked]
                    return self._stage_fn(flat, x_arr,
                                          valid_mask=self._valid_mask())
                return self._pipelined_fn(x_arr, stacked, num_microbatches)

        # recorded as ONE tape op over (x, stacked params) so eager
        # loss.backward() through PipelineLayer reaches the stacked body
        # and upstream layers (ADVICE r2 medium #2)
        return dispatch.apply("pipeline_stack", stack_fn, x_t, *self._stacked)

    def _valid_mask(self):
        """[S*k_max] slot-validity mask; None when stages are uniform."""
        if self.uniform:
            return None
        return jnp.asarray(np.concatenate(
            [np.arange(self.per_stage) < c for c in self.stage_counts]))

    def _pipelined_fn(self, x_arr, stacked, num_microbatches):
        from paddle_tpu.core import random as prandom

        S = self.num_stages
        M = num_microbatches or S
        B = x_arr.shape[0]
        assert B % M == 0, f"batch {B} not divisible by {M} microbatches"
        mb = B // M
        x_mb = x_arr.reshape(M, mb, *x_arr.shape[1:])

        # the stage dim IS the 'pp' mesh axis: saying so lets a
        # shard_map inside a block (the per-shard attention kernels)
        # keep each stage on its own devices instead of gathering it
        if self.uniform:
            vm_stage = jax.vmap(self._stage_fn, in_axes=(0, 0, 0),
                                spmd_axis_name="pp")
        else:
            stage_masks = jnp.asarray(np.stack(
                [np.arange(self.per_stage) < c for c in self.stage_counts]))
            vm_stage_m = jax.vmap(self._stage_fn, in_axes=(0, 0, 0, 0),
                                  spmd_axis_name="pp")
            vm_stage = lambda st, x, ids: vm_stage_m(st, x, ids, stage_masks)
        stage_ids = jnp.arange(S)

        def tick_body(carry, t):
            state, outs = carry
            # inject the next microbatch into stage 0 (warmup beyond M
            # re-injects a clamped index; its output is never collected)
            inj = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            state = state.at[0].set(inj)
            with prandom.fold_key(t):
                y = vm_stage(stacked, state, stage_ids)  # [S, mb, ...]
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            outs = jnp.where(
                t >= S - 1,
                jax.lax.dynamic_update_index_in_dim(outs, y[-1], out_idx, 0),
                outs)
            # shift register: stage s+1 receives y[s] — XLA lowers the
            # roll on the pp-sharded dim to CollectivePermute on ICI
            state = jnp.roll(y, 1, axis=0)
            return (state, outs), None

        if self.recompute_interval:
            tick_body = jax.checkpoint(
                tick_body, policy=jax.checkpoint_policies.nothing_saveable)

        state0 = jnp.zeros((S, mb) + x_arr.shape[1:], x_arr.dtype)
        outs0 = jnp.zeros((M, mb) + x_arr.shape[1:], x_arr.dtype)
        (_, outs), _ = jax.lax.scan(
            tick_body, (state0, outs0), jnp.arange(M + S - 1))
        return outs.reshape(B, *x_arr.shape[1:])


class PipelineLayer(nn.Layer):
    """Analog of PipelineLayer (pp_layers.py:209): takes a LayerDesc list,
    segments the longest uniform run into `num_stages` pipeline stages
    (stage-stacked SPMD body), and runs the non-uniform prefix/suffix as
    plain SPMD compute. SharedLayerDesc occurrences with the same key
    share one layer instance (tied weights)."""

    def __init__(self, layers: Sequence, num_stages: Optional[int] = None,
                 topology=None, loss_fn: Optional[Callable] = None,
                 seg_method: str = "uniform", recompute_interval: int = 0,
                 num_microbatches: Optional[int] = None):
        super().__init__()
        if num_stages is None:
            if topology is not None:
                num_stages = topology.get_dim("pipe")
            else:
                try:
                    num_stages = get_hybrid_communicate_group().axis_size("pp")
                except Exception:
                    num_stages = 1
        self.num_stages = int(num_stages)
        self.loss_fn = loss_fn
        self.num_microbatches = num_microbatches
        self._descs = list(layers)

        # longest contiguous run of identical-spec plain LayerDescs = body
        keys = [d.spec_key() if isinstance(d, LayerDesc)
                and not isinstance(d, SharedLayerDesc) else None
                for d in self._descs]
        best_start, best_len = 0, 0
        i = 0
        while i < len(keys):
            if keys[i] is None:
                i += 1
                continue
            j = i
            while j < len(keys) and keys[j] == keys[i]:
                j += 1
            if j - i > best_len:
                best_start, best_len = i, j - i
            i = j
        if best_len < self.num_stages:
            raise ValueError(
                f"need >= {self.num_stages} identical body layers for "
                f"{self.num_stages} stages; longest uniform run is {best_len}")
        usable = best_len
        body_start = best_start
        body_end = best_start + best_len

        self._shared_instances = {}

        def build(d):
            if isinstance(d, SharedLayerDesc):
                if d.layer_name not in self._shared_instances:
                    self._shared_instances[d.layer_name] = d.build_layer()
                return (self._shared_instances[d.layer_name], d.forward_func)
            if isinstance(d, LayerDesc):
                return (d.build_layer(), None)
            return (d, None)  # already-built Layer

        self._pre = [build(d) for d in self._descs[:body_start]]
        self._post = [build(d) for d in self._descs[body_end:]]
        # register each layer instance exactly once (SharedLayerDesc may
        # reuse one instance in pre and post — tied weights must not show
        # up twice in parameters())
        seen = set()

        def uniq(pairs):
            out = []
            for l, _ in pairs:
                if id(l) not in seen:
                    seen.add(id(l))
                    out.append(l)
            return out

        self.pre_layers = nn.LayerList(uniq(self._pre))
        self.post_layers = nn.LayerList(uniq(self._post))
        self.segment_parts = SegmentLayers(
            self._descs[body_start:body_end], self.num_stages,
            seg_method).do_segment()
        stage_counts = [self.segment_parts[i + 1] - self.segment_parts[i]
                        for i in range(self.num_stages)]
        self.stack = PipelineStack(self._descs[body_start], usable,
                                   self.num_stages, recompute_interval,
                                   stage_counts=stage_counts)

    def forward(self, x, num_microbatches: Optional[int] = None):
        for layer, ffn in self._pre:
            x = ffn(layer, x) if ffn is not None else layer(x)
        x = self.stack(x, num_microbatches or self.num_microbatches)
        for layer, ffn in self._post:
            x = ffn(layer, x) if ffn is not None else layer(x)
        return x
