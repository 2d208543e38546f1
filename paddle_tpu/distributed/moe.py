"""Mixture-of-Experts with expert parallelism — analog of
python/paddle/incubate/distributed/models/moe/moe_layer.py:260 (MoELayer)
with gates (gate/gshard_gate.py, switch_gate.py, naive_gate.py), capacity
limiting (utils.py limit_by_capacity) and the global_scatter/global_gather
all-to-all dispatch ops (operators/collective/global_scatter_op.cu.cc).

TPU-native design: token dispatch is dense one-hot einsum routing into a
[experts, capacity, d] buffer (the GShard/Switch formulation XLA loves —
static shapes, MXU-friendly), and the cross-device exchange over the 'ep'
axis is lax.all_to_all inside the SPMD program instead of NCCL alltoall
kernels. With ep degree 1 everything stays local and the layer is a dense
jax computation.

At hundreds of experts a `[T, E, C]` one-hot is the wrong shape and a
capacity drops tokens: the second half of this module (`sorted_dispatch`,
`expert_share`) is the DROPLESS form — assignments sorted by expert, each
expert's group padded to whole row tiles, one grouped matmul over the
experts HELD HERE (`ops/pallas/moe.py` on the chip). The layer is told
which experts it holds (`first_expert`, the leading axis of its weights)
and computes their part of the result; the router stays whole.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu.nn as nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.ops.dispatch import apply

from .topology import get_hybrid_communicate_group


def top2_gating(logits, capacity, second_policy_train="random", key=None):
    """GShard top-2 gating (gate/gshard_gate.py analog): returns
    combine_weights [T, E, C] and dispatch_mask [T, E, C] plus aux loss.
    Pure jax; T=tokens, E=experts, C=capacity."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    g1_idx = jnp.argmax(probs, axis=-1)  # [T]
    g1 = jnp.take_along_axis(probs, g1_idx[:, None], axis=-1)[:, 0]
    probs_wo1 = probs * (1 - jax.nn.one_hot(g1_idx, E))
    g2_idx = jnp.argmax(probs_wo1, axis=-1)
    g2 = jnp.take_along_axis(probs_wo1, g2_idx[:, None], axis=-1)[:, 0]

    # aux load-balance loss (GShard eq.4): mean_prob * fraction_routed
    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(g1_idx, E).mean(axis=0)
    aux_loss = jnp.sum(me * ce) * E

    # position within each expert queue via cumsum over one-hot
    mask1 = jax.nn.one_hot(g1_idx, E)
    pos1 = (jnp.cumsum(mask1, axis=0) - 1) * mask1  # [T,E]
    mask2 = jax.nn.one_hot(g2_idx, E)
    pos2 = (jnp.cumsum(mask2, axis=0) - 1 + mask1.sum(0)[None, :]) * mask2

    keep1 = (pos1 < capacity) & (mask1 > 0)
    keep2 = (pos2 < capacity) & (mask2 > 0)

    loc1 = pos1.sum(axis=-1).astype(jnp.int32)  # slot for primary expert
    loc2 = pos2.sum(axis=-1).astype(jnp.int32)

    denom = jnp.maximum(g1 + g2, 1e-9)
    w1 = g1 / denom
    w2 = g2 / denom

    cap_oh1 = jax.nn.one_hot(loc1, capacity) * keep1.max(-1, keepdims=True)
    cap_oh2 = jax.nn.one_hot(loc2, capacity) * keep2.max(-1, keepdims=True)
    combine = (w1[:, None, None] * mask1[:, :, None] * cap_oh1[:, None, :]
               + w2[:, None, None] * mask2[:, :, None] * cap_oh2[:, None, :])
    dispatch = combine > 0
    return combine.astype(logits.dtype), dispatch, aux_loss


def switch_gating(logits, capacity):
    """Switch-transformer top-1 gating (switch_gate.py analog)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0]
    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(idx, E).mean(axis=0)
    aux_loss = jnp.sum(me * ce) * E
    mask = jax.nn.one_hot(idx, E)
    pos = (jnp.cumsum(mask, axis=0) - 1) * mask
    keep = (pos < capacity) & (mask > 0)
    loc = pos.sum(axis=-1).astype(jnp.int32)
    cap_oh = jax.nn.one_hot(loc, capacity) * keep.max(-1, keepdims=True)
    combine = gate[:, None, None] * mask[:, :, None] * cap_oh[:, None, :]
    return combine.astype(logits.dtype), combine > 0, aux_loss


class ExpertFFN(nn.Layer):
    """One expert MLP; MoELayer stacks E of these into batched weights."""

    def __init__(self, d_model, d_hidden):
        super().__init__()
        self.fc1 = nn.Linear(d_model, d_hidden)
        self.fc2 = nn.Linear(d_hidden, d_model)

    def forward(self, x):
        import paddle_tpu.nn.functional as F

        return self.fc2(F.gelu(self.fc1(x)))


class MoELayer(nn.Layer):
    """Analog of incubate MoELayer (moe_layer.py:260).

    Experts are stored BATCHED: w1 [E, d, h], w2 [E, h, d] — one einsum
    runs all local experts on the MXU. With ep degree 1 the whole layer
    is a dense local computation; with ep > 1 the forward switches to an
    explicit shard_map over the 'ep' mesh axis with lax.all_to_all token
    dispatch and return (_forward_ep — the global_scatter/global_gather
    analog), and the expert weights carry dist_spec P('ep') so the
    surrounding pjit keeps them sharded at rest.
    """

    def __init__(self, d_model, d_hidden, num_experts, gate="gshard",
                 capacity_factor=1.25, ep_group=None, name=None):
        super().__init__()
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.nn import initializer as I

        self.d_model = d_model
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.gate_type = gate
        self.gate_proj = nn.Linear(d_model, num_experts, bias_attr=False)
        init = I.XavierUniform()
        self.w1 = self.create_parameter([num_experts, d_model, d_hidden],
                                        default_initializer=init)
        self.b1 = self.create_parameter([num_experts, 1, d_hidden], is_bias=True)
        self.w2 = self.create_parameter([num_experts, d_hidden, d_model],
                                        default_initializer=init)
        self.b2 = self.create_parameter([num_experts, 1, d_model], is_bias=True)
        ep = get_hybrid_communicate_group().axis_size("ep")
        if ep > 1:
            if num_experts % ep:
                raise ValueError(
                    f"ep={ep} must divide num_experts={num_experts}")
            for p in (self.w1, self.b1, self.w2, self.b2):
                p.dist_spec = P("ep")
        self.aux_loss = None

    def _gating(self, gt, cap):
        if self.gate_type == "switch":
            return switch_gating(gt, cap)
        return top2_gating(gt, cap)

    def forward(self, x):
        B, S, D = x.shape
        E = self.num_experts
        ep = get_hybrid_communicate_group().axis_size("ep")
        gate_t = self.gate_proj(x)  # [B,S,E] tracked op

        if ep > 1:
            return self._forward_ep(x, gate_t, ep)

        cap = int(self.capacity_factor * B * S / E) or 1

        def fn(xa, ga, w1, b1, w2, b2):
            T = B * S
            xt = xa.reshape(T, D)
            gt = ga.reshape(T, E)
            combine, dispatch, aux = self._gating(gt, cap)
            # dispatch: [T,E,C] one-hot -> expert buffers [E,C,D]
            buf = jnp.einsum("tec,td->ecd", dispatch.astype(xt.dtype), xt)
            h = jnp.einsum("ecd,edh->ech", buf, w1) + b1
            h = jax.nn.gelu(h)
            out = jnp.einsum("ech,ehd->ecd", h, w2) + b2
            # combine back: weighted gather [T,E,C] x [E,C,D] -> [T,D]
            y = jnp.einsum("tec,ecd->td", combine, out)
            return y.reshape(B, S, D), aux

        out, aux = apply("moe", fn, x, gate_t, self.w1, self.b1, self.w2,
                         self.b2)
        self.aux_loss = aux
        return out

    def _forward_ep(self, x, gate_t, ep):
        """Expert-parallel forward: shard_map over 'ep' with explicit
        lax.all_to_all token exchange — the global_scatter/global_gather
        analog (operators/collective/global_scatter_op.cu.cc,
        moe_utils.py). Tokens are sharded over 'ep'; each shard gates its
        local tokens, ships per-expert buffers to the expert owners,
        runs its local experts, and ships results back."""
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        B, S, D = x.shape
        E = self.num_experts
        if E % ep:
            raise ValueError(
                f"ep={ep} must divide num_experts={E}")
        E_loc = E // ep
        T = B * S
        if T % ep:
            raise ValueError(
                f"ep={ep} must divide token count {T}")
        T_loc = T // ep
        cap = int(self.capacity_factor * T_loc / E) or 1
        mesh = get_hybrid_communicate_group().mesh

        def shard_fn(xt, gt, w1, b1, w2, b2):
            # per-shard: xt [T_loc, D], gt [T_loc, E], w1 [E_loc, D, H]...
            combine, dispatch, aux = self._gating(gt[0], cap)
            buf = jnp.einsum("tec,td->ecd", dispatch.astype(xt.dtype), xt[0])
            # [E, cap, D] -> [ep, E_loc, cap, D]; all_to_all sends slice j
            # to ep-rank j (every expert's tokens to its owner)
            buf = buf.reshape(ep, E_loc, cap, D)
            recv = jax.lax.all_to_all(buf, "ep", split_axis=0, concat_axis=0,
                                      tiled=False)
            # recv[j] = rank j's tokens for MY experts -> [E_loc, ep*cap, D]
            recv = jnp.swapaxes(recv, 0, 1).reshape(E_loc, ep * cap, D)
            h = jnp.einsum("ecd,edh->ech", recv, w1[0]) + b1[0]
            h = jax.nn.gelu(h)
            out = jnp.einsum("ech,ehd->ecd", h, w2[0]) + b2[0]
            # ship results back: [E_loc, ep, cap, D] -> [ep, E_loc, cap, D]
            out = jnp.swapaxes(out.reshape(E_loc, ep, cap, D), 0, 1)
            back = jax.lax.all_to_all(out, "ep", split_axis=0, concat_axis=0,
                                      tiled=False)
            # back = my tokens' outputs from every expert group -> [E,cap,D]
            back = back.reshape(E, cap, D)
            y = jnp.einsum("tec,ecd->td", combine, back)
            aux = jax.lax.pmean(aux, "ep")
            return y[None], aux[None]

        smapped = shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P("ep"), P("ep"), P("ep"), P("ep"), P("ep"), P("ep")),
            out_specs=(P("ep"), P("ep")))

        def fn(xa, ga, w1, b1, w2, b2):
            xt = xa.reshape(ep, T_loc, D)
            gt = ga.reshape(ep, T_loc, E)
            y, aux = smapped(xt, gt, w1.reshape(ep, E_loc, D, -1),
                             b1.reshape(ep, E_loc, 1, -1),
                             w2.reshape(ep, E_loc, -1, D),
                             b2.reshape(ep, E_loc, 1, D))
            return y.reshape(B, S, D), jnp.mean(aux)

        out, aux = apply("moe_ep", fn, x, gate_t, self.w1, self.b1, self.w2,
                         self.b2)
        self.aux_loss = aux
        return out


# ---------------------------------------------------------------------------
# dropless sorted / grouped routing over the experts held here
# ---------------------------------------------------------------------------

MOE_BACKENDS = ("auto", "xla", "pallas")
#: which form of the expert product was traced (per trace, as
#: `PAGED_PATH_STATS`): never a silent fallback
MOE_PATH_STATS = {"xla": 0, "pallas": 0}
#: rows of a tile of the grouped matmul (a bf16 vreg holds 16 sublanes)
TILE_ROWS = 16


def reset_moe_path_stats():
    for k in MOE_PATH_STATS:
        MOE_PATH_STATS[k] = 0


def resolve_moe_backend(backend, width=128):
    """`auto` takes the kernel on a TPU where the experts' widths fill
    whole 128-lane tiles; an explicit choice always wins (off the chip
    `pallas` runs the interpreter)."""
    from paddle_tpu.core.device import on_tpu

    if backend not in MOE_BACKENDS:
        raise ValueError(f"backend must be one of {MOE_BACKENDS}, "
                         f"got {backend!r}")
    if backend != "auto":
        return backend
    return "pallas" if on_tpu() and width % 128 == 0 else "xla"


def one_product_reads_less(width):
    """Whether ONE grouped product over two row sets (a prefill chunk's
    and a decode step's) reads the experts' weights fewer times than two
    products, at the experts' narrower `width`: where `auto` takes the
    chip's kernel, which reads an expert's weights once a CALL where the
    whole expert is one weight block (`ops/pallas/moe.column_tile`), and
    once a row tile where it is cut into columns, so one product saves a
    read of every expert that both row sets touch either way (ceil(a +
    b) <= ceil(a) + ceil(b) tiles). The other form gathers a weight block
    a TILE (`_grouped_xla`: off the chip, or widths the kernel does not
    take), as many for one product as for two. A serving spec offers
    `decode_with_chunk` where this holds."""
    return resolve_moe_backend("auto", width) == "pallas"


def sorted_dispatch(ids, first_expert, num_held, tile_rows=TILE_ROWS):
    """Where every assignment goes. ids `[T, k]` int32, experts among
    ALL the router's; this layer holds `first_expert .. first_expert +
    num_held`. No capacity, nothing dropped: the buffer has room for
    every assignment landing here plus each expert's padding.

    -> dict: `row_token` `[M]` (the token of each buffer row, T for a
    padding row), `slot_row` `[T, k]` (the buffer row of each assignment,
    M where its expert is not held), `tile_expert` `[M / tile_rows]`,
    `live_tiles` `[1]`, `group_sizes` `[num_held]`."""
    t, k = ids.shape
    a = t * k
    m = -(-(a + num_held * (tile_rows - 1)) // tile_rows) * tile_rows
    local = ids.reshape(a).astype(jnp.int32) - first_expert
    held = (local >= 0) & (local < num_held)
    key = jnp.where(held, local, num_held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=num_held + 1)[:num_held] \
        .astype(jnp.int32)
    padded = -(-sizes // tile_rows) * tile_rows
    ends = jnp.cumsum(padded)
    group_row = ends - padded                  # first buffer row a group
    group_rank = jnp.cumsum(sizes) - sizes     # first sorted place a group
    sorted_key = key[order]
    safe = jnp.minimum(sorted_key, num_held - 1)
    dest = jnp.where(sorted_key < num_held,
                     group_row[safe] + jnp.arange(a) - group_rank[safe], m)
    row_token = jnp.full(m, t, jnp.int32).at[dest].set(
        (order // k).astype(jnp.int32), mode="drop")
    slot_row = jnp.zeros(a, jnp.int32).at[order].set(dest.astype(jnp.int32))
    live = ends[-1] // tile_rows
    tiles = jnp.arange(m // tile_rows)
    tile_expert = jnp.searchsorted(
        ends, jnp.minimum(tiles, jnp.maximum(live - 1, 0)) * tile_rows,
        side="right")
    return {"row_token": row_token, "slot_row": slot_row.reshape(t, k),
            "tile_expert": jnp.minimum(tile_expert, num_held - 1)
            .astype(jnp.int32),
            "live_tiles": live.reshape(1).astype(jnp.int32),
            "group_sizes": sizes}


def _grouped_xla(x, w, tile_expert, live_tiles, tile_rows, relu_squared):
    """The kernel's mathematics in plain XLA: each row tile against its
    expert's gathered weights (the gather copies a weight block a tile:
    for tests and as the other path, not for speed)."""
    m, k = x.shape
    tiles = x.reshape(m // tile_rows, tile_rows, k)
    out = jnp.einsum("mtk,mkn->mtn", tiles, w[tile_expert],
                     preferred_element_type=jnp.float32)
    if relu_squared:
        out = jnp.square(jnp.maximum(out, 0.0))
    live = jnp.arange(m // tile_rows) < live_tiles[0]
    out = jnp.where(live[:, None, None], out, 0.0)
    return out.reshape(m, -1).astype(x.dtype)


#: what `expert_share` counts, in its order, and how a step folds its
#: layers' counts: the assignments the experts held here took, the
#: experts touched, the largest expert's load, the row tiles of the
#: grouped product (live tiles; a tile past its expert's first finds the
#: weight block the kernel already holds, where the whole expert is one)
EXPERT_COUNTERS = (("moe_assignments_held", "sum"),
                   ("moe_experts_touched", "sum"),
                   ("moe_max_expert_load", "max"),
                   ("moe_row_tiles", "sum"))


def fold_expert_counters(per_layer):
    """`[layers, len(EXPERT_COUNTERS)]` int32 -> one step's counts, each
    folded over the layers as `EXPERT_COUNTERS` names."""
    return jnp.stack([jnp.max(c) if kind == "max" else jnp.sum(c)
                      for c, (_, kind) in zip(per_layer.T, EXPERT_COUNTERS)])


#: an expert's function, by the name a model gives: `relu2` is
#: `relu(x W1)^2 W2`; `swiglu` is `(silu(x W_g) * (x W_u)) W2` with gate
#: and up as ONE grouped product, `W1 = [W_g | W_u]` `[held, d, 2 h]`
EXPERT_ACTIVATIONS = ("relu2", "swiglu")


def expert_share(x, ids, weights, w1, w2, first_expert, backend="auto",
                 tile_rows=TILE_ROWS, activation="relu2"):
    """What the experts held here add for every token:
    `sum_{e chosen and held} weight_e * f_e(x)`, `f` the expert's
    function (`activation`, one of `EXPERT_ACTIVATIONS`).

    x `[T, d]`; ids, weights `[T, k]` (the router's choice among ALL its
    experts, and the weights as normalised over the whole chosen set);
    w1 `[held, d, h]` (`[held, d, 2 h]` for `swiglu`), w2 `[held, h, d]`:
    experts `first_expert .. first_expert + held`. -> (`[T, d]` float32,
    counters `[4]` int32, as `EXPERT_COUNTERS` names them)."""
    from paddle_tpu.core.device import pallas_interpret

    num_held = w1.shape[0]
    plan = sorted_dispatch(ids, first_expert, num_held, tile_rows)
    rows = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    rows = rows[plan["row_token"]]
    resolved = resolve_moe_backend(backend, min(w1.shape[1], w1.shape[2]))
    MOE_PATH_STATS[resolved] += 1
    if resolved == "pallas":
        from paddle_tpu.ops.pallas.moe import moe_grouped_matmul

        gmm = lambda a, w, sq: moe_grouped_matmul(  # noqa: E731
            a, w, plan["tile_expert"], plan["live_tiles"], tile_rows,
            relu_squared=sq, interpret=pallas_interpret())
    else:
        gmm = lambda a, w, sq: _grouped_xla(  # noqa: E731
            a, w, plan["tile_expert"], plan["live_tiles"], tile_rows, sq)
    if activation == "relu2":
        hidden = gmm(rows, w1, True)
    elif activation == "swiglu":
        # the gate's and the up's halves of one product, joined in
        # float32 between the two grouped products
        gate_up = gmm(rows, w1, False).astype(jnp.float32)
        h = w1.shape[2] // 2
        hidden = (jax.nn.silu(gate_up[:, :h]) * gate_up[:, h:]) \
            .astype(rows.dtype)
    else:
        raise ValueError(f"activation must be one of "
                         f"{EXPERT_ACTIVATIONS}, got {activation!r}")
    out = gmm(hidden, w2, False)
    out = jnp.concatenate([out, jnp.zeros((1, out.shape[1]), out.dtype)])
    m = out.shape[0] - 1
    w_held = jnp.where(plan["slot_row"] < m, weights, 0.0)
    picked = out[plan["slot_row"]].astype(jnp.float32)   # [T, k, d]
    sizes = plan["group_sizes"]
    counters = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0),
                          jnp.max(sizes), plan["live_tiles"][0]]) \
        .astype(jnp.int32)
    return jnp.einsum("tk,tkd->td", w_held.astype(jnp.float32), picked,
                      precision=jax.lax.Precision.HIGHEST), counters
