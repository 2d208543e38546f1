"""Trace-machinery introspection metadata — the jit layer's own
description of which APIs stage python callables into XLA programs,
which call keywords mark arguments static or donated, and which
sibling-module calls are host-blocking when issued under a trace.

This module is deliberately PURE DATA (no jax import, no framework
import): `paddle_tpu.analysis` (tpu-lint) reads it to resolve
jit-reachability and donation statically, and `jit.api` consumes the
donation constants for its own `jax.jit(..., donate_argnums=...)`
calls — one source of truth instead of the analyzer string-matching
the framework's internals.

Names are CANONICAL dotted paths as the analyzer resolves them through
import aliases (`import jax.numpy as jnp` resolves `jnp.matmul` to
`jax.numpy.matmul`).
"""
from __future__ import annotations

# ---------------------------------------------------------------------------
# Trace entry points
# ---------------------------------------------------------------------------

#: Decorators that make the decorated function a traced program.
#: Maps canonical name -> "kind". Kind "dy2static" means the wrapper
#: runs the dy2static AST pass first, so python `if`/`while` on traced
#: booleans are converted to lax.cond/while_loop (TPU002 exempts the
#: directly-wrapped function body; its callees are NOT transformed).
TRACE_DECORATORS = {
    "jax.jit": "jit",
    "jax.pmap": "jit",
    "paddle_tpu.jit.to_static": "dy2static",
    "paddle_tpu.jit.api.to_static": "dy2static",
}

#: Callables that stage a python-callable ARGUMENT into traced code.
#: Maps canonical name -> tuple of traced-callable positional indices.
#: For jax.lax.switch the branch list at index 1 is a sequence of
#: callables (the analyzer unpacks list/tuple literals at any traced
#: position).
TRACING_CALLABLES = {
    "jax.jit": (0,),
    "jax.pmap": (0,),
    "jax.vmap": (0,),
    "jax.grad": (0,),
    "jax.value_and_grad": (0,),
    "jax.jacfwd": (0,),
    "jax.jacrev": (0,),
    "jax.hessian": (0,),
    "jax.checkpoint": (0,),
    "jax.remat": (0,),
    "jax.eval_shape": (0,),
    "jax.make_jaxpr": (0,),
    "jax.lax.scan": (0,),
    "jax.lax.map": (0,),
    "jax.lax.while_loop": (0, 1),
    "jax.lax.fori_loop": (2,),
    "jax.lax.cond": (1, 2),
    "jax.lax.switch": (1,),
    "jax.lax.associative_scan": (0,),
    "jax.custom_vjp": (0,),
    "jax.custom_jvp": (0,),
    "jax.experimental.pallas.pallas_call": (0,),
    "jax.shard_map": (0,),
    "paddle_tpu.jit.to_static": (0,),
    "paddle_tpu.jit.api.to_static": (0,),
}

#: The subset of TRACING_CALLABLES / TRACE_DECORATORS that accept
#: static/donate keywords (jit-like signatures).
JIT_LIKE = {"jax.jit", "jax.pmap"}

#: Wrappers that return their first argument's callable semantics
#: unchanged — `jax.jit(count_traces(f))` traces f. The analyzer
#: stages through them.
PASSTHROUGH_WRAPPERS = {
    "paddle_tpu.jit.count_traces",
    "paddle_tpu.jit.api.count_traces",
    "functools.partial",
    "functools.wraps",
}

#: Call keywords that mark arguments STATIC (python values re-traced
#: per value, never tracers) and DONATED (buffer invalidated by the
#: call).
STATIC_ARG_KEYWORDS = ("static_argnums", "static_argnames")
DONATE_ARG_KEYWORDS = ("donate_argnums", "donate_argnames")

#: Decorator marking a function explicitly NOT traced
#: (paddle_tpu.jit.not_to_static).
NOT_TRACED_DECORATORS = {
    "paddle_tpu.jit.not_to_static",
    "paddle_tpu.jit.api.not_to_static",
}

# ---------------------------------------------------------------------------
# Donation layout of the framework's own compiled steps
# ---------------------------------------------------------------------------

#: jit.TrainStep donates (param_arrays, accums, bufs) — the first three
#: positional arguments of every step/scan/repeat program — so the
#: optimizer update happens in-place in HBM. The accumulate path's
#: acc_fn donates only its grad buffers (position 0).
TRAINSTEP_DONATE_ARGNUMS = (0, 1, 2)
ACCUM_DONATE_ARGNUMS = (0,)

#: The serving engine's compiled steps all share ONE donation layout:
#: every step body is `(state_arrays, kpool, vpool, *host_args)` and
#: donates the two pool planes (positions 1, 2) so XLA updates the
#: paged KV cache in place in HBM. The copy-on-write block-copy step
#: is `(kpool, vpool, src, dst)` and donates positions 0, 1.
ENGINE_STEP_DONATE_ARGNUMS = (1, 2)
#: a model with state of fixed size a slot (recurrent layers) threads it
#: as one tuple right after the pools, donated with them
ENGINE_STATEFUL_STEP_DONATE_ARGNUMS = (1, 2, 3)
ENGINE_COW_DONATE_ARGNUMS = (0, 1)

#: Donation layout of EVERY compiled engine program, by program name
#: (the `__name__` the engine assigns each step body). This is the one
#: source of truth both analyzers read: tpu-lint TPU004 resolves
#: `donate_argnums=introspect.<NAME>` expressions through
#: DONATION_CONSTANTS below, and tpu-verify TPU101 checks that the
#: argnums declared HERE produce real input/output aliases in each
#: program's lowered module — no magic `(1, 2)` literals anywhere.
ENGINE_STEP_DONATION = {
    "engine_prefill_chunk": ENGINE_STEP_DONATE_ARGNUMS,
    "engine_decode_step": ENGINE_STEP_DONATE_ARGNUMS,
    "engine_decode_step_with_chunk": ENGINE_STEP_DONATE_ARGNUMS,
    "engine_verify_step": ENGINE_STEP_DONATE_ARGNUMS,
    "engine_cow_copy": ENGINE_COW_DONATE_ARGNUMS,
}

#: Named donation layouts by constant name — TPU004 resolves a
#: `donate_argnums=introspect.<NAME>` expression through this table,
#: so the framework's own jit sites stay visible to the rule.
DONATION_CONSTANTS = {
    "TRAINSTEP_DONATE_ARGNUMS": TRAINSTEP_DONATE_ARGNUMS,
    "ACCUM_DONATE_ARGNUMS": ACCUM_DONATE_ARGNUMS,
    "ENGINE_STEP_DONATE_ARGNUMS": ENGINE_STEP_DONATE_ARGNUMS,
    "ENGINE_STATEFUL_STEP_DONATE_ARGNUMS":
        ENGINE_STATEFUL_STEP_DONATE_ARGNUMS,
    "ENGINE_COW_DONATE_ARGNUMS": ENGINE_COW_DONATE_ARGNUMS,
}

# ---------------------------------------------------------------------------
# Host-sync / side-effect surfaces (TPU001 / TPU005)
# ---------------------------------------------------------------------------

#: Method names that force a device->host transfer of their receiver.
#: `.numpy()` is this framework's Tensor sync (core.tensor.Tensor).
HOST_SYNC_METHODS = ("item", "tolist", "numpy")

#: Free functions that concretize a traced value on host.
HOST_SYNC_CALLS = {
    "numpy.asarray",
    "numpy.array",
    "jax.device_get",
}

#: Builtins that concretize a traced scalar (bool-coercion hazards are
#: TPU002's domain — branches are where they bite).
HOST_SYNC_BUILTINS = ("float", "int")

#: Wall-clock / python-RNG calls that are side effects under trace:
#: they execute ONCE at trace time and bake a constant into the
#: compiled program.
IMPURE_CALLS = {
    "time.time",
    "time.time_ns",
    "time.perf_counter",
    "time.perf_counter_ns",
    "time.monotonic",
    "datetime.datetime.now",
    "datetime.datetime.utcnow",
    "random.random",
    "random.randint",
    "random.uniform",
    "random.choice",
    "random.shuffle",
    "random.sample",
}

#: Module prefixes whose calls are impure under trace (numpy's global
#: RNG draws a host value at trace time).
IMPURE_CALL_PREFIXES = ("numpy.random.",)

# ---------------------------------------------------------------------------
# PRNG key discipline (TPU003)
# ---------------------------------------------------------------------------

#: jax.random functions that DERIVE fresh keys (passing a key here does
#: not "spend" it for reuse purposes — though using the parent after a
#: plain split is still caught when the parent is sampled twice).
RANDOM_KEY_DERIVERS = ("split", "fold_in", "PRNGKey", "key", "clone",
                       "key_data", "wrap_key_data")

#: Prefixes under which a first-argument key is CONSUMED by a sampler.
RANDOM_NAMESPACES = ("jax.random.",)

# ---------------------------------------------------------------------------
# Eager collectives (TPU007)
# ---------------------------------------------------------------------------

#: paddle_tpu.distributed functions that run their OWN compiled
#: program over the mesh and block the host — calling one inside a
#: traced function either fails to trace or silently stages a nested
#: dispatch. Traced code must use mesh-level primitives
#: (jax.lax.psum / shard_map) or the spmd TrainStep shardings instead.
#: tests assert this list stays in sync with paddle_tpu.distributed's
#: public eager API.
EAGER_COLLECTIVES = (
    "all_reduce", "all_gather", "broadcast", "reduce", "scatter",
    "alltoall", "reduce_scatter", "send", "recv", "isend", "irecv",
    "batch_isend_irecv", "barrier",
)

EAGER_COLLECTIVE_PREFIXES = (
    "paddle_tpu.distributed.",
    "paddle_tpu.distributed.collective.",
)

# ---------------------------------------------------------------------------
# Dtype-widening surfaces (TPU008)
# ---------------------------------------------------------------------------

#: Contraction ops whose accumulator dtype follows the operand dtype
#: unless preferred_element_type pins it — the bf16 cancellation bug
#: class (see DESIGN_DECISIONS on the paged-attention PV fix).
CONTRACTION_CALLS = {
    "jax.numpy.matmul",
    "jax.numpy.dot",
    "jax.numpy.einsum",
    "jax.numpy.tensordot",
    "jax.lax.dot_general",
    "jax.lax.dot",
}

ACCUM_DTYPE_KEYWORD = "preferred_element_type"

# ---------------------------------------------------------------------------
# Concurrency surfaces (tpu-race, TPU2xx)
# ---------------------------------------------------------------------------

#: Canonical constructors whose result is a mutual-exclusion guard —
#: an attribute assigned from one of these (or from a name that itself
#: looks like a lock) names a LOCK in tpu-race's lock-set analysis,
#: and `with <that attribute>:` opens a guarded region.
LOCK_CONSTRUCTORS = (
    "threading.Lock",
    "threading.RLock",
    "threading.Condition",
    "threading.Semaphore",
    "threading.BoundedSemaphore",
)

#: Canonical constructor for thread-confined storage: every access
#: whose base is an attribute assigned from one of these is exempt
#: from the shared-mutable rule (the PhaseTimer discipline).
THREAD_LOCAL_CONSTRUCTORS = ("threading.local",)

#: Canonical callables that put a python callable on another thread.
#: Maps canonical name -> (keyword, positional index) locating the
#: callable argument — the seeds of tpu-race's thread-escape analysis
#: (TPU201/TPU205), mirroring how TRACING_CALLABLES seeds tpu-lint's
#: jit-reachability.
THREAD_SPAWN_CALLS = {
    "threading.Thread": ("target", 1),
    "threading.Timer": ("function", 1),
}

#: Method attribute that hands its first positional argument to an
#: executor's worker thread (concurrent.futures submit convention).
EXECUTOR_SUBMIT_METHODS = ("submit",)

#: Host-blocking calls for TPU204 (blocking-call-under-lock): the
#: canonical free functions, plus method attributes that block when
#: their receiver was built by one of BLOCKING_RECEIVER_TYPES (the
#: receiver gate keeps `",".join(...)` and `dict.get` out).
BLOCKING_CALLS = (
    "time.sleep",
    "jax.block_until_ready",
)
BLOCKING_METHODS = ("join", "get", "wait", "result", "acquire")
BLOCKING_RECEIVER_TYPES = (
    "threading.Thread",
    "threading.Event",
    "threading.Condition",
    "threading.Lock",
    "threading.RLock",
    "queue.Queue",
    "queue.SimpleQueue",
    "queue.LifoQueue",
    "queue.PriorityQueue",
)

# ---------------------------------------------------------------------------
# Async-pipeline effect table (tpu-race TPU203)
# ---------------------------------------------------------------------------
# The ENGINE_STEP_DONATION precedent, applied to the pipelined engine
# cores: the engine and the allocators DECLARE their effect surfaces
# here, the race analyzer READS them — no magic method-name strings on
# either side. Three effect classes:
#
# - DISPATCH: engine methods that issue a compiled step and return
#   WITHOUT waiting on its output (they make an `_InFlight` record).
#   Between such a call and its completion the device may still be
#   writing into the KV blocks / state rows / adapter pages of the
#   lanes the step was dispatched over.
# - COMPLETE: calls that synchronize outstanding device work — the
#   explicit wait plus every host materialization the complete stages
#   use (np.asarray IS the sync of the serial and the ahead order).
#   A wait completes the record its argument is drawn from.
# - RELEASE: allocator methods that free or recycle device-visible
#   pages. The invariant (DESIGN_DECISIONS r21, as restated by PR 29):
#   a lane's pages are released only after the LAST step dispatched
#   over the lane has completed. Releasing them while that step is
#   outstanding is the zombie-write hazard; releasing the lanes of a
#   completed step while a LATER step (which does not hold them) is
#   outstanding is the ahead order, and sound.

#: Engine methods that dispatch a compiled step without waiting.
ENGINE_DISPATCH_EFFECTS = (
    "_plain_dispatch",
    "_spec_dispatch",
    "_dispatch_ahead",
)

#: Calls that complete (synchronize) outstanding dispatches.
STEP_COMPLETE_CALLS = ("jax.block_until_ready",) \
    + tuple(sorted(HOST_SYNC_CALLS))

#: Allocator release/recycle surface, by owning class. `free`/
#: `free_state`/`release` drop a lane's references (its pages can get
#: a new owner under an in-flight writer); `allocate`/`acquire`
#: recycle evictable pages in place.
ALLOCATOR_RELEASE_EFFECTS = {
    "PagedKVCache": ("free", "allocate", "free_state"),
    "PagedAdapterPool": ("release", "acquire"),
}

# ---------------------------------------------------------------------------
# Per-axis collective budget (tpu-verify TPU104 / tpu-shard TPU30x)
# ---------------------------------------------------------------------------
# The ENGINE_STEP_DONATION precedent applied to mesh collectives: ONE
# declared table carries, per (mesh axis, collective kind), the
# allowed per-transformer-layer count, the allowed fixed count, and a
# payload bound expressed over the serving geometry. tpu-verify's
# TPU104 consumes the COUNT view (per_layer/fixed/allowed — the same
# surface the old count-only CollectiveBudget exposed, so the count
# gate is unchanged by construction); tpu-shard's TPU301/304/305
# consume the AXIS view (which axis a collective may cross, how many
# bytes it may move, and whether that axis is a fast ICI link or a
# slow DCN one). Counts and bytes can never drift apart because they
# are rows of the same table.


class AxisCollectiveBudget:
    """Per-mesh-axis collective budget of ONE compiled serving step.

    axes: ((axis_name, link), ...) — every mesh axis the step may run
        collectives over, with its link class: "ici" (fast intra-slice
        interconnect) or "dcn" (slow inter-slice network; tpu-shard
        TPU305 flags per-token collectives crossing these).
    entries: ((axis, kind, per_layer, fixed, payload), ...) — per
        (axis, collective kind): the allowed per-transformer-layer
        count, the allowed fixed (embed / lm-head / whole-step) count,
        and a payload bound in BYTES as an arithmetic expression over
        the harvest geometry symbols (tokens, hidden, intermediate,
        vocab, heads, head_dim, layers, blocks, block_size, slots —
        see analysis.shard.model.eval_payload). The bound is the
        GLOBAL (post-gather / pre-reduce logical) payload, which is
        invariant to the axis size — a collective whose bytes scale
        with the mesh is exactly what TPU304 exists to catch.

    Pure data + arithmetic: no jax import, no framework import.
    """

    def __init__(self, axes=(), entries=()):
        self.axes = tuple(tuple(a) for a in axes)
        self.entries = tuple(tuple(e) for e in entries)
        links = {"ici", "dcn"}
        for _, link in self.axes:
            if link not in links:
                raise ValueError(
                    f"axis link must be one of {sorted(links)}, "
                    f"got {link!r}")
        names = set(self.axis_names())
        for axis, kind, per, fix, payload in self.entries:
            if axis not in names:
                raise ValueError(
                    f"budget entry ({axis!r}, {kind!r}) names an axis "
                    "missing from the axes table")

    def __eq__(self, other):
        return (isinstance(other, AxisCollectiveBudget)
                and self.axes == other.axes
                and self.entries == other.entries)

    def __hash__(self):
        return hash((self.axes, self.entries))

    def __repr__(self):
        return (f"AxisCollectiveBudget(axes={self.axes!r}, "
                f"entries={self.entries!r})")

    # -- count view (the CollectiveBudget surface TPU104 consumes) ----
    def _merged(self, idx):
        out = {}
        for e in self.entries:
            out[e[1]] = out.get(e[1], 0) + e[idx]
        return tuple(sorted((k, v) for k, v in out.items() if v))

    @property
    def per_layer(self):
        return self._merged(2)

    @property
    def fixed(self):
        return self._merged(3)

    def allowed(self, kind, num_layers):
        per = dict(self.per_layer).get(kind, 0)
        fix = dict(self.fixed).get(kind, 0)
        return per * num_layers + fix

    def kinds(self):
        return sorted(set(dict(self.per_layer))
                      | set(dict(self.fixed)))

    # -- axis view (tpu-shard TPU301/304/305) -------------------------
    def axis_names(self):
        return tuple(a for a, _ in self.axes)

    def link_of(self, axis):
        return dict(self.axes).get(axis)

    def slow_axes(self):
        return tuple(a for a, link in self.axes if link == "dcn")

    def entries_for(self, axis):
        return tuple(e for e in self.entries if e[0] == axis)

    def allowed_on_axis(self, axis, kind, num_layers):
        n = 0
        for _, k, per, fix, _ in self.entries_for(axis):
            if k == kind:
                n += per * num_layers + fix
        return n

    def payload_bounds(self, axis, kind):
        """Payload-bound expressions for (axis, kind), one per entry
        row — () when the kind is undeclared on that axis."""
        return tuple(e[4] for e in self.entries_for(axis)
                     if e[1] == kind)


#: Per-axis collective budget of ONE tensor-parallel GPT serving step
#: (the table `models/gpt.py:GPT_SERVING_COLLECTIVES` aliases — the
#: helpers there are the only places serving collectives come from).
#: Per transformer layer over the 'mp' (ICI) axis: _attn_out
#: all-gathers twice (head reassembly + out_proj columns) and the MLP
#: twice (fc1 + fc2 columns) = 4, each bounded by the widest gathered
#: activation (the fc1 intermediate rows); plus AT MOST one pmax when
#: the int8 KV cache is on (the quant-on-write grid fold in
#: ops/paged_attention — per-block scales are global across the
#: head-sharded pools, so the shards' absmax must agree; fp steps emit
#: zero pmax and TPU100's exact op snapshot pins that), bounded by the
#: full fp32 scale grid. Fixed: one lm-head logits all-gather
#: (tokens x vocab), one vocab-parallel-embedding psum
#: (tokens x hidden). An accidental fifth per-layer gather (or a
#: brand-new collective kind, or an axis-size-scaling payload) fails
#: the trace gates instead of stretching every decode step.
GPT_SERVING_AXIS_BUDGET = AxisCollectiveBudget(
    axes=(("mp", "ici"),),
    entries=(
        ("mp", "all_gather", 4, 0, "tokens * intermediate * 4"),
        ("mp", "all_gather", 0, 1, "tokens * vocab * 4"),
        ("mp", "psum", 0, 1, "tokens * hidden * 4"),
        ("mp", "pmax", 1, 0, "layers * blocks * 2 * 4"),
    ),
)

#: what the engine's step contracts name: the budget of the one model
#: whose serving steps are sharded today
SERVING_STEP_AXIS_BUDGET = GPT_SERVING_AXIS_BUDGET
