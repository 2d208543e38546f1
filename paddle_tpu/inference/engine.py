"""Continuous-batching generation engine over a paged KV cache.

The serving tier the north star's "heavy traffic" clause asks for:
instead of one request at a time against a per-request fixed-size cache
(a model's own `generate`), MANY requests decode in ONE compiled step
(Orca-style iteration-level scheduling) against a global block pool
shared by all of them (vLLM-style PagedAttention layout).

Three pieces, each shape-stable so steady-state serving never
recompiles:

- `PagedKVCache`: per-layer `[num_blocks, block_size, heads, head_dim]`
  pool planes stacked on a leading layer axis, plus a host-side free
  list. Requests own `ceil(context/block_size)` blocks, allocated on
  demand as their context grows and returned the moment they finish —
  HBM is shared by live CONTEXT, not reserved per request at max
  sequence length. Block 0 is the null block (idle-slot writes land
  there; never allocated).
- a slot scheduler: `num_slots` decode lanes. Between decode
  iterations, finished requests vacate their lane and queued requests
  are admitted into free lanes (priority classes first, FIFO within a
  class). Prefill is CHUNKED: each scheduler iteration runs at most
  ONE fixed-shape compiled prefill chunk (`prefill_chunk` tokens), so
  a long admission interleaves with the in-flight decode batch instead
  of monopolizing an iteration — and the chunk program compiles ONCE
  for every prompt length (`start`/`plen` are traced). A lane that
  cannot get a block this iteration simply skips it (masked to the
  null block) and retries — graceful degradation under pool pressure
  instead of an abort.
- a prefix cache (on by default): `PagedKVCache` keeps a
  chain-hash → block map over FULL prompt blocks with per-block
  refcounts. Admission seats the longest cached block-aligned prefix
  read-only in the slot's table — hit tokens are never recomputed,
  only the tail is prefilled. Shared blocks are copy-on-write: a
  decode write landing in one first promotes it to a private copy via
  a tiny compiled block-copy step, so token streams stay identical to
  the uncached path. Cold cached blocks (refcount 0) form an LRU pool
  that `allocate` evicts from under pressure — the existing
  stall/retry path, unchanged.
- admission QoS: `add_request(..., priority=...)` with
  `PRIORITY_CLASSES` ordering, priority-labeled TTFT/TPOT histograms,
  and `max_queue` shed-on-saturation (shed requests resolve to None —
  the HTTP-429 of this API). Priority is STRICT: under sustained
  higher-class saturation a seated batch lane's prefill can starve —
  that is the contract (`batch` means "whenever there's room");
  `max_queue` shedding, not aging, is the overload control.
- one donated compiled decode step (`jax.jit`, the TrainStep idiom:
  model state threaded as traced args, pools donated so XLA updates
  them in place in HBM): `[slots, 1]` tokens + `[slots]` positions +
  `[slots, max_blocks]` block tables -> next token per slot. Fixed
  shapes regardless of which lanes are live, so arrivals/completions
  never retrace — `jit.count_traces` probes prove it in CI.

Greedy decoding matches the model's own `generate(use_cache=True)`
token-for-token per request (the parity contract CI enforces) — under
either paged-attention backend: `attention_backend` picks `auto` /
`dense` / `pallas` per `ops.paged_attention.resolve_backend`, resolved
once at construction so the compiled decode step is fixed; the
selection is published as the `engine_attention_backend_info` gauge
and every decode dispatch lands in the backend-labeled
`engine_decode_step_seconds` histogram.

Speculative decoding (PR 7): decode is HBM-bandwidth-bound (every
step re-reads the weights and the live KV), so the engine can amortize
one target-model pass over several tokens: with `spec_decode_k=K > 0`,
a host-side DRAFTER
(`inference/speculative.NgramDrafter` by default — model-free
prompt-lookup; any `propose(prompt, generated, k)` object plugs in)
proposes up to K tokens per lane, and ONE fixed-shape compiled verify
step (the model's verify step: `[slots, K+1]` tokens, traced per-row
positions and draft lengths) scores all K+1 positions against the
paged pools, writing their KV through the block tables. Acceptance is
EXACT under the greedy contract: the longest draft prefix matching the
target's own argmax is emitted (plus the target's next token — every
verify step nets >= 1 token), so output streams are token-identical
to the non-speculative engine for ANY drafter. Rejected positions
need no cleanup — the slot position simply does not advance past
them, position-bounded attention makes their stale KV unreachable,
and the next window overwrites them. Writes landing in shared or
prefix-cached blocks COW-promote first, for EVERY block the window
touches, exactly like plain decode. Per-lane variable acceptance
stays inside one program via masking, so `decode_traces == 1` holds
per (backend, K); K=0 builds today's decode step unchanged
(bit-for-bit the same program). Multi-token steps keep the latency
books honest: every accepted token lands in the TPOT histogram
against its producing step (the step gap amortized per token), and
`engine_spec_accepted_tokens` / `engine_spec_draft_hit_rate` track
how much the drafter is actually buying.

Tensor-parallel sharded serving (PR 8): `GenerationEngine(model,
mp_degree=N)` (or `mesh=serving_mesh(N)`) runs
the SAME host-side scheduler — allocator, prefix cache, COW, QoS,
speculative acceptance all unchanged — while every compiled step
(prefill chunk, decode, K-token verify) becomes ONE
shard_map program over an `mp`-axis device mesh. Attention is sharded
by heads: per-shard paged KV pools `[L, blocks, bs, heads/mp, D]`
with the block tables REPLICATED across shards, so a block id means
the same thing everywhere and the host allocator stays mesh-oblivious;
both paged-attention backends (dense fori-loop and the Pallas kernel)
run per-shard unchanged, since neither reads the head count from
config. Weights are sharded Megatron-style but COLUMN-parallel
end-to-end (qkv head-grouped, out_proj/fc1/fc2 output-sharded,
activations reassembled by tiled all-gathers; vocab-parallel embedding
via masked-gather+psum; lm_head logits all-gathered once for the
host's greedy/acceptance) — every floating-point dot stays full
length, so mp=N output is TOKEN-EXACT vs mp=1, not merely close
(DESIGN_DECISIONS r12). The shape-stable single-trace contract holds
per mesh shape (`decode_traces == 1` per (backend, K, mp)) and the
sharded pools stay donated. CPU CI runs the real mp=2/mp=4 program on
a virtual device mesh (`--xla_force_host_platform_device_count`).

Quantized serving (PR 11): decode's other wall is the BYTES — every
step re-streams the live KV and the weights. `kv_dtype='int8'`
stores the paged pools as int8 codes plus a
`[layers, blocks, 2]` per-block K/V scale array threaded through
every compiled step beside the pools: quant-on-write grows and
requantizes only the written (engine-private) block's grid, dequant
is fused into both backends' streamed-block matmuls (fp32 online
softmax unchanged), COW copies scale rows with blocks, and the
prefix cache shares them by block id — so pool bytes halve vs bf16
and warm/speculative runs replay exactly. `weight_dtype='int8'`
(re-snapshot via `quantize_weights()`)
serves qkv/out/fc1/fc2 as (int8, per-channel scale) pairs
dequantized inside the step to the compute dtype — int8 in HBM, fp32
accumulation (tpu-verify TPU103). Both knobs off is BIT-identical to
the unquantized engine; quantized output is tolerance-gated against
the fp path (see README "Quantized serving"), token-exact across
mesh shapes (per-block grids pmax-fold at mp>1) and across backends.

Multi-tenant adapter serving (PR 13): one base model, thousands of
per-tenant LoRA adapters — `GenerationEngine(adapters=registry)` wires
the `paddle_tpu/adapters/` subsystem in: an `AdapterRegistry` holds
rank-padded A/B factors host-side, a `PagedAdapterPool` pages active
adapters on-device (the PagedKVCache block/refcount/LRU +
stall-and-retry pattern, page-sized; host-side swap-in from the
registry on miss), and every compiled step gains a traced `[slots]`
adapter page row that gathers each lane's factors and fuses the
low-rank delta `x·Aᵀ·Bᵀ·scaling` into the qkv/out/fc1/fc2 matmuls
(`ops/lora.py`, fp32 accumulation) — shape-stable in `max_rank`, so
`decode_traces == 1` holds for ANY tenant mix. Adapter id 0 is the
null/base adapter (exact-zero delta); the prefix-cache chain hash is
SALTED with the adapter id, so a base prompt's KV under one tenant can
never alias another's, while id-0 reuse keys exactly as before.
Composes with everything above: speculation verifies under the adapted
model, mp>1 shards the B pages column-parallel (no new collectives,
bit-identical across mesh shapes), and int8 KV/weights quantize the
BASE path while adapters ride fp.

Probabilistic serving (PR 15): `GenerationEngine(sampling=True)`
turns on per-request on-device sampling —
`add_request(..., sampling_params=SamplingParams(temperature, top_k,
top_p, seed))` carries each request's knobs PER SLOT through the
fixed-shape decode and verify steps as traced per-row arrays (params
are data, never trace keys: `decode_traces == 1` holds per
(backend, K, mp, kv_dtype) for any live mix of greedy and sampled
lanes). Each sampled slot owns a `[2]` uint32 base key row derived
from its seed; every draw folds the slot's absolute position (plus a
draw-purpose salt) into it on device (`ops/sampling.py`), so same
(seed, trace, config) means same tokens across chunk sizes, cache
states and backends — while greedy lanes (`temperature=0`, and every
lane of a `sampling=False` engine, whose programs are byte-identical
to the pre-sampling ones) keep taking the literal argmax. With
speculation on, acceptance upgrades from exact argmax equality to
Leviathan-style REJECTION SAMPLING at the verify step: all K+1 logit
positions are already in hand, so the compiled program computes
per-row accept coins and residual/bonus resamples in the same pass,
and the host walk emits `drafts[:n] + choices[n]` — provably
preserving the target distribution for any (deterministic) drafter,
and degenerating to the bit-exact greedy contract at temperature 0.
`best_of_n` fans one prompt into n sampled lanes that share its
prefix-cache blocks (seated once, read-only).

Serving telemetry (PR 2): every engine carries a metrics registry
(`engine.metrics`, observability tier) — TTFT/TPOT histograms, queue/
slot/pool gauges with a high-water mark, admission/finish/stall
counters, and a decode-recompile counter wired to the count_traces
probes (steady-state contract: 0). Scheduler iterations and compiled
prefill/decode dispatches also emit `engine.*` spans into the profiler
recorder, so a chrome trace shows the scheduler timeline next to the
metrics story.
"""
from __future__ import annotations

import hashlib
import math
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.analysis.trace.contracts import TraceContract, \
    register_contract
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference.sampling import SamplingParams
from paddle_tpu.inference.sampling import key_row as _sampling_key_row
from paddle_tpu.inference.speculative import draft_window
from paddle_tpu.jit import introspect
from paddle_tpu.jit.api import bound_state, count_traces, dedup_params, \
    model_buffers
from paddle_tpu.observability.metrics import LATENCY_BUCKETS, \
    MetricsRegistry
from paddle_tpu.observability.tracing import (STEP_PHASES,
                                              FlightRecorder,
                                              STALL_MIN_EXCESS_S,
                                              STALL_RATIO, STALL_WINDOW,
                                              PhaseTimer, StallDetector,
                                              TraceRecorder,
                                              export_timeline,
                                              install_host_pause_hooks,
                                              new_trace_id, now_us,
                                              profiler_host_events,
                                              stall_owner)
from paddle_tpu.profiler import RecordEvent

__all__ = ["PagedKVCache", "GenerationEngine", "Request",
           "PRIORITY_CLASSES", "prefix_key", "iter_prefix_key",
           "SamplingParams"]

#: one span name per step phase, the same in every sink (the profiler's
#: trace, the host-event recorder, the `TraceRecorder`)
_PHASE_SPANS = {p: "engine." + p for p in STEP_PHASES}


class _PhaseSpan:
    """What `GenerationEngine._phase` returns: a context manager and
    not a generator, since a step enters a dozen of them."""

    __slots__ = ("_engine", "_span", "_event", "_clock", "_t0")

    def __init__(self, engine, name):
        self._engine = engine
        self._span = _PHASE_SPANS[name]
        self._event = RecordEvent(self._span)
        self._clock = engine._phases.phase(name)

    def __enter__(self):
        self._t0 = now_us() if self._engine.tracer is not None else None
        self._event.begin()
        self._clock.__enter__()

    def __exit__(self, *exc):
        self._clock.__exit__(*exc)
        self._event.end()
        if self._t0 is not None and exc[0] is None:
            self._engine.tracer.add_span(self._span, self._t0, now_us(),
                                         cat="phase")
        return False


def iter_prefix_key(tokens, block_size, adapter_id=0):
    """Lazy form of `prefix_key`: yields the chain digests one full
    block at a time, so walkers that break at the first cache miss
    (`match_prefix`, `warm_prefix_tokens` on a cold cache) hash only
    as deep as they look."""
    tokens = np.asarray(tokens, np.int32)
    bs = int(block_size)
    # adapter-id SALT (multi-tenant LoRA serving): a tenant adapter
    # changes the qkv projections, so the KV a prompt's prefill writes
    # depends on the adapter — the same base prompt under two adapters
    # must hash to DISJOINT chains or a cache hit would seat the wrong
    # tenant's KV. Adapter 0 (the null/base adapter) salts with the
    # empty seed, so base-model prefix reuse keys exactly as before.
    h = b"" if not adapter_id else hashlib.blake2b(
        b"adapter:%d" % int(adapter_id), digest_size=16).digest()
    for i in range(len(tokens) // bs):
        h = hashlib.blake2b(
            h + tokens[i * bs:(i + 1) * bs].tobytes(),
            digest_size=16).digest()
        yield h


def prefix_key(tokens, block_size, adapter_id=0):
    """Chain digests over the FULL blocks of `tokens`: digest `i` is
    blake2b(digest[i-1] ‖ block_i_tokens), seeded with an adapter-id
    salt (0 — the null/base adapter — seeds empty), so a digest names
    a block's content AND its whole prefix AND the adapter whose
    projections wrote its KV — position/prefix/tenant-safe by
    construction. Returns a tuple of 16-byte digests, one per full
    block (the ragged tail contributes nothing).

    This is the ONE hashing truth shared by the prefix cache
    (`PagedKVCache.match_prefix`/`register_prefix` key their block map
    with these digests) and the fleet router
    (`inference.fleet.ServingFleet` steers a request to the replica
    whose cache owns the deepest digest of its prompt) — factored out
    so the two can never drift: a router key IS a cache key."""
    return tuple(iter_prefix_key(tokens, block_size, adapter_id))


def _best_of_n_intake(eng, sampling_params, n, counter):
    """Shared best-of-n validation + None-seed RANGE claim (engine and
    fleet editions both run this, so the checks and the seed-claim
    invariant can never drift between them). `eng` is the serving
    engine (any fleet replica — they're homogeneous), `counter` the
    caller's deterministic seed counter. Returns (params, base,
    advanced counter); advancing by one instead of n would hand seeds
    base+1..base+n-1 out again to later None-seed requests, replaying
    candidates."""
    if n < 1:
        raise ValueError(f"need n >= 1 candidates, got {n}")
    if not eng.sampling:
        raise ValueError(
            "best_of_n needs sampling=True engines "
            "(GenerationEngine(sampling=True); fleets pass it in "
            "engine_options) — n greedy lanes would be n identical "
            "candidates")
    if not eng.enable_prefix_cache:
        raise ValueError(
            "best_of_n needs the prefix cache — "
            "without it every candidate re-prefills the prompt")
    params = eng._check_sampling(
        sampling_params if sampling_params is not None
        else SamplingParams())
    if params.greedy:
        raise ValueError(
            "best_of_n needs temperature > 0 — greedy candidates "
            "would all be the same continuation")
    if params.seed is None:
        return params, counter, counter + int(n)
    return params, params.seed, counter


def _best_of_n_fanout(add, run, params, n, base):
    """The shared best-of-n candidate loop (engine AND fleet edition
    call this, so the fan-out protocol can never drift between them):
    candidate 0 is served to completion FIRST — its prefill writes and
    registers the prompt's full blocks once — then candidates 1..n-1
    admit against the warm prefix, seeds `base..base+n-1`. Returns
    (candidates in seed order, bystander finishes the two run() calls
    collected along the way)."""
    ids = [add(params.with_seed(base))]
    stash = run()
    for i in range(1, int(n)):
        ids.append(add(params.with_seed(base + i)))
    stash.update(run())
    out = [stash.pop(i) for i in ids]
    if any(c is None for c in out):
        # a candidate was load-shed at admission (max_queue pressure
        # with no lower-priority victim) — a silent None in the
        # returned list would violate the n-candidates contract
        raise RuntimeError(
            f"best_of_n: {sum(c is None for c in out)} of {n} "
            "candidates were shed at admission under max_queue "
            "pressure — serve best_of_n with queue headroom for n "
            "candidates (or raise max_queue)")
    return out, stash


def _wrap_pool(pool):
    """A pool (or a block table) as the step functions take it; None
    where the spec keeps one pool and this is the place of the other, or
    keeps no paged cache at all."""
    return None if pool is None else Tensor._wrap(pool)


def _pool_array(pool):
    return None if pool is None else pool._array


class PagedKVCache:
    """Global paged KV pool + host-side block allocator, refcounts, and
    hash-based prefix cache.

    kpool/vpool: `[layers, num_blocks, block_size, kv_heads, head_dim]`
    device arrays, functionally updated by the compiled steps (donated,
    so updated in place on device). Block 0 is reserved as the null
    block — `allocate` never returns it. `layers` are the layers that
    keep K and V, `kv_heads` what they keep (a model's spec says both).
    A spec that keeps ONE latent row a token a layer (`kv_heads` None)
    gets one pool `[layers, num_blocks, block_size, head_dim]` — no head
    axis, and `vpool` is None: the steps thread it as the empty argument
    it is. Everything below the pools (blocks,
    refcounts, prefix hashes, the LRU, copy-on-write) is the same code.

    Beside the paged blocks the same manager holds the slots' state of
    FIXED size (`slot_state`: recurrent layers' windows and state
    matrices), one row a slot in arrays `[layers, 1 + state_rows, ...]`.
    Row 0 is the null row (idle lanes write there); `allocate_state`
    hands a row out ZEROED, `free_state` takes it back. A model none of
    whose layers caches by position has `num_layers` 0: the manager then
    holds NO pool (`kpool` and `vpool` are None, `num_blocks` is 1: the
    null block alone, nothing to allocate) and the state rows are all it
    manages.

    Every live block carries a reference count: `allocate` hands blocks
    out at refcount 1, `share` seats an existing block in another
    owner's table (+1), `free` decrements and only recycles at zero.
    The prefix cache is a chain-hash → block-id map over FULL prompt
    blocks (`register_prefix` publishes them once a prompt's KV is
    completely written; `match_prefix` walks the chain and takes a
    reference on every hit). A cached block whose refcount drops to
    zero is NOT returned to the free list — it parks in an LRU side
    pool, still addressable by hash, and is only evicted (hash dropped,
    block recycled) when `allocate` runs out of truly-free blocks. So
    cache pressure rides the engine's existing stall/retry path: an
    allocation that fails after eviction is the same stall it always
    was."""

    #: Block-recycling surface declared in introspect (the
    #: ENGINE_STEP_DONATION pattern: the framework names its effect
    #: methods, tpu-race TPU203 reads the table — no method-name
    #: strings live in the analyzer). Calling one of these between a
    #: dispatched step and its completion is the zombie-write hazard.
    RACE_RELEASE_METHODS = \
        introspect.ALLOCATOR_RELEASE_EFFECTS["PagedKVCache"]

    def __init__(self, num_layers, num_blocks, block_size, kv_heads,
                 head_dim, dtype=jnp.float32, mesh=None, mp_axis="mp",
                 kv_dtype=None, slot_state=(), state_rows=0):
        if num_layers and num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the null "
                             "block)")
        if not num_layers:
            num_blocks = 1             # no pool: nothing to allocate
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None (fp pools) or 'int8', got "
                f"{kv_dtype!r}")
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.num_layers = int(num_layers)
        self.kv_heads = None if kv_heads is None else int(kv_heads)
        self.head_dim = int(head_dim)
        if self.kv_heads is None and (mesh is not None or kv_dtype):
            raise ValueError("a pool of latent rows has no head axis to "
                             "shard and no int8 grid")
        # int8 per-block-scaled KV (PR 11): the pools store int8 codes
        # and `self.scales` `[layers, num_blocks, 2]` f32 carries each
        # block's symmetric K/V absmax grid (column 0 = K, 1 = V),
        # threaded through every compiled step alongside the pools.
        # `dtype` stays the MODEL compute dtype the attention output
        # casts back to; pool_spec() is still the one layout truth.
        self.kv_dtype = kv_dtype
        self.dtype = dtype
        # tensor-parallel serving: pools sharded on the HEADS axis over
        # the mesh's mp axis (per-shard planes [L, B, bs, H/mp, D]);
        # the block tables stay host-side and replicated, so the
        # allocator/prefix-cache/COW logic below is mesh-oblivious
        self.mesh = mesh
        self.mp_axis = mp_axis if mesh is not None else None
        shape, dt = self.pool_spec()
        if not self.num_layers:
            self.kpool = self.vpool = None
        elif mesh is not None:
            from jax.sharding import NamedSharding

            mp = mesh.shape[mp_axis]
            if self.kv_heads % mp:
                raise ValueError(
                    f"num_heads={kv_heads} not divisible by mp "
                    f"degree {mp} — cannot head-shard the KV pools")
            sharding = NamedSharding(mesh, self.pool_pspec())
            self.kpool = jax.device_put(jnp.zeros(shape, dt), sharding)
            self.vpool = jax.device_put(jnp.zeros(shape, dt), sharding)
        else:
            self.kpool = jnp.zeros(shape, dt)
            self.vpool = None if self.kv_heads is None \
                else jnp.zeros(shape, dt)
        if self.kv_dtype == "int8":
            from paddle_tpu.ops.paged_attention import KV_QUANT_EPS

            self._scale_eps = KV_QUANT_EPS
            scales = jnp.full(self.scale_spec()[0], KV_QUANT_EPS,
                              self.scale_spec()[1])
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                # per-(layer, block) grids are GLOBAL across the
                # head-sharded pools (the steps pmax-fold the shards'
                # absmax), so the array replicates on the mesh
                scales = jax.device_put(
                    scales, NamedSharding(mesh, PartitionSpec()))
            self.scales = scales
        else:
            self.scales = None
        # LIFO free list: recently-freed (cache-warm) blocks reused first
        self._free = list(range(num_blocks - 1, 0, -1))
        self._ref = [0] * self.num_blocks
        self._ref[0] = 1               # null block: permanently held
        self._block_of = {}            # chain hash -> cached block id
        self._hash_of = {}             # cached block id -> chain hash
        # refcount-zero cached blocks, LRU order (oldest first): the
        # reclaimable tail of the prefix cache
        self._evictable = OrderedDict()   # block id -> chain hash
        # optional observer called with each block id the allocator
        # reclaims from the prefix cache (engine flight recorder)
        self.on_evict = None
        # state of fixed size a slot: rows 1..state_rows, LIFO free list
        self.state = tuple(
            jnp.zeros((s.layers, 1 + int(state_rows)) + tuple(s.shape),
                      s.dtype) for s in slot_state)
        self.state_rows = int(state_rows) if slot_state else 0
        self._free_rows = list(range(self.state_rows, 0, -1))
        self._zero_row = None

    # -- state of fixed size a slot ----------------------------------------
    @property
    def state_rows_used(self):
        return self.state_rows - len(self._free_rows)

    def state_nbytes(self):
        return sum(int(a.nbytes) for a in self.state)

    def allocate_state(self):
        """A row of the state arrays, ZEROED in every layer (a recurrent
        layer starts a prompt from nought, whoever sat there before), or
        None when every row is held; 0 where the model keeps no such
        state."""
        if not self.state:
            return 0
        if not self._free_rows:
            return None
        row = self._free_rows.pop()
        if self._zero_row is None:
            def engine_state_zero_row(arrays, r):
                return tuple(a.at[:, r].set(jnp.zeros((), a.dtype))
                             for a in arrays)

            self._zero_row = jax.jit(
                engine_state_zero_row,
                donate_argnums=(0,) if jax.default_backend() != "cpu"
                else ())
        self.state = self._zero_row(self.state, jnp.int32(row))
        return row

    def free_state(self, row):
        if not self.state or not row:
            return
        if row in self._free_rows:
            raise RuntimeError(f"double free of state row {row}")
        self._free_rows.append(int(row))

    def pool_spec(self):
        """The ONE source of truth for a pool plane's logical
        `([layers, blocks, block_size, kv_heads, head_dim], dtype)`: the
        sharded and unsharded constructors (and anything rebuilding a
        pool-shaped buffer) derive it from here, so the two layouts
        cannot drift. Under `kv_dtype='int8'` the dtype is int8 (the
        codes); the per-block grids live in `scale_spec()`."""
        dt = jnp.int8 if self.kv_dtype == "int8" else self.dtype
        row = (self.head_dim,) if self.kv_heads is None \
            else (self.kv_heads, self.head_dim)
        return ((self.num_layers, self.num_blocks, self.block_size)
                + row, dt)

    def scale_spec(self):
        """Layout of the int8 pools' per-block scale array:
        `([layers, blocks, 2], float32)` — column 0 is the K grid,
        column 1 the V grid. None for fp pools."""
        if self.kv_dtype != "int8":
            return None
        return ((self.num_layers, self.num_blocks, 2), jnp.float32)

    def pool_nbytes(self):
        """Total bytes of the paged KV state: both pool planes plus
        (int8 mode) the per-block scale array — the number the
        capacity claim and the `engine_pool_bytes` gauge report."""
        n = sum(int(p.nbytes) for p in (self.kpool, self.vpool)
                if p is not None)
        if self.scales is not None:
            n += int(self.scales.nbytes)
        return n

    def pool_pspec(self):
        """PartitionSpec sharding the pools' HEADS axis over the mp
        mesh axis (empty spec — replicated/single-chip — without a
        mesh). Shared by the constructor, the engine's shard_map
        in/out specs, and the donated-step sharding contract."""
        from jax.sharding import PartitionSpec

        if self.mp_axis is None:
            return PartitionSpec()
        return PartitionSpec(None, None, None, self.mp_axis, None)

    @property
    def num_free(self):
        """Blocks allocatable right now: truly free + evictable cached
        (the prefix cache's reclaimable tail)."""
        return len(self._free) + len(self._evictable)

    @property
    def num_cached_blocks(self):
        """Blocks the prefix cache can currently serve hits from."""
        return len(self._block_of)

    def refcount(self, block):
        return self._ref[block]

    def allocate(self, n):
        """n pool blocks at refcount 1, or None (caller stalls/retries)
        if the pool cannot serve them even after evicting every
        refcount-zero prefix-cache block (LRU first)."""
        if n > self.num_free:
            return None
        take = min(n, len(self._free))
        got = self._free[-take:] if take else []
        del self._free[-take:]
        while len(got) < n:            # reclaim cold cache blocks
            block, h = self._evictable.popitem(last=False)
            del self._block_of[h]
            del self._hash_of[block]
            got.append(block)
            if self.on_evict is not None:
                # observability hook (engine flight recorder): a warm
                # prefix block just lost its cached content
                self.on_evict(block)
        for b in got:
            self._ref[b] = 1
        if got and self.scales is not None:
            # a recycled block's grid belongs to its PREVIOUS tenant:
            # reset to the floor so the new owner's first write sets a
            # fresh grid instead of quantizing against stale scales
            self.scales = self.scales.at[:, np.asarray(got), :].set(
                self._scale_eps)
        return got

    def free(self, blocks):
        """Drop one reference per block; recycle at refcount zero
        (cached blocks park in the evictable LRU instead of the free
        list). Raises on the null block and on double-free — a
        scheduler bug must fail loudly, not silently double-allocate a
        live block. Blocks are processed deepest-first so that when a
        finished request's chain goes cold, LRU eviction reclaims the
        deepest (least re-usable) links before their parents."""
        for b in reversed(list(blocks)):
            b = int(b)
            if b == 0:
                raise ValueError("refusing to free the null block 0")
            if self._ref[b] <= 0:
                raise RuntimeError(
                    f"double free of pool block {b} (refcount already "
                    "0) — a live block would have been handed out twice")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                h = self._hash_of.get(b)
                if h is None:
                    self._free.append(b)
                else:
                    self._evictable[b] = h   # newest LRU entry

    def share(self, blocks):
        """Take an extra reference on live blocks (seating them
        read-only in another slot's table)."""
        for b in blocks:
            if self._ref[b] <= 0:
                raise RuntimeError(f"cannot share dead block {b}")
            self._ref[b] += 1

    def needs_cow(self, block):
        """True when writing into `block` would corrupt state another
        owner (a slot OR the prefix cache) still reads: shared
        refcount, or registered as cached prefix content."""
        return self._ref[block] > 1 or block in self._hash_of

    def match_prefix(self, tokens, adapter_id=0):
        """Longest cached block-aligned prefix of `tokens` under
        `adapter_id`'s salted chain: walks the `prefix_key` digests
        over full blocks, takes a reference on every hit (reviving
        evictable ones), and returns (blocks, hit_tokens). Hit tokens
        never need recomputing — their KV is already in the pool,
        byte-for-byte what this (prompt, adapter)'s prefill would
        write; a different adapter's chain can never alias it."""
        blocks = []
        for h in iter_prefix_key(tokens, self.block_size, adapter_id):
            b = self._block_of.get(h)
            if b is None:
                break
            if self._ref[b] == 0:
                del self._evictable[b]     # revive: live again
            self._ref[b] += 1
            blocks.append(b)
        return blocks, len(blocks) * self.block_size

    def warm_prefix_tokens(self, tokens, keys=None, adapter_id=0):
        """Prompt tokens a `match_prefix` would serve from this cache
        RIGHT NOW — a read-only peek (no references taken, evictable
        entries left parked) for the fleet router's affinity decision:
        the replica owning the deepest warm chain gets the request.
        Same digests as `match_prefix` (both walk the `prefix_key`
        chain), so a router hit is exactly a cache hit. `keys` lets a
        caller probing SEVERAL caches (the router) hash the prompt
        once and reuse the digests."""
        hit = 0
        for h in (keys if keys is not None
                  else iter_prefix_key(tokens, self.block_size,
                                       adapter_id)):
            if h not in self._block_of:
                break
            hit += self.block_size
        return hit

    def register_prefix(self, tokens, blocks, adapter_id=0):
        """Publish a fully-prefilled prompt's FULL blocks into the
        prefix map under `adapter_id`'s salted chain (call only once
        every one of those blocks' KV rows is written). First writer
        wins: a hash that is already mapped keeps its original block
        and the racing copy stays private to its slot. Returns the
        number of blocks newly cached."""
        added = 0
        keys = iter_prefix_key(tokens, self.block_size, adapter_id)
        for h, blk in zip(keys, blocks):
            b = int(blk)
            if h in self._block_of or b in self._hash_of:
                continue
            self._block_of[h] = b
            self._hash_of[b] = h
            added += 1
        return added

    def leak_check(self):
        """Block-accounting audit for a QUIESCED pool (no live slots):
        every non-null block must either sit on the free list or be a
        refcount-zero prefix-cache block parked in the evictable LRU.
        Returns the list of leaked block ids — blocks still referenced
        or unaccounted for. `GenerationEngine.drain()` asserts this
        empty: it catches the leak class the allocator's double-free
        hardening cannot see (a block freed zero times instead of
        twice)."""
        free = set(self._free)
        leaked = []
        for b in range(1, self.num_blocks):
            if self._ref[b] == 0 and (
                    b in free or b in self._evictable):
                continue
            leaked.append(b)
        return leaked

    def state_leak_check(self):
        """State rows still held on a quiesced pool."""
        return sorted(set(range(1, self.state_rows + 1))
                      - set(self._free_rows))


# admission QoS classes, best-served-first; add_request validates
# against this tuple and the TTFT/TPOT histograms are labeled by it
PRIORITY_CLASSES = ("interactive", "standard", "batch")


@dataclass(eq=False)
class Request:
    """One generation request (prompt in, greedy continuation out).
    Identity equality (eq=False): the prompt is an ndarray, and two
    requests with equal content are still distinct requests."""

    req_id: object
    prompt: np.ndarray                 # int32 [plen]
    max_new_tokens: int
    eos_token_id: int = None
    arrived_at: float = None           # perf_counter at add_request
    priority: str = "standard"         # one of PRIORITY_CLASSES
    # disaggregated serving: a prefill-only request runs the prompt to
    # completion, emits its FIRST token, then parks its KV blocks in
    # the engine's handoff buffer (take_handoff) instead of decoding —
    # the fleet moves those blocks into a decode replica's pool
    prefill_only: bool = False
    # multi-tenant adapter serving: the tenant LoRA adapter this
    # request decodes under (0 = the null/base adapter — the plain
    # base model, bit-identical to a no-adapter engine)
    adapter_id: int = 0
    # probabilistic serving: the request's SamplingParams (seed already
    # resolved at intake), or None for the greedy/argmax contract
    sampling: object = None
    # request-scoped tracing: the id every span this request produces
    # carries — minted at intake (engine or fleet) and riding the
    # disaggregated handoff, so one timeline follows the request
    # across replicas. None on a tracing-disabled engine.
    trace_id: object = None


@dataclass(eq=False)
class _Slot:
    """A live decode lane: the request plus its paged-cache footprint.
    Identity equality: `self._slots.index(slot)` must find THIS lane,
    not a content-equal one."""

    req: Request
    blocks: list                       # owned/shared pool block ids
    generated: list = field(default_factory=list)
    last_token_at: float = None        # perf_counter of newest token
    prefill_pos: int = 0               # next prompt position to prefill
    hit_tokens: int = 0                # prefix-cache tokens never computed
    admit_seq: int = 0                 # admission order tiebreak
    adapter_page: int = 0              # adapter-pool page (0 = null)
    state_row: int = 0                 # row of the fixed state (0 = none)
    # per-slot sampling state threaded into the compiled steps as
    # traced per-row data (greedy lanes: 0 / 0 / 1.0 / zero key row)
    temp: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    key_row: object = None             # [2] uint32 base PRNG key
    # the ahead order: programs launched over this lane (decode steps,
    # its prompt's last chunk) whose token the host has not read yet.
    # The lane's blocks, state row and adapter page go back only at 0.
    ahead: int = 0
    # finish reason, set when the host read the lane's last token (an
    # EOS) while a later step over the lane was still unread: the
    # result is out, the lane stays seated until that step completes
    done: str = None

    @property
    def prefilling(self):
        """Still has prompt tokens to push through the chunked
        prefill (a full-prefix hit skips straight past this)."""
        return self.prefill_pos < len(self.req.prompt)

    @property
    def feed_pos(self):
        """Absolute position of the token the next decode step feeds:
        the newest token DISPATCHED. With nothing unread (`ahead` 0)
        and `generated` non-empty that is the newest generated token;
        empty `generated` is the full-prefix-hit state, where the
        first decode feeds the LAST PROMPT token (its logits produce
        the first generated token — the one step a full hit cannot
        skip). A position is a count: the host knows it without the
        unread tokens themselves."""
        return len(self.req.prompt) + len(self.generated) \
            + self.ahead - 1

    @property
    def dispatched(self):
        """Generated tokens launched so far, read or not: what the
        length test and the scheduler count."""
        return len(self.generated) + self.ahead

    @property
    def feed_token(self):
        return self.generated[-1] if self.generated \
            else int(self.req.prompt[-1])


@dataclass(eq=False)
class _InFlight:
    """One launched program whose device output has NOT been read
    yet: a decode/verify step, or (the ahead order) the last chunk of
    a prompt. The pipelined core leaves exactly one decode step of
    these across `step()` calls (depth 1 — see DESIGN_DECISIONS r21);
    the serial core completes it inline within the same step."""

    out: object                        # device output(s), not yet read
    runnable: list                     # lane indices dispatched
    slots: list                        # the _Slot objects, snapshotted
    drafts: dict = None                # lane -> draft (verify steps)
    counters: object = None            # the step's counters, on device
    first: object = None               # a decode step that carried a
    #                                    chunk: the record of the first
    #                                    token of the prompt it ended
    t_dec: float = 0.0                 # perf_counter at dispatch
    t_span: int = 0                    # now_us at schedule end
    seq: int = 0                       # pipeline sequence number


class GenerationEngine:
    """Iteration-level scheduler + compiled steps over a paged cache.

        engine = GenerationEngine(model, num_slots=8, block_size=16)
        engine.add_request([1, 2, 3], max_new_tokens=32)
        ...                                  # add more any time
        results = engine.run()               # {req_id: full token list}

    `model` is anything with a `serving_spec()`
    (`inference/serving_spec.py`): the sizes, what state a slot holds per
    kind of layer (paged K/V, state of fixed size), the step functions
    the compiled programs call, and what the engine must refuse for it.
    Generation is eval-mode; the engine refuses a model left in training
    mode with active dropout, same as `generate(use_cache=True)`.
    """

    #: Dispatch/complete surface of the pipelined step orders, declared
    #: in introspect so tpu-race TPU203 can order allocator releases
    #: against in-flight device steps (see RACE_RELEASE_METHODS on
    #: PagedKVCache / PagedAdapterPool).
    RACE_DISPATCH_METHODS = introspect.ENGINE_DISPATCH_EFFECTS
    RACE_COMPLETE_CALLS = introspect.STEP_COMPLETE_CALLS

    def __init__(self, model, num_slots=8, block_size=16,
                 num_blocks=None,
                 max_model_len=None, eos_token_id=None, donate=None,
                 registry=None, attention_backend=None,
                 prefill_chunk=128, enable_prefix_cache=None,
                 max_queue=None, spec_decode_k=0, drafter=None,
                 mesh=None, mp_degree=None, kv_dtype=None,
                 weight_dtype=None, adapters=None,
                 adapter_pool_pages=None, sampling=None,
                 tracing=None, trace_capacity=4096,
                 flight_capacity=256, async_core=None):
        from paddle_tpu.ops.paged_attention import copy_pool_block

        spec = self.spec = model.serving_spec()
        if model.training and spec.dropout > 0:
            raise ValueError("GenerationEngine decodes deterministically "
                             "(no dropout) — call model.eval() first")
        self.model = model
        self.num_slots = int(num_slots)
        self.block_size = int(block_size)
        # tensor-parallel serving mesh: mp=1 (the default) is exactly
        # the single-chip engine — no mesh, no shard_map, no resharding.
        self._resolve_mesh(mesh, mp_degree)
        self.max_model_len = int(max_model_len or spec.max_seq_len)
        if self.max_model_len > spec.max_seq_len:
            raise ValueError(
                f"max_model_len={self.max_model_len} exceeds the "
                f"model's position table ({spec.max_seq_len})")
        # a model that keeps no paged cache (`spec.paged_kv` None) has no
        # blocks: no tables are built, nothing is allocated a step, and
        # the one limit on a context is `max_model_len`
        self._paged = spec.paged_kv is not None
        self.max_blocks = math.ceil(
            self.max_model_len / self.block_size) if self._paged else 0
        self.eos_token_id = eos_token_id
        self.max_queue = None if max_queue is None else int(max_queue)
        # prefill runs the prompt through a FIXED-shape compiled chunk
        # step, one chunk per scheduler iteration — long admissions
        # interleave with decode instead of monopolizing an iteration,
        # and the chunk shape is the only prefill program there is
        self.prefill_chunk = max(
            1, min(int(prefill_chunk), self.max_model_len))
        if enable_prefix_cache is None:
            enable_prefix_cache = "prefix_cache" not in spec.refuses
        if enable_prefix_cache:
            self._refuse("prefix_cache")
        self.enable_prefix_cache = bool(enable_prefix_cache)
        # quantized serving (PR 11): kv_dtype='int8' stores the paged
        # pools as int8 codes + per-block scales (halves the HBM bytes
        # every decode step streams and doubles effective prefix-cache
        # capacity); weight_dtype='int8' serves qkv/out/fc1/fc2 as
        # int8 + per-channel scales, dequantized inside the compiled
        # steps. None keeps today's fp path BIT-identical (the fp path
        # is the absence of the knob, not a named dtype).
        for name, requested in (("kv_dtype", kv_dtype),
                                ("weight_dtype", weight_dtype)):
            if requested not in (None, "int8"):
                raise ValueError(
                    f"{name} must be None or 'int8', got {requested!r}")
        self.kv_dtype = kv_dtype
        self.weight_dtype = weight_dtype
        if self.kv_dtype:
            self._refuse("kv_int8")
        if self.weight_dtype:
            self._refuse("weight_int8")
        # probabilistic serving (PR 15): sampling=True threads per-slot
        # SamplingParams (temperature/top-k/top-p + a [slots, 2] uint32
        # key row) through every compiled step as traced DATA. Off (the
        # default) threads nothing — the engine's programs stay
        # byte-identical to the pre-sampling ones.
        self.sampling = bool(sampling)
        self._seed_counter = 0
        # request-scoped tracing (PR 17): host-side spans ONLY — no
        # tracing state ever becomes a compiled-program argument, so a
        # tracing-enabled engine runs byte-identical programs to a
        # disabled one (the sampling=False precedent, held trivially
        # by construction).
        self.tracing = bool(tracing)
        self.tracer = TraceRecorder(capacity=trace_capacity) \
            if self.tracing else None
        # pipelined engine core: `step()` returns with ONE decode step
        # launched and unread, so the host's work (the finish walk,
        # the caller's work between calls, admissions, the prefill
        # chunk, the next schedule) runs behind a device step. Which
        # order that takes follows from the step, not from a knob:
        # plain decode (K = 0) goes AHEAD — step N+1 is launched from
        # step N's tokens where they lie, on the device, before the
        # host reads them (`_step_ahead`); the speculative verify step
        # completes step N first, because its next window's content is
        # the accepted prefix, which only the host's walk knows
        # (`_step_async`). Pure host restructuring: the compiled steps
        # are byte-identical and the token streams identical to the
        # serial order (`async_core=False`, the parity tests' foil).
        # A model whose spec refuses the pipelined core is served in
        # the serial order unless the caller asked for the core outright.
        self.async_core = bool(async_core) if async_core is not None \
            else "async_core" not in spec.refuses
        if self.async_core:
            self._refuse("async_core")
        self._inflight = None          # the one unread decode step
        self._first = None             # a prompt's last chunk, unread
        self._ahead = None             # (helper thread, results dict)
        self._next_drafts = {}         # slot -> precomputed draft
        self._step_seq = 0
        # the flight recorder and the step-phase clock are ALWAYS on:
        # both are bounded host-side bookkeeping (a few appends /
        # perf_counter calls per step) and they feed the always-on
        # leak-audit postmortem and host-gap histograms
        self.flight = FlightRecorder(capacity=flight_capacity)
        self._phases = PhaseTimer()
        # and so are the process's pause hooks (a collection inside a
        # `host.gc` span, compiles counted by function) and the stall
        # detector that reads them: a step far over the median of the
        # steps before it is counted under its owner (`_note_stall`)
        self._pauses = install_host_pause_hooks()
        self._stalls = StallDetector()
        # the newest compiled step's output: a launch that finds it
        # ready found the chip idle (`_dispatch_step`)
        self._last_launch = None
        self._launch_series = {}
        # default pool covers every slot at full context (+ null block):
        # correctness-first; serving deployments size it to live-context
        # expectations and lean on the stall/retry path under pressure
        kv = spec.paged_kv
        self.cache = PagedKVCache(
            kv.layers if self._paged else 0,
            int(num_blocks or 1 + self.num_slots * self.max_blocks),
            self.block_size, kv.kv_heads if self._paged else None,
            kv.head_dim if self._paged else 0,
            dtype=spec.dtype, mesh=self.mesh, kv_dtype=self.kv_dtype,
            slot_state=spec.slot_state, state_rows=self.num_slots)
        self.cache.on_evict = lambda b: self.flight.record(
            "prefix_evict", block=b)
        # multi-tenant adapter serving (paged batched-LoRA): an
        # AdapterRegistry (or a prebuilt PagedAdapterPool) turns on
        # per-slot adapter ids through every compiled step. None (the
        # default) threads nothing — the engine's programs are
        # BIT-identical to the pre-adapter ones.
        self._resolve_adapters(adapters, adapter_pool_pages, donate)
        # paged-attention kernel backend: resolved ONCE to a concrete
        # backend so the compiled decode step is fixed — `auto` never
        # changes mid-engine (decode traces == 1)
        requested = attention_backend or "auto"
        self.attention_backend_requested = requested
        self.attention_backend = spec.attention_backend(
            requested, self.block_size, self.mp_degree)
        # speculative decoding: K drafted tokens verified per compiled
        # step. K=0 builds today's one-token decode step unchanged.
        k = int(spec_decode_k)
        if k < 0:
            raise ValueError(f"spec_decode_k must be >= 0, got {k}")
        self.spec_decode_k = k
        if k > 0:
            self._refuse("spec_decode")
            from paddle_tpu.inference.speculative import NgramDrafter

            self.drafter = drafter if drafter is not None \
                else NgramDrafter()
        else:
            self.drafter = None
        # the state threading of TrainStep: params+buffers ride as traced
        # args, so weight updates are visible without retracing
        self._state = dedup_params(list(model.parameters())) + \
            model_buffers(model)
        # int8 weight serving: qkv/out/fc1/fc2 ride the steps as
        # (int8 codes, per-output-channel scale) pairs and dequantize
        # INSIDE the compiled step (fp32 accumulation pinned by
        # tpu-verify TPU103) — the per-step HBM weight read shrinks to
        # the int8 bytes. `_qmeta[i]` is the entry's dequant target
        # dtype (None = unquantized); quantize_weights() (re)builds
        # the snapshot.
        self._wq_plan = spec.weight_quant_plan() \
            if self.weight_dtype == "int8" else {}
        self._qmeta = [None] * len(self._state)
        self._q_arrays = None
        # tensor parallel: a serving-time SNAPSHOT of the state, each
        # array device_put onto the mesh with its Megatron
        # column-parallel spec (qkv weights head-grouped first); the
        # specs double as the shard_map in_specs. refresh_weights()
        # re-snapshots after a live weight update.
        self._tp_arrays = self._tp_specs = None
        self.quantize_weights()
        donate = (jax.default_backend() != "cpu") if donate is None \
            else donate
        # the one donation table both analyzers and the engine read:
        # introspect.ENGINE_STEP_DONATION (tpu-lint TPU004 resolves
        # the constants, tpu-verify TPU101 checks the lowered aliases)
        # (a model with state of fixed size donates that too: it rides
        # as one tuple right after the pools)
        self._donate_argnums = (
            introspect.ENGINE_STATEFUL_STEP_DONATE_ARGNUMS
            if self.cache.state
            else introspect.ENGINE_STEP_DONATE_ARGNUMS) if donate else ()
        # with speculation on, the verify step IS the engine's decode
        # step: same probe, same donation, same traces==1 contract —
        # one program per (backend, K). Under sampling the verify step
        # leads with TWO replicated outputs (choices, accepts).
        self._decode_pure = count_traces(
            self._build_verify() if k > 0 else self._build_decode())
        self._decode_n_out = 2 if (k > 0 and self.sampling) else 1
        self._decode = jax.jit(
            self._decode_pure, donate_argnums=self._donate_argnums,
            out_shardings=self._step_out_shardings(self._decode_n_out))
        self._prefill_pure = count_traces(self._build_prefill_chunk())
        self._prefill = jax.jit(self._prefill_pure,
                                donate_argnums=self._donate_argnums,
                                out_shardings=self._step_out_shardings(1))
        # a model whose spec offers one step for a chunk's rows and the
        # decode rows together: the ahead order launches it in place of
        # the two programs in an iteration that holds both kinds of work
        # (`_step_ahead`). It is a decode step too: `decode_traces`
        # counts its traces.
        self._decode_pures = [self._decode_pure]
        self._fused = None
        if spec.offers_decode_with_chunk and self._goes_ahead:
            if self.kv_dtype == "int8" or self._mp_axis is not None \
                    or self.adapter_pool is not None:
                raise ValueError(
                    "`decode_with_chunk` takes neither int8 KV, a mesh "
                    "nor adapters: a spec that offers it refuses them")
            pure = count_traces(self._build_decode_with_chunk())
            self._decode_pures.append(pure)
            self._fused = jax.jit(pure,
                                  donate_argnums=self._donate_argnums)
        # copy-on-write promotion: one tiny compiled gather/scatter,
        # traced src/dst so every COW reuses the same program
        cow = count_traces(copy_pool_block)
        cow.__name__ = "engine_cow_copy"
        self._cow_pure = cow
        self._cow = jax.jit(
            cow,
            donate_argnums=introspect.ENGINE_COW_DONATE_ARGNUMS
            if donate else (),
            out_shardings=self._step_out_shardings(0))
        # the ahead order's feed: one tiny program picks each lane's
        # next input token from where it lies — the previous decode
        # step's output, the output of the chunk that ended a prompt
        # this iteration, or the host's row for a lane whose newest
        # token the host has read. The decode step itself is untouched.
        def engine_feed_select(host, from_prev, first_lane, prev, first):
            col = jnp.where(from_prev, prev, host[:, 0])
            lanes = jnp.arange(col.shape[0], dtype=jnp.int32)
            return jnp.where(lanes == first_lane, first, col)[:, None]

        self._feed_select = jax.jit(
            engine_feed_select,
            out_shardings=None if self.mesh is None
            else self._step_out_shardings(1)[0])
        self._no_unread = None         # `_unread_outputs`' zeros
        self._queues = {p: deque() for p in PRIORITY_CLASSES}
        self._slots = [None] * self.num_slots
        self._results = {}
        self._handoffs = {}            # req_id -> (blocks, hit_tokens)
        self._draining = False
        self._auto_id = 0
        self._admit_counter = 0
        self.tokens_generated = 0
        self.prefix_hit_tokens = 0
        self.decode_steps = 0
        # of those, the steps launched while the previous was unread,
        # and the tokens computed past an EOS and discarded
        self.decode_steps_ahead = 0
        self.overshoot_tokens = 0
        self._t_read = 0.0             # perf_counter of the newest read
        # the model's own counters, summed (or the largest) over every
        # decode step: `spec.step_counters` names them
        self.step_counter_totals = {n: 0 for n, _ in spec.step_counters}
        # and over every prefill chunk: `spec.chunk_counters`
        self._chunk_totals = {n: 0 for n, _ in spec.chunk_counters}
        self._chunk_counters = []      # chunks' counters, still unread
        # serving telemetry: per-engine registry by default so counter
        # exactness survives multiple engines in one process; pass
        # observability.get_registry() to publish on the process default
        self.metrics = registry if registry is not None \
            else MetricsRegistry()
        self._init_metrics()

    # -- tensor-parallel serving (mesh) ------------------------------------
    def _refuse(self, feature):
        """Raise, with the model's reason, where its spec lists `feature`
        among what must not be served for it."""
        reason = self.spec.refuses.get(feature)
        if reason:
            raise ValueError(
                f"{feature} is not served for this model: {reason}")

    def _resolve_mesh(self, mesh, mp_degree):
        """Resolve (mesh, mp_degree) to the serving mesh. An explicit
        mesh must agree with `mp_degree` and must carry an 'mp' axis.
        Degree 1 means single-chip (no mesh). The model's spec
        validates its divisibility constraints up front."""
        from paddle_tpu.distributed.topology import serving_mesh

        requested = int(mp_degree) if mp_degree is not None else None
        if mesh is not None:
            if "mp" not in mesh.axis_names:
                raise ValueError(
                    "serving mesh needs an 'mp' axis — build one with "
                    "distributed.serving_mesh(mp) or "
                    "HybridCommunicateGroup.for_serving(mp).get_mesh()")
            mesh_mp = mesh.shape["mp"]
            if requested is not None and requested != mesh_mp:
                raise ValueError(
                    f"mesh mp axis has {mesh_mp} devices but "
                    f"mp_degree={requested} — drop one of the two")
            self.mp_degree = int(mesh_mp)
            self.mesh = mesh if self.mp_degree > 1 else None
        else:
            self.mp_degree = 1 if requested is None else int(requested)
            if self.mp_degree < 1:
                raise ValueError(
                    f"mp degree must be >= 1, got {self.mp_degree}")
            self.mesh = None if self.mp_degree == 1 else serving_mesh(
                self.mp_degree)
        if self.mp_degree > 1:
            # fail HERE with the shape story, not deep in a per-shard
            # reshape (re-checked for an explicitly passed mesh too)
            self.spec.check_mesh(self.mp_degree,
                                 list(self.mesh.devices.reshape(-1)))
        self._mp_axis = "mp" if self.mp_degree > 1 else None

    # -- probabilistic serving (per-slot sampling) -------------------------
    def _check_sampling(self, params):
        """Validate intake sampling params: None always passes (the
        greedy contract); anything else needs the sampling subsystem
        on. Returns the params unchanged (seed may still be None —
        `_resolve_seed` assigns one)."""
        if params is None:
            return None
        if not isinstance(params, SamplingParams):
            raise TypeError(
                "sampling_params takes a SamplingParams, got "
                f"{type(params).__name__}")
        if not self.sampling:
            raise ValueError(
                "sampling_params needs GenerationEngine(sampling=True) "
                "— this engine decodes greedily")
        return params

    def _resolve_seed(self, params):
        """Pin a request's seed: explicit seeds pass through, None
        draws from the engine's deterministic counter — same admission
        order, same seeds, same tokens."""
        if params is None or params.seed is not None:
            return params
        seed = self._seed_counter
        self._seed_counter += 1
        return params.with_seed(seed)

    @staticmethod
    def _slot_sampling_fields(req):
        """The per-slot sampling state a request seats with: greedy
        (or param-less) lanes ride the inert defaults (temp 0, zero
        key row)."""
        p = req.sampling
        if p is None or p.greedy:
            return {}
        return dict(temp=float(p.temperature), top_k=int(p.top_k),
                    top_p=float(p.top_p),
                    key_row=_sampling_key_row(p.seed))

    def _sampling_host_rows(self):
        """The four per-row sampling arrays of one decode/verify
        dispatch as RAW NUMPY: [slots] temperature/top-k/top-p plus
        the [slots, 2] uint32 key rows. Idle and greedy lanes ride
        temp 0 / zero keys — their sampled columns are garbage the
        argmax select (device) and the host both ignore."""
        temps = np.zeros(self.num_slots, np.float32)
        tks = np.zeros(self.num_slots, np.int32)
        tps = np.ones(self.num_slots, np.float32)
        keys = np.zeros((self.num_slots, 2), np.uint32)
        for i, slot in enumerate(self._slots):
            if slot is None or slot.key_row is None:
                continue
            temps[i] = slot.temp
            tks[i] = slot.top_k
            tps[i] = slot.top_p
            keys[i] = slot.key_row
        return [temps, tks, tps, keys]

    def _sampling_host_args(self):
        """`_sampling_host_rows` as device arrays (the prefill paths'
        per-dispatch transfer; the decode paths batch the rows through
        `_put_host_args` instead)."""
        return [jnp.asarray(a) for a in self._sampling_host_rows()]

    def _put_host_args(self, rows):
        """Move one step's dynamic host rows to the device. Serial
        order: one `jnp.asarray` per row, in row order. Pipelined
        orders: ONE fused `jax.device_put` over the whole tree
        (positions, draft windows, sampling rows, page rows ride a
        single transfer instead of 3-8 round trips). The leaf avals
        are identical either way, so the compiled step programs — and
        TRACE_BASELINE.json — cannot move."""
        if not self.async_core:
            return [None if a is None else jnp.asarray(a) for a in rows]
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            return list(jax.device_put(
                tuple(rows), NamedSharding(self.mesh, PartitionSpec())))
        return list(jax.device_put(tuple(rows)))

    @staticmethod
    def _sampling_host_args_one(slot):
        """[1]-row edition of `_sampling_host_args` for the prefill
        steps (one slot per dispatch, like the adapter page row)."""
        greedy = slot.key_row is None
        return [jnp.asarray(np.asarray(
                    [0.0 if greedy else slot.temp], np.float32)),
                jnp.asarray(np.asarray(
                    [0 if greedy else slot.top_k], np.int32)),
                jnp.asarray(np.asarray(
                    [1.0 if greedy else slot.top_p], np.float32)),
                jnp.asarray(np.zeros((1, 2), np.uint32) if greedy
                            else slot.key_row[None])]

    # -- multi-tenant adapter serving (paged batched-LoRA) -----------------
    def _resolve_adapters(self, adapters, pages, donate):
        """Wire the paged adapter pool: an AdapterRegistry builds a
        pool on this engine's mesh (`adapter_pool_pages` pages,
        default 1 + num_slots so a full batch of distinct tenants
        never stalls); a prebuilt PagedAdapterPool is adopted after a
        mesh/geometry check. None disables the subsystem entirely."""
        if adapters is None:
            if pages is not None:
                raise ValueError(
                    "adapter_pool_pages needs adapters= (a registry "
                    "or pool) — pages of nothing would be a no-op")
            self.adapter_pool = None
            return
        from paddle_tpu.adapters import AdapterRegistry, \
            PagedAdapterPool

        geometry = self.spec.adapter_geometry()
        if geometry is None:
            raise ValueError("this model's serving steps take no "
                             "adapters")

        if isinstance(adapters, PagedAdapterPool):
            if pages is not None:
                raise ValueError("adapter_pool_pages conflicts with a "
                                 "prebuilt PagedAdapterPool")
            if adapters.mesh is not self.mesh:
                raise ValueError(
                    "the prebuilt adapter pool's mesh differs from "
                    "the engine's — build it with the engine's mesh "
                    "(or pass the registry and let the engine build "
                    "the pool)")
            if adapters._owner is not None \
                    and adapters._owner is not self:
                raise ValueError(
                    "this PagedAdapterPool already pages for another "
                    "engine — paging state (refcounts/LRU/gauges) is "
                    "per-engine. Pass the AdapterRegistry instead and "
                    "let each engine build its own pool (the registry "
                    "is safely shared).")
            pool, reg = adapters, adapters.registry
        elif isinstance(adapters, AdapterRegistry):
            reg = adapters
            pool = PagedAdapterPool(
                reg, num_pages=int(pages) if pages is not None
                else 1 + self.num_slots,
                dtype=self.spec.dtype, mesh=self.mesh, donate=donate)
        else:
            raise TypeError(
                "adapters= takes an AdapterRegistry or a "
                f"PagedAdapterPool, got {type(adapters).__name__}")
        for name, want in geometry.items():
            if getattr(reg, name) != want:
                raise ValueError(
                    f"adapter registry {name}={getattr(reg, name)} "
                    f"does not match the served model's {want}")
        pool._owner = self
        self.adapter_pool = pool

    def _check_adapter(self, adapter_id):
        """Validate an intake adapter id: 0 always passes (null/base);
        anything else needs the adapter subsystem on and the id
        registered."""
        aid = int(adapter_id)
        if aid == 0:
            return 0
        if self.adapter_pool is None:
            raise ValueError(
                f"adapter_id={aid} needs GenerationEngine("
                "adapters=...) — this engine serves the base model "
                "only")
        if not self.adapter_pool.registry.has(aid):
            raise ValueError(f"adapter {aid} is not registered")
        return aid

    def adapter_page_available(self, adapter_id):
        """True when seating a request under `adapter_id` would not
        stall on an adapter page right now — the fleet's placement
        probe (mirrors `free_lanes` for KV headroom)."""
        return self.adapter_pool is None or int(adapter_id) == 0 \
            or self.adapter_pool.can_acquire(adapter_id)

    # -- int8 weight serving ----------------------------------------------
    def quantize_weights(self):
        """(Re)build the served weight snapshot: the tensor-parallel
        mesh placement (mp > 1) and/or the int8 quantized state
        (weight_dtype='int8'). Called by the constructor and by
        `refresh_weights()`; a no-op for the plain fp mp=1 engine,
        which reads the live tensors every step."""
        if self._mp_axis is not None:
            self._tp_arrays, self._tp_specs = self._build_tp_state()
        elif self.weight_dtype == "int8":
            self._q_arrays = self._build_quant_state()

    def _build_quant_state(self):
        """mp=1 int8 snapshot: state entries become (int8, scale)
        pairs per the spec's `weight_quant_plan`, the rest rides live."""
        from paddle_tpu.quantization import quantize_absmax

        arrays = []
        for i, t in enumerate(self._state):
            if id(t) in self._wq_plan:
                q, s = quantize_absmax(t._array, axis=1)
                arrays.append((q, s))
                self._qmeta[i] = t._array.dtype
            else:
                arrays.append(t._array)
        return arrays

    def _materialize_state(self, state_arrays):
        """Inside a compiled step: dequantize the (int8, scale) state
        entries straight to their compute dtype (the dequantize(dtype=)
        seam) so the matmuls run fp with fp32 accumulation while HBM
        holds — and the step reads — int8 bytes."""
        if not self._wq_plan:
            return state_arrays
        from paddle_tpu.quantization import dequantize

        return [dequantize(e[0], e[1], dtype=meta)
                if meta is not None else e
                for e, meta in zip(state_arrays, self._qmeta)]

    def _build_tp_state(self):
        """Shard the model state onto the serving mesh per the spec's
        `tp_plan`.
        Returns (committed arrays, PartitionSpecs) aligned with
        `self._state` — the arrays ride the compiled steps as traced
        args (weight-stationary: placed once, never re-sharded per
        step) and the specs are the steps' shard_map in_specs."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        plan = self.spec.tp_plan()
        arrays, specs = [], []
        for i, t in enumerate(self._state):
            transform, spec = plan.get(id(t), (None, P()))
            a = t._array
            if id(t) in self._wq_plan:
                # quantize on the ORIGINAL layout (per-output-channel
                # scales), then ship codes + scale through the same
                # head-grouping/sharding as the fp weight would take
                from paddle_tpu.quantization import quantize_absmax

                q, s = quantize_absmax(a, axis=1)
                s_tf, s_spec = self._wq_plan[id(t)]
                if transform is not None:
                    q = transform(q)
                if s_tf is not None:
                    s = s_tf(s)
                arrays.append((
                    jax.device_put(q, NamedSharding(self.mesh, spec)),
                    jax.device_put(s, NamedSharding(self.mesh,
                                                    s_spec))))
                specs.append((spec, s_spec))
                self._qmeta[i] = a.dtype
                continue
            if transform is not None:
                a = transform(a)
            arrays.append(
                jax.device_put(a, NamedSharding(self.mesh, spec)))
            specs.append(spec)
        return arrays, specs

    def refresh_weights(self):
        """Re-snapshot the (tensor-parallel and/or int8-quantized)
        serving state from the live model parameters — call after a
        weight update. Plain fp mp=1 engines read the live tensors
        every step and never need this."""
        self.quantize_weights()

    def _step_out_shardings(self, n_repl):
        """Explicit out_shardings for a compiled step's jit: `n_repl`
        replicated leading outputs (token ids) followed by the two
        pool planes at the pool's sharding. None at mp=1 (jit infers).
        At mp>1 this is LOAD-BEARING for donation, not decoration:
        with inferred output shardings jax demotes donate_argnums to
        best-effort `jax.buffer_donor` markers, while matching
        explicit shardings let lowering PIN input/output aliases
        (`tf.aliasing_output`) — the difference between the paged
        pools provably updating in place and XLA merely being allowed
        to. tpu-verify TPU101 gates on the pinned form."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        pool = NamedSharding(self.mesh, self.cache.pool_pspec())
        repl = NamedSharding(self.mesh, P())
        # int8 KV: the per-block scale array trails the pools in every
        # step's outputs, replicated (the steps pmax-fold it exact)
        tail = (repl,) if self.kv_dtype == "int8" else ()
        return (repl,) * n_repl + (pool, pool) + tail

    def _shard_steps(self, fn, n_repl, n_out=1):
        """Wrap a compiled-step body in shard_map over the serving
        mesh: state per `_tp_specs`, pools head-sharded, the `n_repl`
        trailing host args (tokens/positions/tables/sampling rows/...)
        replicated; outputs (`n_out` replicated leading outputs —
        token ids, and under sampling the verify step's
        choices/accepts pair — then sharded pools). Identity at
        mp=1."""
        if self._mp_axis is None:
            return fn
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        pool = self.cache.pool_pspec()
        # int8 KV: the replicated scale array rides between the pools
        # and the host args (inputs) and trails the pools (outputs)
        scales = (P(),) if self.kv_dtype == "int8" else ()
        # adapters: the pool-array tuple rides before the host args
        # (B pages output-sharded, A pages replicated) and the traced
        # per-slot page row is one extra replicated host arg
        lora = () if self.adapter_pool is None \
            else (self.adapter_pool.pool_pspecs(),)
        if lora:
            n_repl += 1
        sharded = shard_map(
            fn, mesh=self.mesh,
            in_specs=(list(self._tp_specs), pool, pool) + scales
            + lora + (P(),) * n_repl,
            out_specs=(P(),) * n_out + (pool, pool) + scales,
            # all-gathered logits/argmax are replicated by
            # construction; the static rep-checker can't prove it
            check_vma=False)
        sharded.__name__ = fn.__name__
        return sharded

    def _init_metrics(self):
        m = self.metrics
        self._m_ttft = m.histogram(
            "engine_ttft_seconds",
            "Request arrival to first generated token (includes queue "
            "wait and prefill), labeled by QoS priority class.",
            labelnames=("priority",), buckets=LATENCY_BUCKETS)
        self._m_tpot = m.histogram(
            "engine_tpot_seconds",
            "Per-output-token latency, labeled by QoS priority class: "
            "time since the slot's PREVIOUS token, so block-stall "
            "waits show up (not just the producing iteration's wall "
            "time). A request that only ever produces one token "
            "records that token's producing-step latency instead of "
            "staying invisible.",
            labelnames=("priority",), buckets=LATENCY_BUCKETS)
        self._m_queue = m.gauge(
            "engine_queue_depth", "Requests waiting for a slot.")
        self._m_active = m.gauge(
            "engine_active_slots", "Decode lanes currently occupied.")
        self._m_admissions = m.counter(
            "engine_admissions_total", "Requests admitted into a lane.")
        self._m_finished = m.counter(
            "engine_finished_total",
            "Requests finished (lane vacated).", labelnames=("reason",))
        # pool-pressure/utilization series carry a `shard` label (this
        # engine rank's shard id) so multi-host serving ranks each
        # publish their own series and metrics.aggregate() folds the
        # per-shard snapshots exactly — distinct label sets merge
        # side-by-side instead of min/max/meaning across shards
        self._shard = str(jax.process_index())
        self._m_stalls = m.counter(
            "engine_block_stalls_total",
            "Iterations a lane/admission skipped for want of a pool "
            "block (path=spec_degrade: a speculative lane shed its "
            "draft window instead of skipping), labeled by engine "
            "shard.",
            labelnames=("path", "shard"))
        self._m_tokens = m.counter(
            "engine_tokens_generated_total", "New tokens emitted.")
        self._m_pool_used = m.gauge(
            "engine_pool_used_blocks",
            "KV pool blocks in use, by engine shard.",
            labelnames=("shard",)).labels(shard=self._shard)
        kv_name = self.kv_dtype or np.dtype(
            self.cache.pool_spec()[1]).name
        self._m_pool_util = m.gauge(
            "engine_pool_utilization",
            "Used fraction of allocatable KV pool blocks, by engine "
            "shard and pool dtype (int8 = quantized KV serving).",
            labelnames=("shard", "kv_dtype")).labels(
                shard=self._shard, kv_dtype=kv_name)
        self._m_pool_bytes = m.gauge(
            "engine_pool_bytes",
            "Total bytes of the paged KV state (both pool planes plus "
            "the int8 per-block scale array when quantized), by shard "
            "and pool dtype — the capacity-claim number: int8 pools "
            "must come in at <= 0.55x their fp16/bf16 size.",
            labelnames=("shard", "kv_dtype")).labels(
                shard=self._shard, kv_dtype=kv_name)
        self._m_kv_dtype = m.gauge(
            "engine_kv_dtype_info",
            "Paged KV cache storage dtype this engine serves with "
            "(1 = selected).", labelnames=("kv_dtype",))
        self._m_kv_dtype.labels(kv_dtype=kv_name).set(1)
        w_name = self.weight_dtype or np.dtype(self.spec.dtype).name
        self._m_weight_dtype = m.gauge(
            "engine_weight_dtype_info",
            "Served matmul-weight storage dtype (int8 = qkv/out/fc1/"
            "fc2 ride the compiled steps quantized; 1 = selected).",
            labelnames=("weight_dtype",))
        self._m_weight_dtype.labels(weight_dtype=w_name).set(1)
        self._m_pool_hw = m.gauge(
            "engine_pool_used_high_water_blocks",
            "High-water mark of KV pool blocks in use, by engine "
            "shard.",
            labelnames=("shard",)).labels(shard=self._shard)
        self._m_decode_traces = m.gauge(
            "engine_decode_traces",
            "Times the decode step traced (steady-state contract: 1).")
        self._m_prefill_traces = m.gauge(
            "engine_prefill_traces",
            "Times the prefill chunk traced (contract: 1, whatever the "
            "prompt lengths).")
        self._m_prefill_chunks = m.counter(
            "engine_prefill_chunks_total",
            "Compiled prefill-chunk dispatches (prefix-cache hits "
            "shrink this: hit tokens skip prefill compute).")
        self._m_hit_tokens = m.counter(
            "engine_prefix_cache_hit_tokens_total",
            "Prompt tokens served from the prefix cache instead of "
            "being recomputed.")
        self._m_cached_blocks = m.gauge(
            "engine_prefix_cached_blocks",
            "Pool blocks the prefix cache can currently serve hits "
            "from (live + evictable).")
        self._m_cow = m.counter(
            "engine_cow_copies_total",
            "Copy-on-write block promotions: a decode write landed in "
            "a shared/cached block and got a private copy first.")
        self._m_shed = m.counter(
            "engine_shed_total",
            "Requests shed at saturation (max_queue exceeded), by "
            "priority class.", labelnames=("priority",))
        self._m_spec_accepted = m.histogram(
            "engine_spec_accepted_tokens",
            "Tokens emitted per speculative verify step per lane "
            "(1 = no draft token survived; K+1 = the whole window "
            "accepted).",
            buckets=(1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 8.0, 12.0, 16.0))
        self._m_spec_hit_rate = m.gauge(
            "engine_spec_draft_hit_rate",
            "Fraction of drafted tokens the target model confirmed "
            "(exact-acceptance matches / proposals) since the last "
            "registry reset.")
        # hit-rate numerator/denominator live IN the registry so a
        # metrics.reset() (bench warmup, per-window scrapes) restarts
        # the rate instead of averaging over all-time
        spec_drafted = m.counter(
            "engine_spec_draft_tokens_total",
            "Drafted tokens offered to the verify step, by whether "
            "the target's argmax confirmed them.",
            labelnames=("result",))
        self._m_spec_ok = spec_drafted.labels(result="accepted")
        self._m_spec_rej = spec_drafted.labels(result="rejected")
        self._m_recompiles = m.counter(
            "engine_decode_recompiles_total",
            "Decode retraces past the first compile — nonzero means a "
            "shape-stability bug.")
        self._m_sampling = m.gauge(
            "engine_sampling_info",
            "Probabilistic serving state (1 = this engine threads "
            "per-slot sampling params through its compiled steps; "
            "greedy-only engines run the pre-sampling programs "
            "byte-identically).", labelnames=("enabled",))
        self._m_sampling.labels(
            enabled="1" if self.sampling else "0").set(1)
        # registered only when the subsystem is on, so a plain
        # engine's exposition is unchanged (the adapter precedent)
        self._m_sampled_tokens = None
        if self.sampling:
            self._m_sampled_tokens = m.counter(
                "engine_sampled_tokens_total",
                "Tokens emitted by sampled (temperature > 0) lanes — "
                "greedy lanes count only in "
                "engine_tokens_generated_total.")
        self._m_backend = m.gauge(
            "engine_attention_backend_info",
            "Paged-attention kernel backend the compiled decode step "
            "dispatches to (1 = selected).", labelnames=("backend",))
        self._m_backend.labels(backend=self.attention_backend).set(1)
        # what engaged inside the kernel: the fp decode walk's pages a
        # compute step, from the function the kernel itself asks; the
        # int8 and verify kernels walk one page a step, dense none
        if self.attention_backend != "pallas":
            pages = 0
        elif self.kv_dtype == "int8" or self.spec_decode_k:
            pages = 1
        else:
            pages = self.spec.decode_pages_per_step(
                self.block_size, self.mp_degree,
                self.cache.pool_spec()[1])
        m.gauge("engine_paged_decode_pages_per_step",
                "Pool pages the paged decode kernel fetches and scores "
                "per compute step (0 = the dense path serves).").set(pages)
        self._m_mesh = m.gauge(
            "engine_mesh_info",
            "Serving mesh the compiled steps span (1 = this "
            "configuration): tensor-parallel degree and device count.",
            labelnames=("mp_degree", "devices"))
        self._m_mesh.labels(
            mp_degree=str(self.mp_degree),
            devices=str(self.mesh.size if self.mesh is not None
                        else 1)).set(1)
        # the backend label is fixed at construction: resolve the
        # histogram child once, off the per-step path
        self._m_decode_seconds = m.histogram(
            "engine_decode_step_seconds",
            "Wall time of one compiled decode dispatch, labeled by "
            "paged-attention backend.", labelnames=("backend",),
            buckets=LATENCY_BUCKETS).labels(
                backend=self.attention_backend)
        self._decode_retraces_seen = 0
        self._m_steps_ahead = m.counter(
            "engine_decode_steps_ahead_total",
            "Decode steps launched while the previous decode step was "
            "still unread (fed from its tokens on the device): over "
            "`engine.decode_steps` the share of steps the ahead order "
            "engaged; 0 for a speculative or a serial engine.")
        self._m_overshoot = m.counter(
            "engine_overshoot_tokens_total",
            "Tokens computed for a lane past its EOS and discarded: "
            "the ahead order sees an EOS one step late. A finish by "
            "length is a count and never overshoots.")
        # registered only where the model has them, so a plain engine's
        # exposition is unchanged (the adapter precedent)
        self._m_state_used = None
        if self.cache.state:
            self._m_state_used = m.gauge(
                "engine_state_slots_used",
                "Rows of the slots' fixed-size state (recurrent layers) "
                "held by live lanes.")
            self._m_state_bytes = m.gauge(
                "engine_state_bytes",
                "Bytes of the slots' fixed-size state that live lanes "
                "hold (rows held x bytes a row).")
            self._state_row_bytes = self.cache.state_nbytes() \
                // (1 + self.cache.state_rows)
        self._m_step_counters = {
            name: (m.counter if how == "sum" else m.gauge)(
                f"engine_{name}" + ("_total" if how == "sum" else ""),
                f"The model's {step} counter `{name}` "
                f"({how} over {steps}).")
            for step, steps, counters in (
                ("decode-step", "decode steps", self.spec.step_counters),
                ("prefill-chunk", "prefill chunks",
                 self.spec.chunk_counters))
            for name, how in counters}
        # step-phase decomposition (ISSUE 17 / ROADMAP item 3): the
        # host work between compiled steps, per named phase — what
        # the pipelined orders run behind a device step. Always
        # registered: the phase clock is host bookkeeping, on for
        # every engine (tracing only adds the span stream).
        self._m_host_gap = m.histogram(
            "engine_step_host_gap_seconds",
            "Exclusive wall time one engine.step() spent in each named "
            "host phase. device_wait is the only phase that is device "
            "time: in the serial order the whole decode step, in the "
            "pipelined orders the throttle (the host is ahead and "
            "waits for a step that is still running); everything else "
            "is host work, which the pipelined orders run behind a "
            "device step.",
            labelnames=("phase",), buckets=LATENCY_BUCKETS)
        self._m_step_seconds = m.counter(
            "engine_step_seconds_total",
            "Wall seconds of every engine.step(). Over any window the "
            "device fraction is engine_step_host_gap_seconds_sum"
            "{phase=\"device_wait\"} over this: near 1 when the host "
            "keeps ahead.")
        self._m_step_stalls = m.counter(
            "engine_stalls_total",
            f"Steps whose wall was at least {STALL_RATIO:g}x the "
            f"median of the last {STALL_WINDOW} steps and "
            f"{STALL_MIN_EXCESS_S * 1e3:g} ms over it, by owner: gc, "
            "compile (that pause covered half the excess), device "
            "(device_wait) or the host phase with the most exclusive "
            "seconds.",
            labelnames=("owner",))
        self._m_step_stall_seconds = m.counter(
            "engine_stall_seconds_total",
            "Seconds the stalled steps took over the median, by owner.",
            labelnames=("owner",))
        self._m_launches = m.counter(
            "engine_launches_total",
            "Compiled steps launched, by program.",
            labelnames=("program",))
        self._m_launches_idle = m.counter(
            "engine_launches_device_idle_total",
            "Launches that found the previously launched step's output "
            "ready: the chip had drained its queue before the host "
            "launched again.", labelnames=("program",))
        # trace-count series: registered only when tracing is on, so a
        # plain engine's exposition is unchanged (adapter precedent)
        self._m_trace_spans = None
        if self.tracing:
            self._m_trace_spans = m.counter(
                "engine_trace_spans_total",
                "Spans/instants this engine's trace ring recorded "
                "(ring-bounded retention; see "
                "engine_trace_dropped_total).")
            self._m_trace_dropped = m.counter(
                "engine_trace_dropped_total",
                "Trace events evicted by the bounded span ring — "
                "nonzero means the exported timeline is a tail, not "
                "the full history.")
            self._trace_spans_seen = self._trace_dropped_seen = 0
        # multi-tenant adapter serving: per-TENANT latency series plus
        # adapter-pool paging health. Registered only when the
        # subsystem is on, so a plain engine's exposition is unchanged.
        self._m_a_ttft = self._m_a_tpot = None
        if self.adapter_pool is not None:
            self._m_a_ttft = m.histogram(
                "engine_adapter_ttft_seconds",
                "Request arrival to first token, labeled by tenant "
                "adapter id (0 = the null/base adapter) — the "
                "per-tenant SLO view of engine_ttft_seconds.",
                labelnames=("adapter",), buckets=LATENCY_BUCKETS)
            self._m_a_tpot = m.histogram(
                "engine_adapter_tpot_seconds",
                "Per-output-token latency by tenant adapter id — the "
                "per-tenant SLO view of engine_tpot_seconds.",
                labelnames=("adapter",), buckets=LATENCY_BUCKETS)
            self._m_a_pages = m.gauge(
                "engine_adapter_pool_pages",
                "Device-resident adapter pool pages (page 0 is the "
                "permanently-held null adapter).")
            self._m_a_pages.set(self.adapter_pool.num_pages)
            self._m_a_used = m.gauge(
                "engine_adapter_pool_used_pages",
                "Adapter pages referenced by live lanes (warm "
                "refcount-zero pages count as free capacity, like "
                "evictable KV blocks).")
            self._m_a_resident = m.gauge(
                "engine_adapter_pool_resident",
                "Adapters currently materialized on a page (live + "
                "warm LRU).")
            self._m_a_swapins = m.counter(
                "engine_adapter_swapins_total",
                "Host->device adapter page loads (an acquire missed "
                "the pool and copied the registry's stacks in).")
            self._m_a_evictions = m.counter(
                "engine_adapter_evictions_total",
                "Warm adapter pages evicted to make room for another "
                "tenant (LRU, refcount-zero only).")
            self._a_swapins_seen = self._a_evictions_seen = 0
            self._update_adapter_gauges()

    def _obs_ttft(self, req, v):
        """Record one TTFT observation on the priority-labeled series
        and (adapter serving) the tenant-labeled one."""
        self._m_ttft.labels(priority=req.priority).observe(v)
        if self._m_a_ttft is not None:
            self._m_a_ttft.labels(
                adapter=str(req.adapter_id)).observe(v)

    def _obs_tpot(self, req, v):
        self._m_tpot.labels(priority=req.priority).observe(v)
        if self._m_a_tpot is not None:
            self._m_a_tpot.labels(
                adapter=str(req.adapter_id)).observe(v)

    def _update_adapter_gauges(self):
        pool = self.adapter_pool
        if pool is None:
            return
        # re-set the static pages gauge too: a metrics.reset() (bench
        # warmup, per-window scrapes) must not leave it at 0 forever
        self._m_a_pages.set(pool.num_pages)
        self._m_a_used.set(pool.num_pages - 1 - pool.num_free)
        self._m_a_resident.set(pool.num_resident)
        if pool.swapins > self._a_swapins_seen:
            self._m_a_swapins.inc(pool.swapins - self._a_swapins_seen)
            self._a_swapins_seen = pool.swapins
        if pool.evictions > self._a_evictions_seen:
            self._m_a_evictions.inc(
                pool.evictions - self._a_evictions_seen)
            self._a_evictions_seen = pool.evictions

    def _update_pool_gauges(self):
        # "used" = referenced blocks; refcount-zero cached blocks are
        # reclaimable on demand, so they count as free capacity
        used = self.cache.num_blocks - 1 - self.cache.num_free
        self._m_pool_used.set(used)
        self._m_pool_util.set(used / max(self.cache.num_blocks - 1, 1))
        self._m_pool_bytes.set(self.cache.pool_nbytes())
        self._m_pool_hw.set_max(used)
        self._m_cached_blocks.set(self.cache.num_cached_blocks)
        if self._m_state_used is not None:
            self._m_state_used.set(self.cache.state_rows_used)
            self._m_state_bytes.set(
                self.cache.state_rows_used * self._state_row_bytes)

    def _sample_traces(self):
        """Mirror the count_traces probes into metrics; a decode trace
        beyond the first is a recompile (the ==0 steady-state SLO)."""
        again = sum(max(p.traces - 1, 0) for p in self._decode_pures)
        if again > self._decode_retraces_seen:
            self._m_recompiles.inc(again - self._decode_retraces_seen)
            self._decode_retraces_seen = again
        self._m_decode_traces.set(self.decode_traces)
        self._m_prefill_traces.set(self._prefill_pure.traces)

    def metrics_snapshot(self):
        """JSON-able snapshot of this engine's serving metrics."""
        return self.metrics.snapshot()

    # -- request-scoped tracing / step phases ------------------------------
    def _phase(self, name):
        """Enter one named host phase of the current step: the
        `engine.<phase>` span (`RecordEvent`: the profiler's clock and
        the host-event recorder) round the phase clock (exclusive
        accounting — nesting pauses the enclosing phase) and, with
        tracing on, the same span under the same name in the
        `TraceRecorder`."""
        return _PhaseSpan(self, name)

    def _trace_span(self, name, start_us, req=None, tid=0,
                    cat="request", **attrs):
        """Close a request-scoped span started at `start_us` (no-op
        with tracing off or an untraced request)."""
        if self.tracer is None:
            return
        self.tracer.add_span(
            name, start_us, now_us(), tid=tid, cat=cat,
            trace_id=None if req is None else req.trace_id,
            args={"req_id": str(req.req_id), **attrs} if req is not None
            else (attrs or None))

    def _trace_instant(self, name, req=None, **attrs):
        if self.tracer is None:
            return
        self.tracer.add_instant(
            name, cat="request",
            trace_id=None if req is None else req.trace_id,
            args={"req_id": str(req.req_id), **attrs} if req is not None
            else (attrs or None))

    def _step_begin(self):
        """Mark the start of one iteration for the stall detector
        (the process's pause totals, the span clock and this thread's
        CPU clock); returns the `perf_counter` its wall is taken from."""
        p = self._pauses
        self._pause_mark = (p.gc_seconds, p.compile_seconds, now_us(),
                            time.thread_time())
        return time.perf_counter()

    def _flush_step_phases(self, wall):
        """Fold the finished step's phase clock into the host-gap
        histogram and its wall into `engine_step_seconds_total`, and
        judge it against the steps before it (`StallDetector`)."""
        totals = self._phases.reset()
        self._m_step_seconds.inc(wall)
        median = self._stalls.observe(wall)
        if median is not None:
            self._note_stall(wall, median, totals)
        for phase, dt in totals.items():
            self._m_host_gap.labels(phase=phase).observe(dt)

    def _note_stall(self, wall, median, phases):
        """Count a stalled step under its owner (`stall_owner`) and
        leave a `stall` flight event (and, with tracing on, an instant)
        with what the step held: its phases, the collections and the
        functions compiled inside it, and the CPU seconds its thread
        ran (far under the wall: the thread was held off the CPU)."""
        gc0, compile0, t_us, cpu0 = self._pause_mark
        cpu_s = time.thread_time() - cpu0
        p = self._pauses
        gc_s, compile_s = p.gc_seconds - gc0, p.compile_seconds - compile0
        owner = stall_owner(wall, median, phases, gc_s, compile_s)
        self._m_step_stalls.labels(owner=owner).inc()
        self._m_step_stall_seconds.labels(owner=owner).inc(wall - median)
        gens, compiled = p.since(t_us)
        detail = {"owner": owner, "wall_s": wall, "median_s": median,
                  "phases": phases, "gc_s": gc_s,
                  "gc_generations": gens, "compile_s": compile_s,
                  "compiled": compiled, "cpu_s": cpu_s}
        self.flight.record("stall", **detail)
        if self.tracer is not None:
            self.tracer.add_instant("stall", cat="engine", args=detail)

    def dump_flight_recorder(self):
        """The bounded ring of recent request-lifecycle events
        (oldest first, JSON-able) — the postmortem `drain()`'s leak
        audit attaches automatically."""
        return self.flight.dump()

    def _audit_error(self, msg):
        """A drain-audit failure with the flight-recorder history
        attached: the bare assertion becomes a postmortem."""
        return RuntimeError(msg + "\n" + self.flight.format(limit=64))

    def export_trace(self, path, include_profiler=True):
        """Write this engine's span ring as one Chrome trace-event /
        Perfetto JSON timeline, merged (same monotonic clock) with any
        spans currently buffered in the profiler's host-event stream.
        Returns the event count written."""
        if self.tracer is None:
            raise RuntimeError(
                "tracing is off — build the engine with tracing=True "
                "to record spans")
        groups = [("engine", self.tracer.snapshot())]
        if include_profiler:
            ev = profiler_host_events()
            if ev:
                groups.append(("profiler", ev))
        return export_timeline(path, groups)

    # -- compiled steps ----------------------------------------------------
    def _lora_args(self, rest):
        """Unpack a compiled step's OPTIONAL adapter tail: with the
        adapter subsystem on, the pool arrays ride as one tuple arg
        right before the host args and the per-slot page row is the
        LAST host arg. Returns (LoraState-or-None, remaining rest)."""
        if self.adapter_pool is None:
            return None, rest
        from paddle_tpu.ops.lora import LoraState

        return LoraState(rest[0], rest[-1]), rest[1:-1]

    def _state_args(self, rest):
        """Unpack a compiled step's OPTIONAL fixed-state head: where the
        model keeps state of fixed size a slot, the arrays ride as one
        tuple right after the pools (donated with them) and the slots'
        rows are the LAST host arg but the adapter row. Returns (keyword
        arguments for the spec's step function, remaining rest)."""
        if not self.cache.state:
            return {}, rest
        return {"slot_state": rest[0]}, rest[1:]

    def _step_outputs(self, lead, r, counters=None):
        """The outputs of a compiled step in the one order
        `_dispatch_step` reads: the leading replicated outputs, the
        pools, then what rides beside them; last the model's counters
        where the spec names any for this kind of step (`counters`: a
        decode step's unless the caller says the chunk's)."""
        out = tuple(lead) + (_pool_array(r.kpool), _pool_array(r.vpool))
        if r.kv_scales is not None:
            out += (r.kv_scales._array,)
        out += tuple(r.slot_state)
        names = self.spec.step_counters if counters is None else counters
        if names and r.counters is not None:
            out += (r.counters,)
        return out

    def _build_decode(self):
        model, state = self.model, self._state
        spec = self.spec
        backend = self.attention_backend
        mp_axis = self._mp_axis
        use_q = self.kv_dtype == "int8"
        use_s = self.sampling

        def decode_fn(state_arrays, kpool, vpool, *rest):
            kw, rest = self._state_args(rest)
            scales = rest[0] if use_q else None
            lora, rest = self._lora_args(rest[1:] if use_q else rest)
            if kw:
                kw["state_rows"], rest = rest[-1], rest[:-1]
            if use_s:
                (tokens, positions, tables,
                 temps, tks, tps, krows) = rest
            else:
                tokens, positions, tables = rest
            arrays = self._materialize_state(state_arrays)
            with bound_state(zip(state, arrays), state):
                r = spec.decode(
                    Tensor._wrap(tokens), Tensor._wrap(positions),
                    _wrap_pool(kpool), _wrap_pool(vpool),
                    _wrap_pool(tables), backend=backend,
                    mp_axis=mp_axis,
                    kv_scales=None if scales is None
                    else Tensor._wrap(scales), lora=lora, **kw)
                logits = spec.logits(r.hidden, mp_axis=mp_axis)
                if use_s:
                    # per-slot categorical draws on device; greedy
                    # rows take the literal argmax (bit-identical to
                    # the branch below). Draws fold (key row, this
                    # row's absolute position) — replicated at mp>1:
                    # same keys, same all-gathered logits, no
                    # collective.
                    from paddle_tpu.ops.sampling import sample_token

                    nxt = sample_token(logits._array[:, 0], temps,
                                       tks, tps, krows, positions)
                else:
                    nxt = jnp.argmax(logits._array[:, 0], axis=-1) \
                        .astype(jnp.int32)            # logits [slots,1,V]
                return self._step_outputs((nxt,), r)

        decode_fn.__name__ = "engine_decode_step"
        return self._shard_steps(decode_fn, n_repl=7 if use_s else 3)

    def _build_verify(self):
        """The speculative decode step: one fixed `[slots, K+1]` window
        scores the feed token plus up to K drafts per lane in a single
        target-model pass. Per-row positions and draft lengths are
        traced, so every acceptance outcome reuses ONE program. Under
        sampling the step ALSO runs the rejection-sampling acceptance
        on device (all K+1 logit positions are in hand) and leads with
        the (choices, accepts) pair instead of the argmax row."""
        model, state = self.model, self._state
        spec = self.spec
        backend = self.attention_backend
        mp_axis = self._mp_axis
        use_q = self.kv_dtype == "int8"
        use_s = self.sampling

        def verify_fn(state_arrays, kpool, vpool, *rest):
            scales = rest[0] if use_q else None
            lora, rest = self._lora_args(rest[1:] if use_q else rest)
            if use_s:
                (tokens, positions, dlens, tables,
                 temps, tks, tps, krows) = rest
            else:
                tokens, positions, dlens, tables = rest
            arrays = self._materialize_state(state_arrays)
            with bound_state(zip(state, arrays), state):
                r = spec.verify(
                    Tensor._wrap(tokens), Tensor._wrap(positions),
                    Tensor._wrap(dlens), Tensor._wrap(kpool),
                    _wrap_pool(vpool), Tensor._wrap(tables),
                    backend=backend, mp_axis=mp_axis,
                    kv_scales=None if scales is None
                    else Tensor._wrap(scales), lora=lora)
                logits = spec.logits(r.hidden, mp_axis=mp_axis)
                if use_s:
                    # rejection-sampling acceptance in the same
                    # compiled program: per-row accept coins + the
                    # residual/bonus resamples (greedy rows pin the
                    # argmax / equality contract) — replicated at
                    # mp>1, no collective
                    from paddle_tpu.ops.sampling import verify_window

                    choices, accepts = verify_window(
                        logits._array, tokens, dlens, temps, tks,
                        tps, krows, positions)
                    return self._step_outputs((choices, accepts), r)
                nxt = jnp.argmax(logits._array, axis=-1) \
                    .astype(jnp.int32)           # logits [slots,K+1,V]
                return self._step_outputs((nxt,), r)

        verify_fn.__name__ = "engine_verify_step"
        return self._shard_steps(verify_fn, n_repl=8 if use_s else 4,
                                 n_out=2 if use_s else 1)

    def _build_prefill_chunk(self):
        model, state = self.model, self._state
        spec = self.spec
        C = self.prefill_chunk
        backend = self.attention_backend
        mp_axis = self._mp_axis
        use_q = self.kv_dtype == "int8"
        use_s = self.sampling

        def prefill_chunk_fn(state_arrays, kpool, vpool, *rest):
            # tokens [1, C] FIXED; start/plen traced -> ONE program
            # serves every chunk of every prompt length
            kw, rest = self._state_args(rest)
            scales = rest[0] if use_q else None
            lora, rest = self._lora_args(rest[1:] if use_q else rest)
            if kw:
                kw["state_row"], rest = rest[-1], rest[:-1]
            if use_s:
                (tokens, start, plen, table_row,
                 temps, tks, tps, krows) = rest
            else:
                tokens, start, plen, table_row = rest
            arrays = self._materialize_state(state_arrays)
            with bound_state(zip(state, arrays), state):
                r = spec.prefill_chunk(
                    Tensor._wrap(tokens), Tensor._wrap(start),
                    _wrap_pool(kpool), _wrap_pool(vpool),
                    _wrap_pool(table_row), Tensor._wrap(plen),
                    backend=backend, mp_axis=mp_axis,
                    kv_scales=None if scales is None
                    else Tensor._wrap(scales), lora=lora, **kw)
                nxt = _last_prompt_row_token(
                    spec, r.hidden, start, plen, C,
                    (temps, tks, tps, krows) if use_s else None,
                    mp_axis=mp_axis)
                return self._step_outputs((nxt,), r,
                                          spec.chunk_counters)

        prefill_chunk_fn.__name__ = "engine_prefill_chunk"
        return self._shard_steps(prefill_chunk_fn,
                                 n_repl=8 if use_s else 4)

    def _build_decode_with_chunk(self):
        """One program for an iteration's prefill chunk AND its decode
        step (`ServingSpec.decode_with_chunk`): the chunk's host args,
        then the decode step's, each as its own program takes them; it
        leads with the chunk's token and the decode rows' tokens, both
        drawn as the two programs draw them."""
        state = self._state
        spec = self.spec
        C = self.prefill_chunk
        backend = self.attention_backend
        use_s = self.sampling
        n_c = 8 if use_s else 4
        stateful = bool(self.cache.state)

        def fused_fn(state_arrays, kpool, vpool, *rest):
            kw, rest = self._state_args(rest)
            chunk, rest = rest[:n_c], rest[n_c:]
            if stateful:
                # the chunk's row of state trails its args, the lanes'
                # rows the decode step's
                kw.update(state_row=rest[0], state_rows=rest[-1])
                rest = rest[1:-1]
            chunk_tokens, start, plen, table_row = chunk[:4]
            tokens, positions, tables = rest[:3]
            arrays = self._materialize_state(state_arrays)
            with bound_state(zip(state, arrays), state):
                r, hidden = spec.decode_with_chunk(
                    Tensor._wrap(chunk_tokens), Tensor._wrap(start),
                    _wrap_pool(table_row), Tensor._wrap(plen),
                    Tensor._wrap(tokens), Tensor._wrap(positions),
                    _wrap_pool(tables), _wrap_pool(kpool),
                    _wrap_pool(vpool), backend=backend, **kw)
                first = _last_prompt_row_token(
                    spec, r.hidden, start, plen, C,
                    chunk[4:] if use_s else None)
                logits = spec.logits(hidden)._array[:, 0]
                if use_s:
                    from paddle_tpu.ops.sampling import sample_token

                    nxt = sample_token(logits, *rest[3:], positions)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return self._step_outputs((first, nxt), r)

        fused_fn.__name__ = "engine_decode_step_with_chunk"
        return fused_fn

    # -- recompile probes (CI contract) ------------------------------------
    @property
    def decode_traces(self):
        """Times the decode step traced. Steady-state contract: 1,
        regardless of arrivals/evictions — and 1 more where the model
        offers a decode step that carries a chunk, once an iteration
        has held both."""
        return sum(p.traces for p in self._decode_pures)

    @property
    def decode_steps_with_chunk(self):
        """Decode steps read so far that carried a prefill chunk's rows:
        the spec's step counter of that name (0 where it names none)."""
        return self.step_counter_totals.get("decode_steps_with_chunk", 0)

    @property
    def prefill_traces(self):
        """Times the prefill chunk traced. Contract: 1 — `start` and
        `plen` are traced, so one program serves every chunk of every
        prompt length."""
        return self._prefill_pure.traces

    # -- request intake ----------------------------------------------------
    def _intake_guard(self, prompt, max_new_tokens, priority, req_id):
        """Shared admission validation + id claim for BOTH intake
        paths (`add_request` and the fleet's `adopt_request`), so the
        two can never drift: draining gate, prompt/budget/priority/
        length checks, auto-id allocation with collision detection.
        Returns the normalized (prompt, req_id)."""
        if self._draining:
            raise RuntimeError(
                "engine is draining — admissions are closed (finish "
                "the drain, or route to another replica)")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if priority not in PRIORITY_CLASSES:
            raise ValueError(f"priority must be one of "
                             f"{PRIORITY_CLASSES}, got {priority!r}")
        total = prompt.size + int(max_new_tokens)
        if total > self.max_model_len:
            raise ValueError(
                f"prompt({prompt.size}) + max_new({max_new_tokens}) = "
                f"{total} exceeds max_model_len={self.max_model_len}")
        if req_id is None:
            # skip over any live caller-chosen int ids
            while self._auto_id in self._in_flight():
                self._auto_id += 1
            req_id = self._auto_id
            self._auto_id += 1
        elif req_id in self._in_flight():
            raise ValueError(f"req_id {req_id!r} is already queued, "
                             "decoding, or awaiting collection")
        return prompt, req_id

    def add_request(self, prompt, max_new_tokens, eos_token_id=None,
                    req_id=None, priority="standard",
                    prefill_only=False, adapter_id=0,
                    sampling_params=None, trace_id=None):
        """Queue a request; admitted into a free slot between decode
        iterations (may be called while `run`/`step` is mid-stream).
        `priority` is one of PRIORITY_CLASSES — higher classes admit
        first and survive saturation shedding longer. With `max_queue`
        set and the queue full, the lowest-priority loser is shed: its
        result is recorded as None (the HTTP-429 of this API) and
        `engine_shed_total` counts it; the request kept is whichever
        of (incoming, worst queued) ranks higher.

        `prefill_only=True` is the disaggregated-serving intake: the
        engine prefills the prompt, emits the FIRST token, then parks
        the prompt's KV blocks for `take_handoff` instead of decoding
        further (`max_new_tokens` must be 1 — the fleet's decode
        replica owns the rest of the budget).

        `adapter_id` selects the tenant LoRA adapter the request
        decodes under (needs `GenerationEngine(adapters=...)`; 0 — the
        default — is the null/base adapter and always valid).

        `sampling_params` (a `SamplingParams`; needs
        `GenerationEngine(sampling=True)`) selects per-request
        temperature/top-k/top-p sampling — None (the default) and
        temperature=0 are the greedy contract, bit-identical to a
        no-sampling engine. A None seed is resolved here from the
        engine's deterministic counter, so a fixed trace replays
        token-for-token."""
        if prefill_only:
            self._refuse("handoff")
        if prefill_only and max_new_tokens != 1:
            raise ValueError(
                "prefill_only requests carry max_new_tokens=1 (the "
                "single token the final prefill chunk yields); the "
                "decode replica owns the remaining budget")
        adapter_id = self._check_adapter(adapter_id)
        sampling_params = self._resolve_seed(
            self._check_sampling(sampling_params))
        prompt, req_id = self._intake_guard(prompt, max_new_tokens,
                                            priority, req_id)
        eos = self.eos_token_id if eos_token_id is None else eos_token_id
        if self.tracing and trace_id is None:
            trace_id = new_trace_id()
        req = Request(req_id, prompt, int(max_new_tokens), eos,
                      arrived_at=time.perf_counter(), priority=priority,
                      prefill_only=bool(prefill_only),
                      adapter_id=adapter_id, sampling=sampling_params,
                      trace_id=trace_id)
        self.flight.record("queued", req_id, priority=priority,
                           plen=int(prompt.size),
                           adapter=int(adapter_id))
        self._trace_instant("request.queued", req,
                            priority=priority, plen=int(prompt.size))
        if self.max_queue is not None \
                and self.num_pending >= self.max_queue:
            victim = self._shed_victim(priority)
            if victim is None:         # incoming ranks no better: shed it
                self._shed(req)
                return req_id
            self._shed(victim)
        self._queues[priority].append(req)
        self._m_queue.set(self.num_pending)
        return req_id

    def _shed_victim(self, incoming_priority):
        """Worst queued request STRICTLY below the incoming class
        (newest within it — it has waited least), or None when the
        incoming request is the one to shed."""
        rank = PRIORITY_CLASSES.index(incoming_priority)
        for p in reversed(PRIORITY_CLASSES[rank + 1:]):
            if self._queues[p]:
                return self._queues[p].pop()
        return None

    def _shed(self, req):
        self._results[req.req_id] = None
        self._m_shed.labels(priority=req.priority).inc()
        self._m_queue.set(self.num_pending)
        self.flight.record("shed", req.req_id, priority=req.priority)
        self._trace_instant("request.shed", req, priority=req.priority)

    # -- scheduler ---------------------------------------------------------
    def _state_arrays(self):
        if self._tp_arrays is not None:
            # tensor parallel: the mesh-placed (weight-stationary)
            # snapshot — see refresh_weights()
            return list(self._tp_arrays)
        if self._q_arrays is not None:
            # int8 weights at mp=1: the quantized snapshot (weight-
            # stationary too — refresh_weights() requantizes)
            return list(self._q_arrays)
        return [t._array for t in self._state]

    def _dispatch_step(self, jitted, *host_args, n_out=1):
        """Invoke a compiled step: state + pools (+ the int8 scale
        array) (+ the adapter-pool arrays) threaded in, updated pools
        (+ scales) re-seated on the cache, the `n_out` leading outputs
        returned (token ids; the sampling verify step leads with its
        (choices, accepts) pair). With adapters on, the caller appends
        the per-slot adapter page row as the LAST host arg."""
        c = self.cache
        args = [self._state_arrays(), c.kpool, c.vpool]
        if c.state:
            args.append(c.state)
        if c.scales is not None:
            args.append(c.scales)
        if self.adapter_pool is not None:
            args.append(self.adapter_pool.arrays())
        self._count_launch(jitted)
        out = jitted(*args, *host_args)
        self._last_launch = out[0]
        c.kpool, c.vpool = out[n_out:n_out + 2]
        tail = n_out + 2
        if c.scales is not None:
            c.scales = out[tail]
            tail += 1
        if c.state:
            c.state = tuple(out[tail:tail + len(c.state)])
            tail += len(c.state)
        # what is left is the model's counters (a decode step's)
        self._step_counters = out[tail] if len(out) > tail else None
        return out[0] if n_out == 1 else out[:n_out]

    def _count_launch(self, jitted):
        """Count a compiled step's launch, and whether the step
        launched before it had finished already (`is_ready`, which
        does not block): the chip then idled until this launch."""
        series = self._launch_series.get(id(jitted))
        if series is None:
            series = self._launch_series[id(jitted)] = (
                self._m_launches.labels(program=jitted.__name__),
                self._m_launches_idle.labels(program=jitted.__name__))
        series[0].inc()
        prev = self._last_launch
        if prev is not None and prev.is_ready():
            series[1].inc()

    def _in_flight(self):
        """Ids that would collide with a new request: queued, seated in
        a lane, finished but not yet drained by run()/pop_results(),
        or parked in the handoff buffer (a reused id there would
        overwrite the parked entry and leak its still-referenced
        blocks)."""
        ids = {r.req_id for p in PRIORITY_CLASSES
               for r in self._queues[p]}
        ids.update(s.req.req_id for s in self._slots if s is not None)
        ids.update(self._results)
        ids.update(self._handoffs)
        return ids

    def _peek_request(self):
        for p in PRIORITY_CLASSES:
            if self._queues[p]:
                return self._queues[p][0]
        return None

    def _pop_request(self):
        req = self._peek_request()
        if req is not None:
            self._queues[req.priority].popleft()
        return req

    def _release_adapter(self, slot):
        """Return a vacating lane's adapter-page reference (refcount
        down; the page parks warm in the pool's LRU at zero)."""
        if self.adapter_pool is not None and slot.req.adapter_id:
            self.adapter_pool.release(slot.req.adapter_id)
            self._update_adapter_gauges()

    def _note_tokens(self, req, n=1):
        """Account `n` freshly emitted tokens: the engine counter, the
        tokens-total series, and (probabilistic serving) the
        sampled-token series for temperature>0 lanes."""
        self.tokens_generated += n
        self._m_tokens.inc(n)
        if self._m_sampled_tokens is not None \
                and req.sampling is not None and not req.sampling.greedy:
            self._m_sampled_tokens.inc(n)

    def _note_step_counters(self, values, chunk=False):
        """Fold one decode step's counters (the model's: how many
        assignments its experts took, ...) or one prefill chunk's into
        the totals and the metrics, each summed or kept as the largest,
        as its spec says."""
        names, totals = (self.spec.chunk_counters, self._chunk_totals) \
            if chunk else (self.spec.step_counters,
                           self.step_counter_totals)
        for (name, how), v in zip(names, values):
            v = int(v)
            if how == "sum":
                totals[name] += v
                self._m_step_counters[name].inc(v)
            else:
                totals[name] = max(totals[name], v)
                self._m_step_counters[name].set_max(v)

    @property
    def chunk_counter_totals(self):
        """The model's counters over every prefill chunk launched
        (`spec.chunk_counters`). A chunk's counters stay on the device
        until somebody asks: reading them here waits for the newest
        chunk."""
        self._fold_chunk_counters(keep=0)
        return dict(self._chunk_totals)

    def _fold_chunk_counters(self, keep):
        """Read all but the newest `keep` chunks' counters (long
        finished: no wait) into the totals."""
        unread = self._chunk_counters
        while len(unread) > keep:
            self._note_step_counters(np.asarray(unread.pop(0)),
                                     chunk=True)

    def _publish(self, slot, reason):
        """The host holds the lane's last token: the result is out."""
        req = slot.req
        self._results[req.req_id] = \
            list(map(int, req.prompt)) + slot.generated
        self._m_finished.labels(reason=reason).inc()
        self.flight.record("finish", req.req_id, reason=reason,
                           tokens=len(slot.generated))
        self._trace_instant("request.finish", req, reason=reason,
                            tokens=len(slot.generated))

    def _release(self, slot):
        """Give back what a seated lane holds: its blocks, its state
        row, its adapter page. THE allocator-safety invariant of the
        pipelined orders (DESIGN_DECISIONS r21): only after the last
        step launched over the lane has completed, so no program that
        may still write the lane's pages is unfinished when they get
        a new owner. `ahead` counts exactly those steps."""
        if slot.ahead:
            raise self._audit_error(
                f"lane of request {slot.req.req_id!r} released with "
                f"{slot.ahead} step(s) launched over it still unread")
        self.cache.free(slot.blocks)
        if slot.state_row:
            with self._phase("state_free"):
                self.cache.free_state(slot.state_row)
        self._release_adapter(slot)

    def _finish(self, slot, reason):
        self._publish(slot, reason)
        self._release(slot)

    def _retire(self, i, slot, reason):
        """The host has read lane `i`'s last token. A finish by length
        is a count: the scheduler left the lane out of every later
        step, so it is vacated here. An EOS read while a later step
        over the lane is unread (the ahead order sees it one step
        late) puts the result out now and holds the lane and all it
        owns until that step's walk."""
        if slot.req.prefill_only:
            # a count too (one token): park the blocks for the
            # disaggregated handoff, don't free them
            self._handoff_finish(slot)
        else:
            self._publish(slot, reason)
            if slot.ahead:
                slot.done = reason
                return
            self._release(slot)
        self._slots[i] = None

    def _first_token(self, slot, first, t_step):
        """Seat a request's FIRST generated token (from the final
        prefill chunk): TTFT, token accounting, prefix-cache
        publication, and instant-finish retirement. Returns False when the slot finished on the spot
        (its lane has been vacated)."""
        req = slot.req
        now = time.perf_counter()
        slot.generated.append(first)
        slot.last_token_at = now
        self._note_tokens(req)
        self.flight.record("first_token", req.req_id)
        self._trace_instant("request.first_token", req)
        if req.arrived_at is not None:
            self._obs_ttft(req, now - req.arrived_at)
        if self.enable_prefix_cache:
            # the prompt's KV is now fully written: publish its FULL
            # blocks for future admissions to seat read-only (under
            # the request's adapter-salted chain — a tenant's KV can
            # only ever hit the same tenant)
            self.cache.register_prefix(req.prompt, slot.blocks,
                                       adapter_id=req.adapter_id)
        done_eos = (req.eos_token_id is not None
                    and first == req.eos_token_id)
        if done_eos or req.max_new_tokens == 1:
            # instant finisher: its only token would otherwise be
            # invisible to the TPOT histogram while still counting in
            # engine_tokens_generated_total — record the producing
            # step's latency explicitly
            self._obs_tpot(req, now - t_step)
            self._retire(self._slots.index(slot), slot,
                         "eos" if done_eos else "length")
            return False
        return True

    def _handoff_finish(self, slot):
        """Retire a prefill-only lane WITHOUT freeing its blocks: the
        prompt's fully-written KV is this request's product. The blocks
        park in the handoff buffer (still referenced, so neither the
        allocator nor LRU eviction can recycle them) until the fleet
        claims them with `take_handoff`, exports their rows into a
        decode replica's pool, and returns them via
        `release_handoff`."""
        req = slot.req
        self._handoffs[req.req_id] = (list(slot.blocks),
                                      slot.hit_tokens)
        self._results[req.req_id] = \
            list(map(int, req.prompt)) + slot.generated
        # the adapter page is NOT parked with the blocks: its job
        # (prefill under the tenant's projections) is done, and the
        # decode replica acquires from its OWN pool at adoption
        self._release_adapter(slot)
        self._m_finished.labels(reason="handoff").inc()
        self.flight.record("handoff_parked", req.req_id,
                           blocks=len(slot.blocks))
        self._trace_instant("request.handoff", req,
                            blocks=len(slot.blocks))

    # -- admission ---------------------------------------------------------
    def _admit(self):
        """Seat queued requests (priority order, FIFO within a class)
        into free lanes: match the longest cached block-aligned prefix,
        take read-only references on those blocks, and leave the tail
        for the incremental chunk prefill. No compute happens here —
        a full-prefix hit enters decode directly (feeding the last
        prompt token; copy-on-write keeps its write private)."""
        admitted = 0
        with self._phase("schedule"):
            while None in self._slots:
                req = self._pop_request()
                if req is None:
                    break
                page = self._acquire_adapter(req)
                if page is None:
                    # adapter-pool pressure: every page is referenced
                    # by a live lane. Requeue at the FRONT (strict
                    # order kept) and retry when a lane vacates — the
                    # KV stall/retry contract, page-sized.
                    self._queues[req.priority].appendleft(req)
                    break
                blocks, hit = [], 0
                if self.enable_prefix_cache:
                    with self._phase("prefix_lookup"):
                        blocks, hit = self.cache.match_prefix(
                            req.prompt, adapter_id=req.adapter_id)
                    if hit:
                        self.prefix_hit_tokens += hit
                        self._m_hit_tokens.inc(hit)
                state_row = 0
                if self.cache.state:
                    with self._phase("state_alloc"):
                        # a row a slot: a free lane always finds one
                        state_row = self.cache.allocate_state()
                slot = _Slot(req=req, blocks=list(blocks),
                             prefill_pos=hit,
                             hit_tokens=hit,
                             admit_seq=self._admit_counter,
                             adapter_page=page, state_row=state_row,
                             **self._slot_sampling_fields(req))
                self._admit_counter += 1
                self._slots[self._slots.index(None)] = slot
                self._m_admissions.inc()
                self.flight.record("admitted", req.req_id,
                                   hit_tokens=hit)
                self._trace_instant("request.admitted", req,
                                    hit_tokens=hit)
                self._update_pool_gauges()
                admitted += 1
        self._m_queue.set(self.num_pending)
        return admitted

    def _acquire_adapter(self, req):
        """Take the adapter-page reference a request's lane needs (the
        null adapter is page 0, never paged). Returns the page, or
        None on adapter-pool pressure (stall counted; caller requeues
        and retries — admission's analog of a KV block stall)."""
        if self.adapter_pool is None or not req.adapter_id:
            return 0
        with self._phase("adapter_swap"):
            swapins = self.adapter_pool.swapins
            page = self.adapter_pool.acquire(req.adapter_id)
        if page is None:
            self._m_stalls.labels(path="adapter",
                                  shard=self._shard).inc()
            self.flight.record("stall", req.req_id, path="adapter")
            return None
        if self.adapter_pool.swapins > swapins:
            # cold page: the acquire paid a host->device swap-in
            self.flight.record("adapter_swap_in", req.req_id,
                               adapter=int(req.adapter_id), page=page)
            self._trace_instant("adapter.swap_in", req,
                                adapter=int(req.adapter_id), page=page)
        self._update_adapter_gauges()
        return page

    def _prefill_step(self):
        """Run at most ONE compiled prefill chunk: pick the neediest
        prefilling lane (`_chunk_schedule`) and push `prefill_chunk`
        prompt positions through the fixed-shape chunk program. The
        final chunk yields the first generated token."""
        chunk = self._chunk_schedule()
        if chunk is None:
            return 0
        self._chunk_dispatch(*chunk)
        return 1

    def _chunk_dispatch(self, slot, start, end):
        """Launch the chunk program over `_chunk_schedule`'s pick."""
        t_span = now_us()
        with self._phase("dispatch"):
            args = self._chunk_args(slot, start, end)
            if self.adapter_pool is not None:
                # the chunk serves ONE slot: its adapter page,
                # [1]-row
                args.append(jnp.asarray(
                    np.asarray([slot.adapter_page], np.int32)))
            with RecordEvent("engine.prefill"):
                t0 = time.perf_counter()
                nxt = self._dispatch_step(self._prefill, *args)
        if self._step_counters is not None:
            # the chunk's own counters: read once it is long finished
            self._chunk_counters.append(self._step_counters)
            if len(self._chunk_counters) >= 64:
                self._fold_chunk_counters(keep=8)
        first = self._chunk_launched(slot, start, end, nxt, t0, t_span)
        # the prompt's last chunk: its output is the request's first
        # token. The serial order reads it here; the ahead order feeds
        # this iteration's decode step from it where it lies and reads
        # it after that launch (`_step_ahead`).
        if self._goes_ahead:
            self._first = first
        elif first is not None:
            self._first_complete(first)

    def _chunk_schedule(self):
        """The lane that gets this iteration's chunk, by priority, then
        admission order, with the chunk's blocks allocated (evicting
        cold cache blocks if necessary): `(slot, start, end)`, or None.
        A lane that cannot get blocks stalls and the next candidate
        gets the chunk."""
        with self._phase("schedule"):
            cands = [s for s in self._slots
                     if s is not None and s.prefilling]
            cands.sort(key=lambda s: (
                PRIORITY_CLASSES.index(s.req.priority), s.admit_seq))
            for slot in cands:
                start = slot.prefill_pos
                end = min(start + self.prefill_chunk,
                          int(slot.req.prompt.size))
                need = math.ceil(end / self.block_size) \
                    - len(slot.blocks) if self._paged else 0
                if need > 0:
                    got = self.cache.allocate(need)
                    if got is None:
                        self._m_stalls.labels(
                            path="prefill", shard=self._shard).inc()
                        self.flight.record("stall", slot.req.req_id,
                                           path="prefill")
                        continue       # pool pressure: next candidate
                    slot.blocks.extend(got)
                    self._update_pool_gauges()
                return slot, start, end
        return None

    def _chunk_args(self, slot, start, end):
        """The chunk program's host args but the adapter row: tokens
        `[1, C]`, start, prompt length, the lane's block row, its
        sampling rows, its row of state."""
        req = slot.req
        tokens = np.zeros((1, self.prefill_chunk), np.int32)
        tokens[0, :end - start] = req.prompt[start:end]
        row = None
        if self._paged:
            row = np.zeros(self.max_blocks, np.int32)
            row[:len(slot.blocks)] = slot.blocks
            row = jnp.asarray(row)
        args = [jnp.asarray(tokens), jnp.int32(start),
                jnp.int32(req.prompt.size), row]
        if self.sampling:
            # the chunk serves ONE slot: its sampling rows, [1]
            args.extend(self._sampling_host_args_one(slot))
        if self.cache.state:
            args.append(jnp.int32(slot.state_row))
        return args

    def _chunk_launched(self, slot, start, end, out, t0, t_span):
        """A chunk over `slot` is on the device, alone or inside a decode
        step: the lane's prompt advances. Where it ended the prompt,
        returns the unread record of the request's first token."""
        req = slot.req
        self._m_prefill_chunks.inc()
        slot.prefill_pos = end
        final = end == req.prompt.size
        self._trace_span("prefill.chunk", t_span, req=req,
                         start=start, end=end,
                         **({"final": True} if final else {}))
        if not final:                  # mid-prompt: no token to read
            return None
        slot.ahead = 1
        return _InFlight(out=out, runnable=[self._slots.index(slot)],
                         slots=[slot], t_dec=t0, t_span=t_span)

    def _first_complete(self, first):
        """Read the first token a prompt's last chunk produced (the
        sync on that chunk alone) and seat it."""
        slot, = first.slots
        with self._phase("device_wait"):
            tok = int(np.asarray(first.out))   # sync: first token is out
        slot.ahead -= 1
        with self._phase("finish"):
            self._first_token(slot, tok, first.t_dec)

    # -- decode ------------------------------------------------------------
    def _cow_promote(self, slot, bi, count_stall=True):
        """Give `slot` a private copy of its table entry `bi` via the
        compiled block-copy step (the write is about to land there and
        other owners — slots or the prefix cache — still read it).
        Returns False when the pool cannot serve the copy (caller
        stalls the lane this iteration; `count_stall=False` when the
        caller has a degrade path and the lane may still run)."""
        got = self.cache.allocate(1)
        if got is None:
            if count_stall:
                self._m_stalls.labels(path="decode", shard=self._shard).inc()
                self.flight.record("stall", slot.req.req_id,
                                   path="decode")
            return False
        src, dst = slot.blocks[bi], got[0]
        with self._phase("cow"):
            if self.cache.scales is not None:
                # quantized pools: the block's per-layer grid rows
                # ride the copy — a COW'd block must dequantize on
                # the SAME grid its source was written with
                self.cache.kpool, self.cache.vpool, \
                    self.cache.scales = self._cow(
                        self.cache.kpool, self.cache.vpool,
                        jnp.int32(src), jnp.int32(dst),
                        self.cache.scales)
            else:
                self.cache.kpool, self.cache.vpool = self._cow(
                    self.cache.kpool, self.cache.vpool,
                    jnp.int32(src), jnp.int32(dst))
        self.cache.free([src])         # drop our shared reference
        slot.blocks[bi] = dst
        self._m_cow.inc()
        self._update_pool_gauges()
        return True

    def _decode_step(self):
        """One batched decode step over every decode-phase lane that
        holds an exclusively-writable block for its write position, in
        the SERIAL order: schedule, launch and complete run inline in
        this one call. The pipelined orders drive the same three
        stages with the completion of step N and the launch of step
        N+1 in the other order (`_step_ahead`) or split across
        `step()` calls (`_step_async`). Copy-on-write happens in the
        schedule stage: a lane whose feed position sits in a shared or
        prefix-cached block first gets a private copy via the compiled
        block-copy step."""
        if self.spec_decode_k:
            runnable, drafts = self._spec_schedule()
            if not runnable:
                return 0
            inflight = self._spec_dispatch(runnable, drafts)
            return self._spec_complete(inflight, synced=False)
        runnable = self._plain_schedule()
        if not runnable:
            return 0
        inflight = self._plain_dispatch(runnable)
        return self._plain_complete(inflight)

    def _plain_schedule(self):
        """Schedule stage of a plain decode step: on-demand block
        growth + COW promotion per decode-phase lane; returns the
        runnable lane indices. It counts tokens LAUNCHED, not tokens
        read (`_Slot.dispatched`, `feed_pos`): a lane whose last token
        is already on its way is left out (a finish by length never
        overshoots), and so is one whose EOS the host has read."""
        runnable = []
        with self._phase("schedule"):
            for i, slot in enumerate(self._slots):
                if slot is None or slot.prefilling \
                        or slot.done is not None \
                        or slot.dispatched >= slot.req.max_new_tokens:
                    continue
                bi = slot.feed_pos // self.block_size
                if not self._paged:
                    pass               # no pool: nothing to grow or copy
                elif bi >= len(slot.blocks):
                    # on-demand growth: the feed position opens a new
                    # block
                    got = self.cache.allocate(1)
                    if got is None:
                        self._m_stalls.labels(
                            path="decode", shard=self._shard).inc()
                        self.flight.record("stall", slot.req.req_id,
                                           path="decode")
                        continue       # stalled this iteration
                    slot.blocks.extend(got)
                    self._update_pool_gauges()
                elif self.cache.needs_cow(slot.blocks[bi]):
                    # the write position sits in a block other owners
                    # (or the prefix cache) still read — promote to a
                    # private copy so the shared KV stays
                    # byte-identical for them
                    if not self._cow_promote(slot, bi):
                        continue       # pool pressure: stalled
                runnable.append(i)
        return runnable

    def _unread_first(self, prev):
        """The unread record of a prompt's first token a decode step
        may feed from: of the chunk launched alone this iteration, or
        of the chunk the unread decode step carried. Never both over
        runnable lanes: a chunk runs alone only where no lane could
        decode beside it."""
        if self._first is not None or prev is None:
            return self._first
        return prev.first

    def _feed_rows(self, runnable, prev):
        """The ahead order's feed column: the host's column holds the
        lanes whose newest token the host has read; every other
        runnable lane's newest token is still on the device — in the
        unread decode step's output or in the output of the chunk
        that ended its prompt (this iteration's, or the one the unread
        step carried). Returns the two host
        rows of the select that merges the three on the device (which
        lanes take the unread step's token, which lane the chunk's),
        or [] where the host has read every token."""
        from_prev = np.zeros(self.num_slots, bool)
        first_lane = -1
        first = self._unread_first(prev)
        first = None if first is None else first.slots[0]
        for i in runnable:
            slot = self._slots[i]
            if slot is first:
                first_lane = i
            elif slot.ahead:
                from_prev[i] = True
        if first_lane < 0 and not from_prev.any():
            return []
        return [from_prev, np.int32(first_lane)]

    def _unread_outputs(self, prev):
        """The two device values `_feed_select` merges: the unread
        decode step's tokens and the first token of the chunk that
        ended a prompt (`_unread_first`). Where one is absent, zeros
        placed as the other, a compiled step's output, is (committed
        to its sharding or not), so the select compiles once."""
        outs = [None if r is None else r.out
                for r in (prev, self._unread_first(prev))]
        if self._no_unread is None:
            like = outs[0] if outs[1] is None else outs[1]
            where = (like.sharding,) if like.committed else ()
            self._no_unread = (
                jax.device_put(np.zeros(self.num_slots, np.int32),
                               *where),
                jax.device_put(np.int32(0), *where))
        return [h if o is None else o
                for o, h in zip(outs, self._no_unread)]

    def _plain_dispatch(self, runnable, prev=None, chunk=None):
        """Dispatch stage of a plain decode step: build the dynamic
        host rows, move them in one `_put_host_args` batch, and issue
        the compiled step WITHOUT waiting on its output. With `prev`
        (the ahead order: the decode step still unread) the feed
        column comes from the device wherever the host has not read
        the token (`_feed_rows`). With `chunk` (`_chunk_schedule`'s)
        the step is the one program that carries that chunk's rows
        too. Returns the `_InFlight`
        record the complete stage consumes."""
        t_span = now_us()
        with self._phase("dispatch"):
            tokens = np.zeros((self.num_slots, 1), np.int32)
            positions = np.zeros(self.num_slots, np.int32)
            tables = np.zeros((self.num_slots, self.max_blocks),
                              np.int32)
            arows = np.zeros(self.num_slots, np.int32)
            srows = np.zeros(self.num_slots, np.int32)
            for i in runnable:
                slot = self._slots[i]
                if not slot.ahead:
                    tokens[i, 0] = slot.feed_token
                positions[i] = slot.feed_pos
                tables[i, :len(slot.blocks)] = slot.blocks
                arows[i] = slot.adapter_page
                srows[i] = slot.state_row
            # no pool, no tables: the step takes None in their place
            rows = [tokens, positions, tables if self._paged else None]
            if self.sampling:
                # per-slot sampling rows (idle/greedy lanes ride temp
                # 0 — the argmax select, like the null block)
                rows.extend(self._sampling_host_rows())
            if self.cache.state:
                # lanes that do not decode this step (idle, stalled,
                # prefilling) ride the null row 0: their state stays
                rows.append(srows)
            if self.adapter_pool is not None:
                # per-slot adapter page row (idle/stalled lanes ride
                # the null page 0 — exact-zero delta, like the null
                # block)
                rows.append(arows)
            select = self._feed_rows(runnable, prev) \
                if self._goes_ahead else []
            args = self._put_host_args(rows + select)
            if select:
                *args, from_prev, first_lane = args
                args[0] = self._feed_select(
                    args[0], from_prev, first_lane,
                    *self._unread_outputs(prev))
            if chunk is not None:
                args = self._chunk_args(*chunk) + args
            with RecordEvent("engine.decode"):
                t_dec = time.perf_counter()
                if chunk is None:
                    nxt = self._dispatch_step(self._decode, *args)
                else:
                    first, nxt = self._dispatch_step(self._fused, *args,
                                                     n_out=2)
        for i in runnable:
            self._slots[i].ahead += 1
        self._step_seq += 1
        self.decode_steps += 1
        if prev is not None:
            self.decode_steps_ahead += 1
            self._m_steps_ahead.inc()
        inflight = _InFlight(out=nxt, runnable=runnable,
                             slots=[self._slots[i] for i in runnable],
                             counters=self._step_counters,
                             t_dec=t_dec, t_span=t_span,
                             seq=self._step_seq)
        if chunk is not None:
            inflight.first = self._chunk_launched(*chunk, first, t_dec,
                                                  t_span)
        return inflight

    def _plain_complete(self, inflight):
        """Complete stage of a plain decode step: read the device
        output (the sync, measured as `device_wait`: the whole step
        in the serial order, the throttle in the ahead order, where
        the next step is already queued behind this one), then the
        per-lane finish walk."""
        with self._phase("device_wait"):
            nxt = np.asarray(inflight.out)      # sync: tokens are out
        now = time.perf_counter()
        # the step had the device from its launch or, launched ahead,
        # from the read of the step before it
        self._m_decode_seconds.observe(
            now - max(inflight.t_dec, self._t_read))
        self._t_read = now
        self._trace_span("decode.step", inflight.t_span, cat="engine",
                         lanes=len(inflight.runnable))
        t_dec = inflight.t_dec
        with self._phase("finish"):
            if inflight.counters is not None:
                self._note_step_counters(np.asarray(inflight.counters))
            for i, slot in zip(inflight.runnable, inflight.slots):
                slot.ahead -= 1
                if slot.done is not None:
                    # this step was launched before the host read the
                    # lane's EOS: its token is discarded, and with the
                    # step complete the lane gives back what it holds
                    self.overshoot_tokens += 1
                    self._m_overshoot.inc()
                    self.flight.record("overshoot", slot.req.req_id)
                    self._release(slot)
                    self._slots[i] = None
                    continue
                tok = int(nxt[i])
                is_first = not slot.generated   # full-prefix-hit lane
                slot.generated.append(tok)
                req = slot.req
                self._note_tokens(req)
                if is_first:
                    # this decode produced the request's FIRST token
                    # (its whole prompt came from the prefix cache)
                    if req.arrived_at is not None:
                        self._obs_ttft(req, now - req.arrived_at)
                    self.flight.record("first_token", req.req_id)
                    self._trace_instant("request.first_token", req)
                elif slot.last_token_at is not None:
                    # inter-token latency per SLOT, not this
                    # iteration's wall time: a lane that sat out N
                    # stalled iterations reports the (N+1)-iteration
                    # gap its user experienced
                    self._obs_tpot(req, now - slot.last_token_at)
                slot.last_token_at = now
                done_eos = req.eos_token_id is not None \
                    and tok == req.eos_token_id
                if done_eos or len(slot.generated) >= req.max_new_tokens:
                    if is_first:
                        # single-token request: its only token still
                        # lands in the TPOT histogram (producing-step
                        # latency)
                        self._obs_tpot(req, now - t_dec)
                    self._retire(i, slot,
                                 "eos" if done_eos else "length")
        return len(inflight.runnable)

    def _spec_schedule(self):
        """Schedule stage of a speculative verify step: draft up to K
        tokens per decode-phase lane (host-side, between compiled
        steps — or joined from the async core's drafter thread via
        `_next_drafts`), then grow and COW-protect every block the
        `[feed_pos, feed_pos+k]` write window touches. Rejection is
        rollback by position: the lane simply does not advance past
        the accepted prefix, so the rejected rows' KV is unreachable
        (attention is position-bounded) until the next window
        overwrites it. A lane that cannot get blocks for its window
        degrades to a draftless (plain-decode) window before it
        stalls. Returns (runnable lane indices, lane -> draft)."""
        K = self.spec_decode_k
        bs = self.block_size
        vocab = self.spec.vocab_size
        runnable, drafts = [], {}
        with self._phase("schedule"):
            for i, slot in enumerate(self._slots):
                if slot is None or slot.prefilling:
                    continue
                req = slot.req
                # window budget: emitted tokens cap at the request's
                # remaining allowance, and the last write position
                # must stay inside the model's length
                budget = min(
                    K,
                    req.max_new_tokens - len(slot.generated) - 1,
                    self.max_model_len - 1 - slot.feed_pos)
                draft = []
                if budget > 0:
                    with self._phase("draft_propose"):
                        # async core: the drafter thread proposed this
                        # window from the SAME post-walk context while
                        # admissions ran — identical inputs, identical
                        # draft (the serial-vs-async identity gate).
                        # Serial core / fresh lanes: propose inline.
                        draft = self._next_drafts.pop(slot, None)
                        if draft is None:
                            draft = draft_window(
                                self.drafter, req.prompt,
                                slot.generated, budget, vocab)
                # grow the table to cover the window's last write;
                # under pool pressure shed the draft (plain one-token
                # window) before stalling the lane outright
                stalled = False
                while True:
                    need = (slot.feed_pos + len(draft)) // bs + 1 \
                        - len(slot.blocks)
                    if need <= 0:
                        break
                    got = self.cache.allocate(need)
                    if got is not None:
                        slot.blocks.extend(got)
                        self._update_pool_gauges()
                        break
                    if not draft:
                        self._m_stalls.labels(
                            path="decode", shard=self._shard).inc()
                        self.flight.record("stall", req.req_id,
                                           path="decode")
                        stalled = True
                        break
                    draft = []         # degrade: draftless step
                    self._m_stalls.labels(
                        path="spec_degrade", shard=self._shard).inc()
                    self.flight.record("stall", req.req_id,
                                       path="spec_degrade")
                if stalled:
                    continue
                # copy-on-write over EVERY block the window writes
                # into — a speculative write must never land in a
                # block other owners (or the prefix cache) still read
                def cow_window(k_len, count_stall):
                    for bi in range(slot.feed_pos // bs,
                                    (slot.feed_pos + k_len) // bs + 1):
                        if self.cache.needs_cow(slot.blocks[bi]) \
                                and not self._cow_promote(
                                    slot, bi, count_stall=count_stall):
                            return False
                    return True

                if not cow_window(len(draft), count_stall=False):
                    # pool pressure mid-window: shed the draft AND the
                    # surplus tail blocks past the feed block (always
                    # private — they only ever held rejected rows), so
                    # the pool gets them back, then retry the plain
                    # one-token window. Without this a lane could sit
                    # on window blocks while stalling on the COW copy
                    # — deadlocking pools where the K=0 engine
                    # progresses. The degrade is its own stall flavor:
                    # the lane still RUNS, so it must not read as a
                    # skipped iteration.
                    feed_bi = slot.feed_pos // bs
                    surplus = slot.blocks[feed_bi + 1:]
                    if surplus:
                        del slot.blocks[feed_bi + 1:]
                        self.cache.free(surplus)
                        self._update_pool_gauges()
                    if draft:
                        draft = []
                        self._m_stalls.labels(
                            path="spec_degrade", shard=self._shard).inc()
                        self.flight.record("stall", req.req_id,
                                           path="spec_degrade")
                    if not cow_window(0, count_stall=True):
                        continue       # truly stalled this iteration
                drafts[i] = draft
                runnable.append(i)
        return runnable, drafts

    def _spec_dispatch(self, runnable, drafts):
        """Dispatch stage of a speculative verify step: score all K+1
        positions of every runnable lane in ONE compiled pass, issued
        without waiting (one fused `_put_host_args` transfer for the
        dynamic rows). Returns the `_InFlight` record."""
        K = self.spec_decode_k
        W = K + 1
        t_span = now_us()
        with self._phase("dispatch"):
            tokens = np.zeros((self.num_slots, W), np.int32)
            positions = np.zeros(self.num_slots, np.int32)
            dlens = np.zeros(self.num_slots, np.int32)
            tables = np.zeros((self.num_slots, self.max_blocks),
                              np.int32)
            arows = np.zeros(self.num_slots, np.int32)
            for i in runnable:
                slot = self._slots[i]
                d = drafts[i]
                tokens[i, 0] = slot.feed_token
                if d:
                    tokens[i, 1:1 + len(d)] = d
                positions[i] = slot.feed_pos
                dlens[i] = len(d)
                tables[i, :len(slot.blocks)] = slot.blocks
                arows[i] = slot.adapter_page
            rows = [tokens, positions, dlens, tables]
            if self.sampling:
                rows.extend(self._sampling_host_rows())
            if self.adapter_pool is not None:
                rows.append(arows)
            args = self._put_host_args(rows)
            with RecordEvent("engine.decode"):
                t_dec = time.perf_counter()
                out_dev = self._dispatch_step(self._decode, *args,
                                              n_out=self._decode_n_out)
        self._step_seq += 1
        return _InFlight(out=out_dev, runnable=runnable,
                         slots=[self._slots[i] for i in runnable],
                         drafts=drafts, t_dec=t_dec, t_span=t_span,
                         seq=self._step_seq)

    def _spec_complete(self, inflight, synced):
        """Complete stage of a speculative verify step: sync on the
        verify output, then emit the longest draft prefix the target
        confirms plus the target's own next token, per lane. The
        acceptance/sample walks stay on the step thread (their result
        decides the next window's context AND which lanes retire —
        allocator state must not change under an in-flight reader).
        `synced` as in `_plain_complete`."""
        K = self.spec_decode_k
        out_dev = inflight.out
        if synced:
            if self.sampling:
                choices = np.asarray(out_dev[0])
                accepts = np.asarray(out_dev[1])
                nxt = None
            else:
                nxt = np.asarray(out_dev)
        else:
            with self._phase("device_wait"):
                if self.sampling:
                    # sync: per-row stop-choices + accept flags
                    choices = np.asarray(out_dev[0])
                    accepts = np.asarray(out_dev[1])
                    nxt = None
                else:
                    # sync: [slots, K+1] argmaxes
                    nxt = np.asarray(out_dev)
        self._m_decode_seconds.observe(
            time.perf_counter() - inflight.t_dec)
        self._trace_span("decode.verify", inflight.t_span, cat="engine",
                         lanes=len(inflight.runnable), k=K)
        t_dec = inflight.t_dec
        now = time.perf_counter()
        with self._phase("finish"):
            for i, slot in zip(inflight.runnable, inflight.slots):
                req = slot.req
                d = inflight.drafts[i]
                if self.sampling:
                    # rejection-sampling acceptance (computed on
                    # device): accept the longest draft prefix whose
                    # coins passed, then the stop row's choice — the
                    # residual resample on a rejection, the bonus draw
                    # on a full accept. Greedy lanes' flags are exact
                    # argmax equality and their choices the argmax, so
                    # this walk reproduces the exact-acceptance stream
                    # bit-for-bit.
                    with self._phase("sample_walk"):
                        n = 0
                        while n < len(d) and accepts[i, n]:
                            n += 1
                        acc = [int(t) for t in d[:n]] \
                            + [int(choices[i, n])]
                else:
                    # exact greedy acceptance: the target's own next
                    # token, then every draft token that EQUALS the
                    # target's argmax at its position (each match
                    # validates the next column)
                    with self._phase("accept_walk"):
                        out = nxt[i]
                        acc = [int(out[0])]
                        for j, dj in enumerate(d):
                            if dj != int(out[j]):
                                break
                            acc.append(int(out[j + 1]))
                self._m_spec_ok.inc(len(acc) - 1)
                self._m_spec_rej.inc(len(d) - (len(acc) - 1))
                # EOS / length truncation: emit stops AT the first
                # stop token, exactly like the one-token path would
                emit = []
                for t in acc:
                    emit.append(t)
                    if (req.eos_token_id is not None
                            and t == req.eos_token_id) \
                            or len(slot.generated) + len(emit) \
                            >= req.max_new_tokens:
                        break
                m_tok = len(emit)
                is_first = not slot.generated  # full-prefix-hit lane
                slot.generated.extend(emit)
                self._note_tokens(req, m_tok)
                self._m_spec_accepted.observe(m_tok)
                proposed = self._m_spec_ok.value \
                    + self._m_spec_rej.value
                if proposed:
                    self._m_spec_hit_rate.set(
                        self._m_spec_ok.value / proposed)
                if is_first:
                    if req.arrived_at is not None:
                        self._obs_ttft(req, now - req.arrived_at)
                    self.flight.record("first_token", req.req_id)
                    self._trace_instant("request.first_token", req)
                # multi-token latency accounting: every accepted token
                # is recorded against its producing step — the lane's
                # step gap amortized per token, so TPOT sums still
                # integrate to wall time and m_tok=1 degenerates to
                # the plain path
                gap = now - (t_dec
                             if is_first or slot.last_token_at is None
                             else slot.last_token_at)
                n_tpot = m_tok - 1 if is_first else m_tok
                for _ in range(n_tpot):
                    self._obs_tpot(req, gap / m_tok)
                slot.last_token_at = now
                done_eos = req.eos_token_id is not None \
                    and emit[-1] == req.eos_token_id
                if done_eos or len(slot.generated) >= req.max_new_tokens:
                    if is_first and n_tpot == 0:
                        # single-token instant finisher: keep it
                        # visible (the PR-6 TPOT contract)
                        self._obs_tpot(req, now - t_dec)
                    self._retire(i, slot,
                                 "eos" if done_eos else "length")
        return len(inflight.runnable)

    def step(self):
        """One scheduler iteration: admit queued requests into free
        lanes, run AT MOST one prefill chunk (long prompts never
        monopolize an iteration), and one batched decode
        step over every decode-phase lane. Returns the number of
        admissions/chunks/lanes that made progress.

        By default the iteration is PIPELINED: it returns with one
        decode step launched and unread, so the device always has its
        next program queued while the host walks, admits and
        schedules. Plain decode goes one step AHEAD (`_step_ahead`:
        step N+1 is launched from step N's tokens on the device before
        the host reads them); the speculative verify step completes
        step N before it launches N+1 (`_step_async`), because the
        next window's content is the accepted prefix, which only the
        host's walk knows. `async_core=False`
        is the serial order below: launch, read, walk, one after the
        other — what the parity tests compare against."""
        if self.async_core:
            if self.spec_decode_k:
                return self._step_async()
            return self._step_ahead()
        with RecordEvent("engine.step"):
            t_wall = self._step_begin()
            progressed = self._admit()
            progressed += self._prefill_step()
            progressed += self._decode_step()
            self._flush_step_phases(time.perf_counter() - t_wall)
            self._end_of_step_gauges()
            return progressed

    @property
    def _goes_ahead(self):
        """Whether decode steps are launched ahead of the host's read
        of the step before: a property of the step (a plain decode
        step's inputs need no host walk), not a knob."""
        return self.async_core and not self.spec_decode_k

    # -- pipelined engine core: plain decode, one step ahead ---------------
    def _step_ahead(self):
        """One iteration of the ahead order. When the call begins,
        the decode step D(c-1) of the previous call is in flight and
        unread. The call

        (a) admits, and launches at most one prefill chunk P(c);
        (b) schedules and launches D(c), fed from D(c-1)'s tokens
            where they lie, on the device — and from P(c)'s, for the
            lane whose prompt just ended; only lanes whose newest
            token the host has read take it from the host's row;
        (c) only THEN waits for D(c-1) (`engine.device_wait`: the one
            wait, a throttle — D(c) is queued behind it), reads its
            tokens, runs the finish walk and retires lanes; then reads
            P(c)'s first token by a wait on that chunk alone. Both
            tokens are read as their programs end, so `first_token`
            and `finish` stamps stay on one footing.

        It returns with D(c) in flight: between two decode programs
        the device always has the next one queued, and exactly one
        decode step is unread. What the host learns late is safe by
        one rule (`_release`): a lane gives back its blocks, state row
        and adapter page only when the last step launched over it has
        completed. A finish by length is a count, so the scheduler
        leaves the lane out of D(c) and nothing overshoots; an EOS is
        seen one step late: the lane rides D(c), that token is
        discarded (`engine_overshoot_tokens_total`) and the lane is
        vacated at D(c)'s walk. A finished request's result is out at
        the end of the call after the one that launched its last
        step.

        Where the model's spec offers ONE step for a chunk's rows and
        the decode rows (`decode_with_chunk`: every weight crosses
        once for both) and the iteration holds both kinds of work, (a)
        and (b) are one program M(c), launched where D(c) is and
        unread like it when the call returns. The lane whose prompt
        it ended decodes from D(c+1) on, fed from M(c)'s first token
        on the device; that token is read with M(c)'s decode tokens by
        call c+1's one wait. A chunk with no lane to decode beside it,
        and a model that offers no such step, run the two programs as
        above.

        `progressed` counts admissions, prefill chunks and COMPLETED
        decode lanes, as the serial order does; a call that only
        launched counts that launch, so `run()`'s no-progress check
        stays sound."""
        with RecordEvent("engine.step"):
            t_wall = self._step_begin()
            progressed = self._admit()
            prev = self._inflight
            chunk = None
            if self._fused is None:
                progressed += self._prefill_step()
                runnable = self._plain_schedule()
            else:
                # the decode lanes BESIDE the chunk: its own lane is
                # still in its prompt, and joins the next step
                chunk = self._chunk_schedule()
                runnable = self._plain_schedule()
                progressed += chunk is not None
                if chunk is not None and not runnable:
                    # nothing decodes beside it: the chunk's own
                    # program, and where it ended its prompt that
                    # lane decodes behind it, as without the fused
                    # step
                    self._chunk_dispatch(*chunk)
                    chunk = None
                    runnable = self._plain_schedule()
            self._inflight = self._plain_dispatch(runnable, prev, chunk) \
                if runnable else None
            if prev is not None:
                progressed += self._plain_complete(prev)
                if prev.first is not None:
                    # the same program's: no second wait
                    self._first_complete(prev.first)
            first, self._first = self._first, None
            if first is not None:
                self._first_complete(first)
            self._prefetch_ahead()
            if not progressed and self._inflight is not None:
                progressed = 1
            self._flush_step_phases(time.perf_counter() - t_wall)
            self._end_of_step_gauges()
            return progressed

    # -- pipelined engine core: speculative verify, complete-then-launch ---
    def _step_async(self):
        """One pipelined iteration of a SPECULATIVE engine (r21's
        order: the next window's content is the accepted prefix, so
        step N completes before step N+1 is launched). Stage order
        per call:

        1. COMPLETE step N: `jax.block_until_ready` on the in-flight
           output the PREVIOUS call dispatched. `device_wait` here is
           the true residual — every host stage since that dispatch
           (the previous call's adapter prefetch, the caller's
           inter-step work, e.g. the fleet's other replicas) already
           overlapped the device time. The acceptance/sample walks and
           lane retirement stay on the step thread: their results
           decide the NEXT window's context.
        2. SPAWN the drafter helper: every decode lane's next-window
           proposal runs on a short-lived thread over SNAPSHOTS of the
           post-walk context — identical inputs to the serial
           proposal, so drafts (and therefore sampled lanes'
           acceptance coins) cannot diverge.
        3. ADMIT + one prefill chunk on the step thread, concurrently
           with the helper.
        4. SCHEDULE + DISPATCH step N+1: drafts joined from the
           helper (lanes the helper missed — just admitted or fresh
           out of prefill — propose inline, exactly the serial path),
           dynamic rows ride ONE fused `device_put` tree, and the
           dispatched step stays in the in-flight slot for the next
           call.
        5. PREFETCH the queue head's adapter page: the compiled
           swap-in dispatch is cheap host-side and the page copy
           overlaps step N+1 on device, so the NEXT call's admission
           acquires a resident page.

        `progressed` counts admissions, prefill chunks, and COMPLETED
        decode lanes — a dispatch is credited only when its result is
        consumed, so run totals match the serial core and `run()`'s
        no-progress deadlock check stays sound (an outstanding
        in-flight step always progresses on the next call)."""
        with RecordEvent("engine.step"):
            t_wall = self._step_begin()
            progressed = self._complete_inflight()
            self._spawn_ahead()
            progressed += self._admit()
            progressed += self._prefill_step()
            self._next_drafts = self._collect_ahead()
            self._dispatch_ahead()
            self._next_drafts = {}
            self._prefetch_ahead()
            self._flush_step_phases(time.perf_counter() - t_wall)
            self._end_of_step_gauges()
            return progressed

    def _complete_inflight(self):
        """Retire the verify step dispatched ahead, if one is
        outstanding: block for the device residual, then run the
        normal complete stage (walks + finish) on the step thread."""
        inflight = self._inflight
        if inflight is None:
            return 0
        self._inflight = None
        with self._phase("device_wait"):
            # the ONLY wait of the pipeline: everything since the
            # dispatch already ran behind the device step
            jax.block_until_ready(inflight.out)
        self.flight.record("async_complete", seq=inflight.seq,
                           lanes=len(inflight.runnable))
        return self._spec_complete(inflight, synced=True)

    def _dispatch_ahead(self):
        """Schedule + dispatch the next verify step into the single
        in-flight slot — no wait; the next `step()` call (or `drain`)
        completes it."""
        runnable, drafts = self._spec_schedule()
        if not runnable:
            return
        self._inflight = self._spec_dispatch(runnable, drafts)
        self.flight.record("async_dispatch", seq=self._inflight.seq,
                           lanes=len(runnable))

    def _spawn_ahead(self):
        """Launch the drafter helper thread: propose every decode
        lane's next verify window off the step thread while admissions
        and the prefill chunk run. Jobs snapshot `generated` (the live
        list mutates when lanes advance) and run the pure
        `draft_window` — see its thread-safety contract. The helper's
        `draft_propose` seconds land on ITS thread-confined PhaseTimer
        clock, never in the step's host-gap partition."""
        if not self.spec_decode_k or self.drafter is None:
            return
        K = self.spec_decode_k
        vocab = self.spec.vocab_size
        jobs = []
        for slot in self._slots:
            if slot is None or slot.prefilling:
                continue
            budget = min(
                K,
                slot.req.max_new_tokens - len(slot.generated) - 1,
                self.max_model_len - 1 - slot.feed_pos)
            if budget > 0:
                jobs.append((slot, slot.req.prompt,
                             list(slot.generated), budget))
        if not jobs:
            return
        out = {}
        phases = self._phases
        drafter = self.drafter

        def work():
            for slot, prompt, generated, budget in jobs:
                with phases.phase("draft_propose"):
                    out[slot] = draft_window(drafter, prompt,
                                             generated, budget, vocab)

        t = threading.Thread(target=work, name="paddle-draft-ahead",
                             daemon=True)
        t.start()
        self._ahead = (t, out)

    def _collect_ahead(self):
        """Join the drafter helper. Only the step thread's residual
        wait (usually ~zero — admissions ran in between) lands in its
        own `draft_propose` phase; the proposals themselves were
        clocked on the helper's thread."""
        ahead = self._ahead
        if ahead is None:
            return {}
        self._ahead = None
        t, out = ahead
        with self._phase("draft_propose"):
            t.join()
        return out

    def _prefetch_ahead(self):
        """Warm the NEXT admission's adapter page behind the step just
        dispatched: `PagedAdapterPool.prefetch` costs one compiled
        swap-in dispatch on the host while the page copy overlaps the
        in-flight step on device, and it never takes a reference or
        evicts a live page — so the next call's `_acquire_adapter`
        finds the page resident and pays no transfer in the host
        gap."""
        if self.adapter_pool is None:
            return
        req = self._peek_request()
        if req is None or not req.adapter_id \
                or not self.adapter_pool.registry.has(req.adapter_id):
            return
        if self.adapter_pool.page_of(req.adapter_id) is not None:
            return                     # already resident (warm or live)
        page = self.adapter_pool.prefetch(req.adapter_id)
        if page is not None:
            self.flight.record("adapter_prefetch", req.req_id,
                               adapter=int(req.adapter_id), page=page)
            self._update_adapter_gauges()

    def _end_of_step_gauges(self):
        self._m_active.set(self.num_active)
        self._m_queue.set(self.num_pending)
        self._update_pool_gauges()
        self._update_adapter_gauges()
        self._sample_traces()
        if self._m_trace_spans is not None:
            total = self.tracer.total_recorded
            self._m_trace_spans.inc(total - self._trace_spans_seen)
            self._trace_spans_seen = total
            dropped = self.tracer.dropped
            self._m_trace_dropped.inc(dropped - self._trace_dropped_seen)
            self._trace_dropped_seen = dropped

    @property
    def num_active(self):
        return sum(s is not None for s in self._slots)

    @property
    def num_pending(self):
        return sum(len(self._queues[p]) for p in PRIORITY_CLASSES)

    @property
    def free_lanes(self):
        """Decode lanes currently vacant — the fleet's adopt/seat
        headroom signal."""
        return self._slots.count(None)

    def pop_results(self):
        """Drain finished results incrementally: {req_id: tokens} for
        every request that finished since the last pop (None = shed).
        The fleet's collection path — it drives `step()` itself and
        must see finishes as they happen, not at end-of-trace like
        `run()` (which empties the same buffer)."""
        out, self._results = self._results, {}
        return out

    def best_of_n(self, prompt, n, max_new_tokens,
                  sampling_params=None, eos_token_id=None,
                  priority="standard", adapter_id=0):
        """Fan ONE prompt into `n` sampled candidates sharing its
        prefix-cache blocks: candidate 0 is served first (its prefill
        writes and registers the prompt's full blocks ONCE), then
        candidates 1..n-1 admit with a full-prefix hit — the shared
        prompt blocks are seated read-only in each lane's table, never
        re-prefilled and never duplicated (copy-on-write keeps decode
        writes private, the PR 6 contract). Candidate i samples under
        seed `base + i` (base from `sampling_params.seed`, or the
        engine counter when None), so a fixed base replays all n
        candidates token-for-token.

        Drives `run()`; other queued work is served along the way and
        its finishes stay collectable via `pop_results`/`run`. Returns
        the n candidate token lists (prompt + generated), seed
        order."""
        self._refuse("fork")
        params, base, self._seed_counter = _best_of_n_intake(
            self, sampling_params, n, self._seed_counter)
        out, stash = _best_of_n_fanout(
            lambda p: self.add_request(
                prompt, max_new_tokens, eos_token_id=eos_token_id,
                priority=priority, adapter_id=adapter_id,
                sampling_params=p),
            self.run, params, n, base)
        # bystander finishes collected by the two run()s stay
        # deliverable through the normal channels
        self._results.update(stash)
        return out

    # -- disaggregated prefill/decode (fleet handoff) ----------------------
    def take_handoff(self, req_id):
        """Claim a finished prefill-only request's parked KV footprint:
        returns (block ids, prefix-cache hit tokens). The caller owns
        the blocks' references now — export their rows (the
        `ops.paged_attention.export_pool_block` / `ingest_pool_block`
        pair is the transfer unit), then hand them back with
        `release_handoff`."""
        return self._handoffs.pop(req_id)

    def release_handoff(self, blocks):
        """Return a handed-off request's source blocks to the pool
        once their payload is exported. Prefix-cached blocks park in
        the evictable LRU (still matchable — the warm chain the fleet
        router steers toward survives the handoff); private blocks go
        back to the free list."""
        self.cache.free(blocks)
        self._update_pool_gauges()

    def adopt_request(self, prompt, first_token, blocks,
                      max_new_tokens, eos_token_id=None, req_id=None,
                      priority="standard", arrived_at=None,
                      adapter_id=0, sampling_params=None,
                      trace_id=None):
        """Seat a request whose prompt KV is ALREADY in this engine's
        pool — the decode-side intake of disaggregated serving. The
        fleet allocates `blocks` from this engine's cache, ingests the
        prefill replica's exported rows into them, then adopts:
        `first_token` (the token the remote final prefill chunk
        produced) seeds the lane and decode continues exactly as if
        the prefill had run here — same compiled steps, same pool
        contents, token-identical output. `max_new_tokens` is the
        request's ORIGINAL budget (the first token counts against it).
        Raises when no lane is free (check `free_lanes` first) — the
        fleet, not the engine, owns handoff queueing. The first token
        is not re-counted in `tokens_generated` (its producing replica
        already counted it). `adapter_id` is the tenant adapter the
        request decodes under — the page comes from THIS engine's
        adapter pool (the prefill replica's page never travels); the
        fleet probes `adapter_page_available` before placing, so an
        unavailable page here is a caller bug and raises.
        `sampling_params` must arrive with its seed RESOLVED (the
        prefill replica's seed travels with the handoff): the adopted
        lane re-derives the exact per-slot key row the colocated lane
        would carry, so sampled disaggregated output stays
        token-identical to colocated."""
        self._refuse("handoff")
        adapter_id = self._check_adapter(adapter_id)
        sampling_params = self._check_sampling(sampling_params)
        if sampling_params is not None and not sampling_params.greedy \
                and sampling_params.seed is None:
            raise ValueError(
                "adopted sampled requests need an explicit seed — "
                "resolve it at fleet intake so the prefill replica's "
                "key state travels with the handoff")
        prompt, req_id = self._intake_guard(prompt, max_new_tokens,
                                            priority, req_id)
        need = math.ceil(prompt.size / self.block_size)
        if len(blocks) != need:
            raise ValueError(
                f"adopted prompt of {prompt.size} tokens needs exactly "
                f"{need} block(s), got {len(blocks)}")
        if None not in self._slots:
            raise RuntimeError(
                "no free lane to adopt into — check free_lanes before "
                "handing off")
        eos = self.eos_token_id if eos_token_id is None \
            else eos_token_id
        if self.tracing and trace_id is None:
            trace_id = new_trace_id()
        req = Request(req_id, prompt, int(max_new_tokens), eos,
                      arrived_at=arrived_at, priority=priority,
                      adapter_id=adapter_id, sampling=sampling_params,
                      trace_id=trace_id)
        self.flight.record("adopted", req_id, blocks=len(blocks))
        self._trace_instant("request.adopted", req,
                            blocks=len(blocks))
        page = self._acquire_adapter(req)
        if page is None:
            raise RuntimeError(
                f"no free adapter page for adapter {adapter_id} — "
                "probe adapter_page_available before adopting")
        now = time.perf_counter()
        slot = _Slot(req=req, blocks=[int(b) for b in blocks],
                     generated=[int(first_token)],
                     last_token_at=now, prefill_pos=int(prompt.size),
                     admit_seq=self._admit_counter,
                     adapter_page=page,
                     **self._slot_sampling_fields(req))
        self._admit_counter += 1
        self._slots[self._slots.index(None)] = slot
        self._m_admissions.inc()
        self._update_pool_gauges()
        done_eos = (eos is not None and int(first_token) == eos)
        if done_eos or int(max_new_tokens) <= 1:
            # already complete on arrival (EOS'd or single-token
            # budget): retire immediately, blocks back to the pool
            self._finish(slot, "eos" if done_eos else "length")
            self._slots[self._slots.index(slot)] = None
        self._m_active.set(self.num_active)
        return req_id

    def drain(self):
        """Graceful replica shutdown: close admissions (add_request /
        adopt_request raise from now on), run every queued and
        in-flight request to completion, then AUDIT the pool — every
        non-null block must be back on the free list or parked as a
        refcount-zero prefix-cache block (`PagedKVCache.leak_check`).
        A parked handoff fails the drain loudly: its blocks are
        intentionally held, so the fleet must export-and-release
        before retiring the replica. Returns the drained results
        (run()'s contract). Catches the block-leak class the
        allocator's double-free hardening cannot see — a block freed
        zero times instead of twice."""
        self._draining = True
        out = self.run()
        if self._handoffs:
            raise self._audit_error(
                f"{len(self._handoffs)} handoff(s) still parked — "
                "take_handoff/release_handoff them before draining "
                "the replica")
        leaked = self.cache.leak_check()
        if leaked:
            raise self._audit_error(
                f"drain leak check failed: block(s) {leaked} neither "
                "free nor prefix-cached after all lanes finished — a "
                "scheduler path dropped a reference without freeing")
        leaked = self.cache.state_leak_check()
        if leaked:
            raise self._audit_error(
                f"drain leak check failed: state row(s) {leaked} still "
                "held after all lanes finished")
        if self.adapter_pool is not None:
            leaked = self.adapter_pool.leak_check()
            if leaked:
                raise self._audit_error(
                    f"drain leak check failed: adapter page(s) "
                    f"{leaked} still referenced after all lanes "
                    "finished — a scheduler path vacated a lane "
                    "without releasing its adapter page")
        self._end_of_step_gauges()
        return out

    def run(self):
        """Drive until every queued/admitted request finished; returns
        (and drains) {req_id: prompt + generated tokens; None for a
        request shed at saturation}."""
        while self.num_pending or self.num_active:
            if self.step() == 0:
                req = self._peek_request()
                if req is not None:
                    blocker = ("no admission fits (next request needs "
                               f"{math.ceil(req.prompt.size / self.block_size)}"
                               " blocks)")
                else:
                    stalled = sum(s is not None and s.prefilling
                                  for s in self._slots)
                    blocker = (f"{stalled} lane(s) stalled in prefill "
                               f"and {self.num_active - stalled} in "
                               "decode growth/copy-on-write, all "
                               "waiting on a block")
                raise RuntimeError(
                    "generation engine deadlocked: "
                    f"{blocker} with {self.cache.num_free} free blocks "
                    "— grow num_blocks or shrink "
                    "num_slots/max_model_len")
        out, self._results = self._results, {}
        return out


def _last_prompt_row_token(spec, hidden, start, plen, width, sampling,
                           mp_axis=None):
    """The token a chunk's hidden rows `[1, width, hidden]` yield: the
    LAST REAL prompt position's logits give the first generated token;
    it lives in the final chunk — for earlier chunks the one-hot selects
    nothing and the host ignores the returned token. `sampling` is the
    one slot's `(temps, tks, tps, krows)` rows, or None for the argmax."""
    sel = (start + jnp.arange(width) == plen - 1) \
        .astype(hidden._array.dtype)
    h_last = (hidden._array * sel[None, :, None]) \
        .sum(axis=1, keepdims=True)
    logits = spec.logits(Tensor._wrap(h_last), mp_axis=mp_axis)
    if sampling is None:
        return jnp.argmax(logits._array[0, 0]).astype(jnp.int32)
    # the first generated token's draw folds plen-1 (it lands at
    # position plen) — identical to the full-prefix-hit decode's key
    # for that token
    from paddle_tpu.ops.sampling import sample_token

    return sample_token(logits._array[:, 0], *sampling,
                        jnp.maximum(plen - 1, 0).reshape(1))[0]


# -- trace contracts (tpu-verify) ---------------------------------------
# Declared HERE, next to the step builders, so the contract and the
# program evolve in one diff. The harvester
# (analysis/trace/harvest.py) constructs tiny engines over the full
# {dense,pallas} x K x mp matrix and lowers THESE OBJECTS' jitted
# steps; rules TPU101-TPU106 then enforce what is declared below.
# Donation comes from the same introspect table the constructor
# consumes; the collective budget is a lazy reference to the table of
# the one model whose steps are sharded today (the module whose
# all-gather and vocabulary-parallel embedding emit them keeps the
# canonical alias).
_SERVING_BUDGET = "paddle_tpu.jit.introspect:SERVING_STEP_AXIS_BUDGET"

for _step in ("engine_prefill_chunk", "engine_decode_step",
              "engine_decode_step_with_chunk", "engine_verify_step"):
    register_contract(TraceContract(
        name=_step,
        declared_at="paddle_tpu/inference/engine.py",
        donate_argnums=introspect.ENGINE_STEP_DONATION[_step],
        collective_budget=_SERVING_BUDGET,
        # decode/verify are the host loop body — one dispatch per
        # generated token, so their collectives sit on the per-token
        # latency path (tpu-shard TPU305 gates these against any
        # future slow/DCN mesh axis); the prefill chunk runs per
        # admission
        per_token=_step != "engine_prefill_chunk"))
del _step
