"""Replica-parallel serving tier: a prefix-affinity dp router over N
GenerationEngine replicas, with optional disaggregated prefill/decode.

PR 8 finished the mp axis — one engine spans a chip mesh. This module
is the dp axis: `ServingFleet` fronts N engine replicas (each
optionally mp-sharded and/or int8-quantized via the existing knobs)
with ONE host-side router, so aggregate tokens/s scales with replicas
while every per-engine win PRs 6-11 bought (prefix cache, QoS,
speculation, quantization) keeps paying per replica. Three layers:

- **Routing** (`add_request`): admission control (fleet `max_queue`
  shed — the HTTP-429 of this tier), QoS passthrough (priority rides
  to the replica's own class queues), and PREFIX-CACHE-AFFINITY
  placement: the router hashes the prompt's full-block chain with the
  exact `prefix_key` digests `PagedKVCache.match_prefix` /
  `register_prefix` key their block map with (one shared helper — a
  router key IS a cache key, the two cannot drift) and steers the
  request to the replica whose cache owns the deepest warm chain
  (`warm_prefix_tokens`, a read-only peek). Affinity yields to load
  with HYSTERESIS: the warm replica is used unless its backlog
  exceeds the least-loaded replica's by more than `affinity_slack`
  requests — so a hot tenant's shared prompt keeps hitting its warm
  blocks, but can't starve one replica while others idle. Cold
  requests go least-loaded (stable index tie-break), which is what
  keeps a 1-replica fleet BIT-IDENTICAL to a bare engine: same
  arrival order, same engine, same compiled steps. Under multi-tenant
  adapter serving (`engine_options["adapters"]`) the chain is SALTED
  with each request's adapter id — exactly the salt the caches use —
  so a hot base prompt under two tenants routes and caches
  independently.
- **Disaggregated prefill/decode** (`num_prefill_replicas > 0`):
  dedicated prefill replicas run chunked prefill to completion
  (`prefill_only` requests — max_new_tokens=1, the token the final
  chunk yields), then the router moves the finished prompt KV into a
  decode replica's pool BLOCK BY BLOCK: `export_pool_block` gathers
  each block's rows (plus its `[layers, 2]` int8 scale rows —
  `pool_spec()`/`scale_spec()` define the transfer unit) from the
  source pool, `ingest_pool_block` scatters them into
  freshly-allocated destination blocks (one compiled program each,
  traced block ids — shape-stable, donated destination pools), and
  `adopt_request` seats the lane mid-stream. Payloads are bit-copied,
  never re-quantized, so disaggregated output is TOKEN-IDENTICAL to a
  colocated engine — while long-prompt admission burns prefill-replica
  FLOPs only, never a decode step's.
- **Operations**: fleet metrics fold every replica's registry through
  `label_snapshot` + `merge_snapshots` (host-side, no collectives —
  replica-labeled TTFT/TPOT/pool/shed series, counters summing
  exactly); replicas register on the `distributed/launch` elastic
  registry (PADDLE_ELASTIC_TOKEN-authed, permanent leases — the
  launcher-owned-member class) and leave it through a graceful
  `drain`: stop admitting, finish in-flight lanes, leak-check the
  pool (`GenerationEngine.drain`), then drop the membership.

The fleet is single-process and host-driven like the engine itself:
`step()` round-robins every replica's scheduler iteration (jax's async
dispatch overlaps their device work), `run()` drives to completion.
Engines are the unit of failure and of elasticity; the router holds no
device state, so `add_replica`/`remove_replica` are metadata moves
plus (for remove) a drain.
"""
from __future__ import annotations

import time
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.inference.engine import (PRIORITY_CLASSES,
                                         GenerationEngine, prefix_key)
from paddle_tpu.inference.sampling import SamplingParams
from paddle_tpu.observability.metrics import (LATENCY_BUCKETS,
                                              MetricsRegistry,
                                              label_snapshot,
                                              merge_snapshots)
from paddle_tpu.observability.tracing import (TraceRecorder,
                                              export_timeline,
                                              new_trace_id, now_us,
                                              profiler_host_events)

__all__ = ["ServingFleet", "REPLICA_ROLES"]

#: A replica either serves end-to-end ("mixed", the default fleet) or
#: one side of the disaggregated split ("prefill" runs chunked prefill
#: to completion and hands KV blocks off; "decode" only ever adopts
#: handed-off lanes and decodes them).
REPLICA_ROLES = ("mixed", "prefill", "decode")

_ELASTIC_PREFIX = "fleet-replica-"


class _Replica:
    """One engine replica plus its router-side identity: stable id
    (never reused — removal must not re-key another replica's metrics
    or elastic membership), role, retirement flag (a retiring replica
    finishes its in-flight work but takes no new routes), and the
    replica-local compiled block export/ingest pair."""

    def __init__(self, rid, engine, role):
        self.rid = rid
        self.engine = engine
        self.role = role
        self.retired = False
        self._export, self._ingest = _build_transfer(engine)

    @property
    def load(self):
        """Router load signal: requests this replica has accepted but
        not finished (queued + seated)."""
        return self.engine.num_pending + self.engine.num_active


def _build_transfer(engine):
    """Compile the (export, ingest) pair for one replica's pool
    layout. Traced block ids — ONE program each serves every
    handed-off block. Ingest donates the destination pools (the same
    decision the engine made for its steps, read off its
    `_donate_argnums`) and pins the pool out_shardings at mp>1
    exactly like the engine's own steps, so the handoff write is
    in-place in HBM, never a pool rebuild. Export never donates: the
    source replica keeps serving from its pools."""
    from paddle_tpu.ops.paged_attention import (export_pool_block,
                                                ingest_pool_block)

    donate = bool(engine._donate_argnums)
    out_sh = engine._step_out_shardings(0)
    if engine.kv_dtype == "int8":
        def fleet_block_export(kp, vp, src, sc):
            return export_pool_block(kp, vp, src, sc)

        def fleet_block_ingest(kp, vp, kb, vb, dst, sc, srow):
            return ingest_pool_block(kp, vp, kb, vb, dst, sc, srow)

        exp = jax.jit(fleet_block_export)
        ing = jax.jit(fleet_block_ingest,
                      donate_argnums=(0, 1, 5) if donate else (),
                      out_shardings=out_sh)
    else:
        exp = jax.jit(export_pool_block)
        ing = jax.jit(ingest_pool_block,
                      donate_argnums=(0, 1) if donate else (),
                      out_shardings=out_sh)
    return exp, ing


class ServingFleet:
    """N GenerationEngine replicas behind one prefix-affinity router.

        fleet = ServingFleet(model, num_replicas=2, num_slots=8)
        fleet.add_request([1, 2, 3], max_new_tokens=32)
        results = fleet.run()            # {req_id: prompt + tokens}

    Disaggregated prefill/decode:

        fleet = ServingFleet(model, num_replicas=1,
                             num_prefill_replicas=1, num_slots=8)

    `engine_options` forwards to every replica's GenerationEngine
    (num_slots, block_size, attention_backend, spec_decode_k,
    kv_dtype/weight_dtype, mp_degree, ... — replicas are homogeneous;
    heterogeneous fleets route wrong on load). Each replica keeps its
    OWN metrics registry; `metrics_snapshot()` folds them
    replica-labeled. `elastic_endpoint` (+ token, default
    $PADDLE_ELASTIC_TOKEN) registers every replica on the launcher's
    elastic registry and `remove_replica`/`drain` leave it."""

    def __init__(self, model, num_replicas=1, num_prefill_replicas=0,
                 max_queue=None, affinity_slack=None,
                 elastic_endpoint=None, elastic_token=None,
                 registry=None, **engine_options):
        if num_replicas < 1:
            raise ValueError(
                f"need >= 1 serving replica, got {num_replicas}")
        if num_prefill_replicas < 0:
            raise ValueError(
                f"num_prefill_replicas must be >= 0, got "
                f"{num_prefill_replicas}")
        self.model = model
        self._engine_options = dict(engine_options)
        self.disaggregated = num_prefill_replicas > 0
        self.max_queue = None if max_queue is None else int(max_queue)
        self._elastic = None
        if elastic_endpoint is not None:
            from paddle_tpu.distributed.launch.elastic import \
                ElasticClient

            self._elastic = ElasticClient(elastic_endpoint,
                                          token=elastic_token)
        self._replicas = OrderedDict()     # rid -> _Replica, id order
        self._next_rid = 0
        self._requests = {}                # rid -> routing record
        self._pending_handoffs = []        # exported, awaiting a lane
        self._handoff_seq = 0
        self._done = {}
        self._auto_id = 0
        # probabilistic serving: None seeds resolve HERE, before a
        # disaggregated handoff splits the request across replicas —
        # the prefill replica's first-token draw and the decode
        # replica's adopted key state must come from the SAME seed
        self._seed_counter = 0
        self._draining = False
        self.metrics = registry if registry is not None \
            else MetricsRegistry()
        self._init_metrics()
        decode_role = "decode" if self.disaggregated else "mixed"
        for _ in range(num_replicas):
            self.add_replica(role=decode_role)
        for _ in range(num_prefill_replicas):
            self.add_replica(role="prefill")
        # the affinity hysteresis: a warm replica keeps winning routes
        # until its backlog exceeds the least-loaded replica's by more
        # than this many requests. Default one full batch — deep
        # enough that a popular prefix stays where its blocks are,
        # shallow enough that a flood spills to idle replicas.
        if affinity_slack is None:
            affinity_slack = self._any_engine().num_slots
        self.affinity_slack = int(affinity_slack)
        # request-scoped tracing follows the replicas' knob (replicas
        # are homogeneous): the router keeps its OWN span ring so
        # routing/handoff decisions land on a separate Perfetto track
        # from any engine's spans, all on the shared monotonic clock
        self.tracing = bool(self._any_engine().tracing)
        self.tracer = TraceRecorder(process_name="fleet.router") \
            if self.tracing else None

    # -- replica management ------------------------------------------------
    def _any_engine(self):
        rep = next(iter(self._replicas.values()))
        return rep.engine

    def _build_engine(self):
        return GenerationEngine(self.model, **self._engine_options)

    def add_replica(self, role=None):
        """Bring one replica into the fleet: build its engine, compile
        nothing new beyond its own steps (first use warms them),
        register it on the elastic registry (permanent lease — the
        launcher-owned-member class; the registry rejects the call
        without the job token). Returns the replica id."""
        if self._draining:
            raise RuntimeError("fleet is draining — no new replicas")
        if role is None:
            role = "decode" if self.disaggregated else "mixed"
        if role not in REPLICA_ROLES:
            raise ValueError(
                f"role must be one of {REPLICA_ROLES}, got {role!r}")
        if self.disaggregated and role == "mixed":
            raise ValueError(
                "a disaggregated fleet has prefill and decode "
                "replicas — 'mixed' would let long-prompt prefill "
                "steal decode-step FLOPs again")
        if not self.disaggregated and role != "mixed":
            raise ValueError(
                f"role {role!r} needs a disaggregated fleet "
                "(num_prefill_replicas > 0)")
        rid = self._next_rid
        self._next_rid += 1
        rep = _Replica(rid, self._build_engine(), role)
        self._replicas[rid] = rep
        if self._elastic is not None:
            self._elastic.register(
                f"{_ELASTIC_PREFIX}{rid}",
                info={"role": role,
                      "num_slots": rep.engine.num_slots,
                      "mp_degree": rep.engine.mp_degree},
                ttl=None)
        self._update_replica_gauges()
        return rid

    def remove_replica(self, rid):
        """Graceful elastic leave: retire the replica from routing,
        drive the fleet until its in-flight work (and any handoffs it
        sourced) finished, drain it (admissions closed + pool
        leak-check), drop its elastic membership. Finished results
        stay collectable via run()/pop of the remaining fleet."""
        rep = self._replicas.get(rid)
        if rep is None:
            raise KeyError(f"no replica {rid}")
        peers = [r for r in self._routable(rep.role) if r.rid != rid]
        if not peers:
            raise ValueError(
                f"replica {rid} is the last {rep.role!r}-capable "
                "replica — removing it would strand the queue (drain "
                "the fleet instead)")
        rep.retired = True
        while rep.engine.num_pending or rep.engine.num_active \
                or rep.engine._handoffs:
            if self.step() == 0:
                raise RuntimeError(
                    f"cannot drain replica {rid}: its lanes are "
                    "stalled and no fleet progress is possible")
        rep.engine.drain()                 # instant: audits the pool
        if self._elastic is not None:
            self._elastic.leave(f"{_ELASTIC_PREFIX}{rid}")
        del self._replicas[rid]
        self._update_replica_gauges()

    def _routable(self, role):
        """Replicas a request of `role`'s kind could route to (live,
        not retiring), in stable id order."""
        return [r for r in self._replicas.values()
                if r.role == role and not r.retired]

    @property
    def num_replicas(self):
        return len(self._replicas)

    # -- metrics -----------------------------------------------------------
    def _init_metrics(self):
        m = self.metrics
        self._m_replicas = m.gauge(
            "fleet_replicas",
            "Live serving replicas, by role.", labelnames=("role",))
        self._m_routed = m.counter(
            "fleet_routed_total",
            "Requests routed, by replica id and why it won (affinity "
            "= deepest warm prefix chain within the hysteresis band; "
            "least_loaded = cold or affinity yielded to load).",
            labelnames=("replica", "reason"))
        self._m_affinity_tokens = m.counter(
            "fleet_affinity_hit_tokens_total",
            "Prompt tokens the router placed onto a replica already "
            "owning their warm prefix blocks (the tokens the affinity "
            "decision saved from recomputation).")
        self._m_shed = m.counter(
            "fleet_shed_total",
            "Requests shed at fleet admission (max_queue exceeded), "
            "by priority class.", labelnames=("priority",))
        self._m_handoffs = m.counter(
            "fleet_handoffs_total",
            "Prefill->decode handoffs completed (prompt KV exported "
            "from a prefill replica and adopted by a decode "
            "replica).")
        self._m_handoff_blocks = m.counter(
            "fleet_handoff_blocks_total",
            "KV pool blocks moved across replicas by the "
            "disaggregated handoff path.")
        self._m_handoff_stalls = m.counter(
            "fleet_handoff_stalls_total",
            "Iterations a finished prefill sat exported-but-unplaced "
            "for want of a decode lane or pool blocks.")
        self._m_pending_handoffs = m.gauge(
            "fleet_pending_handoffs",
            "Finished prefills currently awaiting a decode replica.")
        self._m_handoff_wait = m.histogram(
            "fleet_handoff_wait_seconds",
            "Prefill-finish to decode-adoption latency (the "
            "disaggregation seam's contribution to TBT).",
            buckets=LATENCY_BUCKETS)

    def _update_replica_gauges(self):
        counts = {role: 0 for role in REPLICA_ROLES}
        for rep in self._replicas.values():
            counts[rep.role] += 1
        for role in REPLICA_ROLES:
            self._m_replicas.labels(role=role).set(counts[role])

    def reset_metrics(self):
        """Zero the fleet registry and every replica registry in
        place (bench warmup / per-window scrapes — same semantics as
        `MetricsRegistry.reset`)."""
        self.metrics.reset()
        for rep in self._replicas.values():
            rep.engine.metrics.reset()

    def metrics_snapshot(self):
        """Fleet-level snapshot: the router's own series plus every
        replica engine's registry, each stamped `replica=<id>` and
        folded through the exact-merge machinery (`merge_snapshots`) —
        counters/buckets sum exactly, the replica label keeps
        per-replica series side-by-side. Host-side, no collectives:
        replicas live in this process; multi-HOST fleets fold these
        merged snapshots again through observability.aggregate()."""
        snaps = [self.metrics.snapshot()]
        for rid in sorted(self._replicas):
            snaps.append(label_snapshot(
                self._replicas[rid].engine.metrics.snapshot(),
                replica=str(rid)))
        return merge_snapshots(snaps)

    def export_trace(self, path, include_profiler=True):
        """One Perfetto timeline for the whole fleet: the router's
        routing/handoff spans plus every replica engine's span ring,
        one track group each (replicas share this process's monotonic
        clock, so a disaggregated request's prefill, handoff, and
        decode spans line up — follow its `trace_id` across tracks).
        Returns the event count written."""
        if self.tracer is None:
            raise RuntimeError(
                "tracing is off — build the fleet with tracing=True "
                "to record spans")
        groups = [("fleet.router", self.tracer.snapshot())]
        for rid in sorted(self._replicas):
            rep = self._replicas[rid]
            if rep.engine.tracer is not None:
                groups.append((f"replica {rid} ({rep.role})",
                               rep.engine.tracer.snapshot()))
        if include_profiler:
            ev = profiler_host_events()
            if ev:
                groups.append(("profiler", ev))
        return export_timeline(path, groups)

    # -- routing -----------------------------------------------------------
    def _route(self, prompt, adapter_id=0):
        """Pick the intake replica: deepest warm `prefix_key` chain
        wins while its backlog stays within `affinity_slack` of the
        least-loaded intake replica; otherwise least-loaded (stable
        id tie-break). The chain is salted with `adapter_id` — router
        keys stay == cache keys, so a hot base prompt under two
        tenants routes (and caches) independently: each adapter's
        chain warms its own replica and can never claim affinity to
        KV another tenant's projections wrote. Returns
        (replica, reason, warm_tokens)."""
        intake = self._routable(
            "prefill" if self.disaggregated else "mixed")
        if not intake:
            raise RuntimeError("fleet has no intake replica")
        loads = {r.rid: r.load for r in intake}
        min_load = min(loads.values())
        best, best_hit, keys = None, 0, None
        for r in intake:
            if not r.engine.enable_prefix_cache:
                continue
            if keys is None:
                # hash the prompt ONCE; every replica peek reuses the
                # digests (replicas are homogeneous in block_size)
                keys = prefix_key(prompt, r.engine.block_size,
                                  adapter_id)
            hit = r.engine.cache.warm_prefix_tokens(prompt, keys=keys)
            if hit > best_hit:
                best, best_hit = r, hit
        if best is not None \
                and loads[best.rid] <= min_load + self.affinity_slack:
            return best, "affinity", best_hit
        cold = min(intake, key=lambda r: (loads[r.rid], r.rid))
        return cold, "least_loaded", 0

    def add_request(self, prompt, max_new_tokens, eos_token_id=None,
                    req_id=None, priority="standard", adapter_id=0,
                    sampling_params=None):
        """Admit one request into the fleet. Same contract as
        `GenerationEngine.add_request` (priority QoS, auto ids,
        validation, per-tenant `adapter_id` when the replicas carry an
        adapter registry), plus fleet admission control: with
        `max_queue` set and that many requests already queued
        fleet-wide, the incoming request is shed (result None — the
        HTTP-429 of this tier; per-replica `max_queue` still does
        priority-aware shedding inside each engine). Routing is
        prefix-affinity first (adapter-salted — a hot base prompt
        under two tenants warms two independent chains), least-loaded
        otherwise; in a disaggregated fleet the request lands on a
        prefill replica as `prefill_only` and the decode budget rides
        the handoff.

        `sampling_params` (needs replicas built with `sampling=True`)
        rides to the serving replica AND through the disaggregated
        handoff: a None seed is resolved by the FLEET's deterministic
        counter before routing, so the prefill replica's first-token
        draw and the decode replica's adopted key state share one
        seed — disaggregated sampled output is token-identical to
        colocated."""
        if self._draining:
            raise RuntimeError(
                "fleet is draining — admissions are closed")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if priority not in PRIORITY_CLASSES:
            raise ValueError(f"priority must be one of "
                             f"{PRIORITY_CLASSES}, got {priority!r}")
        # validate the adapter id BEFORE any router state mutates
        # (replicas are homogeneous — any engine's checker speaks for
        # all): an unknown id must reject cleanly, not leave a phantom
        # in-flight request that deadlocks every later run()
        adapter_id = self._any_engine()._check_adapter(adapter_id)
        # same pre-mutation discipline for sampling: validate against
        # any (homogeneous) replica, then pin a None seed fleet-side
        sampling_params = self._any_engine()._check_sampling(
            sampling_params)
        if sampling_params is not None and sampling_params.seed is None:
            sampling_params = sampling_params.with_seed(
                self._seed_counter)
            self._seed_counter += 1
        total = prompt.size + int(max_new_tokens)
        limit = self._any_engine().max_model_len
        if total > limit:
            raise ValueError(
                f"prompt({prompt.size}) + max_new({max_new_tokens}) ="
                f" {total} exceeds max_model_len={limit}")
        if req_id is None:
            while self._auto_id in self._requests \
                    or self._auto_id in self._done:
                self._auto_id += 1
            req_id = self._auto_id
            self._auto_id += 1
        elif req_id in self._requests or req_id in self._done:
            raise ValueError(f"req_id {req_id!r} is already in flight "
                             "or awaiting collection")
        if self.max_queue is not None and self.max_queue <= sum(
                r.engine.num_pending
                for r in self._replicas.values()) \
                + len(self._pending_handoffs):
            self._m_shed.labels(priority=priority).inc()
            self._done[req_id] = None
            return req_id
        trace_id = new_trace_id() if self.tracing else None
        t_route = now_us()
        rep, reason, warm = self._route(prompt, adapter_id)
        if self.tracer is not None:
            self.tracer.add_span(
                "fleet.route", t_route, now_us(), trace_id=trace_id,
                cat="router",
                args={"req_id": str(req_id), "replica": rep.rid,
                      "reason": reason, "affinity_tokens": warm})
        self._m_routed.labels(replica=str(rep.rid),
                              reason=reason).inc()
        if warm:
            self._m_affinity_tokens.inc(warm)
        # resolve the EFFECTIVE eos (engine default fallback) so the
        # handoff path's already-finished short-circuit agrees with
        # what the prefill replica will actually treat as EOS
        if eos_token_id is None:
            eos_token_id = rep.engine.eos_token_id
        info = {"prompt": prompt, "max_new": int(max_new_tokens),
                "eos": eos_token_id, "priority": priority,
                "arrived": time.perf_counter(), "replica": rep.rid,
                "adapter_id": int(adapter_id),
                "sampling": sampling_params,
                "trace_id": trace_id,
                "phase": "prefill" if self.disaggregated else "serve"}
        self._requests[req_id] = info
        if self.disaggregated:
            rep.engine.add_request(prompt, 1,
                                   eos_token_id=eos_token_id,
                                   req_id=req_id, priority=priority,
                                   prefill_only=True,
                                   adapter_id=adapter_id,
                                   sampling_params=sampling_params,
                                   trace_id=trace_id)
        else:
            rep.engine.add_request(prompt, max_new_tokens,
                                   eos_token_id=eos_token_id,
                                   req_id=req_id, priority=priority,
                                   adapter_id=adapter_id,
                                   sampling_params=sampling_params,
                                   trace_id=trace_id)
        return req_id

    def best_of_n(self, prompt, n, max_new_tokens,
                  sampling_params=None, eos_token_id=None,
                  priority="standard", adapter_id=0):
        """Fleet edition of `GenerationEngine.best_of_n`: candidate 0
        is served to completion first (its prefill warms ONE replica's
        prefix chain), then candidates 1..n-1 — same prompt, seeds
        `base+1..base+n-1` — route by prefix affinity to that warm
        replica and seat the prompt's blocks read-only (seated once
        fleet-wide, not n times). Drives `run()`; other in-flight work
        is served along the way and stays collectable. Returns the n
        candidate token lists in seed order."""
        from paddle_tpu.inference.engine import (_best_of_n_fanout,
                                                 _best_of_n_intake)

        params, base, self._seed_counter = _best_of_n_intake(
            self._any_engine(), sampling_params, n,
            self._seed_counter)
        out, stash = _best_of_n_fanout(
            lambda p: self.add_request(
                prompt, max_new_tokens, eos_token_id=eos_token_id,
                priority=priority, adapter_id=adapter_id,
                sampling_params=p),
            self.run, params, n, base)
        self._done.update(stash)       # bystander finishes collectable
        return out

    # -- disaggregated handoff ---------------------------------------------
    def _export_handoff(self, rep, req_id, toks):
        """A prefill replica finished `req_id`: claim its parked
        blocks, gather every block's rows (plus int8 scale rows) out
        of the source pool with the compiled export step, release the
        source blocks (prefix-cached ones stay warm for the router),
        and queue the payload for a decode lane. An EOS'd or
        single-token request is already complete — no decode leg."""
        info = self._requests[req_id]
        eng = rep.engine
        blocks, _hit = eng.take_handoff(req_id)
        first = int(toks[-1])
        done_eos = info["eos"] is not None and first == info["eos"]
        if done_eos or info["max_new"] <= 1:
            # already complete (EOS'd / single-token budget): no
            # decode leg, so exporting the KV would be pure waste
            eng.release_handoff(blocks)
            self._finalize(req_id, toks)
            return
        c = eng.cache
        t_exp = now_us()
        payload = []
        for b in blocks:
            if c.scales is not None:
                payload.append(rep._export(c.kpool, c.vpool,
                                           jnp.int32(b), c.scales))
            else:
                payload.append(rep._export(c.kpool, c.vpool,
                                           jnp.int32(b)))
        eng.release_handoff(blocks)
        if self.tracer is not None:
            self.tracer.add_span(
                "handoff.export", t_exp, now_us(),
                trace_id=info.get("trace_id"), cat="handoff",
                args={"req_id": str(req_id), "from_replica": rep.rid,
                      "blocks": len(blocks)})
        info["phase"] = "handoff"
        self._pending_handoffs.append(
            {"req_id": req_id, "payload": payload, "first": first,
             "seq": self._handoff_seq,
             "parked_at": time.perf_counter()})
        self._handoff_seq += 1
        self._m_pending_handoffs.set(len(self._pending_handoffs))

    def _place_handoff(self, h):
        """Try to land one exported prefill on a decode replica:
        least-loaded replica with a free lane, destination blocks
        allocated from ITS pool, each payload block ingested through
        the compiled scatter (donated pools), then the lane adopted
        mid-stream. False = no lane/blocks this iteration (the
        handoff stays queued; the stall is counted by the caller)."""
        info = self._requests[h["req_id"]]
        targets = sorted((r for r in self._routable("decode")
                          if r.engine.free_lanes > 0
                          and r.engine.adapter_page_available(
                              info.get("adapter_id", 0))),
                         key=lambda r: (r.load, r.rid))
        need = len(h["payload"])
        rep = blocks = None
        for cand in targets:
            # fall through on pool pressure: a busier replica with
            # free blocks beats stalling the handoff (and every lower
            # priority class behind it) on the least-loaded one
            blocks = cand.engine.cache.allocate(need)
            if blocks is not None:
                rep = cand
                break
        if rep is None:
            return False
        eng = rep.engine
        c = eng.cache
        t_ing = now_us()
        for parts, dst in zip(h["payload"], blocks):
            if c.scales is not None:
                kb, vb, srow = parts
                c.kpool, c.vpool, c.scales = rep._ingest(
                    c.kpool, c.vpool, kb, vb, jnp.int32(dst),
                    c.scales, srow)
            else:
                kb, vb = parts
                c.kpool, c.vpool = rep._ingest(
                    c.kpool, c.vpool, kb, vb, jnp.int32(dst))
        req_id = h["req_id"]
        eng.adopt_request(info["prompt"], h["first"], blocks,
                          info["max_new"],
                          eos_token_id=info["eos"], req_id=req_id,
                          priority=info["priority"],
                          arrived_at=info["arrived"],
                          adapter_id=info.get("adapter_id", 0),
                          sampling_params=info.get("sampling"),
                          trace_id=info.get("trace_id"))
        if self.tracer is not None:
            self.tracer.add_span(
                "handoff.ingest", t_ing, now_us(),
                trace_id=info.get("trace_id"), cat="handoff",
                args={"req_id": str(req_id), "to_replica": rep.rid,
                      "blocks": need})
        info["phase"] = "decode"
        info["replica"] = rep.rid
        self._m_handoffs.inc()
        self._m_handoff_blocks.inc(need)
        self._m_handoff_wait.observe(
            time.perf_counter() - h["parked_at"])
        return True

    def _flush_handoffs(self):
        """Place as many queued handoffs as decode capacity allows,
        best priority class first (FIFO within a class — the same
        strict ordering the engine's own admission uses)."""
        if not self._pending_handoffs:
            return 0
        self._pending_handoffs.sort(key=lambda h: (
            PRIORITY_CLASSES.index(
                self._requests[h["req_id"]]["priority"]), h["seq"]))
        placed, remaining = 0, []
        blocked = set()
        for h in self._pending_handoffs:
            cls = self._requests[h["req_id"]]["priority"]
            # strict priority: a blocked class also blocks everything
            # below it (otherwise a small batch job could leapfrog a
            # stalled interactive handoff into the last free lane)
            if cls in blocked or any(
                    PRIORITY_CLASSES.index(b) <
                    PRIORITY_CLASSES.index(cls) for b in blocked):
                remaining.append(h)
                continue
            if self._place_handoff(h):
                placed += 1
            else:
                self._m_handoff_stalls.inc()
                blocked.add(cls)
                remaining.append(h)
        self._pending_handoffs = remaining
        self._m_pending_handoffs.set(len(self._pending_handoffs))
        return placed

    # -- drive -------------------------------------------------------------
    def _finalize(self, req_id, toks):
        self._done[req_id] = toks
        self._requests.pop(req_id, None)

    def _collect(self, rep, results):
        for req_id in sorted(results, key=str):
            toks = results[req_id]
            info = self._requests.get(req_id)
            if info is None or toks is None:
                # shed by the replica's own max_queue (or unknown):
                # final answer, no decode leg
                self._finalize(req_id, toks)
                continue
            if info["phase"] == "prefill":
                self._export_handoff(rep, req_id, toks)
            else:
                self._finalize(req_id, toks)

    def step(self):
        """One fleet iteration: place queued handoffs, then one
        scheduler iteration on every replica with work, collecting
        finishes as they land. Returns the number of placements /
        engine progress units / finishes — 0 means the fleet cannot
        currently move.

        With async-core replicas the handoff work is the latency
        hiding ROADMAP item 3 promised: each `eng.step()` returns with
        a dispatch-ahead decode step still IN FLIGHT, so the second
        placement pass below (and the leading pass of the NEXT
        iteration) runs its compiled export/ingest scatters and
        adoption bookkeeping while every replica's device is busy —
        not against an idle device as the serial fleet did."""
        progressed = self._flush_handoffs()
        for rid in list(self._replicas):
            rep = self._replicas[rid]
            eng = rep.engine
            if eng.num_pending or eng.num_active:
                progressed += eng.step()
            results = eng.pop_results()
            if results:
                progressed += len(results)
                self._collect(rep, results)
        if self._pending_handoffs:
            # lanes vacated by the steps above can seat exported
            # prefills NOW instead of next iteration (one full fleet
            # sweep earlier) — overlapped with the in-flight steps
            # when replicas run the async core
            progressed += self._flush_handoffs()
            # still-queued handoffs: warm the adapter page their
            # adoption will need on the likeliest target replica while
            # the devices crunch
            self._prestage_handoffs()
        return progressed

    def _prestage_handoffs(self):
        """Adapter prefetch for queued handoffs (async latency
        hiding): for each pending handoff whose tenant carries an
        adapter, warm that adapter's page on the least-loaded decode
        replica that could take the placement — the compiled swap-in
        copy overlaps the replicas' in-flight steps, and the eventual
        `_place_handoff` adoption acquires a RESIDENT page instead of
        paying the transfer in the placement path. Best-effort only:
        no references taken, no placement decisions made here."""
        staged = set()
        for h in self._pending_handoffs:
            info = self._requests.get(h["req_id"])
            if info is None:
                continue
            aid = int(info.get("adapter_id", 0) or 0)
            if not aid or aid in staged:
                continue
            targets = sorted(self._routable("decode"),
                             key=lambda r: (r.load, r.rid))
            for rep in targets:
                pool = rep.engine.adapter_pool
                if pool is None \
                        or not pool.registry.has(aid):
                    continue
                if pool.page_of(aid) is not None \
                        or pool.prefetch(aid) is not None:
                    staged.add(aid)
                    rep.engine.flight.record(
                        "adapter_prefetch", h["req_id"], adapter=aid,
                        page=pool.page_of(aid))
                    break

    @property
    def num_outstanding(self):
        """Requests admitted but not yet finished (any phase)."""
        return len(self._requests)

    def run(self):
        """Drive until every admitted request finished; returns (and
        drains) {req_id: prompt + generated tokens; None for a shed
        request} — the engine `run()` contract, fleet-wide."""
        while self._requests:
            if self.step() == 0:
                pend = len(self._pending_handoffs)
                frees = {r.rid: r.engine.cache.num_free
                         for r in self._replicas.values()}
                raise RuntimeError(
                    "serving fleet deadlocked: "
                    f"{len(self._requests)} request(s) in flight, "
                    f"{pend} handoff(s) unplaceable, free blocks per "
                    f"replica {frees} — grow num_blocks/num_slots or "
                    "add replicas")
        out, self._done = self._done, {}
        return out

    def drain(self):
        """Fleet-wide graceful shutdown: close admissions, finish
        every in-flight request (handoffs included), then drain each
        replica (its own admission close + pool leak-check) and drop
        every elastic membership. Returns the final results."""
        self._draining = True
        out = self.run()
        for rid in list(self._replicas):
            rep = self._replicas.pop(rid)
            rep.retired = True
            rep.engine.drain()
            if self._elastic is not None:
                self._elastic.leave(f"{_ELASTIC_PREFIX}{rid}")
        self._update_replica_gauges()      # fleet_replicas -> 0
        return out
