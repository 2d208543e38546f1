"""Request-scoped tracing + host-gap timeline (ISSUE 17).

The contracts, proven the way PRs 12/13/15 proved theirs:

- OFF IS FREE: a tracing-disabled engine carries no tracer, registers
  no trace series, and emits token streams identical to a
  tracing-ENABLED engine (tracing is host-side only — by construction
  it can never become a compiled-program argument), and
  `decode_traces == 1` holds per (backend, K) with tracing ON.
- PHASES PARTITION THE STEP: `PhaseTimer` is exclusive — nesting
  pauses the enclosing phase, so per-phase totals sum to (at most)
  wall time and the device fraction (device_wait's seconds over
  `engine_step_seconds_total`) is a real fraction. The
  `engine_step_host_gap_seconds{phase}` histogram is ALWAYS on (the
  ROADMAP item 3 measured baseline), tracing knob or not.
- RINGS ARE BOUNDED: TraceRecorder and FlightRecorder hold the newest
  `capacity` events, count their drops, and never grow; `drain()`'s
  leak audit arrives WITH the flight-recorder history.
- ONE TIMELINE: engine spans merge with the profiler's
  `_HostEventRecorder` stream (same monotonic clock); a disaggregated
  2-replica request exports a single Perfetto file whose routing,
  prefill, handoff, and decode spans share ONE trace id across
  per-process track groups.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import GenerationEngine, ServingFleet
from paddle_tpu.observability.metrics import (label_snapshot,
                                              merge_snapshots,
                                              series_total)
from paddle_tpu.observability.tracing import (STEP_PHASES,
                                              FlightRecorder,
                                              PhaseTimer,
                                              TraceRecorder,
                                              merge_trace_events,
                                              new_trace_id)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 64


def _model(seed=0):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(seed)
    cfg = GPTConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=2,
                         seq=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model()


def _trace(rng_seed=0, n=4):
    rng = np.random.RandomState(rng_seed)
    return [(rng.randint(0, VOCAB, rng.randint(4, 14))
             .astype(np.int32), int(rng.randint(3, 7)))
            for _ in range(n)]


def _serve(eng, reqs):
    ids = [eng.add_request(p, mn, req_id=i)
           for i, (p, mn) in enumerate(reqs)]
    out = eng.run()
    return [list(map(int, out[i])) for i in ids]


# ---------------------------------------------------------------------------
# tracing.py primitives
# ---------------------------------------------------------------------------

def test_phase_timer_exclusive_accounting():
    """Nested phases PAUSE the enclosing one: totals are disjoint and
    sum to (at most) the wall time of the outermost section."""
    pt = PhaseTimer()
    t0 = time.perf_counter()
    with pt.phase("outer"):
        time.sleep(0.01)
        with pt.phase("inner"):
            time.sleep(0.02)
        time.sleep(0.01)
    wall = time.perf_counter() - t0
    tot = pt.totals()
    assert set(tot) == {"outer", "inner"}
    assert tot["inner"] >= 0.02
    # exclusive: outer excludes inner's slice entirely
    assert tot["outer"] < wall - tot["inner"] + 0.005
    assert tot["outer"] + tot["inner"] <= wall + 0.005
    # reset returns and clears
    assert pt.reset() == tot
    assert pt.totals() == {}


def test_phase_timer_reentrant_same_name():
    pt = PhaseTimer()
    for _ in range(3):
        with pt.phase("a"):
            time.sleep(0.002)
    assert pt.totals()["a"] >= 0.006


def test_phase_timer_thread_confined_clocks():
    """ISSUE 18 regression: the async core's drafter helper runs its
    `draft_propose` phases on ANOTHER thread while the step thread
    sits in its own phase. Each thread owns its whole clock — stack
    AND accumulator — so an off-thread phase must neither pause the
    step thread's active phase nor leak seconds into its totals (the
    step thread's phase totals must keep partitioning ITS wall
    time)."""
    import threading

    pt = PhaseTimer()
    helper_done = threading.Event()
    helper_tot = {}

    def helper():
        with pt.phase("draft_propose"):
            time.sleep(0.03)
        helper_tot.update(pt.totals())
        helper_done.set()

    t0 = time.perf_counter()
    with pt.phase("dispatch"):
        th = threading.Thread(target=helper)
        th.start()
        helper_done.wait()
        th.join()
    wall = time.perf_counter() - t0
    # step thread: ONLY its own phase, covering its full wall — the
    # helper's concurrent phase neither paused nor shortened it
    tot = pt.totals()
    assert set(tot) == {"dispatch"}
    assert tot["dispatch"] >= 0.03
    assert tot["dispatch"] <= wall + 0.005
    # helper thread: its seconds landed on ITS clock only
    assert set(helper_tot) == {"draft_propose"}
    assert helper_tot["draft_propose"] >= 0.03
    # reset is per-thread too: clearing the step thread's clock is
    # what `_flush_step_phases` does between steps — the helper's
    # clock was never part of the step partition
    assert pt.reset() == tot
    assert pt.totals() == {}


def test_trace_recorder_ring_bound_and_drops():
    tr = TraceRecorder(capacity=4)
    for i in range(10):
        tr.add_span(f"s{i}", i, i + 1)
    snap = tr.snapshot()
    assert len(snap) == 4
    assert [e["name"] for e in snap] == ["s6", "s7", "s8", "s9"]
    assert tr.total_recorded == 10 and tr.dropped == 6
    # snapshot is non-destructive
    assert len(tr.snapshot()) == 4
    tr.clear()
    assert tr.snapshot() == [] and tr.dropped == 0
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)


def test_trace_recorder_span_ids_and_context():
    tr = TraceRecorder()
    tid = new_trace_id()
    parent = tr.add_span("root", 0, 5, trace_id=tid)
    child = tr.add_span("leaf", 1, 2, trace_id=tid, parent_id=parent)
    assert child != parent
    ev = tr.snapshot()[1]
    assert ev["args"]["trace_id"] == tid
    assert ev["args"]["parent_id"] == parent
    assert ev["ph"] == "X" and ev["dur"] == 1
    with tr.span("ctx", trace_id=tid):
        pass
    assert tr.snapshot()[-1]["name"] == "ctx"


def test_new_trace_ids_are_unique_and_pid_prefixed():
    ids = {new_trace_id() for _ in range(100)}
    assert len(ids) == 100
    assert all(i.startswith(f"{os.getpid():x}-") for i in ids)


def test_flight_recorder_bound_and_format():
    fl = FlightRecorder(capacity=3)
    for i in range(5):
        fl.record("ev", req_id=i, k=i * 10)
    rows = fl.dump()
    assert len(rows) == 3 and [r["req_id"] for r in rows] == [2, 3, 4]
    assert fl.total_recorded == 5
    txt = fl.format()
    assert "flight recorder (3 of 5 events" in txt
    assert "k=40" in txt and "req=4" in txt
    assert len(fl.format(limit=1).splitlines()) == 2


def test_merge_trace_events_repids_and_names():
    merged = merge_trace_events([
        ("alpha", [{"name": "a", "ph": "X", "ts": 0, "dur": 1,
                    "pid": 999, "tid": 0}]),
        ("beta", [{"name": "b", "ph": "X", "ts": 0, "dur": 1,
                   "pid": 999, "tid": 0}]),
    ])
    metas = [e for e in merged if e["ph"] == "M"]
    assert [(m["pid"], m["args"]["name"]) for m in metas] == \
        [(1, "alpha"), (2, "beta")]
    spans = [e for e in merged if e["ph"] == "X"]
    assert [(s["name"], s["pid"]) for s in spans] == \
        [("a", 1), ("b", 2)]


# ---------------------------------------------------------------------------
# tentpole: engine lifecycle + phases
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K", [0, 4])
def test_tracing_off_is_token_identical_and_traces_hold(model, K):
    """THE acceptance gate: tracing never changes tokens (host-side
    only, the sampling=False precedent) and `decode_traces == 1`
    holds with tracing ON — the spans ride outside the compiled
    programs."""
    reqs = _trace(3)

    def mk(on):
        return GenerationEngine(model, num_slots=2, block_size=8,
                                spec_decode_k=K, tracing=on)

    eng_off = mk(False)
    out_off = _serve(eng_off, reqs)
    eng_on = mk(True)
    out_on = _serve(eng_on, reqs)
    assert out_on == out_off
    assert eng_off.tracer is None and eng_on.tracer is not None
    assert eng_on.decode_traces == 1
    # conditional registration: the trace series exist only when on
    snap_on = eng_on.metrics_snapshot()
    snap_off = eng_off.metrics_snapshot()
    assert "engine_trace_spans_total" in snap_on
    assert "engine_trace_spans_total" not in snap_off
    assert "engine_trace_dropped_total" not in snap_off
    assert series_total(snap_on, "engine_trace_spans_total") \
        == eng_on.tracer.total_recorded


@pytest.mark.parametrize("K", [0, 4])
def test_host_gap_histogram_and_device_fraction(model, K):
    """The measured baseline for ROADMAP item 3: every step folds its
    phase clock into `engine_step_host_gap_seconds{phase}` — tracing
    knob OFF (the histogram is always on) — and the device fraction
    is a real fraction."""
    eng = GenerationEngine(model, num_slots=2, block_size=8,
                           spec_decode_k=K)
    assert eng.tracer is None
    _serve(eng, _trace(1))
    snap = eng.metrics_snapshot()
    hg = snap["engine_step_host_gap_seconds"]
    phases = {s["labels"]["phase"] for s in hg["series"]}
    assert phases <= set(STEP_PHASES)
    expect = {"schedule", "dispatch", "device_wait", "finish"}
    if K:
        expect |= {"draft_propose", "accept_walk"}
    assert expect <= phases
    for s in hg["series"]:
        assert s["count"] > 0 and s["sum"] >= 0
    # over the whole run, as over any window: device_wait's seconds
    # over every step's wall
    wall = series_total(snap, "engine_step_seconds_total")
    wait = sum(s["sum"] for s in hg["series"]
               if s["labels"]["phase"] == "device_wait")
    assert wall > 0 and 0.0 < wait / wall <= 1.0


def test_request_lifecycle_spans_share_one_trace_id(model):
    eng = GenerationEngine(model, num_slots=2, block_size=8,
                           tracing=True)
    reqs = _trace(5, n=3)
    _serve(eng, reqs)
    events = eng.tracer.snapshot()
    by_req = {}
    for e in events:
        a = e.get("args") or {}
        if "req_id" in a and "trace_id" in a:
            by_req.setdefault(a["req_id"], set()).add(a["trace_id"])
    assert set(by_req) == {"0", "1", "2"}
    # one trace id per request, all distinct
    assert all(len(tids) == 1 for tids in by_req.values())
    assert len({t for tids in by_req.values() for t in tids}) == 3
    names = {e["name"] for e in events}
    assert {"request.queued", "request.admitted",
            "request.first_token", "request.finish",
            "prefill.chunk", "decode.step"} <= names
    # phase spans ride a separate category
    assert any(e.get("cat") == "phase" for e in events)


def test_flight_recorder_lifecycle_and_shed(model):
    eng = GenerationEngine(model, num_slots=1, block_size=8,
                           max_queue=1)
    reqs = _trace(7, n=3)
    for i, (p, mn) in enumerate(reqs):
        eng.add_request(p, mn, req_id=i)
    eng.run()
    events = [e["event"] for e in eng.dump_flight_recorder()]
    assert "queued" in events and "admitted" in events
    assert "first_token" in events and "finish" in events
    assert "shed" in events      # max_queue=1 shed the overflow


def test_drain_leak_audit_attaches_flight_recorder(model):
    """The postmortem contract: a failed leak audit arrives WITH the
    recent request history, not as a bare assertion."""
    eng = GenerationEngine(model, num_slots=2, block_size=8)
    _serve(eng, _trace(2, n=2))
    eng.cache.allocate(1)              # drop a block on the floor
    with pytest.raises(RuntimeError) as ei:
        eng.drain()
    msg = str(ei.value)
    assert "leak check failed" in msg
    assert "flight recorder" in msg
    assert "finish" in msg             # the history rode along


def test_export_trace_merges_profiler_stream(model, monkeypatch,
                                             tmp_path):
    """One timeline: the engine's span ring and the profiler's
    RecordEvent stream land in one Chrome-trace file as separate
    re-pidded track groups (same monotonic clock, no offsets)."""
    from paddle_tpu.profiler.profiler import _recorder

    eng = GenerationEngine(model, num_slots=2, block_size=8,
                           tracing=True)
    monkeypatch.setattr(_recorder, "enabled", True)
    try:
        _serve(eng, _trace(4, n=2))
    finally:
        _recorder.enabled = False
    path = tmp_path / "timeline.json"
    n = eng.export_trace(str(path))
    _recorder.drain()                  # leave no residue for others
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert len(evs) == n
    tracks = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert tracks == {"engine", "profiler"}
    prof_pid = next(e["pid"] for e in evs if e["ph"] == "M"
                    and e["args"]["name"] == "profiler")
    prof_names = {e["name"] for e in evs
                  if e.get("pid") == prof_pid and e["ph"] == "X"}
    assert "engine.step" in prof_names
    # off engines refuse loudly instead of writing an empty file
    with pytest.raises(RuntimeError, match="tracing is off"):
        GenerationEngine(model, num_slots=2, block_size=8) \
            .export_trace(str(tmp_path / "nope.json"))


def test_trace_ring_bound_holds_under_load(model):
    eng = GenerationEngine(model, num_slots=2, block_size=8,
                           tracing=True, trace_capacity=16)
    _serve(eng, _trace(6, n=4))
    assert len(eng.tracer.snapshot()) == 16
    assert eng.tracer.dropped > 0
    snap = eng.metrics_snapshot()
    assert series_total(snap, "engine_trace_dropped_total") \
        == eng.tracer.dropped


# ---------------------------------------------------------------------------
# fleet: trace context across replicas
# ---------------------------------------------------------------------------

def test_disaggregated_handoff_exports_single_timeline(model, tmp_path):
    """THE cross-replica gate: a disaggregated request's routing,
    prefill, handoff export/ingest, and decode spans share ONE trace
    id across the router's and both replicas' track groups — one
    Perfetto file shows the request crossing engines."""
    fleet = ServingFleet(model, num_replicas=1,
                         num_prefill_replicas=1, num_slots=2,
                         block_size=8, tracing=True)
    rng = np.random.RandomState(0)
    rid = fleet.add_request(rng.randint(0, VOCAB, 10)
                            .astype(np.int32), 8)
    out = fleet.run()
    assert len(out[rid]) == 18
    path = tmp_path / "fleet.json"
    fleet.export_trace(str(path))
    evs = json.loads(path.read_text())["traceEvents"]
    tracks = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert {"fleet.router", "replica 0 (decode)",
            "replica 1 (prefill)"} <= tracks
    tids = {e["args"]["trace_id"] for e in evs
            if e.get("args") and e["args"].get("trace_id")}
    assert len(tids) == 1              # one request -> one trace id
    tid = next(iter(tids))
    handoff = {e["name"] for e in evs if e.get("cat") == "handoff"}
    assert handoff == {"handoff.export", "handoff.ingest"}
    assert all(e["args"]["trace_id"] == tid for e in evs
               if e.get("cat") == "handoff")
    route = next(e for e in evs if e["name"] == "fleet.route")
    assert route["args"]["reason"] in ("affinity", "least_loaded")
    assert "replica" in route["args"]
    # the id crosses >= 3 track groups: router, prefill, decode
    pids = {e["pid"] for e in evs
            if e.get("args") and e["args"].get("trace_id") == tid}
    assert len(pids) >= 3


def test_fleet_route_spans_annotate_affinity(model):
    fleet = ServingFleet(model, num_replicas=2, num_slots=2,
                         block_size=8, tracing=True)
    rng = np.random.RandomState(1)
    hot = rng.randint(0, VOCAB, 16).astype(np.int32)
    fleet.add_request(hot, 4)
    fleet.run()
    fleet.add_request(hot.copy(), 4)   # warm chain -> affinity win
    fleet.run()
    routes = [e for e in fleet.tracer.snapshot()
              if e["name"] == "fleet.route"]
    assert len(routes) == 2
    assert routes[1]["args"]["reason"] == "affinity"
    assert routes[1]["args"]["affinity_tokens"] > 0


def test_fleet_folds_host_gap_and_trace_series(model):
    """PR 12's fold contract re-proven with the NEW series present:
    replica-labeled `engine_step_host_gap_seconds{phase}` buckets sum
    exactly across a 2-replica fleet, trace counters fold, and an
    unlabeled collision still raises."""
    fleet = ServingFleet(model, num_replicas=2, num_slots=2,
                         block_size=8, tracing=True)
    reqs = _trace(9, n=4)
    for i, (p, mn) in enumerate(reqs):
        fleet.add_request(p, mn, req_id=i)
    fleet.run()
    snaps = [rep.engine.metrics.snapshot()
             for rep in fleet._replicas.values()]
    merged = fleet.metrics_snapshot()
    hg = merged["engine_step_host_gap_seconds"]
    assert "replica" in hg["labelnames"]
    # exact fold: each replica's per-phase buckets appear verbatim
    for rid, snap in zip(fleet._replicas, snaps):
        for s in snap["engine_step_host_gap_seconds"]["series"]:
            match = [m for m in hg["series"]
                     if m["labels"] == {**s["labels"],
                                        "replica": str(rid)}]
            assert len(match) == 1
            assert match[0]["counts"] == s["counts"]
            assert match[0]["sum"] == s["sum"]
            assert match[0]["count"] == s["count"]
    # trace counters fold too, and total equals the per-replica sum
    assert series_total(merged, "engine_trace_spans_total") == sum(
        series_total(s, "engine_trace_spans_total") for s in snaps)
    # the collision contract survives the new series: re-stamping an
    # already replica-labeled snapshot raises instead of shadowing
    with pytest.raises(ValueError):
        label_snapshot(label_snapshot(snaps[0], replica="0"),
                       replica="1")
    # and merging UNLABELED replica snapshots silently sums identical
    # series — the exact-merge semantics the replica stamp exists for
    folded = merge_snapshots(snaps)
    assert series_total(folded, "engine_trace_spans_total") == \
        series_total(merged, "engine_trace_spans_total")


# ---------------------------------------------------------------------------
# satellites: profiler export collision, import smoke, bench row
# ---------------------------------------------------------------------------

def test_export_chrome_tracing_same_second_no_collision(monkeypatch,
                                                        tmp_path):
    """Regression (ISSUE 17 satellite): two exports within one
    wall-clock second used to silently overwrite — the monotonic
    sequence suffix keeps them distinct files."""
    from paddle_tpu.profiler import profiler as prof_mod

    monkeypatch.setattr(prof_mod.time, "time", lambda: 1234567890.5)
    handler = prof_mod.export_chrome_tracing(str(tmp_path),
                                             worker_name="w")
    p1 = prof_mod.Profiler(timer_only=True)
    p2 = prof_mod.Profiler(timer_only=True)
    handler(p1)
    handler(p2)
    assert p1._export_path != p2._export_path
    assert os.path.exists(p1._export_path)
    assert os.path.exists(p2._export_path)
    for p in (p1, p2):
        assert "traceEvents" in json.loads(
            open(p._export_path).read())


def test_tracing_import_has_no_backend_init():
    """Importing observability.tracing must never initialize a JAX
    backend (the paged-attention/conv smoke precedent): the fleet
    router and serving hosts import it at module import."""
    code = (
        "import paddle_tpu.observability.tracing as t\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, 'backend initialized'\n"
        "assert len(t.STEP_PHASES) == 12\n"
        "r = t.TraceRecorder(capacity=2)\n"
        "r.add_span('x', 0, 1)\n"
        "assert r.snapshot()[0]['name'] == 'x'\n"
        "print('SMOKE_OK')\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "SMOKE_OK" in res.stdout


def test_suite_rows_carry_host_gap_row():
    import bench_ops

    assert "gpt_engine_host_gap" in bench_ops.SUITE_ROWS


@pytest.mark.slow
def test_host_gap_bench_runner_tiny():
    """The `gpt_engine_host_gap` runner end-to-end on a tiny config:
    phases report for K in {0,4}, cold and warm, device fraction is a
    fraction, and the record carries the adoption-gate "ms" key."""
    from paddle_tpu.models import GPTConfig

    import bench_ops

    cfg = GPTConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=2,
                         seq=64)
    rec = bench_ops._engine_host_gap_case(
        model_cfg=cfg, num_requests=3, num_slots=2, block_size=8,
        max_new=6)()
    assert "ms" in rec and rec["ms"] > 0
    for k in ("k0", "k4"):
        for window in ("cold", "warm"):
            phases = rec[k][f"phase_ms_per_step_{window}"]
            assert "dispatch" in phases and "device_wait" in phases
            frac = rec[k][f"device_fraction_{window}"]
            assert 0.0 <= frac <= 1.0
        assert rec[k]["spans"] > 0
    assert "draft_propose" in rec["k4"]["phase_ms_per_step_warm"]
