"""Every stall has an owner inside the program (ISSUE 37).

- HOST PAUSES: once a `GenerationEngine` or a `TrainStep` is built, every
  garbage collection runs inside a `host.gc` span (the profiler's clock,
  so `idle_gaps` files a collection's gap under it) and is counted by
  generation; every JAX compile stage is counted, and the function it
  compiled kept in a bounded ring.
- STALLS: a step far over the median of the steps before it is counted
  under its owner — `gc`, `compile`, `device` or the host phase with the
  most exclusive seconds — and leaves a `stall` flight event.
- LAUNCHES: a compiled step's launch that finds the step before it done
  found the chip idle; counted per program, never more than launched.
"""
import gc
import time
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import GenerationEngine
from paddle_tpu.observability import get_registry
from paddle_tpu.observability.metrics import series_total
from paddle_tpu.observability.tracing import (HOST_GC_SPAN,
                                              STALL_MIN_EXCESS_S,
                                              STALL_RATIO, STALL_WINDOW,
                                              StallDetector,
                                              install_host_pause_hooks,
                                              is_stall, stall_owner)
from paddle_tpu.profiler import Profiler
from paddle_tpu.profiler.profiler import _recorder

VOCAB = 64


def _engine(**kw):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig.tiny(vocab=VOCAB, hidden=32,
                                          layers=2, heads=2, seq=128))
    model.eval()
    return GenerationEngine(model, num_slots=2, block_size=8, **kw)


def _gen_series(name):
    snap = get_registry().snapshot()
    return {s["labels"]["generation"]: s["value"]
            for s in snap[name]["series"]}


def test_installer_is_idempotent():
    pauses = install_host_pause_hooks()
    assert pauses.installed and install_host_pause_hooks() is pauses
    assert gc.callbacks.count(pauses._on_gc) == 1


def test_a_collection_under_a_profiler_leaves_a_host_gc_event():
    install_host_pause_hooks()
    n0, s0 = (_gen_series("host_gc_pauses_total"),
              _gen_series("host_gc_pause_seconds_total"))
    prof = Profiler()
    prof.start()
    gc.collect()                       # a full collection: generation 2
    gc.collect(0)
    names = [e["name"] for e in _recorder.peek()]
    prof.stop()
    assert names.count(HOST_GC_SPAN) >= 2
    n1, s1 = (_gen_series("host_gc_pauses_total"),
              _gen_series("host_gc_pause_seconds_total"))
    assert n1["2"] >= n0.get("2", 0) + 1 and n1["0"] >= n0.get("0", 0) + 1
    assert s1["2"] > s0.get("2", 0.0)
    peak = get_registry().snapshot()["host_gc_pause_max_seconds"]
    assert peak["series"][0]["value"] > 0


def test_a_fresh_jit_is_counted_and_named():
    pauses = install_host_pause_hooks()

    def host_pause_probe_fn(x):
        return x * 3 + 1

    before = series_total(get_registry().snapshot(), "host_compiles_total")
    t0 = pauses.compile_seconds
    jax.jit(host_pause_probe_fn)(jnp.ones(3)).block_until_ready()
    snap = get_registry().snapshot()
    assert series_total(snap, "host_compiles_total") >= before + 3
    stages = {s["labels"]["stage"] for s in
              snap["host_compiles_total"]["series"] if s["value"]}
    assert {"jaxpr_trace", "jaxpr_to_mlir", "backend_compile"} <= stages
    assert pauses.compile_seconds > t0
    names = {f for _, f, _, _ in pauses.compiles}
    assert any("host_pause_probe_fn" in str(f) for f in names)


@pytest.mark.parametrize("wall,median,expect", [
    (0.40, 0.01, True),
    (0.03, 0.005, False),              # 6x, but only 25 ms over
    (0.30, 0.10, False),               # 200 ms over, but only 3x
    (STALL_RATIO * 0.02, 0.02, True),  # at the bounds, both held
])
def test_the_stall_rule(wall, median, expect):
    assert is_stall(wall, median) is expect
    assert STALL_MIN_EXCESS_S == 0.05 and STALL_RATIO == 4.0


@pytest.mark.parametrize("gc_s,compile_s,phases,owner", [
    # a collection covers half of the 0.3 s excess
    (0.16, 0.0, {"dispatch": 0.25, "device_wait": 0.01}, "gc"),
    (0.0, 0.2, {"schedule": 0.25}, "compile"),
    (0.2, 0.28, {"dispatch": 0.3}, "compile"),      # the larger of two
    # pauses too short: the phase with the most exclusive seconds
    (0.1, 0.0, {"device_wait": 0.29, "finish": 0.01}, "device"),
    (0.0, 0.01, {"device_wait": 0.02, "dispatch": 0.28}, "dispatch"),
    (0.0, 0.0, {}, "other"),
])
def test_the_owner_rule(gc_s, compile_s, phases, owner):
    assert stall_owner(0.31, 0.01, phases, gc_s, compile_s) == owner


def test_the_detector_judges_a_full_window_only():
    det = StallDetector()
    for _ in range(STALL_WINDOW - 1):
        assert det.observe(0.01) is None
    assert det.observe(1.0) is None    # the window was not full yet
    for _ in range(STALL_WINDOW):
        det.observe(0.01)
    assert det.observe(1.0) == pytest.approx(0.01)
    assert det.observe(0.02) is None


def test_a_sleeping_phase_is_a_stall_owned_by_that_phase():
    eng = _engine(tracing=True)
    rng = np.random.RandomState(0)
    for i in range(2):
        eng.add_request(rng.randint(1, VOCAB, size=6).astype(np.int32),
                        110, req_id=i)
    orig, armed = eng._phase, {"steps": 0}

    @contextmanager
    def phase(name):
        with orig(name):
            if name == "finish" and armed["steps"] == 70:
                armed["steps"] += 1
                time.sleep(0.3)
            yield

    eng._phase = phase
    while eng.num_active or eng.num_pending:
        if armed["steps"] < 70:
            armed["steps"] += 1
        eng.step()
    assert armed["steps"] == 71        # the sleep happened
    snap = eng.metrics_snapshot()
    owners = {s["labels"]["owner"]: s["value"]
              for s in snap["engine_stalls_total"]["series"]}
    # at least one: a loaded host may stall on its own as well
    assert owners.get("finish", 0) >= 1
    lost = {s["labels"]["owner"]: s["value"]
            for s in snap["engine_stall_seconds_total"]["series"]}
    assert lost["finish"] >= 0.25
    stalls = [e for e in eng.dump_flight_recorder()
              if e["event"] == "stall" and e.get("owner") == "finish"]
    assert stalls and stalls[0]["phases"]["finish"] >= 0.3
    assert stalls[0]["wall_s"] >= 0.3 > stalls[0]["median_s"]
    # a thread that sleeps is off the CPU: its clock tells it apart
    assert stalls[0]["cpu_s"] < stalls[0]["wall_s"] - 0.2
    assert any(e["name"] == "stall" and e["ph"] == "i"
               for e in eng.tracer.snapshot())


@pytest.mark.parametrize("kw", [{}, {"async_core": False},
                                {"spec_decode_k": 2}])
def test_launches_that_found_the_chip_idle_are_counted(kw):
    eng = _engine(**kw)
    rng = np.random.RandomState(1)
    for i in range(3):
        eng.add_request(rng.randint(1, VOCAB, size=9).astype(np.int32), 6,
                        req_id=i)
    eng.run()
    snap = eng.metrics_snapshot()
    launched = {s["labels"]["program"]: s["value"]
                for s in snap["engine_launches_total"]["series"]}
    idle = {s["labels"]["program"]: s["value"]
            for s in snap["engine_launches_device_idle_total"]["series"]}
    decode = "engine_verify_step" if kw.get("spec_decode_k") \
        else "engine_decode_step"
    assert launched["engine_prefill_chunk"] >= 3 and launched[decode] > 0
    assert set(idle) == set(launched)
    for program, n in launched.items():
        assert 0 <= idle[program] <= n
    # the gauge no window could read is gone; its counter is there
    assert "engine_step_device_fraction" not in snap
    assert series_total(snap, "engine_step_seconds_total") > 0


def test_a_collection_inside_the_recorders_lock_does_not_deadlock():
    """A collection that starts while the host-event recorder holds its
    lock (it allocates under it) records its own `host.gc` span on the
    same thread: the lock is reentrant, so the step goes on."""
    import threading

    install_host_pause_hooks()
    done = threading.Event()

    def record_many():
        was = gc.get_threshold()
        gc.set_threshold(1)            # a collection at every allocation
        try:
            for i in range(500):
                _recorder.record("probe", i, i + 1, 0)
        finally:
            gc.set_threshold(*was)
        done.set()

    prev, _recorder.enabled = _recorder.enabled, True
    try:
        t = threading.Thread(target=record_many, daemon=True)
        t.start()
        t.join(60)
        assert done.is_set(), "the recorder deadlocked on its own lock"
        names = {e["name"] for e in _recorder.drain()}
        assert {"probe", HOST_GC_SPAN} <= names
    finally:
        _recorder.enabled = prev
