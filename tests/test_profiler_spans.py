"""One span stream on the profiler's clock (ISSUE 26).

`RecordEvent` is the one bridge: every span it opens is also a
`jax.profiler.TraceAnnotation`, so the engine's step phases
(`engine.<phase>`) and TrainStep's host stages (`trainstep.*`) land on
the host line of whatever `jax.profiler` trace is running, beside the
device lines. Names are constants, the same in every sink. The Pallas
kernels carry stable names too.
"""
import glob
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.inference import GenerationEngine
from paddle_tpu.observability.tracing import STEP_PHASES
from paddle_tpu.profiler import Profiler, RecordEvent
from paddle_tpu.profiler.profiler import _recorder

ENGINE_SPANS = ["engine.step", "engine.schedule", "engine.dispatch",
                "engine.device_wait", "engine.finish"]
TRAIN_SPANS = ["trainstep.step", "trainstep.gather", "trainstep.dispatch",
               "trainstep.scatter"]
OUTER = "test.outer"


def _engine(**kw):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig.tiny(vocab=64, hidden=32, layers=2,
                                          heads=2, seq=64))
    model.eval()
    eng = GenerationEngine(model, num_slots=2, block_size=8, **kw)
    rng = np.random.RandomState(0)
    for i in range(2):
        eng.add_request(rng.randint(1, 64, size=6).astype(np.int32), 4,
                        req_id=i)
    return eng


def _train_step(accumulate_steps=1):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    step = paddle.jit.TrainStep(net, opt, lambda o, y: F.mse_loss(o, y),
                                accumulate_steps=accumulate_steps)
    return step, paddle.randn([4, 8]), paddle.randn([4, 1])


# -- with no profiler of either kind ------------------------------------------
def test_record_event_runs_clean_with_no_profiler_of_either_kind():
    assert not _recorder.enabled
    before = len(_recorder.events)
    with RecordEvent("outer"):
        ev = RecordEvent("inner")
        ev.begin()
        ev.end()
        ev.end()                       # a second end is a no-op
    assert len(_recorder.events) == before


def test_a_process_without_jax_does_not_import_it_for_a_span(monkeypatch):
    """A dataloader worker that never imported jax: the span still goes
    to the host-event recorder and jax stays unimported."""
    monkeypatch.delitem(sys.modules, "jax.profiler")
    monkeypatch.setattr(_recorder, "enabled", True)
    monkeypatch.setattr(_recorder, "events", [])
    with RecordEvent("worker.batch"):
        pass
    assert "jax.profiler" not in sys.modules
    assert [e["name"] for e in _recorder.events] == ["worker.batch"]


def test_engine_phases_and_trainstep_run_clean_with_no_profiler():
    eng = _engine()
    for name in STEP_PHASES:
        with eng._phase(name):
            pass
    assert set(eng._phases.reset()) == set(STEP_PHASES)
    assert len(eng.run()) == 2
    step, x, y = _train_step()
    assert np.isfinite(float(step(x, y)))


# -- under jax.profiler on the CPU backend ------------------------------------
def _host_lines(trace_dir):
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert paths, f"no .xplane.pb under {trace_dir}"
    data = ProfileData.from_file(paths[-1])
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                yield [(e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events]


@pytest.fixture(scope="module")
def traced_spans(tmp_path_factory):
    """Two steps of a tiny engine, two of a tiny TrainStep and two
    micro-batches of an accumulating one, inside a span the test opens
    itself, under `jax.profiler.start_trace`; -> the events of the one
    host line that carries that span."""
    eng = _engine()
    step, x, y = _train_step()
    acc, ax, ay = _train_step(accumulate_steps=2)
    eng.step(), step(x, y), acc(ax, ay), acc(ax, ay)   # compile first
    out = str(tmp_path_factory.mktemp("xplane"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(OUTER):
            eng.step(), eng.step()
            step(x, y), step(x, y)
            acc(ax, ay), acc(ax, ay)
    finally:
        jax.profiler.stop_trace()
    lines = [ev for ev in _host_lines(out)
             if any(n == OUTER for n, _, _ in ev)]
    assert len(lines) == 1
    return lines[0]


@pytest.mark.parametrize("span", ENGINE_SPANS + TRAIN_SPANS)
def test_span_lands_on_the_profilers_host_line(traced_spans, span):
    (o_start, o_end), = [(s, s + d) for n, s, d in traced_spans
                         if n == OUTER]
    mine = [(s, s + d) for n, s, d in traced_spans if n == span]
    assert len(mine) >= 2, (span, sorted({n for n, _, _ in traced_spans}))
    assert all(o_start <= s and e <= o_end for s, e in mine)


def test_phases_nest_in_engine_step_and_stages_in_trainstep_step(
        traced_spans):
    def spans(name):
        return [(s, s + d) for n, s, d in traced_spans if n == name]

    def inside(inner, outer):
        return all(any(a <= s and e <= b for a, b in spans(outer))
                   for s, e in spans(inner))

    for name in ENGINE_SPANS[1:]:
        assert inside(name, "engine.step"), name
    for name in TRAIN_SPANS[1:]:
        assert inside(name, "trainstep.step"), name
    # the accumulating step has the same three stages: 2 plain steps +
    # 2 micro-batches + the update program of the second
    assert len(spans("trainstep.step")) == 4
    assert len(spans("trainstep.dispatch")) == 5
    # no name carries a request id, a step number or a lane count
    names = {n for n, _, _ in traced_spans
             if n.startswith(("engine.", "trainstep."))}
    assert names <= {"engine." + p for p in STEP_PHASES} | set(TRAIN_SPANS) \
        | {"engine.step", "engine.prefill", "engine.decode"}


# -- one name in every sink ---------------------------------------------------
def test_tracing_on_records_the_phase_under_the_same_name():
    eng = _engine(tracing=True)
    eng.run()
    phases = {e["name"] for e in eng.tracer.snapshot()
              if e.get("cat") == "phase"}
    assert {"engine.schedule", "engine.dispatch", "engine.device_wait",
            "engine.finish"} <= phases
    assert phases <= {"engine." + p for p in STEP_PHASES}


def test_a_recording_profiler_gets_the_same_names():
    eng = _engine()
    step, x, y = _train_step()
    with Profiler(timer_only=True) as prof:
        eng.run()
        step(x, y)
    names = {e["name"] for e in prof._events}
    assert set(ENGINE_SPANS + TRAIN_SPANS) <= names


# -- a device trace that does not start says so -------------------------------
def test_a_device_trace_that_cannot_start_raises(monkeypatch, tmp_path):
    def refuse(*a, **k):
        raise RuntimeError("profiler busy")

    monkeypatch.setenv("PADDLE_TPU_TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    prof = Profiler()
    with pytest.raises(RuntimeError, match="profiler busy"):
        prof.start()
    assert not prof._jax_tracing and not _recorder.enabled


def test_profiler_starts_a_trace_that_holds_its_own_record_events(
        monkeypatch, tmp_path):
    monkeypatch.setenv("PADDLE_TPU_TRACE_DIR", str(tmp_path))
    with Profiler():
        with RecordEvent("user.block"):
            jnp.ones(8).block_until_ready()
    assert any(n == "user.block" for line in _host_lines(str(tmp_path))
               for n, _, _ in line)


# -- stable kernel names ------------------------------------------------------
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def _bh(bh=2, s=128, d=64, dtype=jnp.float32):
    return jnp.zeros((bh, s, d), dtype)


def _lse(bh=2, s=128):
    return jnp.zeros((bh, 8, s), jnp.float32)


def _paged(fn, window):
    from paddle_tpu.ops.pallas import paged_attention as pa

    slots, heads, hd, bs, mb, layers, nb = 2, 2, 64, 8, 4, 1, 9
    row = jnp.zeros((slots, window, heads, hd), jnp.float32)
    pool = jnp.zeros((layers, nb, bs, heads, hd), jnp.float32)
    tail = [jnp.zeros((slots, mb), jnp.int32), jnp.zeros(slots, jnp.int32)]
    if window > 1:
        tail.append(jnp.zeros(slots, jnp.int32))
    return (lambda *a: getattr(pa, fn)(*a[:5], 0, *a[5:], interpret=True),
            (row, row, row, pool, pool, *tail))


KERNELS = {
    "flash_causal_fwd": lambda: (
        lambda q, k, v: fa._causal_call_fwd(q, k, v, 0.125, 128,
                                            interpret=True),
        (_bh(), _bh(), _bh())),
    "flash_causal_bwd": lambda: (
        lambda *a: fa._causal_call_bwd(*a, 0.125, 128, interpret=True),
        (_bh(), _bh(), _bh(), _bh(), _bh(), _lse())),
    "flash_shortseq_fwd": lambda: (
        lambda q, k, v: fa._shortseq_call_fwd(q, k, v, None, 0.125, 1,
                                              interpret=True),
        (_bh(), _bh(), _bh())),
    "flash_shortseq_bwd": lambda: (
        lambda *a: fa._shortseq_call_bwd(*a[:3], None, *a[3:], 0.125, 1,
                                         interpret=True),
        (_bh(), _bh(), _bh(), _bh(), _bh(), _lse())),
    "flash_sdpa_fwd": lambda: (
        lambda q, k, v: fa.pallas_sdpa_forward(q, k, v, interpret=True),
        (jnp.zeros((1, 128, 2, 64)),) * 3),
    "paged_decode_attention": lambda: _paged("paged_decode_attention", 1),
    "paged_verify_attention": lambda: _paged("paged_verify_attention", 3),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_each_pallas_call_carries_its_kernels_name(name):
    fn, args = KERNELS[name]()
    text = str(jax.make_jaxpr(fn)(*args))
    assert "pallas_call" in text
    assert name in text
    assert not [k for k in KERNELS if k != name and k in text]
