"""chip_smoke.py rehearsed on the CPU, and the rules it leans on: the
platform helper and `Place` raise rather than hide a missing device,
and the compile cache lives where it can hit."""
import importlib.util
import json
import os
import sys

import jax
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import device as device_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(monkeypatch, *argv):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py", *argv])
    return mod


def test_smoke_refuses_a_cpu_at_phase_one(monkeypatch, capsys):
    """The real path: no accelerator, no result line, a non-zero exit
    (an exception out of main is one) — before any model is built."""
    mod = _smoke(monkeypatch)
    place = device_mod.get_place()
    cache = jax.config.jax_compilation_cache_dir
    with pytest.raises((RuntimeError, SystemExit)) as e:
        mod.main()
    assert e.value.args and e.value.args[0] != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out and "[trainer" not in out
    assert device_mod.get_place() == place      # nothing was selected
    assert jax.config.jax_compilation_cache_dir == cache    # or cached


def test_smoke_rehearsal_runs_every_phase_tiny(monkeypatch, capsys):
    mod = _smoke(monkeypatch, "--rehearsal", "--steps", "2",
                 "--requests", "3")
    mod.main()
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    # a rehearsal is never a result, and names the device it ran on
    assert last == {"ok": False, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    text = "\n".join(lines)
    for phase in ("[device ", "[trainer ", "kernel=decode",
                  "kernel=verify", "decode_traces=1",
                  "first_token_within_logit_tol=3/3",
                  "first_token_equal_to_generate=2/2"):
        assert phase in text, phase
    assert "[cache" not in text                 # and leaves no cache


@pytest.mark.parametrize("ask", [
    device_mod.platform, device_mod.on_tpu, device_mod.pallas_interpret,
    paddle.device.is_compiled_with_tpu,
    lambda: importlib.import_module("paddle_tpu.ops.paged_attention")
    .resolve_backend("auto", head_dim=128, block_size=16, num_heads=16),
    lambda: importlib.import_module("paddle_tpu.ops.pallas.conv")
    .resolve_conv_backend("auto", kernel=(1, 1)),
])
def test_platform_helper_raises_when_backend_cannot_be_asked(
        monkeypatch, ask):
    """One helper, and it does not catch: a backend that fails to
    initialise is an error, never the answer "not a TPU"."""
    def broken(*a, **k):
        raise RuntimeError("Unable to initialize backend")

    monkeypatch.setattr(jax, "devices", broken)
    monkeypatch.delenv("PADDLE_CONV_BACKEND", raising=False)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        ask()


def test_on_cpu_the_helper_answers_cpu():
    assert device_mod.platform() == "cpu"
    assert device_mod.on_tpu() is False
    assert device_mod.pallas_interpret() is True


@pytest.mark.parametrize("name", ["tpu", "tpu:0", "gpu"])
def test_absent_device_raises(name):
    place = device_mod.get_place()
    with pytest.raises(RuntimeError):
        device_mod._parse(name).jax_device()
    with pytest.raises(RuntimeError):
        paddle.device.set_device(name)
    assert device_mod.get_place() == place
    with pytest.raises(ValueError):             # present kind, no such id
        device_mod.Place("cpu", len(jax.devices())).jax_device()


def test_tpu_memory_stats_come_from_the_allocator_only():
    """On an accelerator the allocator is the source and its silence
    raises; the live-array sum is the CPU's and says so."""
    from paddle_tpu.device import memory

    class Dev:
        platform = "tpu"

        def __init__(self, raw):
            self.raw = raw

        def memory_stats(self):
            return self.raw

    st = memory.memory_stats(Dev({"bytes_in_use": 7,
                                  "peak_bytes_in_use": 9}))
    assert (st["source"], st["allocated_bytes"],
            st["peak_allocated_bytes"]) == ("pjrt", 7, 9)
    assert memory.memory_allocated(Dev({"bytes_in_use": 7})) == 7
    with pytest.raises(RuntimeError, match="no allocator statistics"):
        memory.memory_stats(Dev(None))
    with pytest.raises(RuntimeError, match="no allocator statistics"):
        memory.memory_allocated(Dev(None))
    assert memory.memory_stats()["source"] == "live_arrays"


@pytest.mark.parametrize("env", [None, "/somewhere/else"])
def test_compile_cache_dir_is_fixed_or_from_the_environment(
        monkeypatch, env):
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    try:
        got = enable_compile_cache()
        if env is None:     # one fixed path inside the checkout
            assert got == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:               # JAX's own setting; the code names no other
            assert got == env
            assert jax.config.jax_compilation_cache_dir == \
                saved["jax_compilation_cache_dir"]
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
