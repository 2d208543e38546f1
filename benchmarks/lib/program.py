"""With `constructors/`, the only code of the benchmark that touches the
system under test.

From the program the benchmark takes the entry points the users call
(`jit.TrainStep`, `GenerationEngine`), its public counters and the
kernel-path statistics; everything else (inputs, weights, clocks,
counts, the reference) is the benchmark's own. Imports of `paddle_tpu`
happen inside the functions, so that importing this module loads
nothing.
"""
from __future__ import annotations

import importlib


def enable_compile_cache():
    """The program's own placement: `JAX_COMPILATION_CACHE_DIR` where it
    is set, else `<checkout>/.jax_cache` — a fixed path inside the
    checkout, so the second run of a cell finds every program."""
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    return enable_compile_cache()


def build_model(cfg, bench_dir=None):
    """The model a configuration file names: `constructors/<name>.py`
    calls the program's own constructor; the dtype is the file's."""
    from . import registry

    model = registry.load_module("constructors", cfg["constructor"],
                                 bench_dir).build(cfg)
    model.eval()                    # dropout is 0 in every cell
    if cfg["dtype"] != "float32":
        model.to(dtype=cfg["dtype"])
    return model


def bind_weights(model, arrays):
    """Give every parameter the seeded array of its name; a name or a
    shape that does not match is an error (the reference's parameter
    list and the program's must be the same model)."""
    named = dict(model.named_parameters())
    if set(named) != set(arrays):
        raise ValueError(
            "parameter names differ: program only "
            f"{sorted(set(named) - set(arrays))[:5]}, reference only "
            f"{sorted(set(arrays) - set(named))[:5]}")
    for name, p in named.items():
        a = arrays[name]
        if tuple(p.shape) != tuple(a.shape) or p._array.dtype != a.dtype:
            raise ValueError(f"{name}: program {p.shape} "
                             f"{p._array.dtype}, seeded {a.shape} {a.dtype}")
        p._in_place_update(a)


def build_trainer(model, opt_cfg):
    import paddle_tpu as paddle
    import paddle_tpu.jit as jit

    opt = paddle.optimizer.AdamW(
        learning_rate=opt_cfg["learning_rate"], beta1=opt_cfg["beta1"],
        beta2=opt_cfg["beta2"], epsilon=opt_cfg["epsilon"],
        weight_decay=opt_cfg["weight_decay"],
        parameters=model.parameters())
    return jit.TrainStep(model, opt, model.loss_fn), opt


def to_tensor(array):
    import paddle_tpu as paddle

    return paddle.to_tensor(array)


def first_moments(model, opt):
    """`{parameter name: moment1 array}` through the optimizer's public
    `state_dict` (slot i belongs to `model.parameters()[i]`)."""
    state = opt.state_dict()
    return {name: state[f"moment1_{i}"]._array
            for i, (name, _) in enumerate(model.named_parameters())}


def parameters(model):
    return {name: p._array for name, p in model.named_parameters()}


def train_trace_count(step):
    """Times the compiled step was traced (jax's own cache size of the
    jitted callable): 1 after warm-up, and still 1 after the window."""
    return step._jitted._cache_size()


def flash_path_stats(reset=False):
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    if reset:
        fa.reset_path_stats()
    return dict(fa.PATH_STATS)


def paged_path_stats(reset=False):
    from paddle_tpu.ops import paged_attention as pa

    if reset:
        pa.reset_paged_path_stats()
    return dict(pa.PAGED_PATH_STATS)


def build_engine(model, engine_cfg):
    from paddle_tpu.inference.engine import GenerationEngine

    return GenerationEngine(model, **engine_cfg)


def pool_blocks_used(engine):
    """Blocks of the paged pool that live contexts hold right now (the
    null block and what the prefix cache could give back left out)."""
    return engine.cache.num_blocks - 1 - engine.cache.num_free


def pool_blocks_total(engine):
    return engine.cache.num_blocks - 1
