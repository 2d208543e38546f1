"""The main-path Pallas kernels, compiled for a DESCRIBED TPU v5e chip
at real widths with `interpret=False` — what the interpreter-mode tests
cannot see (Mosaic's layout, tiling and VMEM rules). Nothing runs: a
compile that passes is not a chip run (`python chip_smoke.py` is).

The rule these guard: nothing `auto` selects on a TPU may be a kernel
the chip's compiler refuses.

The topology is described inside the module-scoped fixture below, never
at import time: only one process at a time may load the TPU library, and
every xdist worker imports every test file. Compiles happen in the
test's own process, with the persistent compilation cache off (a compile
for a described chip cannot be read back without one).
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """`chip(fn, *shapes)` compiles `fn` for the first described chip;
    each shape is `(dims, dtype)`. Returns the compiled text."""
    one = SingleDeviceSharding(topo.devices[0])

    def compile_for_chip(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one)
                for s, d in shapes]
        # production precision: conftest pins `highest` for CPU parity
        with jax.default_matmul_precision("default"):
            text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text    # the kernel is in there
        return text

    return compile_for_chip


# GPT-1.3B serving geometry: 16 heads x 128 (4 per shard at mp=4), the
# default engine's 8 slots, block 16, 128-block tables, 24-layer pool
SLOTS, HD, BS, MB, LAYERS, NB = 8, 128, 16, 128, 24, 1025
BF16, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32


@pytest.mark.parametrize("heads", [16, 4])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("window", [1, 4])
def test_paged_attention_kernels_compile(chip, heads, pool, window):
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, paged_verify_attention)

    pdt = I8 if pool == "int8" else BF16
    row = ((SLOTS, window, heads, HD), BF16)
    new = ((SLOTS, window, heads, HD), pdt)
    pools = ((LAYERS, NB, BS, heads, HD), pdt)
    tail = [((SLOTS, MB), I32), ((SLOTS,), I32)]
    if window > 1:
        tail.append(((SLOTS,), I32))            # draft lengths
    if pool == "int8":
        tail.append(((NB, 2), F32))             # this layer's K/V grid
    op = paged_verify_attention if window > 1 else paged_decode_attention

    def fn(q, k, v, kp, vp, *rest):
        if pool == "int8":
            *rest, scales = rest
            return op(q, k, v, kp, vp, 3, *rest, kv_scales=scales)
        return op(q, k, v, kp, vp, 3, *rest)

    chip(fn, row, new, new, pools, pools, *tail)


@pytest.mark.parametrize("slots,heads,blocks", [
    # 8 heads under a 16-row bf16 tile
    pytest.param(SLOTS, 8, NB, id="mp2_shard"),
    # `gpt1p3b_serve_chat` as it runs
    pytest.param(96, 16, 3072, id="chat_cell"),
])
def test_paged_decode_walk_compiles_at(chip, slots, heads, blocks):
    """The several-pages-a-step walk (PR 27) at the two geometries the
    table above lacks: the mp=2 shard, and the benchmark's own engine
    (96 slots, the 3,072-block pool, 128-entry tables)."""
    from paddle_tpu.ops.pallas.paged_attention import (
        pages_per_step, paged_decode_attention)

    assert pages_per_step(BS, heads, HD, BF16) == 8
    row = ((slots, 1, heads, HD), BF16)
    pools = ((LAYERS, blocks, BS, heads, HD), BF16)
    chip(lambda q, k, v, kp, vp, bt, pos: paged_decode_attention(
        q, k, v, kp, vp, 3, bt, pos),
        row, row, row, pools, pools, ((slots, MB), I32), ((slots,), I32))


@pytest.mark.parametrize("name,shape,causal", [
    ("gpt_1p3b", (2, 2048, 16, 128), True),     # chunked causal kernel
    ("bert_base", (32, 512, 12, 64), False),    # short-sequence kernel
])
def test_training_attention_compiles_fwd_and_bwd(chip, monkeypatch, name,
                                                 shape, causal):
    """Through `flash_attention()` itself, told it is on a TPU, so the
    shape tests pick the kernel exactly as they will on the chip."""
    from paddle_tpu.distributed import topology

    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    # one chip: no hybrid mesh left behind by an earlier test of this
    # worker may wrap the kernel in a shard_map over CPU devices
    monkeypatch.setattr(topology, "_default_hcg", None)
    fa.reset_path_stats()

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal) \
            .astype(F32).sum()

    chip(jax.grad(loss, argnums=(0, 1, 2)), *[(shape, BF16)] * 3)
    assert fa.PATH_STATS == {"pallas": 1, "xla": 0}


@pytest.mark.parametrize("hw,cin,cout,stride", [
    (56, 64, 256, 1),       # stage-2 expand: the worst matmul-gap row
    (56, 256, 512, 2),      # strided downsample
    (7, 512, 2048, 1),      # stage-5 expand
])
def test_conv1x1_compiles_infer_and_train(chip, hw, cin, cout, stride):
    from paddle_tpu.ops.pallas.conv import (fused_conv_bn_relu,
                                            fused_conv_bn_relu_train)

    x, w = ((32, hw, hw, cin), BF16), ((1, 1, cin, cout), BF16)
    vec = ((cout,), F32)
    chip(lambda x, w, a, b: fused_conv_bn_relu(
        x, w, a, b, stride=stride, interpret=False), x, w, vec, vec)

    def loss(x, w, g, b):
        y, _, _ = fused_conv_bn_relu_train(x, w, g, b, stride=stride,
                                           interpret=False)
        return y.astype(F32).sum()

    chip(jax.grad(loss, argnums=(0, 1, 2, 3)), x, w, vec, vec)


@pytest.mark.parametrize("heads,head_dim,block,want", [
    (16, 128, 16, "pallas"),    # GPT-1.3B
    (4, 128, 16, "pallas"),     # its mp=4 shard
    (8, 256, 8, "pallas"),
    (12, 64, 16, "dense"),      # GPT-small widths: 64-wide heads refused
    (16, 64, 16, "dense"),
    (12, 128, 16, "dense"),     # 12 rows: "must be aligned to tiling (8)"
    (2, 128, 16, "dense"),      # refused for int8 pools
    (16, 128, 4, "dense"),
])
def test_auto_selects_only_paged_geometries_the_chip_accepts(
        monkeypatch, heads, head_dim, block, want):
    """Probed against the chip's compiler in PR 23: the fused paged
    kernels compile at head_dim % 128 == 0 with 4 or a multiple of 8
    heads per program, and are refused elsewhere. PR 27's decode walk
    (several pages a compute step, rows scored as they lie) compiles at
    all of them, 4 heads under a 16-row bf16 tile included, so nothing
    admitted here was narrowed to dense for it."""
    pa = importlib.import_module("paddle_tpu.ops.paged_attention")
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    assert pa.resolve_backend("auto", head_dim=head_dim,
                              block_size=block, num_heads=heads) == want


def test_auto_never_selects_the_refused_conv3x3(monkeypatch):
    """The chip's compiler refuses the 3x3 family (unaligned slab
    slices, strided vector slices — ROADMAP A4), so on a TPU `auto`
    resolves it dense and only the 1x1 family fused."""
    conv = importlib.import_module("paddle_tpu.ops.pallas.conv")
    monkeypatch.setattr(conv, "on_tpu", lambda: True)
    monkeypatch.delenv("PADDLE_CONV_BACKEND", raising=False)
    kw = dict(in_channels=64, out_channels=64)
    assert conv.resolve_conv_backend(
        "auto", kernel=(3, 3), padding=1, **kw) == "dense"
    assert conv.resolve_conv_backend(
        "auto", kernel=(3, 3), stride=(2, 2), padding=1, **kw) == "dense"
    assert conv.resolve_conv_backend("auto", kernel=(1, 1), **kw) \
        == "pallas"
    # an explicit request is still honoured (interpreter tests, A4)
    assert conv.resolve_conv_backend(
        "pallas", kernel=(3, 3), padding=1, **kw) == "pallas"


# -- the kernels' names, as the chip's compiler prints them ------------------
def _instructions(text):
    """The compiled text cut into instructions, each starting at its
    `%name = ` as a trace's `XLA Ops` event does (the `kernel_metadata`
    attribute spreads one instruction over several lines)."""
    return [c for c in _all_instructions(text, root=False)
            if "tpu_custom_call" in c]


def _all_instructions(text, root=True):
    """Every instruction of the compiled text; with `root` a
    computation's ROOT too, its prefix taken off (a trace names an
    event by the instruction)."""
    import re

    flat = "\n".join(line.strip() for line in text.splitlines())
    start = r"\n(?=(?:ROOT )?%\S+ = )" if root else r"\n(?=%\S+ = )"
    return [c.removeprefix("ROOT ") for c in re.split(start, flat)]


def test_paged_decode_kernel_is_named_and_still_found_by_the_benchmark(
        chip):
    """`paged_attn_roofline` finds the decode kernel by the enclosing
    program's name (`%engine_decode_step.N`): the kernel's own name must
    reach the text without renaming the instruction."""
    import json
    import re
    from pathlib import Path

    from benchmarks.lib import trace_reduce
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    def engine_decode_step(q, k, v, kp, vp, tables, positions):
        return paged_decode_attention(q, k, v, kp, vp, 3, tables, positions)

    row = ((SLOTS, 1, 16, HD), BF16)
    pools = ((LAYERS, NB, BS, 16, HD), BF16)
    text = chip(engine_decode_step, row, row, row, pools, pools,
                ((SLOTS, MB), I32), ((SLOTS,), I32))
    (call,) = _instructions(text)
    patterns = json.loads(
        (Path(__file__).parent.parent / "benchmarks" / "layer_metrics"
         / "paged_attn_roofline.json").read_text())["patterns"]
    assert any(re.search(p, call) for p in patterns), call[:200]
    assert "paged_decode_attention" in trace_reduce.op_group(call)


@pytest.mark.parametrize("kernel,shape,causal", [
    ("flash_causal", (2, 2048, 16, 128), True),
    ("flash_shortseq", (32, 512, 12, 64), False),
])
def test_training_kernels_are_named_in_the_compiled_text(
        chip, monkeypatch, kernel, shape, causal):
    from benchmarks.lib import trace_reduce
    from paddle_tpu.distributed import topology

    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    monkeypatch.setattr(topology, "_default_hcg", None)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal) \
            .astype(F32).sum()

    text = chip(jax.grad(loss, argnums=(0, 1, 2)), *[(shape, BF16)] * 3)
    calls = _instructions(text)
    for name in (kernel + "_fwd", kernel + "_bwd"):
        # jax's name stack wraps the name: `%jvp_flash_causal_fwd_.1`,
        # `%transpose_jvp_flash_causal_bwd__.1`
        mine = [c for c in calls if name in c.split(" = ")[0]]
        assert mine, (name, [c[:40] for c in calls])
        assert all(name in trace_reduce.op_group(c) for c in mine)


# -- the hybrid decoder's kernels at `nemotron3s_serve_chat`'s shapes ---------
# 128 slots; Mamba-2: 128 heads x 64, 8 groups, state 128, 5 layers in the
# pool; LatentMoE: 128 experts held, 1024 -> 2688 -> 1024, 22 a token

N_SLOTS, M_LAYERS, M_HEADS, M_P, M_GROUPS, M_STATE = 128, 5, 128, 64, 8, 128
E_HELD, E_LATENT, E_INTER, E_TOPK, E_TILE = 128, 1024, 2688, 22, 16


def _metric_patterns(metric):
    import json
    from pathlib import Path

    return json.loads(
        (Path(__file__).parent.parent / "benchmarks" / "layer_metrics"
         / f"{metric}.json").read_text())["patterns"]


def test_ssm_decode_kernel_compiles_and_the_benchmark_finds_it(chip):
    """`ssm_decode_roofline` finds the scan's decode kernel inside the
    engine's jitted decode step: by the enclosing program's name and the
    kernel's own, which must reach the compiled text without renaming
    the instruction."""
    import re

    from benchmarks.lib import trace_reduce
    from paddle_tpu.ops.pallas.ssm import ssm_decode_update

    def engine_decode_step(pool, rows, x, dt, a, d, b, c):
        return ssm_decode_update(pool, 2, rows, x, dt, a, d, b, c)

    text = chip(
        engine_decode_step,
        ((M_LAYERS, N_SLOTS + 1, M_HEADS, M_P, M_STATE), F32),
        ((N_SLOTS,), I32), ((N_SLOTS, M_HEADS, M_P), F32),
        ((N_SLOTS, M_HEADS), F32), ((M_HEADS,), F32), ((M_HEADS,), F32),
        ((N_SLOTS, M_GROUPS, M_STATE), F32),
        ((N_SLOTS, M_GROUPS, M_STATE), F32))
    (call,) = _instructions(text)
    assert any(re.search(p, call)
               for p in _metric_patterns("ssm_decode_roofline")), call[:200]
    assert not any(re.search(p, call)
                   for p in _metric_patterns("moe_experts_roofline"))
    assert "ssm_decode_update" in trace_reduce.op_group(call)


@pytest.mark.parametrize("tokens", [N_SLOTS, 256, 256 + N_SLOTS],
                         ids=["decode", "chunk", "decode_with_chunk"])
def test_moe_grouped_matmul_compiles_and_the_benchmark_finds_it(chip,
                                                                tokens):
    """Both products of an expert layer (up with relu^2, down) over the
    dropless buffer of a decode step (128 rows), of a prefill chunk
    (256) and of both together (384): every assignment could land here,
    each expert padded to a tile."""
    import re

    from benchmarks.lib import trace_reduce
    from paddle_tpu.ops.pallas.moe import column_tile, moe_grouped_matmul

    rows = -(-(tokens * E_TOPK + E_HELD * (E_TILE - 1)) // E_TILE) * E_TILE
    # a whole expert a block (5.5 MB each way): an expert's row tiles
    # share it, and its weights cross once a call
    assert column_tile(E_LATENT, E_INTER, 2) == E_INTER
    assert column_tile(E_INTER, E_LATENT, 2) == E_LATENT

    def product(x, w1, w2, tile_expert, live):
        hid = moe_grouped_matmul(x, w1, tile_expert, live, E_TILE,
                                 relu_squared=True)
        return moe_grouped_matmul(hid, w2, tile_expert, live, E_TILE)

    # the enclosing program's name tells the two metrics apart
    product.__name__, metric, other = {
        N_SLOTS: ("engine_decode_step", "moe_experts_roofline",
                  "moe_prefill_experts_busy_pct"),
        256: ("engine_prefill_chunk", "moe_prefill_experts_busy_pct",
              "moe_experts_roofline"),
        256 + N_SLOTS: ("engine_decode_step_with_chunk",
                        "moe_experts_roofline",
                        "moe_prefill_experts_busy_pct")}[tokens]
    shapes = (((rows, E_LATENT), BF16),
              ((E_HELD, E_LATENT, E_INTER), BF16),
              ((E_HELD, E_INTER, E_LATENT), BF16),
              ((rows // E_TILE,), I32), ((1,), I32))
    assert _grouped_blocks(product, *shapes) == [
        ((rows // E_TILE, 1), (1, E_LATENT, E_INTER)),
        ((rows // E_TILE, 1), (1, E_INTER, E_LATENT))]
    text = chip(product, *shapes)
    # the second product is this test function's ROOT; in the engine's
    # step neither is, and a trace names an event by the instruction
    calls = [c for c in _all_instructions(text)
             if "tpu_custom_call" in c]
    assert len(calls) == 2
    for call in calls:
        assert any(re.search(p, call)
                   for p in _metric_patterns(metric)), call[:200]
        assert not any(re.search(p, call)
                       for p in _metric_patterns(other))
        assert "moe_grouped_matmul" in trace_reduce.op_group(call)


def _grouped_blocks(fn, *shapes):
    """(grid, weight block) of every grouped matmul `fn` traces, in
    order: what the kernel is built with, which the compiled text keeps
    inside the serialized Mosaic module."""
    found = []
    jaxpr = jax.make_jaxpr(fn)(*[jax.ShapeDtypeStruct(s, d)
                                 for s, d in shapes])
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grid = eqn.params["grid_mapping"]
            weights = grid.block_mappings[1].block_shape
            found.append((tuple(grid.grid), tuple(
                getattr(b, "block_size", b) for b in weights)))
    return found


# the latent decoder's experts (`pangu_ultra_serve_docqa`): 8 held,
# 7,680 -> [gate | up] 2 x 2,048 -> 7,680, 8 a token
P_HELD, P_HIDDEN, P_INTER, P_TOPK = 8, 7680, 2048, 8


@pytest.mark.parametrize("tokens", [96, 256], ids=["decode", "chunk"])
def test_moe_grouped_matmul_cuts_a_large_expert_into_columns(chip, tokens):
    """An expert too large for one block (63 MB and 31.5 MB) keeps the
    column blocks of 3 MB at most, walked inside each row tile: 128
    columns of gate-and-up, 768 of down. Its experts hold a tile or two
    a step, so there is little to re-fetch; swapping the grid would read
    the row tiles once a column block instead."""
    from paddle_tpu.ops.pallas.moe import moe_grouped_matmul

    rows = -(-(tokens * P_TOPK + P_HELD * (E_TILE - 1)) // E_TILE) \
        * E_TILE

    def product(x, w1, w2, tile_expert, live):
        gate_up = moe_grouped_matmul(x, w1, tile_expert, live, E_TILE)
        return moe_grouped_matmul(gate_up[:, :P_INTER], w2, tile_expert,
                                  live, E_TILE)

    shapes = (((rows, P_HIDDEN), BF16),
              ((P_HELD, P_HIDDEN, 2 * P_INTER), BF16),
              ((P_HELD, P_INTER, P_HIDDEN), BF16),
              ((rows // E_TILE,), I32), ((1,), I32))
    assert _grouped_blocks(product, *shapes) == [
        ((rows // E_TILE, 2 * P_INTER // 128), (1, P_HIDDEN, 128)),
        ((rows // E_TILE, P_HIDDEN // 768), (1, P_INTER, 768))]
    text = chip(product, *shapes)
    assert len([c for c in _all_instructions(text)
                if "tpu_custom_call" in c]) == 2


def test_auto_takes_the_new_kernels_on_a_tpu_only(monkeypatch):
    from paddle_tpu.distributed import moe
    from paddle_tpu.ops import ssm

    assert ssm.resolve_ssm_backend("auto", M_STATE) == "xla"     # a CPU
    assert moe.resolve_moe_backend("auto", E_LATENT) == "xla"
    monkeypatch.setattr(ssm, "on_tpu", lambda: True)
    monkeypatch.setattr("paddle_tpu.core.device.on_tpu", lambda: True)
    assert ssm.resolve_ssm_backend("auto", M_STATE) == "pallas"
    assert ssm.resolve_ssm_backend("auto", 16) == "xla"
    assert moe.resolve_moe_backend("auto", E_LATENT) == "pallas"
    assert moe.resolve_moe_backend("auto", 48) == "xla"


# -- the hybrid decoder's engine programs, whole ---------------------------------

def _engine_programs_for_chip(topo, monkeypatch):
    """The chunk program, the decode step and the decode step that
    carries a chunk of a small hybrid decoder at the kernels' widths
    (state 128, experts 128 -> 256 -> 128), compiled for the described
    chip as the engine jits them (pools and state donated):
    `{name: compiled text}`, and the pools' shapes."""
    import numpy as np

    from paddle_tpu.inference.engine import GenerationEngine
    from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                              NemotronHForCausalLM)

    cfg = NemotronHConfig(
        vocab_size=512, hidden_size=256, hybrid_override_pattern="ME*",
        mamba_num_heads=16, mamba_head_dim=64, n_groups=2,
        ssm_state_size=128, num_attention_heads=4, num_key_value_heads=2,
        head_dim=128, n_routed_experts=8, router_experts=16,
        num_experts_per_tok=4, moe_latent_size=128,
        moe_intermediate_size=256,
        moe_shared_expert_intermediate_size=256, max_seq_len=128,
        dtype="bfloat16", init="zeros")
    model = NemotronHForCausalLM(cfg)
    model.eval()
    eng = GenerationEngine(model, num_slots=16, block_size=16,
                           prefill_chunk=32, donate=True)
    one = SingleDeviceSharding(topo.devices[0])
    c, i32 = eng.cache, np.int32
    slots, blocks = eng.num_slots, eng.max_blocks
    chunk = (np.zeros((1, eng.prefill_chunk), i32), i32(0), i32(0),
             np.zeros(blocks, i32), i32(0))
    decode = (np.zeros((slots, 1), i32), np.zeros(slots, i32),
              np.zeros((slots, blocks), i32), np.zeros(slots, i32))
    head = (eng._state_arrays(), c.kpool, c.vpool, c.state)
    # what the engine's steps ask at trace time: on the chip `auto`
    # takes the kernels, and none runs under the interpreter
    monkeypatch.setattr("paddle_tpu.core.device.platform", lambda: "tpu")
    texts = {}
    for name, jitted, host in (("chunk", eng._prefill, chunk),
                               ("decode", eng._decode, decode),
                               ("fused", eng._fused, chunk + decode)):
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=one), head + host)
        with jax.default_matmul_precision("default"):
            texts[name] = jitted.lower(*args).compile().as_text()
    shapes = lambda arrays: {tuple(a.shape) for a in arrays}
    return texts, shapes((c.kpool, c.vpool)), shapes(c.state)


def test_decode_step_with_chunk_keeps_the_kernels_names_and_copies_no_pool(
        topo, monkeypatch, fused_step_offered):
    """The one program for a chunk and a decode step is named with the
    decode step's prefix, so the benchmark's two decode-step patterns
    find its Mosaic calls (and the chunk program's pattern does not);
    and where the chunk's `.at[row].set` and the decode kernel's aliased
    update meet in one program, no pool of state is copied beyond what
    the two programs it replaces copy. (The K/V pools are: the compiler
    keeps the pool the chunk's attention loop reads apart from the one
    the decode rows' write updates — 2 x 33.5 MB in and out at the
    cell's size, 0.16 ms of a step; `PERF.md` section 7.) The engine is
    built on this CPU, where the spec offers no fused step: the fixture
    offers it, as the chip does at these widths."""
    import re

    texts, kv_pools, state_pools = _engine_programs_for_chip(topo,
                                                             monkeypatch)

    instructions = _all_instructions

    def calls(text, metric):
        return [c for c in instructions(text) if "tpu_custom_call" in c
                and any(re.search(p, c) for p in _metric_patterns(metric))]

    def copies(text, pools):
        """Copies of a pool-shaped array within the device's memory, in
        line or started async (a prefetch into the fast memory space
        `S(1)` and its way back are the compiler's staging, which only a
        pool of a test's size fits)."""
        found = []
        for c in instructions(text):
            m = re.match(
                r"%\S+ = (\(?\w+\[([\d,]+)\].*) copy(?:-start)?\(", c)
            if m and "S(1)" not in m.group(1) and \
                    tuple(map(int, m.group(2).split(","))) in pools:
                found.append(c[:80])
        return found

    fused = texts["fused"]
    assert len(calls(fused, "ssm_decode_roofline")) == 1        # 1 M layer
    assert len(calls(fused, "moe_experts_roofline")) == 2       # 1 E layer
    assert calls(fused, "moe_prefill_experts_busy_pct") == []
    assert all(c.startswith("%engine_decode_step_with_chunk.")
               for c in instructions(fused) if "tpu_custom_call" in c)
    # the same kernels as the two programs', once
    assert len(calls(texts["decode"], "ssm_decode_roofline")) == 1
    assert len(calls(texts["decode"], "moe_experts_roofline")) == 2
    assert len(calls(texts["chunk"], "moe_prefill_experts_busy_pct")) == 2
    assert len(copies(fused, state_pools)) <= \
        len(copies(texts["chunk"], state_pools)) \
        + len(copies(texts["decode"], state_pools)), \
        copies(fused, state_pools)
    assert len(copies(fused, kv_pools)) <= 4, copies(fused, kv_pools)


# -- the latent-attention decoder's kernel and engine programs -------------------

def test_mla_decode_kernel_compiles_at_the_cells_geometry(chip):
    """`pangu_ultra_serve_docqa` as it runs: 96 slots of 128 heads over
    rows of 640 lanes (576 values), pages of 64, 8 pages a compute step;
    a pool of 576-wide rows is what the chip's copy engine refuses."""
    from paddle_tpu.ops.pallas.paged_attention import (
        latent_pages_per_step, mla_paged_decode)

    slots, heads, width, value, block, blocks, tables = \
        96, 128, 640, 512, 64, 10240, 136
    assert latent_pages_per_step(block, width, BF16) == 8
    text = chip(
        lambda q, new, pool, bt, pos: mla_paged_decode(
            q, new, pool, 2, bt, pos, value, 192 ** -0.5),
        ((slots, heads, width), BF16), ((slots, width), BF16),
        ((5, blocks, block, width), BF16), ((slots, tables), I32),
        ((slots,), I32))
    assert "mla_paged_decode" in text


#: what a per-layer metric of the prefill kernel's time would look for
#: (as `moe_prefill_experts_busy_pct` does for the experts' product)
MLA_PREFILL_PATTERN = \
    r"(?s)^%engine_prefill_chunk\S* = .*kernel_name\W+mla_paged_prefill"


@pytest.mark.parametrize("chunk,pages", [(256, 16), (512, 8)])
def test_mla_prefill_kernel_compiles_at_the_cells_geometry(chip, chunk,
                                                           pages):
    """`pangu_ultra_serve_docqa`'s chunk as it runs (256 rows of 128
    heads against rows of 640 lanes in pages of 64, 8 heads a program,
    16 pages = 1,024 keys a step), and a chunk twice as wide, which takes
    half the keys a step."""
    from paddle_tpu.ops.pallas.paged_attention import (
        latent_prefill_heads, latent_prefill_pages_per_step,
        mla_paged_prefill)

    heads, width, rank, block, blocks, tables = 128, 640, 512, 64, 10240, 136
    assert latent_prefill_heads(heads) == 8
    assert latent_prefill_pages_per_step(chunk, block, width, BF16) == pages
    text = chip(
        lambda qn, qr, w, pool, row, start, plen: mla_paged_prefill(
            qn, qr, w, pool, 2, row, start, plen, 192 ** -0.5),
        ((chunk, heads, 128), BF16), ((chunk, heads, 64), BF16),
        ((rank, heads, 256), BF16), ((5, blocks, block, width), BF16),
        ((tables,), I32), ((), I32), ((), I32))
    assert "mla_paged_prefill" in text


def test_latent_engine_programs_hold_the_kernel_and_copy_no_pool(
        topo, monkeypatch, fused_step_offered):
    """The programs of a small latent-attention decoder at the
    kernels' widths (rows of 256 + 64 values in 384 lanes, so that a
    chunk of 256 rows takes the expanded form; experts 256 -> 2 x 128 ->
    256), compiled for the described chip as the engine jits
    them (the ONE pool donated): the latent walk's Mosaic call of every
    layer lies under `%engine_decode_step`, where `mla_decode_roofline`
    looks for it, the experts' two under `moe_experts_roofline`'s, the
    chunk's attention kernel of every layer under
    `%engine_prefill_chunk` (the engine's one backend reaches both
    programs); the step that carries a chunk holds all three under
    `%engine_decode_step_with_chunk`, where the two decode-step
    patterns find its decode walk and its experts' products; and no
    program copies a pool-shaped array. The engine is built on this
    CPU, where the spec offers no fused step: the fixture offers it, as
    the chip does at these widths."""
    import re

    import numpy as np

    from paddle_tpu.inference.engine import GenerationEngine
    from paddle_tpu.models.pangu_ultra_moe import (
        PanguUltraMoEConfig, PanguUltraMoEForCausalLM)

    cfg = PanguUltraMoEConfig(
        vocab_size=512, hidden_size=256, num_hidden_layers=2,
        first_k_dense_replace=1, num_attention_heads=8, q_lora_rank=128,
        kv_lora_rank=256, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, intermediate_size=512, moe_intermediate_size=128,
        n_routed_experts=8, router_experts=16, num_experts_per_tok=4,
        max_seq_len=512, dtype="bfloat16", init="zeros")
    assert cfg.pool_row_width == 384
    model = PanguUltraMoEForCausalLM(cfg)
    model.eval()
    monkeypatch.setattr("paddle_tpu.core.device.platform", lambda: "tpu")
    monkeypatch.setattr("paddle_tpu.ops.paged_attention.on_tpu",
                        lambda: True)
    eng = GenerationEngine(model, num_slots=16, block_size=16,
                           prefill_chunk=256, donate=True)
    assert eng.attention_backend == "pallas"
    one = SingleDeviceSharding(topo.devices[0])
    c, i32 = eng.cache, np.int32
    assert c.vpool is None
    slots, blocks = eng.num_slots, eng.max_blocks
    chunk = (np.zeros((1, eng.prefill_chunk), i32), i32(0), i32(0),
             np.zeros(blocks, i32))
    decode = (np.zeros((slots, 1), i32), np.zeros(slots, i32),
              np.zeros((slots, blocks), i32))
    head = (eng._state_arrays(), c.kpool, None)
    texts = {}
    for name, jitted, host in (("chunk", eng._prefill, chunk),
                               ("decode", eng._decode, decode),
                               ("fused", eng._fused, chunk + decode)):
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=one), head + host)
        with jax.default_matmul_precision("default"):
            texts[name] = jitted.lower(*args).compile().as_text()

    def calls(text, metric):
        return [c for c in _all_instructions(text)
                if "tpu_custom_call" in c and any(
                    re.search(p, c) for p in _metric_patterns(metric))]

    assert len(calls(texts["decode"], "mla_decode_roofline")) == 2
    assert len(calls(texts["decode"], "moe_experts_roofline")) == 2
    assert calls(texts["chunk"], "mla_decode_roofline") == []
    prefill = [c for c in _all_instructions(texts["chunk"])
               if "tpu_custom_call" in c
               and re.search(MLA_PREFILL_PATTERN, c)]
    assert len(prefill) == 2
    assert not any(re.search(MLA_PREFILL_PATTERN, c)
                   for c in _all_instructions(texts["decode"]))
    fused = texts["fused"]
    assert len(calls(fused, "mla_decode_roofline")) == 2
    assert len(calls(fused, "moe_experts_roofline")) == 2
    assert len([c for c in _all_instructions(fused)
                if "tpu_custom_call" in c and re.search(
                    r"kernel_name\W+mla_paged_prefill", c)]) == 2
    assert all(c.startswith("%engine_decode_step_with_chunk.")
               for c in _all_instructions(fused) if "tpu_custom_call" in c)
    pool = tuple(c.kpool.shape)
    for name, text in texts.items():
        copied = [i[:80] for i in _all_instructions(text)
                  if (m := re.match(
                      r"%\S+ = (\(?\w+\[([\d,]+)\].*) copy(?:-start)?\(",
                      i)) and "S(1)" not in m.group(1)
                  and tuple(map(int, m.group(2).split(","))) == pool]
        assert copied == [], (name, copied)


# -- power retention: the decode kernel and the engine with no pool -------------

R_SLOTS, R_KVH, R_READERS, R_WIDTH = 20, 8, 5, 8704
# the chunk kernel as the device trace names it inside the prefill program
CHUNK_PATTERN = \
    r"(?s)^%engine_prefill_chunk\S* = .*kernel_name\W+power_retention_chunk"


def test_retention_decode_kernel_compiles_and_the_benchmark_finds_it(chip):
    """The retention's decode kernel at the cell's geometry (20 slots, 8
    KV heads each read by 5 query heads, a state of 8,704 x 128 float32 a
    head: 4.5 MB a block, in and out): Mosaic takes the transposes that
    build `phi`, the dynamic tile slices and the 18 MB of VMEM, and
    `retention_decode_roofline` finds the call inside the engine's decode
    step."""
    import re

    from benchmarks.lib import trace_reduce
    from paddle_tpu.ops.pallas.retention import retention_decode_update

    def engine_decode_step(pool, rows, q, k, v, g):
        return retention_decode_update(pool, 3, rows, q, k, v, g)

    row = ((R_SLOTS, R_KVH, HD), F32)
    text = chip(
        engine_decode_step,
        ((8, R_SLOTS + 1, R_KVH, R_WIDTH, HD), F32), ((R_SLOTS,), I32),
        ((R_SLOTS, R_KVH, R_READERS, HD), F32), row, row,
        ((R_SLOTS, R_KVH), F32))
    (call,) = _instructions(text)
    assert any(re.search(p, call)
               for p in _metric_patterns("retention_decode_roofline"))
    assert not any(re.search(p, call)
                   for p in _metric_patterns("ssm_decode_roofline"))
    assert "power_retention_decode" in trace_reduce.op_group(call)


def test_retention_engine_programs_hold_no_pool_and_copy_no_state(
        topo, monkeypatch):
    """The two programs of an engine built for a model with NO paged
    cache (heads of 128 as published, a narrow body), compiled for the
    described chip as the engine jits them (the state rows donated, None
    in the pools' and the tables' places): the decode step holds the
    retention's decode kernel once a layer and no loop; the chunk holds
    the chunk kernel once a layer (`CHUNK_PATTERN`) and no loop, so
    `retention_chunk_busy_pct`, which reads the XLA form's sub-chunk
    `while`s, finds nothing there since PR 36; neither copies an array
    of the state's shape."""
    import re

    import numpy as np

    from paddle_tpu.inference.engine import GenerationEngine
    from paddle_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM

    cfg = BrumbyConfig(
        vocab_size=512, hidden_size=256, num_hidden_layers=2,
        intermediate_size=512, max_seq_len=512, dtype="bfloat16",
        init="zeros")
    model = BrumbyForCausalLM(cfg)
    model.eval()
    monkeypatch.setattr("paddle_tpu.core.device.platform", lambda: "tpu")
    eng = GenerationEngine(model, num_slots=4, prefill_chunk=256,
                           donate=True)
    assert eng.attention_backend == "pallas"
    one = SingleDeviceSharding(topo.devices[0])
    c, i32 = eng.cache, np.int32
    assert c.kpool is None and c.vpool is None and eng.max_blocks == 0
    slots = eng.num_slots
    chunk = (np.zeros((1, eng.prefill_chunk), i32), i32(0), i32(0), None,
             i32(0))
    decode = (np.zeros((slots, 1), i32), np.zeros(slots, i32), None,
              np.zeros(slots, i32))
    head = (eng._state_arrays(), None, None, c.state)
    texts = {}
    for name, jitted, host in (("chunk", eng._prefill, chunk),
                               ("decode", eng._decode, decode)):
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=one), head + host)
        with jax.default_matmul_precision("default"):
            texts[name] = jitted.lower(*args).compile().as_text()

    def found(text, metric):
        return [i for i in _all_instructions(text) if any(
            re.search(p, i) for p in _metric_patterns(metric))]

    assert len(found(texts["decode"], "retention_decode_roofline")) == 2
    assert found(texts["decode"], "retention_chunk_busy_pct") == []
    assert found(texts["chunk"], "retention_chunk_busy_pct") == []
    assert " while(" not in texts["chunk"]
    chunk_calls = [i for i in _all_instructions(texts["chunk"])
                   if "tpu_custom_call" in i]
    assert len(chunk_calls) == 2
    assert all(re.search(CHUNK_PATTERN, i) for i in chunk_calls)
    assert not any(re.search(CHUNK_PATTERN, i)
                   for i in _all_instructions(texts["decode"]))
    held = {tuple(a.shape) for a in c.state}
    for name, text in texts.items():
        copied = [i[:80] for i in _all_instructions(text)
                  if (m := re.match(
                      r"%\S+ = (\(?\w+\[([\d,]+)\].*) copy(?:-start)?\(",
                      i)) and "S(1)" not in m.group(1)
                  and tuple(map(int, m.group(2).split(","))) in held]
        assert copied == [], (name, copied)
