"""Harvest: abstractly trace every registered compiled engine program
over the serving config matrix, on CPU, with no device execution.

For each matrix point ({dense,pallas} x K in {0,4} x mp in {1,2}) a
TINY GPT engine is constructed exactly the way serving constructs it
(same builders, same jit wrappers, same donation/out_shardings — the
checker lowers the ENGINE'S OWN jitted objects, so a contract break in
`inference/engine.py` cannot hide behind a checker-side rebuild), its
step bodies are traced with `jax.make_jaxpr` and lowered with
`.lower()`, and the TPU1xx rules run over the resulting
jaxpr/StableHLO. Tracing and lowering never dispatch a computation;
the only device interaction is allocating the tiny engine's zeroed
pools, which is why the whole matrix runs in CPU-only CI.

The committed `TRACE_BASELINE.json` (repo root, next to the other
baselines) snapshots per-program op/collective/byte counts; any drift
is a TPU100 finding — an intentional change regenerates it with
`tools/tpu_verify.py --write-trace-baseline` and reviews the diff.

jax / the framework are imported INSIDE the functions here: importing
`paddle_tpu.analysis.trace` must not initialize a JAX backend (the
import-smoke contract).
"""
from __future__ import annotations

import json
import os

from ..findings import Finding, assign_ids
from .contracts import get_contract
from .rules import TracedProgram, check_program

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

#: Committed drift snapshot (repo root, BENCH_BASELINE.json precedent).
DEFAULT_TRACE_BASELINE = os.path.join(_REPO_ROOT, "TRACE_BASELINE.json")

#: The serving config matrix every contract is checked under.
BACKENDS = ("dense", "pallas")
SPEC_KS = (0, 4)
MP_DEGREES = (1, 2)
#: None = today's fp serving; "int8" = the quantized configs (int8
#: per-block-scaled KV pools AND int8 weights through the state seam)
#: — every contract is proven over both, so a quantization regression
#: (dropped donation, bf16 accumulation on a dequantized matmul, an
#: unbudgeted collective in the scale fold) fails the same gate.
KV_DTYPES = (None, "int8")

#: Multi-tenant LoRA configs (PR 13): the base matrix threads NO
#: adapter state (its programs must stay byte-identical to the
#: pre-adapter baseline), and these two extra configs prove the
#: adapter-threaded steps — a plain fp mp=1 decode+prefill pass and
#: the fully-composed (pallas, K=4, mp=2, int8) verify step — under
#: every TPU1xx rule: donation still pins both pools, the lora
#: einsums accumulate fp32 (TPU103), and the adapter gathers add NO
#: collectives (TPU104's budget is unchanged).
LORA_CONFIGS = (("dense", 0, 1, None, True),
                ("pallas", 4, 2, "int8", True))

#: Probabilistic serving configs (PR 15): the base matrix threads NO
#: sampling state (a sampling=False engine's programs must stay
#: byte-identical to the pre-sampling baseline — the greedy
#: no-regression proof at the trace level), and these two extra
#: configs prove the sampling-threaded steps — a plain fp mp=1
#: decode+prefill pass and the fully-composed (pallas, K=4, mp=2,
#: int8) REJECTION-SAMPLING verify step — under every TPU1xx rule:
#: donation still pins both pools, the draw/masking math stays fp32
#: (TPU103), and the per-slot key folds add NO collectives (TPU104's
#: budget is unchanged — the draws run replicated on the all-gathered
#: logits).
SAMPLING_CONFIGS = (("dense", 0, 1, None, False, True),
                    ("pallas", 4, 2, "int8", False, True))

#: Tiny-but-structurally-real harvest geometry: 2 layers so per-layer
#: collective budgets multiply, 4 heads so mp=2 head-sharding divides,
#: block_size 8 so the pallas kernel's sublane constraint holds.
TINY = dict(vocab=64, hidden=32, layers=2, heads=4, seq=32,
            slots=2, block_size=8, max_rank=4)


def default_matrix():
    return tuple((b, k, mp, kv, False, False) for b in BACKENDS
                 for k in SPEC_KS for mp in MP_DEGREES
                 for kv in KV_DTYPES) \
        + tuple((*m, False) for m in LORA_CONFIGS) + SAMPLING_CONFIGS


def _require_devices(mp):
    import jax

    if mp > 1 and len(jax.devices()) < mp:
        raise RuntimeError(
            f"harvesting the mp={mp} configs needs {mp}+ devices — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "BEFORE the first jax use (tools/tpu_verify.py does this "
            "for you)")


def _build_model():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig.tiny(vocab=TINY["vocab"], hidden=TINY["hidden"],
                         layers=TINY["layers"], heads=TINY["heads"],
                         seq=TINY["seq"])
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def _trace_one(name, config, pure_fn, jitted, args, mp, num_layers,
               declared=None, geometry=None):
    """make_jaxpr + lower ONE program and capture the TracedProgram
    record the rules consume. `jitted` is the engine's own jit wrapper
    (its donation and out_shardings, not the checker's). `declared` is
    an optional (in_specs, out_specs) pair of per-leaf layout tuples
    (see `_declared_specs`) and `geometry` the serving-symbol dict —
    both consumed by the tpu-shard tier."""
    import jax

    contract = get_contract(name)
    closed = jax.make_jaxpr(pure_fn)(*args)
    lowered = jitted.lower(*args)
    donated = sum(
        len(jax.tree_util.tree_leaves(args[i]))
        for i in contract.donate_argnums)
    leaves = [(jax.tree_util.keystr(path), leaf) for path, leaf in
              jax.tree_util.tree_flatten_with_path(args)[0]]
    d_in, d_out = declared if declared is not None else (None, None)
    return TracedProgram(
        contract=contract, config=config, mp=mp,
        num_layers=num_layers, jaxpr=closed,
        lowered_text=lowered.as_text(), donated_leaves=donated,
        arg_leaves=leaves, declared_in_specs=d_in,
        declared_out_specs=d_out, geometry=geometry)


def _declared_specs(eng, args, kv, lora, n_out_repl):
    """The engine's DECLARED layout truth for one step, flattened per
    argument leaf in signature order: `_tp_specs` for the state
    (quantized entries contribute their (codes, scale) spec pair),
    `pool_pspec()` for both pool planes, a replicated spec for the
    int8 scale grid, the adapter pool's `pool_pspecs()`, and None
    (no declaration) for the trailing host args. Outputs mirror
    `_step_out_shardings`: `n_out_repl` replicated leading outputs,
    then the sharded pools, then the replicated scale grid. Specs are
    converted to pure per-dim axis-name tuples (() = replicated) so
    the tpu-shard rules never import jax. None/None at mp == 1 —
    there is no declared mesh layout to drift from."""
    if eng.mesh is None:
        return None, None
    import jax
    from jax.sharding import PartitionSpec as P

    ins = []
    for spec in eng._tp_specs:
        pair = (spec,) if isinstance(spec, P) else tuple(spec)
        ins.extend(tuple(s) for s in pair)
    pool = tuple(eng.cache.pool_pspec())
    ins += [pool, pool]
    if kv:
        ins.append(())
    if lora:
        ins.extend(tuple(s) for s in eng.adapter_pool.pool_pspecs())
    n_host = len(jax.tree_util.tree_leaves(args)) - len(ins)
    assert n_host >= 0, "declared specs outnumber the program's leaves"
    out_specs = ((),) * n_out_repl + (pool, pool) \
        + (((),) if kv else ())
    return tuple(ins) + (None,) * n_host, out_specs


def _geometry(eng, num_layers, tokens):
    """The serving-geometry symbols tpu-shard's payload bounds
    (AxisCollectiveBudget entries) evaluate over — from the engine
    and model the program was actually traced from."""
    cfg = eng.model.config
    return dict(tokens=tokens, hidden=cfg.hidden_size,
                intermediate=cfg.intermediate_size,
                vocab=cfg.vocab_size, heads=cfg.num_heads,
                head_dim=cfg.hidden_size // cfg.num_heads,
                layers=num_layers, blocks=eng.cache.num_blocks,
                block_size=eng.cache.block_size,
                slots=eng.num_slots)


def _build_registry(config):
    """A tiny one-adapter registry for the LoRA configs: shapes are
    all abstract tracing sees, so the factors are zero-filled."""
    import numpy as np

    from paddle_tpu.adapters import AdapterRegistry

    reg = AdapterRegistry(config, max_rank=TINY["max_rank"])
    r, L = 2, config.num_layers
    weights = {}
    for site in ("qkv", "out", "fc1", "fc2"):
        in_d, out_d = reg.site_dims(site)
        weights[site] = [(np.zeros((r, in_d), np.float32),
                          np.zeros((out_d, r), np.float32))
                         for _ in range(L)]
    reg.register(1, weights, scaling=0.5)
    return reg


def harvest(matrix=None):
    """-> list[TracedProgram] over the full contract matrix: one
    engine per (backend, K, mp, kv_dtype) contributes its
    decode-or-verify step (16 programs — where the backends/K/kv
    diverge); the backend/K-invariant programs (the prefill chunk,
    COW block-copy) harvest once per (mp, kv_dtype) (8 more). The
    kv="int8" configs serve int8 per-block-scaled KV AND int8 weights
    — the full quantized serving shape. The LORA_CONFIGS entries add
    the adapter-threaded programs (3 more: a dense mp=1 decode + its
    prefill chunk, and the composed pallas/K=4/mp=2/int8 verify); the
    SAMPLING_CONFIGS entries add the sampling-threaded programs
    (3 more: a dense mp=1 sampled decode + its sampled prefill chunk,
    and the composed pallas/K=4/mp=2/int8 REJECTION-SAMPLING verify).
    The default (full) harvest also carries the fused Pallas conv
    suite's 4 programs (`_conv_programs`) so their lowering is
    drift-gated like every engine step."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.inference.engine import GenerationEngine

    include_conv = matrix is None
    # pad short (pre-sampling / pre-lora) matrix entries with the
    # DEFAULTS for the missing trailing fields
    matrix = default_matrix() if matrix is None else tuple(
        (*m, *(None, False, False)[len(m) - 3:]) if len(m) < 6 else m
        for m in matrix)
    for _, _, mp, _, _, _ in matrix:
        _require_devices(mp)
    model = _build_model()
    L = model.config.num_layers
    programs = []

    def samp_rows(n):
        """The four traced sampling rows of an n-slot dispatch —
        the engine's host-arg layout, reproduced exactly."""
        return (jnp.asarray(np.zeros(n, np.float32)),
                jnp.asarray(np.zeros(n, np.int32)),
                jnp.asarray(np.ones(n, np.float32)),
                jnp.asarray(np.zeros((n, 2), np.uint32)))

    registry = None
    for backend, K, mp, kv, lora, samp in matrix:
        tag = (",int8" if kv else "") + (",lora" if lora else "") \
            + (",sampling" if samp else "")
        config = f"{backend},K={K},mp={mp}{tag}"
        quant = dict(kv_dtype=kv, weight_dtype=kv) if kv else {}
        if lora and registry is None:
            registry = _build_registry(model.config)
        adapt = dict(adapters=registry) if lora else {}
        skw = dict(sampling=True) if samp else {}
        eng = GenerationEngine(
            model, num_slots=TINY["slots"],
            block_size=TINY["block_size"], attention_backend=backend,
            spec_decode_k=K, mp_degree=mp, donate=True, **quant,
            **adapt, **skw)
        S, MB, C = eng.num_slots, eng.max_blocks, eng.prefill_chunk
        state = eng._state_arrays()
        kp, vp = eng.cache.kpool, eng.cache.vpool
        sc = (eng.cache.scales,) if kv else ()
        # adapter serving: the pool-array tuple rides before the host
        # args and the per-slot page row is the LAST host arg — the
        # engine's _dispatch_step layout, reproduced exactly
        lp = (eng.adapter_pool.arrays(),) if lora else ()
        arow = (jnp.asarray(np.zeros(S, np.int32)),) if lora else ()
        # probabilistic serving: the temp/top-k/top-p + key rows ride
        # between the tables and the adapter page row
        srows = samp_rows(S) if samp else ()
        tokens = jnp.asarray(np.zeros((S, K + 1), np.int32))
        positions = jnp.asarray(np.zeros(S, np.int32))
        tables = jnp.asarray(np.zeros((S, MB), np.int32))
        if K > 0:
            dlens = jnp.asarray(np.zeros(S, np.int32))
            step_args = (state, kp, vp, *sc, *lp, tokens, positions,
                         dlens, tables, *srows, *arow)
            step_name = "engine_verify_step"
        else:
            step_args = (state, kp, vp, *sc, *lp, tokens, positions,
                         tables, *srows, *arow)
            step_name = "engine_decode_step"
        programs.append(_trace_one(
            step_name, config, eng._decode_pure, eng._decode,
            step_args, mp, L,
            declared=_declared_specs(eng, step_args, kv, lora,
                                     eng._decode_n_out),
            geometry=_geometry(eng, L, S * (K + 1))))
        # the prefill chunk and the COW copy are backend- and
        # K-invariant today (paged_prefill_chunk has no backend seam;
        # the decode/verify steps are where the backends diverge), so
        # they harvest ONCE per (mp, kv_dtype, lora) — if a prefill
        # backend ever grows, widen this to the full config string.
        # The COW copy is adapter-oblivious, so the lora configs skip
        # it (no duplicate baseline entry).
        if K == 0 and backend == "dense":
            arow1 = (jnp.asarray(np.zeros(1, np.int32)),) if lora \
                else ()
            srows1 = samp_rows(1) if samp else ()
            chunk_tokens = jnp.asarray(np.zeros((1, C), np.int32))
            row = jnp.asarray(np.zeros(MB, np.int32))
            pc_args = (state, kp, vp, *sc, *lp, chunk_tokens,
                       jnp.int32(0), jnp.int32(TINY["block_size"] + 1),
                       row, *srows1, *arow1)
            programs.append(_trace_one(
                "engine_prefill_chunk", f"mp={mp}{tag}",
                eng._prefill_pure, eng._prefill, pc_args, mp, L,
                declared=_declared_specs(eng, pc_args, kv, lora, 1),
                geometry=_geometry(eng, L, C)))
            if not lora and not samp:
                # the COW copy is adapter- AND sampling-oblivious:
                # both config families skip it (no duplicate entry)
                cow_args = (kp, vp, jnp.int32(1), jnp.int32(2), *sc)
                if mp > 1:
                    # plain jit, not shard_map — but the pools ride
                    # committed at pool_pspec() and the jit pins its
                    # out_shardings, so the declared truth is the same
                    pool = tuple(eng.cache.pool_pspec())
                    tail = (((),) if kv else ())
                    cow_declared = ((pool, pool, None, None) + tail,
                                    (pool, pool) + tail)
                else:
                    cow_declared = (None, None)
                programs.append(_trace_one(
                    "engine_cow_copy", f"mp={mp}{tag}", eng._cow_pure,
                    eng._cow, cow_args, mp, L,
                    declared=cow_declared,
                    geometry=_geometry(eng, L, 0)))
    if include_conv:
        programs.extend(_conv_programs())
    return programs


def _conv_programs():
    """The fused Pallas conv suite's programs (ops/pallas/conv.py):
    one tiny-but-real jitted instance per kernel family x stride,
    interpret-mode on CPU like the pallas attention configs. Not part
    of the engine matrix — they ride the DEFAULT harvest only, so a
    test harvesting a restricted engine matrix sees exactly what it
    asked for."""
    from paddle_tpu.ops.pallas import conv as pallas_conv

    return [_trace_one(name, config, pure, jitted, args, 1, 1)
            for name, config, pure, jitted, args
            in pallas_conv.harvest_programs()]


# ---------------------------------------------------------------------------
# drift snapshot (TRACE_BASELINE.json / TPU100)
# ---------------------------------------------------------------------------

def snapshot_of(programs):
    """program key -> per-step op/collective/byte counts, the unit of
    the committed drift baseline."""
    out = {}
    for p in programs:
        out[p.key] = {
            "ops": {k: p.ops[k] for k in sorted(p.ops)},
            "collectives": dict(sorted(p.collectives.items())),
            "const_bytes": p.const_bytes,
            "donated_aliases":
                p.lowered_text.count("tf.aliasing_output"),
        }
    return out


def load_trace_baseline(path):
    with open(path) as f:
        data = json.load(f)
    return data.get("programs", data)


def write_trace_baseline(path, programs):
    with open(path, "w") as f:
        json.dump({"version": 1, "programs": snapshot_of(programs)},
                  f, indent=1, sort_keys=True)
        f.write("\n")
    return len(programs)


def _diff_counts(cur, base):
    """Short human summary of what drifted."""
    bits = []
    for field in ("const_bytes", "donated_aliases"):
        if cur[field] != base.get(field):
            bits.append(f"{field} {base.get(field)} -> {cur[field]}")
    for field in ("collectives", "ops"):
        c, b = cur[field], base.get(field, {})
        for k in sorted(set(c) | set(b)):
            if c.get(k, 0) != b.get(k, 0):
                bits.append(f"{k} {b.get(k, 0)} -> {c.get(k, 0)}")
    return "; ".join(bits[:6]) + (" ..." if len(bits) > 6 else "")


def compare_snapshot(programs, baseline):
    """-> (drift findings [TPU100], stale baseline keys). Exact-match
    comparison: ANY change in a program's op/collective/byte counts
    fails loudly until --write-trace-baseline re-snapshots it and the
    diff is reviewed."""
    current = snapshot_of(programs)
    by_key = {p.key: p for p in programs}
    findings = []
    for key in sorted(current):
        prog = by_key[key]
        if key not in baseline:
            findings.append(Finding(
                rule="TPU100", path=prog.contract.declared_at, line=1,
                col=0, qualname=prog.contract.name, source=prog.config,
                message=f"program {key} has no TRACE_BASELINE.json "
                        "entry — run tools/tpu_verify.py "
                        "--write-trace-baseline and review the "
                        "snapshot"))
        elif current[key] != baseline[key]:
            findings.append(Finding(
                rule="TPU100", path=prog.contract.declared_at, line=1,
                col=0, qualname=prog.contract.name, source=prog.config,
                message=f"program {key} drifted from "
                        "TRACE_BASELINE.json: "
                        f"{_diff_counts(current[key], baseline[key])}"
                        " — intentional? re-snapshot with "
                        "--write-trace-baseline"))
    stale = sorted(set(baseline) - set(current))
    return findings, stale


# ---------------------------------------------------------------------------
# the full check
# ---------------------------------------------------------------------------

class TraceResult:
    """Mirror of analysis.Result for the trace tier."""

    def __init__(self):
        self.findings = []
        self.programs = []
        self.stale_baseline = []        # findings-baseline ids
        self.stale_trace_baseline = []  # snapshot keys

    def new_findings(self):
        return [f for f in self.findings
                if not f.suppressed and not f.baselined]

    def per_rule_counts(self):
        from .rules import all_trace_rule_ids

        out = {r: 0 for r in all_trace_rule_ids()}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out


def apply_findings_baseline(res, baseline):
    """Apply a findings baseline to a TraceResult — EXCEPT TPU100:
    a drift finding's stable ID hashes the program key, not the drift
    content, so one grandfathered entry would silently mask every
    FUTURE drift of that program too. Drift has its own reviewed
    acceptance mechanism (--write-trace-baseline); a baseline entry
    matching a TPU100 id is surfaced as stale instead of honored."""
    from ..baseline import apply_baseline

    return apply_baseline(
        [f for f in res.findings if f.rule != "TPU100"], baseline)


def verify_matrix(matrix=None, baseline=None, trace_baseline="auto"):
    """Harvest the matrix and run every rule + the drift comparison.

    `baseline` is a loaded findings baseline ({id: entry}, see
    analysis.baseline) or None; `trace_baseline` is a path, a loaded
    snapshot dict, "auto" (the committed TRACE_BASELINE.json when
    present) or None to skip drift checking."""
    res = TraceResult()
    res.programs = harvest(matrix)
    for prog in res.programs:
        res.findings.extend(check_program(prog))
    if trace_baseline == "auto":
        trace_baseline = DEFAULT_TRACE_BASELINE \
            if os.path.exists(DEFAULT_TRACE_BASELINE) else None
    if isinstance(trace_baseline, str):
        trace_baseline = load_trace_baseline(trace_baseline)
    if trace_baseline is not None:
        drift, res.stale_trace_baseline = compare_snapshot(
            res.programs, trace_baseline)
        res.findings.extend(drift)
    assign_ids(res.findings)
    if baseline:
        res.stale_baseline = apply_findings_baseline(res, baseline)
    res.findings.sort(key=lambda f: (f.path, f.qualname, f.source,
                                     f.rule))
    return res
