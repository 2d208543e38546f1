"""tpu-shard — static sharding-layout & per-axis collective-byte
analysis.

The fourth analysis tier (TPU3xx): tpu-lint (`paddle_tpu.analysis`,
AST trace-safety), tpu-verify (`analysis.trace`, jaxpr contracts) and
tpu-race (`analysis.race`, host concurrency) check what programs DO;
this package checks where their data LIVES and what the mesh MOVES —
every collective in every harvested program classified by mesh axis
with its moved bytes computed from operand shapes/dtypes and checked
against the `jit.introspect.AxisCollectiveBudget` table, and every
declared PartitionSpec (`_tp_specs`, `pool_pspec()`, the adapter
pool's `pool_pspecs()`) compared against the lowered module's actual
`sdy.sharding` attributes. It is the readiness gate for the pp/DCN
mesh axis of ROADMAP item 1: per-axis byte totals are drift-pinned in
`SHARD_BASELINE.json` (TPU300), and the DCN-hostile rule (TPU305) is
armed before the slow axis exists. `verify_shards` is the in-process
API the tier-1 gate uses; `tools/tpu_shard.py` is the CLI.

LAZY package init (PEP 562), like the sibling tiers: nothing here
loads until analysis actually runs, and importing it never
initializes a JAX backend (the model walks jaxprs by duck typing and
parses lowered StableHLO text — no jax import anywhere in the tier).
"""
from __future__ import annotations

_EXPORTS = {
    "model": ("ShardRecord", "CollectiveSite", "build_record",
              "parse_main_shardings", "eval_payload",
              "LARGE_BUFFER_BYTES"),
    "rules": ("SHARD_RULES", "all_shard_rule_ids", "check_record"),
    "core": ("ShardResult", "analyze_programs", "verify_shards",
             "snapshot_of", "load_shard_baseline",
             "write_shard_baseline", "compare_snapshot",
             "load_baseline", "apply_baseline", "write_baseline",
             "BaselineError", "SUPPRESS_TAG", "Finding",
             "DEFAULT_SHARD_BASELINE", "_REPO_ROOT"),
    "cli": ("main", "DEFAULT_BASELINE"),
}

__all__ = sorted(n for names in _EXPORTS.values() for n in names
                 if not n.startswith("_"))

_WHENCE = {name: mod for mod, names in _EXPORTS.items()
           for name in names}


def __getattr__(name):
    mod = _WHENCE.get(name)
    if mod is not None:
        import importlib

        return getattr(
            importlib.import_module(f".{mod}", __name__), name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_WHENCE))
