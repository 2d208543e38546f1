"""Sharding-layout model for tpu-shard: the parsed view of ONE
harvested program the TPU3xx rules consume.

Three extraction passes over a `TracedProgram` (the tpu-verify
harvest record — tpu-shard deliberately harvests NOTHING itself):

- `parse_main_shardings` reads the lowered StableHLO module's
  `@main` signature and returns, per argument and per result, the
  tensor shape/dtype and the `sdy.sharding` attribute decoded to
  per-dim partition COUNTS — the form actually compiled, which is why
  the rules run on lowered shardings and not on source PartitionSpecs
  (a pspec the lowering dropped is exactly the bug class TPU302/303
  exist to catch).
- `collect_sites` walks the jaxpr (duck-typed, recursively — shard_map
  and loop bodies included) and captures every mesh collective as a
  `CollectiveSite`: kind, axes crossed, per-shard and global payload
  bytes, and whether it sits inside an on-device loop body.
- `eval_payload` evaluates an `AxisCollectiveBudget` payload-bound
  expression over the program's harvest geometry.

No jax import anywhere (the import-smoke contract shared with the
sibling tiers): jaxprs are walked by duck typing and the lowered
module is plain text.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from functools import cached_property

from ..trace.contracts import CollectiveBudget, resolve_budget
from ..trace.rules import COLLECTIVE_PRIMS

#: TPU302 threshold: a buffer at least this large that lowers
#: replicated where the declared layout says sharded is a real
#: HBM-doubling (weights, KV pool planes, adapter pages); smaller
#: leaves (biases, norm scales, scalar rows) replicate by design.
LARGE_BUFFER_BYTES = 1024

#: Primitives whose sub-jaxpr params are ON-DEVICE LOOP BODIES — a
#: collective inside one runs per iteration, not per dispatch
#: (TPU305's latency multiplier).
_LOOP_PRIMS = frozenset({"while", "scan"})

#: Collective kinds whose logical (global) payload is the GATHERED
#: output; every other kind's global payload is its operand.
_GATHER_KINDS = frozenset({"all_gather", "pgather"})

_ITEMSIZE = {
    "pred": 1, "i1": 1, "i4": 1, "ui4": 1, "i8": 1, "ui8": 1,
    "f8E4M3FN": 1, "f8E5M2": 1, "i16": 2, "ui16": 2, "f16": 2,
    "bf16": 2, "i32": 4, "ui32": 4, "f32": 4, "i64": 8, "ui64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

_MAIN_RE = re.compile(
    r"func\.func\s+(?:public\s+)?@main\((?P<args>.*?)\)\s*->\s*"
    r"(?:\((?P<res>.*?)\)|(?P<res1>tensor<[^>]*>))\s*"
    r"(?:attributes\b[^{]*)?\{", re.S)
_TENSOR_RE = re.compile(r"tensor<([0-9x]*)([A-Za-z][A-Za-z0-9]*)>")
# Shardy, the partitioner of the installed JAX: the module declares
# `sdy.mesh @mesh = <["mp"=2]>` and each sharded value carries
# `sdy.sharding = #sdy.sharding<@mesh, [{}, {"mp"}]>` — one `{...}`
# per tensor dim listing the mesh axes that split it
_MESH_RE = re.compile(r"sdy\.mesh\s+@(\w+)\s*=\s*<\[([^\]]*)\]")
_AXIS_RE = re.compile(r'"([^"]+)"\s*=\s*(\d+)')
_SHARDING_RE = re.compile(
    r"sdy\.sharding\s*=\s*#sdy\.sharding<@(\w+),\s*\[([^\]]*)\]")
_DIM_RE = re.compile(r"\{([^}]*)\}")
_NAME_RE = re.compile(r'"([^"]+)"')


class ShardParseError(ValueError):
    """The lowered module's @main signature did not parse — reported
    as a TPU300 finding by the caller, never silently skipped."""


def _itemsize(dtype):
    return _ITEMSIZE.get(dtype, 4)


def _parse_tensor(text):
    """-> (shape tuple, dtype str, nbytes) from one `tensor<...>`."""
    m = _TENSOR_RE.search(text)
    if m is None:
        raise ShardParseError(f"no tensor type in {text[:80]!r}")
    dims, dtype = m.group(1), m.group(2)
    shape = tuple(int(d) for d in dims.split("x") if d)
    n = _itemsize(dtype)
    for d in shape:
        n *= d
    return shape, dtype, n


def _parse_meshes(lowered_text):
    """-> {mesh name: {axis name: size}} from the module's `sdy.mesh`
    declarations."""
    return {m.group(1): {a: int(n) for a, n in
                         _AXIS_RE.findall(m.group(2))}
            for m in _MESH_RE.finditer(lowered_text)}


def _parse_sharding(text, meshes):
    """Decode one `sdy.sharding` attribute to per-dim partition
    counts: () = replicated/maximal, (1, 1, 1, 2, 1) = dim 3 split in
    two. None when the entry carries no sharding attribute at all
    (unspecified — jit chose; host args look like this)."""
    m = _SHARDING_RE.search(text)
    if m is None:
        return None
    sizes = meshes.get(m.group(1), {})
    counts = []
    for dim in _DIM_RE.findall(m.group(2)):
        n = 1
        for axis in _NAME_RE.findall(dim):
            if axis not in sizes:
                raise ShardParseError(
                    f"sharding names axis {axis!r} of an undeclared "
                    f"mesh @{m.group(1)}")
            n *= sizes[axis]
        counts.append(n)
    return tuple(counts) if any(c > 1 for c in counts) else ()


def parse_main_shardings(lowered_text):
    """-> (args, results): two lists of (shape, dtype, nbytes,
    partition_counts) tuples for the lowered module's @main
    signature. Raises ShardParseError when the signature is missing
    or malformed."""
    m = _MAIN_RE.search(lowered_text)
    if m is None:
        raise ShardParseError("no @main signature in lowered module")
    meshes = _parse_meshes(lowered_text)
    args = []
    arg_text = m.group("args").strip()
    if arg_text:
        for part in re.split(r",\s*(?=%arg\d+\s*:)", arg_text):
            shape, dtype, nbytes = _parse_tensor(part)
            args.append((shape, dtype, nbytes,
                         _parse_sharding(part, meshes)))
    results = []
    res_text = (m.group("res") or m.group("res1") or "").strip()
    if res_text:
        for part in re.split(r",\s*(?=tensor<)", res_text):
            shape, dtype, nbytes = _parse_tensor(part)
            results.append((shape, dtype, nbytes,
                            _parse_sharding(part, meshes)))
    return args, results


# ---------------------------------------------------------------------------
# collective sites (duck-typed jaxpr walk; no jax import)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CollectiveSite:
    """One mesh-collective equation in a harvested program."""

    kind: str                # primitive name (all_gather, psum, ...)
    axes: tuple              # mesh axis names it crosses
    axis_size: int           # total participants across those axes
    shard_bytes: int         # per-participant operand bytes
    global_bytes: int        # logical payload (gathered out / operand)
    in_loop: bool            # inside an on-device while/scan body

    @property
    def moved_bytes(self):
        """Wire-cost proxy: bytes each participant RECEIVES from its
        peers (the ring lower bound) — shard payload x (axis_size-1)
        for gathers and reductions alike; see DESIGN_DECISIONS r23."""
        return self.shard_bytes * max(self.axis_size - 1, 0)


def _aval_bytes(var):
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = getattr(getattr(aval, "dtype", None), "itemsize", 4)
    for d in shape:
        n *= int(d)
    return n


def _site_axes(params):
    names = params.get("axis_name", params.get("axes", ()))
    if not isinstance(names, (tuple, list)):
        names = (names,)
    return tuple(n for n in names if isinstance(n, str))


def _inner(obj):
    if hasattr(obj, "eqns"):
        return obj
    inner = getattr(obj, "jaxpr", None)
    return inner if hasattr(inner, "eqns") else None


def collect_sites(jaxpr, axis_sizes):
    """Every CollectiveSite in `jaxpr`, recursing into sub-jaxprs
    (shard_map bodies, loop bodies — marked `in_loop` below a
    while/scan). `axis_sizes` maps mesh axis name -> size; a gather's
    own `axis_size` param wins when present."""
    sites = []

    def walk(closed, in_loop):
        top = _inner(closed)
        if top is None:
            return
        for eqn in top.eqns:
            name = eqn.primitive.name
            if name in COLLECTIVE_PRIMS:
                axes = _site_axes(eqn.params)
                size = eqn.params.get("axis_size")
                if size is None:
                    size = 1
                    for a in axes:
                        size *= int(axis_sizes.get(a, 1))
                shard = sum(_aval_bytes(v) for v in eqn.invars)
                if name in _GATHER_KINDS:
                    glob = sum(_aval_bytes(v) for v in eqn.outvars)
                else:
                    glob = shard
                sites.append(CollectiveSite(
                    kind=name, axes=axes, axis_size=int(size),
                    shard_bytes=shard, global_bytes=glob,
                    in_loop=in_loop))
            below = in_loop or name in _LOOP_PRIMS
            for v in eqn.params.values():
                vs = v if isinstance(v, (list, tuple)) else (v,)
                for sub in vs:
                    if _inner(sub) is not None:
                        walk(sub, below)

    walk(jaxpr, False)
    return sites


# ---------------------------------------------------------------------------
# payload-bound expressions
# ---------------------------------------------------------------------------

_EXPR_RE = re.compile(r"^[\sa-z_0-9*+\-/()]*$")


def eval_payload(expr, geometry):
    """Evaluate one AxisCollectiveBudget payload-bound expression
    (bytes) over the harvest geometry symbols. The grammar is plain
    integer arithmetic over [a-z_] symbols — anything else is a
    declaration error, not code execution."""
    if not _EXPR_RE.match(expr):
        raise ValueError(f"bad payload expression {expr!r}")
    try:
        val = eval(expr, {"__builtins__": {}}, dict(geometry))
    except Exception as e:
        raise ValueError(
            f"payload expression {expr!r} does not evaluate over "
            f"geometry {sorted(geometry)}: {e}") from e
    return int(val)


# ---------------------------------------------------------------------------
# the record
# ---------------------------------------------------------------------------

@dataclass
class ShardRecord:
    """One harvested program, parsed for the TPU3xx rules. Wraps the
    tpu-verify TracedProgram (`prog`) — same contract anchor, same
    config key, so a finding's stable ID matches across tiers'
    conventions."""

    prog: object                     # trace.rules.TracedProgram
    axis_sizes: dict = field(default_factory=dict)
    parse_error: str = ""

    def __post_init__(self):
        if not self.axis_sizes:
            self.axis_sizes = {"mp": self.prog.mp}

    @property
    def key(self):
        return self.prog.key

    @property
    def contract(self):
        return self.prog.contract

    @property
    def sharded(self):
        """Any mesh axis with more than one participant?"""
        return any(int(s) > 1 for s in self.axis_sizes.values())

    @cached_property
    def budget(self):
        """The contract's resolved budget — axis/byte checks need the
        AxisCollectiveBudget form; a legacy count-only
        CollectiveBudget declares NO axes (every collective is then an
        undeclared resharding, which is the point: the per-axis gate
        requires the per-axis table)."""
        return resolve_budget(self.contract)

    @property
    def axis_budget(self):
        b = self.budget
        return None if isinstance(b, CollectiveBudget) else b

    @cached_property
    def sites(self):
        return collect_sites(self.prog.jaxpr, self.axis_sizes)

    @cached_property
    def _signature(self):
        try:
            return parse_main_shardings(self.prog.lowered_text)
        except ShardParseError as e:
            # surfaced by core.analyze_programs as a TPU300 finding
            self.parse_error = str(e)
            return [], []

    @property
    def lowered_in(self):
        return self._signature[0]

    @property
    def lowered_out(self):
        return self._signature[1]

    def declared_vs_lowered(self):
        """-> [(side, index, declared, lowered, nbytes)] pairing every
        DECLARED leaf layout with the lowered signature entry at the
        same position (inputs then outputs). Leaves with no
        declaration (None — host args) are skipped; a declared leaf
        beyond the lowered signature pairs with lowered=None."""
        out = []
        for side, declared, lowered in (
                ("in", self.prog.declared_in_specs, self.lowered_in),
                ("out", self.prog.declared_out_specs,
                 self.lowered_out)):
            if declared is None:
                continue
            for i, spec in enumerate(declared):
                if spec is None:
                    continue
                low = lowered[i] if i < len(lowered) else None
                counts = low[3] if low is not None else None
                nbytes = low[2] if low is not None else 0
                out.append((side, i, spec, counts, nbytes))
        return out

    def expected_counts(self, spec, ndim):
        """Partition counts a declared per-dim axis-name tuple demands
        of the lowered sharding, padded to the leaf's rank; () for a
        declared-replicated leaf."""
        counts = []
        for k in range(ndim):
            axis = spec[k] if k < len(spec) else None
            if axis is None:
                counts.append(1)
            else:
                axes = axis if isinstance(axis, tuple) else (axis,)
                n = 1
                for a in axes:
                    n *= int(self.axis_sizes.get(a, 1))
                counts.append(n)
        return tuple(counts) if any(c > 1 for c in counts) else ()

    @cached_property
    def axis_totals(self):
        """{axis: {kind: {"count": n, "moved_bytes": b}}} — the unit
        of the SHARD_BASELINE.json drift snapshot. Collectives that
        lower away at axis size 1 contribute nothing (mp=1 programs
        have no collectives to begin with)."""
        totals = {}
        for s in self.sites:
            for axis in s.axes:
                per = totals.setdefault(axis, {}).setdefault(
                    s.kind, {"count": 0, "moved_bytes": 0})
                per["count"] += 1
                per["moved_bytes"] += s.moved_bytes
        return totals


def build_record(prog, axis_sizes=None):
    return ShardRecord(prog=prog, axis_sizes=dict(axis_sizes or {}))
