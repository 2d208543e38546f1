"""tpu-race analysis model: per-module concurrency facts.

Builds on the tpu-lint `ModuleAnalysis` (alias resolution, scope tree,
jit-reachability) and adds the three fact tables the TPU2xx rules
consume:

1. **Thread escape** — which local callables can run on a helper
   thread, seeded at `threading.Thread(target=...)` / executor
   `.submit(fn, ...)` call sites (`introspect.THREAD_SPAWN_CALLS` /
   `EXECUTOR_SUBMIT_METHODS`) and propagated through module-local
   calls — the same worklist shape as tpu-lint's traced-ness pass C.
2. **Lock sets** — which attribute / module names are locks (assigned
   from `introspect.LOCK_CONSTRUCTORS`, or from a value whose own
   name says lock), and for every attribute/global access, which
   locks are lexically held (`with <lock>:` regions) or asserted held
   by the caller via a same-line `# guarded-by: <lock>` annotation.
3. **Pipeline effects** — the ordered dispatch / complete / release
   effect trace of every function, from introspect's
   `ENGINE_DISPATCH_EFFECTS` / `STEP_COMPLETE_CALLS` /
   `ALLOCATOR_RELEASE_EFFECTS` tables (the ENGINE_STEP_DONATION
   precedent: the engine declares its effect surfaces, the analyzer
   reads them). Module-local calls are spliced into the caller's
   trace, loop bodies replay twice (loop-carried dispatches — the
   depth-2 pipe shape), so TPU203 can walk "is an allocator release
   reachable between a dispatch and its completion" per function.
   A dispatch makes a RECORD, and the trace says which record a wait
   completes and from which record a release draws its lanes (`bind`
   effects follow the names a record is held under through
   assignments, loop targets and the parameters of spliced calls):
   the engine's ahead order waits for step N and releases step N's
   lanes while step N+1 is outstanding, and that is sound; a release
   drawn from a record that is still outstanding is not.

Everything is name-based and module-local, like tpu-lint: locks are
keyed by their attribute/global NAME (one lock reached through two
names reads as two locks), threads crossing module boundaries are
invisible, and a lock held by a CALLER is invisible unless the access
line says `# guarded-by: <lock>`. The effect walk models `if` as a
fork: each arm starts from the pre-branch state, the merge is
pessimistic (a dispatch left outstanding on EITHER arm stays
outstanding), and an arm that ends in return/raise/break/continue
contributes nothing to the fall-through state — so an early-return
guard (`if x is None: return`) is the complete-guard idiom the
analyzer understands, while a wrapping `if x is not None: wait(x)`
reads as "may not complete". DESIGN_DECISIONS r22 records the full
false-negative boundary.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass

from paddle_tpu.jit import introspect as I

from ..engine import ModuleAnalysis

#: `# guarded-by: _lock` — asserts the named lock is held by every
#: caller when this line executes; the analyzer treats accesses on the
#: line as performed under that lock.
_GUARD_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z_]\w*)")

#: Method calls that mutate their receiver in place — a
#: `self._ring.append(...)` is a WRITE to `_ring` for lock-discipline
#: purposes.
_MUTATOR_METHODS = frozenset({
    "append", "appendleft", "extend", "extendleft", "add", "insert",
    "remove", "discard", "pop", "popleft", "popitem", "clear",
    "update", "setdefault", "put", "put_nowait", "sort", "reverse",
})

#: Constructors whose instances synchronize internally — accesses to
#: an attribute assigned from one of these are exempt from the shared
#: -mutable rules (queue/Event/lock objects guard themselves;
#: threading.local confines by construction).
_SYNCHRONIZED_TYPES = frozenset(
    I.BLOCKING_RECEIVER_TYPES
    + I.THREAD_LOCAL_CONSTRUCTORS
    + I.LOCK_CONSTRUCTORS
)

#: Constructor/initializer method names whose writes are
#: pre-concurrency by convention (no helper thread exists yet).
CTOR_NAMES = frozenset({"__init__", "__new__", "__post_init__"})


#: Builtins whose result is made of their arguments' items: a loop over
#: `zip(inflight.runnable, inflight.slots)` draws from `inflight`.
_PASS_THROUGH_CALLS = frozenset({
    "zip", "enumerate", "list", "tuple", "reversed", "sorted", "iter"})


def _root_key(e):
    """The name an expression is drawn from: `inflight` for
    `inflight.slots[0].blocks`, `self._inflight` for
    `self._inflight.out`, the first argument with one for `zip(...)` /
    `[src]`; None where there is none."""
    while True:
        if isinstance(e, ast.Name):
            return e.id
        if isinstance(e, ast.Attribute):
            if isinstance(e.value, ast.Name) and \
                    e.value.id in ("self", "cls"):
                return "self." + e.attr
            e = e.value
        elif isinstance(e, (ast.Subscript, ast.Starred)):
            e = e.value
        elif isinstance(e, (ast.List, ast.Tuple)) or (
                isinstance(e, ast.Call) and isinstance(e.func, ast.Name)
                and e.func.id in _PASS_THROUGH_CALLS):
            items = e.elts if not isinstance(e, ast.Call) else e.args
            for item in items:
                key = _root_key(item)
                if key is not None:
                    return key
            return None
        else:
            return None


def _target_keys(t):
    """Names an assignment or loop target binds (`x`, `self.x`)."""
    if isinstance(t, ast.Name):
        return [t.id]
    if isinstance(t, ast.Attribute):
        return ["self." + t.attr] if isinstance(t.value, ast.Name) \
            and t.value.id in ("self", "cls") else []
    if isinstance(t, (ast.Tuple, ast.List)):
        return [k for e in t.elts for k in _target_keys(e)]
    if isinstance(t, ast.Starred):
        return _target_keys(t.value)
    return []


def _diverges(stmts):
    """True when a statement list ends by leaving the enclosing path
    (return/raise/break/continue) — such a branch contributes nothing
    to the fall-through state at an effect-walk merge point."""
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Raise, ast.Break, ast.Continue))


@dataclass
class Access:
    key: tuple          # ("self", class_name, attr) | ("global", name)
    kind: str           # "read" | "write"
    node: object
    fi: object
    locks: frozenset    # lock names held (incl. guarded-by asserts)
    in_thread: bool

    def name(self):
        return f"self.{self.key[2]}" if self.key[0] == "self" \
            else self.key[1]


class RaceModuleAnalysis(ModuleAnalysis):
    """ModuleAnalysis + the concurrency fact tables above."""

    def __init__(self, path, src, module_name=None):
        super().__init__(path, src, module_name=module_name)
        self.guard_annotations = self._parse_guards(src)
        self._release_attrs = frozenset(
            a for attrs in sorted(I.ALLOCATOR_RELEASE_EFFECTS.values())
            for a in attrs)
        self._dispatch_attrs = frozenset(I.ENGINE_DISPATCH_EFFECTS)
        self._complete_calls = frozenset(I.STEP_COMPLETE_CALLS)
        # names are followed only where a record can be made at all
        self._tracks_records = any(a in src for a in self._dispatch_attrs)
        self._collect_name_types()
        self._collect_thread_reachable()
        self.accesses = []
        self.blocking_under_lock = []  # (node, fi, lock, what)
        self.spawn_sites = []          # (node, fi) — thread starts
        self.effects = {}              # id(fi) -> [(kind, node, detail)]
        self._effect_memo = {}
        for fi in self.functions:
            _FnWalker(self, fi).run()

    # -- source annotations ------------------------------------------------

    @staticmethod
    def _parse_guards(src):
        out = {}
        for n, text in enumerate(src.splitlines(), start=1):
            m = _GUARD_RE.search(text)
            if m:
                out[n] = m.group(1)
        return out

    # -- lock / synchronized / mutable-global name tables ------------------

    @staticmethod
    def _binding_name(target):
        """Leaf name a lock/local/queue binding lives under: `x`,
        `self.x`, or the dict in `LOCKS[k] = threading.Lock()`."""
        if isinstance(target, ast.Name):
            return target.id
        if isinstance(target, ast.Attribute):
            return target.attr
        if isinstance(target, ast.Subscript):
            return RaceModuleAnalysis._binding_name(target.value)
        return None

    def _collect_name_types(self):
        self.lock_names = set()
        self.threadlocal_names = set()
        self.sync_names = set()
        self.name_types = {}       # leaf name -> set of canonical ctors
        self.mutable_globals = set()
        for node in ast.walk(self.tree):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            value = getattr(node, "value", None)
            if value is None:
                continue
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            names = sorted(n for n in (self._binding_name(t)
                                       for t in targets) if n)
            ctor = self.resolve(value.func) \
                if isinstance(value, ast.Call) else None
            leaf = value.id if isinstance(value, ast.Name) else (
                value.attr if isinstance(value, ast.Attribute) else None)
            for name in names:
                if ctor:
                    self.name_types.setdefault(name, set()).add(ctor)
                if ctor in I.LOCK_CONSTRUCTORS or (
                        leaf is not None and "lock" in leaf.lower()):
                    self.lock_names.add(name)
                if ctor in I.THREAD_LOCAL_CONSTRUCTORS:
                    self.threadlocal_names.add(name)
                if ctor in _SYNCHRONIZED_TYPES:
                    self.sync_names.add(name)
        # module-level mutable bindings (for global-write tracking)
        for node in self.module_fn.nodes:
            if isinstance(node, (ast.Assign, ast.AugAssign,
                                 ast.AnnAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    if isinstance(t, ast.Name):
                        self.mutable_globals.add(t.id)

    # -- thread escape -----------------------------------------------------

    def _collect_thread_reachable(self):
        self.thread_reachable = set()   # id(FuncInfo)
        self._thread_work = []

        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            owner = getattr(node, "_tl_owner", self.module_fn)
            fname = self.resolve(node.func)
            spec = I.THREAD_SPAWN_CALLS.get(fname)
            if spec is not None:
                kw_name, pos = spec
                target = None
                for kw in node.keywords:
                    if kw.arg == kw_name:
                        target = kw.value
                if target is None and len(node.args) > pos:
                    target = node.args[pos]
                if target is not None:
                    self._seed_thread_callable(target, owner)
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr in I.EXECUTOR_SUBMIT_METHODS and \
                    node.args:
                self._seed_thread_callable(node.args[0], owner)

        # propagation: module-local callees of thread code run on the
        # thread too (pass-C shape of the traced-ness fixpoint)
        while self._thread_work:
            fi = self._thread_work.pop()
            for node in fi.nodes:
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if isinstance(f, ast.Name):
                    callee = fi.lookup(f.id)
                    if callee is not None:
                        self._mark_thread(callee)
                elif isinstance(f, ast.Attribute) and \
                        isinstance(f.value, ast.Name) and \
                        f.value.id in ("self", "cls") and fi.class_name:
                    for cand in self._by_simple_name.get(f.attr, []):
                        if cand.class_name == fi.class_name:
                            self._mark_thread(cand)

    def _mark_thread(self, fi):
        if fi is None or id(fi) in self.thread_reachable:
            return
        self.thread_reachable.add(id(fi))
        self._thread_work.append(fi)

    def _seed_thread_callable(self, expr, owner):
        if isinstance(expr, ast.Name):
            self._mark_thread(owner.lookup(expr.id))
        elif isinstance(expr, ast.Lambda):
            self._mark_thread(getattr(expr, "_tl_func", None))
        elif isinstance(expr, ast.Attribute):
            cands = self._by_simple_name.get(expr.attr, [])
            for c in [c for c in cands if c.class_name] or cands:
                self._mark_thread(c)

    def is_thread_reachable(self, fi):
        return id(fi) in self.thread_reachable

    # -- effect sequences (TPU203) -----------------------------------------

    def effect_seq(self, fi, _stack=None):
        """Flattened ordered effect trace of `fi`: module-local calls
        inlined (effects re-anchored at the call site in `fi`, between
        an `enter` that maps the callee's parameters to the names the
        caller passed and an `exit`), cycles cut. Entries are (kind,
        node, detail) with kind in dispatch/complete/release (`detail`
        of the last two: the call and the name its argument is drawn
        from), `bind` (`detail`: (target, source) pairs), plus the
        structural fork/alt/join markers (always balanced; `detail` on
        alt/join is the diverged flag of the arm just closed)."""
        if id(fi) in self._effect_memo:
            return self._effect_memo[id(fi)]
        stack = _stack if _stack is not None else set()
        if id(fi) in stack:
            return []
        stack.add(id(fi))
        out = []
        for kind, node, detail in self.effects.get(id(fi), []):
            if kind == "call":
                callee, argmap = detail
                out.append(("enter", node, argmap))
                for k2, _n2, d2 in self.effect_seq(callee, stack):
                    out.append((k2, node, d2))
                out.append(("exit", node, None))
            else:
                out.append((kind, node, detail))
        stack.discard(id(fi))
        if not stack:
            self._effect_memo[id(fi)] = out
        return out


class _FnWalker:
    """One function's lexical walk: lock-region stack, access
    recording, blocking-call sites, and the raw effect list."""

    def __init__(self, race, fi):
        self.r = race
        self.fi = fi
        self.held = []                 # stack of held lock names
        self.in_thread = race.is_thread_reachable(fi)
        self.effects = []
        self._seen_access = {}         # id(node) -> Access (replay dedupe)
        self._seen_blocking = set()

    def run(self):
        node = self.fi.node
        if isinstance(node, ast.Lambda):
            self.scan(node.body)
        else:
            self.block(getattr(node, "body", []))
        self.r.effects[id(self.fi)] = self.effects

    # -- statements --------------------------------------------------------

    def block(self, stmts):
        for s in stmts:
            self.stmt(s)

    def stmt(self, s):
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return                     # separate FuncInfo walks it
        if isinstance(s, ast.ClassDef):
            self.block(s.body)
            return
        if isinstance(s, ast.Assign):
            self.scan(s.value)
            for t in s.targets:
                self.write_target(t)
                self.bind(s, t, s.value)
        elif isinstance(s, ast.AnnAssign):
            if s.value is not None:
                self.scan(s.value)
                self.write_target(s.target)
        elif isinstance(s, ast.AugAssign):
            self.scan(s.value)
            self.scan(s.target)        # read half of the update
            self.write_target(s.target)
        elif isinstance(s, ast.Expr):
            self.scan(s.value)
        elif isinstance(s, (ast.Return, ast.Raise, ast.Assert,
                            ast.Await)):
            for child in ast.iter_child_nodes(s):
                if isinstance(child, ast.expr):
                    self.scan(child)
        elif isinstance(s, ast.Delete):
            for t in s.targets:
                self.write_target(t)
        elif isinstance(s, ast.If):
            # exclusive arms: fork the TPU203 state machine so a
            # dispatch on one arm can't read as "outstanding" across
            # the other, and a diverging arm (return/raise/...) drops
            # out of the fall-through merge entirely
            self.scan(s.test)
            self.effects.append(("fork", s, None))
            self.block(s.body)
            self.effects.append(("alt", s, _diverges(s.body)))
            self.block(s.orelse)
            self.effects.append(("join", s, _diverges(s.orelse)))
        elif isinstance(s, (ast.For, ast.AsyncFor)):
            self.scan(s.iter)
            self.bind(s, s.target, s.iter, each=True)
            # replay the body: loop-carried dispatch/release ordering
            # (iteration N dispatches, N+1 releases) needs two passes
            self.block(s.body)
            self.block(s.body)
            self.block(s.orelse)
        elif isinstance(s, ast.While):
            self.scan(s.test)
            self.block(s.body)
            self.block(s.body)
            self.block(s.orelse)
        elif isinstance(s, (ast.With, ast.AsyncWith)):
            pushed = 0
            for item in s.items:
                self.scan(item.context_expr)
                lock = self.lock_leaf(item.context_expr)
                if lock is not None:
                    self.held.append(lock)
                    pushed += 1
            self.block(s.body)
            for _ in range(pushed):
                self.held.pop()
        elif isinstance(s, ast.Try):
            self.block(s.body)
            for h in s.handlers:
                # each handler is an OPTIONAL branch off the main
                # line (first arm = "no exception", no effects)
                self.effects.append(("fork", h, None))
                self.effects.append(("alt", h, False))
                self.block(h.body)
                self.effects.append(("join", h, _diverges(h.body)))
            self.block(s.orelse)
            self.block(s.finalbody)
        else:
            for child in ast.iter_child_nodes(s):
                if isinstance(child, ast.expr):
                    self.scan(child)

    def bind(self, stmt, target, value, each=False):
        """Record which names now hold what `value` is drawn from
        (`each`: every item of it, a loop's target). A dispatch call's
        own result is the record that call makes."""
        if not self.r._tracks_records:
            return
        if not each and isinstance(target, (ast.Tuple, ast.List)) \
                and isinstance(value, (ast.Tuple, ast.List)) \
                and len(target.elts) == len(value.elts):
            pairs = list(zip(target.elts, value.elts))
        else:
            pairs = [(target, value)]
        out = []
        for t, v in pairs:
            made = isinstance(v, ast.Call) \
                and isinstance(v.func, ast.Attribute) \
                and v.func.attr in self.r._dispatch_attrs
            source = ("dispatch", v) if made else _root_key(v)
            out.extend((key, source) for key in _target_keys(t))
        if out:
            self.effects.append(("bind", stmt, out))

    def lock_leaf(self, expr):
        """Lock name a `with <expr>:` guards, or None."""
        if isinstance(expr, ast.Name):
            return expr.id if expr.id in self.r.lock_names else None
        if isinstance(expr, ast.Attribute):
            return expr.attr if expr.attr in self.r.lock_names else None
        if isinstance(expr, ast.Subscript):
            return self.lock_leaf(expr.value)
        if isinstance(expr, ast.Call) and \
                isinstance(expr.func, ast.Name) and \
                expr.func.id == "getattr" and len(expr.args) >= 2 and \
                isinstance(expr.args[1], ast.Constant) and \
                isinstance(expr.args[1].value, str):
            # `with getattr(self, "_lock", threading.Lock()):` — the
            # defensive-attribute idiom still names the lock
            name = expr.args[1].value
            return name if name in self.r.lock_names else None
        return None

    # -- expressions -------------------------------------------------------

    def scan(self, e):
        if e is None or isinstance(e, ast.Lambda):
            return                     # lambda body is its own walk
        if isinstance(e, ast.Call):
            if isinstance(e.func, ast.Attribute):
                self.scan(e.func.value)
            for a in e.args:
                self.scan(a)
            for kw in e.keywords:
                self.scan(kw.value)
            self.handle_call(e)
            return
        if isinstance(e, ast.Attribute):
            self.record(e, "write" if isinstance(e.ctx, (ast.Store,
                                                         ast.Del))
                        else "read")
            self.scan(e.value)
            return
        if isinstance(e, ast.Name):
            if isinstance(e.ctx, ast.Load):
                self.record(e, "read")
            return
        for child in ast.iter_child_nodes(e):
            if isinstance(child, ast.expr):
                self.scan(child)
            elif isinstance(child, ast.comprehension):
                self.scan(child.iter)
                for cond in child.ifs:
                    self.scan(cond)
            elif isinstance(child, ast.keyword):
                self.scan(child.value)

    def write_target(self, t):
        if isinstance(t, ast.Attribute):
            self.record(t, "write")
            self.scan(t.value)
        elif isinstance(t, ast.Subscript):
            # self._slots[i] = x / _STATE[k] = x: write to the container
            self.record(t.value, "write")
            self.scan(t.value)
            self.scan(t.slice)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                self.write_target(e)
        elif isinstance(t, ast.Starred):
            self.write_target(t.value)
        elif isinstance(t, ast.Name):
            if t.id in self.fi.global_names:
                self.record(t, "write")

    # -- access recording --------------------------------------------------

    def locks_at(self, node):
        held = set(self.held)
        guard = self.r.guard_annotations.get(
            getattr(node, "lineno", 0))
        if guard is not None:
            held.add(guard)
        return frozenset(held)

    def key_of(self, node):
        """Shared-state key of an access, or None for locals /
        synchronized / thread-confined storage."""
        r = self.r
        if isinstance(node, ast.Attribute):
            base = node.value
            if isinstance(base, ast.Name) and base.id in ("self",
                                                          "cls"):
                attr = node.attr
                if attr in r.sync_names or attr in r.lock_names:
                    return None
                return ("self", self.fi.class_name or "", attr)
            if isinstance(base, ast.Attribute):
                # self._tls.acc: thread-local base confines the leaf
                if isinstance(base.value, ast.Name) and \
                        base.value.id in ("self", "cls") and \
                        base.attr in r.threadlocal_names:
                    return None
            return None
        if isinstance(node, ast.Name):
            name = node.id
            if name in self.fi.global_names or (
                    name in r.mutable_globals
                    and name not in self.fi.local_bindings
                    and self.fi is not r.module_fn):
                if name in r.sync_names or name in r.lock_names or \
                        name in r.threadlocal_names:
                    return None
                return ("global", name)
        return None

    def record(self, node, kind):
        key = self.key_of(node)
        if key is None:
            return
        prev = self._seen_access.get(id(node))
        if prev is not None:
            # `self._counts[i] += 1`: the expression scan sees the
            # inner Attribute as a Load first, then write_target
            # reports the same node as the store — upgrade, the
            # write is what lock discipline cares about
            if kind == "write" and prev.kind == "read":
                prev.kind = "write"
            return
        acc = Access(
            key=key, kind=kind, node=node, fi=self.fi,
            locks=self.locks_at(node), in_thread=self.in_thread)
        self._seen_access[id(node)] = acc
        self.r.accesses.append(acc)

    # -- calls: effects, blocking, mutators, spawns ------------------------

    def handle_call(self, c):
        r = self.r
        fname = r.resolve(c.func)
        attr = c.func.attr if isinstance(c.func, ast.Attribute) \
            else None

        # mutator methods write their receiver
        if attr in _MUTATOR_METHODS and \
                isinstance(c.func.value, (ast.Attribute, ast.Name)):
            self.record(c.func.value, "write")

        # thread spawns (TPU205 checks these against jit-reachability)
        if fname in I.THREAD_SPAWN_CALLS or (
                attr in I.EXECUTOR_SUBMIT_METHODS and c.args):
            r.spawn_sites.append((c, self.fi))

        # blocking call under a held lock (TPU204)
        what = None
        if fname in I.BLOCKING_CALLS:
            what = fname
        elif attr in I.BLOCKING_METHODS and \
                self._blocking_receiver(c.func.value):
            what = f".{attr}()"
        locks = self.locks_at(c)
        if what is not None and locks and id(c) not in \
                self._seen_blocking:
            self._seen_blocking.add(id(c))
            r.blocking_under_lock.append(
                (c, self.fi, sorted(locks)[0], what))

        # pipeline effects (TPU203)
        drawn = _root_key(c.args[0]) if c.args else None
        if fname in r._complete_calls:
            self.effects.append(("complete", c, (fname, drawn)))
        elif attr in r._dispatch_attrs:
            self.effects.append(("dispatch", c, attr))
        elif attr in r._release_attrs and \
                self.lock_leaf(c.func.value) is None:
            self.effects.append(("release", c, (attr, drawn)))
        else:
            callee = self._local_callee(c)
            if callee is not None:
                self.effects.append(
                    ("call", c, (callee, self._argmap(c, callee))))

    @staticmethod
    def _argmap(c, callee):
        """Callee parameter -> the caller's name its argument is drawn
        from (None where there is none), positionals and keywords."""
        a = getattr(callee.node, "args", None)
        if a is None:
            return {}
        params = [p.arg for p in a.posonlyargs + a.args]
        if params and params[0] in ("self", "cls") \
                and isinstance(c.func, ast.Attribute):
            params = params[1:]
        out = {p: _root_key(v) for p, v in zip(params, c.args)}
        out.update({kw.arg: _root_key(kw.value)
                    for kw in c.keywords if kw.arg})
        return out

    def _blocking_receiver(self, base):
        """Was the receiver built by a known blocking type (Thread,
        Event, queue, lock)? Gates `.join()`/`.get()`/`.wait()` so
        `",".join(...)` and `dict.get` stay invisible."""
        r = self.r
        types = set()
        if isinstance(base, ast.Name):
            call, _scope = self.fi.lookup_assigned_call(base.id)
            if call is not None:
                ctor = r.resolve(call.func)
                if ctor:
                    types.add(ctor)
            types |= r.name_types.get(base.id, set()) \
                if base.id in r.mutable_globals else set()
        elif isinstance(base, ast.Attribute) and \
                isinstance(base.value, ast.Name) and \
                base.value.id in ("self", "cls"):
            types |= r.name_types.get(base.attr, set())
        return bool(types & set(I.BLOCKING_RECEIVER_TYPES))

    def _local_callee(self, c):
        f = c.func
        if isinstance(f, ast.Name):
            return self.fi.lookup(f.id)
        if isinstance(f, ast.Attribute) and \
                isinstance(f.value, ast.Name) and \
                f.value.id in ("self", "cls") and self.fi.class_name:
            for cand in self.r._by_simple_name.get(f.attr, []):
                if cand.class_name == self.fi.class_name:
                    return cand
        return None
