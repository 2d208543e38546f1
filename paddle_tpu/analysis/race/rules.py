"""tpu-race rules (TPU2xx): lock discipline + allocator lifetime.

Each check takes a `RaceModuleAnalysis` and returns Finding objects.
The TPU2xx namespace sits beside tpu-lint's TPU0xx (AST trace-safety)
and tpu-verify's TPU1xx (jaxpr contracts); a registry test asserts the
three stay disjoint.
"""
from __future__ import annotations

import ast

from paddle_tpu.jit import introspect as I

from .model import CTOR_NAMES


def _grouped(mod):
    """accesses grouped by shared-state key, deterministic order."""
    groups = {}
    for a in mod.accesses:
        groups.setdefault(a.key, []).append(a)
    return sorted(groups.items())


def _line(a):
    return getattr(a.node, "lineno", 0)


def check_tpu201(mod):
    """unguarded-shared-mutable: an attribute/global written by
    helper-thread-reachable code with NO lock held (and no guarded-by
    assertion, no threading.local confinement) while step-thread code
    also touches it."""
    if not mod.thread_reachable:
        return []
    findings = []
    for key, accs in _grouped(mod):
        thread_writes = sorted(
            (a for a in accs if a.in_thread and a.kind == "write"
             and not a.locks), key=_line)
        if not thread_writes:
            continue
        step_side = sorted(
            (a for a in accs if not a.in_thread
             and a.fi.name not in CTOR_NAMES), key=_line)
        if not step_side:
            continue
        touch = step_side[0]
        for a in thread_writes:
            findings.append(mod.finding(
                "TPU201", a.node,
                f"{a.name()} is written on a helper thread with no "
                f"lock held, but the step thread touches it too "
                f"(line {_line(touch)}); hold one common lock on both "
                "sides, confine it via threading.local, or assert the "
                "caller's lock with '# guarded-by: <lock>'", a.fi))
    return findings


def check_tpu202(mod):
    """inconsistent-guard: one attribute written under a lock in one
    place and with no lock (or a different lock) in another. Unlocked
    thread-side writes are TPU201's domain and skipped here; reads
    are deliberately out of scope (racy snapshot reads are a
    documented idiom — see the metrics `.value` properties)."""
    findings = []
    for key, accs in _grouped(mod):
        writes = sorted((a for a in accs if a.kind == "write"
                         and a.fi.name not in CTOR_NAMES), key=_line)
        locked = [a for a in writes if a.locks]
        if not locked:
            continue
        primary = sorted(locked[0].locks)[0]
        for a in writes:
            if a.locks and primary in a.locks:
                continue
            if a.locks:
                other = sorted(a.locks)[0]
                msg = (f"{a.name()} is written under lock '{other}' "
                       f"here but under '{primary}' at line "
                       f"{_line(locked[0])} — one attribute, one lock")
            else:
                if a.in_thread and mod.thread_reachable:
                    continue           # TPU201 reports that shape
                msg = (f"{a.name()} is written under lock '{primary}' "
                       f"at line {_line(locked[0])} but with no lock "
                       "here; hold the same lock or assert the "
                       "caller's with '# guarded-by: <lock>'")
            findings.append(mod.finding("TPU202", a.node, msg, a.fi))
    return findings


class _PipeState:
    """What the TPU203 walk knows at one point of a function: which
    dispatched records are outstanding (in launch order), which
    records a wait has completed, and which record each name holds.
    A record is an int (made by a dispatch seen in this walk) or, for
    one made elsewhere, the qualified name it was first met under."""

    def __init__(self, outstanding=(), completed=(), env=None):
        self.outstanding = list(outstanding)
        self.completed = set(completed)
        self.env = dict(env or {})

    def copy(self):
        return _PipeState(self.outstanding, self.completed, self.env)

    @staticmethod
    def merged(arms):
        """Pessimistic join of exclusive arms: outstanding on ANY arm
        stays outstanding; completed, and a name's record, only where
        every arm agrees."""
        out = arms[0].copy()
        for arm in arms[1:]:
            out.outstanding = sorted(
                set(out.outstanding) | set(arm.outstanding))
            out.completed &= arm.completed
            out.env = {k: v for k, v in out.env.items()
                       if arm.env.get(k) == v}
        return out


def check_tpu203(mod):
    """free-before-complete: an allocator release (introspect
    ALLOCATOR_RELEASE_EFFECTS) reachable on a path between a recorded
    dispatch (ENGINE_DISPATCH_EFFECTS) and THAT dispatch's completion
    (STEP_COMPLETE_CALLS) — the zombie-write hazard (DESIGN_DECISIONS
    r21/r22). The invariant it holds: a lane's pages are released only
    after the last step dispatched over the lane has completed. So a
    release drawn from a record whose wait has been seen is sound even
    with a LATER step outstanding (the engine's ahead order: launch
    N+1, wait for N, release N's lanes), and any other release with a
    step outstanding fires: one drawn from the outstanding record
    itself, from no record, or made before the wait. Loop bodies
    replay twice in the effect walk, so the depth-2 shape (iteration
    N+1 frees before waiting on iteration N's dispatch) fires too.

    A wait completes the record its argument is drawn from and every
    record launched before it (one stream). A wait on anything else
    completes only dispatches whose result was never bound to a name
    (nothing could wait on them by name). `if` arms fork the state
    (exclusive arms can't see each other's dispatches); the merge is
    pessimistic — a dispatch surviving on ANY non-diverging arm stays
    outstanding, and an arm ending in return/raise/break/continue
    drops out of the merge entirely (early-return guards read as
    guards)."""
    findings = []
    seen = set()
    for fi in mod.functions:
        st = _PipeState()
        forks = []      # [saved_state, [non-diverged arm exit states]]
        nodes = {}      # record -> the dispatch call that made it
        named = set()   # records some name has held
        frames = [0]
        n_frames = 0

        def held(key):
            """The record `key` holds in the frame being walked (and
            the key qualified by that frame); a name never bound
            stands for the record made elsewhere that it holds."""
            q = key if key.startswith("self.") else (frames[-1], key)
            return st.env.get(q, q), q

        for kind, node, detail in mod.effect_seq(fi):
            if kind == "dispatch":
                rec = len(nodes)
                nodes[rec] = node
                st.outstanding.append(rec)
            elif kind == "bind":
                values = []
                for key, source in detail:
                    if isinstance(source, tuple):
                        # the result of the dispatch call just walked
                        values.append(len(nodes) - 1 if nodes else None)
                    else:
                        values.append(None if source is None
                                      else held(source)[0])
                for (key, _), rec in zip(detail, values):
                    _, q = held(key)
                    st.completed.discard(q)
                    if rec is None:
                        st.env.pop(q, None)
                    else:
                        st.env[q] = rec
                        named.add(rec)
            elif kind == "enter":
                n_frames += 1
                for param, key in detail.items():
                    if key is not None:
                        st.env[(n_frames, param)] = held(key)[0]
                frames.append(n_frames)
            elif kind == "exit":
                frames.pop()
            elif kind == "complete":
                rec = None if detail[1] is None else held(detail[1])[0]
                if rec in st.outstanding:
                    cut = st.outstanding.index(rec) + 1
                    st.completed.update(st.outstanding[:cut])
                    st.outstanding = st.outstanding[cut:]
                else:
                    if rec is not None:
                        st.completed.add(rec)
                    st.outstanding = [r for r in st.outstanding
                                      if r in named]
            elif kind == "fork":
                forks.append([st.copy(), []])
            elif kind == "alt":
                if forks:
                    saved, arms = forks[-1]
                    if not detail:
                        arms.append(st)
                    st = saved.copy()
            elif kind == "join":
                if forks:
                    saved, arms = forks.pop()
                    if not detail:
                        arms.append(st)
                    st = _PipeState.merged(arms) if arms else saved
            elif kind == "release":
                # a dispatch spliced from the SAME callee as this
                # release is reported inside that callee, not here
                live = [r for r in st.outstanding if nodes[r] is not node]
                if not live:
                    continue
                attr, key = detail
                if key is not None and held(key)[0] in st.completed:
                    continue
                sig = (id(fi), getattr(node, "lineno", 0),
                       getattr(node, "col_offset", 0), attr)
                if sig in seen:
                    continue
                seen.add(sig)
                findings.append(mod.finding(
                    "TPU203", node,
                    f"allocator release '{attr}' is reachable "
                    f"between the dispatch at line "
                    f"{getattr(nodes[live[0]], 'lineno', 0)} and its "
                    "completion — a dispatched step may still write "
                    "the released blocks (zombie write); complete "
                    "the step the lanes were last dispatched in "
                    "before releasing them", fi))
    return findings


def check_tpu204(mod):
    """blocking-call-under-lock: block_until_ready / Thread.join /
    sleep / queue-get while holding a registry or allocator lock —
    every other thread contending on that lock stalls behind device
    or wall-clock time."""
    findings = []
    for node, fi, lock, what in mod.blocking_under_lock:
        findings.append(mod.finding(
            "TPU204", node,
            f"blocking call {what} while holding lock '{lock}'; "
            "move the wait outside the guarded region", fi))
    return findings


def check_tpu205(mod):
    """thread-spawn-in-trace: jit-reachable code starting threads
    (tpu-lint's reachability tables) — a spawn inside a traced
    function runs ONCE at trace time and stages nothing."""
    findings = []
    for node, fi in mod.spawn_sites:
        if not fi.traced:
            continue
        fname = mod.resolve(node.func)
        what = fname if fname in I.THREAD_SPAWN_CALLS \
            else f".{node.func.attr}(...)" \
            if isinstance(node.func, ast.Attribute) else "thread spawn"
        findings.append(mod.finding(
            "TPU205", node,
            f"jit-reachable code starts a thread ({what}); the spawn "
            "runs once at trace time and is invisible to the compiled "
            "program — hoist it out of the traced region", fi))
    return findings


#: rule id -> (name, description, check). TPU200 is the parse-error
#: rule (no checker — emitted by analyze_file), mirroring TPU000.
RACE_RULES = {
    "TPU200": ("parse-error",
               "file could not be parsed (reported, never skipped)",
               None),
    "TPU201": ("unguarded-shared-mutable",
               "helper-thread write to shared state with no common "
               "lock, confinement, or guarded-by annotation",
               check_tpu201),
    "TPU202": ("inconsistent-guard",
               "attribute written under different locks, or both "
               "with and without one",
               check_tpu202),
    "TPU203": ("free-before-complete",
               "allocator release between a dispatched step and its "
               "completion (zombie-write hazard)",
               check_tpu203),
    "TPU204": ("blocking-call-under-lock",
               "block_until_ready/join/sleep/queue-get while holding "
               "a lock",
               check_tpu204),
    "TPU205": ("thread-spawn-in-trace",
               "jit-reachable code starts a thread",
               check_tpu205),
}


def all_race_rule_ids():
    return sorted(RACE_RULES)
