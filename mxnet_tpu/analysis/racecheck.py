"""Host-concurrency lint (RC2xx): cross-thread instance state vs locks.

PRs 13-19 grew a threaded host plane — the serve dispatch thread, the
decode scheduler thread, the checkpoint writer, the opsd HTTP handlers
— whose shared mutable state the graph-level passes cannot see. This
pass builds a *class-scoped* model of that plane, AST-only (nothing is
imported or executed):

* **lock discovery** — ``self.X = threading.Lock()/RLock()`` declares a
  lock attribute; ``threading.Condition(self.X)`` aliases the condition
  to the lock it wraps (the decode scheduler's ``_cond`` IS ``_lock``),
  a bare ``Condition()`` is its own lock. ``queue.Queue``/
  ``threading.Event``-valued attributes are safe channels — their
  method calls synchronize internally and never count as shared-state
  accesses.
* **thread entries** — a method passed as ``threading.Thread(target=
  self.M)`` anywhere in the class runs on the spawned thread; classes
  deriving from ``BaseHTTPRequestHandler`` run their ``do_*`` methods
  on server threads. A class that spawns nothing has no cross-thread
  surface and is skipped.
* **sides** — the *thread side* is the call-graph closure of the
  entries over ``self.m()`` edges; the *caller side* is the closure of
  the public methods (plus dunders). A method reachable from both (the
  decode scheduler's ``_iterate`` runs under ``pump()`` and under the
  dispatch thread) counts on both sides.
* **guards** — the lock set lexically held at each access
  (``with self.L:`` nesting), plus propagation: a private method whose
  every intra-class call site holds lock L inherits L (the
  "caller holds the lock" docstring convention, verified instead of
  trusted).
* **writes** — attribute stores/augmented stores, subscript stores,
  and mutating method calls (``append``/``add``/``update``/...) on
  attributes the class initializes to a list/dict/set display (so
  ``self._registry.add(...)`` on an internally-locked object is not
  miscounted as an unguarded container mutation). ``__init__`` accesses
  are exempt: they happen-before ``Thread.start()``.

Rules (all error severity — the CI gate demands zero unannotated):

* **RC201** — an attribute written on one side and touched on the
  other has at least one access holding no lock at all;
* **RC202** — every access is guarded, but no single lock covers all
  of them (the same attr under two different locks);
* **RC203** — two functions each nest the same two locks in opposite
  orders (lock-order inversion: the classic ABBA deadlock shape).

Suppression records intent: ``# mxlint: guarded-by(<lockname>)`` on any
access line of the attribute suppresses RC201/RC202 for that (class,
attr) and lands in the audit's ``annotated`` list — the reviewer sees
the claim, the lint stops repeating it.

CLI: ``python tools/mxlint.py --race-audit`` (and inside ``--check``);
the scanned surface is ``serve/``, ``checkpoint/``, ``telemetry/`` and
``faults/``. The audit is test/CLI-time only — nothing here runs at
bind time.
"""
from __future__ import annotations

import ast
import os
import re

__all__ = ["audit", "scan_source", "SCAN_DIRS"]

#: directories under mxnet_tpu/ the repo audit walks (the threaded
#: host plane; the dispatch-path modules have no thread spawns)
SCAN_DIRS = ("serve", "checkpoint", "telemetry", "faults")

_ANNOT_RE = re.compile(
    r"#\s*mxlint:\s*guarded-by\(\s*([A-Za-z_][A-Za-z0-9_.-]*)\s*\)")

_LOCK_CTORS = {"Lock", "RLock"}
_COND_CTOR = "Condition"
_SAFE_CTORS = {"Queue", "LifoQueue", "PriorityQueue", "SimpleQueue",
               "Event", "Semaphore", "BoundedSemaphore", "Barrier"}
_HTTP_BASES = {"BaseHTTPRequestHandler", "SimpleHTTPRequestHandler",
               "CGIHTTPRequestHandler"}
#: method names that mutate builtin containers (only applied to attrs
#: the class initializes to a list/dict/set display)
_MUTATORS = {"append", "extend", "insert", "remove", "pop", "clear",
             "add", "discard", "update", "setdefault", "popitem",
             "sort", "reverse"}
_EXEMPT_METHODS = {"__init__", "__del__"}


def _ctor_name(call):
    """Trailing name of a Call's callee (``threading.RLock`` -> RLock)."""
    fn = call.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return None


def _self_attr(node):
    """'X' for a ``self.X`` attribute node, else None."""
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return None


def _contains_container_display(expr):
    for sub in ast.walk(expr):
        if isinstance(sub, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                            ast.DictComp, ast.SetComp)):
            return True
    return False


class _MethodFacts:
    __slots__ = ("accesses", "calls", "pairs")

    def __init__(self):
        self.accesses = []   # (attr, kind 'r'|'w', lineno, frozenset)
        self.calls = []      # (method name, frozenset held, lineno)
        self.pairs = []      # (outer lock, inner lock, lineno)


class _MethodVisitor(ast.NodeVisitor):
    """One method walk: accesses/calls with the lexically held locks."""

    def __init__(self, model, func):
        self.model = model
        self.facts = _MethodFacts()
        self.held = []       # stack of canonical lock names
        for stmt in func.body:
            self.visit(stmt)

    # -- lock scopes ---------------------------------------------------
    def visit_With(self, node):
        pushed = 0
        for item in node.items:
            attr = _self_attr(item.context_expr)
            lock = self.model.canonical_lock(attr)
            if lock is not None:
                for outer in self.held:
                    if outer != lock:
                        self.facts.pairs.append(
                            (outer, lock, node.lineno))
                self.held.append(lock)
                pushed += 1
            else:
                self.visit(item.context_expr)
        for stmt in node.body:
            self.visit(stmt)
        del self.held[len(self.held) - pushed:len(self.held)]

    # -- nested defs run in unknown contexts: analyze with no locks ---
    def visit_FunctionDef(self, node):
        saved, self.held = self.held, []
        self.generic_visit(node)
        self.held = saved

    visit_AsyncFunctionDef = visit_FunctionDef

    # -- accesses ------------------------------------------------------
    def _record(self, attr, kind, lineno):
        if attr is None or self.model.is_synchronizer(attr):
            return
        self.facts.accesses.append(
            (attr, kind, lineno, frozenset(self.held)))

    def visit_Attribute(self, node):
        attr = _self_attr(node)
        if attr is not None:
            kind = "w" if isinstance(node.ctx,
                                     (ast.Store, ast.Del)) else "r"
            self._record(attr, kind, node.lineno)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        attr = _self_attr(node.target)
        if attr is not None:
            self._record(attr, "w", node.lineno)
        self.generic_visit(node)

    def visit_Subscript(self, node):
        if isinstance(node.ctx, (ast.Store, ast.Del)):
            attr = _self_attr(node.value)
            if attr is not None:
                self._record(attr, "w", node.lineno)
        self.generic_visit(node)

    def visit_Call(self, node):
        fn = node.func
        if isinstance(fn, ast.Attribute):
            owner = _self_attr(fn.value)
            if owner is not None:
                if fn.attr in _MUTATORS and \
                        owner in self.model.containers:
                    self._record(owner, "w", node.lineno)
            target = _self_attr(fn)
            if target is not None and target in self.model.methods:
                self.facts.calls.append(
                    (target, frozenset(self.held), node.lineno))
        self.generic_visit(node)


class _ClassModel:
    """The per-class concurrency model the rules evaluate over."""

    def __init__(self, node, rel_path, annotations):
        self.node = node
        self.name = node.name
        self.path = rel_path
        self.methods = {}        # name -> FunctionDef
        self.locks = {}          # attr -> canonical lock attr
        self.safe = set()        # queue/event channel attrs
        self.containers = set()  # attrs initialized to a display
        self.entries = set()
        self.facts = {}          # method -> _MethodFacts
        self.annotations = annotations   # line -> lock name claim

        for item in node.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.methods[item.name] = item
        self._discover_attrs()
        self._discover_entries()

    # -- discovery -----------------------------------------------------
    def _discover_attrs(self):
        for func in self.methods.values():
            for stmt in ast.walk(func):
                if not isinstance(stmt, ast.Assign):
                    continue
                for target in stmt.targets:
                    attr = _self_attr(target)
                    if attr is None:
                        continue
                    value = stmt.value
                    if isinstance(value, ast.Call):
                        ctor = _ctor_name(value)
                        if ctor in _LOCK_CTORS:
                            self.locks.setdefault(attr, attr)
                        elif ctor == _COND_CTOR:
                            wrapped = _self_attr(value.args[0]) \
                                if value.args else None
                            self.locks[attr] = wrapped if wrapped \
                                else attr
                        elif ctor in _SAFE_CTORS:
                            self.safe.add(attr)
                    if _contains_container_display(value):
                        self.containers.add(attr)
        # resolve one level of condition->lock aliasing
        for attr, canon in list(self.locks.items()):
            self.locks[attr] = self.locks.get(canon, canon)

    def _discover_entries(self):
        bases = {b.attr if isinstance(b, ast.Attribute) else
                 getattr(b, "id", None) for b in self.node.bases}
        if bases & _HTTP_BASES:
            self.entries.update(m for m in self.methods
                                if m.startswith("do_"))
        for func in self.methods.values():
            for call in ast.walk(func):
                if not isinstance(call, ast.Call):
                    continue
                if _ctor_name(call) != "Thread":
                    continue
                for kw in call.keywords:
                    if kw.arg != "target":
                        continue
                    target = _self_attr(kw.value)
                    if target is not None and target in self.methods:
                        self.entries.add(target)

    def canonical_lock(self, attr):
        if attr is None:
            return None
        return self.locks.get(attr)

    def is_synchronizer(self, attr):
        return attr in self.locks or attr in self.safe

    # -- analysis ------------------------------------------------------
    def analyze(self):
        if not self.entries:
            return [], []
        for name, func in self.methods.items():
            self.facts[name] = _MethodVisitor(self, func).facts
        inherited = self._propagate_guards()
        thread_side = self._closure(self.entries)
        caller_roots = {m for m in self.methods
                        if m not in self.entries and
                        (not m.startswith("_") or m.startswith("__"))}
        caller_side = self._closure(caller_roots)
        findings = self._attr_findings(thread_side, caller_side,
                                       inherited)
        findings += self._order_findings(inherited)
        annotated = self._annotated_attrs()
        keep = []
        for f in findings:
            if f["rule"] in ("RC201", "RC202") and \
                    f["node"].split(".", 1)[-1] in annotated:
                continue
            keep.append(f)
        notes = [{"file": self.path, "class": self.name, "attr": attr,
                  "lock": lock, "line": line}
                 for attr, (lock, line) in sorted(annotated.items())]
        return keep, notes

    def _closure(self, roots):
        seen = set(roots)
        frontier = list(roots)
        while frontier:
            m = frontier.pop()
            for callee, _held, _ln in self.facts.get(
                    m, _MethodFacts()).calls:
                if callee not in seen:
                    seen.add(callee)
                    frontier.append(callee)
        return seen

    def _propagate_guards(self):
        """Locks every intra-class call site of a private method holds;
        fixpoint over the call graph (public methods and entries are
        externally callable with nothing held)."""
        inherited = {m: frozenset() for m in self.methods}
        callers = {}    # method -> [(caller, held at the call)]
        for name, facts in self.facts.items():
            for callee, held, _ln in facts.calls:
                callers.setdefault(callee, []).append((name, held))
        for _ in range(len(self.methods) + 1):
            changed = False
            for m in self.methods:
                if not m.startswith("_") or m.startswith("__") or \
                        m in self.entries or m not in callers:
                    continue
                guard = None
                for caller, held in callers[m]:
                    site = held | inherited[caller]
                    guard = site if guard is None else guard & site
                guard = guard or frozenset()
                if guard != inherited[m]:
                    inherited[m] = guard
                    changed = True
            if not changed:
                break
        return inherited

    def _attr_findings(self, thread_side, caller_side, inherited):
        per_attr = {}   # attr -> {"t": [...], "c": [...]}
        for name, facts in self.facts.items():
            if name in _EXEMPT_METHODS:
                continue
            sides = ("t" if name in thread_side else "") + \
                    ("c" if name in caller_side else "")
            if not sides:
                continue
            for attr, kind, lineno, held in facts.accesses:
                eff = held | inherited[name]
                rec = (kind, name, lineno, eff)
                slot = per_attr.setdefault(attr, {"t": [], "c": []})
                for side in sides:
                    slot[side].append(rec)
        findings = []
        for attr in sorted(per_attr):
            t_acc, c_acc = per_attr[attr]["t"], per_attr[attr]["c"]
            if not t_acc or not c_acc:
                continue
            if not any(kind == "w" for kind, *_ in t_acc + c_acc):
                continue
            all_acc = {(m, ln): (kind, guards)
                       for kind, m, ln, guards in t_acc + c_acc}
            unguarded = [(m, ln) for (m, ln), (k, g) in
                         sorted(all_acc.items()) if not g]
            if unguarded:
                m, ln = unguarded[0]
                findings.append(self._finding(
                    "RC201", attr, ln,
                    f"{self.name}.{attr} crosses the "
                    f"{'/'.join(sorted(self.entries))} thread boundary "
                    f"but {m}() touches it with no lock held "
                    f"(line {ln})",
                    "guard the access with the class lock, or annotate "
                    "the line with  # mxlint: guarded-by(<lock>)  and a "
                    "comment justifying benignity"))
                continue
            common = None
            for _k, g in all_acc.values():
                common = g if common is None else common & g
            if not common:
                locks = sorted({l for _k, g in all_acc.values()
                                for l in g})
                findings.append(self._finding(
                    "RC202", attr,
                    min(ln for _m, ln in all_acc),
                    f"{self.name}.{attr} is guarded inconsistently: "
                    f"accesses hold {locks} but no single lock covers "
                    "every path",
                    "pick one lock for the attribute (or annotate with "
                    "# mxlint: guarded-by(<lock>))"))
        return findings

    def _order_findings(self, inherited):
        seen = {}    # (A, B) ordered -> (method, line)
        for name, facts in self.facts.items():
            for outer, inner, ln in facts.pairs:
                seen.setdefault((outer, inner), (name, ln))
            # a method entered with a propagated (call-site) lock that
            # then takes another forms a cross-function ordering edge
            base = inherited[name]
            if not base:
                continue
            for item in ast.walk(self.methods[name]):
                if not isinstance(item, ast.With):
                    continue
                for witem in item.items:
                    lock = self.canonical_lock(
                        _self_attr(witem.context_expr))
                    if lock is None:
                        continue
                    for outer in base:
                        if outer != lock:
                            seen.setdefault((outer, lock),
                                            (name, item.lineno))
        findings = []
        for (a, b), (f1, ln1) in sorted(seen.items()):
            if (b, a) not in seen or a >= b:
                continue
            f2, ln2 = seen[(b, a)]
            findings.append(self._finding(
                "RC203", f"{a}<>{b}", ln1,
                f"{self.name} acquires {a} then {b} in {f1}() "
                f"(line {ln1}) but {b} then {a} in {f2}() (line {ln2}) "
                "— lock-order inversion can deadlock",
                "pick one acquisition order and restructure the "
                "second site"))
        return findings

    def _annotated_attrs(self):
        """attr -> (claimed lock, line) for guarded-by annotations on
        access lines of the attr (``__init__`` lines count — the
        declaration site is the natural place for the claim)."""
        out = {}
        if not self.annotations:
            return out
        for name, func in self.methods.items():
            for node in ast.walk(func):
                attr = _self_attr(node)
                if attr is None:
                    continue
                claim = self.annotations.get(node.lineno)
                if claim is not None and attr not in self.locks:
                    out.setdefault(attr, (claim, node.lineno))
        return out

    def _finding(self, rule, attr, line, message, hint):
        return {"target": self.path, "rule": rule, "severity": "error",
                "node": f"{self.name}.{attr}", "line": line,
                "message": message, "hint": hint}


def scan_source(source, rel_path="<fixture>"):
    """(findings, annotated) for one module's source text."""
    try:
        tree = ast.parse(source, filename=rel_path)
    except SyntaxError as e:
        return ([{"target": rel_path, "rule": "XX001",
                  "severity": "info", "node": None, "line": 0,
                  "message": f"racecheck could not parse: {e}",
                  "hint": None}], [])
    annotations = {}
    for lineno, line in enumerate(source.splitlines(), 1):
        m = _ANNOT_RE.search(line)
        if m:
            annotations[lineno] = m.group(1)
    findings, annotated = [], []
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef):
            f, a = _ClassModel(node, rel_path, annotations).analyze()
            findings += f
            annotated += a
    return findings, annotated


def audit(repo_root, subdirs=SCAN_DIRS, sources=None):
    """Run the race audit; returns a result dict.

    ``sources`` (name -> source text) replaces the repo walk — the
    seeded-fixture path the tests drive. ``findings`` is the list of
    unsuppressed RC2xx dicts; ``annotated`` records every guarded-by
    claim so suppression is visible, not silent.
    """
    findings, annotated, scanned = [], [], 0
    if sources is not None:
        for name in sorted(sources):
            f, a = scan_source(sources[name], name)
            findings += f
            annotated += a
            scanned += 1
    else:
        code_root = os.path.join(repo_root, "mxnet_tpu")
        for sub in subdirs:
            base = os.path.join(code_root, sub)
            for dirpath, dirnames, filenames in os.walk(base):
                dirnames[:] = [d for d in dirnames
                               if d != "__pycache__"]
                for fname in sorted(filenames):
                    if not fname.endswith(".py"):
                        continue
                    path = os.path.join(dirpath, fname)
                    rel = os.path.relpath(path, repo_root)
                    try:
                        with open(path) as f:
                            src = f.read()
                    except OSError:
                        continue
                    fs, an = scan_source(src, rel)
                    findings += fs
                    annotated += an
                    scanned += 1
    return {"findings": findings, "annotated": annotated,
            "files_scanned": scanned, "ok": not findings}
