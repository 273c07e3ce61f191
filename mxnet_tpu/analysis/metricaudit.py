"""Metric-name doc-sync lint: recorded metrics vs docs/telemetry.md.

The observability surface grows a few metric families per PR and the
catalog rots silently — a metric nobody can discover is a dashboard
nobody builds. The PR-12 env audit solved exactly this shape of drift
for env vars; this is its mirror for the metrics registry, ast-based so
it survives formatting:

* **code scan** — every ``*.py`` under ``mxnet_tpu/`` is parsed and
  every ``counter(...)``/``gauge(...)``/``histogram(...)`` call site
  contributes its metric name (``held_counters(...)`` every name it
  lists). Names are resolved best-effort within
  the enclosing function scope: plain literals, ``name + ".seconds"``
  concatenations, and ``a if cond else b`` literal ternaries all
  resolve to exact names; f-string names (``f"serve.decode.{key}"``)
  contribute their literal *prefix*. ``hist=``/``_hist=`` keyword
  literals (the span-to-histogram feed) count as exact histogram
  names, and ``metric_prefix=`` keywords (and defaults) declare a
  ``<prefix>.`` family (the circuit breaker's ``.state``/
  ``.transitions`` gauges). Docstring mentions are not calls and never
  count;
* **doc scan** — the "Metric catalog" section of docs/telemetry.md:
  every backticked token in the section is a catalog row; rows with a
  ``<placeholder>`` segment (``step.phase.<phase>.seconds``) document
  a prefix family;
* **drift** — code metrics missing a catalog row fail the audit, and
  so do dead catalog rows naming metrics no code records. A code
  f-string family with no catalog row for its prefix fails too
  (reported as ``prefix*``).

CLI: ``python tools/mxlint.py --metric-audit`` (nonzero exit on drift —
the CI gate); the test suite runs the same audit in-process next to
``--env-audit``.
"""
from __future__ import annotations

import ast
import os
import re

__all__ = ["scan_code", "scan_docs", "audit", "CATALOG_HEADING"]

CATALOG_HEADING = "## Metric catalog"

_METRIC_FNS = {"counter", "gauge", "histogram"}
_HELD_FNS = {"held_counters"}      # every positional argument is a name
_HIST_KWARGS = {"hist", "_hist"}

_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
_PREFIX_RE = re.compile(r"^[a-z][a-z0-9_.]*\.$")
_DOC_TOKEN_RE = re.compile(r"`([^`\s]+)`")


# ------------------------------------------------------------- code scan
def _resolve(node, env, depth=0):
    """Best-effort set of string values an expression can take within
    its function scope; None when unresolvable."""
    if depth > 6:
        return None
    if isinstance(node, ast.Constant):
        return {node.value} if isinstance(node.value, str) else None
    if isinstance(node, ast.Name):
        return env.get(node.id)
    if isinstance(node, ast.IfExp):
        a = _resolve(node.body, env, depth + 1)
        b = _resolve(node.orelse, env, depth + 1)
        return (a or set()) | (b or set()) if (a or b) else None
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        left = _resolve(node.left, env, depth + 1)
        right = _resolve(node.right, env, depth + 1)
        if left and right:
            return {a + b for a in left for b in right}
        return None
    return None


def _joined_prefix(node):
    """The leading literal of an f-string, when it has one."""
    if isinstance(node, ast.JoinedStr) and node.values:
        first = node.values[0]
        if isinstance(first, ast.Constant) and \
                isinstance(first.value, str) and first.value:
            return first.value
    return None


def _call_fn_name(node):
    fn = node.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return None


def _scope_nodes(scope):
    """Child nodes of a scope, not descending into nested function
    scopes (classes are transparent: methods become their own scopes
    via the outer walk, class-level assigns belong to the class body)."""
    stack = list(ast.iter_child_nodes(scope))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


def _scan_scope(scope, exact, prefixes):
    env = {}
    for node in _scope_nodes(scope):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            vals = _resolve(node.value, env)
            if vals:
                name = node.targets[0].id
                env[name] = env.get(name, set()) | vals
    for node in _scope_nodes(scope):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a metric_prefix="..." default declares the family the
            # function records under when callers don't override
            for arg, default in zip(node.args.args[-len(node.args.defaults):]
                                    if node.args.defaults else [],
                                    node.args.defaults):
                if arg.arg == "metric_prefix" and \
                        isinstance(default, ast.Constant) and \
                        isinstance(default.value, str):
                    prefixes.add(default.value + ".")
            continue
        if not isinstance(node, ast.Call):
            continue
        for kw in node.keywords:
            if kw.arg in _HIST_KWARGS:
                for v in _resolve(kw.value, env) or ():
                    if _NAME_RE.match(v):
                        exact.add(v)
            elif kw.arg == "metric_prefix":
                for v in _resolve(kw.value, env) or ():
                    prefixes.add(v + ".")
        fn = _call_fn_name(node)
        if fn not in _METRIC_FNS | _HELD_FNS:
            continue
        for arg in node.args if fn in _HELD_FNS else node.args[:1]:
            resolved = _resolve(arg, env)
            if resolved:
                exact.update(v for v in resolved if _NAME_RE.match(v))
                continue
            prefix = _joined_prefix(arg)
            if prefix is not None and _PREFIX_RE.match(prefix):
                prefixes.add(prefix)


def scan_code(root):
    """(exact_names, prefixes) of recorded metric names under ``root``."""
    exact, prefixes = set(), set()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fname in filenames:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            try:
                with open(path) as f:
                    tree = ast.parse(f.read(), filename=path)
            except SyntaxError:
                continue
            scopes = [tree] + [n for n in ast.walk(tree)
                               if isinstance(n, (ast.FunctionDef,
                                                 ast.AsyncFunctionDef))]
            for scope in scopes:
                _scan_scope(scope, exact, prefixes)
    return exact, prefixes


# -------------------------------------------------------------- doc scan
def scan_docs(doc_path):
    """(exact_rows, prefix_rows) from the doc's Metric catalog section.

    Only the catalog section counts — prose elsewhere may mention
    metric names without cataloguing them. A backticked token with a
    ``<placeholder>`` documents the family of names sharing its literal
    prefix."""
    with open(doc_path) as f:
        text = f.read()
    exact, prefixes = set(), set()
    in_section = False
    for line in text.splitlines():
        if line.strip() == CATALOG_HEADING:
            in_section = True
            continue
        if in_section and line.startswith("## "):
            break
        if not in_section:
            continue
        for token in _DOC_TOKEN_RE.findall(line):
            if "<" in token:
                prefix = token.split("<", 1)[0]
                if _PREFIX_RE.match(prefix):
                    prefixes.add(prefix)
            elif _NAME_RE.match(token):
                exact.add(token)
    return exact, prefixes


# ----------------------------------------------------------------- audit
def audit(repo_root):
    """Run the doc-sync audit; returns a result dict.

    ``undocumented``: metric names the code records with no catalog row
    (an f-string family is covered when a catalog row falls under its
    prefix; uncovered families report as ``prefix*``). ``dead``:
    catalog rows naming metrics no code records (exactly or via a
    family). Empty both ways = in sync.
    """
    code_root = os.path.join(repo_root, "mxnet_tpu")
    doc_path = os.path.join(repo_root, "docs", "telemetry.md")
    exact, prefixes = scan_code(code_root)
    doc_exact, doc_prefixes = scan_docs(doc_path)

    def doc_covers(name):
        if name in doc_exact:
            return True
        return any(name.startswith(p) for p in doc_prefixes)

    def doc_covers_family(prefix):
        if any(d.startswith(prefix) for d in doc_exact):
            return True
        return any(d.startswith(prefix) or prefix.startswith(d)
                   for d in doc_prefixes)

    def code_covers(name):
        if name in exact:
            return True
        return any(name.startswith(p) for p in prefixes)

    def code_covers_family(prefix):
        if any(e.startswith(prefix) for e in exact):
            return True
        return any(c.startswith(prefix) or prefix.startswith(c)
                   for c in prefixes)

    undocumented = sorted(n for n in exact if not doc_covers(n))
    undocumented += sorted(f"{p}*" for p in prefixes
                           if not doc_covers_family(p))
    dead = sorted(d for d in doc_exact if not code_covers(d))
    dead += sorted(f"{p}*" for p in doc_prefixes
                   if not code_covers_family(p))
    return {"undocumented": undocumented, "dead": dead,
            "code_names": sorted(exact),
            "code_prefixes": sorted(prefixes),
            "doc_names": sorted(doc_exact),
            "doc_prefixes": sorted(doc_prefixes),
            "ok": not undocumented and not dead}
