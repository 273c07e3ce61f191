"""Env-var doc-sync lint: ``MXNET_*`` reads vs ``docs/env_var.md``.

The configuration surface grows one env knob per PR and the doc rots
silently — a knob nobody can discover is a knob that ships
half-supported. This audit keeps the two in lockstep, ast-based so it
survives formatting:

* **code scan** — every ``*.py`` under ``mxnet_tpu/`` is parsed and
  every string constant that IS an ``MXNET_*`` name is collected: the
  codebase's convention is that such a literal is always an environ
  key — ``os.environ.get/[...]``, ``os.getenv``, the ``_env_int``-style
  wrappers, and the env dicts recovery re-exec writes. Mentions inside
  docstrings or longer messages are not full-token literals and do not
  count as reads. f-string keys (``f"MXNET_RETRY_{site}"``) contribute
  their literal *prefix*, matched against doc rows by prefix;
* **doc scan** — every ``MXNET_*`` token in ``docs/env_var.md``;
* **drift** — code vars missing a doc row fail the audit, and so do
  dead doc rows naming vars no code touches.

CLI: ``python tools/mxlint.py --env-audit`` (nonzero exit on drift —
the CI gate); the test suite runs the same audit in-process.
"""
from __future__ import annotations

import ast
import os
import re

__all__ = ["scan_code", "scan_docs", "audit"]

_NAME_RE = re.compile(r"MXNET_[A-Z0-9_]+")


def _collect_keys(expr, exact):
    """Record a literal env-key expression as an exact name."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str) \
            and expr.value.startswith("MXNET_"):
        m = _NAME_RE.match(expr.value)
        if m and m.group(0) == expr.value:
            exact.add(expr.value)


def _collect_prefix(expr, prefixes):
    """A ``f"MXNET_FOO_{x}"`` anywhere declares a constructed env-key
    family; its leading MXNET_* literal becomes a prefix."""
    if isinstance(expr, ast.JoinedStr) and expr.values:
        first = expr.values[0]
        if isinstance(first, ast.Constant) and \
                isinstance(first.value, str):
            m = _NAME_RE.match(first.value)
            if m:
                prefixes.add(m.group(0))


def _scan_file(path, exact, prefixes):
    try:
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError):
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant):
            _collect_keys(node, exact)
        elif isinstance(node, ast.JoinedStr):
            _collect_prefix(node, prefixes)


def scan_code(root):
    """(exact_names, prefixes) of MXNET_* environ keys under ``root``."""
    exact, prefixes = set(), set()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fname in filenames:
            if not fname.endswith(".py"):
                continue
            _scan_file(os.path.join(dirpath, fname), exact, prefixes)
    return exact, prefixes


def scan_docs(doc_path):
    """All MXNET_* tokens appearing in the doc."""
    with open(doc_path) as f:
        return set(_NAME_RE.findall(f.read()))


def audit(repo_root):
    """Run the doc-sync audit; returns a result dict.

    ``undocumented``: env vars the code reads with no doc row (a
    prefix-read like MXNET_RETRY_* is covered when at least one doc row
    starts with the prefix). ``dead``: doc rows naming vars no code
    touches (exactly or via a prefix read). Empty both ways = in sync.
    """
    code_root = os.path.join(repo_root, "mxnet_tpu")
    doc_path = os.path.join(repo_root, "docs", "env_var.md")
    exact, prefixes = scan_code(code_root)
    doc = scan_docs(doc_path)

    def doc_covers(name):
        if name in doc:
            return True
        # a code var constructed from a documented-prefix family row
        return any(name.startswith(p) and any(
            d.startswith(p) for d in doc) for p in prefixes)

    def code_covers(name):
        if name in exact:
            return True
        return any(name.startswith(p) for p in prefixes)

    undocumented = sorted(n for n in exact if not doc_covers(n))
    dead = sorted(n for n in doc if not code_covers(n))
    return {"undocumented": undocumented, "dead": dead,
            "code_vars": sorted(exact), "code_prefixes": sorted(prefixes),
            "doc_vars": sorted(doc),
            "ok": not undocumented and not dead}
