"""Hashed prefix store over slot-pool KV-cache rows.

Requests that share a system prompt keep re-prefilling it: the cache
rows they'd compute are byte-identical every time. ``PrefixStore`` is
the reuse plane — when a sequence submitted with ``prefix_id=`` finishes
prefilling, the scheduler snapshots its first ``len(prompt)`` cache
positions (every layer's K and V rows, for the target engine and — when
speculative decoding is armed — the draft engine too) plus the token
ids they encode. The next ``submit(prefix_id=...)`` whose prompt starts
with those tokens *joins at cursor C*: the bit-clean slot join writes
the stored rows back and rewinds the cursor to C instead of 0, so the
sequence skips straight past the shared prefix (⌈C/S⌉ dispatches
saved) and its cache is bitwise what a cold prefill would have written.

Contract: one ``prefix_id`` names one token prefix. The store
VALIDATES (stored tokens must equal the new prompt's head) — a
mismatched id counts as a miss (and a ``mismatches`` tick), never a
wrong join. Entries are LRU-evicted under a byte budget
(``MXNET_SERVE_PREFIX_CACHE_MB``, default 64) charged in the static
memory planner (``analysis.memplan``) so ME801 gates HBM with the
store's worst case included.
"""
from __future__ import annotations

import os
from collections import OrderedDict

import numpy as np

__all__ = ["PrefixStore", "default_prefix_budget_bytes"]


def default_prefix_budget_bytes():
    """``MXNET_SERVE_PREFIX_CACHE_MB`` (docs/env_var.md), default 64
    MiB; 0 disables reuse."""
    try:
        mb = float(os.environ.get("MXNET_SERVE_PREFIX_CACHE_MB", "64"))
    except ValueError:
        mb = 64.0
    return int(max(0.0, mb) * (1 << 20))


class _Entry:
    __slots__ = ("tokens", "payloads", "nbytes", "hits", "mismatches")

    def __init__(self, tokens, payloads):
        self.tokens = np.asarray(tokens, np.int64).reshape(-1)
        self.payloads = payloads     # engine tag -> {cell name: rows}
        self.nbytes = self.tokens.nbytes + sum(
            arr.nbytes for rows in payloads.values()
            for arr in rows.values())
        self.hits = 0
        self.mismatches = 0


class PrefixStore:
    """LRU byte-budgeted map ``prefix_id -> (tokens, cache rows)``."""

    def __init__(self, budget_bytes=None):
        self.budget_bytes = int(budget_bytes
                                if budget_bytes is not None
                                else default_prefix_budget_bytes())
        self._entries = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.mismatches = 0
        self.evictions = 0

    def __len__(self):
        return len(self._entries)

    @property
    def used_bytes(self):
        return sum(e.nbytes for e in self._entries.values())

    def lookup(self, prefix_id, prompt, tags=(), least=1):
        """Hit test for one admission: returns ``(C, entry)`` — the
        usable cursor: the length of the longest head that ``prompt``
        and the stored tokens share (capped at ``len(prompt) - 1`` so
        the join always has at least one token left to feed, which the
        first dispatch samples from) — or ``(0, None)`` on miss: no
        entry, or a common head shorter than ``least``. ``tags`` names
        the engine payloads the caller needs (e.g. the draft engine's
        rows when speculation is armed): an entry missing one is a
        miss, not a half-join."""
        entry = self._entries.get(prefix_id)
        if entry is None:
            self.misses += 1
            return 0, None
        prompt = np.asarray(prompt, np.int64).reshape(-1)
        n = min(entry.tokens.shape[0], prompt.shape[0] - 1)
        differ = np.flatnonzero(entry.tokens[:n] != prompt[:n])
        c = int(differ[0]) if differ.size else n
        if c < max(1, least):
            entry.mismatches += 1
            self.mismatches += 1
            self.misses += 1
            return 0, None
        if any(tag not in entry.payloads for tag in tags):
            self.misses += 1
            return 0, None
        self._entries.move_to_end(prefix_id)
        entry.hits += 1
        self.hits += 1
        return c, entry

    def put(self, prefix_id, tokens, payloads):
        """Store (or refresh) one prefix. Oversized entries are
        dropped whole, and an entry under the same id that has served
        more prompts than it has failed stays (module docstring);
        otherwise LRU entries evict until the budget holds. Returns
        True when stored."""
        entry = _Entry(tokens, payloads)
        if self.budget_bytes <= 0 or entry.nbytes > self.budget_bytes:
            return False
        old = self._entries.get(prefix_id)
        if old is not None and old.hits > old.mismatches:
            return False
        self._entries.pop(prefix_id, None)
        while self._entries and \
                self.used_bytes + entry.nbytes > self.budget_bytes:
            self._entries.popitem(last=False)
            self.evictions += 1
        self._entries[prefix_id] = entry
        return True

    def stats(self):
        total = self.hits + self.misses
        return {
            "entries": len(self._entries),
            "bytes": self.used_bytes,
            "budget_bytes": self.budget_bytes,
            "hits": self.hits,
            "misses": self.misses,
            "mismatches": self.mismatches,
            "evictions": self.evictions,
            "hit_rate": round(self.hits / total, 4) if total else None,
        }
