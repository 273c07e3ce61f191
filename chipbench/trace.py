"""From the JAX profiler's trace to numbers.

``flatten`` turns an ``.xplane.pb`` into plain events
``{"plane", "line", "name", "start_ns", "dur_ns"}``; everything else
works on those, so that ``testdata/`` can hold a small recorded trace
as JSON and the tests check the same code the chip run uses.

On a TPU each chip is a plane ``/device:TPU:<n>`` with a line
``XLA Modules`` (one event for each run of a compiled program) and a
line ``XLA Ops`` (one event for each operation inside it); the host is
the plane ``/host:CPU`` with a line for each thread.
"""
from __future__ import annotations

import glob
import os
import re

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")


def flatten(trace_dir):
    """Events of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return []
    data = ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (MODULE_LINE, OP_LINE):
                continue
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": ev.start_ns,
                            "dur_ns": ev.duration_ns})
    return out


def device_planes(events):
    return sorted({e["plane"] for e in events
                   if e["plane"].startswith("/device:")})


def _intervals(events):
    return sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                  for e in events)


def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(intervals):
    return sum(b - a for a, b in intervals)


def _subtract(intervals, cover):
    """Total length of ``intervals`` not covered by ``cover`` (both
    merged)."""
    total = 0
    j = 0
    for a, b in intervals:
        pos = a
        while j < len(cover) and cover[j][1] <= pos:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > pos:
                total += cover[k][0] - pos
            pos = max(pos, cover[k][1])
            k += 1
        if pos < b:
            total += b - pos
    return total


def _ops(events, plane):
    return [e for e in events if e["plane"] == plane
            and e["line"] == OP_LINE]


def window(events):
    """(start_ns, end_ns) of the traced window: the span of the device
    events."""
    dev = [e for e in events if e["plane"].startswith("/device:")]
    if not dev:
        return None
    return (min(e["start_ns"] for e in dev),
            max(e["start_ns"] + e["dur_ns"] for e in dev))


def busy(events, span=None):
    """``(busy_s, window_s)``: seconds in which an operation ran on the
    device (union of the XLA Ops intervals), averaged over the chips,
    and the length of the window."""
    span = span or window(events)
    if span is None:
        return 0.0, 0.0
    planes = device_planes(events)
    total = 0
    for plane in planes:
        merged = union(_intervals(_ops(events, plane)))
        total += _length([(max(a, span[0]), min(b, span[1]))
                          for a, b in merged
                          if b > span[0] and a < span[1]])
    return total / len(planes) / 1e9, (span[1] - span[0]) / 1e9


def modules(events):
    """``{module name: {plane: [dur_ns, ...]}}`` from the XLA Modules
    lines. A name carries the program's fingerprint, so two programs of
    one jitted function are two names."""
    out = {}
    for e in events:
        if e["line"] == MODULE_LINE and e["plane"].startswith("/device:"):
            out.setdefault(e["name"], {}).setdefault(
                e["plane"], []).append(e["dur_ns"])
    return out


def ranked_modules(events, min_runs=3):
    """Module names by median run time, longest first, of those that
    ran at least ``min_runs`` times on some chip: the step programs come
    before the one-operation programs of host bookkeeping."""
    from .stats import median
    rows = []
    for name, per_plane in modules(events).items():
        runs = max(per_plane.values(), key=len)
        if len(runs) >= min_runs:
            rows.append((median(runs), name))
    return [name for _m, name in sorted(rows, reverse=True)]


def module_ms(events, name):
    """Median run time of one program in ms; over several chips, the
    slowest chip's."""
    from .stats import median
    per_plane = modules(events).get(name)
    if not per_plane:
        return None
    return max(median(runs) for runs in per_plane.values()) / 1e6


def exposed_collective_ms(events, per_runs_of=None):
    """Collective-operation time during which no other operation runs
    on that chip, in ms: the slowest chip's total, divided by the runs
    of program ``per_runs_of`` when given (per step)."""
    worst = None
    for plane in device_planes(events):
        ops = _ops(events, plane)
        coll = union(_intervals(
            [e for e in ops if _COLLECTIVE.search(e["name"])]))
        comp = union(_intervals(
            [e for e in ops if not _COLLECTIVE.search(e["name"])]))
        exposed = _subtract(coll, comp)
        worst = exposed if worst is None else max(worst, exposed)
    if worst is None:
        return None
    runs = 1
    if per_runs_of is not None:
        per_plane = modules(events).get(per_runs_of, {})
        runs = max([len(r) for r in per_plane.values()] + [1])
    return worst / runs / 1e6


def _short(name, limit=80):
    """An operation's trace name is its whole HLO line; keep the name."""
    name = name.split(" = ")[0].lstrip("%")
    return name if len(name) <= limit else name[:limit - 1] + "~"


def breakdown(events, top=10):
    """``{"device_ops": [[name, seconds]...], "idle_gaps": [[name,
    seconds]...]}``: the operations that took most device time (first
    chip), and the idle time of that chip by what the host was doing -
    each gap goes to the host event that overlaps it longest."""
    planes = device_planes(events)
    if not planes:
        return None
    ops = _ops(events, planes[0])
    by_op = {}
    for e in ops:
        by_op[e["name"]] = by_op.get(e["name"], 0) + e["dur_ns"]
    device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]

    merged = union(_intervals(ops))
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
            if b[0] - a[1] > 0]
    host = sorted(((e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                   for e in events if e["plane"].startswith("/host:")),
                  key=lambda h: h[0])
    by_host = {}
    live, nxt = [], 0               # host events that may still overlap
    for g0, g1 in gaps:
        while nxt < len(host) and host[nxt][0] < g1:
            live.append(host[nxt])
            nxt += 1
        live = [h for h in live if h[1] > g0]
        # the innermost host event that covers at least half of the gap,
        # else the one that overlaps it longest
        best, best_key = "no_host_event", None
        for h0, h1, name in live:
            ov = min(h1, g1) - max(h0, g0)
            if ov <= 0:
                continue
            half = 2 * ov >= g1 - g0
            key = (half, -(h1 - h0) if half else ov)
            if best_key is None or key > best_key:
                best, best_key = name, key
        by_host[best] = by_host.get(best, 0) + (g1 - g0)
    idle_gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[_short(n), s / 1e9] for n, s in device_ops],
            "idle_gaps": [[_short(n), s / 1e9] for n, s in idle_gaps]}


def summary(events, names=12):
    """What a trace holds, for a reader: every plane and line with its
    event count and commonest names, and the programs by run time."""
    from .stats import median
    lines = {}
    for e in events:
        key = f"{e['plane']} | {e['line']}"
        row = lines.setdefault(key, {"events": 0, "names": {}})
        row["events"] += 1
        row["names"][e["name"]] = row["names"].get(e["name"], 0) + 1
    for row in lines.values():
        top = sorted(row["names"].items(), key=lambda kv: -kv[1])[:names]
        row["names"] = {_short(n, 120): c for n, c in top}
    mods = {name: {plane: {"runs": len(r), "median_ms": median(r) / 1e6,
                           "total_ms": sum(r) / 1e6}
                   for plane, r in per_plane.items()}
            for name, per_plane in modules(events).items()}
    return {"lines": lines, "modules": mods,
            "ranked_modules": ranked_modules(events)}


def head(events, seconds):
    """The events that start in the first ``seconds`` of the device
    window, times rebased to it: a slice small enough to keep as test
    data."""
    span = window(events)
    if span is None:
        return []
    end = span[0] + seconds * 1e9
    return [dict(e, start_ns=e["start_ns"] - span[0]) for e in events
            if span[0] <= e["start_ns"] < end]


def pack(events):
    """Events as rows over string tables, for a recorded trace kept as
    test data: ``{"planes", "lines", "names", "rows"}``."""
    tables = {"planes": [], "lines": [], "names": []}
    index = {k: {} for k in tables}

    def ref(kind, value):
        if value not in index[kind]:
            index[kind][value] = len(tables[kind])
            tables[kind].append(value)
        return index[kind][value]

    rows = [[ref("planes", e["plane"]), ref("lines", e["line"]),
             ref("names", e["name"]), int(e["start_ns"]), int(e["dur_ns"])]
            for e in events]
    return dict(tables, rows=rows)


def unpack(packed):
    return [{"plane": packed["planes"][p], "line": packed["lines"][l],
             "name": packed["names"][n], "start_ns": s, "dur_ns": d}
            for p, l, n, s, d in packed["rows"]]
