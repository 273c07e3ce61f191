"""The fixed set of reducers behind ``layers/<metric>.json``.

A declaration names one reducer and its parameters; the reducer takes
the metric from what the traced run observed (``obs``) and returns a
float, or None when there is nothing to read - the harness then leaves
the metric out of the line. ``obs`` holds:

  counters   {name: increase over the window} of telemetry counters
  ring       the flight ring's records inside the window, oldest first
  stepattr   telemetry.stepattr records of the window's steps
  series     client-side series, e.g. {"ttft_s": [...]}
  events     the flat profiler trace (chipbench/trace.py), or None
  cost       {name: {"flops", "bytes"}} for this cell (chipbench/costs.py)
  device_kind, chips

A metric that needs more than these is a ``layers/<metric>.py`` with
``read(obs)``.
"""
from __future__ import annotations

from . import costs, trace
from .stats import median, percentile

_OPS = {"gt": lambda a, b: a > b, "eq": lambda a, b: a == b,
        "ge": lambda a, b: a >= b, "lt": lambda a, b: a < b}


def _match(rec, where):
    if not where:
        return True
    if where["field"] not in rec:
        return False
    return _OPS[where["op"]](rec[where["field"]], where["value"])


def _ring(obs, decl):
    return [r for r in obs.get("ring") or [] if r.get("kind") == decl["kind"]]


def counter_ratio(obs, decl):
    c = obs.get("counters") or {}
    num, den = c.get(decl["num"]), c.get(decl["den"])
    if not num or not den:
        return None
    return decl.get("scale", 1.0) * num / den


def ring_share(obs, decl):
    recs = _ring(obs, decl)
    if not recs:
        return None
    hit = sum(1 for r in recs if _match(r, decl.get("where")))
    return decl.get("scale", 100.0) * hit / len(recs)


def ring_p50(obs, decl):
    vals = [r[decl["field"]] for r in _ring(obs, decl)
            if _match(r, decl.get("where")) and decl["field"] in r]
    if not vals:
        return None
    return decl.get("scale", 1.0) * median(vals)


def ring_interval_p50(obs, decl):
    """Time from one record to the next (the later record's iteration)."""
    recs = _ring(obs, decl)
    vals = [rec["ts_us"] - prev["ts_us"] for prev, rec in zip(recs, recs[1:])
            if _match(rec, decl.get("where"))]
    if not vals:
        return None
    return decl.get("scale", 1.0) * median(vals)


def stepattr_p50(obs, decl):
    recs = obs.get("stepattr") or []
    vals = [sum(r["phases_us"].get(p, 0) for p in decl["phases"])
            / max(1, r.get("steps", 1)) for r in recs]
    if not vals:
        return None
    return decl.get("scale", 1.0) * median(vals)


def series_percentile(obs, decl):
    vals = (obs.get("series") or {}).get(decl["series"])
    if not vals:
        return None
    v = percentile(vals, decl["q"])
    return None if v is None else decl.get("scale", 1.0) * v


def _module(obs, decl):
    events = obs.get("events")
    if not events:
        return None
    ranked = trace.ranked_modules(events)
    rank = decl.get("rank", 0)
    return ranked[rank] if rank < len(ranked) else None


def module_ms(obs, decl):
    name = _module(obs, decl)
    return None if name is None else trace.module_ms(obs["events"], name)


def module_roofline(obs, decl):
    """Least time for the program's work over its measured device time,
    in percent. Never clipped: a reading over 100 means the cost or the
    time is wrong."""
    name = _module(obs, decl)
    cost = (obs.get("cost") or {}).get(decl["cost"])
    if name is None or cost is None:
        return None
    ms = trace.module_ms(obs["events"], name)
    least_s, _bound = costs.roofline(cost, obs["device_kind"],
                                     obs.get("chips", 1))
    return 100.0 * least_s * 1e3 / ms


def exposed_collective_ms(obs, decl):
    events = obs.get("events")
    if not events:
        return None
    return trace.exposed_collective_ms(events, per_runs_of=_module(obs, decl))


REDUCERS = {f.__name__: f for f in (
    counter_ratio, ring_share, ring_p50, ring_interval_p50, stepattr_p50,
    series_percentile, module_ms, module_roofline, exposed_collective_ms)}


def read(metric, obs):
    """The value of one per-layer ``Metric`` (chipbench/manifest.py), or
    None."""
    if metric.reader is not None:
        return metric.reader(obs)
    decl = metric.decl
    if decl["reducer"] not in REDUCERS:
        raise KeyError(f"layers/{metric.name}.json names reducer "
                       f"{decl['reducer']!r}; there are {sorted(REDUCERS)}")
    return REDUCERS[decl["reducer"]](obs, decl)
