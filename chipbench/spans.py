"""Readers of the program's own spans in the profiler's trace.

The program annotates its host phases on the profiler's clock
(``mxnet_tpu.telemetry.span`` -> ``jax.profiler.TraceAnnotation``), so
they lie in ``obs["events"]`` (chipbench/trace.py) on a ``/host:`` plane
beside the device's ``XLA Ops``. The names are the program's contract
(docs/telemetry.md, "Profiler annotations"); a program that writes none
- the parent of the PR that added them - gives every reader here None,
and the metric is left out of the line.
"""
from __future__ import annotations

import bisect

from . import trace
from .stats import median, percentile


def host_events(obs, name):
    """The host events called ``name`` as ``(start_ns, end_ns)``, by
    start. A TraceMe's name may carry its arguments after a ``#``."""
    return sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                  for e in obs.get("events") or []
                  if e["plane"].startswith("/host:")
                  and e["name"].split("#")[0] == name)


def median_ms(obs, name):
    """Median duration of the host events called ``name``, in ms."""
    spans = host_events(obs, name)
    if not spans:
        return None
    return median([b - a for a, b in spans]) / 1e6


def device_busy(obs):
    """Merged intervals in which an operation ran on the first chip."""
    events = obs.get("events") or []
    planes = trace.device_planes(events)
    if not planes:
        return []
    return trace.union(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
        if e["plane"] == planes[0] and e["line"] == trace.OP_LINE)


def idle_ns(busy, start, end):
    """Length of ``[start, end)`` that no interval of ``busy`` (merged,
    sorted) covers."""
    covered = 0
    i = max(0, bisect.bisect_right(busy, [start, float("inf")]) - 1)
    while i < len(busy) and busy[i][0] < end:
        covered += max(0, min(busy[i][1], end) - max(busy[i][0], start))
        i += 1
    return (end - start) - covered


def idle_ms_p50(obs, name):
    """Per host event called ``name``, the ms of it in which no device
    operation ran; the median."""
    spans, busy = host_events(obs, name), device_busy(obs)
    if not spans or not busy:
        return None
    return median([idle_ns(busy, a, b) for a, b in spans]) / 1e6


def idle_between_ms_p50(obs, outer, inner):
    """Per host event called ``outer`` that another one follows: the
    device-idle ms from its start to the next one's start, outside its
    children called ``inner`` - the idle time nobody waiting on the
    device accounts for. The median."""
    outers, busy = host_events(obs, outer), device_busy(obs)
    if len(outers) < 2 or not busy:
        return None
    children = sorted(s for name in inner for s in host_events(obs, name))
    vals = []
    for (start, _end), (nxt, _) in zip(outers, outers[1:]):
        idle = idle_ns(busy, start, nxt)
        for a, b in children:
            if start <= a and b <= nxt:
                idle -= idle_ns(busy, a, b)
        vals.append(idle)
    return median(vals) / 1e6


def ring_span_percentile_ms(obs, name, q):
    """Nearest-rank ``q``-th percentile of ``dur_us`` over the flight
    ring's ``trace.span`` records called ``name`` (the request trace
    plane mirrors every span there), in ms."""
    vals = [r["dur_us"] for r in obs.get("ring") or []
            if r.get("kind") == "trace.span" and r.get("name") == name
            and "dur_us" in r]
    if not vals:
        return None
    return percentile(vals, q) / 1e3
