"""Scripted load replay against a FakeClock-driven server.

* ``run_scripted`` — deterministic replay of explicit arrival times
  against a FakeClock server via ``pump()``: zero wall-clock sleeps,
  exact flush/deadline decisions, the tier-1 scheduler gate. Arrivals
  are open-loop: a slow server does not slow the script down.

``summarize`` folds completed handles into the req/s + latency
percentile + SLO-attainment dict it reports.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError

__all__ = ["run_scripted", "summarize"]


def summarize(handles, elapsed_s, slo_ms=None):
    """Fold handles into the load-test report dict.

    ``elapsed_s``: generator-side wall (or virtual) span the requests
    were offered over — the req/s denominator. ``slo_ms`` adds
    ``p99_within_slo`` (p99 latency <= SLO).
    """
    done = [h for h in handles if h.done() and h.exception() is None]
    lat = sorted(h.latency for h in done if h.latency is not None)
    misses = sum(1 for h in done if h.missed_deadline())

    def pct(q):
        if not lat:
            return None
        return round(lat[min(len(lat) - 1, int(q * len(lat)))] * 1e3, 3)

    out = {
        "offered": len(handles),
        "completed": len(done),
        "errors": sum(1 for h in handles
                      if h.done() and h.exception() is not None),
        "req_per_sec": round(len(done) / elapsed_s, 2) if elapsed_s else
        None,
        "latency_ms": {"p50": pct(0.50), "p90": pct(0.90),
                       "p99": pct(0.99),
                       "mean": round(float(np.mean(lat)) * 1e3, 3)
                       if lat else None},
        "deadline_misses": misses,
    }
    if slo_ms is not None:
        out["slo_ms"] = slo_ms
        out["p99_within_slo"] = (out["latency_ms"]["p99"] is not None
                                 and out["latency_ms"]["p99"] <= slo_ms)
    return out


def run_scripted(server, arrivals, make_input, model=None,
                 deadline_ms=None, slo_ms=None):
    """Deterministic replay: ``arrivals`` are absolute FakeClock times.

    The server must NOT be started — the script advances the clock to
    each arrival, submits, and ``pump()``s, then advances past the last
    deadline and pumps until drained. Everything (flush instants,
    latencies, percentiles) is exact and repeatable.
    """
    clock = server._clock
    if not hasattr(clock, "advance"):
        raise MXNetError("run_scripted needs a FakeClock-driven server")
    handles = []
    t_start = clock.now()
    for i, t in enumerate(sorted(arrivals)):
        if t > clock.now():
            # walk deadline boundaries between now and the arrival so
            # flushes fire at their exact scheduled instants
            while True:
                with server._lock:
                    action, wait = server._registry.next_action(
                        clock.now())
                if action != "wait" or wait is None or \
                        clock.now() + wait > t:
                    break
                clock.advance(wait)
                server.pump()
            clock.advance(max(0.0, t - clock.now()))
        server.pump()
        handles.append(server.submit(
            make_input(i, np.random.RandomState(i)), model=model,
            deadline_ms=deadline_ms))
        server.pump()
    # drain: advance through remaining flush instants
    while any(len(e.queue) for e in server._registry.entries()):
        with server._lock:
            action, wait = server._registry.next_action(clock.now())
        if action == "wait":
            if wait is None:
                raise MXNetError("scripted drain stuck: queued work "
                                 "with no flush deadline")
            clock.advance(wait)
        server.pump()
    return summarize(handles, clock.now() - t_start, slo_ms=slo_ms)
