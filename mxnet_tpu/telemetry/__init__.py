"""Unified telemetry: structured spans + metrics registry + exporters.

The measurement layer the reference implements engine-side in
``src/engine/profiler.cc`` (per-op exec records -> chrome://tracing via
MXDumpProfile), rebuilt framework-wide: every layer — executor
(compile/run), KVStore (push/pull/collectives), the IO pipeline, and
Module.fit — records into ONE process-wide tracer + registry, and three
exporters serialize it:

* ``telemetry.chrome_trace`` — chrome://tracing / Perfetto JSON (also
  reachable through the reference-shaped ``mx.profiler.dump_profile()``);
* ``telemetry.prometheus`` — Prometheus text exposition format;
* ``telemetry.jsonl`` — JSON-lines event log (tools/parse_log.py reads it).

Usage::

    mx.telemetry.enable()                      # off by default
    with mx.telemetry.span("my.phase", step=3):
        ...
    mx.telemetry.counter("my.items").inc(8)
    mx.telemetry.snapshot()                    # everything, as one dict
    mx.telemetry.chrome_trace.dump("trace.json")

Naming conventions: dotted lowercase ``layer.what[.unit]`` —
``executor.compile``, ``kvstore.push.bytes``, ``io.next.seconds``,
``module.fit.batch.seconds``. Histograms end in a unit; counters of
bytes end in ``.bytes``. Off by default: the disabled fast path is one
branch per site (0.43 us a disabled span site on the v5e host;
PERF.md section 6, PR 25).

On top of the tracer/registry sits the always-on diagnostics layer:

* ``telemetry.flightrec`` — bounded ring of recent activity + crash
  reports on exceptions escaping Executor/Module.fit/KVStore;
* ``telemetry.memory`` — per-context live/peak byte accounting over
  NDArray handles, ``assert_no_leak()`` for tests;
* ``telemetry.sentinel`` — opt-in NaN/Inf tripwire (``NanSentinel``)
  with warn-vs-raise policy and op/array attribution;
* ``tools/diagnose.py`` — renders a crash report or jsonl event log
  into a human-readable health report.
"""
from __future__ import annotations

from .core import (span, event, record_event, enable, disable, enabled,
                   clear, get_spans, get_events, null_span, wrap_dispatch)
from .metrics import (Counter, Gauge, Histogram, counter, gauge, histogram,
                      get_metric)
from .sentinel import NanSentinel, AnomalyError
from . import core
from . import metrics
from . import fleet
from . import flightrec
from . import memory
from . import mfu
from . import optable
from . import sentinel
from . import trace
from . import stepattr
from . import health
from . import chrome_trace
from . import prometheus
from . import jsonl
from . import opsd
from .opsd import serve_ops

__all__ = ["span", "event", "record_event", "enable", "disable", "enabled",
           "clear", "get_spans", "get_events", "null_span", "wrap_dispatch",
           "Counter", "Gauge", "Histogram", "counter", "gauge", "histogram",
           "get_metric", "snapshot", "reset", "NanSentinel", "AnomalyError",
           "fleet", "flightrec", "memory", "mfu", "optable", "sentinel",
           "trace",
           "stepattr", "health", "chrome_trace", "prometheus", "jsonl",
           "opsd", "serve_ops"]


def snapshot():
    """The whole training step at a glance: the metrics registry plus
    span/event buffer depths and per-context memory watermarks."""
    snap = metrics.snapshot()
    snap["spans"] = len(core.get_spans())
    snap["events"] = len(core.get_events())
    snap["memory"] = memory.snapshot()
    snap["rank"] = fleet.rank()
    return snap


def reset():
    """Clear spans, events, the metrics registry, the flight-recorder
    ring, the trace-plane buffer and the step-attribution records; drop
    memory peak watermarks to current live (live accounting tracks real
    handles and is never cleared). The enabled/disabled switch is left
    as-is."""
    core.clear()
    metrics.reset()
    flightrec.clear()
    trace.clear()
    stepattr.reset()
    health.reset()
    memory.reset_peak()


# arm the live ops endpoint when the env asks for one (no-op otherwise)
opsd.maybe_serve_from_env()
