"""Flight recorder: always-on bounded activity ring + crash reports.

The span tracer (core.py) is off by default because a full trace of a
long run is unbounded; but when a pod job OOMs, stalls, or diverges at
3am, the evidence is gone unless *something* was recording. The flight
recorder is that something: a fixed-size ring (``collections.deque``
with ``maxlen``) of the most recent activity — batch boundaries,
executor dispatches, kvstore traffic, anomaly events — cheap enough to
leave on for every production run (one dict build + deque append per
record).

Two feeds fill the ring:

* **always-on notes** at the framework's cardinal sites (Module.fit's
  batch loop, executor dispatch, KVStore push/pull) — these fire even
  with the span tracer disabled, so an uninstrumented run still leaves
  a timeline;
* **mirrored spans/events** whenever the tracer IS enabled (core.py
  forwards every finished span and instant event here), so an enabled
  run gets the full-resolution tail for free.

On any exception escaping ``Executor.forward/backward``, ``Module.fit``,
or KVStore push/pull, ``on_crash`` writes a crash report — ring
contents, metrics-registry snapshot, per-context memory watermarks
(telemetry.memory), jax device/backend info, filtered env — as one JSON
file in ``MXNET_CRASH_DIR`` (default: ``mxnet_crash/`` under the
system's temporary directory), exactly
once per exception. ``tools/diagnose.py`` renders it human-readable.

Pure stdlib at import time (jax is touched only inside dump_crash), so
any layer can import this module without ordering constraints.
"""
from __future__ import annotations

import collections
import json
import logging
import os
import sys
import tempfile
import threading
import time
import traceback

from . import fleet as _fleet

__all__ = ["note", "note_event", "note_span", "enabled", "configure",
           "get_records", "clear", "on_crash", "dump_crash"]

log = logging.getLogger(__name__)

_DEFAULT_CAPACITY = 512

_enabled = os.environ.get("MXNET_FLIGHT_RECORDER", "1") != "0"
_ring = collections.deque(maxlen=max(1, int(os.environ.get(
    "MXNET_FLIGHT_RECORDER_CAPACITY", _DEFAULT_CAPACITY))))
_dump_dir = os.environ.get(
    "MXNET_CRASH_DIR", os.path.join(tempfile.gettempdir(), "mxnet_crash"))
_dump_lock = threading.Lock()
_dump_seq = 0


def enabled():
    return _enabled


def configure(capacity=None, dump_dir=None, enabled=None):
    """Adjust the recorder (ring size, crash-dump directory, on/off).

    Resizing preserves the newest entries that still fit. Defaults come
    from MXNET_FLIGHT_RECORDER / MXNET_FLIGHT_RECORDER_CAPACITY /
    MXNET_CRASH_DIR at import time.
    """
    global _ring, _dump_dir, _enabled
    if capacity is not None:
        _ring = collections.deque(_ring, maxlen=max(1, int(capacity)))
    if dump_dir is not None:
        _dump_dir = dump_dir
    if enabled is not None:
        _enabled = bool(enabled)


def note(kind, **info):
    """Append one record to the ring (no-op while disabled).

    Kept deliberately thin — one dict build, one clock read, one deque
    append — because the always-on sites sit on the training hot path.
    """
    if not _enabled:
        return
    rec = {"kind": kind, "ts_us": time.perf_counter_ns() // 1000, **info}
    if _fleet.tagged():
        rec["rank"] = _fleet.rank()
    _ring.append(rec)


def note_event(rec):
    """Mirror a core.event() record (already timestamped) into the ring."""
    if not _enabled:
        return
    out = {"kind": rec["kind"], "ts_us": rec["ts_us"], **rec["payload"]}
    if _fleet.tagged():
        out["rank"] = _fleet.rank()
    _ring.append(out)


def note_span(span):
    """Mirror a finished core.Span into the ring."""
    if not _enabled:
        return
    rec = {"kind": "span", "name": span.name, "ts_us": span.ts,
           "dur_us": span.dur, **span.args}
    if _fleet.tagged():
        rec["rank"] = _fleet.rank()
    _ring.append(rec)


def get_records():
    """The ring's contents, oldest first."""
    return list(_ring)


def clear():
    _ring.clear()


# ------------------------------------------------------------ crash dumps
def on_crash(exc, where):
    """Dump a crash report for ``exc`` exactly once; never raises.

    Nested instrumentation (an executor failure inside Module.fit) hits
    several guards with the same exception — the dump path is memoized
    on the exception object so only the innermost guard writes a file.
    Returns the report path (or None when disabled / dump failed).
    """
    if not _enabled:
        return None
    existing = getattr(exc, "_mx_crash_dump", None)
    if existing is not None:
        return existing
    try:
        path = dump_crash(exc=exc, where=where)
    except Exception:
        return None          # a broken dump must never mask the crash
    try:
        exc._mx_crash_dump = path
    except Exception:
        pass
    return path


def dump_crash(exc=None, where="", extra=None):
    """Write a crash report JSON into the configured directory.

    The report carries everything an operator needs to debug a dead run
    after the fact: the activity ring, the metrics registry, per-context
    memory watermarks, device/backend identity, and the MXNET_*/JAX_*/
    XLA_*/DMLC_* environment. Returns the written path.
    """
    global _dump_seq
    report = _build_report(exc, where, extra)
    os.makedirs(_dump_dir, exist_ok=True)
    with _dump_lock:
        _dump_seq += 1
        seq = _dump_seq
    fname = f"mxnet_crash_{os.getpid()}_{seq}.json"
    path = os.path.join(_dump_dir, fname)
    with open(path, "w") as f:
        json.dump(report, f, indent=2, default=str)
    log.error("crash report written to %s (while in %s)", path,
              where or "unknown")
    return path


def _build_report(exc, where, extra):
    report = {
        "type": "crash_report",
        "version": 1,
        "time_unix": time.time(),
        "time": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "where": where,
        "pid": os.getpid(),
        "rank": _fleet.rank(),
        "host": _fleet.host(),
        "argv": list(sys.argv),
        "ring": get_records(),
    }
    if exc is not None:
        report["exception"] = {
            "type": type(exc).__name__,
            "message": str(exc),
            "traceback": traceback.format_exception(
                type(exc), exc, exc.__traceback__),
        }
    try:
        from . import metrics as _metrics
        report["metrics"] = _metrics.snapshot()
    except Exception as e:
        report["metrics_error"] = repr(e)
    try:
        from . import memory as _memory
        report["memory"] = _memory.snapshot()
    except Exception as e:
        report["memory_error"] = repr(e)
    try:
        import jax
        report["backend"] = jax.default_backend()
        report["devices"] = [
            {"id": d.id, "platform": d.platform,
             "device_kind": d.device_kind,
             "process_index": d.process_index}
            for d in jax.local_devices()]
    except Exception as e:            # never require a live backend
        report["devices_error"] = repr(e)
    report["env"] = {
        k: v for k, v in sorted(os.environ.items())
        if k.startswith(("MXNET_", "JAX_", "XLA_", "DMLC_", "PS_", "TPU_"))}
    if extra:
        report["extra"] = extra
    return report
