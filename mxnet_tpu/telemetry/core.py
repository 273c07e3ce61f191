"""Span tracer core: one span API, three sinks.

The reference collects per-op exec records engine-side into
``profiler.cc``'s ProfileStat ring and serializes them to chrome://tracing
JSON on MXDumpProfile. Here the analogous record is a *span*: a named,
nested interval. ``span(name, **args)`` is the only call-site API, and
what it writes depends on who is listening:

* **the JAX profiler** (always, once ``jax`` is in the process): the
  span is a ``jax.profiler.TraceAnnotation`` - a no-op in C++ while no
  profiler session runs (0.4 us a site), and while one runs an event on
  the calling thread's line of ``/host:CPU``, under its plain name with
  the keyword arguments as stats, on the one clock the device trace
  shares. This is how a host phase is put next to the device's idle gaps.
* **the span buffer** (``telemetry.enable()``, off by default because a
  long run's spans are unbounded): a ``Span`` measured with
  ``time.perf_counter_ns`` carrying the thread/process ids
  chrome://tracing wants, kept until an exporter (chrome_trace,
  prometheus, jsonl) drains a copy; ``clear()`` resets between runs.
  An enabled ``Span`` opens the profiler annotation too.
* **the flight ring** (always on, bounded): finished ``Span``s are
  mirrored into it, and the hot loops write their own records there with
  the durations the spans enclose (``serve.decode.step``,
  ``module.fit.batch``), so an operator joins ring and trace by a shared
  field such as ``iter``.

Design constraints:

* **Near-zero when nobody listens.** With the buffer disabled and no
  profiler session a site costs one function call, one branch and one
  small C++ object (0.43 us on the v5e host; PERF.md section 6, PR 25).
* **Thread-safe.** The span *stack* (for parent attribution) is
  thread-local; the finished-span buffer is shared under one lock, so
  PrefetchingIter's producer thread and the main loop interleave safely.
* **Pure stdlib at import.** ``jax`` is never imported here: a process
  that has not imported it (the launcher, the mp-decode workers) gets
  the shared ``null_span``, so any layer of the framework can import
  telemetry without ordering constraints.
"""
from __future__ import annotations

import os
import sys
import threading
import time

from . import flightrec as _flightrec

__all__ = ["span", "event", "record_event", "enable", "disable", "enabled",
           "clear", "get_spans", "get_events", "null_span", "wrap_dispatch",
           "watch_compiles", "backend_compiles"]

_lock = threading.Lock()
_local = threading.local()
_spans = []        # finished Span objects, completion order
_events = []       # instant events: dicts with kind/ts_us/pid/tid/payload
_enabled = False


def _stack():
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class _NullSpan:
    """Shared do-nothing span for the disabled fast path."""

    __slots__ = ()
    dur = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kwargs):
        return self


null_span = _NullSpan()

_annotation_cls = None


def _annotation():
    """The profiler-annotation class of this process, or None while
    ``jax`` has not been imported in it (never imported from here)."""
    global _annotation_cls
    if _annotation_cls is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return None

        class Annotation(profiler.TraceAnnotation):
            """A profiler annotation with ``null_span``'s surface."""

            __slots__ = ()
            dur = 0

            def set(self, **kwargs):
                self.set_metadata(**kwargs)
                return self

        _annotation_cls = Annotation
    return _annotation_cls


class Span:
    """One named interval. ``ts``/``dur`` are microseconds on the
    perf_counter timeline (chrome://tracing's native unit)."""

    __slots__ = ("name", "args", "ts", "dur", "pid", "tid", "parent",
                 "depth", "_hist", "_annotation")

    def __init__(self, name, args, hist=None):
        self.name = name
        self.args = args
        self.ts = 0
        self.dur = 0
        self.pid = os.getpid()
        self.tid = threading.get_ident()
        self.parent = None
        self.depth = 0
        self._hist = hist
        self._annotation = None

    def set(self, **kwargs):
        self.args.update(kwargs)
        if self._annotation is not None:
            self._annotation.set_metadata(**kwargs)
        return self

    def __enter__(self):
        cls = _annotation()
        if cls is not None:
            self._annotation = cls(self.name, **self.args)
            self._annotation.__enter__()
        st = _stack()
        if st:
            self.parent = st[-1].name
            self.depth = len(st)
        st.append(self)
        self.ts = time.perf_counter_ns() // 1000
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur = time.perf_counter_ns() // 1000 - self.ts
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        with _lock:
            _spans.append(self)
        _flightrec.note_span(self)   # ring keeps the tail post-mortem
        if self._hist is not None:
            from .metrics import histogram
            histogram(self._hist).observe(self.dur / 1e6)
        return False


def span(name, _hist=None, **args):
    """Context manager around a named interval.

    While the span buffer is disabled this is the profiler's annotation
    alone (a no-op in C++ unless a profiler session runs), or the shared
    ``null_span`` in a process without ``jax``. ``_hist`` names a
    histogram that additionally receives an enabled span's duration in
    seconds, so one call site feeds both the trace and the registry.
    """
    if _enabled:
        return Span(name, args, hist=_hist)
    cls = _annotation()
    if cls is None:
        return null_span
    return cls(name, **args)


def event(kind, **payload):
    """Record an instant event (chrome 'i' phase / one jsonl line)."""
    if not _enabled:
        return
    rec = {"kind": kind, "ts_us": time.perf_counter_ns() // 1000,
           "pid": os.getpid(), "tid": threading.get_ident(),
           "payload": payload}
    with _lock:
        _events.append(rec)
    _flightrec.note_event(rec)


# the structured-log spelling of the same record (jsonl exporter)
record_event = event


def enable():
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def enabled():
    return _enabled


def clear():
    """Drop buffered spans/events (metrics have their own reset)."""
    with _lock:
        del _spans[:]
        del _events[:]


def get_spans():
    with _lock:
        return list(_spans)


def get_events():
    with _lock:
        return list(_events)


def wrap_dispatch(fn, kind, compiled=True):
    """Wrap a (possibly jitted) program so each dispatch records a span.

    The first dispatch of a jitted program is where jax traces + XLA
    compiles, so it reports as ``executor.compile`` (the analog of the
    reference's graph-init segment in its profile) and every later one as
    ``executor.run``. Uncompiled programs (NaiveEngine) always report
    ``executor.run``. Disabled telemetry costs one extra frame + branch.

    Every call additionally bumps the untagged ``executor.dispatch``
    counter — the per-step host→device submission count that the K-step
    scan dispatch amortizes.
    """
    state = {"first": compiled}

    def dispatch(*args):
        first, state["first"] = state["first"], False
        name = "executor.compile" if first else "executor.run"
        if not _enabled:
            with span(name, kind=kind):
                if not _flightrec._enabled:
                    return fn(*args)
                # always-on flight-recorder timing of the XLA dispatch —
                # the crash-report timeline's backbone when tracing is off
                t0 = time.perf_counter_ns()
                try:
                    return fn(*args)
                finally:
                    _flightrec.note(
                        name, program=kind,
                        dur_us=(time.perf_counter_ns() - t0) // 1000)
        from .metrics import counter
        counter("executor.dispatch").inc()
        counter(name + ".calls", kind=kind).inc()
        with Span(name, {"kind": kind}, hist=name + ".seconds"):
            return fn(*args)

    dispatch.__wrapped__ = fn
    if hasattr(fn, "lower"):     # keep jitted introspection reachable
        dispatch.lower = fn.lower
    return dispatch


# ---------------------------------------------------------- XLA compiles
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_backend_compiles = 0
_watching_compiles = False


def _on_compile(event, duration, **kw):
    global _backend_compiles
    if event != _COMPILE_EVENT:
        return
    with _lock:
        _backend_compiles += 1
    from .metrics import counter
    counter("xla.compile.count").inc()
    counter("xla.compile.seconds").inc(duration)
    _flightrec.note("xla.compile", fun_name=kw.get("fun_name"),
                    dur_us=int(duration * 1e6))


def watch_compiles():
    """Register, once a process, the ``jax.monitoring`` listener behind
    ``xla.compile.count`` / ``xla.compile.seconds`` and the ``xla.compile``
    ring record (``fun_name``, ``dur_us``): one for every program XLA's
    backend compiled or read from the persistent cache, the eager
    one-operation programs included - what ``program_cache.
    compile_count()`` (traces entering the program cache) cannot see.
    Called where the program first imports jax (context.py)."""
    global _watching_compiles
    if _watching_compiles:
        return
    _watching_compiles = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_compile)


def backend_compiles():
    """Backend compiles of this process so far (monotone: unlike the
    ``xla.compile.count`` counter, ``telemetry.reset()`` leaves it)."""
    return _backend_compiles
