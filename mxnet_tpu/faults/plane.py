"""Deterministic fault-injection plane: named points, scripted triggers.

The chaos suite (tests/test_chaos.py) can produce exactly one failure
mode — a killed process — and only at @slow multi-process cost. Every
other failure seam the robustness story cares about (a full disk under
the checkpoint writer, a transient collective error, a corrupt record
in the decode pipeline, a dispatch failure in the serving engine) was
untestable deterministically. This module is the FakeClock of failures:
each seam declares a *named injection point*::

    from mxnet_tpu import faults
    faults.point("ckpt.write", seq=seq)

and an operator/test arms the plane with a scripted trigger per point::

    MXNET_FAULTS="ckpt.write:nth=2;io.decode:prob=0.1,seed=7"
    # or programmatically, scoped:
    with faults.scope("kvstore.collective:nth=1"):
        ...

Trigger grammar (per point, comma-separated ``key=value`` tokens after
the ``point:`` prefix; see docs/faults.md for the catalog):

==================  ====================================================
``once``            fire on the first call only (= ``nth=1``)
``always``          fire on every call
``nth=N``           fire on exactly the Nth call (1-based)
``every=N``         fire on every Nth call
``first=K``         fire on the first K calls
``prob=P``          fire with probability P per call, from a private
                    ``random.Random(seed)`` stream (``seed=S``,
                    default 0) — deterministic across runs
``latency=D``       inject a delay instead of an error (``50ms``,
                    ``0.5s``, or bare seconds)
``error=KIND``      exception class to raise: ``fault`` (default,
                    :class:`InjectedFault`), ``os``, ``runtime``,
                    ``conn``, ``timeout``, ``value``
``msg=TEXT``        override the exception message
==================  ====================================================

Design constraints, mirroring telemetry's:

* **Compiled out when unarmed.** ``point()`` with no plane armed is one
  module-global load, one ``is None`` branch and a return (the same
  discipline as telemetry's disabled sites).
* **Deterministic.** Every trigger is a pure function of its private
  call counter (and, for ``prob``, a seeded private rng) — the same
  armed spec produces the same fault sequence on every run, which is
  what lets tier-1 assert exact degradation paths.
* **Observable.** Every fired injection bumps the
  ``faults.injected{point=...}`` counter and leaves a
  ``fault.injected`` flight-ring record, so crash reports and
  tools/diagnose.py show what the plane did to the run.
"""
from __future__ import annotations

import contextlib
import os
import random
import threading
import time

from ..base import MXNetError
from .. import telemetry as _telemetry

__all__ = ["InjectedFault", "point", "configure", "scope", "clear",
           "enabled", "fired", "calls", "parse_spec", "KNOWN_POINTS"]


class InjectedFault(MXNetError):
    """The default exception an armed injection point raises. Carries
    ``mx_fault_point`` (every injected exception does, whatever its
    class) so handlers and tests can tell injected failures from real
    ones."""


# the seams instrumented in-tree (docs/faults.md catalog); arming an
# unknown point is allowed — user code can declare its own points
KNOWN_POINTS = (
    "ckpt.write",          # checkpoint commit (serialize+fsync+rename)
    "ckpt.d2h",            # snapshot device->host transfer
    "kvstore.collective",  # bucket all-reduce dispatch
    "io.decode",           # prefetch/decode of one batch
    "serve.dispatch",      # serving batch dispatch
    "serve.admit",         # serving admission
    "train.health.triage", # health-plane escalation ladder entry
)

_ERROR_KINDS = {
    "fault": InjectedFault,
    "os": OSError,
    "runtime": RuntimeError,
    "conn": ConnectionError,
    "timeout": TimeoutError,
    "value": ValueError,
}


def _parse_duration(tok):
    """'50ms' / '0.5s' / '0.05' -> seconds."""
    tok = tok.strip().lower()
    try:
        if tok.endswith("ms"):
            return float(tok[:-2]) / 1000.0
        if tok.endswith("s"):
            return float(tok[:-1])
        return float(tok)
    except ValueError:
        raise MXNetError(f"bad duration {tok!r} (want e.g. 50ms, 0.5s)")


class _Trigger:
    """One point's scripted trigger: mode + private counter/rng."""

    __slots__ = ("point", "mode", "n", "prob", "latency_s", "exc_cls",
                 "msg", "calls", "fired", "_rng")

    def __init__(self, point, spec):
        self.point = point
        self.mode = None          # "nth" | "every" | "first" | "prob"
        self.n = 0
        self.prob = None
        self.latency_s = None     # delay action instead of raise
        self.exc_cls = InjectedFault
        self.msg = None
        self.calls = 0
        self.fired = 0
        seed = 0
        for tok in spec.split(","):
            tok = tok.strip()
            if not tok:
                continue
            if tok == "once":
                self.mode, self.n = "nth", 1
                continue
            if tok == "always":
                self.mode, self.n = "first", float("inf")
                continue
            if "=" not in tok:
                raise MXNetError(
                    f"MXNET_FAULTS: bad token {tok!r} for point "
                    f"{point!r} (want key=value, 'once' or 'always')")
            key, _, val = tok.partition("=")
            key = key.strip()
            if key in ("nth", "every", "first"):
                self.mode, self.n = key, int(val)
                if self.n < 1:
                    raise MXNetError(f"MXNET_FAULTS: {key}={val} must "
                                     "be >= 1")
            elif key == "prob":
                self.mode, self.prob = "prob", float(val)
                if not 0.0 <= self.prob <= 1.0:
                    raise MXNetError(f"MXNET_FAULTS: prob={val} outside "
                                     "[0, 1]")
            elif key == "seed":
                seed = int(val)
            elif key == "latency":
                self.latency_s = _parse_duration(val)
            elif key == "error":
                if val not in _ERROR_KINDS:
                    raise MXNetError(
                        f"MXNET_FAULTS: unknown error kind {val!r} "
                        f"(have: {sorted(_ERROR_KINDS)})")
                self.exc_cls = _ERROR_KINDS[val]
            elif key == "msg":
                self.msg = val
            else:
                raise MXNetError(f"MXNET_FAULTS: unknown key {key!r} "
                                 f"for point {point!r}")
        if self.mode is None and self.latency_s is None:
            raise MXNetError(
                f"MXNET_FAULTS: point {point!r} needs a trigger "
                "(once/always/nth=/every=/first=/prob=)")
        if self.mode is None:
            self.mode, self.n = "first", float("inf")  # bare latency=
        self._rng = random.Random(seed)

    def should_fire(self):
        """Advance the private counter; decide deterministically."""
        self.calls += 1
        if self.mode == "nth":
            return self.calls == self.n
        if self.mode == "every":
            return self.calls % self.n == 0
        if self.mode == "first":
            return self.calls <= self.n
        return self._rng.random() < self.prob


class _Plane:
    """One armed configuration: point name -> trigger."""

    def __init__(self, triggers):
        self.triggers = triggers
        self._lock = threading.Lock()

    def hit(self, name, ctx):
        trig = self.triggers.get(name)
        if trig is None:
            return
        with self._lock:
            fire = trig.should_fire()
            if fire:
                trig.fired += 1
                call = trig.calls
        if not fire:
            return
        _telemetry.counter("faults.injected", point=name).inc()
        _telemetry.flightrec.note(
            "fault.injected", point=name, call=call,
            action="delay" if trig.latency_s is not None else
            trig.exc_cls.__name__, **ctx)
        if trig.latency_s is not None:
            time.sleep(trig.latency_s)
            return
        exc = trig.exc_cls(trig.msg or
                           f"injected fault at point {name!r} "
                           f"(call {call})")
        exc.mx_fault_point = name
        raise exc


_active = None     # None = disarmed: the point() fast path


def parse_spec(spec):
    """``MXNET_FAULTS`` string (or dict point->trigger) -> trigger map."""
    if isinstance(spec, dict):
        return {p: _Trigger(p, s) for p, s in spec.items()}
    triggers = {}
    for clause in str(spec).split(";"):
        clause = clause.strip()
        if not clause:
            continue
        pt, sep, trig = clause.partition(":")
        if not sep or not pt.strip():
            raise MXNetError(
                f"MXNET_FAULTS: bad clause {clause!r} "
                "(want point:trigger[,key=value...])")
        pt = pt.strip()
        if pt in triggers:
            raise MXNetError(f"MXNET_FAULTS: point {pt!r} configured "
                             "twice")
        triggers[pt] = _Trigger(pt, trig)
    return triggers


def point(name, **ctx):
    """One named injection site. A no-op (one global load + branch)
    unless the plane is armed AND has a trigger for ``name``; when the
    trigger decides to fire, raises the configured exception (marked
    with ``mx_fault_point``) or sleeps the configured latency. ``ctx``
    rides into the flight-ring record."""
    plane = _active
    if plane is not None:
        plane.hit(name, ctx)


def configure(spec):
    """Arm the plane from a spec string/dict; ``None``/empty disarms.
    Returns the previous configuration handle (for scope())."""
    global _active
    prev = _active
    _active = _Plane(parse_spec(spec)) if spec else None
    return prev


def clear():
    """Disarm the plane."""
    global _active
    _active = None


def enabled():
    return _active is not None


@contextlib.contextmanager
def scope(spec):
    """Arm ``spec`` for the duration of a with-block, restoring the
    previous arming after — the tier-1 testing idiom."""
    global _active
    prev = configure(spec)
    try:
        yield _active
    finally:
        _active = prev


def fired(name=None):
    """Injections fired so far: count for one point, or dict for all."""
    plane = _active
    trigs = plane.triggers if plane is not None else {}
    if name is not None:
        t = trigs.get(name)
        return t.fired if t is not None else 0
    return {p: t.fired for p, t in trigs.items()}


def calls(name=None):
    """Point traversals seen by armed triggers (fired or not) — the
    per-batch site count."""
    plane = _active
    trigs = plane.triggers if plane is not None else {}
    if name is not None:
        t = trigs.get(name)
        return t.calls if t is not None else 0
    return {p: t.calls for p, t in trigs.items()}


# arm from the environment once at import: the process-wide spec a
# production run or a chaos harness sets before launch
_env_spec = os.environ.get("MXNET_FAULTS", "")
if _env_spec:
    configure(_env_spec)
