"""Profiler (reference: python/mxnet/profiler.py + src/engine/profiler.cc).

The reference collects per-op exec records into chrome://tracing JSON
surfaced by MXDumpProfile. Two trace sources serve that contract here:

* the **telemetry span tracer** (telemetry/) — framework-level spans
  (executor compile/run, per-op dispatch, kvstore collectives, IO,
  Module.fit batches) serialized to chrome://tracing JSON at the
  configured ``filename``, exactly the reference's artifact shape;
* the **JAX/XLA profiler** — xplane traces (fusion boundaries, HBM
  traffic, MXU utilization) written to ``<filename stem>_trace/``,
  viewable in TensorBoard/Perfetto — strictly richer than the
  reference's records at the op level.

The reference's **per-operator records** are the join of the two:
``operator_table()`` sums the xplane trace's device operations by the
Symbol node that each compiled instruction came from (the executor's
per-node named scopes, read back out of the compiled text;
telemetry/optable.py), with phase (forward, backward, update, ...),
FLOPs, bytes and roofline share a row, and ``dump_profile()`` writes
it under ``otherData.operators`` of the JSON whenever a JAX trace was
taken.

API kept: profiler_set_config, profiler_set_state, dump_profile.
``profiler_set_state("run")`` turns the telemetry tracer on (so spans
from every instrumented layer start recording) and starts a JAX trace;
``dump_profile()`` writes the chrome://tracing JSON and returns its path.
"""
from __future__ import annotations

import logging
import os

import jax

from . import telemetry

_STATE = {"mode": "symbolic", "filename": "profile.json", "running": False,
          "trace_dir": None, "owns_telemetry": False, "jax_trace": True}


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """reference: profiler.py profiler_set_config."""
    _STATE["mode"] = mode
    _STATE["filename"] = filename


def trace_dir():
    """The JAX xplane trace directory of the current/last run (None when
    no trace ever started)."""
    return _STATE["trace_dir"]


def profiler_set_state(state="stop"):
    """'run' enables telemetry span recording and starts a jax profiler
    trace; 'stop' ends both. reference: profiler.py profiler_set_state."""
    if state == "run" and not _STATE["running"]:
        if not telemetry.enabled():
            telemetry.enable()
            _STATE["owns_telemetry"] = True
        trace_dir = os.path.splitext(_STATE["filename"])[0] + "_trace"
        _STATE["trace_dir"] = trace_dir
        try:
            jax.profiler.start_trace(trace_dir)
            _STATE["jax_trace"] = True
        except Exception as exc:  # spans still collect without xplane
            logging.warning("jax profiler trace unavailable (%s); "
                            "telemetry spans still recording", exc)
            _STATE["jax_trace"] = False
        _STATE["running"] = True
    elif state == "stop" and _STATE["running"]:
        if _STATE["jax_trace"]:
            jax.profiler.stop_trace()
            logging.info("profiler trace written to %s", _STATE["trace_dir"])
        if _STATE["owns_telemetry"]:
            telemetry.disable()
            _STATE["owns_telemetry"] = False
        _STATE["running"] = False
    elif state not in ("run", "stop"):
        raise ValueError("state must be 'run' or 'stop'")


def operator_table(events=None, trace_dir=None, device_kind=None):
    """Device time by Symbol node, phase and cost of every program of a
    live binding that ran in a profiler trace: ``events`` (flat events
    ``{"plane", "line", "name", "start_ns", "dur_ns"}``), else the
    newest trace under ``trace_dir``, else the last trace
    ``profiler_set_state`` took. ``telemetry.optable.operator_table``
    has the rows' fields; docs/telemetry.md, "Operator table", a worked
    reading."""
    from .telemetry import optable
    if events is None and trace_dir is None and _STATE["jax_trace"]:
        trace_dir = _STATE["trace_dir"]
    return optable.operator_table(events=events, trace_dir=trace_dir,
                                  device_kind=device_kind)


def dump_profile():
    """Serialize collected spans to chrome://tracing JSON at the
    configured filename and return that path (reference: MXDumpProfile).

    Besides the executor/fit spans, the dump carries the request trace
    plane (``serve.trace/<id>`` tracks, one per traced request/decode
    session) and the training step-phase breakdown (``step.phase``
    track) whenever those planes recorded anything — docs/telemetry.md
    "Trace plane" / "Step-time attribution".

    Always returns the written file's path — including when no trace was
    ever started (the file then just carries an empty/partial span set),
    never a silent None. The JAX xplane trace dir (when one ran) is
    recorded in the JSON's ``otherData.jax_trace_dir``, and the
    operator table of that trace (``operator_table()``) in
    ``otherData.operators``.
    """
    if _STATE["running"]:
        profiler_set_state("stop")
    path = _STATE["filename"]
    if not path:
        raise ValueError(
            "no profile filename configured; call profiler_set_config("
            "filename=...) first")
    meta = {"mode": _STATE["mode"]}
    if _STATE["trace_dir"]:
        meta["jax_trace_dir"] = os.path.abspath(_STATE["trace_dir"])
        if _STATE["jax_trace"]:
            try:
                meta["operators"] = operator_table()
            except Exception as exc:    # the dump is written all the same
                logging.warning("operator table unavailable (%r)", exc,
                                exc_info=True)
                meta["operators"] = {"error": repr(exc)}
    return telemetry.chrome_trace.dump(path, metadata=meta)
