"""Training step-time attribution: per-phase breakdown + stall detector.

End-to-end img/s says a run is slow; it never says *where* — the batch
could be starved by the input pipeline (data wait), burning host time
in batch assembly, queuing dispatches, or genuinely bound on device
compute. This module splits every ``Module.fit`` step into phases:

==============  =====================================================
``data_wait``   blocking in the iterator handoff (PrefetchingIter's
                queue.get — the producer thread fell behind)
``assemble``    host-side batch staging: ``_load_batch`` /
                ``_stack_window`` + lr/wd and arg-dict preparation
``dispatch``    the jitted program call (async — returns at submit)
``device``      the wait for the chip: behind each dispatch (one per
                K batches on the scan path) the loop blocks until the
                dispatch BEFORE it has finished, so the chip always
                has the next step queued, as in a run nobody measures
``other``       the remainder of the step wall (metric update,
                callbacks, Python loop) — kept explicit so the phases
                always sum to the measured wall time
==============  =====================================================

Each phase lands in a ``step.phase.<name>.seconds`` histogram (per
logical batch, window phases divided by K) — the per-worker surface a
multihost aggregation pushes up — and a rolling straggler detector
flags any step whose wall time exceeds ``median + k*MAD`` over the
recent window (``MXNET_STRAGGLER_K``, default 5), recording the
offending step's phase breakdown into the flight ring (``step.
straggler``) so a stall names its phase, not just its existence.

Arming: follows the telemetry switch (``telemetry.enable()``), or force
with ``MXNET_STEP_ATTRIBUTION=1`` / off with ``=0`` independent of the
tracer. Disabled cost is one module-attr read + branch per site.

``device`` is a lagged wait (``executor_group._wait_for_step_before``):
the parameters and optimizer states a dispatch returns are donated to
the next one, so the loop holds a metric scalar or an output of each
dispatch and waits on it behind the NEXT dispatch. Step n cannot end
before step n-1: the wait is device time on the host's clock,
completion to completion. It reads "the loop waited this long for the
chip" - in a chip-bound loop the step's device time less the host's
own work, in a host-bound loop zero - and the first armed step waits
on nothing. An armed loop therefore runs one dispatch ahead of the
chip exactly as an unarmed one does; until PR 50 it drained the chip
after every dispatch and a traced run measured a loop nobody runs.

The clock is injectable (``use_clock``) so deterministic tests can
script exact phase durations.
"""
from __future__ import annotations

import collections
import os
import threading
import time

from . import core as _core
from . import flightrec as _flightrec

__all__ = ["armed", "active", "clock", "use_clock", "configure",
           "step_begin", "note", "note_data_wait", "step_end",
           "records", "stragglers", "reset", "PHASES"]

PHASES = ("data_wait", "assemble", "dispatch", "device", "other")

_local = threading.local()
_lock = threading.Lock()
_records = collections.deque(maxlen=1024)   # recent finished steps
_stragglers = collections.deque(maxlen=64)
_window = collections.deque(maxlen=64)      # per-step walls, straggler base
_thresh = None          # cached straggler threshold (median + k*MAD)
_thresh_at = 0          # window appends when the cache was computed
_appends = 0
_hists = None           # cached phase-histogram handles
_hists_gen = -1
_THRESH_EVERY = 16      # recompute cadence: the rolling median moves
                        # slowly; per-step sorting would dominate the
                        # armed cost the overhead gate bounds

clock = time.perf_counter

_MIN_SAMPLES = 8        # straggler detector warm-up
_MAD_FLOOR_FRAC = 0.02  # MAD floor as a fraction of the median plus an
_MAD_FLOOR_S = 1e-4     # absolute floor: a uniform micro-step run
                        # (median ~us) must not flag scheduler noise

_env_armed = os.environ.get("MXNET_STEP_ATTRIBUTION", "")
_forced = None          # configure() override (tests/benchmarks)


def _env_k():
    try:
        return float(os.environ.get("MXNET_STRAGGLER_K", "") or 5.0)
    except ValueError:
        return 5.0


_k_mad = _env_k()


def armed():
    """Is step attribution recording? MXNET_STEP_ATTRIBUTION=1/0 wins,
    then a configure(armed=...) override, else the telemetry switch."""
    if _forced is not None:
        return _forced
    if _env_armed == "1":
        return True
    if _env_armed == "0":
        return False
    return _core._enabled


def active():
    """Is a step record open on THIS thread? (the executor's cheap
    guard: phases only record inside a fit step, so raw
    forward_backward loops never pay the boundary block)."""
    return getattr(_local, "current", None) is not None


def use_clock(fn):
    """Swap the time source (tests); returns the previous one."""
    global clock
    prev, clock = clock, fn
    return prev


_UNSET = object()


def configure(armed=_UNSET, k_mad=None):
    """Override the arming decision / straggler threshold
    (``armed=None`` restores the env/telemetry-driven default)."""
    global _forced, _k_mad, _thresh
    if armed is not _UNSET:
        _forced = armed
    if k_mad is not None:
        _k_mad = float(k_mad)
        _thresh = None


def note_data_wait(seconds):
    """Bank iterator-handoff wait measured *before* the step opens (the
    fit loop times ``next()`` first); ``step_begin`` claims it."""
    _local.pending_wait = getattr(_local, "pending_wait", 0.0) + seconds


def clear_pending_wait():
    """Drop banked data-wait (resume fast-forward skips a batch)."""
    _local.pending_wait = 0.0


def step_begin(epoch, nbatch):
    """Open a step record on this thread (no-op unless armed)."""
    if not armed():
        return
    wait = getattr(_local, "pending_wait", 0.0)
    _local.pending_wait = 0.0
    _local.current = {"epoch": epoch, "nbatch": nbatch, "t0": clock(),
                      "phases": {"data_wait": wait}}


def note(phase, seconds):
    """Add ``seconds`` to a phase of the open step (no-op without one)."""
    cur = getattr(_local, "current", None)
    if cur is None:
        return
    ph = cur["phases"]
    ph[phase] = ph.get(phase, 0.0) + seconds


def _phase_hists():
    """Cached phase-histogram handles (registry lookups cost a lock
    each; the armed-overhead gate counts every microsecond here).
    Refreshed when the metrics registry resets."""
    global _hists, _hists_gen
    from . import metrics as _metrics
    gen = _metrics.generation()
    if _hists is None or _hists_gen != gen:
        _hists = {p: _metrics.histogram(f"step.phase.{p}.seconds")
                  for p in PHASES}
        _hists["_count"] = _metrics.counter("step.count")
        _hists["_strag"] = _metrics.counter("step.stragglers")
        _hists_gen = gen
    return _hists


def _straggler_threshold():
    """median + k*MAD over the rolling window, recomputed every
    ``_THRESH_EVERY`` appends (the rolling median drifts slowly; two
    sorts per step would dominate the armed cost)."""
    global _thresh, _thresh_at
    if len(_window) < _MIN_SAMPLES:
        return None
    if _thresh is None or _appends - _thresh_at >= _THRESH_EVERY:
        win = sorted(_window)
        med = win[len(win) // 2]
        mad = sorted(abs(w - med) for w in win)[len(win) // 2]
        mad = max(mad, _MAD_FLOOR_FRAC * med, _MAD_FLOOR_S)
        _thresh = (med, med + _k_mad * mad)
        _thresh_at = _appends
    return _thresh


def step_end(steps=1):
    """Close the step: fold ``other``, feed the ``step.phase.*``
    histograms (per logical batch — window phases divide by ``steps``)
    and run the straggler detector on the per-step wall."""
    global _appends, _thresh
    cur = getattr(_local, "current", None)
    if cur is None:
        return None
    _local.current = None
    hists = _phase_hists()
    wall = (clock() - cur["t0"]) + cur["phases"].get("data_wait", 0.0)
    known = sum(cur["phases"].values())
    cur["phases"]["other"] = max(0.0, wall - known)
    steps = max(1, int(steps))
    per_step = wall / steps
    for phase in PHASES:
        hists[phase].observe(cur["phases"].get(phase, 0.0) / steps)
    hists["_count"].inc(steps)

    # the step interval opens at the iterator wait, not at step_begin —
    # [ts, ts+wall] then covers exactly the phases laid end to end
    rec = {"epoch": cur["epoch"], "nbatch": cur["nbatch"],
           "ts_us": round((cur["t0"] -
                           cur["phases"].get("data_wait", 0.0)) * 1e6),
           "wall_us": round(wall * 1e6),
           "steps": steps, "straggler": False,
           "phases_us": {p: round(cur["phases"].get(p, 0.0) * 1e6)
                         for p in PHASES}}

    thresh = _straggler_threshold()
    with _lock:
        _window.append(per_step)
        _appends += 1
    if thresh is not None and per_step > thresh[1]:
        rec["straggler"] = True
        rec["median_us"] = round(thresh[0] * 1e6)
        hists["_strag"].inc()
        with _lock:
            _stragglers.append(rec)
        _flightrec.note(
            "step.straggler", epoch=rec["epoch"],
            nbatch=rec["nbatch"], steps=steps,
            wall_us=rec["wall_us"], median_us=rec["median_us"],
            **{f"{p}_us": rec["phases_us"][p] for p in PHASES})
    with _lock:
        _records.append(rec)
    return rec


def records():
    """Recent finished step records, oldest first."""
    with _lock:
        return list(_records)


def stragglers():
    """Recent flagged stragglers, oldest first."""
    with _lock:
        return list(_stragglers)


def reset():
    """Drop step records, stragglers and the rolling window (histograms
    live in the metrics registry and reset with it)."""
    global _thresh, _thresh_at, _appends
    with _lock:
        _records.clear()
        _stragglers.clear()
        _window.clear()
        _thresh = None
        _thresh_at = _appends = 0
    _local.current = None
    _local.pending_wait = 0.0
