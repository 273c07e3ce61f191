"""One S=1 decode iteration as a closed account, from the program's
spans and the device's line of the same trace.

The decode loop is serial: it stages an iteration's inputs
(``decode.step.stage``), launches the step program
(``decode.step.launch``) and the row selection behind it, waits for the
token ids (``serve.decode.iter.fetch.ids``, inside
``serve.decode.iter.fetch``), commits, and stages the next. From one
``decode.step.stage`` start to the next, six boundaries cut the time
into six segments that leave nothing out:

    stage           stage start          -> launch start
    launch_latency  launch start         -> first XLA Op of the step program's run
    device          that first op        -> end of the last op launched in the dispatch
    wake_latency    that end             -> end of fetch.ids (the ids on the host)
    fetch_rest      end of fetch.ids     -> end of fetch (rows, moe_stats)
    turnaround      end of fetch         -> the next stage start (commit, rewind,
                                            account, the loop, the lock, plan)

A launch owns the one ``jit_fwd_infer_<slots>x<S>`` run of the ``XLA
Modules`` line that starts between its own stage and the next one; the
ops of its dispatch are those that start between that run's start and
the end of its fetch (the step program, then ``select_rows_*``: a
cursor program launched in commit, rewind or the next plan lies in the
turn-around). The segments' medians are taken over the iterations whose
run is the top rung's S=1 program
(``kernel_time.top_rung_decode_module``); the window iterations are
accounted the same way and enter ``idle_share_all`` alone, which is what
the trace's own busy and window seconds are to be held against.

**The two planes' clocks are tied within the trace.** The profiler
sets the device plane against the host's once a session, and on the v5e
it misses by a constant of 0.25 to 1.45 ms, the device early (PERF.md,
PR 36): enough to move a millisecond from wake latency to launch
latency. ``skew`` measures the miss on the trace's own markers -
one-operation programs (``jit_<fn>`` on the ``XLA Modules`` line, under
0.1 ms) that found the chip idle, each of which started while its call
(the host event ``PjitFunction(<fn>)``) was on the host - and every
device time is taken less that skew. Where a trace has no marker the
account's ``skew_ms`` is None, and the two latencies that cross the
planes then mean nothing.

An iteration *orders impossibly* when its program starts on the chip
before its launch span does, when the ids are on the host before the
dispatch's last op has ended, or when no run lies where the launch says
one is: host and device planes then do not share a clock, and no
latency between them means anything. ``account`` returns None for a
trace without the spans (a program that writes none), and a result
whose ``"p50_ms"`` is None where more than ``IMPOSSIBLE_SHARE`` of the
iterations order impossibly.
"""
from __future__ import annotations

import bisect
import re

from . import kernel_time, trace
from .spans import device_busy, host_events, idle_ns
from .stats import median

SEGMENTS = ("stage", "launch_latency", "device", "wake_latency",
            "fetch_rest", "turnaround")
IMPOSSIBLE_SHARE = 0.01
#: host spans whose time inside an S=1 iteration the ``critical_path``
#: line also gives, for the reader of a run: they say what the segments
#: consist of (``launch`` with ``io.load_batch`` and ``executor.run``
#: inside it, and ``select_rows``, of the time up to the device's end;
#: the others of the turn-around) and decide nothing
HOST_PARTS = ("decode.step.launch", "io.load_batch", "executor.run",
              "decode.select_rows", "serve.decode.iter.moe_stats", "serve.decode.iter.commit",
              "serve.decode.iter.rewind", "serve.decode.iter.account",
              "serve.decode.iter.plan")
_STEP_PROGRAM = re.compile(r"fwd_infer_\d+x\d+$")
_CALL = re.compile(r"PjitFunction\((.+)\)$")
MARKER_NS = 100_000     # a marker runs under 0.1 ms on a chip idle as long
CROSSING = ("launch_latency", "wake_latency")


def _first_in(spans, starts, lo, hi):
    """The first of ``spans`` (sorted, ``starts`` their starts) that
    starts in ``[lo, hi)``, or None."""
    i = bisect.bisect_left(starts, lo)
    return spans[i] if i < len(spans) and starts[i] < hi else None


def skew(obs):
    """``(skew_ns, markers, half_width_ns)``: how far the device plane's
    clock reads ahead of the host's (negative: the device early), as the
    median over the trace's markers of a marker's start on the chip less
    the middle of its call on the host; how many markers; and half a
    call's length, which is how well one marker can know it. None
    without a marker."""
    events = obs.get("events") or []
    planes = trace.device_planes(events)
    if not planes:
        return None
    calls = {}
    for e in events:
        m = e["plane"].startswith("/host:") and \
            _CALL.match(e["name"].split("#")[0])
        if m:
            calls.setdefault(m.group(1), set()).add(
                (e["start_ns"], e["start_ns"] + e["dur_ns"]))
    calls = {fn: sorted(v) for fn, v in calls.items()}
    busy = device_busy(obs)
    found, widths = [], []
    for e in events:
        fn = e["name"].split("(")[0]
        if e["plane"] != planes[0] or e["line"] != trace.MODULE_LINE \
                or e["dur_ns"] >= MARKER_NS or fn[4:] not in calls \
                or idle_ns(busy, e["start_ns"] - MARKER_NS,
                           e["start_ns"]) < MARKER_NS:
            continue
        a, b = min(calls[fn[4:]],
                   key=lambda c: abs(e["start_ns"] - (c[0] + c[1]) / 2))
        if abs(e["start_ns"] - (a + b) / 2) < 5_000_000:
            found.append(e["start_ns"] - (a + b) / 2)
            widths.append((b - a) / 2)
    if not found:
        return None
    return median(found), len(found), median(widths)


def iterations(obs):
    """``(rows, impossible)``: for each iteration that orders possibly,
    oldest first, ``({segment: ns}, is it an S=1 iteration of the top
    rung, (its stage's start, the next one's))``, and the number that
    do not; device times less ``skew``, as read where there is none.
    None without the spans or the device."""
    events = obs.get("events") or []
    stages = host_events(obs, "decode.step.stage")
    launches = host_events(obs, "decode.step.launch")
    ids = host_events(obs, "serve.decode.iter.fetch.ids")
    fetches = host_events(obs, "serve.decode.iter.fetch")
    planes = trace.device_planes(events)
    top = kernel_time.top_rung_decode_module(events)
    if not (stages and launches and ids and fetches and planes and top):
        return None
    tied = skew(obs)
    ahead = 0 if tied is None else tied[0]
    chip = [e for e in events if e["plane"] == planes[0]]
    runs = sorted((e["start_ns"] - ahead,
                   e["start_ns"] + e["dur_ns"] - ahead, e["name"])
                  for e in chip if e["line"] == trace.MODULE_LINE
                  and _STEP_PROGRAM.search(e["name"].split("(")[0]))
    ops = sorted((e["start_ns"] - ahead, e["start_ns"] + e["dur_ns"] - ahead)
                 for e in chip if e["line"] == trace.OP_LINE)
    stage_starts = [a for a, _b in stages]
    run_starts = [r[0] for r in runs]
    op_starts = [a for a, _b in ops]
    id_starts = [a for a, _b in ids]
    fetch_starts = [a for a, _b in fetches]

    rows, impossible = [], 0
    for launch, _launch_end in launches:
        i = bisect.bisect_right(stage_starts, launch) - 1
        if i < 0 or i + 1 >= len(stages):
            continue            # cut by the trace's start or its end
        begin, nxt = stage_starts[i], stage_starts[i + 1]
        got = _first_in(ids, id_starts, launch, nxt)
        fetch = _first_in(fetches, fetch_starts, launch, nxt)
        if got is None or fetch is None:
            continue            # a speculative step fetches no ids
        run = _first_in(runs, run_starts, begin, nxt)
        if run is None:
            impossible += 1
            continue
        lo = bisect.bisect_left(op_starts, run[0])
        hi = bisect.bisect_left(op_starts, fetch[1])
        if lo >= hi:
            impossible += 1     # a run without an operation before the
            continue            # fetch's end: it ran after its ids came
        first = ops[lo][0]
        last = max(b for _a, b in ops[lo:hi])
        if first < launch or got[1] < last:
            impossible += 1
            continue
        rows.append(({"stage": launch - begin,
                      "launch_latency": first - launch,
                      "device": last - first,
                      "wake_latency": got[1] - last,
                      "fetch_rest": fetch[1] - got[1],
                      "turnaround": nxt - fetch[1]}, run[2] == top,
                     (begin, nxt)))
    return rows, impossible


def _host_parts(obs, windows):
    """``{span: ms}``: per iteration of ``windows`` (``(start, end)``,
    oldest first) the time of the host events called ``span`` that
    start inside it; the median. A span the program does not write is
    left out."""
    out = {}
    for name in HOST_PARTS:
        spans = host_events(obs, name)
        if not spans:
            continue
        starts = [a for a, _b in spans]
        out[name] = median(
            [sum(b - a for a, b in spans[bisect.bisect_left(starts, lo):
                                         bisect.bisect_left(starts, hi)])
             for lo, hi in windows]) / 1e6
    return out


def _thirds(rows):
    """``{segment: [median over the first third of the iterations, over
    the last]}`` for the two segments that cross from one plane to the
    other. Planes whose clocks drift apart move the two the opposite
    way by the same amount as the trace goes on, and leave their sum."""
    n = max(1, len(rows) // 3)
    return {s: [median([r[s] for r in part]) / 1e6
                for part in (rows[:n], rows[-n:])]
            for s in CROSSING}


def _executor_to_chip(obs, kept):
    """Median ms from the start of the ``executor.run`` span inside an
    iteration's launch - the jitted call, which enqueues the program
    some way in - to the program's first op. The chip cannot start
    before the call does: a reading near or under zero says the device
    plane runs early by at least that much, and launch latency reads
    short and wake latency long by it. None without the span."""
    calls = host_events(obs, "executor.run")
    starts = [a for a, _b in calls]
    gaps = []
    for row, (begin, nxt) in kept:
        launch = begin + row["stage"]
        call = _first_in(calls, starts, launch, nxt)
        if call is not None:
            gaps.append(launch + row["launch_latency"] - call[0])
    return median(gaps) / 1e6 if gaps else None


def account(obs):
    """The account of the traced iterations, computed once for an
    observation and kept in it: ``{"iterations", "impossible", "p50_ms":
    {segment: ms} | None, "mean_ms": {segment: ms}, "wall_ms_p50",
    "wall_ms_mean", "idle_share", "iterations_all", "idle_share_all",
    "skew_ms", "skew_markers", "skew_half_width_ms",
    "host_ms_p50": {span: ms}, "thirds_ms_p50": {segment: [ms, ms]},
    "executor_to_chip_ms_p50"}``.
    ``iterations`` counts the top rung's S=1 iterations, which the
    medians, the means and ``idle_share`` ((wall - device) / wall over
    their sums) are of; ``impossible`` and the ``_all`` pair are of
    every iteration; ``host_ms_p50`` is ``HOST_PARTS``' time inside an
    S=1 iteration, ``thirds_ms_p50`` and ``executor_to_chip_ms_p50`` what
    ``_thirds`` and ``_executor_to_chip`` say of the planes' clocks. The means add up to the mean wall time to the
    nanosecond; the medians nearly. None where there is nothing to
    read."""
    if "critical_path" in obs:
        return obs["critical_path"]
    found = iterations(obs)
    out = None
    if found is not None and (found[0] or found[1]):
        every, impossible = found
        rows = [r for r, s1, _w in every if s1]
        tied = skew(obs)
        out = {"iterations": len(rows), "impossible": impossible,
               "iterations_all": len(every), "p50_ms": None,
               "skew_ms": None if tied is None else tied[0] / 1e6,
               "skew_markers": 0 if tied is None else tied[1],
               "skew_half_width_ms": None if tied is None
               else tied[2] / 1e6}
        if every:
            out["idle_share_all"] = 1.0 - sum(
                r["device"] for r, _s1, _w in every) / sum(
                sum(r.values()) for r, _s1, _w in every)
        if rows:
            walls = [sum(r.values()) for r in rows]
            out["mean_ms"] = {s: sum(r[s] for r in rows) / len(rows) / 1e6
                              for s in SEGMENTS}
            out["wall_ms_p50"] = median(walls) / 1e6
            out["wall_ms_mean"] = sum(walls) / len(rows) / 1e6
            out["idle_share"] = 1.0 - sum(r["device"] for r in rows) \
                / sum(walls)
            out["host_ms_p50"] = _host_parts(
                obs, [w for _r, s1, w in every if s1])
            out["thirds_ms_p50"] = _thirds(rows)
            out["executor_to_chip_ms_p50"] = _executor_to_chip(
                obs, [(r, w) for r, s1, w in every if s1])
            if impossible <= IMPOSSIBLE_SHARE * (len(every) + impossible):
                out["p50_ms"] = {s: median([r[s] for r in rows]) / 1e6
                                 for s in SEGMENTS}
    obs["critical_path"] = out
    if out is not None:
        from .common import say
        say("critical_path", **out)
    return out

