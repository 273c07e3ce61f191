"""The critical path of a decode iteration (chipbench/critical_path.py:
the account and its ``critical_path`` line, which decide nothing) and the
five ``layers/`` files of ISSUE 36's eight that read the flight ring, on
a scripted trace of three iterations small enough to check by hand. The
three that read the account's segments went with PR 59: every iteration
has ordered impossibly since dispatches run ahead (PR 46, PR 53). Run by tier-1 through
``tests/test_chipbench_yardstick.py``; by hand: ``python -m pytest
chipbench/tests/test_critical_path.py``."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import critical_path, manifest, readers, spans, trace  # noqa: E402
from chipbench.tests import scripted_trace  # noqa: E402
from chipbench.tests.test_spans import scripted as phases_alone  # noqa: E402

CELLS = ("cgpt1.3b-serve-chat-closed", "olmoe-1b-7b-serve-chat-closed",
         "evabyte-6.5b-serve-longdoc-closed", "glm5.2-serve-longctx-closed")
NEW = {"engine.stage_ms_p50.chat", "engine.launch_ms_p50.chat",
       "engine.select_ms_p50.chat", "sched.loop_turn_ms_p50.chat",
       "sched.lock_wait_ms_p50.chat"}
GONE = {"engine.launch_latency_ms_p50.chat",
        "engine.wake_latency_ms_p50.chat", "sched.turnaround_ms_p50.chat",
        "sched.iter_host_ms_p50"}
S1, WINDOW = "jit_fwd_infer_8x1(7)", "jit_fwd_infer_8x64(9)"


def _e(plane, line, name, start_us, dur_us):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": int(start_us * 1000), "dur_ns": int(dur_us * 1000)}


def scripted(skew_us=0, early_ids_us=0, markers=True):
    """Five iterations, in microseconds from each one's start; the first
    three are S=1 iterations 1,000 / 1,100 / 1,200 us long, the fourth a
    window iteration, the fifth is there to end the fourth.

    An S=1 iteration ``k`` (0, 1, 2), with ``d = 100 k``: plan
    [0, 40), dispatch [40, 300 + d) holding stage [50, 150), launch
    [150, 250 + d) (the jitted call, executor.run, [170, 230 + 2 d))
    and select_rows [260 + d, 290 + d); the step program
    runs on chip 0 over [200 + d, 700 + d) as two operations back to
    back and ``select_rows_8x1`` over [700 + d, 710 + d); fetch
    [300 + d, 800 + d) holds fetch.ids [310 + d, 760 + d) and moe_stats
    behind it; commit [800 + d, 860 + d), rewind [860 + d, 880 + d),
    account [880 + d, 930 + d); the iteration ends at 1,000 + d and
    the next one's stage starts 50 us into it. So from one stage's start:

        stage           100, 100, 100
        launch latency   50, 150, 250   (launch start -> first op)
        device          510             (step program + select_rows)
        wake latency     50             (710 + d -> 760 + d)
        rest of fetch    40
        turn-around     250             (800 + d -> the next stage)

    and a wall time of 1,000, 1,100 and 1,200. The window iteration is
    4,500 long with 4,010 on the device. ``cursor_update_8`` runs over
    [990, 995) of iteration 1, inside its turn-around: not part of the
    dispatch. Inside every plan the host calls a one-operation program,
    ``PjitFunction(poke_8)`` over [10, 30), which runs on the idle chip
    over [20, 22): the marker that ties the planes' clocks (``markers``
    False leaves the call out). ``skew_us`` moves the whole device plane
    earlier, ``early_ids_us`` ends iteration 1's fetch.ids that much
    earlier (ids on the host before the last op ended). A second chip
    must not be read."""
    out = []
    host, dev = "/host:CPU", "/device:TPU:0"
    base = 0
    for k in range(5):
        window = k == 3
        d = 0 if k > 2 else 100 * k
        prog, run_us = (WINDOW, 4000) if window else (S1, 500)
        ids_end = base + 260 + d + run_us - \
            (early_ids_us if k == 1 else 0)
        out.append(_e(host, "python", f"serve.decode.iter#iter={k}#", base,
                      500 + d + run_us))
        for name, start, dur in (
                ("serve.decode.iter.plan", 0, 40),
                ("serve.decode.iter.dispatch", 40, 260 + d),
                ("decode.step.stage", 50, 100),
                ("decode.step.launch", 150, 100 + d),
                ("executor.run", 170, 60 + d),
                ("decode.select_rows", 260 + d, 30),
                ("serve.decode.iter.fetch", 300 + d, run_us),
                ("serve.decode.iter.moe_stats", 270 + d + run_us, 20),
                ("serve.decode.iter.commit", 300 + d + run_us, 60),
                ("serve.decode.iter.rewind", 360 + d + run_us, 20),
                ("serve.decode.iter.account", 380 + d + run_us, 50)):
            out.append(_e(host, "python", name, base + start, dur))
        out.append(_e(host, "python", "serve.decode.iter.fetch.ids",
                      base + 310 + d, ids_end - (base + 310 + d)))
        on = base + 200 + d - skew_us
        out.append(_e(dev, "XLA Modules", prog, on, run_us))
        out.append(_e(dev, "XLA Ops", "fusion.1", on, run_us * 0.6))
        out.append(_e(dev, "XLA Ops", "fusion.2", on + run_us * 0.6,
                      run_us * 0.4))
        sel = "jit_select_rows_8x64(3)" if window else \
            "jit_select_rows_8x1(2)"
        out.append(_e(dev, "XLA Modules", sel, on + run_us, 10))
        out.append(_e(dev, "XLA Ops", "argmax.4", on + run_us, 10))
        if k == 1:
            out.append(_e(dev, "XLA Modules", "jit_cursor_update_8(5)",
                          base + 990 - skew_us, 5))
            out.append(_e(dev, "XLA Ops", "select.6", base + 990 - skew_us,
                          5))
        if markers:
            out.append(_e(host, "python", "PjitFunction(poke_8)", base + 10,
                          20))
        out.append(_e(dev, "XLA Modules", "jit_poke_8(4)",
                      base + 20 - skew_us, 2))
        out.append(_e(dev, "XLA Ops", "add.8", base + 20 - skew_us, 2))
        out.append(_e("/device:TPU:1", "XLA Ops", "fusion.1", base, 900))
        base += 500 + d + run_us
    return out


def test_three_iterations_by_hand():
    obs = {"events": scripted()}
    every, impossible = critical_path.iterations(obs)
    assert impossible == 0
    rows = [r for r, s1, _w in every if s1]
    # the fourth is a window iteration; the fifth has no successor
    assert [s1 for _r, s1, _w in every] == [True, True, True, False]
    us = [{s: v / 1000 for s, v in r.items()} for r in rows]
    assert [r["stage"] for r in us] == [100, 100, 100]
    assert [r["launch_latency"] for r in us] == [50, 150, 250]
    assert [r["device"] for r in us] == [510, 510, 510]
    assert [r["wake_latency"] for r in us] == [50, 50, 50]
    assert [r["fetch_rest"] for r in us] == [40, 40, 40]
    assert [r["turnaround"] for r in us] == [250, 250, 250]
    assert [sum(r.values()) for r in us] == [1000, 1100, 1200]
    assert every[3][0]["device"] == 4_010_000

    found = critical_path.account(obs)
    assert found["iterations"] == 3 and found["iterations_all"] == 4
    assert found["p50_ms"] == {
        "stage": pytest.approx(0.100),
        "launch_latency": pytest.approx(0.150),
        "device": pytest.approx(0.510),
        "wake_latency": pytest.approx(0.050),
        "fetch_rest": pytest.approx(0.040),
        "turnaround": pytest.approx(0.250)}
    assert found["wall_ms_p50"] == pytest.approx(1.100)
    assert (found["skew_ms"], found["skew_markers"]) == (0, 5)
    assert found["skew_half_width_ms"] == pytest.approx(0.010)
    assert sum(found["mean_ms"].values()) == \
        pytest.approx(found["wall_ms_mean"]) == pytest.approx(1.100)
    assert found["idle_share"] == pytest.approx(1 - 1530 / 3300)
    assert found["idle_share_all"] == \
        pytest.approx(1 - (1530 + 4010) / (3300 + 4500))
    # the next iteration's plan lies in this one's turn-around
    assert found["host_ms_p50"] == {
        "decode.step.launch": pytest.approx(0.200),
        "executor.run": pytest.approx(0.160),
        "decode.select_rows": pytest.approx(0.030),
        "serve.decode.iter.moe_stats": pytest.approx(0.020),
        "serve.decode.iter.commit": pytest.approx(0.060),
        "serve.decode.iter.rewind": pytest.approx(0.020),
        "serve.decode.iter.account": pytest.approx(0.050),
        "serve.decode.iter.plan": pytest.approx(0.040)}
    # what the line says of the planes' clocks: the first and the last
    # third of the iterations, and the jitted call's start to the chip's
    assert found["thirds_ms_p50"] == {
        "launch_latency": [pytest.approx(0.050), pytest.approx(0.250)],
        "wake_latency": [pytest.approx(0.050), pytest.approx(0.050)]}
    assert found["executor_to_chip_ms_p50"] == pytest.approx(0.130)
    assert critical_path.account(obs) is found      # computed once


def _ring():
    recs = [{"kind": "serve.decode.step", "window": w, "stage_us": 100 * k,
             "launch_us": 1000 * k, "select_us": 10 * k, "ids_us": 7 * k,
             "turn_us": 20 * k, "lock_us": 3 * k, "dispatch_us": 1200 * k}
            for k, w in ((1, 1), (2, 1), (3, 1), (50, 64), (60, 64))]
    return recs + [{"kind": "serve.decode.step", "window": 1,
                    "dispatch_us": 5}]      # a parent's record


@pytest.mark.parametrize("cell", CELLS)
def test_the_eight_layer_files_read_ring_and_trace(cell):
    """Five files since PR 59 (the name is what tier-1 imports,
    ``tests/test_chipbench_yardstick.py``): the ring's fields, whatever
    the trace holds; the account's three segments are no metric's."""
    per_layer = {m.name: m for m in
                 manifest.resolve(manifest.load(), cell).per_layer}
    assert NEW <= set(per_layer) and not GONE & set(per_layer)
    assert not [g for g in GONE for ext in (".py", ".json")
                if os.path.exists(os.path.join(ROOT, "chipbench", "layers",
                                               g + ext))]
    obs = {"events": scripted(), "ring": _ring()}
    p50 = critical_path.account(obs)["p50_ms"]
    assert (p50["launch_latency"], p50["wake_latency"], p50["turnaround"]) \
        == (pytest.approx(0.150), pytest.approx(0.050), pytest.approx(0.250))
    got = {n: readers.read(per_layer[n], obs) for n in NEW}
    assert got == {
        "engine.stage_ms_p50.chat": pytest.approx(0.2),
        "engine.launch_ms_p50.chat": pytest.approx(2.0),
        "engine.select_ms_p50.chat": pytest.approx(0.02),
        "sched.loop_turn_ms_p50.chat": pytest.approx(0.04),
        "sched.lock_wait_ms_p50.chat": pytest.approx(0.006)}
    for m in per_layer.values():
        if m.name in NEW:
            assert (m.unit, m.source, m.moves, m.layer) == (
                "ms", "program_span", "serve_tokens_per_s",
                "DecodeEngine" if m.name.startswith("engine.")
                else "DecodeScheduler")


@pytest.mark.parametrize("events", [
    phases_alone(), scripted_trace.events(), [], None])
def test_a_trace_without_the_new_spans_reads_as_nothing(events):
    """Every parent of the PR that added them: the scheduler's phases
    without ``decode.step.*`` and ``fetch.ids``, another program's
    trace, an untraced run; and a ring whose records lack the fields."""
    obs = {"events": events,
           "ring": [{"kind": "serve.decode.step", "window": 1,
                     "dispatch_us": 4000, "fetch_us": 9000}]}
    assert critical_path.iterations(obs) is None
    assert critical_path.account(obs) is None
    for m in manifest.resolve(manifest.load(), CELLS[0]).per_layer:
        if m.name in NEW:
            assert readers.read(m, obs) is None, m.name


@pytest.mark.parametrize("skew_us", [260, 450, -300])
def test_a_skewed_device_plane_is_tied_back_by_its_markers(skew_us):
    """A device plane 260 us early starts every program before its
    launch span, one 450 us early before its launch's stage (a marker
    takes the nearest call of its program, so a skew is known up to
    half the calls' spacing: 1 ms here, 10 ms and more on the chip,
    where the v5e's worst was 1.45 ms; PERF.md, PR 36): the markers say
    by how much, and the account is the unskewed one."""
    straight = critical_path.account({"events": scripted()})
    found = critical_path.account({"events": scripted(skew_us=skew_us)})
    assert found["skew_ms"] == pytest.approx(-skew_us / 1000)
    assert found["impossible"] == 0
    assert found["p50_ms"] == pytest.approx(straight["p50_ms"])
    assert found["idle_share_all"] == \
        pytest.approx(straight["idle_share_all"])


def _read(names, obs):
    per_layer = {m.name: m for m in
                 manifest.resolve(manifest.load(), CELLS[0]).per_layer}
    return {n: readers.read(per_layer[n], obs) for n in names}


def test_without_a_marker_the_crossing_latencies_read_as_none():
    """Nothing ties the planes' clocks: the account says so
    (``skew_ms`` None, no marker; its two segments that cross from one
    plane to the other then mean nothing) and the ring's fields are read
    as ever."""
    obs = {"events": scripted(markers=False), "ring": _ring()}
    assert critical_path.skew(obs) is None
    found = critical_path.account(obs)
    assert found["skew_ms"] is None and found["skew_markers"] == 0
    assert found["p50_ms"]["device"] == pytest.approx(0.510)
    assert _read(sorted(NEW), obs) == \
        _read(sorted(NEW), {"events": scripted(), "ring": _ring()})


@pytest.mark.parametrize("fault", [
    {"early_ids_us": 80}, {"skew_us": 260, "markers": False}])
def test_an_impossible_order_reads_as_none_with_its_count(fault, capsys):
    """Ids 80 us early are on the host before ``select_rows`` ended, in
    one iteration of four; a device plane 260 us early that no marker
    ties back starts every program before its launch span. Either way
    the account has no medians and its line says how many iterations
    said so."""
    obs = {"events": scripted(**fault), "ring": _ring()}
    found = critical_path.account(obs)
    assert found["impossible"] == (4 if "skew_us" in fault else 1)
    assert found["p50_ms"] is None
    assert found["iterations"] + found["impossible"] == \
        (0 + 4 if "skew_us" in fault else 2 + 1)
    line = capsys.readouterr().out
    assert '"chipbench": "critical_path"' in line and \
        f'"impossible": {found["impossible"]}' in line
    # the ring's fields do not depend on the trace's clocks
    assert _read(["engine.stage_ms_p50.chat"], obs) == \
        {"engine.stage_ms_p50.chat": pytest.approx(0.2)}


def test_recorded_chat_slice_closes_the_account():
    """60 ms of the Cerebras chat cell on the v5e (testdata's note):
    three whole S=1 iterations, none ordering impossibly; the six
    segments are the whole of the time from one ``decode.step.stage`` to
    the next; the chip starts inside the jitted call, before it returns,
    and the ids arrive a millisecond after its last operation."""
    with open(os.path.join(ROOT, "chipbench", "testdata",
                           "v5e_serve_chat_critical_path_slice.json")) as f:
        obs = {"events": trace.unpack(json.load(f)["events"])}
    every, impossible = critical_path.iterations(obs)
    assert impossible == 0 and [s1 for _r, s1, _w in every] == [True] * 3
    stages = [a for a, _b in spans.host_events(obs, "decode.step.stage")]
    for (row, _s1, (begin, nxt)), want in zip(every, zip(stages,
                                                        stages[1:])):
        assert (begin, nxt) == want
        assert sum(row.values()) == nxt - begin
        assert all(v > 0 for v in row.values())
    found = critical_path.account(obs)
    p50, host = found["p50_ms"], found["host_ms_p50"]
    # the device plane of this session reads about 0.13 ms early, by
    # ten markers (the key split's two programs an iteration)
    assert -0.3 < found["skew_ms"] < 0 and found["skew_markers"] == 10
    assert found["skew_half_width_ms"] < 0.2
    assert 0.5 < found["executor_to_chip_ms_p50"] < 1.5
    assert 9.3 < p50["device"] < 9.5
    assert p50["launch_latency"] < host["decode.step.launch"]
    assert host["io.load_batch"] + host["executor.run"] \
        < host["decode.step.launch"]
    assert 0.8 < p50["wake_latency"] < 1.5
    assert found["wall_ms_mean"] == \
        pytest.approx(sum(found["mean_ms"].values()))
    assert found["idle_share"] == pytest.approx(
        1 - found["mean_ms"]["device"] / found["wall_ms_mean"])
