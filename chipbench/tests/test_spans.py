"""The readers of the program's own spans (chipbench/spans.py and the
``layers/*.py`` that call it), on a scripted trace small enough to check
by hand and on a recorded slice of the doc cell. By hand, like the
other tests here: ``python -m pytest chipbench/tests/test_spans.py``."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest, readers, spans, trace  # noqa: E402
from chipbench.tests import scripted_trace  # noqa: E402

DOC, CHAT, FIT = ("cgpt1.3b-serve-doc-closed", "cgpt1.3b-serve-chat-closed",
                  "resnet50-fit-1chip")


def _e(plane, line, name, start_us, dur_us):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": int(start_us * 1000), "dur_ns": int(dur_us * 1000)}


def scripted():
    """Three scheduler iterations 1,100 us apart, in microseconds from
    each one's start: plan [0, 50), dispatch [50, 150), fetch
    [150, 600), commit [600, 700), rewind [700, 750); the iteration
    ends at 1,000. The window program runs on chip 0 over [120, 420)
    (iteration 1: to 520, 100 us longer) as two operations back to
    back, and the rewind's scatter over [720, 730) (not in iteration
    1). So the device is idle inside fetch for 600 - 420 = 180 us (80
    in iteration 1), and outside dispatch and fetch, up to the next
    iteration's start, for 50 + 500 - 10 = 540 us (550 without the
    scatter). The producer thread stages three batches of 200, 240 and
    260 us. A second chip must not be read."""
    out = []
    for it, base in enumerate((0, 1100, 2200)):
        longer = 100 if it == 1 else 0
        dev = "/device:TPU:0"
        out.append(_e(dev, "XLA Modules", "jit_fwd_infer_8x64(1)",
                      base + 120, 300 + longer))
        out.append(_e(dev, "XLA Ops", "fusion.1", base + 120, 180))
        out.append(_e(dev, "XLA Ops", "fusion.2", base + 300, 120 + longer))
        if it != 1:
            out.append(_e(dev, "XLA Ops", "scatter.9", base + 720, 10))
        out.append(_e("/device:TPU:1", "XLA Ops", "fusion.1", base, 1000))
        host = "/host:CPU"
        out.append(_e(host, "python", f"serve.decode.iter#iter={it}#",
                      base, 1000))
        for name, start, dur in (("plan", 0, 50), ("dispatch", 50, 100),
                                 ("fetch", 150, 450), ("commit", 600, 100),
                                 ("rewind", 700, 50)):
            out.append(_e(host, "python", "serve.decode.iter." + name,
                          base + start, dur))
        out.append(_e(host, "python", "PjitFunction(scatter)", base + 710,
                      30))
    for start, dur in ((0, 200), (300, 240), (700, 260)):
        out.append(_e("/host:CPU", "python", "io.prefetch.batch", start,
                      dur))
        out.append(_e("/host:CPU", "python", "io.prefetch.fetch", start,
                      dur - 50))
    return out


def test_idle_ns_against_merged_busy_intervals():
    busy = [[10, 20], [30, 40], [100, 200]]
    assert spans.idle_ns(busy, 0, 50) == 30
    assert spans.idle_ns(busy, 15, 35) == 10
    assert spans.idle_ns(busy, 120, 180) == 0
    assert spans.idle_ns(busy, 40, 100) == 60
    assert spans.idle_ns(busy, 250, 300) == 50
    assert spans.idle_ns([], 0, 7) == 7


def test_scripted_fetch_idle_and_idle_between_iterations():
    obs = {"events": scripted()}
    assert spans.device_busy(obs)[0] == [120_000, 420_000]
    assert len(spans.host_events(obs, "serve.decode.iter")) == 3
    assert spans.median_ms(obs, "serve.decode.iter.fetch") == \
        pytest.approx(0.450)
    # fetch: 180, 80, 180 us idle -> median 180
    assert spans.idle_ms_p50(obs, "serve.decode.iter.fetch") == \
        pytest.approx(0.180)
    # between: 540 and 550 us (the last iteration has no successor)
    assert spans.idle_between_ms_p50(
        obs, "serve.decode.iter",
        ("serve.decode.iter.dispatch", "serve.decode.iter.fetch")) == \
        pytest.approx(0.545)
    assert spans.median_ms(obs, "io.prefetch.batch") == pytest.approx(0.240)
    assert spans.median_ms(obs, "io.prefetch.fetch") == pytest.approx(0.190)


def _new_metrics(cell):
    """The per-layer metrics of ``cell`` that read the program's spans
    and phase fields, by name."""
    mf = manifest.load()
    names = ("sched.plan_", "sched.commit_", "sched.rewind_",
             "engine.dispatch_", "engine.fetch_", "fit.data_wait_",
             "fit.metric_", "input.", "sched.between_", "sched.queue_")
    return {m.name: m for m in manifest.resolve(mf, cell).per_layer
            if m.name.startswith(names)}


def test_layer_files_read_the_scripted_observation():
    ring = [{"kind": "serve.decode.step", "window": w, "plan_us": 100 * k,
             "dispatch_us": 1000 * k, "fetch_us": 2000 * k,
             "commit_us": 300 * k, "rewind_us": 10 * k, "step_us": 3100 * k}
            for k, w in ((1, 64), (2, 64), (3, 64), (7, 1), (9, 1))]
    ring += [{"kind": "trace.span", "name": "serve.decode.queue.wait",
              "dur_us": 1000 * k} for k in range(1, 11)]
    ring += [{"kind": "trace.span", "name": "serve.decode.step",
              "dur_us": 10 ** 9}]
    obs = {"events": scripted(), "ring": ring,
           "stepattr": [{"steps": 1, "phases_us": {"data_wait": d,
                                                    "dispatch": 999}}
                        for d in (1000, 3000, 2000)]}
    doc = {n: readers.read(m, obs) for n, m in _new_metrics(DOC).items()}
    assert doc == {
        "sched.plan_ms_p50.doc": pytest.approx(0.2),
        "sched.commit_ms_p50.doc": pytest.approx(0.6),
        "sched.rewind_ms_p50.doc": pytest.approx(0.02),
        "engine.dispatch_ms_p50.doc": pytest.approx(2.0),
        "engine.fetch_ms_p50.doc": pytest.approx(4.0),
        "engine.fetch_idle_ms_p50.doc": pytest.approx(0.180),
        "sched.between_idle_ms_p50.doc": pytest.approx(0.545)}
    chat = {n: readers.read(m, obs) for n, m in _new_metrics(CHAT).items()}
    assert chat == {
        "engine.dispatch_ms_p50.chat": pytest.approx(8.0),
        "engine.fetch_ms_p50.chat": pytest.approx(16.0),
        "sched.queue_wait_ms_p90.chat": pytest.approx(9.0)}
    fit = {n: readers.read(m, obs) for n, m in _new_metrics(FIT).items()}
    assert fit == {
        "fit.data_wait_ms_per_step": pytest.approx(2.0),
        "input.producer_ms_per_batch": pytest.approx(0.240),
        "input.fetch_ms_per_batch": pytest.approx(0.190),
        "input.to_device_ms_per_batch": None,     # no such span scripted
        "fit.metric_ms_per_step": None}


def _recorded(name):
    with open(os.path.join(ROOT, "chipbench", "testdata", name)) as f:
        return trace.unpack(json.load(f)["events"])


@pytest.mark.parametrize("events", [
    scripted_trace.events(), _recorded("v5e_serve_chat_slice.json"),
    _recorded("v5e_fit_dp4_slice.json"), [], None])
def test_a_program_without_the_spans_reads_as_nothing(events):
    """The parent of the PR that added the spans, or an untraced run:
    every reader returns None and raises nothing, so the metric is left
    out of the line."""
    obs = {"events": events, "ring": [{"kind": "serve.decode.step",
                                       "window": 1, "step_us": 5}],
           "stepattr": []}
    for cell in (DOC, CHAT, FIT):
        for name, metric in _new_metrics(cell).items():
            assert readers.read(metric, obs) is None, name


def test_recorded_doc_slice_has_the_phases_inside_the_iterations():
    """A quarter second of the doc cell on the v5e (CHIPBENCH_TRACE_DUMP):
    the scheduler's phases are host events named letter for letter,
    the window program carries its name, and the device is idle for
    most of every fetch."""
    obs = {"events": _recorded("v5e_serve_doc_annotated_slice.json")}
    iters = spans.host_events(obs, "serve.decode.iter")
    assert iters
    # the slice ends 0.4 ms before this iteration's rewind phase begins
    for phase in ("plan", "dispatch", "fetch", "commit"):
        inside = spans.host_events(obs, "serve.decode.iter." + phase)
        assert inside, phase
        for a, b in inside:
            # a phase cut by the slice's start has no iteration around it
            assert a < iters[0][0] or any(s <= a and b <= e
                                          for s, e in iters), phase
    assert any(n.startswith("jit_fwd_infer_8x64")
               for n in trace.modules(obs["events"]))
    fetch_ms = spans.median_ms(obs, "serve.decode.iter.fetch")
    idle_ms = spans.idle_ms_p50(obs, "serve.decode.iter.fetch")
    assert 0 < idle_ms <= fetch_ms
    metrics = _new_metrics(DOC)
    assert readers.read(metrics["engine.fetch_idle_ms_p50.doc"], obs) == \
        idle_ms
