"""Request-scoped trace plane + step-time attribution (ISSUE 14).

Gates, per the acceptance criteria:

* a served request — including a multi-step stateful decode session —
  reconstructs to a SINGLE parented span tree from the trace buffer /
  ring export, deterministic under FakeClock;
* ``step.phase.*`` histograms sum to within 5% of the measured step
  wall time on both the fused (K=1) and the K=4 scan paths;
* ``Histogram.quantile``'s exemplar plumbing leaves the default
  Prometheus exposition byte-identical (golden-output test), and
  trace records stay inside the flight ring's capacity bound.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tm
from mxnet_tpu.serve import FakeClock
from mxnet_tpu.telemetry import stepattr as sa
from mxnet_tpu.telemetry import trace as trc


@pytest.fixture(autouse=True)
def _clean_trace_state():
    trc.configure(capacity=4096, sample=1.0, reset_ids=True)
    trc.clear()
    sa.reset()
    tm.flightrec.clear()
    yield
    sa.configure(armed=None)
    trc.configure(capacity=4096, sample=1.0)


def _mlp(prefix="fc", feat=6, hidden=8, classes=3):
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=hidden,
                               name=f"{prefix}1")
    act = mx.sym.Activation(fc, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=classes,
                                name=f"{prefix}2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _bound_module(sym, feat=6, batch=4):
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind([("data", (batch, feat))], [("softmax_label", (batch,))],
             for_training=False)
    mod.init_params(mx.initializer.Xavier())
    return mod


# ------------------------------------------------------------- primitives
def test_record_tree_and_dedupe():
    tr = trc.new_trace()
    root = trc.record(tr, "serve.request", 0.0, 0.10, model="m")
    a = trc.record(tr, "serve.queue.wait", 0.0, 0.04, parent=root)
    trc.record(tr, "serve.exec", 0.04, 0.10, parent=root)
    # a span id re-recorded (growing session root) dedupes last-wins
    trc.record(tr, "serve.request", 0.0, 0.20, span_id=root, model="m")
    t = trc.tree(tr.trace_id)
    assert t["name"] == "serve.request" and t["dur_us"] == 200000
    assert [c["name"] for c in t["children"]] == \
        ["serve.queue.wait", "serve.exec"]
    assert t["children"][0]["span"] == a
    assert len(trc.spans(tr.trace_id)) == 3      # deduped
    assert tr.root == root


def test_trace_buffer_capacity_bounded():
    trc.configure(capacity=8)
    tr = trc.new_trace()
    for i in range(50):
        trc.record(tr, f"s{i}", 0.0, 0.001)
    assert len(trc.spans()) <= 8


def test_flight_ring_counts_trace_records_under_capacity():
    """Bugfix sweep: trace records ride the flight ring under the
    existing MXNET_FLIGHT_RECORDER_CAPACITY bound — an always-on trace
    plane can never grow the ring unbounded."""
    tm.flightrec.configure(capacity=32)
    try:
        tr = trc.new_trace()
        for i in range(200):
            trc.record(tr, f"s{i}", 0.0, 0.001)
        recs = tm.flightrec.get_records()
        assert len(recs) <= 32
        assert all(r["kind"] == "trace.span" for r in recs)
    finally:
        tm.flightrec.configure(capacity=512)
        tm.flightrec.clear()


def test_sampling_deterministic():
    trc.configure(sample=0.5)
    picks = [trc.sample() for _ in range(10)]
    assert sum(picks) == 5
    trc.configure(sample=0.5)        # reset the counter: same decisions
    assert [trc.sample() for _ in range(10)] == picks
    trc.configure(sample=0.0)
    assert not any(trc.sample() for _ in range(5))
    trc.configure(sample=1.0)
    assert all(trc.sample() for _ in range(5))


# ------------------------------------------------------- serve span trees
def test_served_request_span_tree_deterministic_fakeclock():
    """Acceptance: a served request reconstructs to a single parented
    span tree, byte-deterministic under FakeClock — and batch-mates
    share the dispatch span id."""
    clock = FakeClock()
    sym = _mlp("tr")
    server = mx.serve.serve(_bound_module(sym), ladder=[1, 2, 4],
                            start=False, clock=clock,
                            default_deadline_ms=10)
    rs = np.random.RandomState(0)
    h1 = server.submit({"data": rs.rand(2, 6).astype(np.float32)})
    h2 = server.submit({"data": rs.rand(1, 6).astype(np.float32)})
    assert h1.trace_id and h2.trace_id and h1.trace_id != h2.trace_id
    clock.advance(0.010)
    assert server.pump() == 1

    t = trc.tree(h1.trace_id)
    assert t["name"] == "serve.request"
    assert t["ts_us"] == 0 and t["dur_us"] == 10000   # exact fake time
    assert t["model"] == "default" and t["rows"] == 2
    kids = {c["name"]: c for c in t["children"]}
    assert set(kids) == {"serve.queue.wait", "serve.dispatch"}
    assert kids["serve.queue.wait"]["dur_us"] == 10000
    disp = kids["serve.dispatch"]
    assert disp["n_requests"] == 2 and disp["shared"] is True
    assert [c["name"] for c in disp["children"]] == \
        ["serve.assemble", "serve.exec", "serve.respond"]
    # every span of the tree carries the same trace id
    assert {r["trace"] for r in trc.spans(h1.trace_id)} == {h1.trace_id}

    # the batch-mate's tree shares the dispatch span id, nothing else
    t2 = trc.tree(h2.trace_id)
    disp2 = [c for c in t2["children"] if c["name"] == "serve.dispatch"][0]
    assert disp2["span"] == disp["span"]
    assert t2["span"] != t["span"]

    # the ring mirrored the records (joinable post-mortem)
    ring = [r for r in tm.flightrec.get_records()
            if r["kind"] == "trace.span"]
    assert {r["trace"] for r in ring} >= {h1.trace_id, h2.trace_id}
    disp_ring = [r for r in tm.flightrec.get_records()
                 if r["kind"] == "serve.dispatch"]
    assert disp_ring and set(disp_ring[-1]["trace_ids"]) == \
        {h1.trace_id, h2.trace_id}


def test_session_trace_multi_step_single_tree():
    """Acceptance (stateful-decode shape through serve): N submits that
    join one session trace reconstruct to ONE tree — per-step request
    roots parented under the session root."""
    clock = FakeClock()
    sym = _mlp("ss")
    server = mx.serve.serve(_bound_module(sym), ladder=[1, 2],
                            start=False, clock=clock,
                            default_deadline_ms=5)
    session = trc.new_trace(session=True)
    rs = np.random.RandomState(1)
    for _step in range(3):
        server.submit({"data": rs.rand(1, 6).astype(np.float32)},
                      trace=session)
        clock.advance(0.005)
        assert server.pump() == 1
    t = trc.tree(session.trace_id)
    assert t["name"] == "serve.decode.session"
    steps = [c for c in t["children"] if c["name"] == "serve.request"]
    assert len(steps) == 3
    # one trace id across all N steps; the session root spans them all
    assert {r["trace"] for r in trc.spans(session.trace_id)} == \
        {session.trace_id}
    assert t["dur_us"] == steps[-1]["ts_us"] + steps[-1]["dur_us"] - \
        steps[0]["ts_us"]


def test_shed_request_stamps_trace_ids():
    """Satellite: a shed request is traceable to the queue state that
    doomed it — ShedError.trace_id, the serve.shed ring record's
    trace_ids, and a root span carrying queue depth/watermark."""
    clock = FakeClock()
    sym = _mlp("sh")
    server = mx.serve.serve(_bound_module(sym), ladder=[1, 2, 4],
                            start=False, clock=clock, max_queue=8,
                            shed_watermark=2, default_deadline_ms=1000)
    rs = np.random.RandomState(2)
    h1 = server.submit({"data": rs.rand(1, 6).astype(np.float32)},
                       deadline_ms=1)
    h2 = server.submit({"data": rs.rand(1, 6).astype(np.float32)},
                       deadline_ms=1)
    clock.advance(0.005)            # both queued requests now doomed
    h3 = server.submit({"data": rs.rand(1, 6).astype(np.float32)})
    for h in (h1, h2):
        exc = h.exception()
        assert isinstance(exc, mx.serve.ShedError)
        assert exc.trace_id == h.trace_id
        root = trc.tree(h.trace_id)
        assert root["error"] == "shed"
        assert root["queue_depth"] == 0 and root["shed_depth"] == 2
        assert root["retry_after_ms"] >= 1
        assert [c["name"] for c in root["children"]] == \
            ["serve.queue.wait"]
    shed_recs = [r for r in tm.flightrec.get_records()
                 if r["kind"] == "serve.shed"]
    assert shed_recs and set(shed_recs[-1]["trace_ids"]) == \
        {h1.trace_id, h2.trace_id}
    assert not h3.done()            # the live request kept its slot


def test_breaker_reject_stamps_trace_id():
    """Satellite: a breaker-open rejection leaves a trace-stamped ring
    record and CircuitOpenError.trace_id."""
    clock = FakeClock()
    sym = _mlp("br")
    server = mx.serve.serve(_bound_module(sym), ladder=[1, 2],
                            start=False, clock=clock,
                            breaker_threshold=2)
    entry = server._registry.entry("default")
    now = clock.now()
    entry.breaker.record_failure(now)
    entry.breaker.record_failure(now)
    rs = np.random.RandomState(3)
    with pytest.raises(mx.serve.CircuitOpenError) as ei:
        server.submit({"data": rs.rand(1, 6).astype(np.float32)})
    assert ei.value.trace_id is not None
    root = trc.tree(ei.value.trace_id)
    assert root["name"] == "serve.request"
    assert root["error"] == "circuit_open"
    rej = [r for r in tm.flightrec.get_records()
           if r["kind"] == "serve.breaker.reject"]
    assert rej and rej[-1]["trace"] == ei.value.trace_id


def test_stats_surfaces_exemplar_and_slowest_trace():
    # the registry is the process's: a "default" model served by a file
    # that ran earlier on this worker would own the p99 exemplar
    tm.metrics.reset()
    clock = FakeClock()
    sym = _mlp("st")
    server = mx.serve.serve(_bound_module(sym), ladder=[1, 2],
                            start=False, clock=clock,
                            default_deadline_ms=20)
    rs = np.random.RandomState(4)
    h = server.submit({"data": rs.rand(1, 6).astype(np.float32)})
    clock.advance(0.020)
    server.pump()
    m = server.stats()["models"]["default"]
    assert m["p99_trace"] == h.trace_id
    assert m["slowest_trace"]["trace"] == h.trace_id
    assert m["slowest_trace"]["latency_ms"] == pytest.approx(20.0)


# ------------------------------------------------------------- exemplars
def test_prometheus_default_render_byte_identical_golden():
    """Bugfix sweep: exemplar plumbing must not change the default
    exposition format — pinned against the exact expected text."""
    tm.metrics.reset()
    h = tm.histogram("lat.seconds", buckets=(0.1, 1.0), model="m")
    h.observe(0.05, exemplar="t000001")
    h.observe(0.5, exemplar="t000002")
    h.observe(5.0, exemplar="t000003")
    tm.counter("reqs", model="m").inc(3)
    expected = (
        '# TYPE mxnet_lat_seconds histogram\n'
        'mxnet_lat_seconds_bucket{model="m",le="0.1"} 1\n'
        'mxnet_lat_seconds_bucket{model="m",le="1"} 2\n'
        'mxnet_lat_seconds_bucket{model="m",le="+Inf"} 3\n'
        'mxnet_lat_seconds_sum{model="m"} 5.55\n'
        'mxnet_lat_seconds_count{model="m"} 3\n'
        '# TYPE mxnet_reqs_total counter\n'
        'mxnet_reqs_total{model="m"} 3\n')
    assert tm.prometheus.render() == expected
    # the existing parser round-trips the (unchanged) default text
    parsed = tm.prometheus.parse(tm.prometheus.render())
    assert parsed['mxnet_lat_seconds_count{model="m"}'] == 3
    # quantile estimation is untouched by exemplars
    assert h.quantile(0.5) == pytest.approx(0.55, rel=0.02)
    # openmetrics opt-in renders them
    om = tm.prometheus.render(openmetrics=True)
    assert '# {trace_id="t000001"} 0.05' in om
    assert '# {trace_id="t000003"} 5' in om
    tm.metrics.reset()


def test_histogram_exemplar_tracks_quantile_bucket():
    tm.metrics.reset()
    h = tm.histogram("q.seconds", buckets=(0.01, 0.1, 1.0))
    for i in range(99):
        h.observe(0.005, exemplar=f"fast{i}")
    h.observe(0.5, exemplar="slow")
    assert h.exemplar(0.5) == "fast98"
    assert h.exemplar(0.999) == "slow"
    assert h.exemplar(0.99) in ("fast98", "slow")
    tm.metrics.reset()


# ----------------------------------------------------- step attribution
def _fit_mod(prefix, batches=8, batch=8, feat=6, K=1, epochs=1):
    X = np.random.rand(batches * batch, feat).astype(np.float32)
    Y = (np.random.rand(batches * batch) * 3).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=batch)
    mod = mx.mod.Module(_mlp(prefix, feat=feat), context=mx.cpu())
    mod.fit(it, num_epoch=epochs, steps_per_dispatch=K,
            initializer=mx.initializer.Xavier(),
            optimizer_params={"learning_rate": 0.05})
    return mod


def _phase_hist_sums():
    snap = tm.metrics.snapshot()["histograms"]
    out = {}
    for key, rec in snap.items():
        if key.startswith("step.phase."):
            out[key[len("step.phase."):-len(".seconds")]] = rec
    return out


def test_step_phases_sum_to_wall_fused():
    """Acceptance: step.phase.* histograms sum to within 5% of the
    measured step wall time on the fused (K=1) path."""
    tm.metrics.reset()
    sa.configure(armed=True)
    _fit_mod("sp1", batches=8)
    recs = sa.records()
    assert len(recs) == 8
    for r in recs:
        assert r["steps"] == 1
        assert sum(r["phases_us"].values()) == \
            pytest.approx(r["wall_us"], rel=0.05)
    hists = _phase_hist_sums()
    assert set(hists) == set(sa.PHASES)
    assert all(rec["count"] == 8 for rec in hists.values())
    total_wall = sum(r["wall_us"] for r in recs) / 1e6
    total_phases = sum(rec["sum"] for rec in hists.values())
    assert total_phases == pytest.approx(total_wall, rel=0.05)
    # the real phases were attributed, not just folded into "other"
    assert hists["dispatch"]["sum"] > 0 and hists["device"]["sum"] >= 0
    assert hists["data_wait"]["count"] == 8
    assert tm.get_metric("step.count").value == 8


def test_step_phases_sum_to_wall_scan_k4():
    """Acceptance: same 5% gate on the K=4 scan path — one attribution
    record per window, phases divided over the K logical batches, and
    one device block per window only."""
    tm.metrics.reset()
    sa.configure(armed=True)
    _fit_mod("sp4", batches=8, K=4)
    recs = sa.records()
    assert len(recs) == 2 and all(r["steps"] == 4 for r in recs)
    for r in recs:
        assert sum(r["phases_us"].values()) == \
            pytest.approx(r["wall_us"], rel=0.05)
    hists = _phase_hist_sums()
    assert all(rec["count"] == 2 for rec in hists.values())
    total_wall_per_step = sum(r["wall_us"] / r["steps"]
                              for r in recs) / 1e6
    total_phases = sum(rec["sum"] for rec in hists.values())
    assert total_phases == pytest.approx(total_wall_per_step, rel=0.05)
    assert tm.get_metric("step.count").value == 8


def test_step_attribution_unarmed_records_nothing():
    sa.configure(armed=None)
    tm.metrics.reset()
    assert not sa.armed()
    _fit_mod("sp0", batches=4)
    assert sa.records() == []
    assert not _phase_hist_sums()


def test_straggler_detector_flags_with_phase_breakdown():
    """A step k*MAD above the rolling median is flagged with its phase
    breakdown (scripted clock: fully deterministic)."""
    t = [0.0]

    def fake_clock():
        return t[0]

    prev = sa.use_clock(fake_clock)
    sa.configure(armed=True, k_mad=5.0)
    tm.metrics.reset()
    try:
        def one_step(dur, n):
            sa.step_begin(0, n)
            sa.note("assemble", dur * 0.25)
            sa.note("dispatch", dur * 0.25)
            t[0] += dur
            sa.step_end()

        for n in range(20):
            one_step(0.010, n)
        assert sa.stragglers() == []
        one_step(0.200, 20)              # 20x the median: a stall
        strag = sa.stragglers()
        assert len(strag) == 1
        rec = strag[0]
        assert rec["nbatch"] == 20 and rec["straggler"]
        assert rec["wall_us"] == 200000
        assert rec["median_us"] == 10000
        assert rec["phases_us"]["assemble"] == 50000
        assert rec["phases_us"]["other"] == 100000
        assert tm.get_metric("step.stragglers").value == 1
        ring = [r for r in tm.flightrec.get_records()
                if r["kind"] == "step.straggler"]
        assert ring and ring[-1]["wall_us"] == 200000
        assert ring[-1]["assemble_us"] == 50000
    finally:
        sa.use_clock(prev)
        sa.configure(armed=None, k_mad=5.0)
        sa.reset()


# ------------------------------------------------- decode session traces
def test_kv_cache_decoder_single_trace_across_steps():
    """Acceptance: a multi-step stateful decode carries ONE trace —
    every token step a child span of the session root; reset() rotates
    to a fresh session."""
    from mxnet_tpu.models import transformer as tfm
    V, D, H, T, B = 64, 32, 4, 8, 4
    full_sym = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=1,
                              n_head=H, seq_len=T, include_loss=False,
                              max_seq_len=T)
    full = mx.mod.Module(full_sym, label_names=[])
    full.bind([("data", (B, T))], None, for_training=False)
    full.init_params(mx.initializer.Xavier(magnitude=2.0))
    args, _ = full.get_params()
    dec_sym = tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=1,
                                    n_head=H, capacity=T, max_seq_len=T)
    dec = mx.mod.Module(dec_sym, label_names=[])
    dec.bind([("data", (B, 1))], None, for_training=False)
    dec.init_params(initializer=None, arg_params=args, aux_params={},
                    allow_missing=True)
    drv = tfm.KVCacheDecoder(dec, capacity=T)
    sid = drv.trace.trace_id
    tokens = np.random.RandomState(5).randint(0, V, (B, T)).astype(
        np.int32)
    for step in range(4):
        drv.step(tokens[:, step:step + 1])
    t = trc.tree(sid)
    assert t["name"] == "lm.decode.session"
    steps = [c for c in t["children"] if c["name"] == "lm.decode.step"]
    assert len(steps) == 4
    assert [s["pos"] for s in steps] == [0, 1, 2, 3]
    assert {r["trace"] for r in trc.spans(sid)} == {sid}
    # the session root grew across steps: it covers first -> last
    assert t["dur_us"] >= steps[-1]["ts_us"] + steps[-1]["dur_us"] - \
        t["ts_us"] - 1
    drv.reset()
    assert drv.trace.trace_id != sid     # a new sequence = a new trace
    drv.step(tokens[:, :1])
    t2 = trc.tree(drv.trace.trace_id)
    assert len([c for c in t2["children"]
                if c["name"] == "lm.decode.step"]) == 1


# ----------------------------------------------------- exporters / tools
def test_dump_profile_includes_serve_and_step_tracks(tmp_path):
    """Satellite: profiler.dump_profile's chrome trace carries the new
    track names — serve.trace/* lanes and the step.phase lane."""
    clock = FakeClock()
    sym = _mlp("dp")
    server = mx.serve.serve(_bound_module(sym), ladder=[1, 2],
                            start=False, clock=clock,
                            default_deadline_ms=10)
    h = server.submit({"data": np.random.RandomState(6)
                       .rand(1, 6).astype(np.float32)})
    clock.advance(0.010)
    server.pump()
    sa.configure(armed=True)
    _fit_mod("dpf", batches=4)
    sa.configure(armed=None)

    path = str(tmp_path / "profile.json")
    mx.profiler.profiler_set_config(filename=path)
    out = mx.profiler.dump_profile()
    with open(out) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    tracks = {e["args"]["name"] for e in events
              if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert f"serve.trace/{h.trace_id}" in tracks
    assert "step.phase" in tracks
    xnames = {e["name"] for e in events if e.get("ph") == "X"}
    assert "serve.request" in xnames and "serve.dispatch" in xnames
    assert "step" in xnames
    assert any(n.startswith("step.phase.") for n in xnames)
    # phase events nest inside their step interval on the step lane
    steps = [e for e in events if e.get("ph") == "X"
             and e["name"] == "step"]
    phases = [e for e in events if e.get("ph") == "X"
              and e["name"].startswith("step.phase.")]
    assert steps and phases
    s0 = steps[0]
    inside = [p for p in phases
              if s0["ts"] <= p["ts"] <= s0["ts"] + s0["dur"] + 1]
    assert inside


def test_jsonl_and_diagnose_render_traces_sections(tmp_path):
    """Satellite: tools/diagnose.py renders the traces section (request
    trees, step-phase table, stragglers) in BOTH the jsonl and the
    crash paths."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import diagnose

    clock = FakeClock()
    sym = _mlp("dg")
    server = mx.serve.serve(_bound_module(sym), ladder=[1, 2],
                            start=False, clock=clock,
                            default_deadline_ms=25)
    h = server.submit({"data": np.random.RandomState(7)
                       .rand(1, 6).astype(np.float32)})
    clock.advance(0.025)
    server.pump()
    sa.configure(armed=True)
    _fit_mod("dgf", batches=4)
    sa.configure(armed=None)
    # a scripted straggler so the list renders
    t = [0.0]
    prev = sa.use_clock(lambda: t[0])
    try:
        sa.configure(armed=True)
        for n in range(16):
            sa.step_begin(1, n)
            t[0] += 0.01
            sa.step_end()
        sa.step_begin(1, 16)
        t[0] += 0.3
        sa.step_end()
    finally:
        sa.use_clock(prev)
        sa.configure(armed=None)

    # jsonl path
    jl = tm.jsonl.dump(str(tmp_path / "ev.jsonl"))
    with open(jl) as f:
        lines = f.read().splitlines()
    trace_lines = [json.loads(l) for l in lines
                   if json.loads(l).get("type") == "trace"]
    assert {r["trace"] for r in trace_lines} >= {h.trace_id}
    report = diagnose.render_file(jl)
    assert "traces:" in report
    assert "serve.request" in report and "serve.queue.wait" in report
    assert "step phases (per logical batch):" in report
    assert "stragglers:" in report

    # crash path (ring-mirrored records)
    tm.flightrec.configure(dump_dir=str(tmp_path))
    crash = tm.flightrec.dump_crash(where="test_trace")
    report2 = diagnose.render_file(crash)
    assert "traces:" in report2
    assert "serve.request" in report2
    assert "step phases (per logical batch):" in report2
    assert "stragglers:" in report2
