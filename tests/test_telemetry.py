"""Telemetry subsystem: spans, metrics registry, exporters, integration.

Covers the ISSUE 1 acceptance surface: span nesting/ordering, counter/
histogram math, chrome-trace JSON schema (traceEvents with ph/ts/dur/
pid/tid), Prometheus text round-trip, Speedometer/Monitor registry
integration, and the end-to-end snapshot after a dist-sync fit smoke run
(compile-cache hit/miss + KVStore byte counters nonzero).

ISSUE 2 diagnostics layer: flight-recorder ring (always-on, bounded,
crash dumps on exceptions escaping fit/executor), per-context device-
memory accounting (live/peak gauges, assert_no_leak), and the NaN/Inf
sentinel (warn/raise policies, executor-level and per-op attribution,
fused-path coverage).
"""
import gc
import json
import logging
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tm
from mxnet_tpu.telemetry import flightrec, memory as tmem


@pytest.fixture(autouse=True)
def _clean_telemetry():
    tm.disable()
    tm.reset()
    yield
    tm.disable()
    tm.reset()


# --------------------------------------------------------------- span core
def test_span_disabled_is_profiler_annotation_only():
    """With the span buffer off a span is the JAX profiler's annotation
    (jax is imported here) with null_span's surface; nothing buffers."""
    import jax
    assert not tm.enabled()
    s1 = tm.span("anything", k=1)
    assert isinstance(s1, jax.profiler.TraceAnnotation)
    with s1 as entered:
        assert entered.set(more=2) is entered
    assert s1.dur == 0
    assert tm.get_spans() == []


def test_span_nesting_and_ordering():
    tm.enable()
    with tm.span("outer", phase=1):
        with tm.span("inner.a"):
            pass
        with tm.span("inner.b"):
            pass
    spans = tm.get_spans()
    # completion order: children close before the parent
    assert [s.name for s in spans] == ["inner.a", "inner.b", "outer"]
    by_name = {s.name: s for s in spans}
    assert by_name["inner.a"].parent == "outer"
    assert by_name["inner.b"].parent == "outer"
    assert by_name["outer"].parent is None
    assert by_name["inner.a"].depth == 1 and by_name["outer"].depth == 0
    # children are contained in the parent's interval
    o = by_name["outer"]
    for child in ("inner.a", "inner.b"):
        c = by_name[child]
        assert c.ts >= o.ts
        assert c.ts + c.dur <= o.ts + o.dur
    assert o.args == {"phase": 1}


def test_span_survives_exception_and_pops_stack():
    tm.enable()
    with pytest.raises(RuntimeError):
        with tm.span("failing"):
            raise RuntimeError("boom")
    with tm.span("after"):
        pass
    spans = {s.name: s for s in tm.get_spans()}
    assert set(spans) == {"failing", "after"}
    assert spans["after"].parent is None  # stack fully unwound


def test_span_feeds_histogram():
    tm.enable()
    with tm.span("timed", _hist="timed.seconds"):
        pass
    h = tm.get_metric("timed.seconds")
    assert h is not None and h.count == 1


# ----------------------------------------------------------------- metrics
def test_counter_math_and_labels():
    c = tm.counter("widgets")
    c.inc().inc(4)
    assert c.value == 5
    assert tm.counter("widgets") is c          # create-or-get
    c2 = tm.counter("widgets", kind="blue")
    assert c2 is not c and c2.value == 0
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c2.key == 'widgets{kind="blue"}'


def test_held_counters_survive_a_registry_reset():
    """A hot path's handles: looked up once, and again after
    ``reset()`` - an increment never lands on a counter the registry
    has dropped."""
    held = tm.metrics.held_counters("t.held.a", "t.held.b", model="m")
    a, b = held()
    assert held() == (a, b) and a is tm.counter("t.held.a", model="m")
    a.inc(2)
    tm.metrics.reset()
    a2, _b2 = held()
    assert a2 is not a and a2.value == 0
    a2.inc()
    assert tm.get_metric("t.held.a", model="m").value == 1


def test_gauge_set_inc_dec():
    g = tm.gauge("depth")
    g.set(3.5)
    assert g.value == 3.5
    g.inc(2)
    g.dec()
    assert g.value == 4.5


def test_histogram_buckets_and_stats():
    h = tm.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(55.55)
    assert h.min == 0.05 and h.max == 50.0
    assert h.mean == pytest.approx(55.55 / 4)
    # cumulative bucket counts: <=0.1 -> 1, <=1.0 -> 2, <=10.0 -> 3
    assert h.cumulative() == [(0.1, 1), (1.0, 2), (10.0, 3)]


def test_metric_type_collision_raises():
    tm.counter("clash")
    with pytest.raises(TypeError):
        tm.gauge("clash")


def test_snapshot_shape():
    tm.counter("a").inc(2)
    tm.gauge("b").set(7)
    tm.histogram("c").observe(0.5)
    snap = tm.snapshot()
    assert snap["counters"]["a"] == 2
    assert snap["gauges"]["b"] == 7.0
    assert snap["histograms"]["c"]["count"] == 1
    assert "spans" in snap and "events" in snap


# ----------------------------------------------------------- chrome trace
def _valid_trace_event(e):
    assert isinstance(e["name"], str) and e["name"]
    assert e["ph"] in ("X", "M", "i")
    assert isinstance(e["pid"], int)
    if e["ph"] == "X":
        assert isinstance(e["tid"], int)
        assert isinstance(e["ts"], int) and e["ts"] >= 0
        assert isinstance(e["dur"], int) and e["dur"] >= 0
        assert isinstance(e["args"], dict)


def test_chrome_trace_schema(tmp_path):
    tm.enable()
    with tm.span("parent"):
        with tm.span("child", op="FC"):
            pass
    tm.record_event("marker", epoch=0)
    path = tm.chrome_trace.dump(str(tmp_path / "trace.json"),
                                metadata={"mode": "test"})
    doc = json.load(open(path))
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    assert doc["otherData"]["mode"] == "test"
    events = doc["traceEvents"]
    for e in events:
        _valid_trace_event(e)
    complete = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {"parent", "child"}
    child = next(e for e in complete if e["name"] == "child")
    assert child["args"]["op"] == "FC"
    assert child["args"]["parent"] == "parent"
    assert [e["name"] for e in events if e["ph"] == "i"] == ["marker"]
    # lane metadata present for the emitting thread
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in events)


# ------------------------------------------------------------- prometheus
def test_prometheus_round_trip():
    tm.counter("kvstore.push.bytes").inc(1024)
    tm.counter("executor.op_dispatch", op="Convolution").inc(3)
    tm.gauge("speedometer.samples_per_sec").set(1234.5)
    h = tm.histogram("module.fit.batch.seconds", buckets=(0.01, 0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = tm.prometheus.render()
    parsed = tm.prometheus.parse(text)
    types = parsed.pop("__types__")
    assert types["mxnet_kvstore_push_bytes_total"] == "counter"
    assert types["mxnet_speedometer_samples_per_sec"] == "gauge"
    assert types["mxnet_module_fit_batch_seconds"] == "histogram"
    assert parsed["mxnet_kvstore_push_bytes_total"] == 1024
    assert parsed[
        'mxnet_executor_op_dispatch_total{op="Convolution"}'] == 3
    assert parsed["mxnet_speedometer_samples_per_sec"] == 1234.5
    assert parsed['mxnet_module_fit_batch_seconds_bucket{le="0.1"}'] == 1
    assert parsed['mxnet_module_fit_batch_seconds_bucket{le="+Inf"}'] == 2
    assert parsed["mxnet_module_fit_batch_seconds_count"] == 2
    assert parsed["mxnet_module_fit_batch_seconds_sum"] == \
        pytest.approx(0.55)


# ------------------------------------------------------------------ jsonl
def test_jsonl_event_log(tmp_path):
    tm.enable()
    tm.record_event("batch_end", epoch=0, nbatch=1, duration_us=2000,
                    batch_size=32)
    with tm.span("kvstore.push", bytes=64):
        pass
    tm.counter("io.batches", iter="NDArrayIter").inc(7)
    path = tm.jsonl.dump(str(tmp_path / "events.jsonl"))
    recs = [json.loads(ln) for ln in open(path) if ln.strip()]
    by_type = {}
    for r in recs:
        by_type.setdefault(r["type"], []).append(r)
    ev = by_type["event"][0]
    assert ev["kind"] == "batch_end" and ev["epoch"] == 0
    assert ev["batch_size"] == 32                # payload flattened
    sp = by_type["span"][0]
    assert sp["name"] == "kvstore.push" and sp["dur_us"] >= 0
    ctr = by_type["counter"][0]
    assert ctr["name"] == "io.batches" and ctr["value"] == 7
    assert ctr["labels"] == {"iter": "NDArrayIter"}


# ------------------------------------------------- monitor / speedometer
def test_monitor_records_into_registry_and_flush():
    tm.enable()
    mon = mx.Monitor(interval=1, pattern=".*fc.*")
    x = mx.sym.var("data")
    out = mx.sym.FullyConnected(x, num_hidden=4, name="monfc")
    exe = out.simple_bind(ctx=mx.cpu(), data=(2, 3))
    mon.install(exe)
    exe.arg_dict["data"][:] = np.ones((2, 3), "f")
    mon.tic()
    exe.forward(is_train=False)
    records = mon.toc()
    assert records, "monitor collected nothing"
    steps = {r[0] for r in records}
    assert steps == {0}, "all window records must share the tic step"
    # registry gauges exist for observed tensors
    names = [r[1] for r in records]
    g = tm.get_metric("monitor.stat", tensor=names[0])
    assert g is not None and g.value == pytest.approx(float(records[0][2]))
    # monitor events landed in the buffer
    kinds = [e["kind"] for e in tm.get_events()]
    assert "monitor" in kinds

    # flush drops queued entries so cycles don't leak
    mon.tic()
    exe.forward(is_train=False)
    mon.flush()
    assert mon.toc() == []          # window was discarded


def test_monitor_repeated_cycles_do_not_leak():
    mon = mx.Monitor(interval=1, pattern=".*fc.*")
    x = mx.sym.var("data")
    out = mx.sym.FullyConnected(x, num_hidden=4, name="leakfc")
    exe = out.simple_bind(ctx=mx.cpu(), data=(2, 3))
    mon.install(exe)
    exe.arg_dict["data"][:] = np.ones((2, 3), "f")
    sizes = []
    for _ in range(3):
        mon.tic()
        exe.forward(is_train=False)
        sizes.append(len(mon.toc()))
    assert sizes[0] == sizes[1] == sizes[2], sizes


def test_speedometer_records_into_registry():
    tm.enable()
    speedo = mx.callback.Speedometer(batch_size=32, frequent=2)
    metric = mx.metric.create("acc")
    from mxnet_tpu.model import BatchEndParam
    for nbatch in range(1, 5):
        speedo(BatchEndParam(epoch=0, nbatch=nbatch, eval_metric=None,
                             locals=None))
    g = tm.get_metric("speedometer.samples_per_sec")
    assert g is not None and g.value > 0
    speeds = [e for e in tm.get_events() if e["kind"] == "speed"]
    assert speeds and speeds[-1]["payload"]["samples_per_sec"] == g.value


# ------------------------------------------------------- fit integration
def _fit_smoke(kvstore, num_epoch=1, batch_size=4, n=8):
    X = np.random.rand(n, 10).astype("f")
    Y = (np.random.rand(n) * 3).astype("f")
    it = mx.io.NDArrayIter(X, Y, batch_size=batch_size)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=3),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu(),
                        logger=logging.getLogger("telemetry_smoke"))
    mod.fit(it, num_epoch=num_epoch, kvstore=kvstore,
            optimizer_params={"learning_rate": 0.1})
    return mod


def test_snapshot_after_dist_sync_fit():
    """ISSUE 1 acceptance: compile-cache hit/miss and KVStore byte
    counters are nonzero after a dist-sync fit smoke run."""
    tm.enable()
    _fit_smoke("dist_sync")
    snap = tm.snapshot()
    c = snap["counters"]
    assert c.get("executor.jit_cache.miss", 0) > 0
    assert c.get("executor.jit_cache.hit", 0) > 0
    assert c.get("kvstore.push.bytes", 0) > 0
    assert c.get("kvstore.pull.bytes", 0) > 0
    assert c.get("module.fit.batches", 0) == 2
    # per-op dispatch attribution from the registry
    assert any(k.startswith("executor.op_dispatch")
               for k in c), list(c)
    # span timeline covers the whole step
    names = {s.name for s in tm.get_spans()}
    for need in ("executor.compile", "kvstore.push", "kvstore.pull",
                 "io.next", "io.load_batch", "module.fit.batch",
                 "module.fit.epoch"):
        assert need in names, (need, sorted(names))
    assert any(n.startswith("op.") for n in names)
    # batch histograms populated
    h = snap["histograms"].get("module.fit.batch.seconds")
    assert h and h["count"] == 2
    # events for the jsonl log
    kinds = [e["kind"] for e in tm.get_events()]
    assert kinds.count("batch_end") == 2
    assert kinds.count("epoch_end") == 1


def test_fit_disabled_telemetry_records_nothing():
    _fit_smoke("local")
    assert tm.get_spans() == []
    assert tm.get_events() == []
    snap = tm.snapshot()
    # the compile counters are always on (they are how a compile inside
    # a serving window is seen at all); nothing else counts
    assert [k for k in snap["counters"]
            if not k.startswith("xla.compile.")] == []


# --------------------------------------------------------- flight recorder
def test_flight_ring_bounded_and_always_on():
    """The ring records with the span tracer OFF and never exceeds its
    capacity (oldest entries fall off)."""
    flightrec.configure(capacity=8)
    try:
        flightrec.clear()
        assert not tm.enabled()
        for i in range(20):
            flightrec.note("tick", i=i)
        recs = flightrec.get_records()
        assert len(recs) == 8
        assert [r["i"] for r in recs] == list(range(12, 20))
        assert all(r["kind"] == "tick" and r["ts_us"] > 0 for r in recs)
    finally:
        flightrec.configure(capacity=512)


def test_flight_ring_records_fit_timeline_with_tracer_off():
    flightrec.clear()
    _fit_smoke("local")
    kinds = {r["kind"] for r in flightrec.get_records()}
    assert "module.fit.batch" in kinds, kinds
    assert "executor.compile" in kinds or "executor.run" in kinds, kinds
    assert "executor.bind" in kinds, kinds
    batches = [r for r in flightrec.get_records()
               if r["kind"] == "module.fit.batch"]
    assert all(r["dur_us"] > 0 for r in batches)


def test_flight_ring_mirrors_spans_and_events_when_enabled():
    tm.enable()
    flightrec.clear()
    with tm.span("mirrored.phase", step=1):
        pass
    tm.record_event("mirrored_marker", epoch=0)
    recs = flightrec.get_records()
    assert any(r["kind"] == "span" and r["name"] == "mirrored.phase"
               for r in recs)
    assert any(r["kind"] == "mirrored_marker" and r["epoch"] == 0
               for r in recs)


def test_crash_dump_on_fit_exception_and_diagnose(tmp_path):
    """ISSUE 2 acceptance: a Module.fit run killed by an injected
    mid-batch exception leaves a crash dump on disk (recent ring,
    memory watermarks, metrics snapshot) that tools/diagnose.py
    renders."""
    flightrec.configure(dump_dir=str(tmp_path))
    try:
        flightrec.clear()
        X = np.random.rand(16, 10).astype("f")
        Y = (np.random.rand(16) * 3).astype("f")
        it = mx.io.NDArrayIter(X, Y, batch_size=4)
        net = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=3),
            name="softmax")
        mod = mx.mod.Module(net, context=mx.cpu())

        class Boom(RuntimeError):
            pass

        def bomb(param):
            if param.nbatch == 1:
                raise Boom("injected mid-batch failure")

        with pytest.raises(Boom):
            mod.fit(it, num_epoch=1, batch_end_callback=bomb,
                    optimizer_params={"learning_rate": 0.1})

        dumps = sorted(tmp_path.glob("mxnet_crash_*.json"))
        assert len(dumps) == 1, dumps      # exactly one dump per crash
        rep = json.load(open(dumps[0]))
        assert rep["type"] == "crash_report"
        assert rep["where"] == "module.fit"
        assert rep["exception"]["type"] == "Boom"
        assert "injected mid-batch" in rep["exception"]["message"]
        # ring carries the recent timeline: batches ran before the crash
        kinds = [r["kind"] for r in rep["ring"]]
        assert "module.fit.batch" in kinds
        # memory watermarks and metrics snapshot present
        assert rep["memory"] and all(
            "live_bytes" in v and "peak_bytes" in v
            for v in rep["memory"].values())
        assert "counters" in rep["metrics"]
        assert rep["devices"], "jax device info missing"
        assert any(k.startswith("MXNET_") or k.startswith("JAX_")
                   for k in rep["env"])

        # tools/diagnose.py renders it human-readable
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        try:
            import diagnose
        finally:
            sys.path.pop(0)
        text = diagnose.render_file(str(dumps[0]))
        assert "CRASH REPORT" in text
        assert "Boom" in text
        assert "module.fit" in text
        assert "memory watermarks:" in text
        assert "module.fit.batch" in text          # timeline rendered
    finally:
        flightrec.configure(dump_dir=os.environ.get("MXNET_CRASH_DIR",
                                                    "."))


def test_crash_dump_deduped_across_nested_guards(tmp_path):
    """An exception escaping Executor.backward inside fit passes two
    crash guards — only the innermost writes a dump."""
    flightrec.configure(dump_dir=str(tmp_path))
    try:
        x = mx.sym.var("data")
        net = mx.sym.FullyConnected(x, num_hidden=4, name="dedupfc")
        exe = net.simple_bind(ctx=mx.cpu(), data=(2, 3))
        with pytest.raises(mx.MXNetError):
            exe.backward()          # no prior forward: raises
        # user errors raised before dispatch carry no dump; now force a
        # dispatch-time failure via a sentinel raise
        sent = tm.NanSentinel(policy="raise")
        sent.install(exe)
        exe.arg_dict["data"][:] = np.full((2, 3), np.nan, "f")
        with pytest.raises(tm.AnomalyError):
            exe.forward(is_train=False)
        dumps = sorted(tmp_path.glob("mxnet_crash_*.json"))
        assert len(dumps) == 1
        assert json.load(open(dumps[0]))["where"] == "executor.forward"
    finally:
        flightrec.configure(dump_dir=os.environ.get("MXNET_CRASH_DIR",
                                                    "."))


# ------------------------------------------------------- memory accounting
def test_memory_accounting_bind_run_free_cycle():
    """ISSUE 2 acceptance: per-context live/peak gauges track a
    bind/run/free cycle and assert_no_leak() passes."""
    gc.collect()
    key = "cpu(0)"
    base = tmem.live_bytes(key)
    with tmem.assert_no_leak(ctx=key):
        x = mx.sym.var("data")
        net = mx.sym.FullyConnected(x, num_hidden=16, name="memfc")
        exe = net.simple_bind(ctx=mx.cpu(), data=(8, 4))
        # bind allocated visible bytes: data (8x4) + weight (16x4) +
        # bias (16), each f32, plus grads
        grown = tmem.live_bytes(key)
        assert grown >= base + (8 * 4 + 16 * 4 + 16) * 4
        # the executor reported its footprint at bind time
        fp = exe.memory_footprint
        assert fp["arg_bytes"] == (8 * 4 + 16 * 4 + 16) * 4
        assert fp["grad_bytes"] > 0
        assert fp["output_bytes"] == 8 * 16 * 4
        g = tm.get_metric("executor.memory.arg_bytes", ctx=key)
        assert g is not None and g.value == fp["arg_bytes"]
        exe.forward(is_train=False)
        _ = exe.outputs
        assert tmem.peak_bytes(key) >= tmem.live_bytes(key) > grown - 1
        del exe, _
    # after the cycle the ledger is back at (or below) baseline; the
    # registry gauges track the ledger
    gc.collect()
    assert tmem.live_bytes(key) <= base + 1
    snap = tm.snapshot()
    assert key in snap["memory"]
    assert snap["memory"][key]["live_bytes"] == tmem.live_bytes(key)
    g = tm.get_metric("memory.live_bytes", ctx=key)
    assert g is not None and g.value == tmem.live_bytes(key)


def test_assert_no_leak_catches_held_array():
    holder = []
    with pytest.raises(AssertionError, match="leak"):
        with tmem.assert_no_leak(ctx="cpu(0)"):
            holder.append(mx.nd.zeros((4096,)))
    holder.clear()


def test_memory_accounting_swap_adjusts_live():
    a = mx.nd.zeros((1024,))            # 4 KiB f32
    live0 = tmem.live_bytes("cpu(0)")
    a._set(a.asjax()[:256])             # shrink to 1 KiB
    assert tmem.live_bytes("cpu(0)") == live0 - 3 * 1024
    del a


# ---------------------------------------------------------------- sentinel
def _nan_executor(policy, per_op=False, train=False):
    x = mx.sym.var("data")
    net = mx.sym.FullyConnected(x, num_hidden=4, name="sentfc")
    if train:
        net = mx.sym.SoftmaxOutput(net, name="softmax")
    exe = net.simple_bind(ctx=mx.cpu(), data=(2, 3))
    sent = tm.NanSentinel(policy=policy)
    sent.install(exe, per_op=per_op)
    exe.arg_dict["data"][:] = np.full((2, 3), np.nan, "f")
    for nm in ("sentfc_weight",):
        exe.arg_dict[nm][:] = np.ones(exe.arg_dict[nm].shape, "f")
    return exe, sent


def test_sentinel_warn_flags_output_with_attribution(tmp_path):
    flightrec.configure(dump_dir=str(tmp_path))
    try:
        flightrec.clear()
        exe, sent = _nan_executor("warn")
        exe.forward(is_train=False)
        _ = exe.outputs
        assert sent.anomalies == [
            {"step": 0, "kind": "output", "array": "sentfc_output"}]
        # registry counter with op/array attribution
        c = tm.get_metric("sentinel.anomalies", kind="output",
                          array="sentfc_output")
        assert c is not None and c.value == 1
        # anomaly landed in the flight ring for the crash timeline
        assert any(r["kind"] == "anomaly"
                   and r["array"] == "sentfc_output"
                   for r in flightrec.get_records())
        # warn policy: training continues (no exception), second window
        # flags again
        exe.forward(is_train=False)
        _ = exe.outputs
        assert len(sent.anomalies) == 2 and c.value == 2
    finally:
        flightrec.configure(dump_dir=os.environ.get("MXNET_CRASH_DIR",
                                                    "."))


def test_sentinel_raise_policy_and_crash_dump(tmp_path):
    flightrec.configure(dump_dir=str(tmp_path))
    try:
        exe, sent = _nan_executor("raise")
        with pytest.raises(tm.AnomalyError, match="sentfc_output"):
            exe.forward(is_train=False)
        # the raise escaped the executor -> crash report with the
        # anomaly in its ring
        dumps = sorted(tmp_path.glob("mxnet_crash_*.json"))
        assert dumps
        rep = json.load(open(dumps[-1]))
        assert rep["exception"]["type"] == "AnomalyError"
        assert any(r["kind"] == "anomaly" for r in rep["ring"])
    finally:
        flightrec.configure(dump_dir=os.environ.get("MXNET_CRASH_DIR",
                                                    "."))


def test_sentinel_per_op_attribution():
    exe, sent = _nan_executor("warn", per_op=True)
    exe.forward(is_train=False)
    _ = exe.outputs
    kinds = {a["kind"] for a in sent.anomalies}
    assert "op_output" in kinds          # Monitor-tap install point fired
    assert any(a["array"] == "sentfc_output" for a in sent.anomalies
               if a["kind"] == "op_output")


def test_sentinel_flags_nan_gradients():
    exe, sent = _nan_executor("warn", train=True)
    exe.forward(is_train=True)
    exe.backward()
    grads = [a for a in sent.anomalies if a["kind"] == "gradient"]
    assert grads, sent.anomalies
    assert all(a["array"] in exe.arg_names for a in grads)


def test_sentinel_interval_windows():
    exe, sent = _nan_executor("warn")
    sent.interval = 2
    for _ in range(4):
        exe.forward(is_train=False)
        _ = exe.outputs
    # steps 0 and 2 checked; 1 and 3 skipped
    assert [a["step"] for a in sent.anomalies] == [0, 2]


def test_sentinel_module_fused_path_raise(tmp_path):
    """The sentinel trips inside the fused fwd+bwd+update step and the
    escaping AnomalyError leaves a crash dump."""
    flightrec.configure(dump_dir=str(tmp_path))
    try:
        X = np.random.rand(16, 10).astype("f")
        X[6, :] = np.nan                 # second batch poisons outputs
        Y = (np.random.rand(16) * 3).astype("f")
        it = mx.io.NDArrayIter(X, Y, batch_size=4)
        net = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=3),
            name="softmax")
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.bind([("data", (4, 10))], [("softmax_label", (4,))])
        mod.install_sentinel(tm.NanSentinel(policy="raise"))
        with pytest.raises(tm.AnomalyError):
            mod.fit(it, num_epoch=1,
                    optimizer_params={"learning_rate": 0.1})
        assert mod._fused_armed          # tripped on the fused path
        dumps = sorted(tmp_path.glob("mxnet_crash_*.json"))
        assert dumps
        rep = json.load(open(dumps[-1]))
        assert any(r["kind"] == "anomaly" for r in rep["ring"])
    finally:
        flightrec.configure(dump_dir=os.environ.get("MXNET_CRASH_DIR",
                                                    "."))


def test_histogram_quantile_estimation():
    """Histogram.quantile: bucket-interpolated percentile estimates
    (the serving p50/p99 SLO readout) — exact at bucket bounds, clamped
    to the recorded max above the last bound, None while empty."""
    from mxnet_tpu.telemetry.metrics import Histogram
    h = Histogram("t.q", (), buckets=(0.01, 0.1, 1.0))
    assert h.quantile(0.5) is None
    for v in (0.005, 0.005, 0.05, 0.05, 0.5, 0.5, 2.0, 3.0):
        h.observe(v)
    # 8 observations: ranks 1-2 in <=0.01, 3-4 in <=0.1, 5-6 in <=1.0,
    # 7-8 above the last bound
    assert h.quantile(0.25) == pytest.approx(0.01)
    assert h.quantile(0.5) == pytest.approx(0.1)
    assert h.quantile(1.0) == pytest.approx(3.0)    # clamps to max
    q99 = h.quantile(0.99)
    assert q99 == pytest.approx(3.0)                # beyond last bucket
    assert 0.01 <= h.quantile(0.4) <= 0.1           # interpolated
