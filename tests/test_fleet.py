"""Fleet observability plane (ISSUE 16): rank identity, snapshot/merge,
the live ops endpoint and fleet forensics.

Tier-1 coverage for the cross-rank layer:

* ``telemetry.fleet`` — rank resolution precedence, versioned
  ``snapshot()``, lossless ``merge()`` (counters sum exactly, gauges
  keep per-rank + min/max/mean, histograms merge bucket-wise so fleet
  quantiles stay within one bucket width of the pooled stream);
* ``telemetry.prometheus.render(fleet=...)`` — one exposition text
  with ``rank`` labels on every sample;
* ``telemetry.opsd`` — /metrics (OpenMetrics negotiation), /healthz
  (200/503), /varz, /tracez, /fleetz, scraped during a live fit loop;
* ``tools/fleetstat.py`` — the fast chaos-shaped path: synthesized
  3-rank dumps with a straggler, a diverging rank, and a dead rank
  must produce the same report shape the @slow chaos test asserts on
  real per-rank dumps (tests/test_chaos.py), byte-deterministically;
* ``tools/diagnose.py`` — the decode-engine section renders in BOTH
  the crash-report and the jsonl path.
"""
import json
import os
import random
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.telemetry import fleet, metrics, opsd, prometheus

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(ROOT, "tools")

_FLEET_ENV = ("MXNET_FLEET_RANK", "DMLC_WORKER_ID", "DMLC_NUM_WORKER",
              "MXNET_RECOVERY_GENERATION", "MXNET_OPS_PORT")


def _tool(name):
    sys.path.insert(0, TOOLS)
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


@pytest.fixture(autouse=True)
def _clean_fleet(monkeypatch):
    """Every test starts untagged with an empty registry and no live
    endpoint, and leaves nothing behind for the rest of the suite."""
    for var in _FLEET_ENV:
        monkeypatch.delenv(var, raising=False)
    # a dist kvstore that an earlier file of this worker left open is an
    # identity source too (xdist decides which files share a process)
    monkeypatch.setattr(fleet, "_kv_ref", None)
    fleet.configure()
    mx.telemetry.reset()
    yield
    opsd.stop_ops()
    fleet.configure()
    mx.telemetry.reset()
    mx.telemetry.disable()


# --------------------------------------------------------- rank identity
def test_rank_resolution_precedence(monkeypatch):
    """configure() > MXNET_FLEET_RANK > DMLC_WORKER_ID > 0; tagged()
    flips exactly when a source is active."""
    assert fleet.rank() == 0
    assert not fleet.tagged()

    monkeypatch.setenv("DMLC_WORKER_ID", "2")
    assert fleet.rank() == 2 and fleet.tagged()

    monkeypatch.setenv("MXNET_FLEET_RANK", "3")
    assert fleet.rank() == 3          # explicit env beats the launcher's

    fleet.configure(rank=5)
    assert fleet.rank() == 5          # programmatic override beats env
    fleet.configure()
    assert fleet.rank() == 3          # cleared back to env resolution

    monkeypatch.setenv("MXNET_FLEET_RANK", "junk")
    assert fleet.rank() == 2          # malformed env falls through


def test_num_workers_and_generation(monkeypatch):
    assert fleet.num_workers() == 1
    assert fleet.generation() == 0
    monkeypatch.setenv("DMLC_NUM_WORKER", "4")
    assert fleet.num_workers() == 4
    fleet.configure(num_workers=7)
    assert fleet.num_workers() == 7
    monkeypatch.setenv("MXNET_RECOVERY_GENERATION", "2")
    assert fleet.generation() == 2


# -------------------------------------------------------------- snapshot
def test_snapshot_schema_and_determinism(monkeypatch):
    monkeypatch.setenv("MXNET_FLEET_RANK", "1")
    metrics.counter("t.fleet.items", shard="a").inc(3)
    metrics.gauge("t.fleet.depth").set(2.5)
    metrics.histogram("t.fleet.seconds",
                      buckets=(0.1, 1.0)).observe(0.05, exemplar="tr01")

    snap = fleet.snapshot()
    assert snap["schema"] == fleet.SCHEMA_VERSION
    assert snap["rank"] == 1 and snap["pid"] == os.getpid()
    assert snap["generation"] == 0

    [ctr] = [c for c in snap["counters"] if c["name"] == "t.fleet.items"]
    assert ctr == {"name": "t.fleet.items", "labels": {"shard": "a"},
                   "value": 3}
    [h] = [h for h in snap["histograms"]
           if h["name"] == "t.fleet.seconds"]
    assert h["buckets"] == [0.1, 1.0]
    assert h["bucket_counts"] == [1, 1]      # cumulative
    assert h["count"] == 1 and h["min"] == h["max"] == 0.05
    assert h["exemplars"] == {"0": ["tr01", 0.05]}

    # JSON-pure and deterministic: two snapshots of the same registry
    # state serialize byte-identically
    assert json.dumps(snap) == json.dumps(fleet.snapshot())
    json.loads(json.dumps(snap))


# ----------------------------------------------------------------- merge
def _snap(rank, counters=(), gauges=(), hists=(), gen=0, nw=3):
    return {"schema": fleet.SCHEMA_VERSION, "rank": rank,
            "host": f"h{rank}", "pid": 100 + rank, "num_workers": nw,
            "generation": gen,
            "counters": [{"name": n, "labels": dict(l), "value": v}
                         for n, l, v in counters],
            "gauges": [{"name": n, "labels": dict(l), "value": v}
                       for n, l, v in gauges],
            "histograms": list(hists)}


def _hist_record(h):
    """A registry Histogram as its schema-v1 snapshot record."""
    return {"buckets": list(h.buckets),
            "bucket_counts": list(h.bucket_counts),
            "count": h.count, "sum": h.sum, "min": h.min, "max": h.max,
            "exemplars": {str(i): [ex[0], ex[1]]
                          for i, ex in sorted(h.exemplars.items())}}


def test_merge_counters_sum_gauges_spread():
    snaps = [
        _snap(0, counters=[("io.batches", {}, 10)],
              gauges=[("q.depth", {}, 1.0)]),
        _snap(1, counters=[("io.batches", {}, 32)],
              gauges=[("q.depth", {}, 4.0)], gen=1),
        _snap(2, counters=[("io.batches", {}, 8),
                           ("only.rank2", {}, 5)],
              gauges=[("q.depth", {}, 1.0)]),
    ]
    out = fleet.merge(snaps)
    assert out["ranks"] == [0, 1, 2]
    assert out["hosts"] == {"0": "h0", "1": "h1", "2": "h2"}
    assert out["generations"] == {"0": 0, "1": 1, "2": 0}

    ctr = out["counters"]["io.batches"]
    assert ctr["by_rank"] == {"0": 10, "1": 32, "2": 8}
    assert ctr["total"] == 50                  # exact sum, nothing lost
    assert out["counters"]["only.rank2"]["total"] == 5

    g = out["gauges"]["q.depth"]
    assert g["min"] == 1.0 and g["max"] == 4.0 and g["mean"] == 2.0

    # deterministic regardless of input order
    assert json.dumps(out) == json.dumps(fleet.merge(reversed(snaps)))

    # two dumps from the same rank merge rank-wise: counters sum
    twice = fleet.merge([snaps[0], snaps[0]])
    assert twice["counters"]["io.batches"]["by_rank"] == {"0": 20}

    with pytest.raises(ValueError):
        fleet.merge([dict(snaps[0], schema=99)])


def test_histogram_merge_identical_bounds_is_lossless():
    """Satellite: merging per-rank records with the same bounds equals
    observing the pooled stream into one histogram — counts, sum and
    every quantile — and the estimate sits within one bucket width of
    the true pooled-stream quantile."""
    bounds = (0.01, 0.05, 0.1, 0.5, 1.0, 5.0)
    rng = random.Random(7)
    stream1 = [rng.uniform(0.001, 1.2) for _ in range(400)]
    stream2 = [rng.uniform(0.02, 4.0) for _ in range(300)]

    h1 = metrics.Histogram("t.merge.seconds", (), buckets=bounds)
    h2 = metrics.Histogram("t.merge.seconds", (), buckets=bounds)
    pooled = metrics.Histogram("t.merge.seconds", (), buckets=bounds)
    for v in stream1:
        h1.observe(v)
        pooled.observe(v)
    for v in stream2:
        h2.observe(v)
        pooled.observe(v)

    merged = fleet.merge_histogram_records([_hist_record(h1),
                                            _hist_record(h2)])
    assert merged["buckets"] == list(bounds)
    assert merged["bucket_counts"] == list(pooled.bucket_counts)
    assert merged["count"] == 700
    assert merged["sum"] == pytest.approx(sum(stream1) + sum(stream2))
    assert merged["min"] == min(stream1 + stream2)
    assert merged["max"] == max(stream1 + stream2)

    observations = sorted(stream1 + stream2)
    edges = [0.0] + list(bounds)
    for q in (0.1, 0.5, 0.9, 0.99):
        est = fleet.hist_quantile(merged, q)
        assert est == pooled.quantile(q)       # merge loses nothing
        true_q = observations[int(q * (len(observations) - 1))]
        # within one bucket width of the pooled stream's quantile
        import bisect
        i = min(bisect.bisect_left(bounds, true_q), len(bounds) - 1)
        width = edges[i + 1] - edges[i]
        assert abs(est - true_q) <= width, (q, est, true_q, width)


def test_histogram_merge_mismatched_bounds_conservative():
    r1 = {"buckets": [0.1, 1.0], "bucket_counts": [3, 10], "count": 10,
          "sum": 4.0, "min": 0.02, "max": 0.9, "exemplars": {}}
    r2 = {"buckets": [0.5, 2.0], "bucket_counts": [4, 6], "count": 6,
          "sum": 3.0, "min": 0.3, "max": 1.8, "exemplars": {}}
    merged = fleet.merge_histogram_records([r1, r2])
    assert merged["buckets"] == [0.1, 0.5, 1.0, 2.0]   # union of bounds
    assert merged["count"] == 16
    assert merged["min"] == 0.02 and merged["max"] == 1.8
    # cumulative counts stay monotone and end at the full population
    counts = merged["bucket_counts"]
    assert counts == sorted(counts)
    assert counts[-1] == 16
    q99 = fleet.hist_quantile(merged, 0.99)
    assert 0.1 <= q99 <= 2.0


def test_histogram_merge_exemplars_highest_wins():
    base = {"buckets": [0.1, 1.0], "count": 2, "sum": 1.0,
            "min": 0.05, "max": 0.9}
    r1 = dict(base, bucket_counts=[1, 2],
              exemplars={"1": ["trace-a", 0.40]})
    r2 = dict(base, bucket_counts=[1, 2],
              exemplars={"1": ["trace-b", 0.45], "0": ["trace-c", 0.05]})
    merged = fleet.merge_histogram_records([r1, r2])
    # per-bucket collision: the slowest exemplar survives
    assert merged["exemplars"]["1"] == ["trace-b", 0.45]
    assert merged["exemplars"]["0"] == ["trace-c", 0.05]
    assert fleet.hist_exemplar(merged, 0.99) == "trace-b"
    assert fleet.hist_exemplar(merged, 0.01) == "trace-c"


# ----------------------------------------------------- prometheus render
def test_prometheus_fleet_render_rank_labels():
    hist = {"buckets": [0.1, 1.0], "bucket_counts": [2, 5], "count": 5,
            "sum": 1.5, "min": 0.01, "max": 0.9,
            "exemplars": {"1": ["tr99", 0.7]}}
    merged = fleet.merge([
        _snap(0, counters=[("io.batches", {"shard": "a"}, 10)],
              gauges=[("q.depth", {}, 1.0)], hists=[
                  dict(hist, name="step.seconds", labels={})]),
        _snap(1, counters=[("io.batches", {"shard": "a"}, 32)],
              gauges=[("q.depth", {}, 4.0)]),
    ])
    text = prometheus.render(fleet=merged)
    for line in text.splitlines():
        if not line.startswith("#"):
            assert 'rank="' in line, line      # every sample is ranked
    parsed = prometheus.parse(text)
    assert parsed['mxnet_io_batches_total{rank="0",shard="a"}'] == 10
    assert parsed['mxnet_io_batches_total{rank="1",shard="a"}'] == 32
    assert parsed['mxnet_q_depth{rank="0"}'] == 1.0
    assert parsed['mxnet_step_seconds_count{rank="0"}'] == 5
    assert parsed['mxnet_step_seconds_bucket{le="+Inf",rank="0"}'] == 5
    assert parsed["__types__"]["mxnet_io_batches_total"] == "counter"
    assert parsed["__types__"]["mxnet_step_seconds"] == "histogram"

    # default text carries no exemplars; OpenMetrics opts in
    assert "tr99" not in text
    om = prometheus.render(fleet=merged, openmetrics=True)
    assert '# {trace_id="tr99"} 0.7' in om


# ----------------------------------------------------------- ops endpoint
def _get(url, accept=None):
    req = urllib.request.Request(url)
    if accept:
        req.add_header("Accept", accept)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, resp.headers.get("Content-Type", ""), \
                resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get("Content-Type", ""), \
            e.read().decode()


def test_opsd_routes(monkeypatch):
    monkeypatch.setenv("MXNET_FLEET_RANK", "4")
    metrics.counter("t.opsd.requests").inc(2)
    srv = mx.telemetry.serve_ops(port=0)
    assert srv.port > 0 and opsd.active() is srv
    assert mx.telemetry.serve_ops(port=0) is srv     # idempotent

    status, ct, body = _get(srv.url + "/metrics")
    assert status == 200 and ct.startswith("text/plain")
    assert prometheus.parse(body)["mxnet_t_opsd_requests_total"] == 2

    status, ct, _body = _get(srv.url + "/metrics",
                             accept="application/openmetrics-text")
    assert status == 200 and ct.startswith("application/openmetrics-text")

    status, _ct, body = _get(srv.url + "/healthz")
    doc = json.loads(body)
    assert status == 200 and doc["ok"] is True
    assert doc["rank"] == 4 and doc["pid"] == os.getpid()
    assert doc["kvstore"] == {"attached": False, "dead_nodes": []}

    status, _ct, body = _get(srv.url + "/varz")
    doc = json.loads(body)
    assert status == 200
    assert doc["env"]["MXNET_FLEET_RANK"] == "4"
    assert not any(k in doc["env"] for k in ("HOME", "PATH"))
    assert doc["telemetry"]["enabled"] in (True, False)
    assert "mesh" in doc

    status, _ct, body = _get(srv.url + "/tracez")
    doc = json.loads(body)
    assert status == 200
    assert isinstance(doc["slowest"], list)
    assert isinstance(doc["traces_buffered"], int)

    status, _ct, body = _get(srv.url + "/fleetz")
    doc = json.loads(body)
    assert status == 200 and doc["schema"] == fleet.SCHEMA_VERSION
    assert doc["rank"] == 4
    assert any(c["name"] == "t.opsd.requests" for c in doc["counters"])

    status, _ct, body = _get(srv.url + "/")
    assert status == 200 and "/fleetz" in json.loads(body)["routes"]
    status, _ct, _body = _get(srv.url + "/nope")
    assert status == 404

    opsd.stop_ops()
    assert opsd.active() is None


def test_opsd_healthz_degrades_on_open_breaker():
    g = metrics.gauge("t.breaker.opsd.state")
    g.set(2)                                   # OPEN
    srv = mx.telemetry.serve_ops(port=0)
    status, _ct, body = _get(srv.url + "/healthz")
    doc = json.loads(body)
    assert status == 503 and doc["ok"] is False
    assert doc["breakers"]["t.breaker.opsd.state"]["name"] == "open"

    g.set(0)                                   # closed again
    status, _ct, body = _get(srv.url + "/healthz")
    assert status == 200 and json.loads(body)["ok"] is True


def test_opsd_env_arming(monkeypatch):
    assert opsd.maybe_serve_from_env() is None         # unset: no-op
    monkeypatch.setenv("MXNET_OPS_PORT", "not-a-port")
    assert opsd.maybe_serve_from_env() is None         # malformed: warn
    assert opsd.active() is None
    monkeypatch.setenv("MXNET_OPS_PORT", "0")
    srv = opsd.maybe_serve_from_env()
    assert srv is not None and srv.port > 0


def test_opsd_scrape_during_live_fit_loop():
    """The acceptance shape in miniature: /metrics and /healthz answer
    correctly while a training loop is dispatching."""
    mx.telemetry.enable()
    srv = mx.telemetry.serve_ops(port=0)
    scrapes = []

    def cb(p):
        if len(scrapes) < 2:
            scrapes.append(_get(srv.url + "/metrics"))
            scrapes.append(_get(srv.url + "/healthz"))

    rng = np.random.RandomState(3)
    X = rng.rand(64, 8).astype("f")
    y = (X[:, 1] > 0.5).astype("f")
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=2, name="fc")
    mod = mx.mod.Module(mx.sym.SoftmaxOutput(fc, name="softmax"),
                        context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(X, y, batch_size=16), num_epoch=1,
            initializer=mx.initializer.Xavier(),
            batch_end_callback=cb)

    assert len(scrapes) == 2 * 1 or len(scrapes) == 2
    m_status, _ct, m_body = scrapes[0]
    assert m_status == 200
    parsed = prometheus.parse(m_body)
    assert any(k.startswith("mxnet_module_fit") for k in parsed)
    h_status, _ct, h_body = scrapes[1]
    assert h_status == 200 and json.loads(h_body)["ok"] is True

    # after the loop the endpoint sees the finished counters
    _st, _ct, body = _get(srv.url + "/metrics")
    assert prometheus.parse(body)["mxnet_module_fit_batches_total"] == 4


def test_opsd_scrape_during_live_decode_engine():
    """/metrics and /healthz stay correct while a continuous-decode
    engine iterates, and scraping compiles nothing: the engine's
    compile delta after warmup is 0 with the scraper active."""
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.serve import FakeClock

    V, D, L, H, T = 16, 8, 1, 2, 8
    warm = mx.mod.Module(
        tfm.get_symbol(vocab_size=V, d_model=D, n_layer=L, n_head=H,
                       seq_len=4, include_loss=False, max_seq_len=T),
        label_names=[])
    warm.bind([("data", (1, 4))], None, for_training=False)
    warm.init_params(mx.initializer.Xavier())
    args, _ = warm.get_params()

    mx.telemetry.enable()
    eng = mx.serve.DecodeEngine(
        "fleetdec",
        tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                              n_head=H, capacity=T, per_slot=True,
                              max_seq_len=T),
        dict(args), capacity=T, ladder=[2])
    clock = FakeClock()
    sched = mx.serve.DecodeScheduler(eng, clock=clock)
    srv = mx.telemetry.serve_ops(port=0)

    handles = [sched.submit([1, 2], max_new_tokens=3),
               sched.submit([3], max_new_tokens=3)]
    sched.pump(max_iterations=1)

    # scrape mid-decode: the serve.decode.* series are live and ranked 0
    status, _ct, body = _get(srv.url + "/metrics")
    assert status == 200
    parsed = prometheus.parse(body)
    assert parsed['mxnet_serve_decode_requests_total{model="fleetdec"}'] \
        == 2
    status, _ct, body = _get(srv.url + "/healthz")
    assert status == 200 and json.loads(body)["ok"] is True

    sched.pump()
    for h in handles:
        assert len(list(h.result(timeout=5))) == 3
    st = sched.stats()
    assert st["responses"] == 2 and st["errors"] == 0
    assert st["compiles_since_warmup"] == 0    # scraping compiled nothing

    _st, _ct, body = _get(srv.url + "/metrics")
    parsed = prometheus.parse(body)
    assert parsed['mxnet_serve_decode_responses_total{model="fleetdec"}'] \
        == 2
    assert parsed['mxnet_serve_decode_tokens_total{model="fleetdec"}'] == 6


# ------------------------------------------------------------- fleetstat
def _jsonl_rank(path, rank, gen, t, walls_us, phase_of, monitor,
                events=(), counters=()):
    """One synthesized per-rank dump shaped like the chaos run's."""
    lines = [{"type": "meta", "schema": fleet.SCHEMA_VERSION,
              "rank": rank, "host": f"h{rank}", "pid": 100 + rank,
              "num_workers": 3, "generation": gen, "time_unix": t}]
    for wall in walls_us:
        lines.append({"type": "step", "wall_us": wall,
                      "phases_us": dict(phase_of(wall))})
    lines.append({"type": "gauge", "name": "monitor.stat",
                  "labels": {"stat": "loss"}, "value": monitor})
    for ev in events:
        lines.append(dict({"type": "event"}, **ev))
    for name, value in counters:
        lines.append({"type": "counter", "name": name, "labels": {},
                      "value": value})
    with open(path, "w") as f:
        f.write("\n".join(json.dumps(rec) for rec in lines) + "\n")
    return str(path)


def _chaos_shaped_dumps(tmp_path):
    """3 ranks: rank 1 straggles on data_wait, rank 2 is dead (stale
    dump, frozen at generation 0, reported by rank 0) and diverging."""
    def lean(wall):
        return {"data_wait": 2000, "dispatch": wall - 2000}

    def starved(wall):
        return {"data_wait": wall - 8000, "dispatch": 8000}

    f0 = _jsonl_rank(tmp_path / "r0.jsonl", 0, 1, 1000.0,
                     [10000] * 5, lean, monitor=0.52,
                     events=[{"kind": "dead_node", "ranks": [2]}],
                     counters=[("recovery.reexec", 1)])
    f1 = _jsonl_rank(tmp_path / "r1.jsonl", 1, 1, 1000.5,
                     [20000] * 4 + [40000], starved, monitor=0.48,
                     counters=[("recovery.reexec", 1)])
    f2 = _jsonl_rank(tmp_path / "r2.jsonl", 2, 0, 900.0,
                     [10000] * 5, lean, monitor=5.0)
    return [f0, f1, f2]


def test_fleetstat_chaos_shaped_report(tmp_path):
    """The fast tier-1 twin of the @slow chaos assertions: straggler
    attribution, divergence flag, dead-rank timeline and byte-stable
    rendering over synthesized dumps."""
    fleetstat = _tool("fleetstat")
    files = _chaos_shaped_dumps(tmp_path)
    ranks = [fleetstat.load_file(p) for p in files]
    doc = fleetstat.build(ranks)

    assert doc["ranks"] == [0, 1, 2]
    assert doc["generations"] == {"0": 1, "1": 1, "2": 0}

    # straggler: rank 1's mean wall is +140% over the fleet median and
    # the excess sits in data_wait (input starvation, not compute)
    st = doc["step"]["straggler"]
    assert st["rank"] == "1" and st["phase"] == "data_wait"
    assert st["excess_pct"] > 100
    assert doc["step"]["per_rank"]["0"]["p99_over_p50"] == 1.0
    assert doc["step"]["spread_rank"] == "1"
    assert doc["series"]["step.wall.p99_over_p50"] == pytest.approx(2.0)

    # divergence: only rank 2's loss is flagged (leave-one-out z)
    assert len(doc["divergence"]) == 1
    flag = doc["divergence"][0]
    assert flag["rank"] == "2" and flag["z"] > 3
    assert flag["series"].startswith("monitor.stat")

    # dead-rank timeline: stale dump + survivor report + generations
    assert doc["dead"]["stale_ranks"] == ["2"]
    assert doc["dead"]["reported_dead"] == ["2"]
    assert doc["dead"]["lag_seconds"]["2"] == pytest.approx(100.5)
    assert doc["dead"]["recovery"] == {"0": {"reexec": 1},
                                       "1": {"reexec": 1}}

    # byte-determinism: permuted input order, same report text
    text = fleetstat.render(doc)
    doc2 = fleetstat.build([fleetstat.load_file(p)
                            for p in reversed(files)])
    assert fleetstat.render(doc2) == text
    assert "STRAGGLER: rank 1" in text
    assert "RANK 2 DIVERGING" in text
    assert "STALE" in text


def test_fleetstat_loads_snapshot_and_crash_formats(tmp_path):
    fleetstat = _tool("fleetstat")
    metrics.counter("t.fleetstat.items").inc(7)
    fleet.configure(rank=1)
    snap_path = tmp_path / "snap.json"
    snap_path.write_text(json.dumps(fleet.snapshot()))
    rec = fleetstat.load_file(str(snap_path))
    assert rec["rank"] == 1 and rec["had_meta"]
    assert any(c["name"] == "t.fleetstat.items" and c["value"] == 7
               for c in rec["counters"])

    crash = {"type": "crash_report", "rank": 2, "host": "h2",
             "time_unix": 500.0,
             "env": {"MXNET_RECOVERY_GENERATION": "1"},
             "ring": [{"kind": "dead_node", "ts_us": 1, "ranks": [0]},
                      {"kind": "span", "name": "op.X", "ts_us": 2}],
             "metrics": {"counters": {"io.batches": 4}, "gauges": {},
                         "histograms": {}}}
    crash_path = tmp_path / "crash.json"
    crash_path.write_text(json.dumps(crash))
    rec = fleetstat.load_file(str(crash_path))
    assert rec["rank"] == 2 and rec["generation"] == 1
    assert [e["kind"] for e in rec["events"]] == ["dead_node"]
    assert rec["counters"] == [{"name": "io.batches", "labels": {},
                                "value": 4}]


def test_fleetstat_cli(tmp_path, capsys):
    fleetstat = _tool("fleetstat")
    files = _chaos_shaped_dumps(tmp_path)
    assert fleetstat.main(files) == 0
    out = capsys.readouterr().out
    assert "FLEET REPORT — 3 rank(s)" in out

    assert fleetstat.main(files + ["--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert "merged" not in doc                 # slim machine document
    assert doc["series"]["step.wall.p99_over_p50"] == pytest.approx(2.0)

    assert fleetstat.main([str(tmp_path / "missing.jsonl")]) == 2


def test_fleetstat_scrapes_live_endpoint():
    fleetstat = _tool("fleetstat")
    metrics.counter("t.scrape.items").inc(1)
    fleet.configure(rank=2)
    srv = mx.telemetry.serve_ops(port=0)
    rec = fleetstat.scrape(srv.url)
    assert rec["rank"] == 2 and rec["had_meta"]
    assert rec["health"]["ok"] is True
    assert any(c["name"] == "t.scrape.items" for c in rec["counters"])
    doc = fleetstat.build([rec])
    assert doc["ranks"] == [2]

    with pytest.raises(OSError):
        fleetstat.scrape("http://127.0.0.1:9")     # discard port


# ------------------------------------------- diagnose decode sections
_DECODE_COUNTERS = {'serve.decode.requests{model="m"}': 10,
                    'serve.decode.responses{model="m"}': 9,
                    'serve.decode.iterations{model="m"}': 50,
                    'serve.decode.tokens{model="m"}': 200,
                    'serve.decode.joins{model="m"}': 10,
                    'serve.decode.leaves{model="m"}': 9,
                    'serve.decode.migrations{model="m"}': 1}
_DECODE_GAUGES = {'serve.decode.slots{model="m"}': 8,
                  'serve.decode.active{model="m"}': 6,
                  'serve.decode.occupancy{model="m"}': 0.75,
                  'serve.decode.queue.depth{model="m"}': 2}
_DECODE_HIST = {"count": 50, "sum": 1.0, "min": 0.01, "max": 0.09,
                "buckets": {"0.05": 30, "0.1": 50}}


def _assert_decode_section(out):
    assert "decode engine (continuous batching):" in out
    assert "model m: 6/8 slots active (75% occupancy), queue depth 2" \
        in out
    assert "sessions: 10 admitted, 9 completed" in out
    assert "iterations: 50 (200 tokens, 4.00 tokens/iteration)" in out
    assert "churn: 10 joins, 9 leaves, 1 rung migration(s)" in out
    assert "step time: p50" in out


def test_diagnose_decode_section_crash_path():
    diagnose = _tool("diagnose")
    report = {"type": "crash_report", "pid": 1, "where": "serve.decode",
              "exception": {"type": "RuntimeError", "message": "x"},
              "ring": [],
              "metrics": {
                  "counters": dict(_DECODE_COUNTERS),
                  "gauges": dict(_DECODE_GAUGES),
                  "histograms": {
                      'serve.decode.step.seconds{model="m"}':
                          dict(_DECODE_HIST)}}}
    _assert_decode_section(diagnose.render_crash(report))


def test_diagnose_decode_section_jsonl_path():
    diagnose = _tool("diagnose")

    def split(series):
        name, _, rest = series.partition("{")
        return name, {"model": rest.rstrip("}").split('"')[1]}

    lines = []
    for series, v in _DECODE_COUNTERS.items():
        name, labels = split(series)
        lines.append(json.dumps({"type": "counter", "name": name,
                                 "labels": labels, "value": v}))
    for series, v in _DECODE_GAUGES.items():
        name, labels = split(series)
        lines.append(json.dumps({"type": "gauge", "name": name,
                                 "labels": labels, "value": v}))
    lines.append(json.dumps(
        {"type": "histogram", "name": "serve.decode.step.seconds",
         "labels": {"model": "m"}, **_DECODE_HIST}))
    _assert_decode_section(diagnose.render_jsonl(lines))


# ----------------------------------------------------- jsonl meta line
def test_jsonl_meta_line_carries_identity(monkeypatch):
    monkeypatch.setenv("MXNET_FLEET_RANK", "6")
    monkeypatch.setenv("MXNET_RECOVERY_GENERATION", "1")
    first = json.loads(mx.telemetry.jsonl.render().splitlines()[0])
    assert first["type"] == "meta"
    assert first["schema"] == fleet.SCHEMA_VERSION
    assert first["rank"] == 6 and first["generation"] == 1
    assert first["time_unix"] > 1.7e9          # wall clock, not perf ctr
