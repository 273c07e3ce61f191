"""Continuous-batching inference server (mxnet_tpu/serve/).

Gates, per ISSUE 8 acceptance:

* every served response is bitwise-equal to a direct
  ``Module.predict``/``Predictor`` forward of the same input (the
  pad/slice batcher is bit-transparent — row-independent inference ops
  plus the SAME bucket program via the process-wide program cache);
* zero XLA compiles after warmup (``program_cache.compile_count``
  deltas + the ``serve.program_cache.compiles_since_warmup`` gauge);
* p99 latency + queue-depth series present in the telemetry registry
  and the Prometheus export;
* deadline-aware flush proven on a deterministic FakeClock: a request
  is dispatched AT its flush instant in a smaller bucket rather than
  kept waiting for a larger one past its deadline.
"""
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.serve import (BucketLadder, FakeClock, QueueFullError,
                             bucket_for, pad_rows, run_scripted,
                             slice_rows)


def _mlp(prefix="fc", hidden=8, classes=3):
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


def _direct_predict(sym, mod, x, batch):
    """The oracle the acceptance names: Module.predict of the same
    input through an independent module at the serving bucket size
    (same program via the process-wide cache). Rows beyond a bucket
    multiple ride as NDArrayIter pad rows, which iter_predict drops —
    row-independent inference ops make the valid rows bit-identical
    regardless of pad content."""
    ref = mx.mod.Module(sym, context=mx.cpu())
    ref.bind([("data", (batch,) + x.shape[1:])], for_training=False,
             label_shapes=None)
    arg_params, aux_params = mod.get_params()
    ref.init_params(initializer=None, arg_params=arg_params,
                    aux_params=aux_params)
    n = x.shape[0]
    if n % batch:               # NDArrayIter needs >= one full batch
        x = np.concatenate(
            [x, np.zeros((batch - n % batch,) + x.shape[1:], x.dtype)])
    out = ref.predict(mx.io.NDArrayIter(x, None, batch))
    return out.asnumpy()[:n]


# --------------------------------------------------------------- helpers
def test_pad_slice_roundtrip_and_ladder():
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    p = pad_rows(x, 8)
    assert p.shape == (8, 4) and np.array_equal(p[:3], x)
    assert not p[3:].any()
    assert np.array_equal(pad_rows(x, 3), x)          # no-op at the rung
    back = slice_rows([p], 1, 2)[0].asnumpy()
    assert np.array_equal(back, x[1:3])

    lad = BucketLadder([8, 2, 4, 2])
    assert lad.sizes == [2, 4, 8] and lad.max == 8
    assert lad.bucket_for(1) == 2 and lad.bucket_for(5) == 8
    assert lad.bucket_for(9) is None
    assert bucket_for(3, [2, 4]) == 4
    with pytest.raises(mx.base.MXNetError):
        pad_rows(x, 2)                                 # rows > bucket


def test_ladder_env_default(monkeypatch):
    monkeypatch.setenv("MXNET_SERVE_BUCKETS", "4, 1,16")
    assert BucketLadder().sizes == [1, 4, 16]
    monkeypatch.setenv("MXNET_SERVE_BUCKETS", "zero")
    with pytest.raises(mx.base.MXNetError):
        BucketLadder()


# ------------------------------------------------- deterministic scheduler
def test_deadline_flush_fake_clock():
    """A lone request must flush at deadline - exec_estimate (0 on the
    fake clock) in the SMALLEST covering bucket — never held past its
    deadline waiting for a fuller batch."""
    mx.telemetry.reset()    # the counts below are this server's alone
    clock = FakeClock()
    sym = _mlp("dl")
    server = mx.serve.serve(_bound_module(sym), ladder=[1, 2, 4],
                            start=False, clock=clock,
                            default_deadline_ms=50)
    x = np.random.RandomState(0).rand(1, 6).astype(np.float32)
    h = server.submit({"data": x})
    assert server.pump() == 0, "no flush before the deadline instant"
    clock.advance(0.049)
    assert server.pump() == 0
    clock.advance(0.001)                    # exactly t = deadline
    assert server.pump() == 1
    assert h.done() and h.bucket == 1, \
        "the smallest covering bucket serves the deadline flush"
    assert h.latency == pytest.approx(0.050)
    assert not h.missed_deadline()
    stats = server.stats()["models"]["default"]
    assert stats["deadline_misses"] == 0
    assert stats["dispatches"] == 1


def test_full_bucket_flushes_immediately():
    """rows_pending == max bucket leaves no batching benefit in
    waiting: dispatch fires with zero clock advance."""
    clock = FakeClock()
    sym = _mlp("fb")
    server = mx.serve.serve(_bound_module(sym), ladder=[2, 4],
                            start=False, clock=clock,
                            default_deadline_ms=1000)
    rs = np.random.RandomState(1)
    hs = [server.submit({"data": rs.rand(2, 6).astype(np.float32)})
          for _ in range(2)]                # 4 rows == max bucket
    assert server.pump() == 1
    assert all(h.done() for h in hs)
    assert {h.bucket for h in hs} == {4}
    assert all(h.latency == 0.0 for h in hs)


def test_coalesced_batch_slices_per_request():
    """Two queued requests coalesce into one padded bucket; each handle
    gets exactly its own rows back."""
    mx.telemetry.reset()
    clock = FakeClock()
    sym = _mlp("co")
    mod = _bound_module(sym)
    server = mx.serve.serve(mod, ladder=[1, 2, 4], start=False,
                            clock=clock, default_deadline_ms=10)
    rs = np.random.RandomState(2)
    x1 = rs.rand(2, 6).astype(np.float32)
    x2 = rs.rand(1, 6).astype(np.float32)
    h1 = server.submit({"data": x1})
    h2 = server.submit({"data": x2})
    clock.advance(0.010)
    assert server.pump() == 1
    assert h1.bucket == h2.bucket == 4      # 3 rows -> rung 4
    ref = _direct_predict(sym, mod, np.concatenate([x1, x2]), 4)
    assert np.array_equal(h1.result()[0].asnumpy(), ref[:2])
    assert np.array_equal(h2.result()[0].asnumpy(), ref[2:3])
    stats = server.stats()["models"]["default"]
    assert stats["batch_occupancy"] == pytest.approx(0.75)
    assert stats["padding_waste_pct"] == pytest.approx(25.0)


def test_fair_scheduling_round_robin():
    """Two saturated tenants alternate dispatches (least-recently-
    dispatched wins among ready models)."""
    clock = FakeClock()
    server = mx.serve.InferenceServer(clock=clock)
    sym_a, sym_b = _mlp("fa"), _mlp("fb2", hidden=5)
    server.register("a", model=_bound_module(sym_a), ladder=[2])
    server.register("b", model=_bound_module(sym_b), ladder=[2])
    order = []
    rs = np.random.RandomState(3)

    def sub(name):
        h = server.submit({"data": rs.rand(2, 6).astype(np.float32)},
                          model=name)
        h.add_done_callback(lambda _h: order.append(name))
        return h

    for _ in range(2):
        sub("a")
    for _ in range(2):
        sub("b")
    assert server.pump() == 4
    assert order == ["a", "b", "a", "b"], order


def test_queue_full_rejection():
    mx.telemetry.reset()
    clock = FakeClock()
    sym = _mlp("qf")
    server = mx.serve.serve(_bound_module(sym), ladder=[1, 4],
                            start=False, clock=clock, max_queue=2,
                            default_deadline_ms=1000)
    x = np.zeros((1, 6), np.float32)
    server.submit({"data": x})
    server.submit({"data": x})
    with pytest.raises(QueueFullError):
        server.submit({"data": x})
    assert server.stats()["models"]["default"]["rejected"] == 1


def test_submit_validation_errors():
    clock = FakeClock()
    sym = _mlp("va")
    server = mx.serve.serve(_bound_module(sym), ladder=[1, 2],
                            start=False, clock=clock)
    with pytest.raises(mx.base.MXNetError):
        server.submit({"data": np.zeros((1, 7), np.float32)})  # bad feat
    with pytest.raises(mx.base.MXNetError):
        server.submit({"data": np.zeros((3, 6), np.float32)})  # > max
    with pytest.raises(mx.base.MXNetError):
        server.submit({"wrong": np.zeros((1, 6), np.float32)})
    with pytest.raises(mx.base.MXNetError):
        server.submit({"data": np.zeros((1, 6), np.float32)},
                      model="ghost")


def test_dispatch_error_fails_batch_not_server():
    mx.telemetry.reset()
    clock = FakeClock()
    sym = _mlp("er")
    server = mx.serve.serve(_bound_module(sym), ladder=[1],
                            start=False, clock=clock,
                            default_deadline_ms=5)
    engine = server.engine()
    real_forward = engine.forward
    engine.forward = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("injected"))
    h_bad = server.submit({"data": np.zeros((1, 6), np.float32)})
    clock.advance(0.005)
    server.pump()
    with pytest.raises(RuntimeError, match="injected"):
        h_bad.result(timeout=1)
    engine.forward = real_forward           # server keeps serving
    h_ok = server.submit({"data": np.zeros((1, 6), np.float32)})
    clock.advance(0.005)
    server.pump()
    assert h_ok.result(timeout=1)[0].shape == (1, 3)
    assert server.stats()["models"]["default"]["errors"] == 1


def test_stop_without_drain_fails_pending():
    sym = _mlp("sp")
    server = mx.serve.serve(_bound_module(sym), ladder=[4], start=False,
                            clock=FakeClock(), default_deadline_ms=1000)
    h = server.submit({"data": np.zeros((1, 6), np.float32)})
    server.stop(drain=False)
    with pytest.raises(mx.base.MXNetError):
        h.result(timeout=1)


# ----------------------------------------------------- scripted load path
def test_scripted_arrivals_deterministic():
    """The fast tier-1 loadgen path: scripted arrivals on a FakeClock —
    exact flush instants, no wall-clock sleeps."""
    clock = FakeClock()
    sym = _mlp("sc")
    server = mx.serve.serve(_bound_module(sym), ladder=[1, 2, 4],
                            start=False, clock=clock,
                            default_deadline_ms=20)
    arrivals = [0.000, 0.004, 0.008, 0.030, 0.031]
    out = run_scripted(
        server, arrivals,
        lambda i, rng: {"data": rng.rand(1, 6).astype(np.float32)},
        slo_ms=25)
    assert out["offered"] == out["completed"] == 5
    assert out["errors"] == 0 and out["deadline_misses"] == 0
    # first three coalesce at the first request's flush instant
    # (t=0.020), so their latencies are exactly 20/16/12 ms
    assert out["latency_ms"]["p99"] == pytest.approx(20.0)
    assert out["p99_within_slo"] is True
    # rerun is bit-identical (fresh server, same script)
    server2 = mx.serve.serve(_bound_module(_mlp("sc2")),
                             ladder=[1, 2, 4], start=False,
                             clock=FakeClock(), default_deadline_ms=20)
    out2 = run_scripted(
        server2, arrivals,
        lambda i, rng: {"data": rng.rand(1, 6).astype(np.float32)},
        slo_ms=25)
    assert out2["latency_ms"] == out["latency_ms"]


# ------------------------------------------------------------ end to end
def test_e2e_two_model_registry_concurrent():
    """The acceptance scenario: concurrent clients, mixed row counts,
    two tenants — bitwise-correct responses, zero compiles after
    warmup, latency/queue metrics in the registry and the Prometheus
    export."""
    mx.program_cache.clear()
    mx.telemetry.reset()
    sym_a, sym_b = _mlp("ea", hidden=8), _mlp("eb", hidden=5, classes=2)
    mod_a = _bound_module(sym_a, feat=6)
    mod_b = _bound_module(sym_b, feat=6)
    server = mx.serve.InferenceServer(default_deadline_ms=200)
    server.register("a", model=mod_a, ladder=[1, 2, 4])
    server.register("b", model=mod_b, ladder=[1, 2, 4])
    compiles_before = mx.program_cache.compile_count()

    results = []
    res_lock = threading.Lock()

    def client(cid):
        rs = np.random.RandomState(100 + cid)
        for j in range(3):
            name = "a" if (cid + j) % 2 == 0 else "b"
            rows = 1 + (cid + j) % 3
            x = rs.rand(rows, 6).astype(np.float32)
            h = server.submit({"data": x}, model=name)
            out = h.result(timeout=30)[0].asnumpy()
            with res_lock:
                results.append((name, x, out, h.bucket))

    with server:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    assert len(results) == 12
    for name, x, out, bucket in results:
        sym, mod = (sym_a, mod_a) if name == "a" else (sym_b, mod_b)
        ref = _direct_predict(sym, mod, x, bucket)
        assert np.array_equal(out, ref), \
            f"served response differs from direct predict ({name})"

    # zero compiles after warmup — the program-cache counters, the
    # engine-level delta, and the published gauge all agree
    assert mx.program_cache.compile_count() == compiles_before
    stats = server.stats()
    assert stats["compiles_since_warmup"] == 0
    for name in ("a", "b"):
        # (compiles_since_warmup is process-global — model b's warmup
        # counts against a's engine-level mark; the server-level delta
        # above is the steady-state gate)
        assert server.engine(name).programs_resident()
        assert stats["models"][name]["latency_ms"]["p99"] is not None
        assert stats["models"][name]["responses"] == 6

    # latency histogram + queue-depth gauge live in the registry...
    assert mx.telemetry.get_metric("serve.request.latency.seconds",
                                   model="a").count > 0
    assert mx.telemetry.get_metric("serve.queue.depth",
                                   model="b") is not None
    # ...and in the Prometheus exposition
    prom = mx.telemetry.prometheus.render()
    assert "mxnet_serve_request_latency_seconds_bucket" in prom
    assert "mxnet_serve_queue_depth" in prom
    assert "mxnet_serve_batch_occupancy" in prom
    # flight ring carries per-dispatch records
    kinds = [r.get("kind") for r in mx.telemetry.flightrec.get_records()]
    assert "serve.dispatch" in kinds


def test_exact_bucket_request_matches_module_predict_bitwise():
    """A request whose rows equal a rung pads nothing: its response is
    the bucket program's output verbatim, bitwise-equal to
    Module.predict at that batch size."""
    sym = _mlp("bw")
    mod = _bound_module(sym)
    server = mx.serve.serve(mod, ladder=[4], start=False,
                            clock=FakeClock(), default_deadline_ms=10)
    x = np.random.RandomState(7).rand(4, 6).astype(np.float32)
    h = server.submit({"data": x})
    server.pump()                           # full bucket -> immediate
    assert np.array_equal(h.result()[0].asnumpy(),
                          _direct_predict(sym, mod, x, 4))


def test_predictor_engine_serves_mxp(tmp_path):
    """predict.py artifacts served directly: the .mxp's exported batch
    is the single ladder rung and responses match Predictor.forward."""
    sym = _mlp("px")
    mod = _bound_module(sym)
    arg_params, aux_params = mod.get_params()
    path = str(tmp_path / "mlp.mxp")
    mx.export_model(path, sym, arg_params, aux_params, {"data": (4, 6)})

    clock = FakeClock()
    server = mx.serve.serve(path, start=False, clock=clock,
                            default_deadline_ms=10)
    assert server.engine().ladder.sizes == [4]
    x = np.random.RandomState(9).rand(2, 6).astype(np.float32)
    h = server.submit({"data": x})
    clock.advance(0.010)
    assert server.pump() == 1
    ref = mx.Predictor(path).forward(data=pad_rows(x, 4))[0].asnumpy()
    assert np.array_equal(h.result()[0].asnumpy(), ref[:2])
