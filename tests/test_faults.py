"""Deterministic fault-injection plane + hardened degradation paths
(mxnet_tpu/faults, ISSUE 10).

The acceptance matrix: for each instrumented seam — checkpoint write,
snapshot D2H, kvstore collective, IO decode, serve dispatch — an
injected TRANSIENT fault must recover via its policy (retry / skip /
shed) with bit-identical results where the policy claims transparency,
and an injected PERMANENT fault must degrade along the documented path
(quarantine / DeadWorkerError / breaker-open). All of it runs in
tier-1: no process kills, no wall-clock sleeps, no @slow — the fault
plane plus FakeClock make every path scriptable (docs/faults.md).
"""
import importlib.util
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults
from mxnet_tpu.faults import (CircuitBreaker, CircuitOpenError,
                              InjectedFault, RetryPolicy, retry_call)
from mxnet_tpu.serve import FakeClock, QueueFullError, ShedError
from mxnet_tpu.telemetry import metrics as _metrics


def _cval(name, **labels):
    m = _metrics.get_metric(name, **labels)
    return m.value if m is not None else 0


def _fast_policy(attempts=3):
    return RetryPolicy(attempts=attempts, base_s=0.0, jitter=0.0)


# ------------------------------------------------------------- the plane
def _fire_pattern(spec, n=6):
    """Which of n calls to one armed point raise (1-based indices)."""
    hits = []
    with faults.scope(f"p:{spec}"):
        for i in range(1, n + 1):
            try:
                faults.point("p")
            except Exception:
                hits.append(i)
    return hits


def test_trigger_grammar_matrix():
    assert _fire_pattern("nth=3") == [3]
    assert _fire_pattern("once") == [1]
    assert _fire_pattern("always") == [1, 2, 3, 4, 5, 6]
    assert _fire_pattern("every=2") == [2, 4, 6]
    assert _fire_pattern("first=2") == [1, 2]


def test_prob_trigger_seeded_deterministic():
    a = _fire_pattern("prob=0.5,seed=11", n=32)
    b = _fire_pattern("prob=0.5,seed=11", n=32)
    assert a == b and 0 < len(a) < 32      # same seed, same script
    assert _fire_pattern("prob=0", n=16) == []
    assert _fire_pattern("prob=1", n=4) == [1, 2, 3, 4]


def test_error_kinds_and_msg():
    with faults.scope("p:once,error=os,msg=disk full"):
        with pytest.raises(OSError, match="disk full") as ei:
            faults.point("p")
        assert ei.value.mx_fault_point == "p"
    with faults.scope("p:once,error=timeout"):
        with pytest.raises(TimeoutError):
            faults.point("p")
    with faults.scope("p:once"):
        with pytest.raises(InjectedFault):
            faults.point("p")


def test_latency_injection_no_error():
    with faults.scope("p:latency=1ms,first=2") as plane:
        faults.point("p")
        faults.point("p")
        faults.point("p")
        assert faults.fired("p") == 2       # slept twice, raised never


@pytest.mark.parametrize("bad", [
    "noseparator", "p:", "p:nth=0", "p:prob=2", "p:wat=1",
    "p:once;p:always", "p:once,error=bogus", "p:latency=xyz",
])
def test_bad_specs_raise(bad):
    with pytest.raises(mx.base.MXNetError):
        faults.parse_spec(bad)


def test_point_noop_when_disarmed_and_scope_restores():
    assert not faults.enabled()
    faults.point("anything")                # must be a no-op
    with faults.scope("a:once"):
        assert faults.enabled()
        with faults.scope("b:once"):        # nested scope replaces
            assert faults.calls("a") == 0
            with pytest.raises(InjectedFault):
                faults.point("b")
        assert faults.enabled()             # outer restored
        with pytest.raises(InjectedFault):
            faults.point("a")
    assert not faults.enabled()


def test_injection_counter_and_ring():
    before = _cval("faults.injected", point="p")
    with faults.scope("p:always"):
        with pytest.raises(InjectedFault):
            faults.point("p", extra="ctx")
    assert _cval("faults.injected", point="p") == before + 1
    recs = [r for r in mx.telemetry.flightrec.get_records()
            if r.get("kind") == "fault.injected"]
    assert recs and recs[-1]["point"] == "p" and recs[-1]["extra"] == "ctx"


# ----------------------------------------------------------------- retry
def test_retry_policy_backoff_curve():
    p = RetryPolicy(attempts=5, base_s=0.1, multiplier=2.0, max_s=0.5,
                    jitter=0.0)
    assert [p.backoff(k) for k in (1, 2, 3, 4)] == \
        [0.1, 0.2, 0.4, 0.5]                # capped at max_s


def test_retry_success_after_transient_counts():
    site = "t.transient"
    before = _cval("retry.retries", site=site)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("flaky")
        return "ok"

    assert retry_call(flaky, _fast_policy(5), site=site) == "ok"
    assert len(calls) == 3
    assert _cval("retry.retries", site=site) == before + 2


def test_retry_gives_up_after_attempts():
    site = "t.permanent"
    before = _cval("retry.giveups", site=site)
    with pytest.raises(OSError):
        retry_call(lambda: (_ for _ in ()).throw(OSError("dead")),
                   _fast_policy(3), site=site)
    assert _cval("retry.giveups", site=site) == before + 1


def test_retry_deadline_budget():
    # first backoff (1s) overruns the 0.1s budget: give up after ONE
    # attempt without sleeping
    p = RetryPolicy(attempts=10, base_s=1.0, jitter=0.0, deadline_s=0.1,
                    sleep=lambda s: pytest.fail("must not sleep"))
    calls = []
    with pytest.raises(OSError):
        retry_call(lambda: calls.append(1) or
                   (_ for _ in ()).throw(OSError("x")), p, site="t.dl")
    assert len(calls) == 1


def test_retry_give_up_hook_converts():
    class Hard(Exception):
        pass

    with pytest.raises(Hard) as ei:
        retry_call(lambda: (_ for _ in ()).throw(OSError("soft")),
                   _fast_policy(5), site="t.hook",
                   give_up=lambda exc: Hard("converted"))
    assert isinstance(ei.value.__cause__, OSError)


def test_retry_env_parsing(monkeypatch):
    monkeypatch.setenv("MXNET_RETRY_XYZ",
                       "attempts=7,base=0.25,mult=3,max=9,deadline=60,"
                       "jitter=0")
    p = RetryPolicy.from_env("xyz")
    assert (p.attempts, p.base_s, p.multiplier, p.max_s, p.deadline_s,
            p.jitter) == (7, 0.25, 3.0, 9.0, 60.0, 0.0)
    monkeypatch.setenv("MXNET_RETRY_XYZ", "bogus=1")
    with pytest.raises(mx.base.MXNetError):
        RetryPolicy.from_env("xyz")


# --------------------------------------------------------------- breaker
def test_breaker_state_machine():
    b = CircuitBreaker(threshold=2, cooldown_s=1.0, site="m")
    assert b.acquire(0.0)
    b.record_failure(0.0)
    assert b.state == "closed"              # 1 < threshold
    assert b.acquire(0.1)
    b.record_failure(0.1)
    assert b.state == "open"                # consecutive threshold hit
    assert not b.acquire(0.5)               # cooldown running
    assert not b.admit_allowed(0.5)
    assert b.retry_after(0.5) == pytest.approx(0.6)
    assert b.admit_allowed(1.2)             # probe possible
    assert b.acquire(1.2) and b.state == "half_open"
    assert not b.acquire(1.3)               # single probe in flight
    b.record_failure(1.3)                   # probe failed: open again
    assert b.state == "open" and b.retry_after(1.4) > 0
    assert b.acquire(2.4)                   # next probe
    b.record_success(2.5)
    assert b.state == "closed" and b.consecutive_failures == 0


def test_breaker_success_resets_consecutive():
    b = CircuitBreaker(threshold=3, cooldown_s=1.0)
    for t in (0.0, 0.1):
        b.acquire(t)
        b.record_failure(t)
    b.acquire(0.2)
    b.record_success(0.2)
    b.acquire(0.3)
    b.record_failure(0.3)
    assert b.state == "closed"              # non-consecutive failures


# --------------------------------------------------- seam: ckpt.write/d2h
BATCH, FEATS, CLASSES = 4, 6, 3


def _mlp(prefix="f", dropout=0.0):
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=8, name=f"{prefix}1")
    act = mx.sym.Activation(fc, act_type="relu")
    if dropout:
        act = mx.sym.Dropout(act, p=dropout)
    fc2 = mx.sym.FullyConnected(act, num_hidden=CLASSES,
                                name=f"{prefix}2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _fit_mod(ckpt=None, every=2, it=None, prefix="f", seed=7,
             num_epoch=1):
    X = np.random.RandomState(0).rand(6 * BATCH, FEATS).astype("f")
    y = np.random.RandomState(1).randint(
        0, CLASSES, (6 * BATCH,)).astype("f")
    mx.random.seed(seed)
    if it is None:
        it = mx.io.NDArrayIter(X, y, batch_size=BATCH)
    mod = mx.mod.Module(_mlp(prefix), context=mx.cpu())
    rs = np.random.RandomState(2)
    args = {f"{prefix}1_weight": mx.nd.array(
                rs.randn(8, FEATS).astype("f") * 0.1),
            f"{prefix}1_bias": mx.nd.array(np.zeros(8, "f")),
            f"{prefix}2_weight": mx.nd.array(
                rs.randn(CLASSES, 8).astype("f") * 0.1),
            f"{prefix}2_bias": mx.nd.array(np.zeros(CLASSES, "f"))}
    mod.fit(it, num_epoch=num_epoch, arg_params=args,
            optimizer_params={"learning_rate": 0.05},
            checkpoint=ckpt)
    return mod


def test_ckpt_write_transient_retried_commit_intact(tmp_path):
    """nth=1 on ckpt.write: the first attempt fails, the retry commits
    — transparently (the committed state restores bit-identically to
    the module that was saved), with no .tmp- residue."""
    d = str(tmp_path / "ck")
    mgr = mx.checkpoint.CheckpointManager(d, retry_policy=_fast_policy())
    mod = _fit_mod()
    before = _cval("retry.retries", site="ckpt.write")
    with faults.scope("ckpt.write:nth=1"):
        mgr.save(mod, 3, 5, block=True)
    assert _cval("retry.retries", site="ckpt.write") >= before + 1
    assert mgr.latest() is not None
    assert not [n for n in os.listdir(d) if n.startswith(".tmp-")]
    assert mgr.quarantined == []
    mgr.close()

    # transparency: the retried commit restores bit-for-bit into a
    # module holding unrelated (freshly initialized) params
    mod2 = mx.mod.Module(_mlp("f"), context=mx.cpu())
    mod2.bind([("data", (BATCH, FEATS))], [("softmax_label", (BATCH,))])
    mod2.init_params(mx.initializer.Xavier())
    cursor = mx.checkpoint.restore_module(mod2, d)
    assert cursor == {"epoch": 3, "nbatch": 5}
    a1, _ = mod.get_params()
    a2, _ = mod2.get_params()
    for k in a1:
        np.testing.assert_array_equal(a1[k].asnumpy(), a2[k].asnumpy())


def test_ckpt_write_permanent_quarantine_writer_survives(tmp_path):
    """always on ckpt.write: retries exhaust, the seq is quarantined
    (counted + ring-recorded, wait() raises once), the staging dir is
    swept, and the writer thread keeps committing later snapshots."""
    d = str(tmp_path / "ck")
    mgr = mx.checkpoint.CheckpointManager(d, retry_policy=_fast_policy())
    mod = _fit_mod()
    q_before = _cval("ckpt.quarantined")
    f_before = _cval("ckpt.failures")
    with faults.scope("ckpt.write:always"):
        seq = mgr.save(mod, 0, 1)
        with pytest.raises(InjectedFault):
            mgr.wait()
    assert mgr.quarantined == [seq]
    assert mgr.latest() is None
    assert not [n for n in os.listdir(d) if n.startswith(".tmp-")]
    assert _cval("ckpt.quarantined") == q_before + 1
    assert _cval("ckpt.failures") == f_before + 1
    recs = [r for r in mx.telemetry.flightrec.get_records()
            if r.get("kind") == "ckpt.quarantine"]
    assert recs and recs[-1]["seq"] == seq
    # the writer thread survived: the next save commits normally
    mgr.save(mod, 0, 2, block=True)
    assert mgr.latest() is not None
    mgr.wait()                              # error raised once, cleared
    mgr.close()


def test_ckpt_d2h_transient_retried(tmp_path):
    d = str(tmp_path / "ck")
    mgr = mx.checkpoint.CheckpointManager(d, retry_policy=_fast_policy())
    mod = _fit_mod()
    with faults.scope("ckpt.d2h:nth=1"):
        mgr.save(mod, 1, 0, block=True)
    assert mgr.latest() is not None
    mgr.close()


def test_ckpt_injected_fit_bit_identical(tmp_path):
    """The transparency gate the ISSUE names: a fit whose mid-run
    checkpoint write failed once (and retried) produces the same final
    params AND the same committed checkpoint as an uninjected fit."""
    da, db = str(tmp_path / "a"), str(tmp_path / "b")
    mgr_a = mx.checkpoint.CheckpointManager(da, every_n_batches=2,
                                            retry_policy=_fast_policy())
    mgr_b = mx.checkpoint.CheckpointManager(db, every_n_batches=2,
                                            retry_policy=_fast_policy())
    with faults.scope("ckpt.write:nth=1"):
        mod_a = _fit_mod(ckpt=mgr_a)
        mgr_a.wait()
    mod_b = _fit_mod(ckpt=mgr_b)
    mgr_b.wait()
    a, _ = mod_a.get_params()
    b, _ = mod_b.get_params()
    for k in a:
        np.testing.assert_array_equal(a[k].asnumpy(), b[k].asnumpy())
    # both runs committed the same number of checkpoints (none lost)
    assert len(mgr_a.list_committed()) == len(mgr_b.list_committed())
    mgr_a.close()
    mgr_b.close()


# ------------------------------------------------ seam: kvstore.collective
def test_collective_transient_retry_transparent(monkeypatch):
    monkeypatch.setenv("MXNET_RETRY_COLLECTIVE",
                       "attempts=3,base=0,jitter=0")
    kv = mx.kv.create("dist_sync")
    kv.init("w", mx.nd.array(np.zeros(5, "f")))
    out = mx.nd.zeros(5)
    before = _cval("retry.retries", site="kvstore.collective")
    with faults.scope("kvstore.collective:nth=1"):
        kv.push("w", mx.nd.array(np.arange(5, dtype="f")))
        kv.pull("w", out=out)
    np.testing.assert_array_equal(out.asnumpy(),
                                  np.arange(5, dtype="f"))
    assert _cval("retry.retries", site="kvstore.collective") >= before + 1
    kv.close()


def test_collective_permanent_dead_peer_raises_deadworker(monkeypatch):
    """Liveness decides: a persistent collective failure with a dead
    peer converts to DeadWorkerError IMMEDIATELY (clean=False) instead
    of burning the retry budget."""
    monkeypatch.setenv("MXNET_RETRY_COLLECTIVE",
                       "attempts=3,base=0,jitter=0")
    kv = mx.kv.create("dist_sync")
    kv.init("w", mx.nd.array(np.zeros(5, "f")))
    monkeypatch.setattr(kv, "get_dead_nodes",
                        lambda timeout_ms=2000: [2])
    attempts_before = _cval("retry.attempts", site="kvstore.collective")
    with faults.scope("kvstore.collective:always"):
        with pytest.raises(mx.checkpoint.DeadWorkerError) as ei:
            kv.push("w", mx.nd.array(np.ones(5, "f")))
            kv.pull("w", out=mx.nd.zeros(5))
    assert ei.value.dead_ranks == [2] and not ei.value.clean
    # exactly one attempt: the liveness check short-circuits the budget
    assert _cval("retry.attempts",
                 site="kvstore.collective") == attempts_before + 1
    kv.close(abort=True)


def test_collective_permanent_alive_reraises_after_budget(monkeypatch):
    monkeypatch.setenv("MXNET_RETRY_COLLECTIVE",
                       "attempts=2,base=0,jitter=0")
    kv = mx.kv.create("dist_sync")
    kv.init("w", mx.nd.array(np.zeros(5, "f")))
    before = _cval("retry.giveups", site="kvstore.collective")
    with faults.scope("kvstore.collective:always"):
        with pytest.raises(InjectedFault):
            kv.push("w", mx.nd.array(np.ones(5, "f")))
            kv.pull("w", out=mx.nd.zeros(5))
    assert _cval("retry.giveups", site="kvstore.collective") == before + 1
    kv.close(abort=True)


# ---------------------------------------------------------- seam: io.decode
def test_io_decode_skip_with_record():
    X = np.arange(24, dtype="f").reshape(6, 4)
    y = np.arange(6, dtype="f")
    before = _cval("io.decode.skipped")
    with faults.scope("io.decode:nth=3"):
        it = mx.io.PrefetchingIter(mx.io.NDArrayIter(X, y, batch_size=1),
                                   on_decode_error="skip")
        rows = [b.data[0].asnumpy()[0, 0] for b in it]
    assert rows == [0.0, 4.0, 12.0, 16.0, 20.0]     # batch 3 skipped
    assert it.skipped_batches == 1
    assert _cval("io.decode.skipped") == before + 1
    recs = [r for r in mx.telemetry.flightrec.get_records()
            if r.get("kind") == "io.decode.skip"]
    assert recs and "InjectedFault" in recs[-1]["error"]


def test_io_decode_raise_is_default():
    X = np.arange(8, dtype="f").reshape(2, 4)
    with faults.scope("io.decode:nth=1"):
        it = mx.io.PrefetchingIter(
            mx.io.NDArrayIter(X, np.zeros(2, "f"), batch_size=1))
        with pytest.raises(InjectedFault):
            for _ in it:
                pass
    with pytest.raises(mx.base.MXNetError):
        mx.io.PrefetchingIter(
            mx.io.NDArrayIter(X, np.zeros(2, "f"), batch_size=1),
            on_decode_error="bogus")


def test_io_decode_consecutive_skip_cap():
    X = np.arange(24, dtype="f").reshape(6, 4)
    with faults.scope("io.decode:always"):
        it = mx.io.PrefetchingIter(
            mx.io.NDArrayIter(X, np.zeros(6, "f"), batch_size=1),
            on_decode_error="skip", max_decode_skip=3)
        with pytest.raises(mx.base.MXNetError,
                           match="consecutive decode failures"):
            for _ in it:
                pass


def test_io_skip_training_equivalence():
    """Skipped-batch bookkeeping is transparent: training through a
    decode failure under the skip policy equals training on the same
    data with that batch REMOVED — bit-identical params."""
    X = np.random.RandomState(3).rand(6 * BATCH, FEATS).astype("f")
    y = np.random.RandomState(4).randint(
        0, CLASSES, (6 * BATCH,)).astype("f")

    def fit(it, seed=5):
        mx.random.seed(seed)
        mod = mx.mod.Module(_mlp("sk"), context=mx.cpu())
        rs = np.random.RandomState(6)
        args = {"sk1_weight": mx.nd.array(
                    rs.randn(8, FEATS).astype("f") * 0.1),
                "sk1_bias": mx.nd.array(np.zeros(8, "f")),
                "sk2_weight": mx.nd.array(
                    rs.randn(CLASSES, 8).astype("f") * 0.1),
                "sk2_bias": mx.nd.array(np.zeros(CLASSES, "f"))}
        mod.fit(it, num_epoch=1, arg_params=args,
                optimizer_params={"learning_rate": 0.05})
        a, _ = mod.get_params()
        return {k: v.asnumpy() for k, v in a.items()}

    with faults.scope("io.decode:nth=3"):       # batch 3 fails decode
        # staged where the module binds: fit's placement hand-over then
        # finds nothing to change and does not restart the producer (a
        # restart would re-fetch, and spend nth=3 before training)
        injected = fit(mx.io.PrefetchingIter(
            mx.io.NDArrayIter(X, y, batch_size=BATCH),
            device=mx.cpu(), on_decode_error="skip"))
    keep = np.r_[0:2 * BATCH, 3 * BATCH:6 * BATCH]  # drop batch 3's rows
    reference = fit(mx.io.NDArrayIter(X[keep], y[keep],
                                      batch_size=BATCH))
    assert injected.keys() == reference.keys()
    for k in injected:
        np.testing.assert_array_equal(injected[k], reference[k],
                                      err_msg=k)


# ------------------------------------------------------ seam: serve.dispatch
def _serve_module(prefix="sv"):
    mod = mx.mod.Module(_mlp(prefix), context=mx.cpu())
    mod.bind([("data", (4, FEATS))], [("softmax_label", (4,))],
             for_training=False)
    mod.init_params(mx.initializer.Xavier())
    return mod


def test_serve_dispatch_transient_failure_keeps_serving():
    clock = FakeClock()
    server = mx.serve.serve(_serve_module(), ladder=[1, 2], start=False,
                            clock=clock, default_deadline_ms=50)
    x = np.random.RandomState(0).rand(1, FEATS).astype("f")
    errors_before = _cval("serve.errors", model="default")
    with faults.scope("serve.dispatch:nth=1"):
        h1 = server.submit({"data": x})
        clock.advance(0.06)
        server.pump()
        assert h1.done() and isinstance(h1.exception(), InjectedFault)
        h2 = server.submit({"data": x})
        clock.advance(0.06)
        server.pump()
    assert h2.done() and h2.exception() is None     # server kept serving
    assert _cval("serve.errors", model="default") == errors_before + 1
    entry = server._registry.entry("default")
    assert entry.breaker.state == "closed"          # 1 < threshold (5)


def test_serve_breaker_opens_probes_and_recovers():
    clock = FakeClock()
    server = mx.serve.serve(_serve_module("bk"), ladder=[1, 2],
                            start=False, clock=clock,
                            default_deadline_ms=50, breaker_threshold=2,
                            breaker_cooldown_ms=1000)
    x = np.random.RandomState(0).rand(1, FEATS).astype("f")
    entry = server._registry.entry("default")
    with faults.scope("serve.dispatch:always"):
        for _ in range(2):                  # two consecutive failures
            h = server.submit({"data": x})
            clock.advance(0.06)
            server.pump()
            assert isinstance(h.exception(), InjectedFault)
    assert entry.breaker.state == "open"
    # open: admission rejected fast with a retry-after hint, and the
    # scheduler wait is bounded by the probe instant
    with pytest.raises(CircuitOpenError) as ei:
        server.submit({"data": x})
    assert 0 < ei.value.retry_after_ms <= 1000
    assert _metrics.get_metric("serve.breaker.state",
                               model="default").value == 2
    # cooldown elapses: the queued request becomes the half-open probe
    clock.advance(1.0)
    h = server.submit({"data": x})
    clock.advance(0.06)
    assert server.pump() == 1
    assert h.done() and h.exception() is None
    assert entry.breaker.state == "closed"
    assert _cval("serve.breaker.transitions", to="open",
                 model="default") >= 1


def test_serve_breaker_failed_probe_reopens():
    clock = FakeClock()
    server = mx.serve.serve(_serve_module("bk2"), ladder=[1],
                            start=False, clock=clock,
                            default_deadline_ms=50, breaker_threshold=1,
                            breaker_cooldown_ms=500)
    x = np.random.RandomState(0).rand(1, FEATS).astype("f")
    entry = server._registry.entry("default")
    with faults.scope("serve.dispatch:always"):
        h = server.submit({"data": x})
        clock.advance(0.06)
        server.pump()
        assert entry.breaker.state == "open"
        clock.advance(0.5)                  # probe window
        h2 = server.submit({"data": x})
        clock.advance(0.06)
        server.pump()                       # probe fails too
        assert isinstance(h2.exception(), InjectedFault)
    assert entry.breaker.state == "open"    # re-opened
    assert entry.breaker.retry_after(clock.now()) > 0


def test_serve_shed_doomed_and_queue_full_backpressure():
    clock = FakeClock()
    server = mx.serve.serve(_serve_module("sh"), ladder=[1, 2],
                            start=False, clock=clock, max_queue=4,
                            shed_watermark=2, default_deadline_ms=50)
    x = np.random.RandomState(0).rand(1, FEATS).astype("f")
    shed_before = _cval("serve.shed", model="default")
    rej_before = _cval("serve.rejected", model="default")
    # two requests whose deadlines expire unserved
    doomed = [server.submit({"data": x}, deadline_ms=10)
              for _ in range(2)]
    clock.advance(5.0)
    # depth at watermark: this admission sheds the doomed first
    h = server.submit({"data": x}, deadline_ms=60000)
    for d in doomed:
        assert d.done() and isinstance(d.exception(), ShedError)
        assert d.exception().retry_after_ms >= 1
    assert _cval("serve.shed", model="default") == shed_before + 2
    assert _cval("serve.rejected", model="default") == rej_before
    clock.advance(60.0)
    server.pump()
    assert h.done() and h.exception() is None   # the viable one served
    # queue full (all viable): rejected with a drain-time hint,
    # counted under serve.rejected, NOT serve.shed
    hs = [server.submit({"data": x}, deadline_ms=600000)
          for _ in range(4)]
    with pytest.raises(QueueFullError) as ei:
        server.submit({"data": x}, deadline_ms=600000)
    assert ei.value.retry_after_ms >= 1
    assert _cval("serve.rejected", model="default") == rej_before + 1
    assert _cval("serve.shed", model="default") == shed_before + 2
    clock.advance(600.0)
    server.pump()
    assert all(hh.exception() is None for hh in hs)


# --------------------------------------------------------- warm restart
def test_serve_warm_restart_zero_compiles(tmp_path):
    """The ROADMAP-5 remainder: kill the server 'process' (abandon the
    object mid-load with queued work), restore from the
    CheckpointManager-managed state, and serve again — zero compiles
    past the warmup mark, bitwise-identical outputs, acked requests
    keeping their results and unacked ones failing loudly."""
    d = str(tmp_path / "serve-ck")
    mod = _serve_module("wr")
    clock = FakeClock()
    server = mx.serve.serve(mod, ladder=[1, 2], start=False,
                            clock=clock, default_deadline_ms=50)
    x = np.random.RandomState(0).rand(1, FEATS).astype("f")
    acked = server.submit({"data": x})
    clock.advance(0.06)
    server.pump()
    ref = acked.result()[0].asnumpy()           # accepted AND acked
    mgr = mx.checkpoint.CheckpointManager(d)
    seq = server.checkpoint_to(mgr)
    assert seq >= 1
    mgr.close()

    # mid-load kill: a request is queued but never dispatched
    unacked = server.submit({"data": x})
    server.stop(drain=False)                    # the 'process dies'
    assert isinstance(unacked.exception(), mx.base.MXNetError)
    assert np.array_equal(acked.result()[0].asnumpy(), ref)

    # restart: rebuild from the committed serve state
    server2 = mx.serve.restore_server(d, clock=FakeClock())
    assert server2.models == ["default"]
    import mxnet_tpu.program_cache as pc
    mark = pc.compile_count()
    h = server2.submit({"data": x})
    server2._clock.advance(0.06)
    server2.pump()
    np.testing.assert_array_equal(h.result()[0].asnumpy(), ref)
    assert pc.compile_count() == mark, \
        "steady-state serving after warm restart must not compile"
    assert server2.stats()["compiles_since_warmup"] == 0


def test_serve_warm_restart_survives_damaged_newest(tmp_path):
    """A truncated newest serve commit falls back to the previous one
    (the same damage-tolerant walk training resume uses)."""
    d = str(tmp_path / "serve-ck")
    server = mx.serve.serve(_serve_module("wd"), ladder=[1],
                            start=False, clock=FakeClock())
    mgr = mx.checkpoint.CheckpointManager(d)
    server.checkpoint_to(mgr)
    server.checkpoint_to(mgr)
    mgr.close()
    committed = mx.checkpoint.CheckpointManager(d).list_committed()
    assert len(committed) == 2
    with open(os.path.join(committed[-1][1], "state.pkl"), "r+b") as f:
        f.truncate(16)                      # damage the newest
    server2 = mx.serve.restore_server(d, clock=FakeClock())
    assert server2.models == ["default"]

    # and a serve payload never restores as training state
    mod = _fit_mod(prefix="wd2")
    assert mx.checkpoint.restore_module(mod, d) is None


def test_restore_server_empty_dir_raises(tmp_path):
    with pytest.raises(mx.base.MXNetError, match="no committed serve"):
        mx.serve.restore_server(str(tmp_path / "empty"))


# ------------------------------------------------------------- diagnose
def _diagnose():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "diagnose_faults_test", os.path.join(root, "tools",
                                             "diagnose.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def test_diagnose_faults_section_crash_path():
    diagnose = _diagnose()
    report = {
        "type": "crash_report", "time": "t", "pid": 1, "where": "x",
        "metrics": {
            "counters": {
                'faults.injected{point="ckpt.write"}': 3,
                'retry.attempts{site="ckpt.write"}': 5,
                'retry.retries{site="ckpt.write"}': 2,
                'retry.giveups{site="ckpt.write"}': 1,
                'serve.shed{model="m"}': 4,
                'serve.breaker.transitions{model="m",to="open"}': 1,
                "io.decode.skipped": 2,
                "ckpt.quarantined": 1,
            },
            "gauges": {'serve.breaker.state{model="m"}': 2.0},
            "histograms": {}},
        "ring": [{"kind": "fault.injected", "ts_us": 1,
                  "point": "ckpt.write", "call": 1},
                 {"kind": "ckpt.quarantine", "ts_us": 2, "seq": 7,
                  "error": "OSError: disk full"}],
    }
    out = diagnose.render_crash(report)
    assert "faults / degradation:" in out
    assert "injections fired: 3 (ckpt.write x3)" in out
    assert "retries [ckpt.write]: 2 retried over 5 attempts, 1 GAVE UP" \
        in out
    assert "breaker [m]: OPEN (1 trips)" in out
    assert "load shed [m]: 4 request(s)" in out
    assert "decode skips: 2" in out
    assert "1 seq(s) QUARANTINED" in out
    assert "ckpt.quarantine" in out


def test_diagnose_faults_section_jsonl_path(tmp_path):
    diagnose = _diagnose()
    lines = [
        json.dumps({"type": "counter", "name": "faults.injected",
                    "labels": {"point": "io.decode"}, "value": 2}),
        json.dumps({"type": "counter", "name": "retry.retries",
                    "labels": {"site": "kvstore.collective"},
                    "value": 1}),
        json.dumps({"type": "counter", "name": "retry.attempts",
                    "labels": {"site": "kvstore.collective"},
                    "value": 3}),
        json.dumps({"type": "gauge", "name": "serve.breaker.state",
                    "labels": {"model": "m"}, "value": 1.0}),
        json.dumps({"type": "event", "kind": "io.decode.skip",
                    "ts_us": 9, "payload": {}}),
    ]
    out = diagnose.render_jsonl(lines)
    assert "faults / degradation:" in out
    assert "injections fired: 2 (io.decode x2)" in out
    assert "retries [kvstore.collective]: 1 retried over 3 attempts" \
        in out
    assert "breaker [m]: half-open" in out


def test_diagnose_no_faults_section_when_clean():
    diagnose = _diagnose()
    report = {"type": "crash_report", "time": "t", "pid": 1,
              "where": "x", "metrics": {"counters": {}, "gauges": {},
                                        "histograms": {}}, "ring": []}
    assert "faults / degradation" not in diagnose.render_crash(report)
