"""Training-health plane (ISSUE 17): in-program run statistics,
divergence detection, and automated triage.

Tier-1 coverage for the three layers:

* detector units — ``telemetry.health`` is jax-free, so every rule
  (loss_spike / loss_plateau / grad explosion+collapse / update-ratio
  band / nonfinite), the MAD warm-up, cooldown and policy resolution
  run on scripted stat dicts;
* the in-program stats — an armed K=8 scan fit is bit-identical to an
  unarmed one (the stats are read-only ys), arming keys the program
  cache (``("health", armed)`` — the regression that motivated it), and
  both fit paths deliver every step's observation despite the
  readiness-gated drain lag;
* triage — the ``warn → snapshot → checkpoint → raise`` ladder lands
  flight-recorder reports and emergency ``CheckpointManager`` commits,
  the ``train.health.triage`` fault point injects, and the seeded
  lr-bomb run diverges end-to-end: detect → emergency commit →
  ``AnomalyError`` → ``/healthz`` 503 → exact resume with zero
  steady-state compiles — plus the 2-rank fleetstat attribution that
  names the rank whose detector fired first.
"""
import json
import os
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import program_cache
from mxnet_tpu.telemetry import (fleet, flightrec, health, metrics,
                                 opsd)
from mxnet_tpu.telemetry.sentinel import AnomalyError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(ROOT, "tools")

BATCH = 4
N_BATCHES = 16
CLASSES = 3
FEATS = 6

# detector knobs that keep every rule quiet on a toy run (warm-up loss
# drops fast and lr=0.05 gives window update-ratios a real optimizer
# run would alarm on)
QUIET = {"k_mad": 1e12, "plateau_tol": 0.0, "ratio_band": (0.0, 1e30),
         "collapse_frac": 0.0}

_HEALTH_ENV = ("MXNET_TRAIN_HEALTH", "MXNET_TRAIN_HEALTH_POLICY",
               "MXNET_TRAIN_HEALTH_WINDOW", "MXNET_TRAIN_HEALTH_K",
               "MXNET_CKPT_DIR", "MXNET_FAULTS")


def _tool(name):
    sys.path.insert(0, TOOLS)
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


@pytest.fixture(autouse=True)
def _clean_health(monkeypatch, tmp_path):
    """Every test starts unarmed with a fresh monitor/registry, dumps
    its crash reports under its own tmp_path, and leaves no forced
    arming, live endpoint, or resized ring behind."""
    for var in _HEALTH_ENV:
        monkeypatch.delenv(var, raising=False)
    health.configure(armed=None)
    mx.telemetry.reset()
    flightrec.configure(dump_dir=str(tmp_path))
    yield
    opsd.stop_ops()
    health.configure(armed=None)
    mx.telemetry.reset()
    mx.telemetry.disable()
    flightrec.configure(capacity=512,
                        dump_dir=os.environ["MXNET_CRASH_DIR"])


# ------------------------------------------------------------ fit helper
def _mlp():
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data=data, num_hidden=8, name="fc1")
    # every layer named: auto-name counters are process-global, and a
    # drifting symbol hash would defeat the cross-module program-cache
    # hits the zero-compile resume assertion measures
    act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(act, num_hidden=CLASSES, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _data():
    rs = np.random.RandomState(0)
    X = rs.rand(N_BATCHES * BATCH, FEATS).astype(np.float32)
    y = rs.randint(0, CLASSES, (N_BATCHES * BATCH,)).astype(np.float32)
    return X, y


def _init_args():
    rs = np.random.RandomState(1)
    return {
        "fc1_weight": mx.nd.array(rs.randn(8, FEATS).astype(np.float32)
                                  * 0.1),
        "fc1_bias": mx.nd.array(np.zeros(8, np.float32)),
        "fc2_weight": mx.nd.array(rs.randn(CLASSES, 8).astype(np.float32)
                                  * 0.1),
        "fc2_bias": mx.nd.array(np.zeros(CLASSES, np.float32)),
    }


def _fit(K=1, health_arg=None, checkpoint=None, resume=None,
         num_epoch=1, sched=None, cursors=None):
    """One deterministic training run; returns the module."""
    X, y = _data()
    mx.random.seed(7)
    it = mx.io.NDArrayIter(X, y, batch_size=BATCH)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    opt_params = {"learning_rate": 0.05}
    if sched is not None:
        opt_params["lr_scheduler"] = sched
    cb = None
    if cursors is not None:
        cb = lambda p: cursors.append((p.epoch, p.nbatch))
    mod.fit(it, num_epoch=num_epoch, steps_per_dispatch=K,
            arg_params={k: v.copy() for k, v in _init_args().items()},
            optimizer="sgd", optimizer_params=opt_params,
            batch_end_callback=cb, checkpoint=checkpoint, resume=resume,
            health=health_arg)
    return mod


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _stats(loss=1.0, gn=1.0, pn=5.0, ur=1e-3, nonfinite=0.0):
    return {"loss": [loss], "grad_norm": gn, "param_norm": pn,
            "update_ratio": ur, "nonfinite": nonfinite}


# -------------------------------------------------------- detector units
def test_loss_spike_fires_over_mad_threshold():
    mon = health.HealthMonitor(window=8, k_mad=6.0, policy="warn",
                               **{k: v for k, v in QUIET.items()
                                  if k != "k_mad"})
    # alternating jitter keeps the plateau counter quiet at tol=0
    for i in range(10):
        assert mon.observe(_stats(loss=1.0 + 0.001 * (-1) ** i)) == []
    fired = mon.observe(_stats(loss=9.0))
    assert [f["rule"] for f in fired] == ["loss_spike"]
    f = fired[0]
    assert f["policy"] == "warn"
    assert f["value"] == pytest.approx(9.0)
    assert f["threshold"] < 9.0
    assert mon.state == 2 and health.STATE_NAMES[mon.state] == "diverged"
    # the firing landed on the metric surface
    assert metrics.counter("train.health.firings",
                           rule="loss_spike").value == 1
    assert metrics.gauge("train.health.rule_fired",
                         rule="loss_spike").value == 11
    assert metrics.gauge("train.health.first_firing",
                         rule="loss_spike").value == 11
    assert metrics.gauge("train.health.state").value == 2
    assert metrics.gauge("train.health.loss", head="0").value \
        == pytest.approx(9.0)
    # ...and the flight ring, carrying the full stat window
    recs = [r for r in flightrec.get_records()
            if r["kind"] == "train.health"]
    assert len(recs) == 1 and recs[0]["rule"] == "loss_spike"
    assert len(recs[0]["window"]["loss"]) == 8


def test_mad_detectors_hold_during_warmup():
    mon = health.HealthMonitor(window=8, k_mad=6.0, policy="warn",
                               **{k: v for k, v in QUIET.items()
                                  if k != "k_mad"})
    # 7 samples < the 8-sample warm-up: even a wild value stays quiet
    for i in range(7):
        mon.observe(_stats(loss=1.0 + 0.001 * (-1) ** i, gn=1.0))
    assert mon.observe(_stats(loss=500.0, gn=500.0)) == []


def test_grad_explosion_and_collapse():
    quiet = {k: v for k, v in QUIET.items()
             if k not in ("k_mad", "collapse_frac")}
    mon = health.HealthMonitor(window=8, k_mad=6.0, collapse_frac=0.01,
                               policy="warn", **quiet)
    jig = lambda i: 1.0 + 0.001 * (-1) ** i   # keeps plateau_tol=0 quiet
    for i in range(10):
        assert mon.observe(_stats(gn=jig(i), loss=jig(i))) == []
    fired = mon.observe(_stats(gn=80.0))
    assert [f["rule"] for f in fired] == ["grad_explosion"]
    assert mon.state == 2

    mon2 = health.HealthMonitor(window=8, k_mad=6.0, collapse_frac=0.01,
                                policy="warn", **quiet)
    for i in range(10):
        mon2.observe(_stats(gn=jig(i), loss=jig(i)))
    fired = mon2.observe(_stats(gn=1e-6))
    assert [f["rule"] for f in fired] == ["grad_collapse"]
    assert mon2.state == 1      # collapse degrades, never diverges


def test_update_ratio_band():
    quiet = {k: v for k, v in QUIET.items() if k != "ratio_band"}
    mon = health.HealthMonitor(window=8, ratio_band=(1e-4, 0.5),
                               policy="warn", **quiet)
    fired = mon.observe(_stats(ur=0.8))     # band rules need no warm-up
    assert [f["rule"] for f in fired] == ["update_ratio_high"]

    mon2 = health.HealthMonitor(window=8, ratio_band=(1e-4, 0.5),
                                policy="warn", **quiet)
    fired = mon2.observe(_stats(ur=1e-6, gn=1.0))
    assert [f["rule"] for f in fired] == ["update_ratio_low"]
    # a zero-grad step legitimately moves nothing: no firing
    mon3 = health.HealthMonitor(window=8, ratio_band=(1e-4, 0.5),
                                policy="warn", **quiet)
    assert mon3.observe(_stats(ur=0.0, gn=0.0)) == []


def test_loss_plateau_fires_after_full_flat_window():
    mon = health.HealthMonitor(window=8, plateau_tol=1e-3, policy="warn",
                               **{k: v for k, v in QUIET.items()
                                  if k != "plateau_tol"})
    firings = []
    for _ in range(9):
        firings.append(mon.observe(_stats(loss=1.0)))
    # obs 1 seeds the EMA; obs 2..8 are 7 flat steps; obs 9 is the 8th
    assert all(f == [] for f in firings[:-1])
    assert [f["rule"] for f in firings[-1]] == ["loss_plateau"]
    assert mon.state == 1


def test_nonfinite_rule_from_flag_and_from_values():
    mon = health.HealthMonitor(window=8, policy="warn", **QUIET)
    fired = mon.observe(_stats(nonfinite=1.0))
    assert [f["rule"] for f in fired] == ["nonfinite"]
    mon2 = health.HealthMonitor(window=8, policy="warn", **QUIET)
    fired = mon2.observe(_stats(loss=float("nan")))
    assert [f["rule"] for f in fired] == ["nonfinite"]
    assert mon2.state == 2


def test_cooldown_bounds_refires():
    mon = health.HealthMonitor(window=8, ratio_band=(0.0, 0.5),
                               cooldown=4, policy="warn",
                               **{k: v for k, v in QUIET.items()
                                  if k != "ratio_band"})
    fired_at = [n for n in range(1, 11)
                if mon.observe(_stats(ur=0.9,
                                      loss=1.0 + 0.001 * (-1) ** n))]
    assert fired_at == [1, 6]       # held down for `cooldown` obs
    assert metrics.gauge("train.health.first_firing",
                         rule="update_ratio_high").value == 1
    assert metrics.gauge("train.health.rule_fired",
                         rule="update_ratio_high").value == 6


def test_flight_ring_health_records_stay_bounded():
    """Bugfix satellite: a pathological rule storm cannot grow the ring
    past its capacity."""
    flightrec.configure(capacity=8)
    mon = health.HealthMonitor(window=8, ratio_band=(0.0, 0.5),
                               cooldown=0, policy="warn",
                               **{k: v for k, v in QUIET.items()
                                  if k != "ratio_band"})
    for i in range(50):
        assert mon.observe(_stats(ur=0.9, loss=1.0 + 0.01 * (-1) ** i))
    recs = flightrec.get_records()
    assert len(recs) <= 8
    assert any(r["kind"] == "train.health" for r in recs)


# ------------------------------------------------------ policies / state
def test_policy_resolution_precedence(monkeypatch):
    # built-in default, then the monitor's own spec
    assert health.resolve_policy("loss_spike") == "warn"
    mon = health.HealthMonitor(policy={"loss_spike": "snapshot"})
    assert mon.policy_for("loss_spike") == "snapshot"
    assert mon.policy_for("grad_collapse") == "warn"
    # env spec: bare default + per-rule overrides (sentinel rides too)
    monkeypatch.setenv("MXNET_TRAIN_HEALTH_POLICY",
                       "checkpoint,nonfinite=raise,sentinel=raise")
    assert health.resolve_policy("loss_spike") == "checkpoint"
    assert health.resolve_policy("nonfinite") == "raise"
    assert health.resolve_policy("sentinel") == "raise"
    assert mon.policy_for("grad_collapse") == "checkpoint"
    # an explicit override beats everything
    assert health.resolve_policy("nonfinite", override="warn") == "warn"
    # malformed policy tokens are ignored, not fatal
    monkeypatch.setenv("MXNET_TRAIN_HEALTH_POLICY", "bogus")
    assert health.resolve_policy("loss_spike") == "warn"


def test_armed_override_and_reset():
    assert not health.armed()
    health.configure(armed=True)
    assert health.armed()
    health.configure(armed=False)
    assert not health.armed()
    # reset() keeps the override (fit pins arming process-wide)...
    health.configure(armed=True)
    health.reset()
    assert health.armed()
    # ...and configure(armed=None) restores the env default
    health.configure(armed=None)
    assert not health.armed()


def test_status_document_shape():
    doc = health.status()
    assert doc == {"armed": False, "state": 0, "state_name": "ok",
                   "observations": 0, "rules": [], "series": {}}
    health.observe(_stats(), epoch=0, nbatch=0)
    doc = health.status()
    assert doc["observations"] == 1 and doc["state_name"] == "ok"
    assert doc["series"]["grad_norm"] == [1.0]


# ---------------------------------------------------------------- triage
def test_escalate_snapshot_writes_flight_report(tmp_path):
    flightrec.configure(dump_dir=str(tmp_path))
    health.escalate("loss_plateau", "snapshot", "loss went flat")
    files = [f for f in os.listdir(tmp_path)
             if f.startswith("mxnet_crash_")]
    assert len(files) == 1
    text = (tmp_path / files[0]).read_text()
    assert "train.health.loss_plateau" in text
    assert "loss went flat" in text


def test_escalate_checkpoint_lands_emergency_commit(tmp_path):
    mod = _fit(K=1, health_arg=False)
    d = str(tmp_path / "ck")
    mod._ckpt_manager = mx.checkpoint.CheckpointManager(d)
    try:
        health.bind_triage(mod)     # the fit-loop binding escalate uses
        health.escalate("grad_explosion", "checkpoint",
                        "grad norm blew up", epoch=0, nbatch=5)
        mod._ckpt_manager.wait()
    finally:
        health.release_triage()
        mod._ckpt_manager.close()
    assert mx.checkpoint.latest_checkpoint(d) is not None
    assert metrics.counter("train.health.emergency_ckpts").value == 1
    recs = [r for r in flightrec.get_records()
            if r["kind"] == "train.health.ckpt"]
    assert recs and recs[-1]["rule"] == "grad_explosion"
    assert recs[-1]["nbatch"] == 5


def test_escalate_checkpoint_without_manager_warns(caplog):
    with caplog.at_level("WARNING"):
        health.escalate("loss_spike", "checkpoint", "spiked")
    assert "no checkpoint manager" in caplog.text


def test_escalate_raise_commits_then_raises(tmp_path):
    mod = _fit(K=1, health_arg=False)
    d = str(tmp_path / "ck")
    mod._ckpt_manager = mx.checkpoint.CheckpointManager(d)
    try:
        with pytest.raises(AnomalyError, match="nonfinite"):
            health.escalate("nonfinite", "raise", "NaN in the stats",
                            module=mod, epoch=0, nbatch=9)
    finally:
        mod._ckpt_manager.close()
    # the raise path blocks on the commit, so the run is resumable
    assert mx.checkpoint.latest_checkpoint(d) is not None


def test_triage_fault_injection_point():
    from mxnet_tpu import faults
    with faults.scope("train.health.triage:once,error=value"):
        with pytest.raises(ValueError):
            health.escalate("loss_spike", "warn", "spiked")
        assert faults.fired("train.health.triage") == 1
    health.escalate("loss_spike", "warn", "spiked")   # unarmed: clean


# ----------------------------------------------------- fit integration
def test_armed_scan_fit_bit_identical_and_keys_program_cache():
    """The acceptance gate: the stats are read-only outputs — an armed
    K=8 scan run ends bit-for-bit where the unarmed one does — and
    arming keys the program cache so the two never share a trace."""
    mu = _fit(K=8, health_arg=False, num_epoch=2)
    ma = _fit(K=8, health_arg=dict(QUIET, policy="warn"), num_epoch=2)
    au, _ = mu.get_params()
    aa, _ = ma.get_params()
    assert sorted(au) == sorted(aa)
    for k in sorted(au):
        np.testing.assert_array_equal(au[k].asnumpy(), aa[k].asnumpy(),
                                      err_msg=k)
    # every step produced an observation, drained by the epoch-end flush
    assert health.monitor().observations == 2 * N_BATCHES
    assert health.status()["rules"] == []
    assert health.state() == 0
    # cache-key regression: ("health", armed) is a key element
    ku = mu._exec_group._fused_cache_key
    ka = ma._exec_group._fused_cache_key
    assert ("health", False) in ku
    assert ("health", True) in ka
    assert ku != ka


def test_plain_path_observes_and_dict_knobs_reach_monitor():
    _fit(K=1, health_arg=dict(QUIET, policy="warn", k_mad=9.0))
    mon = health.monitor()
    assert mon.observations == N_BATCHES
    assert mon.k_mad == 9.0         # fit(health={...}) knobs applied
    doc = health.status()
    assert doc["armed"] and doc["state_name"] == "ok"
    assert len(doc["series"]["grad_norm"]) == min(N_BATCHES, mon.window)
    assert all(g > 0.0 for g in doc["series"]["grad_norm"])
    assert all(0.0 < r < 1.0 for r in doc["series"]["update_ratio"])


class _LRBomb(mx.lr_scheduler.LRScheduler):
    """Benign lr until one poisoned update: a seeded, reproducible
    divergence (finite but violent, so the emergency commit stays
    loadable)."""

    def __init__(self, at, boost):
        super().__init__()
        self.at = at
        self.boost = boost

    def _rate(self, num_update):
        return self.boost if num_update == self.at else self.base_lr


def test_seeded_divergence_end_to_end(tmp_path):
    """The seeded-divergence satellite: an lr bomb mid-epoch must be
    detected in-program, land an emergency commit, raise AnomalyError
    out of fit, flip /healthz to 503 — and the run must resume from the
    commit with zero steady-state compiles."""
    flightrec.configure(dump_dir=str(tmp_path / "dumps"))
    d = str(tmp_path / "ck")
    with pytest.raises(AnomalyError):
        # spike detectors live (k_mad=6); the rules a healthy toy run
        # trips anyway (ratio band, plateau, collapse) stay quiet
        _fit(K=8, num_epoch=2, checkpoint=d, sched=_LRBomb(12, 1e3),
             health_arg=dict(QUIET, policy="raise", k_mad=6.0))
    fired = {f["rule"] for f in health.status()["rules"]}
    assert fired & {"loss_spike", "grad_explosion", "nonfinite"}
    assert health.state() == 2
    assert metrics.counter("train.health.emergency_ckpts").value >= 1
    assert mx.checkpoint.latest_checkpoint(d) is not None

    # the live endpoint degrades: /healthz 503, /trainz shows the rules
    srv = mx.telemetry.serve_ops(port=0)
    code, body = _get(srv.url + "/healthz")
    doc = json.loads(body)
    assert code == 503 and doc["ok"] is False
    assert doc["train_health"]["state"] == 2
    assert doc["train_health"]["name"] == "diverged"
    assert doc["train_health"]["rules"] == sorted(fired)
    code, body = _get(srv.url + "/trainz")
    tdoc = json.loads(body)
    assert code == 200 and tdoc["state_name"] == "diverged"
    assert tdoc["rules"]
    opsd.stop_ops()

    # resume (benign schedule, detectors back to warn): completes,
    # fast-forwards past the commit cursor, re-uses the armed program
    c0 = program_cache.compile_count()
    cursors = []
    mod2 = _fit(K=8, num_epoch=2, checkpoint=d, resume=True,
                health_arg=dict(QUIET, policy="warn"), cursors=cursors)
    assert program_cache.compile_count() == c0
    assert cursors and cursors[0] != (0, 0)
    args, _ = mod2.get_params()
    for k, v in args.items():
        assert np.isfinite(v.asnumpy()).all(), k


# -------------------------------------------------- fleet attribution
def _rank_dump(path, rank, state, rules):
    """One synthesized per-rank jsonl dump carrying health gauges."""
    lines = [{"type": "meta", "schema": fleet.SCHEMA_VERSION,
              "rank": rank, "host": f"h{rank}", "pid": 100 + rank,
              "num_workers": 2, "generation": 0, "time_unix": 1000.0},
             {"type": "step", "wall_us": 10000,
              "phases_us": {"dispatch": 10000}},
             {"type": "gauge", "name": "train.health.state",
              "labels": {}, "value": state}]
    for rule, n in rules.items():
        lines.append({"type": "gauge",
                      "name": "train.health.first_firing",
                      "labels": {"rule": rule}, "value": n})
    path.write_text("\n".join(json.dumps(l) for l in lines) + "\n")
    return str(path)


def test_fleetstat_names_first_diverged_rank(tmp_path):
    """2-rank attribution: the fleet minimum of first-firing indices
    names the sick rank even after the blast radius trips its peer."""
    fleetstat = _tool("fleetstat")
    f0 = _rank_dump(tmp_path / "r0.jsonl", 0, 1,
                    {"grad_explosion": 120})
    f1 = _rank_dump(tmp_path / "r1.jsonl", 1, 2,
                    {"loss_spike": 40, "nonfinite": 55})
    doc = fleetstat.build([fleetstat.load_file(p) for p in (f0, f1)])
    th = doc["train_health"]
    assert th["by_rank"]["0"] == {"state": 1, "name": "degraded",
                                  "rules": {"grad_explosion": 120}}
    assert th["by_rank"]["1"]["name"] == "diverged"
    assert th["first"] == {"rank": "1", "rule": "loss_spike",
                           "observation": 40}
    text = fleetstat.render(doc)
    assert "FIRST DIVERGED: rank 1 — loss_spike at observation 40" \
        in text
    # byte-determinism under permuted input order
    doc2 = fleetstat.build([fleetstat.load_file(p) for p in (f1, f0)])
    assert fleetstat.render(doc2) == text
