"""Static memory planner (analysis/memplan.py, ME8xx) tests.

The load-bearing gate: the planner's residual estimate for resnet20
b32 agrees with the traced ``remat.residual_bytes`` figure within 5%
for ALL THREE remat policies — with the planner performing zero
compiles and zero traces (pinned via the program-cache compile counter
and a jax trace hook). Plus: the exec-group static fast path
cross-checks ``fused_memory_report``, the batch-headroom gate consumes
the plan, ME801/802 fire on seeded fixtures through the lint pass, the
SPMD/ZeRO/int8 layout awareness, and the diagnose rendering.
"""
import json

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import remat
from mxnet_tpu.analysis import AnalysisContext, memplan, run_passes
from mxnet_tpu.models import resnet

BATCH = 32
SHAPES = {"data": (BATCH, 3, 32, 32), "softmax_label": (BATCH,)}


def _resnet20():
    return resnet.get_symbol(10, 20, "3,32,32")


def _armed_module(policy):
    remat.set_active(policy)
    mod = mx.mod.Module(_resnet20(), context=mx.cpu())
    mod.bind(data_shapes=[("data", SHAPES["data"])],
             label_shapes=[("softmax_label", SHAPES["softmax_label"])])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    assert mod._fused_armed
    return mod


@pytest.fixture(autouse=True)
def _reset_remat():
    yield
    remat.set_active(None)


# ------------------------------------------------- the agreement gate
@pytest.mark.parametrize("policy", remat.POLICIES)
def test_planner_agrees_with_traced_residuals(policy):
    """Planner residual estimate vs the eval_shape-traced
    ``remat.residual_bytes`` on resnet20 b32: within 5% per policy,
    and the summed fused-step total (params + state + batch +
    residuals) within 5% too."""
    mod = _armed_module(policy)
    report = mod._exec_group.fused_memory_report()
    assert report is not None and report["policy"] == policy

    plan = memplan.plan_symbol(_resnet20(), SHAPES, policy=policy)
    measured = report["residual_bytes"]
    assert abs(plan["residual_bytes"] - measured) <= 0.05 * measured, (
        policy, plan["residual_bytes"], measured)

    keys = ("residual_bytes", "param_bytes", "state_bytes",
            "batch_bytes")
    total_plan = sum(plan[k] for k in keys)
    total_meas = sum(report[k] for k in keys)
    assert abs(total_plan - total_meas) <= 0.05 * total_meas


@pytest.mark.parametrize("policy", remat.POLICIES)
def test_planner_agrees_on_lenet(policy):
    """Second agreement point with a different op mix (max pooling,
    tanh, dense tail — the rules resnet20 alone does not exercise)."""
    from mxnet_tpu.models import lenet
    shapes = {"data": (40, 1, 28, 28), "softmax_label": (40,)}
    remat.set_active(policy)
    mod = mx.mod.Module(lenet.get_symbol(2), context=mx.cpu())
    mod.bind(data_shapes=[("data", shapes["data"])],
             label_shapes=[("softmax_label", shapes["softmax_label"])])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    report = mod._exec_group.fused_memory_report()
    plan = memplan.plan_symbol(lenet.get_symbol(2), shapes,
                               policy=policy)
    measured = report["residual_bytes"]
    assert abs(plan["residual_bytes"] - measured) <= 0.05 * measured, (
        policy, plan["residual_bytes"], measured)


def test_planner_is_trace_free(monkeypatch):
    """Zero compiles AND zero jax traces while planning: the plan is
    pure python over the symbol graph."""
    import jax
    before = mx.program_cache.compile_count()
    calls = []
    for name in ("eval_shape", "make_jaxpr"):
        monkeypatch.setattr(
            jax, name, lambda *a, _name=name, **k: calls.append(_name))
    for policy in remat.POLICIES:
        memplan.plan_symbol(_resnet20(), SHAPES, policy=policy)
    assert mx.program_cache.compile_count() == before
    assert not calls


def test_policy_ordering_and_components():
    """all < dots < none residuals; components are sane."""
    plans = {p: memplan.plan_symbol(_resnet20(), SHAPES, policy=p)
             for p in remat.POLICIES}
    assert plans["all"]["residual_bytes"] < \
        plans["dots"]["residual_bytes"] < \
        plans["none"]["residual_bytes"]
    p = plans["none"]
    assert p["param_bytes"] > 0 and p["batch_bytes"] > 0
    assert p["state_bytes"] == p["grad_bytes"]      # sgd_mom: 1x f32
    assert p["peak_bytes_per_device"] >= p["residual_bytes"]
    assert p["batch_size"] == BATCH


# ------------------------------------------- exec-group static fast path
def test_static_memory_plan_cross_checks_eval_shape():
    """The static fast path reproduces fused_memory_report's component
    bytes (exact for params/state/batch, <=5% residuals) and feeds the
    batch-headroom gate the same way (the eval_shape cross-check the
    tentpole promises)."""
    from mxnet_tpu.telemetry.memory import batch_headroom
    mod = _armed_module("dots")
    g = mod._exec_group
    report = g.fused_memory_report()
    plan = g.static_memory_plan()
    assert plan["param_bytes"] == report["param_bytes"]
    assert plan["state_bytes"] == report["state_bytes"]
    assert plan["batch_bytes"] == report["batch_bytes"]
    resid = report["residual_bytes"]
    assert abs(plan["residual_bytes"] - resid) <= 0.05 * resid

    # identical headroom decisions from the two per-sample figures
    # (1% slack over the 128 rung so the <=5% residual delta cannot
    # straddle the exact boundary)
    buckets = (32, 64, 128, 256)
    fixed = report["param_bytes"] + report["state_bytes"]
    per_sample_meas = (resid + report["batch_bytes"]) / BATCH
    budget = fixed + per_sample_meas * 128 * 1.06
    static = batch_headroom(budget, fixed, plan["per_sample_bytes"],
                            buckets)
    traced = batch_headroom(budget, fixed, per_sample_meas, buckets)
    assert static == traced == 128

    plan2 = g.static_memory_plan(buckets=buckets,
                                 capacity_bytes=int(budget))
    assert plan2["headroom_bucket"] in (64, 128)


def test_static_memory_plan_without_armed_optimizer():
    """The fast path works on a bare binding (no fused step, no
    optimizer): state falls back to the multiplier estimate."""
    mod = mx.mod.Module(_resnet20(), context=mx.cpu())
    mod.bind(data_shapes=[("data", SHAPES["data"])],
             label_shapes=[("softmax_label",
                            SHAPES["softmax_label"])])
    plan = mod._exec_group.static_memory_plan(policy="none",
                                              )
    assert plan["residual_bytes"] > 0
    assert plan["param_bytes"] > 0


# -------------------------------------------------- layout awareness
def test_int8_params_count_one_byte():
    """Quantized weights cost 1 B/element in the plan."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.quant import quantize_symbol
    from mxnet_tpu.models import mlp as mlp_mod
    sym = mlp_mod.get_symbol(10)
    shapes = {"data": (8, 784)}
    arg_shapes, _o, _a = sym.infer_shape(**shapes)
    args = {nm: mx.nd.NDArray(jnp.zeros(s, np.float32))
            for nm, s in zip(sym.list_arguments(), arg_shapes)
            if nm not in shapes}
    qsym, _ = quantize_symbol(sym, args)
    fplan = memplan.plan_symbol(sym, shapes, for_training=False)
    qplan = memplan.plan_symbol(qsym, shapes, for_training=False)
    # int8 weights + f32 scales land well under half the float bytes
    assert qplan["param_bytes"] < 0.5 * fplan["param_bytes"]
    assert qplan["grad_bytes"] == 0 and qplan["residual_bytes"] == 0


def test_zero_shards_state_and_data_divides():
    """ZeRO divides optimizer state 1/N; activations divide over the
    data axis."""
    one = memplan.plan_symbol(_resnet20(), SHAPES, policy="none")
    sharded = memplan.plan_symbol(_resnet20(), SHAPES, policy="none",
                                  n_data=8, zero=True)
    assert sharded["state_bytes_per_device"] == one["state_bytes"] // 8
    assert sharded["peak_bytes_per_device"] < one["peak_bytes_per_device"]


def test_spmd_plan_shards_params():
    """An SpmdPlan param spec shrinks per-device param bytes."""
    class FakePlan:
        def param_shard_fraction(self, name, shape):
            return 0.25 if name.endswith("_weight") else 1.0

    base = memplan.plan_symbol(_resnet20(), SHAPES, policy="all")
    spmd = memplan.plan_symbol(_resnet20(), SHAPES, policy="all",
                               spmd_plan=FakePlan())
    assert spmd["param_bytes"] < base["param_bytes"]


def test_spmd_param_shard_fraction():
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.mesh import MeshConfig, build_mesh
    from mxnet_tpu.parallel.spmd import SpmdPlan
    import jax
    mesh = build_mesh(MeshConfig(data=4, model=2),
                      devices=jax.devices()[:8])
    plan = SpmdPlan(mesh)
    plan.param_specs["w"] = P("model", None)
    assert plan.param_shard_fraction("w", (64, 32)) == 0.5
    assert plan.param_shard_fraction("other", (64, 32)) == 1.0
    # non-divisible dims stay whole (XLA would pad/replicate)
    assert plan.param_shard_fraction("w", (63, 32)) == 1.0


@pytest.mark.parametrize("policy", remat.POLICIES)
def test_armed_module_lints_clean_per_policy(policy):
    """Zero-false-positive gate along the remat axis: a fused resnet20
    module armed under each policy runs the FULL pass set clean."""
    from mxnet_tpu.analysis import lint_module
    mod = _armed_module(policy)
    report = lint_module(mod)
    assert not len(report), f"{policy}: {report.format()}"


# ------------------------------------------------ ME8xx lint findings
def test_fixture_me801_predicted_oom():
    """A capacity below the predicted peak trips ME801 (error) through
    the memory_planner pass, and nothing else."""
    report = run_passes(AnalysisContext(
        symbol=_resnet20(), known_shapes=SHAPES,
        memplan={"capacity_bytes": 10 << 20, "policy": "none"}),
        passes=["memory_planner"])
    assert report.rules == {"ME801"}
    assert report.errors


def test_fixture_me802_headroom_admits_bucket():
    """Ample capacity + a bucket ladder trips the ME802 info finding."""
    report = run_passes(AnalysisContext(
        symbol=_resnet20(), known_shapes=SHAPES,
        memplan={"capacity_bytes": 8 << 30, "policy": "dots",
                 "buckets": (32, 64, 128, 256)}),
        passes=["memory_planner"])
    assert report.rules == {"ME802"}
    assert report.infos


def test_memory_planner_pass_inert_by_default(monkeypatch):
    """No memplan options, no env budget -> the pass is a no-op (the
    warm-bind overhead gate depends on this)."""
    monkeypatch.delenv("MXNET_LINT_MEMPLAN_BUDGET", raising=False)
    report = run_passes(AnalysisContext(symbol=_resnet20(),
                                        known_shapes=SHAPES),
                        passes=["memory_planner"])
    assert not len(report)


def test_memory_planner_env_budget(monkeypatch):
    """MXNET_LINT_MEMPLAN_BUDGET arms the pass at bind-time lint."""
    monkeypatch.setenv("MXNET_LINT_MEMPLAN_BUDGET", "50M")
    report = run_passes(AnalysisContext(symbol=_resnet20(),
                                        known_shapes=SHAPES),
                        passes=["memory_planner"])
    assert "ME801" in report.rules


# --------------------------------------------------------- rendering
def test_plan_telemetry_and_diagnose_section(tmp_path):
    """record_plan lands memplan.* gauges + a flight note, and
    tools/diagnose.py renders the 'memory plan' section."""
    import os
    import sys
    from mxnet_tpu.telemetry import flightrec, metrics
    plan = memplan.plan_symbol(_resnet20(), SHAPES, policy="dots")
    flightrec.clear()
    memplan.record_plan(plan, model="resnet20")
    g = metrics.get_metric("memplan.peak_bytes_per_device",
                           model="resnet20", policy="dots")
    assert g is not None and g.value == plan["peak_bytes_per_device"]

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import diagnose
    finally:
        sys.path.pop(0)
    crash = {
        "type": "crash_report", "time": "t", "pid": 1, "where": "bind",
        "ring": [{"kind": "memplan.plan", "ts_us": 1,
                  "model": "resnet20", "policy": "dots", "batch": 32,
                  "peak_bytes": plan["peak_bytes_per_device"],
                  "residual_bytes": plan["residual_bytes"]}],
        "metrics": {"gauges": {
            'memplan.peak_bytes_per_device{model="resnet20",'
            'policy="dots"}': plan["peak_bytes_per_device"]}},
    }
    path = tmp_path / "crash.json"
    path.write_text(json.dumps(crash))
    text = diagnose.render_file(str(path))
    assert "memory plan" in text and "resnet20" in text


def test_format_plan_renders():
    plan = memplan.plan_symbol(_resnet20(), SHAPES, policy="all")
    text = memplan.format_plan(plan, model="resnet20",
                               capacity_bytes=1 << 30)
    assert "policy=all" in text and "peak/device" in text \
        and "capacity" in text
